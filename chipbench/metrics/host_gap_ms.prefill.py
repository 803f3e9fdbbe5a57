"""Mean device-idle time per gap between consecutive fused decode calls in
the traced slice that falls under prefill dispatch (``engine.prefill``)
(ms)."""

from chipbench.host_gap import part_ms


def read(rec):
    return part_ms(rec, "prefill")
