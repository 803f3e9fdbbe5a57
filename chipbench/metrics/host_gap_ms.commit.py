"""Mean device-idle time per gap between consecutive fused decode calls in
the traced slice that falls under the per-lane bookkeeping after the
fused call's tokens come back (``engine.decode_commit``) (ms)."""

from chipbench.host_gap import part_ms


def read(rec):
    return part_ms(rec, "commit")
