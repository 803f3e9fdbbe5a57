"""Mean device-idle time per gap between consecutive fused decode calls in
the traced slice that falls under the fused call's preparation: lane
plan and KV grants, host arrays, host-to-device copies and dispatch
(``engine.decode_prepare``) (ms)."""

from chipbench.host_gap import part_ms


def read(rec):
    return part_ms(rec, "prepare")
