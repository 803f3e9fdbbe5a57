"""Mean device-idle time per gap between consecutive fused decode calls in
the traced slice that falls under scheduling: selection and preemption,
admission, pressure relief (``engine.select``, ``engine.admit``,
``engine.relieve``) and the scheduler's calls (``scheduler.*``) (ms)."""

from chipbench.host_gap import part_ms


def read(rec):
    return part_ms(rec, "schedule")
