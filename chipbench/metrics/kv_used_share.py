"""Window mean, weighted by step time, of KV blocks held over the pool's
blocks (%)."""


def read(rec):
    steps = rec.window_steps()
    dt = sum(s.t1 - s.t0 for s in steps)
    if not steps or dt <= 0:
        return None
    held = sum((s.t1 - s.t0) * s.used_blocks for s in steps) / dt
    return 100.0 * held / rec.n_blocks
