"""Host milliseconds per engine step inside the scheduler's per-step
calls (order, refresh, admit_batch, on_progress_many, eviction_order),
over the window's steps."""


def read(rec):
    steps = rec.window_steps()
    if not steps:
        return None
    return 1e3 * sum(s.sched_s for s in steps) / len(steps)
