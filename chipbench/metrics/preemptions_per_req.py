"""Engine preemptions during the window per request due in it."""


def read(rec):
    n = len(rec.window_reqs())
    if not n or not rec.counters1:
        return None
    return (rec.counters1["preemptions"] - rec.counters0["preemptions"]) / n
