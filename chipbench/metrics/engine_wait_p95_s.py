"""95th percentile, over window requests the engine took, of its
submit to the first time it held a slot (the program's
``ServeRequest.submitted`` and ``admitted``): the wait in the
scheduler's waiting set; one never admitted counts the time to the
run's end (s)."""

from chipbench.readers import quantile


def read(rec):
    reqs = [s.sr for s in rec.window_reqs()]
    if not reqs or not hasattr(reqs[0], "admitted"):
        return None
    return quantile([(r.admitted if r.admitted == r.admitted else rec.end)
                     - r.submitted for r in reqs
                     if r.submitted == r.submitted], 0.95)
