"""95th percentile, over window requests, of due time to the engine's
submit (the program's ``ServeRequest.submitted``): the wait in the
gateway's queue; one never submitted counts the time to the run's end
(s)."""

from chipbench.readers import quantile


def read(rec):
    reqs = rec.window_reqs()
    if not reqs or not hasattr(reqs[0].sr, "submitted"):
        return None
    return quantile([(s.sr.submitted if s.sr.submitted == s.sr.submitted
                      else rec.end) - s.due for s in reqs], 0.95)
