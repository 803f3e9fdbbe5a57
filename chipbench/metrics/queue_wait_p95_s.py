"""95th percentile, over window requests, of due time to the first step
after which the request was seen RUNNING: the wait in the gateway's
queue and the scheduler's admission (s)."""

from chipbench.readers import quantile, queue_waits


def read(rec):
    return quantile(queue_waits(rec), 0.95)
