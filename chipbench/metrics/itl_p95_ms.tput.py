"""95th percentile of every gap between successive output tokens of one
request, pooled, later token in the window, in a cell above the knee
where it is recorded and not judged (ms)."""

from chipbench.readers import itl_gaps, quantile


def read(rec):
    q = quantile(itl_gaps(rec), 0.95)
    return None if q is None else q * 1e3
