"""95th percentile of due time to first token over every window request,
in a cell above the knee where it is recorded and not judged (s)."""

from chipbench.readers import quantile, ttfts


def read(rec):
    return quantile(ttfts(rec), 0.95)
