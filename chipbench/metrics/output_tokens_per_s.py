"""Every output token stamped in the window over the window's seconds."""

from chipbench.readers import window_tokens


def read(rec):
    return window_tokens(rec) / (rec.w1 - rec.w0)
