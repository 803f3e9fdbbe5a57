"""Process start to window start: loading, weights, compiles, warm-up
and warm traffic (s)."""


def read(rec):
    return rec.setup_s
