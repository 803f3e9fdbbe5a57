"""Device time of the prefill chunk programs and their KV scatters in
the traced slice per thousand true prompt tokens prefilled in it (ms)."""

from chipbench.readers import prefill_ns, traced_prefill_tokens


def read(rec):
    ns, toks = prefill_ns(rec), traced_prefill_tokens(rec)
    if ns is None or toks <= 0:
        return None
    return ns * 1e-6 / (toks / 1e3)
