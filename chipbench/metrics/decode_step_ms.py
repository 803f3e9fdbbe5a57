"""Mean device time of one fused decode call in the traced slice (ms)."""

from chipbench.readers import fused_events


def read(rec):
    ev = fused_events(rec)
    if not ev:
        return None
    return sum(e - s for s, e in ev) / len(ev) * 1e-6
