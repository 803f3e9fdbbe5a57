"""Mean device-idle time between consecutive fused decode calls in the
traced slice (ms): the host's bookkeeping, scheduling and dispatch that
the device waits through."""

from chipbench.readers import fused_events
from chipbench.tracing import idle_between


def read(rec):
    ev = fused_events(rec)
    if len(ev) < 2:
        return None
    busy = rec.trace["busy0"]
    gaps = [idle_between(busy, a[1], b[0]) for a, b in zip(ev, ev[1:])]
    return sum(gaps) / len(gaps) * 1e-6
