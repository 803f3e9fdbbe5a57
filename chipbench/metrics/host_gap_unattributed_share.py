"""Share of the device-idle time between consecutive fused decode calls
in the traced slice that no span finer than the harness's
``gateway.step`` names, or no span at all (%)."""

from chipbench.host_gap import unattributed_share


def read(rec):
    return unattributed_share(rec)
