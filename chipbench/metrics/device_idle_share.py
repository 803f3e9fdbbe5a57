"""Share of the traced slice in which no operation ran on the device
(%), averaged over the chips used."""


def read(rec):
    if not rec.trace or rec.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - rec.trace["busy_s"] / rec.trace["window_s"])
