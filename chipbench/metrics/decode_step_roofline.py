"""Share of its roofline the fused decode step reaches in the traced
slice (%): the mean over the slice's decode calls of the least time the
chip could take for each (the larger of its required FLOPs over the bf16
peak and its required bytes over HBM bandwidth: weights once, each
lane's true-context KV), over the mean device time of a fused call."""

from chipbench import work
from chipbench.readers import fused_events


def read(rec):
    ev = fused_events(rec)
    calls = [st.positions for st in rec.trace_steps if st.positions]
    if not ev or not calls:
        return None
    pk = rec.peaks
    t_min = 0.0
    for positions in calls:
        f, b = work.decode_call(rec.cell.config, positions)
        t_min += max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
    t_dev = sum(e - s for s, e in ev) * 1e-9
    return 100.0 * (t_min / len(calls)) / (t_dev / len(ev))
