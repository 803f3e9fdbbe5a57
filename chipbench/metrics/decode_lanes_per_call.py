"""Ready lanes per fused decode call over the window: the program's
``decode_lanes`` counter over its ``fused_steps``, both as deltas."""


def read(rec):
    c0, c1 = rec.counters0, rec.counters1
    if "decode_lanes" not in c1 or "decode_lanes" not in c0:
        return None
    calls = c1["fused_steps"] - c0["fused_steps"]
    return (c1["decode_lanes"] - c0["decode_lanes"]) / calls if calls \
        else None
