"""Model FLOPs of the window's work (prompt positions through every
layer, each sampled token with its logits, each at its own position)
over the window's seconds times the chip's bf16 peak (%)."""

from chipbench.readers import window_flops


def read(rec):
    f = window_flops(rec)
    if f <= 0:
        return None
    return 100.0 * f / ((rec.w1 - rec.w0) * rec.peaks["bf16_flops_per_s"])
