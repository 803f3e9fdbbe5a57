"""Pallas TPU kernel: batched Gittins indices over bucketized cost
distributions.

At cluster scale (paper Fig. 12: 64 nodes x 8 RPS with a 1000-deep queue
and ~queue/10 refreshes per arrival) the scheduler evaluates thousands of
Gittins indices per second; this kernel computes a whole batch in one
VMEM-resident pass: two prefix sums + a running min along the bucket axis.

Grid: (n_blocks,) over the request batch; each block holds (block_n, k)
support/prob tiles in VMEM, with k padded to whole 128-lane vregs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gittins_kernel"]

_LANES = 128


def _prefix_sum(x):
    """Inclusive prefix sum along lanes: log2(k) roll-and-add steps
    (Hillis-Steele).  Mosaic has no lowering for ``jnp.cumsum``."""
    k = x.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    shift = 1
    while shift < k:
        x = x + jnp.where(lane >= shift, pltpu.roll(x, shift, 1), 0.0)
        shift *= 2
    return x


def _kernel(support_ref, probs_ref, out_ref):
    c = support_ref[...].astype(jnp.float32)       # (bn, k)
    p = probs_ref[...].astype(jnp.float32)
    valid = p > 0.0
    # zero dead columns BEFORE multiplying: padded support may be huge
    # (or even +inf), and inf * 0 would poison the prefix sums with NaN
    cz = jnp.where(valid, c, 0.0)
    mass = _prefix_sum(p)                          # P(X <= c_j)
    spent = _prefix_sum(cz * p)                    # E[X ; X <= c_j]
    num = spent + cz * (1.0 - mass)                # E[min(X, c_j)]
    ratio = jnp.where(valid & (mass > 1e-12),
                      num / jnp.maximum(mass, 1e-12), jnp.inf)
    out_ref[...] = ratio.min(axis=1, keepdims=True)


def gittins_kernel(support, probs, *, block_n: int = 256,
                   interpret: bool = False):
    """support/probs: (n, k) float32 (rows non-decreasing in support;
    padded entries must carry prob 0 — any support value is tolerated
    there, including +inf, but prefer a large finite pad as ops.py
    does).  Returns (n,)."""
    n, k = support.shape
    bn = min(block_n, n)
    pad = (-n) % bn
    kpad = (-k) % _LANES
    if pad or kpad:
        # pad columns carry prob 0 (inert); pad rows are unit-mass
        support = jnp.pad(support, ((0, pad), (0, kpad)),
                          constant_values=1.0)
        probs = jnp.pad(probs, ((0, pad), (0, kpad)))
        probs = probs.at[n:, 0].set(1.0)
    blocks = (n + pad) // bn
    kp = k + kpad

    out = pl.pallas_call(
        _kernel,
        grid=(blocks,),
        in_specs=[
            pl.BlockSpec((bn, kp), lambda i: (i, 0)),
            pl.BlockSpec((bn, kp), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n + pad, 1), jnp.float32),
        interpret=interpret,
    )(support, probs)
    return out[:n, 0]
