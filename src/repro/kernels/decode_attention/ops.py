"""jit'd wrappers for the flash-decode kernels (ref fallback off-TPU).

The paged kernel without partials has one dispatch point,
``models.attention.decode_attention_paged``."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..bucketing import pow2_bucket
from .kernel import decode_attention_kernel, decode_attention_paged_lse_kernel
from .ref import (decode_attention_paged_lse_reference,
                  decode_attention_reference)

__all__ = ["decode_attention_op", "decode_attention_paged_lse_op"]


@functools.partial(jax.jit, static_argnames=("window", "block_s",
                                             "force_pallas"))
def decode_attention_op(q, k_cache, v_cache, cache_len, *, window: int = 0,
                        block_s: int = 512, force_pallas: bool = False):
    """q: (B, H, dh); caches (B, S_max, KV, dh); cache_len (B,)."""
    native = jax.default_backend() == "tpu"
    if not native and not force_pallas:
        return decode_attention_reference(q, k_cache, v_cache, cache_len,
                                          window=window)
    s_max = k_cache.shape[1]
    blk = min(block_s, s_max)
    pad = (-s_max) % blk
    if pad and window > 0:
        raise ValueError("ring-buffer (window) caches must be a multiple of "
                         "block_s — padding would corrupt wrap masking")
    if pad:
        cfg = [(0, 0), (0, pad), (0, 0), (0, 0)]
        k_cache = jnp.pad(k_cache, cfg)
        v_cache = jnp.pad(v_cache, cfg)
    return decode_attention_kernel(
        q, k_cache, v_cache, cache_len.astype(jnp.int32),
        window=window, block_s=blk, interpret=not native)


@functools.partial(jax.jit, static_argnames=("window", "force_pallas"))
def decode_attention_paged_lse_op(q, k_pool, v_pool, block_tables,
                                  cache_len, *, window: int = 0,
                                  force_pallas: bool = False):
    """Partial paged flash-decode returning ``(out, lse)``.

    q (B, H, dh); pools (n_pages, page, KV, dh); block_tables (B, P)
    int32; cache_len (B,).  The logical-page axis is padded to a pow2
    bucket with entries on physical page 0 (the serving engine's scratch
    page), past every row's ``cache_len``.  ``out`` is normalized over
    only the pages reachable through THIS call's block tables and
    ``lse`` (B, H) f32 is their log-sum-exp.
    This is the per-stripe building block for LSE-combined sharded
    attention: when kv heads don't divide the mesh, each shard runs this
    op over its stripe of the logical page axis and the partials merge
    exactly via ``models.attention.combine_lse_partials`` — the same
    split-KV reduction the kernel already does across its grid, lifted
    one level up so GSPMD can place the final combine as a collective."""
    native = jax.default_backend() == "tpu"
    if not native and not force_pallas:
        return decode_attention_paged_lse_reference(
            q, k_pool, v_pool, block_tables, cache_len, window=window)
    p_max = block_tables.shape[1]
    pb = pow2_bucket(p_max)
    if pb != p_max:
        block_tables = jnp.pad(block_tables, ((0, 0), (0, pb - p_max)))
    return decode_attention_paged_lse_kernel(
        q, k_pool, v_pool, block_tables.astype(jnp.int32),
        cache_len.astype(jnp.int32), window=window, interpret=not native)
