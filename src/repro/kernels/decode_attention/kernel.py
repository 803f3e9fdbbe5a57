"""Pallas TPU flash-decode kernel: one-token attention over a KV cache.

Grid: (batch, kv_blocks) — the KV sequence is partitioned and partial
softmax statistics (m, l, acc) are combined across blocks in VMEM scratch
via the log-sum-exp trick.  This is the TPU-idiomatic analogue of
PagedAttention v2's split-KV reduction (DESIGN.md hardware adaptation):
no warp shuffles, just a sequential grid axis with running renormalization.

The per-request valid length arrives as a scalar-prefetch operand in SMEM,
so masking is dynamic per batch row (continuous batching: every request
has its own cache fill level).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["decode_attention_kernel", "decode_attention_paged_kernel",
           "decode_attention_paged_lse_kernel"]

_NEG = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, block_s: int, n_blocks: int, kv_heads: int,
            rep: int, window: int, s_max: int):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                                    # (H, dh)
    k = k_ref[0]                                    # (block_s, KV, dh)
    v = v_ref[0]
    h, dh = q.shape
    qg = q.reshape(kv_heads, rep, dh)
    # scores: (KV, rep, block_s)
    s = jax.lax.dot_general(
        qg, k, (((2,), (2,)), ((0,), (1,))),
        preferred_element_type=jnp.float32) * scale

    valid_len = len_ref[0]
    pos = si * block_s + jax.lax.broadcasted_iota(
        jnp.int32, (kv_heads, rep, block_s), 2)
    mask = pos < valid_len
    if window > 0:
        # ring buffer: once wrapped, every slot is within the window
        mask = mask | (valid_len >= s_max)
    s = jnp.where(mask, s, _NEG)

    sf = s.reshape(h, block_s)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, sf.max(axis=1))
    p = jnp.exp(sf - m_new[:, None])                # (H, block_s)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
    pv = jax.lax.dot_general(
        p.reshape(kv_heads, rep, block_s).astype(v.dtype), v,
        (((2,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32)         # (KV, rep, dh)
    acc_scr[...] = acc_scr[...] * corr[:, None] + pv.reshape(h, dh)
    m_scr[...] = m_new

    @pl.when(si == n_blocks - 1)
    def _flush():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def decode_attention_kernel(q, k_cache, v_cache, cache_len, *,
                            window: int = 0, block_s: int = 512,
                            interpret: bool = False):
    """q: (B, H, dh); k_cache/v_cache: (B, S_max, KV, dh);
    cache_len: (B,) int32 valid lengths.  Returns (B, H, dh)."""
    b, h, dh = q.shape
    _, s_max, kv, _ = k_cache.shape
    rep = h // kv
    assert s_max % block_s == 0, (s_max, block_s)
    n_blocks = s_max // block_s
    scale = dh ** -0.5

    kernel = functools.partial(
        _kernel, scale=scale, block_s=block_s, n_blocks=n_blocks,
        kv_heads=kv, rep=rep, window=window, s_max=s_max)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(b, n_blocks),
        in_specs=[
            pl.BlockSpec((1,), lambda bi, si: (bi,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, h, dh), lambda bi, si: (bi, 0, 0)),
            pl.BlockSpec((1, block_s, kv, dh), lambda bi, si: (bi, si, 0, 0)),
            pl.BlockSpec((1, block_s, kv, dh), lambda bi, si: (bi, si, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, dh), lambda bi, si: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h,), jnp.float32),
            pltpu.VMEM((h,), jnp.float32),
            pltpu.VMEM((h, dh), jnp.float32),
        ],
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, dh), q.dtype),
        interpret=interpret,
    )(cache_len, q, k_cache, v_cache)


def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale: float, page: int,
                  n_blocks: int, kv_heads: int, rep: int, window: int):
    """Paged variant: the grid walks *logical* pages of each sequence; the
    physical page is resolved by the BlockSpec index maps through the
    scalar-prefetched block table, so the kernel body only ever sees one
    (page, KV, dh) tile — PagedAttention's indirection without gather."""
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                                    # (H, dh)
    k = k_ref[0]                                    # (page, KV, dh)
    v = v_ref[0]
    h, dh = q.shape
    qg = q.reshape(kv_heads, rep, dh)
    s = jax.lax.dot_general(
        qg, k, (((2,), (2,)), ((0,), (1,))),
        preferred_element_type=jnp.float32) * scale  # (KV, rep, page)

    # scalar-prefetch operands are whole-array SMEM refs; pick this row
    valid_len = len_ref[pl.program_id(0)]
    pos = si * page + jax.lax.broadcasted_iota(
        jnp.int32, (kv_heads, rep, page), 2)
    mask = pos < valid_len
    if window > 0:
        # logical sliding window: no ring wrap in a paged pool
        mask = mask & (pos >= valid_len - window)
    s = jnp.where(mask, s, _NEG)

    sf = s.reshape(h, page)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, sf.max(axis=1))
    p = jnp.exp(sf - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
    pv = jax.lax.dot_general(
        p.reshape(kv_heads, rep, page).astype(v.dtype), v,
        (((2,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * corr[:, None] + pv.reshape(h, dh)
    m_scr[...] = m_new

    @pl.when(si == n_blocks - 1)
    def _flush():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def _paged_kernel_lse(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                      m_scr, l_scr, acc_scr, *, scale: float, page: int,
                      n_blocks: int, kv_heads: int, rep: int, window: int):
    """``_paged_kernel`` flushing flash-style partials instead of a
    finished output: o = acc / l (normalized over THIS kernel's pages)
    plus lse = m + log(l), so a mesh that stripes the logical page axis
    across shards can run this kernel per stripe and merge the partials
    with ``models.attention.combine_lse_partials`` — PagedAttention
    v2's cross-partition reduction, hoisted out of the kernel and into
    the (GSPMD-collective) merge."""
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                                    # (H, dh)
    k = k_ref[0]                                    # (page, KV, dh)
    v = v_ref[0]
    h, dh = q.shape
    qg = q.reshape(kv_heads, rep, dh)
    s = jax.lax.dot_general(
        qg, k, (((2,), (2,)), ((0,), (1,))),
        preferred_element_type=jnp.float32) * scale  # (KV, rep, page)

    valid_len = len_ref[pl.program_id(0)]
    pos = si * page + jax.lax.broadcasted_iota(
        jnp.int32, (kv_heads, rep, page), 2)
    mask = pos < valid_len
    if window > 0:
        mask = mask & (pos >= valid_len - window)
    s = jnp.where(mask, s, _NEG)

    sf = s.reshape(h, page)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, sf.max(axis=1))
    p = jnp.exp(sf - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
    pv = jax.lax.dot_general(
        p.reshape(kv_heads, rep, page).astype(v.dtype), v,
        (((2,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * corr[:, None] + pv.reshape(h, dh)
    m_scr[...] = m_new

    @pl.when(si == n_blocks - 1)
    def _flush():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)
        # lse = m + log(l): exactly -inf-ish (_NEG + log(1e-30)) for a
        # fully-masked stripe, so its merge weight underflows to 0
        lse_ref[0] = (m_scr[...] + jnp.log(l))[:, None].astype(lse_ref.dtype)


def decode_attention_paged_kernel(q, k_pool, v_pool, block_tables,
                                  cache_len, *, window: int = 0,
                                  interpret: bool = False):
    """q: (B, H, dh); k_pool/v_pool: (n_pages, page, KV, dh) shared pool;
    block_tables: (B, P) int32 physical-page ids; cache_len: (B,) int32.
    Returns (B, H, dh)."""
    b, h, dh = q.shape
    n_pages, page, kv, _ = k_pool.shape
    p_max = block_tables.shape[1]
    rep = h // kv
    scale = dh ** -0.5

    kernel = functools.partial(
        _paged_kernel, scale=scale, page=page, n_blocks=p_max,
        kv_heads=kv, rep=rep, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,      # block_tables, cache_len
        grid=(b, p_max),
        in_specs=[
            pl.BlockSpec((1, h, dh), lambda bi, si, bt, cl: (bi, 0, 0)),
            pl.BlockSpec((1, page, kv, dh),
                         lambda bi, si, bt, cl: (bt[bi, si], 0, 0, 0)),
            pl.BlockSpec((1, page, kv, dh),
                         lambda bi, si, bt, cl: (bt[bi, si], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, dh),
                               lambda bi, si, bt, cl: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h,), jnp.float32),
            pltpu.VMEM((h,), jnp.float32),
            pltpu.VMEM((h, dh), jnp.float32),
        ],
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, dh), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), cache_len.astype(jnp.int32),
      q, k_pool, v_pool)


def decode_attention_paged_lse_kernel(q, k_pool, v_pool, block_tables,
                                      cache_len, *, window: int = 0,
                                      interpret: bool = False):
    """Partial-softmax paged decode: same operands as
    ``decode_attention_paged_kernel`` but returns ``(out, lse)`` with
    out (B, H, dh) normalized over only the pages this call saw and
    lse (B, H) f32 log-sum-exp — the flash-style partial that
    ``models.attention.combine_lse_partials`` merges across KV stripes
    when the page axis is sharded over the mesh."""
    b, h, dh = q.shape
    n_pages, page, kv, _ = k_pool.shape
    p_max = block_tables.shape[1]
    rep = h // kv
    scale = dh ** -0.5

    kernel = functools.partial(
        _paged_kernel_lse, scale=scale, page=page, n_blocks=p_max,
        kv_heads=kv, rep=rep, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,      # block_tables, cache_len
        grid=(b, p_max),
        in_specs=[
            pl.BlockSpec((1, h, dh), lambda bi, si, bt, cl: (bi, 0, 0)),
            pl.BlockSpec((1, page, kv, dh),
                         lambda bi, si, bt, cl: (bt[bi, si], 0, 0, 0)),
            pl.BlockSpec((1, page, kv, dh),
                         lambda bi, si, bt, cl: (bt[bi, si], 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, h, dh), lambda bi, si, bt, cl: (bi, 0, 0)),
            # (B, H, 1): a (1, H) block of a (B, H) array breaks the
            # TPU's (8, 128) tiling rule on its first dim
            pl.BlockSpec((1, h, 1), lambda bi, si, bt, cl: (bi, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((h,), jnp.float32),
            pltpu.VMEM((h,), jnp.float32),
            pltpu.VMEM((h, dh), jnp.float32),
        ],
    )

    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, dh), q.dtype),
            jax.ShapeDtypeStruct((b, h, 1), jnp.float32),
        ],
        interpret=interpret,
    )(block_tables.astype(jnp.int32), cache_len.astype(jnp.int32),
      q, k_pool, v_pool)
    return out, lse[..., 0]
