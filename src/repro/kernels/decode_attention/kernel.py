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


# K bytes each paged-kernel block aims for: 16 pages at qwen2-1.5b's
# 2 KV heads (8 KiB pages), 2 at olmoe-1b-7b's 16 (64 KiB pages)
_BLOCK_BYTES = 128 << 10


def _pages_per_block(page: int, kv_heads: int, head_dim: int,
                     itemsize: int, table_pages: int) -> int:
    """Pages one paged-kernel block copies: ~``_BLOCK_BYTES`` of K from
    the page's own size, never more than the block table holds."""
    page_bytes = page * kv_heads * head_dim * itemsize
    return max(1, min(table_pages, _BLOCK_BYTES // page_bytes))


def _paged_kernel(bt_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                  k_buf, v_buf, sems, slot_ref, *, scale: float, page: int,
                  ppb: int, p_max: int, n_lanes: int, kv_heads: int,
                  rep: int, window: int):
    """Paged flash-decode for one lane per grid step.

    The pools stay in HBM where they lie (n_pages, page, KV, dh).  The
    lane's live pages, and no others, are copied through its
    scalar-prefetched block table into a double-buffered VMEM block of
    ``ppb`` pages, one async copy per page; the copy of the next block
    (this lane's, or the next lane's first) runs while this one is
    computed.  Softmax statistics (m, l, acc) merge across blocks in f32
    with the late divide, as in the jnp reference.  Every KV head is
    computed on its own, so a head's result does not depend on how many
    heads the pool holds.

    Written in ``lax`` primitives, unlike the kernels above: on a TPU
    v5e the same body in ``jnp`` and Python operators, with 2-D products
    per head, ran this kernel ~2.8x slower (0.715 s against 0.255 s of a
    3 s traced slice of the chat-overload cell), and each fused program
    traces the kernel anew, where every ``jnp`` call is one more trace."""
    lax = jax.lax
    f32 = jnp.float32
    add, sub, mul, div = lax.add, lax.sub, lax.mul, lax.div
    b = pl.program_id(0)
    h, dh = q_ref.shape[1:]
    shape = (kv_heads, rep, ppb * page)

    def live(lane):
        """(length, first live page, end page) of ``lane``: the pages
        that hold its context, from the window's first page when the
        window is set."""
        length = len_ref[lane]
        end = lax.min(div(add(length, page - 1), page), p_max)
        first = 0 if window <= 0 else div(lax.max(sub(length, window), 0),
                                          page)
        return length, first, end

    def block_copies(lane, i, slot, wait: bool):
        """Start (or wait for) the copies of block ``i`` of ``lane``
        into buffer ``slot``: one per live page, K and V."""
        _, first, end = live(lane)
        start = add(first, mul(i, ppb))
        row = add(mul(lane, p_max), start)

        def one(j, carry):
            phys = bt_ref[add(row, j)]
            dst = pl.ds(mul(j, page), page)
            for kv, (src, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                copy = pltpu.make_async_copy(src.at[phys], buf.at[slot, dst],
                                             sems.at[kv, slot])
                if wait:
                    copy.wait()
                else:
                    copy.start()
            return carry

        lax.fori_loop(0, lax.clamp(0, sub(end, start), ppb), one, 0)

    @pl.when(b == 0)
    def _prologue():
        # a block's pages past the lane's end are never copied; zeros
        # keep what they hold finite before any copy has landed
        k_buf[...] = lax.full(k_buf.shape, 0, k_buf.dtype)
        v_buf[...] = lax.full(v_buf.shape, 0, v_buf.dtype)
        slot_ref[0] = 0
        block_copies(0, 0, 0, wait=False)

    length, first, end = live(b)
    # an empty lane still runs one (empty) block, so that the lane
    # before it knows what to prefetch
    n_blk = lax.max(div(add(sub(end, first), ppb - 1), ppb), 1)
    qg = q_ref[0].reshape(kv_heads, rep, dh)
    lo = sub(length, window) if window > 0 else None

    def keep(x):
        """Broadcast (KV, rep, 1) statistics over the block."""
        return lax.broadcast_in_dim(x, shape, (0, 1, 2))

    def block(i, carry):
        m_prev, l_prev, acc = carry
        slot = slot_ref[0]
        nxt = sub(1, slot)
        # the next block to copy: this lane's, else the next lane's first
        own = lax.lt(add(i, 1), n_blk)

        @pl.when(own | lax.lt(add(b, 1), n_lanes))
        def _prefetch():
            block_copies(lax.select(own, b, add(b, 1)),
                         lax.select(own, add(i, 1), 0), nxt, wait=False)

        block_copies(b, i, slot, wait=True)
        # one (rep, T) score matrix per KV head, each from its own
        # (T, dh) rows of the block
        s = mul(lax.concatenate([
            lax.dot_general(lax.index_in_dim(qg, g),
                            lax.expand_dims(k_buf[slot, :, g, :], (0,)),
                            (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=f32)
            for g in range(kv_heads)], 0), f32(scale))   # (KV, rep, T)
        pos = add(mul(add(first, mul(i, ppb)), page),
                  lax.broadcasted_iota(jnp.int32, shape, 2))
        valid = lax.lt(pos, length)
        if lo is not None:
            valid = lax.bitwise_and(valid, lax.ge(pos, lo))
        s = lax.select(valid, s, lax.full(shape, _NEG, f32))
        m_new = lax.max(m_prev,
                        lax.expand_dims(lax.reduce_max(s, (2,)), (2,)))
        p = lax.select(valid, lax.exp(sub(s, keep(m_new))),
                       lax.full(shape, 0, f32))
        corr = lax.exp(sub(m_prev, m_new))
        pv = lax.concatenate([
            lax.dot_general(lax.index_in_dim(p, g),
                            lax.expand_dims(lax.convert_element_type(
                                v_buf[slot, :, g, :], f32), (0,)),
                            (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=f32)
            for g in range(kv_heads)], 0)                 # (KV, rep, dh)
        slot_ref[0] = nxt
        l_new = add(mul(l_prev, corr),
                    lax.expand_dims(lax.reduce_sum(p, (2,)), (2,)))
        acc = add(mul(acc, lax.broadcast_in_dim(corr, acc.shape, (0, 1, 2))),
                  pv)
        return m_new, l_new, acc

    stats = (lax.full((kv_heads, rep, 1), _NEG, f32),
             lax.full((kv_heads, rep, 1), 0, f32),
             lax.full((kv_heads, rep, dh), 0, f32))
    _, l, acc = lax.fori_loop(0, n_blk, block, stats)
    l = lax.broadcast_in_dim(lax.max(l, lax.full(l.shape, 1e-30, f32)),
                             acc.shape, (0, 1, 2))
    o_ref[0] = lax.div(acc, l).reshape(h, dh).astype(o_ref.dtype)


def _paged_kernel_lse(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                      m_scr, l_scr, acc_scr, *, scale: float, page: int,
                      n_blocks: int, kv_heads: int, rep: int, window: int):
    """Paged decode that walks one logical page per grid step (the
    physical page resolved by the BlockSpec index maps through the
    scalar-prefetched block table) and flushes flash-style partials
    instead of a finished output: o = acc / l (normalized over THIS
    kernel's pages) plus lse = m + log(l), so a mesh that stripes the
    logical page axis across shards can run this kernel per stripe and
    merge the partials with ``models.attention.combine_lse_partials`` —
    PagedAttention v2's cross-partition reduction, hoisted out of the
    kernel and into the (GSPMD-collective) merge."""
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
                                  pool_kv_heads: int | None = None,
                                  interpret: bool = False):
    """q: (B, H, dh); k_pool/v_pool: (n_pages, page, KV, dh) shared pool;
    block_tables: (B, P) int32 physical-page ids; cache_len: (B,) int32.
    Returns (B, H, dh).  Reads only the pages that hold each lane's
    ``cache_len`` tokens (within ``window`` when set); a lane with no
    token returns zeros.

    ``pool_kv_heads``: the whole pool's KV heads when this call holds
    one shard's.  Pages per block follow from the whole pool's page
    bytes, so every shard blocks (and merges) a head's pages exactly as
    the unsharded kernel does."""
    b, h, dh = q.shape
    n_pages, page, kv, _ = k_pool.shape
    p_max = block_tables.shape[1]
    ppb = _pages_per_block(page, pool_kv_heads or kv, dh,
                           k_pool.dtype.itemsize, p_max)

    kernel = functools.partial(
        _paged_kernel, scale=dh ** -0.5, page=page, ppb=ppb, p_max=p_max,
        n_lanes=b, kv_heads=kv, rep=h // kv, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,      # block tables (flat), cache_len
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, dh), lambda bi, bt, cl: (bi, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, h, dh), lambda bi, bt, cl: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb * page, kv, dh), k_pool.dtype),
            pltpu.VMEM((2, ppb * page, kv, dh), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, dh), q.dtype),
        # lanes run in order: each prefetches the next lane's first block
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_tables.astype(jnp.int32).reshape(-1),
      cache_len.astype(jnp.int32), q, k_pool, v_pool)


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
