"""Attention: chunked causal (flash-style reference), sliding window, and
single-token decode over a KV cache.

The chunked implementation is the pure-jnp twin of the Pallas flash kernel
(repro.kernels.flash_attention): online softmax over KV blocks, so peak
memory is O(S * block) instead of O(S^2) — this is what the dry-run
compiles, keeping 32k-prefill activation memory sane.  On TPU the Pallas
kernel replaces it via repro.kernels.flash_attention.ops.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..kernels.decode_attention.kernel import decode_attention_paged_kernel
from ..sharding.context import paged_pool_sharding

__all__ = ["gqa_attention", "decode_attention", "decode_attention_paged",
           "encoder_attention", "combine_lse_partials"]

_NEG = -1e30


def combine_lse_partials(outs, lses, axis: int = 0):
    """Merge flash-style partial attention results along ``axis``.

    ``outs``: stacked *normalized* partial outputs (each partial is
    softmax-complete over its own KV stripe), with a trailing head_dim
    axis; ``lses``: the matching log-sum-exp values, shaped like
    ``outs`` minus that trailing axis.  The merged result equals the
    softmax over the union of the stripes (up to f32 reassociation):

        w_i = exp(lse_i - max_j lse_j);  out = sum_i w_i out_i / sum_i w_i

    An all-masked stripe contributes lse = log(l) + m ~ -inf and weight
    exactly 0.  This is the reduction the sharded paged-decode path and
    the Pallas ``(out, lse)`` kernel variant share — the property test
    in tests/test_tolerance.py pins merge == dense softmax.
    """
    m = jax.lax.stop_gradient(lses).max(axis=axis, keepdims=True)
    # clamp: if every stripe is empty (lse = -inf everywhere) the merge
    # must return 0, not NaN
    m = jnp.maximum(m, _NEG)
    w = jnp.exp(lses - m)                       # (..., n, ...)
    den = jnp.maximum(w.sum(axis=axis), 1e-30)
    num = (outs * jnp.expand_dims(w, -1)).sum(axis=axis)
    out = num / jnp.expand_dims(den, -1)
    lse = jnp.squeeze(m, axis) + jnp.log(den)
    return out, lse


def _repeat_kv(k, n_rep: int):
    """(B,S,KV,dh) -> (B,S,KV*n_rep,dh) by head repetition (GQA)."""
    if n_rep == 1:
        return k
    b, s, kv, dh = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, n_rep, dh)
                            ).reshape(b, s, kv * n_rep, dh)


def gqa_attention(q, k, v, *, causal: bool = True, window: int = 0,
                  block: int = 512, positions=None, kv_positions=None):
    """Chunked multi-head (self or cross) attention.

    q: (B, Sq, H, dh); k, v: (B, Sk, KV, dh) with H % KV == 0.
    window > 0 enables sliding-window causal masking.
    Returns (B, Sq, H, dh).
    """
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    kv = k.shape[2]
    k = _repeat_kv(k, h // kv)
    v = _repeat_kv(v, h // kv)
    scale = dh ** -0.5
    if positions is None:
        positions = jnp.arange(sq)
    if kv_positions is None:
        kv_positions = positions if sk == sq else jnp.arange(sk)
    q_pos = positions            # (Sq,)

    blk = min(block, sk)
    n_blocks = -(-sk // blk)
    pad = n_blocks * blk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kt = k.reshape(b, n_blocks, blk, h, dh)
    vt = v.reshape(b, n_blocks, blk, h, dh)
    k_pos = jnp.pad(kv_positions, (0, pad), constant_values=-(10 ** 9)
                    ).reshape(n_blocks, blk)

    def step(carry, xs):
        m, l, acc = carry            # (B,Sq,H), (B,Sq,H), (B,Sq,H,dh)
        kb, vb, kp = xs              # (B,blk,H,dh), (B,blk,H,dh), (blk,)
        # f32 accumulation (not bf16-rounded-then-upcast): the decode path
        # accumulates scores in f32, and any systematic rounding gap
        # between the two paths is amplified by discrete MoE routing
        scores = jnp.einsum("bshd,bthd->bsth", q, kb,
                            preferred_element_type=jnp.float32)
        scores = scores * scale      # (B,Sq,blk,H)
        mask = jnp.ones((sq, blk), bool)
        if causal:
            mask &= q_pos[:, None] >= kp[None, :]
        if window > 0:
            mask &= q_pos[:, None] - kp[None, :] < window
        mask &= kp[None, :] >= 0     # padding
        scores = jnp.where(mask[None, :, :, None], scores, _NEG)
        m_new = jnp.maximum(m, scores.max(axis=2))
        p = jnp.exp(scores - m_new[:, :, None, :])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=2)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bsth,bthd->bshd", p, vb, preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, sq, h), _NEG, jnp.float32)
    l0 = jnp.zeros((b, sq, h), jnp.float32)
    a0 = jnp.zeros((b, sq, h, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (jnp.moveaxis(kt, 1, 0), jnp.moveaxis(vt, 1, 0), k_pos))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def decode_attention_paged(q, k_pool, v_pool, block_tables, cache_len, *,
                           window: int = 0, n_splits: int = 1,
                           constrain_split=None):
    """One-token decode attention over a *paged* KV pool (vLLM block-table
    indirection).  On TPU this is repro.kernels.decode_attention's paged
    kernel, which reads only each row's live pages (per shard under a
    mesh plan); on the CPU and for ``n_splits > 1`` it is the jnp gather
    below, the kernel's reference.

    q: (B, 1, H, dh); k_pool/v_pool: (n_pages, page, KV, dh) — one pool
    shared by the whole batch; block_tables: (B, P) int32 mapping logical
    page ``j`` of row ``b`` to its physical page (page 0 is the engine's
    scratch block); cache_len: (B,) valid tokens.  Logical capacity per
    row is P * page.  ``window > 0`` is a *logical* sliding window
    (positions in [cache_len - window, cache_len)) — paged caches keep
    every block resident instead of ring-wrapping.
    Returns (B, 1, H, dh).

    Bucket-stable by construction: P may be padded to a pow2 bucket with
    scratch-page rows (they sit past ``cache_len`` and mask to exact
    zeros under the unnormalized-exp softmax), and batch rows are
    independent lanes — so the fused engine step can gather active slots
    into pow2 batch buckets without perturbing any real lane's logits.

    Shard-invariant too: both paths treat the KV dim as a batch dim and
    contract only dh/sequence — the gather's einsums batch over it, and
    the kernel runs per shard with the whole pool's pages per block —
    so a pool sharded over kv-heads (serving.sharded) computes per-shard
    slices of the identical arithmetic; the mesh engine's bit-identity
    rests on this.

    ``n_splits > 1`` (the efficient-mode LSE fallback, installed via
    ``sharding.context`` when kv heads don't divide the mesh) splits the
    *logical page* axis into ``n_splits`` stripes: each stripe runs its
    own softmax to flash-style (m, l, acc) partials and the stripes
    merge by log-sum-exp combining — numerically the
    ``combine_lse_partials`` reduction.  ``constrain_split`` (optional)
    pins the stripe axis to the mesh so GSPMD assigns stripe i to shard
    i and the merge lowers to one small psum over (m, l, acc)-sized
    tensors instead of replicating the pool gather.  NOT bit-identical
    to the unsplit path (different reduction order) — tolerance
    contract applies.
    """
    if n_splits > 1:
        return _decode_attention_paged_split(
            q, k_pool, v_pool, block_tables, cache_len, window=window,
            n_splits=n_splits, constrain_split=constrain_split)
    if jax.default_backend() == "tpu":
        return _decode_attention_paged_kernel(q, k_pool, v_pool,
                                              block_tables, cache_len,
                                              window)
    b = q.shape[0]
    n_pages, page, kvh, dh = k_pool.shape
    h = q.shape[2]
    rep = h // kvh
    p_max = block_tables.shape[1]
    s_log = p_max * page
    scale = dh ** -0.5
    # gather each row's logical cache from the pool (reference path)
    tok = (block_tables.astype(jnp.int32) * page)[:, :, None] \
        + jnp.arange(page, dtype=jnp.int32)[None, None, :]   # (B, P, page)
    tok = tok.reshape(b, s_log)
    k = k_pool.reshape(n_pages * page, kvh, dh)[tok]         # (B, S, KV, dh)
    v = v_pool.reshape(n_pages * page, kvh, dh)[tok]
    qg = q.reshape(b, 1, kvh, rep, dh)
    # numerics mirror decode_attention exactly (f32 scores, unnormalized
    # exp, late divide) so paged and dense decode are step-parity equal
    scores = jnp.einsum("bqkrd,bskd->bqkrs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    idx = jnp.arange(s_log)
    valid = idx[None, :] < cache_len[:, None]                # (B, S)
    if window > 0:
        valid &= idx[None, :] >= cache_len[:, None] - window
    scores = jnp.where(valid[:, None, None, None, :], scores, _NEG)
    m = scores.max(axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    out = jnp.einsum("bqkrs,bskd->bqkrd", p, v,
                     preferred_element_type=jnp.float32)
    out = out / jnp.maximum(p.sum(axis=-1), 1e-30)[..., None]
    return out.reshape(b, 1, h, dh).astype(q.dtype)


def _decode_attention_paged_kernel(q, k_pool, v_pool, block_tables,
                                   cache_len, window):
    """The Pallas paged kernel, reading each lane's live pages where they
    lie in the pool.  Under a mesh serving plan it runs under
    ``shard_map`` on each shard's KV heads and the query heads grouped
    over them (all of them where the pool is replicated), blocking pages
    as the whole pool's kernel does, so each head's result is the
    single-device kernel's bit for bit."""
    run = functools.partial(decode_attention_paged_kernel, window=window,
                            pool_kv_heads=k_pool.shape[2])
    pool = paged_pool_sharding()
    if pool is not None:
        kv = pool.spec[3]
        heads, pages = P(None, kv, None), P(None, None, kv, None)
        run = jax.shard_map(run, mesh=pool.mesh,
                            in_specs=(heads, pages, pages, P(), P()),
                            out_specs=heads, check_vma=False)
    return run(q[:, 0], k_pool, v_pool, block_tables, cache_len)[:, None]


def _decode_attention_paged_split(q, k_pool, v_pool, block_tables,
                                  cache_len, *, window: int,
                                  n_splits: int, constrain_split):
    """LSE page-split paged decode: stripe s owns logical pages
    [s*P/n, (s+1)*P/n), computes flash-style (m, l, acc) partials over
    its stripe, and the stripes merge via log-sum-exp combining.  The
    jnp twin of running the Pallas ``(out, lse)`` kernel variant per
    stripe and reducing with ``combine_lse_partials``."""
    b = q.shape[0]
    n_pages, page, kvh, dh = k_pool.shape
    h = q.shape[2]
    rep = h // kvh
    p_max = block_tables.shape[1]
    scale = dh ** -0.5
    pad = (-p_max) % n_splits
    if pad:
        # scratch-page rows past every cache_len — masked like any other
        # tail padding
        block_tables = jnp.pad(block_tables, ((0, 0), (0, pad)))
    pp = p_max + pad
    per = pp // n_splits                 # logical pages per stripe
    s_per = per * page                   # tokens per stripe
    tok = (block_tables.astype(jnp.int32) * page)[:, :, None] \
        + jnp.arange(page, dtype=jnp.int32)[None, None, :]  # (B, pp, page)
    tok = tok.reshape(b, n_splits, s_per)
    if constrain_split is not None:
        # stripe axis -> 'model': the gather below pulls only this
        # shard's stripe from the (replicated-fallback) pool, and the
        # final stripe reduction becomes the cross-shard LSE combine
        tok = constrain_split(tok)
    k = k_pool.reshape(n_pages * page, kvh, dh)[tok]   # (B, n, S, KV, dh)
    v = v_pool.reshape(n_pages * page, kvh, dh)[tok]
    qg = q.reshape(b, 1, kvh, rep, dh)
    scores = jnp.einsum("bqkrd,bnskd->bnqkrs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    idx = (jnp.arange(n_splits) * s_per)[None, :, None] \
        + jnp.arange(s_per)[None, None, :]             # (1, n, S) global pos
    valid = idx < cache_len[:, None, None]
    if window > 0:
        valid &= idx >= cache_len[:, None, None] - window
    scores = jnp.where(valid[:, :, None, None, None, :], scores, _NEG)
    # per-stripe flash partials (m, l, acc), then the LSE merge over the
    # stripe axis — same reduction as combine_lse_partials, kept in
    # unnormalized (l, acc) form to skip one divide
    m = scores.max(axis=-1)                            # (B, n, 1, KV, rep)
    p = jnp.exp(scores - m[..., None])
    l = p.sum(axis=-1)
    acc = jnp.einsum("bnqkrs,bnskd->bnqkrd", p, v,
                     preferred_element_type=jnp.float32)
    m_tot = m.max(axis=1, keepdims=True)               # (B, 1, 1, KV, rep)
    w = jnp.exp(m - m_tot)
    l_tot = (l * w).sum(axis=1)                        # (B, 1, KV, rep)
    acc_tot = (acc * w[..., None]).sum(axis=1)
    out = acc_tot / jnp.maximum(l_tot, 1e-30)[..., None]
    return out.reshape(b, 1, h, dh).astype(q.dtype)


def encoder_attention(q, k, v, *, kv_mask=None):
    """Bidirectional (encoder / cross) attention — chunked (flash-style).

    q: (B,Sq,H,dh); k,v: (B,Sk,KV,dh).  kv_mask (B,Sk) is not supported in
    the chunked path; padding is handled by the caller's kv_positions.
    """
    assert kv_mask is None, "use kv_positions-based masking"
    return gqa_attention(q, k, v, causal=False)


def decode_attention(q, k_cache, v_cache, cache_len, *, window: int = 0):
    """One-token decode attention over a (possibly ring-buffer) KV cache.

    q: (B, 1, H, dh); k_cache/v_cache: (B, S_max, KV, dh);
    cache_len: (B,) number of valid tokens (for ring buffers, the write
    cursor — all S_max slots valid once wrapped).
    Returns (B, 1, H, dh).

    Under the serving mesh the cache shards over KV (a batch dim of both
    einsums — exact); the train/serve rule sets may instead shard S over
    'model' for MQA/low-KV models, where GSPMD inserts the partial-max/sum
    all-reduces — the LSE-combine flash-decode pattern.
    """
    b, s_max, kvh, dh = k_cache.shape
    h = q.shape[2]
    rep = h // kvh
    scale = dh ** -0.5
    # grouped-head contraction — no materialized KV head repetition (a
    # (B,S,H,dh) broadcast of the cache would be GSPMD-resharded at full
    # size; measured collective-bound decode before this, §Perf).
    qg = q.reshape(b, 1, kvh, rep, dh)
    # bf16 reads + f32 accumulation (flash-decode numerics): casting the
    # cache to f32 doubles its HBM traffic for nothing (§Perf A.2)
    scores = jnp.einsum("bqkrd,bskd->bqkrs", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    idx = jnp.arange(s_max)
    valid = idx[None, :] < cache_len[:, None]            # (B, S_max)
    if window > 0:
        # ring buffer: every slot holds one of the last `window` tokens
        valid = valid | (cache_len[:, None] >= s_max)
    scores = jnp.where(valid[:, None, None, None, :], scores, _NEG)
    # unnormalized-exp then late divide, mirroring gqa_attention's online
    # softmax step for step-parity with the prefill/full-forward path:
    # p stays f32 into the value contraction, normalizer applied last
    m = scores.max(axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    out = jnp.einsum("bqkrs,bskd->bqkrd", p, v_cache,
                     preferred_element_type=jnp.float32)
    out = out / jnp.maximum(p.sum(axis=-1), 1e-30)[..., None]
    return out.reshape(b, 1, h, dh).astype(q.dtype)
