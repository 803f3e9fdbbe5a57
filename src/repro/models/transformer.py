"""Decoder-only transformer assembly: dense GQA, MoE, Mamba2 SSD, hybrid,
and VLM (frontend-embedding) variants — one code path per family, all with
scan-over-layers (+ optional remat) so 96-layer configs lower to compact
HLO for the multi-pod dry-run.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..sharding.context import (attn_split_count, constrain_activations,
                                constrain_attn_split, constrain_heads,
                                constrain_kv_heads, constrain_q_heads,
                                gather_model)
from .attention import decode_attention, decode_attention_paged, gqa_attention
from .config import ModelConfig
from .layers import (ParamSpec, apply_rope, attention_template, linear, mlp,
                     mlp_template, norm_template, rms_norm)
from .moe import moe_ffn, moe_template
from .ssm import (mamba2_block, mamba2_decode_step, ssm_state_shape,
                  ssm_template)

__all__ = ["decoder_template", "decoder_forward", "decoder_decode_step",
           "decoder_decode_step_paged", "decoder_prefill_chunk",
           "init_cache_shapes", "paged_cache_shapes", "lm_loss"]


# ------------------------------------------------------------------ template

def _block_template(cfg: ModelConfig, kind: str, layers: int | None):
    """kind: dense | moe | ssm."""
    if kind == "ssm":
        return {"ln": norm_template(cfg.d_model, layers),
                "ssm": ssm_template(cfg, layers)}
    t = {"ln1": norm_template(cfg.d_model, layers),
         "ln2": norm_template(cfg.d_model, layers),
         "attn": attention_template(cfg, layers)}
    if kind == "moe":
        t["moe"] = moe_template(cfg, layers)
    else:
        t["mlp"] = mlp_template(cfg.d_model, cfg.d_ff, cfg.activation, layers)
    return t


def decoder_template(cfg: ModelConfig):
    D, V = cfg.d_model, cfg.padded_vocab
    t = {
        "embed": ParamSpec((V, D), jnp.bfloat16, ("vocab", "embed")),
        "final_norm": norm_template(D),
    }
    if not cfg.tie_embeddings:
        t["lm_head"] = ParamSpec((D, V), jnp.bfloat16, ("embed", "vocab"))

    if cfg.family in ("dense", "vlm"):
        t["layers"] = _block_template(cfg, "dense", cfg.n_layers)
    elif cfg.family == "moe":
        n_moe = cfg.n_layers - cfg.first_k_dense
        if cfg.first_k_dense:
            dense_cfg = cfg.with_overrides(d_ff=cfg.dense_d_ff or cfg.d_ff)
            t["dense_layers"] = _block_template(dense_cfg, "dense",
                                                cfg.first_k_dense)
        t["layers"] = _block_template(cfg, "moe", n_moe)
    elif cfg.family == "ssm":
        t["layers"] = _block_template(cfg, "ssm", cfg.n_layers)
    elif cfg.family == "hybrid":
        t["layers"] = _block_template(cfg, "ssm", cfg.n_layers)
        t["shared_attn"] = _block_template(cfg, "dense", None)  # one block
    else:
        raise ValueError(f"decoder_template: bad family {cfg.family}")
    return t


# ----------------------------------------------------------------- blocks

def _wo_proj(cfg, p, o):
    """Attention output projection, decomposed per kv-head group.

    o: (B, S, H, dh) -> (B, S, D).  A single (H*dh)-long contraction fed
    by an all-gathered ``o`` is NOT shard-stable: GSPMD rewrites
    all-gather+dot into partial-dot+all-reduce (psum ordering), and even
    a blocked gather leaves the GEMM consuming a collective's buffer,
    whose layout steers the backend to a different accumulation order —
    both flip the last bf16 bit, which MoE routing amplifies into token
    divergence.  Instead: per-group partial dots (contraction never
    crosses a group, so never crosses a shard), all-gather the f32
    partials, then a fixed-order group sum on replicated data.  Under
    the training rules — and the serving engine's
    ``parallel="efficient"`` plan (wo row-sharded over 'model', gather
    hook = identity) — the same code reduces over a sharded axis and
    GSPMD emits the standard Megatron row-parallel psum: the "single
    psum per block" of the efficient decode plan falls out of this
    decomposition for free.

    The partials come out group-leading, (g, B, S, D): that is
    dot_general's own batch-first order, so no transpose follows the
    dot.  XLA's CPU dot thunk rejects the group-inner layout
    (``bsgk,gkf->bsgf``) for bf16 operands with f32 accumulation.
    """
    b, s, h, dh = o.shape
    g = cfg.n_kv_heads
    w = p["wo"].reshape(g, (h // g) * dh, -1)
    partial = jnp.einsum("bsgk,gkf->gbsf", o.reshape(b, s, g, (h // g) * dh),
                         w, preferred_element_type=jnp.float32)
    # the barrier keeps the TPU compiler from folding the group sum into
    # the dot's contraction when every group is local (one device): the
    # sharded program cannot fold across its gather, so folding would
    # give one device a different accumulation order than the mesh
    partial = jax.lax.optimization_barrier(partial)
    return gather_model(partial).sum(axis=0).astype(o.dtype)


def _pin_qkv(q, k, v):
    """Pin freshly projected (and rope'd) q/k/v to the serving context's
    layout (identity outside a serving context).

    Exact mode: pin REPLICATED (``gather_model`` is a P() constraint).
    Without the pin, the engine's KV-pool output constraints
    back-propagate through the cache writes into the wq/wk/wv GEMMs,
    re-sharding their output columns — and a column-split GEMM takes a
    different accumulation path on the backend, wobbling the last bf16
    bit (see decode_rule_table).  A user annotation stops the backward
    inference; sharded consumers (the paged-attention einsums) slice
    these replicated values locally, which is exact and collective-free.

    Efficient mode: ``gather_model`` is the identity and the
    ``constrain_*_heads`` hooks pin q/k/v HEAD-SHARDED instead — the
    column-parallel wq/wk/wv outputs stay split, k/v match the sharded
    pool's layout so the page scatter is shard-local, and the attention
    einsums run on per-shard head stripes."""
    q = constrain_q_heads(q)
    k = constrain_kv_heads(k)
    v = constrain_kv_heads(v)
    return gather_model(q), gather_model(k), gather_model(v)


def _attn_seq(cfg, p, h, positions, *, window: int):
    """Full-sequence attention sub-block. Returns (out, (k, v))."""
    b, s, d = h.shape
    q = constrain_heads(
        linear(p["wq"], h, p.get("bq")).reshape(b, s, cfg.n_heads, cfg.head_dim))
    k = linear(p["wk"], h, p.get("bk")).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = linear(p["wv"], h, p.get("bv")).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q, k, v = _pin_qkv(q, k, v)
    o = gqa_attention(q, k, v, causal=True, window=window, positions=positions)
    o = constrain_heads(o)
    return _wo_proj(cfg, p, o), (k, v)


def _dense_block_seq(cfg, p, h, positions, *, window: int, with_moe: bool):
    h = constrain_activations(h)
    attn_out, kv = _attn_seq(cfg, p["attn"], rms_norm(p["ln1"], h, cfg.norm_eps),
                             positions, window=window)
    h = h + attn_out
    hn = rms_norm(p["ln2"], h, cfg.norm_eps)
    if with_moe:
        ffn_out, aux = moe_ffn(p["moe"], hn, cfg)
    else:
        ffn_out, aux = mlp(p["mlp"], hn, cfg.activation), 0.0
    return h + ffn_out, kv, aux


def _ssm_block_seq(cfg, p, h, state=None, lengths=None):
    h = constrain_activations(h)
    out, new_state = mamba2_block(p["ssm"], rms_norm(p["ln"], h, cfg.norm_eps),
                                  cfg, state, lengths=lengths)
    return h + out, new_state


# ------------------------------------------------------- sequence forward

def decoder_forward(params, cfg: ModelConfig, tokens, positions=None,
                    frontend_embeds=None, *, collect_cache: bool = False,
                    remat: bool | None = None, lengths=None):
    """Full-sequence forward (training and prefill).

    tokens: (B, S_text) int32.  frontend_embeds: (B, P, D) optional patch /
    audio-frame embeddings prepended to the text sequence (VLM stub).
    lengths: (B,) int32 true row lengths for end-padded batches — threaded
    into the SSM recurrence (true-length mask, bit-identical to unpadded)
    so recurrent families can prefill over pow2-bucketed padding; the
    attention families are causal, so end-pads never reach valid
    positions and need no mask.
    Returns (logits (B,S,V), cache_or_None, aux_loss).
    """
    remat = cfg.remat if remat is None else remat
    h = params["embed"][tokens]                           # (B, S_text, D)
    if frontend_embeds is not None:
        h = jnp.concatenate([frontend_embeds.astype(h.dtype), h], axis=1)
    b, s, _ = h.shape
    if positions is None:
        positions = jnp.arange(s)
    window = cfg.window if cfg.attention_kind == "sliding_window" else 0

    aux_total = jnp.zeros((), jnp.float32)
    cache = {}

    def scan_blocks(h, stacked, body):
        nonlocal aux_total
        fn = jax.checkpoint(body) if remat else body

        def step(carry, layer_params):
            hh, aux = carry
            hh, kv, aux_l = fn(hh, layer_params)
            return (hh, aux + aux_l), kv

        (h, aux), kvs = jax.lax.scan(step, (h, aux_total), stacked)
        aux_total = aux
        return h, kvs

    if cfg.family in ("dense", "vlm", "moe"):
        if cfg.family == "moe" and cfg.first_k_dense:
            def dense_body(hh, lp):
                hh, kv, aux = _dense_block_seq(cfg.with_overrides(
                    d_ff=cfg.dense_d_ff or cfg.d_ff), lp, hh, positions,
                    window=window, with_moe=False)
                return hh, kv, aux
            h, kv_d = scan_blocks(h, params["dense_layers"], dense_body)

        def body(hh, lp):
            return _dense_block_seq(cfg, lp, hh, positions, window=window,
                                    with_moe=cfg.family == "moe")
        h, kv_m = scan_blocks(h, params["layers"], body)
        if collect_cache:
            if cfg.family == "moe" and cfg.first_k_dense:
                k = jnp.concatenate([kv_d[0], kv_m[0]], axis=0)
                v = jnp.concatenate([kv_d[1], kv_m[1]], axis=0)
            else:
                k, v = kv_m
            cache = {"k": k, "v": v}                      # (L,B,S,KV,dh)

    elif cfg.family == "ssm":
        def body(carry, lp):
            hh = _ssm_block_seq(cfg, lp, carry, lengths=lengths)
            return hh[0], hh[1]
        fn = jax.checkpoint(body) if remat else body
        h, states = jax.lax.scan(fn, h, params["layers"])
        if collect_cache:
            cache = {"ssm": states}                       # dict of (L,...)

    elif cfg.family == "hybrid":
        every = cfg.hybrid_attn_every
        L = cfg.n_layers
        bounds = list(range(0, L, every))
        attn_caches = []
        mamba_states = []

        def body(carry, lp):
            hh = _ssm_block_seq(cfg, lp, carry, lengths=lengths)
            return hh[0], hh[1]
        fn = jax.checkpoint(body) if remat else body
        for gi, start in enumerate(bounds):
            end = min(start + every, L)
            seg = jax.tree.map(lambda x: x[start:end], params["layers"])
            h, st = jax.lax.scan(fn, h, seg)
            mamba_states.append(st)
            # shared attention block after each group
            sh = params["shared_attn"]
            h2, kv, _ = _dense_block_seq(cfg, sh, h, positions,
                                         window=window, with_moe=False)
            h = h2
            attn_caches.append(kv)
        if collect_cache:
            states = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                                  *mamba_states)
            k = jnp.stack([kv[0] for kv in attn_caches])  # (G,B,S,KV,dh)
            v = jnp.stack([kv[1] for kv in attn_caches])
            cache = {"ssm": states, "k": k, "v": v}
    else:
        raise ValueError(cfg.family)

    h = rms_norm(params["final_norm"], h, cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", h, head)
    return logits, (cache if collect_cache else None), aux_total


# ----------------------------------------------------------------- caches

def init_cache_shapes(cfg: ModelConfig, batch: int, max_len: int):
    """Shapes (not arrays) of the decode cache, as jax.ShapeDtypeStruct."""
    dh, kv = cfg.head_dim, cfg.n_kv_heads
    out = {}
    if cfg.family in ("dense", "vlm", "moe"):
        out["k"] = jax.ShapeDtypeStruct(
            (cfg.n_layers, batch, max_len, kv, dh), jnp.bfloat16)
        out["v"] = jax.ShapeDtypeStruct(
            (cfg.n_layers, batch, max_len, kv, dh), jnp.bfloat16)
    if cfg.family in ("ssm", "hybrid"):
        ss = ssm_state_shape(cfg, batch)
        out["ssm"] = {
            "ssd": jax.ShapeDtypeStruct((cfg.n_layers,) + ss["ssd"],
                                        jnp.float32),
            "conv": jax.ShapeDtypeStruct((cfg.n_layers,) + ss["conv"],
                                         jnp.bfloat16),
        }
    if cfg.family == "hybrid":
        groups = -(-cfg.n_layers // cfg.hybrid_attn_every)
        out["k"] = jax.ShapeDtypeStruct((groups, batch, max_len, kv, dh),
                                        jnp.bfloat16)
        out["v"] = jax.ShapeDtypeStruct((groups, batch, max_len, kv, dh),
                                        jnp.bfloat16)
    return out


def _update_cache(cache_l, new, pos):
    """cache_l: (B,S,KV,dh); new: (B,1,KV,dh); pos: (B,) write index."""
    def upd(c, n, p):
        return jax.lax.dynamic_update_slice(c, n, (p, 0, 0))
    return jax.vmap(upd)(cache_l, new, pos)


def _attn_decode(cfg, p, h, k_cache, v_cache, cache_len, *, window: int):
    """h: (B,1,D). Updates cache at cache_len (mod ring for windows)."""
    b = h.shape[0]
    q = linear(p["wq"], h, p.get("bq")).reshape(b, 1, cfg.n_heads, cfg.head_dim)
    k = linear(p["wk"], h, p.get("bk")).reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
    v = linear(p["wv"], h, p.get("bv")).reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
    pos = cache_len[:, None]                              # (B,1) true position
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    q, k, v = _pin_qkv(q, k, v)
    s_max = k_cache.shape[1]
    write = cache_len % s_max if window > 0 else cache_len
    k_cache = _update_cache(k_cache, k, write)
    v_cache = _update_cache(v_cache, v, write)
    o = decode_attention(q, k_cache, v_cache, cache_len + 1, window=window)
    return _wo_proj(cfg, p, o), k_cache, v_cache


def decoder_decode_step(params, cfg: ModelConfig, token, cache, cache_len):
    """One decode step.  token: (B,1) int32; cache_len: (B,) int32 (tokens
    already in cache).  Returns (logits (B,1,V), new_cache)."""
    h = params["embed"][token]                            # (B,1,D)
    window = cfg.window if cfg.attention_kind == "sliding_window" else 0
    new_cache = dict(cache)

    if cfg.family in ("dense", "vlm", "moe"):
        if cfg.family == "moe" and cfg.first_k_dense:
            fk = cfg.first_k_dense
            stacked = (params["dense_layers"], cache["k"][:fk],
                       cache["v"][:fk])

            def dense_body(hh, xs):
                lp, kc, vc = xs
                a, kc, vc = _attn_decode(
                    cfg, lp["attn"], rms_norm(lp["ln1"], hh, cfg.norm_eps),
                    kc, vc, cache_len, window=window)
                hh = hh + a
                dcfg = cfg.with_overrides(d_ff=cfg.dense_d_ff or cfg.d_ff)
                hh = hh + mlp(lp["mlp"], rms_norm(lp["ln2"], hh, cfg.norm_eps),
                              dcfg.activation)
                return hh, (kc, vc)
            h, (kd, vd) = jax.lax.scan(dense_body, h, stacked)
            moe_k, moe_v = cache["k"][fk:], cache["v"][fk:]
        else:
            fk = 0
            moe_k, moe_v = cache["k"], cache["v"]

        def body(hh, xs):
            lp, kc, vc = xs
            a, kc, vc = _attn_decode(
                cfg, lp["attn"], rms_norm(lp["ln1"], hh, cfg.norm_eps),
                kc, vc, cache_len, window=window)
            hh = hh + a
            hn = rms_norm(lp["ln2"], hh, cfg.norm_eps)
            if cfg.family == "moe":
                f, _ = moe_ffn(lp["moe"], hn, cfg, decode=True)
            else:
                f = mlp(lp["mlp"], hn, cfg.activation)
            return hh + f, (kc, vc)

        h, (km, vm) = jax.lax.scan(body, h, (params["layers"], moe_k, moe_v))
        if fk:
            new_cache["k"] = jnp.concatenate([kd, km], axis=0)
            new_cache["v"] = jnp.concatenate([vd, vm], axis=0)
        else:
            new_cache["k"], new_cache["v"] = km, vm

    elif cfg.family == "ssm":
        def body(hh, xs):
            lp, st = xs
            out, new_st = mamba2_decode_step(
                lp["ssm"], rms_norm(lp["ln"], hh, cfg.norm_eps), cfg, st)
            return hh + out, new_st
        h, new_states = jax.lax.scan(body, h, (params["layers"], cache["ssm"]))
        new_cache["ssm"] = new_states

    elif cfg.family == "hybrid":
        every = cfg.hybrid_attn_every
        L = cfg.n_layers
        bounds = list(range(0, L, every))
        new_states, new_ks, new_vs = [], [], []

        def body(hh, xs):
            lp, st = xs
            out, new_st = mamba2_decode_step(
                lp["ssm"], rms_norm(lp["ln"], hh, cfg.norm_eps), cfg, st)
            return hh + out, new_st
        for gi, start in enumerate(bounds):
            end = min(start + every, L)
            seg = jax.tree.map(lambda x: x[start:end], params["layers"])
            st = jax.tree.map(lambda x: x[start:end], cache["ssm"])
            h, ns = jax.lax.scan(body, h, (seg, st))
            new_states.append(ns)
            sh = params["shared_attn"]
            a, kc, vc = _attn_decode(
                cfg, sh["attn"], rms_norm(sh["ln1"], h, cfg.norm_eps),
                cache["k"][gi], cache["v"][gi], cache_len, window=window)
            h = h + a
            h = h + mlp(sh["mlp"], rms_norm(sh["ln2"], h, cfg.norm_eps),
                        cfg.activation)
            new_ks.append(kc)
            new_vs.append(vc)
        new_cache["ssm"] = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0), *new_states)
        new_cache["k"] = jnp.stack(new_ks)
        new_cache["v"] = jnp.stack(new_vs)
    else:
        raise ValueError(cfg.family)

    h = rms_norm(params["final_norm"], h, cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    # vocab-sharded lm_head: column-parallel, no contraction over the
    # sharded dim.  Exact mode gathers (pure relayout — sampling sees
    # the exact single-device logits); efficient mode leaves the hook
    # as identity so the logits STAY vocab-sharded and the fused step's
    # argmax/categorical runs partitioned — only the winning token
    # crosses shards, never the logits
    logits = gather_model(jnp.einsum("bsd,dv->bsv", h, head))
    return logits, new_cache


# -------------------------------------------------------------------- loss

def lm_loss(logits, labels, mask=None):
    """Mean next-token cross-entropy.  logits: (B,S,V); labels: (B,S)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if mask is None:
        return nll.mean()
    mask = mask.astype(jnp.float32)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


# ---------------------------------------------------------- paged serving

def paged_cache_shapes(cfg: ModelConfig, n_pages: int, page_size: int,
                       n_slots: int):
    """Decode-cache shapes for the *paged* serving layout: attention KV
    lives in one (L, n_pages, page, KV, dh) pool shared by the whole
    batch (block-table indirection maps logical positions to physical
    pages; page 0 is the engine's scratch block), while SSM state — a
    constant-size recurrence, nothing to page — stays per decode slot."""
    dh, kv = cfg.head_dim, cfg.n_kv_heads
    out = {}
    if cfg.family in ("dense", "vlm", "moe"):
        shape = (cfg.n_layers, n_pages, page_size, kv, dh)
        out["k"] = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        out["v"] = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    if cfg.family in ("ssm", "hybrid"):
        ss = ssm_state_shape(cfg, n_slots)
        out["ssm"] = {
            "ssd": jax.ShapeDtypeStruct((cfg.n_layers,) + ss["ssd"],
                                        jnp.float32),
            "conv": jax.ShapeDtypeStruct((cfg.n_layers,) + ss["conv"],
                                         jnp.bfloat16),
        }
    if cfg.family == "hybrid":
        groups = -(-cfg.n_layers // cfg.hybrid_attn_every)
        shape = (groups, n_pages, page_size, kv, dh)
        out["k"] = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        out["v"] = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    if cfg.family == "encdec":
        raise ValueError("paged serving does not support encdec")
    return out


def _attn_decode_paged(cfg, p, h, k_pool, v_pool, cache_len, block_tables,
                       *, window: int, page: int):
    """h: (B,1,D); pools (n_pages, page, KV, dh).  Writes this step's KV
    at each row's logical position through its block table (inactive rows
    point at the scratch page), then reads via paged flash-decode.
    ``window`` is a logical sliding window — no ring wrap."""
    b = h.shape[0]
    n_pages = k_pool.shape[0]
    q = linear(p["wq"], h, p.get("bq")).reshape(b, 1, cfg.n_heads, cfg.head_dim)
    k = linear(p["wk"], h, p.get("bk")).reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
    v = linear(p["wv"], h, p.get("bv")).reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
    pos = cache_len[:, None]                              # (B,1) true position
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    q, k, v = _pin_qkv(q, k, v)
    logical = cache_len.astype(jnp.int32)
    phys = block_tables[jnp.arange(b), logical // page] * page \
        + logical % page                                  # (B,) flat token idx
    flat = (n_pages * page,) + k_pool.shape[2:]
    k_pool = k_pool.reshape(flat).at[phys].set(
        k[:, 0].astype(k_pool.dtype)).reshape(k_pool.shape)
    v_pool = v_pool.reshape(flat).at[phys].set(
        v[:, 0].astype(v_pool.dtype)).reshape(v_pool.shape)
    # efficient-mode LSE fallback (sharding.context): when kv heads
    # don't divide the mesh, the logical page axis is split instead and
    # partial softmaxes merge via log-sum-exp combining
    o = decode_attention_paged(q, k_pool, v_pool, block_tables,
                               cache_len + 1, window=window,
                               n_splits=attn_split_count(),
                               constrain_split=constrain_attn_split)
    return _wo_proj(cfg, p, o), k_pool, v_pool


def decoder_decode_step_paged(params, cfg: ModelConfig, token, cache,
                              cache_len, block_tables, *, page_size: int):
    """One decode step over a paged KV pool.  token: (B,1) int32;
    cache_len: (B,) int32; block_tables: (B, P) int32 physical-page ids.
    Returns (logits (B,1,V), new_cache).  SSM families carry their
    (unpaged, per-slot) recurrent state unchanged in layout."""
    if cfg.family == "ssm":
        # attention-free: nothing to page — identical to the dense step
        return decoder_decode_step(params, cfg, token, cache, cache_len)
    h = params["embed"][token]                            # (B,1,D)
    window = cfg.window if cfg.attention_kind == "sliding_window" else 0
    new_cache = dict(cache)

    if cfg.family in ("dense", "vlm", "moe"):
        if cfg.family == "moe" and cfg.first_k_dense:
            fk = cfg.first_k_dense
            stacked = (params["dense_layers"], cache["k"][:fk],
                       cache["v"][:fk])

            def dense_body(hh, xs):
                lp, kc, vc = xs
                a, kc, vc = _attn_decode_paged(
                    cfg, lp["attn"], rms_norm(lp["ln1"], hh, cfg.norm_eps),
                    kc, vc, cache_len, block_tables,
                    window=window, page=page_size)
                hh = hh + a
                dcfg = cfg.with_overrides(d_ff=cfg.dense_d_ff or cfg.d_ff)
                hh = hh + mlp(lp["mlp"], rms_norm(lp["ln2"], hh, cfg.norm_eps),
                              dcfg.activation)
                return hh, (kc, vc)
            h, (kd, vd) = jax.lax.scan(dense_body, h, stacked)
            moe_k, moe_v = cache["k"][fk:], cache["v"][fk:]
        else:
            fk = 0
            moe_k, moe_v = cache["k"], cache["v"]

        def body(hh, xs):
            lp, kc, vc = xs
            a, kc, vc = _attn_decode_paged(
                cfg, lp["attn"], rms_norm(lp["ln1"], hh, cfg.norm_eps),
                kc, vc, cache_len, block_tables,
                window=window, page=page_size)
            hh = hh + a
            hn = rms_norm(lp["ln2"], hh, cfg.norm_eps)
            if cfg.family == "moe":
                f, _ = moe_ffn(lp["moe"], hn, cfg, decode=True)
            else:
                f = mlp(lp["mlp"], hn, cfg.activation)
            return hh + f, (kc, vc)

        h, (km, vm) = jax.lax.scan(body, h, (params["layers"], moe_k, moe_v))
        if fk:
            new_cache["k"] = jnp.concatenate([kd, km], axis=0)
            new_cache["v"] = jnp.concatenate([vd, vm], axis=0)
        else:
            new_cache["k"], new_cache["v"] = km, vm

    elif cfg.family == "hybrid":
        every = cfg.hybrid_attn_every
        L = cfg.n_layers
        bounds = list(range(0, L, every))
        new_states, new_ks, new_vs = [], [], []

        def body(hh, xs):
            lp, st = xs
            out, new_st = mamba2_decode_step(
                lp["ssm"], rms_norm(lp["ln"], hh, cfg.norm_eps), cfg, st)
            return hh + out, new_st
        for gi, start in enumerate(bounds):
            end = min(start + every, L)
            seg = jax.tree.map(lambda x: x[start:end], params["layers"])
            st = jax.tree.map(lambda x: x[start:end], cache["ssm"])
            h, ns = jax.lax.scan(body, h, (seg, st))
            new_states.append(ns)
            sh = params["shared_attn"]
            a, kc, vc = _attn_decode_paged(
                cfg, sh["attn"], rms_norm(sh["ln1"], h, cfg.norm_eps),
                cache["k"][gi], cache["v"][gi], cache_len, block_tables,
                window=window, page=page_size)
            h = h + a
            h = h + mlp(sh["mlp"], rms_norm(sh["ln2"], h, cfg.norm_eps),
                        cfg.activation)
            new_ks.append(kc)
            new_vs.append(vc)
        new_cache["ssm"] = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0), *new_states)
        new_cache["k"] = jnp.stack(new_ks)
        new_cache["v"] = jnp.stack(new_vs)
    else:
        raise ValueError(cfg.family)

    h = rms_norm(params["final_norm"], h, cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    # vocab-sharded lm_head: column-parallel, no contraction over the
    # sharded dim.  Exact mode gathers (pure relayout — sampling sees
    # the exact single-device logits); efficient mode leaves the hook
    # as identity so the logits STAY vocab-sharded and the fused step's
    # argmax/categorical runs partitioned — only the winning token
    # crosses shards, never the logits
    logits = gather_model(jnp.einsum("bsd,dv->bsv", h, head))
    return logits, new_cache


# -------------------------------------------------------- chunked prefill

def decoder_prefill_chunk(params, cfg: ModelConfig, tokens, past_k, past_v,
                          start):
    """One Sarathi-style prefill chunk: run the chunk's tokens against the
    already-cached prefix and return ONLY the chunk's new KV (the engine
    scatters it into the paged pool; logits come later from the shared
    decode path via the rewind-one-position trick).

    tokens: (1, C) int32 chunk (C may be padded; pad rows' KV is simply
    not scattered); past_k/past_v: (L, 1, S_past, KV, dh) gathered prefix
    KV — S_past may exceed the true prefix, ``start`` masks the tail;
    start: scalar int32, true prefix length = the chunk's first position.
    Returns (k_chunk, v_chunk): (L, 1, C, KV, dh).
    """
    if cfg.family not in ("dense", "vlm", "moe"):
        raise ValueError(f"chunked prefill unsupported for {cfg.family}")
    h = params["embed"][tokens]                           # (1, C, D)
    b, c, _ = h.shape
    s_past = past_k.shape[2]
    positions = start + jnp.arange(c)
    past_pos = jnp.arange(s_past)
    # invalid prefix rows get position -1e9: masked by gqa_attention's
    # kp >= 0 padding test, exactly like its internal end-padding
    kv_positions = jnp.concatenate([
        jnp.where(past_pos < start, past_pos, -(10 ** 9)), positions])
    window = cfg.window if cfg.attention_kind == "sliding_window" else 0

    def attn(acfg, p, hh, pk, pv):
        q = linear(p["wq"], hh, p.get("bq")).reshape(
            b, c, acfg.n_heads, acfg.head_dim)
        k = linear(p["wk"], hh, p.get("bk")).reshape(
            b, c, acfg.n_kv_heads, acfg.head_dim)
        v = linear(p["wv"], hh, p.get("bv")).reshape(
            b, c, acfg.n_kv_heads, acfg.head_dim)
        q = apply_rope(q, positions, acfg.rope_theta)
        k = apply_rope(k, positions, acfg.rope_theta)
        q, k, v = _pin_qkv(q, k, v)
        kf = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
        vf = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
        o = gqa_attention(q, kf, vf, causal=True, window=window,
                          positions=positions, kv_positions=kv_positions)
        return _wo_proj(acfg, p, o), (k, v)

    def block(acfg, lp, hh, pk, pv, *, with_moe):
        hh = constrain_activations(hh)
        a, kv = attn(acfg, lp["attn"], rms_norm(lp["ln1"], hh, acfg.norm_eps),
                     pk, pv)
        hh = hh + a
        hn = rms_norm(lp["ln2"], hh, acfg.norm_eps)
        if with_moe:
            f, _ = moe_ffn(lp["moe"], hn, acfg)
        else:
            f = mlp(lp["mlp"], hn, acfg.activation)
        return hh + f, kv

    def scan_blocks(hh, stacked, pk_all, pv_all, body):
        def step(carry, xs):
            lp, pk, pv = xs
            out, kv = body(carry, lp, pk, pv)
            return out, kv
        return jax.lax.scan(step, hh, (stacked, pk_all, pv_all))

    if cfg.family == "moe" and cfg.first_k_dense:
        fk = cfg.first_k_dense
        dcfg = cfg.with_overrides(d_ff=cfg.dense_d_ff or cfg.d_ff)
        h, (kd, vd) = scan_blocks(
            h, params["dense_layers"], past_k[:fk], past_v[:fk],
            lambda hh, lp, pk, pv: block(dcfg, lp, hh, pk, pv,
                                         with_moe=False))
        h, (km, vm) = scan_blocks(
            h, params["layers"], past_k[fk:], past_v[fk:],
            lambda hh, lp, pk, pv: block(cfg, lp, hh, pk, pv, with_moe=True))
        k_new = jnp.concatenate([kd, km], axis=0)
        v_new = jnp.concatenate([vd, vm], axis=0)
    else:
        h, (k_new, v_new) = scan_blocks(
            h, params["layers"], past_k, past_v,
            lambda hh, lp, pk, pv: block(cfg, lp, hh, pk, pv,
                                         with_moe=cfg.family == "moe"))
    return k_new, v_new
