"""Plain float32 reference of the Qwen2 decoder (arXiv:2407.10671), and
the seeded weights both it and the served program run on.

Architecture, as the Hugging Face ``Qwen2ForCausalLM`` defines it:
pre-norm RMSNorm blocks; grouped-query attention with biases on q, k and
v and none on the output projection; rotary embeddings (rotate-half,
``rope_theta``) on q and k; softmax scaled by head_dim**-0.5; a SwiGLU
MLP (down(silu(gate(x)) * up(x))); a final RMSNorm; logits from the
embedding matrix when ``tie_word_embeddings``.  Nothing here imports the
program under test.

Weights are drawn in one jitted call from the seed, in the types they
are served in: bf16 matrices (std 0.02, the config's
``initializer_range``), float32 biases (std 0.02) and float32 norm
scales (uniform 0.8..1.2).  The embedding gets ``vocab_rows`` rows; rows
past ``vocab_size`` are zero and never a reference token.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["make_weights", "final_hidden", "QUANT_MODES"]

# the controls' precisions, one step below the configuration's bf16:
# int8 or float8 (e4m3) weights, scaled per output channel, with
# bfloat16 activations
QUANT_MODES = ("float32", "int8", "fp8")


def _shapes(cfg: dict, vocab_rows: int) -> dict:
    L, D = cfg["num_hidden_layers"], cfg["hidden_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, F = D // H, cfg["intermediate_size"]
    bf, f32 = jnp.bfloat16, jnp.float32
    out = {
        "embed": ((vocab_rows, D), bf, "w"),
        "final_norm": ((D,), f32, "norm"),
        "ln1": ((L, D), f32, "norm"), "ln2": ((L, D), f32, "norm"),
        "wq": ((L, D, H * dh), bf, "w"), "bq": ((L, H * dh), f32, "b"),
        "wk": ((L, D, KV * dh), bf, "w"), "bk": ((L, KV * dh), f32, "b"),
        "wv": ((L, D, KV * dh), bf, "w"), "bv": ((L, KV * dh), f32, "b"),
        "wo": ((L, H * dh, D), bf, "w"),
        "w_gate": ((L, D, F), bf, "w"), "w_up": ((L, D, F), bf, "w"),
        "w_down": ((L, F, D), bf, "w"),
    }
    if not cfg.get("tie_word_embeddings", False):
        out["lm_head"] = ((D, vocab_rows), bf, "w")
    return out


def key_for(seed: int):
    """A JAX key for any whole-number seed."""
    s = int(seed)
    k = jax.random.PRNGKey(s & 0xFFFFFFFF)
    k = jax.random.fold_in(k, (s >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(k, int(s < 0))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _build(key, cfg_items: tuple, vocab_rows: int):
    cfg = dict(cfg_items)
    shapes = _shapes(cfg, vocab_rows)
    std = float(cfg.get("initializer_range", 0.02))
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, (shape, dtype, kind)) in zip(keys, sorted(shapes.items())):
        if kind == "norm":
            out[name] = jax.random.uniform(k, shape, dtype, 0.8, 1.2)
        else:
            out[name] = (jax.random.normal(k, shape, dtype) * std
                         ).astype(dtype)
    v = cfg["vocab_size"]
    if vocab_rows > v:
        out["embed"] = out["embed"].at[v:].set(0)
        if "lm_head" in out:
            out["lm_head"] = out["lm_head"].at[:, v:].set(0)
    return out


def make_weights(cfg: dict, seed: int, vocab_rows: int | None = None) -> dict:
    """All weights on the default device, in one jitted call."""
    rows = vocab_rows or cfg["vocab_size"]
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, bool, str))))
    return _build(key_for(seed), items, rows)


# ------------------------------------------------------------- reference

def _quant(w, mode: str):
    """Weights as the forward uses them: float32, or int8 / float8 e4m3
    scaled per output channel (the last axis), dequantized to
    bfloat16."""
    if mode == "float32":
        return w.astype(jnp.float32)
    w32 = w.astype(jnp.float32)
    top = 127.0 if mode == "int8" else 448.0
    scale = jnp.max(jnp.abs(w32), axis=-2, keepdims=True) / top
    scale = jnp.where(scale == 0, 1.0, scale)
    if mode == "int8":
        q = jnp.clip(jnp.round(w32 / scale), -top, top)
    else:
        q = (w32 / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return (q * scale).astype(jnp.bfloat16)


def _act(mode: str):
    return jnp.float32 if mode == "float32" else jnp.bfloat16


def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def _rope(x, pos, theta):
    """x: (S, heads, dh); rotate-half convention."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos[:, None].astype(jnp.float32) * inv          # (S, dh/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., : dh // 2], x32[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _attention(q, k, v, q_block: int):
    """Causal attention over one sequence, queries in blocks so that the
    score matrix stays small.  q: (S, H, dh); k, v: (S, KV, dh)."""
    S, H, dh = q.shape
    rep = H // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    q_block = q_block if S % q_block == 0 else S
    nb = S // q_block
    qb = q.reshape(nb, q_block, H, dh)

    def one(args):
        i, qi = args
        s = jnp.einsum("qhd,khd->hqk", qi, k,
                       preferred_element_type=jnp.float32) * dh ** -0.5
        qpos = i * q_block + jnp.arange(q_block)
        mask = qpos[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("hqk,khd->qhd", p, v,
                          preferred_element_type=jnp.float32).astype(q.dtype)

    out = jax.lax.map(one, (jnp.arange(nb), qb))
    return out.reshape(S, H, dh)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _hidden(w, tokens, cfg_items: tuple, mode: str, q_block: int):
    cfg = dict(cfg_items)
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D = cfg["hidden_size"]
    dh = D // H
    eps = float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_theta"])
    act = _act(mode)
    S = tokens.shape[0]
    pos = jnp.arange(S)
    emb = w["embed"][tokens]
    h = emb.astype(jnp.float32) if mode == "float32" else emb.astype(act)
    layers = {k: w[k] for k in ("ln1", "ln2", "wq", "bq", "wk", "bk", "wv",
                                "bv", "wo", "w_gate", "w_up", "w_down")}

    def body(h, lw):
        x = _rms(h, lw["ln1"], eps)
        q = (x @ _quant(lw["wq"], mode) + lw["bq"].astype(act)).reshape(S, H, dh)
        k = (x @ _quant(lw["wk"], mode) + lw["bk"].astype(act)).reshape(S, KV, dh)
        v = (x @ _quant(lw["wv"], mode) + lw["bv"].astype(act)).reshape(S, KV, dh)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        o = _attention(q, k, v, q_block).reshape(S, H * dh)
        h = h + (o @ _quant(lw["wo"], mode)).astype(h.dtype)
        x = _rms(h, lw["ln2"], eps)
        g = x @ _quant(lw["w_gate"], mode)
        u = x @ _quant(lw["w_up"], mode)
        h = h + ((jax.nn.silu(g) * u) @ _quant(lw["w_down"], mode)
                 ).astype(h.dtype)
        return h, None

    h, _ = jax.lax.scan(body, h, layers)
    return _rms(h, w["final_norm"], eps).astype(jnp.float32)


def head(w: dict, cfg: dict, mode: str = "float32"):
    """(D, vocab_size) output matrix as the forward in ``mode`` uses it."""
    v = cfg["vocab_size"]
    m = w["embed"][:v].T if cfg.get("tie_word_embeddings", False) \
        else w["lm_head"][:, :v]
    return _quant(m, mode)


def final_hidden(w: dict, cfg: dict, tokens, *, mode: str = "float32",
                 pad_to: int, q_block: int = 512):
    """Final-norm hidden states (pad_to, D) float32 of one sequence,
    computed at ``highest`` matmul precision; the sequence is end-padded
    to ``pad_to`` (causal, so pads never reach real positions) so that
    every sequence shares one program.  Rows past the sequence are
    padding."""
    buf = np.zeros(pad_to, np.int32)
    buf[:len(tokens)] = tokens
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, bool, str))))
    with jax.default_matmul_precision("highest"):
        return _hidden(w, jnp.asarray(buf), items, mode, q_block)
