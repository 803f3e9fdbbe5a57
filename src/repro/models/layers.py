"""Parameter templates + elementary layers (pure-functional JAX).

Every parameter is declared via a ``ParamSpec(shape, dtype, axes)`` in a
nested-dict *template*; ``init_from_template`` materializes weights and
``specs_from_template`` yields the logical-axis tree that
``repro.sharding.partitioning`` resolves into PartitionSpecs.  This keeps
shape declaration, initialization, and sharding in one place — the pattern
MaxText uses with flax metadata, without the flax dependency.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "ParamSpec", "init_from_template", "specs_from_template",
    "shapes_from_template", "rms_norm", "linear", "rope_freqs",
    "apply_rope", "mlp", "mlp_template", "attention_template",
    "norm_template", "activation_fn",
]


class ParamSpec(NamedTuple):
    shape: tuple
    dtype: jnp.dtype
    axes: tuple          # logical axis name per dim (None allowed)
    init: str = "normal"  # normal | zeros | ones


def _is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def _materialize(leaves: tuple, treedef, key, scale: float):
    keys = jax.random.split(key, len(leaves))

    def mk(spec: ParamSpec, k):
        if spec.init == "zeros":
            return jnp.zeros(spec.shape, spec.dtype)
        if spec.init == "ones":
            return jnp.ones(spec.shape, spec.dtype)
        if spec.init == "ssm_a":  # mamba2 A_log in [log 1, log 16]
            u = jax.random.uniform(k, spec.shape, jnp.float32, 1.0, 16.0)
            return jnp.log(u).astype(spec.dtype)
        fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
        std = min(scale, float(np.sqrt(1.0 / max(1, fan_in))))
        # the barrier keeps XLA from fusing the scale into the draw,
        # which would round some weights differently from the same draw
        # made op by op (one bf16 ulp in ~1e-5 of them)
        z = jax.lax.optimization_barrier(
            jax.random.normal(k, spec.shape, jnp.float32))
        return (z * std).astype(spec.dtype)

    return treedef.unflatten([mk(s, k) for s, k in zip(leaves, keys)])


def init_from_template(template, key, scale: float = 0.02, shardings=None):
    """Materialize parameters from a template tree, in one jitted program
    whose outputs land where ``shardings`` (a matching tree of
    ``Sharding``; None = the default device) puts them.  Each leaf is
    drawn and cast on the devices that hold it, so no device ever holds
    an unsharded copy of the tree.  Values equal the same draws made op
    by op on one device."""
    leaves, treedef = jax.tree.flatten(template, is_leaf=_is_spec)
    build = jax.jit(_materialize, static_argnums=(0, 1, 3),
                    out_shardings=shardings)
    return build(tuple(leaves), treedef, key, scale)


def specs_from_template(template):
    """Logical-axis tree mirroring the parameter tree."""
    return jax.tree.map(lambda s: s.axes, template, is_leaf=_is_spec)


def shapes_from_template(template):
    """ShapeDtypeStruct tree (for eval_shape-free dry runs)."""
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                        template, is_leaf=_is_spec)


# ---------------------------------------------------------------- templates

def norm_template(d: int, layers: int | None = None):
    shape, axes = (d,), ("embed",)
    if layers is not None:
        shape, axes = (layers, d), ("layers", "embed")
    return {"scale": ParamSpec(shape, jnp.float32, axes, "ones")}


def attention_template(cfg, layers: int | None = None, bias: bool | None = None):
    D, H, KV, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    bias = cfg.qkv_bias if bias is None else bias
    L = (layers,) if layers is not None else ()
    la = ("layers",) if layers is not None else ()
    t = {
        "wq": ParamSpec(L + (D, H * dh), jnp.bfloat16, la + ("embed", "heads")),
        "wk": ParamSpec(L + (D, KV * dh), jnp.bfloat16, la + ("embed", "kv")),
        "wv": ParamSpec(L + (D, KV * dh), jnp.bfloat16, la + ("embed", "kv")),
        # wo's input dim gets its own logical axis: training and the
        # serving engine's parallel="efficient" rules shard it over
        # 'model' (Megatron row-parallel, psum after), but the exact
        # serving-decode rules must keep wo replicated — a row-parallel
        # output projection forces a psum of partial sums, whose
        # reduction order breaks bit-identity with the unsharded engine.
        "wo": ParamSpec(L + (H * dh, D), jnp.bfloat16,
                        la + ("heads_out", "embed")),
    }
    if bias:
        t["bq"] = ParamSpec(L + (H * dh,), jnp.float32, la + ("heads",), "zeros")
        t["bk"] = ParamSpec(L + (KV * dh,), jnp.float32, la + ("kv",), "zeros")
        t["bv"] = ParamSpec(L + (KV * dh,), jnp.float32, la + ("kv",), "zeros")
    return t


def mlp_template(d_model: int, d_ff: int, activation: str,
                 layers: int | None = None, mlp_axis: str = "mlp"):
    L = (layers,) if layers is not None else ()
    la = ("layers",) if layers is not None else ()
    t = {
        "w_in": ParamSpec(L + (d_model, d_ff), jnp.bfloat16,
                          la + ("embed", mlp_axis)),
        "w_out": ParamSpec(L + (d_ff, d_model), jnp.bfloat16,
                           la + (mlp_axis, "embed")),
    }
    if activation == "swiglu":
        t["w_gate"] = ParamSpec(L + (d_model, d_ff), jnp.bfloat16,
                                la + ("embed", mlp_axis))
    return t


# ------------------------------------------------------------------- layers

def rms_norm(params, x, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps) * params["scale"]
    return y.astype(x.dtype)


def linear(w, x, b=None):
    y = jnp.einsum("...d,df->...f", x, w)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def activation_fn(name: str):
    if name == "swiglu":          # handled by caller (gated)
        return jax.nn.silu
    if name == "squared_relu":
        return lambda x: jnp.square(jax.nn.relu(x))
    if name == "gelu":
        return jax.nn.gelu
    raise ValueError(f"unknown activation {name!r}")


def mlp(params, x, activation: str):
    if activation == "swiglu":
        h = jax.nn.silu(linear(params["w_gate"], x)) * linear(params["w_in"], x)
    else:
        h = activation_fn(activation)(linear(params["w_in"], x))
    return linear(params["w_out"], h)


# --------------------------------------------------------------------- rope

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, dh); positions: broadcastable to (..., S)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)                         # (dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (...,S,dh/2)
    cos = jnp.cos(angles)[..., None, :]                   # (...,S,1,dh/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    x32_1, x32_2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate(
        [x32_1 * cos - x32_2 * sin, x32_2 * cos + x32_1 * sin], axis=-1)
    return out.astype(x.dtype)
