"""Activation-sharding context: Megatron-style sequence parallelism hook.

Model code calls ``constrain_activations(h)`` at block boundaries; by
default it is the identity.  The launcher installs a PartitionSpec (e.g.
P(('pod','data'), 'model', None) — sequence over 'model') before lowering
big-model training steps, which caps the per-device rematerialized
residual-stream memory (DESIGN.md Sec. 5).
"""

from __future__ import annotations

import contextlib
import threading

import jax

_state = threading.local()

__all__ = ["constrain_activations", "activation_sharding",
           "gather_model", "serving_sharding", "constrain_q_heads",
           "constrain_kv_heads", "attn_split_count",
           "constrain_attn_split", "paged_pool_sharding"]


def constrain_activations(h):
    spec = getattr(_state, "spec", None)
    if spec is None:
        return h
    return jax.lax.with_sharding_constraint(h, spec)


def constrain_heads(x):
    """Constrain (batch, seq, heads, head_dim) projections to head-sharded.

    Without this, the backward of the QKV/output projections under 2-D
    (FSDP x TP) weight sharding resolves the seq-vs-heads contraction
    conflict by full replication ('Involuntary full rematerialization' —
    60 x 1.27 GiB f32 on nemotron-340b; EXPERIMENTS.md §Perf)."""
    spec = getattr(_state, "heads_spec", None)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def constrain_inner(x):
    """Constrain (batch, seq, d_inner) SSM projections: seq gathered,
    inner dim sharded over 'model' — resolves the seq-vs-inner GSPMD
    conflict in Mamba2 blocks under sequence parallelism (§Perf B)."""
    spec = getattr(_state, "inner_spec", None)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def constrain_ssm_state(x):
    """Constrain the (B, H, P, N) SSD scan carry head-sharded over 'model'
    — an unannotated zeros-init carry is otherwise replicated, forcing
    full-head re-gathers of every chunk's inputs in the scan (§Perf B.3)."""
    spec = getattr(_state, "state_spec", None)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def constrain_expert_buf(x):
    """Constrain the (E, C, D) MoE capacity buffer expert-sharded over
    'model' — without it GSPMD replicates the expert einsums so every
    chip computes all experts (measured 35x FLOP inflation on olmoe
    prefill, §Perf addendum)."""
    spec = getattr(_state, "expert_spec", None)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def gather_model(x):
    """Force a model-sharded activation back to replicated.

    Identity by default.  The serving sharded-decode plan installs a
    with_sharding_constraint(P()) here at the points where the exact
    (bit-identical) tensor-parallel decomposition must leave the sharded
    regime: before the attention output projection, after the MoE
    capacity-buffer pick, and on the final logits.  Every collective this
    inserts is a pure all-gather (relayout, no arithmetic), which is what
    keeps the sharded engine bit-identical to the single-device one —
    see docs/sharded_serving.md."""
    fn = getattr(_state, "gather_fn", None)
    if fn is None:
        return x
    return fn(x)


def constrain_q_heads(x):
    """Pin a freshly projected (B, S, H, dh) query to the serving plan's
    head sharding (identity outside an efficient-mode serving context).
    Separate from ``constrain_heads`` (the *training* hook) so the
    serving engine never perturbs train/dry-run lowering."""
    spec = getattr(_state, "q_heads_spec", None)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def constrain_kv_heads(x):
    """Pin a freshly projected (B, S, KV, dh) key/value to the serving
    plan's kv-head sharding — matching the paged pool's layout, so the
    pool scatter is shard-local (identity outside an efficient-mode
    serving context)."""
    spec = getattr(_state, "kv_heads_spec", None)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def attn_split_count() -> int:
    """Number of log-sum-exp splits of the logical page axis in paged
    decode attention (models.attention.decode_attention_paged).  1 (no
    split) outside a serving context; the efficient-mode plan installs
    tp when the kv heads don't divide the mesh, so attention still
    parallelizes via flash-style (m, l, acc) partials merged across
    splits."""
    return int(getattr(_state, "attn_splits", 1) or 1)


def constrain_attn_split(x):
    """Constrain a tensor whose axis 1 is the LSE split axis (the token
    index map, then transitively the gathered KV stripes and partial
    softmax stats) to split-sharded over 'model'."""
    spec = getattr(_state, "split_spec", None)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def paged_pool_sharding():
    """The serving plan's (L, n_pages, page, KV, dh) paged-pool
    ``NamedSharding`` while its hooks are installed (inside
    ``serving_sharding``); None on a single device."""
    return getattr(_state, "pool_sharding", None)


@contextlib.contextmanager
def serving_sharding(gather_fn, expert_spec=None, q_heads_spec=None,
                     kv_heads_spec=None, attn_splits=1, split_spec=None,
                     pool_sharding=None):
    """Install the serving-decode hooks around a jit trace: ``gather_fn``
    backs ``gather_model``; ``expert_spec`` (optional) backs
    ``constrain_expert_buf`` so the MoE capacity buffer stays
    expert-sharded.  The efficient-mode plan additionally installs
    ``q_heads_spec``/``kv_heads_spec`` (column-parallel projection
    outputs pinned head-sharded), and ``attn_splits``/``split_spec``
    (the LSE page-split fallback when heads don't divide).
    ``pool_sharding`` is the paged pool's layout, which paged decode
    attention follows (``paged_pool_sharding``).  Scoped: the
    engine enters this only around its jit call sites, so plain
    single-device engines in the same process never see the
    constraints."""
    prev_g = getattr(_state, "gather_fn", None)
    prev_e = getattr(_state, "expert_spec", None)
    prev_q = getattr(_state, "q_heads_spec", None)
    prev_kv = getattr(_state, "kv_heads_spec", None)
    prev_n = getattr(_state, "attn_splits", 1)
    prev_sp = getattr(_state, "split_spec", None)
    prev_pool = getattr(_state, "pool_sharding", None)
    _state.gather_fn = gather_fn
    _state.expert_spec = expert_spec
    _state.q_heads_spec = q_heads_spec
    _state.kv_heads_spec = kv_heads_spec
    _state.attn_splits = attn_splits
    _state.split_spec = split_spec
    _state.pool_sharding = pool_sharding
    try:
        yield
    finally:
        _state.gather_fn = prev_g
        _state.expert_spec = prev_e
        _state.q_heads_spec = prev_q
        _state.kv_heads_spec = prev_kv
        _state.attn_splits = prev_n
        _state.split_spec = prev_sp
        _state.pool_sharding = prev_pool


@contextlib.contextmanager
def activation_sharding(spec, heads_spec=None, inner_spec=None,
                        state_spec=None, expert_spec=None):
    """spec: a PartitionSpec/NamedSharding for (batch, seq, d_model)
    activations; heads_spec: for (batch, seq, heads, head_dim);
    inner_spec: for (batch, seq, d_inner) SSM projections."""
    prev = getattr(_state, "spec", None)
    prev_h = getattr(_state, "heads_spec", None)
    prev_i = getattr(_state, "inner_spec", None)
    prev_s = getattr(_state, "state_spec", None)
    prev_e = getattr(_state, "expert_spec", None)
    _state.spec = spec
    _state.heads_spec = heads_spec
    _state.inner_spec = inner_spec
    _state.state_spec = state_spec
    _state.expert_spec = expert_spec
    try:
        yield
    finally:
        _state.spec = prev
        _state.heads_spec = prev_h
        _state.inner_spec = prev_i
        _state.state_spec = prev_s
        _state.expert_spec = prev_e
