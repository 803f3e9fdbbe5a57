"""Mesh-parallel serving execution: the sharded fused decode plan.

``ShardingPlan`` turns the (previously dry-run-only) partitioning rules
in ``repro.sharding.partitioning`` into a *serving* execution layout for
one engine:

  * a sharded paged KV pool — the (L, n_pages, page, KV, dh) tensors
    split over the kv-head dim, so every physical page is striped
    across shards while the page *grid* (and the host-side block tables
    in ``KVCacheManager``, which stay fully authoritative) is
    shard-invariant.  Swap payloads gather/scatter per-shard slices
    transparently: a payload is the full-head numpy array, so swap-mode
    preemption, CoW prefix sharing, and cluster migration are untouched.
    The attention einsums inherit the pool's sharding and parallelize
    over the kv-head batch dim (on TPU the paged decode kernel runs per
    shard under ``shard_map``, following ``context()``'s pool layout) —
    the decode bottleneck (pool bandwidth) scales with the mesh;
  * expert-parallel MoE — the (E, C, D) capacity buffer and per-expert
    weights shard over 'model'; the router and the K-way weighted
    combine stay replicated;
  * replicated projections — wq/wk/wv, wo, mlp, lm_head/embed run
    full-shape on every shard.

The default plan (``parallel="exact"``) is deliberately *exact*: only
batch-like einsum dims are sharded, so no floating-point contraction
crosses a shard boundary and every per-slice GEMM keeps the exact shape
it has in the unsharded program (see
``repro.sharding.partitioning.decode_rule_table`` for why
column-/row-parallel projections forfeit bit-identity).  This makes the
sharded engine bit-identical to the single-device one — the parity
suite asserts token-identical streams, not tolerances.  Components
whose dimensions don't divide the mesh axis fall back to replicated
(correct, just not parallel) and are reported by ``describe()``.

``parallel="efficient"`` flips the Megatron axes on: column-parallel
wq/wk/wv and MLP up/gate, row-parallel wo/down (one psum per attention
block and one per MLP), vocab-sharded lm_head with a partitioned
argmax/categorical, and kv-head-striped paged attention (or, when the
heads don't divide, an explicit log-sum-exp split of the logical page
axis).  Remarkably little model code changes: the plan's ``gather``
hook becomes the identity and the weight rules flip, and GSPMD derives
the whole dataflow from sharding propagation.  Per-token FLOPs shrink
~tp-fold; bit-identity is replaced by the tolerance contract
(``repro.testing.assert_tokens_close``, docs/sharded_serving.md) —
bit-identical at tp=1, greedy-token match >= 0.999 at tp>1.

Execution model: jit + ``NamedSharding`` (GSPMD), not a hand-written
``shard_map`` (paged decode attention's Pallas kernel aside) — the
engine's host loop, global logical shapes, pow2 bucket ladders, and
buffer donation all carry over unchanged; the plan
only (a) places params and pool once, (b) installs trace-scoped hooks
(``gather_model`` / ``constrain_expert_buf``) around the engine's jit
call sites, and (c) pins cache-typed jit outputs back to the pool
layout so donation round-trips shard-stable.
"""

from __future__ import annotations

import functools
import warnings as _warnings
from dataclasses import dataclass
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels.bucketing import pow2_bucket
from ..sharding.context import serving_sharding
from ..sharding.partitioning import (decode_rule_table, decode_rules,
                                     named_shardings, paged_kv_pool_spec,
                                     resolve_specs, shard_bytes_table)

__all__ = ["ShardingPlan", "estimate_device_bytes",
           "REPLICATION_WARN_BYTES"]

# sharding_report() warns when a weight at least this big silently hit
# the replication fallback (its logical axis didn't divide the mesh) —
# below this, replication is noise; above it, it's the difference
# between fitting and OOM.
REPLICATION_WARN_BYTES = 32 << 20


@dataclass(frozen=True)
class ShardingPlan:
    mesh: Mesh
    tp: int
    parallel: str                 # "exact" | "efficient"
    rules: dict
    report: dict
    param_shardings: Any          # pytree of NamedSharding
    kv_pool: NamedSharding        # (L, n_pages, page, KV, dh) layout
    replicated: NamedSharding
    expert_buf: NamedSharding | None
    q_heads: NamedSharding | None   # (B, S, H, dh) column-parallel q
    kv_heads: NamedSharding | None  # (B, S, KV, dh) column-parallel k/v
    attn_splits: int                # LSE page-splits (1 = no split)
    split_spec: NamedSharding | None
    tensor_rows: tuple            # per-tensor byte/spec accounting rows
    warnings: tuple               # big-weight replication-fallback notes

    @classmethod
    def build(cls, model, mesh: Mesh,
              parallel: str = "exact") -> "ShardingPlan":
        """Resolve the serving-decode rules for ``model`` on ``mesh``
        (raises if any non-'model' axis is bigger than 1).

        ``parallel="exact"`` (default) is the bit-identical plan from
        PR 8; ``parallel="efficient"`` flips the Megatron axes on —
        column/row-parallel projections, vocab-sharded lm_head,
        kv-head-striped attention (or the LSE page-split fallback) —
        trading bit-identity for per-token FLOPs that shrink ~tp-fold
        (tolerance contract: docs/sharded_serving.md)."""
        rules, report = decode_rules(model.cfg, mesh, parallel=parallel)
        specs = resolve_specs(model.param_specs(), rules)
        tp = int(mesh.shape["model"])
        rows = tuple(shard_bytes_table(model.template(), rules, tp,
                                       fallbacks=report["fallbacks"]))
        warns = tuple(
            f"{r['name']} ({r['bytes'] / 2**20:.0f} MiB, axes {r['axes']}) "
            "hit the replication fallback — its sharding axis does not "
            f"divide tp={tp}; every device holds a full copy"
            for r in rows
            if r["fallback"] and r["bytes"] >= REPLICATION_WARN_BYTES)
        for w in warns:
            _warnings.warn(w, RuntimeWarning, stacklevel=3)
        efficient = parallel == "efficient"
        heads_sharded = rules.get("heads") is not None
        attn_splits = int(report.get("attn_splits", 1))
        return cls(
            mesh=mesh,
            tp=tp,
            parallel=parallel,
            rules=rules,
            report=report,
            param_shardings=named_shardings(mesh, specs),
            kv_pool=NamedSharding(mesh, paged_kv_pool_spec(rules)),
            replicated=NamedSharding(mesh, P()),
            expert_buf=(NamedSharding(mesh, P("model", None, None))
                        if rules.get("expert") else None),
            q_heads=(NamedSharding(mesh, P(None, None, "model", None))
                     if efficient and heads_sharded else None),
            kv_heads=(NamedSharding(mesh, P(None, None, "model", None))
                      if efficient and heads_sharded else None),
            attn_splits=attn_splits if efficient else 1,
            split_spec=(NamedSharding(mesh, P(None, "model", None))
                        if efficient and attn_splits > 1 else None),
            tensor_rows=rows,
            warnings=warns,
        )

    # ------------------------------------------------------------ placement

    def place_params(self, params):
        return jax.device_put(params, self.param_shardings)

    def cache_shardings(self, cache: dict) -> dict:
        """Plan layout of a paged-cache dict (arrays or shapes): k/v on
        the pool sharding, recurrent state replicated."""
        return {key: (self.kv_pool if key in ("k", "v") else
                      jax.tree.map(lambda _: self.replicated, val))
                for key, val in cache.items()}

    def place_cache(self, cache: dict) -> dict:
        """Commit a paged-cache dict to the plan layout.  Used to re-pin
        the pool after eager host-side updates (swap restore) whose
        sharding propagation is XLA's choice, not ours — a no-op copy
        when the layout already matches."""
        return jax.device_put(cache, self.cache_shardings(cache))

    # ------------------------------------------------- trace-time constraints

    def gather(self, x):
        """The ``gather_model`` hook body.  Exact mode: all-gather the
        model-sharded axis back to replicated (pure relayout, exact).
        Efficient mode: IDENTITY — leaving the hook's call sites
        unconstrained is precisely what lets GSPMD emit the Megatron
        dataflow through the unchanged model code: ``_wo_proj``'s
        post-hook ``.sum(axis=0)`` over the group-sharded partials
        becomes the row-parallel psum, ``_pin_qkv`` leaves q/k/v
        head-sharded off the column-parallel projections, the final
        logits stay vocab-sharded into a partitioned argmax/categorical
        (only the winning token crosses shards), and the MoE
        capacity-buffer pick becomes a cross-shard gather."""
        if self.parallel == "efficient":
            return x
        return jax.lax.with_sharding_constraint(x, self.replicated)

    def constrain_kv(self, x):
        """Pin a rank-5 (..., KV, dh) KV tensor — pool, prefill cache,
        chunk output, or gathered prefix — to the kv-head sharding."""
        return jax.lax.with_sharding_constraint(x, self.kv_pool)

    def constrain_cache(self, cache: dict) -> dict:
        """Pin a cache dict's outputs inside a traced function: k/v to
        the pool layout, recurrent state replicated.  Keeps the donated
        fused-step round-trip shard-stable (input sharding == output
        sharding is what lets XLA alias the donated pool buffers)."""
        out = {}
        for key, val in cache.items():
            if key in ("k", "v"):
                out[key] = self.constrain_kv(val)
            else:
                out[key] = jax.tree.map(
                    lambda a: jax.lax.with_sharding_constraint(
                        a, self.replicated), val)
        return out

    def context(self):
        """Trace-scoped hook installation (see sharding.context): only
        the engine's own jit calls see the constraints, so unsharded
        engines in the same process are unaffected."""
        return serving_sharding(self.gather, self.expert_buf,
                                q_heads_spec=self.q_heads,
                                kv_heads_spec=self.kv_heads,
                                attn_splits=self.attn_splits,
                                split_spec=self.split_spec,
                                pool_sharding=self.kv_pool)

    def wrap_jit(self, fn, **jit_kwargs):
        """jax.jit that traces under ``context()``.  Forwards the
        private compile counter and ``lower`` so the engine's
        compile-bound checks and the roofline bench's HLO dump work
        identically on the wrapped function."""
        jitted = jax.jit(fn, **jit_kwargs)
        plan = self

        @functools.wraps(fn)
        def call(*args, **kwargs):
            with plan.context():
                return jitted(*args, **kwargs)

        def lower(*args, **kwargs):
            # lowering must trace under the same hooks as execution or
            # the dumped HLO loses the sharding constraints (and with
            # them the collectives the roofline bench prices)
            with plan.context():
                return jitted.lower(*args, **kwargs)

        # a Python closure, not jitted's bound method: the garbage
        # collector cannot see through that nanobind object, so the
        # cycle engine -> call -> jitted -> fn -> engine would never be
        # freed, and with it every buffer the engine holds
        call._cache_size = lambda: jitted._cache_size()
        call.lower = lower
        return call

    # -------------------------------------------------------------- reporting

    def describe(self) -> dict:
        """What actually sharded (per component) on this mesh — the
        divisibility fallbacks make this the source of truth, not the
        requested tp.  Includes the per-tensor byte/spec rows, the
        ``replicated_bytes`` total (what every device pays again), and
        any big-weight replication-fallback warnings."""
        n_dev = 1
        for a in self.mesh.axis_names:
            n_dev *= int(self.mesh.shape[a])
        rows = [dict(r) for r in self.tensor_rows]
        return {
            "devices": n_dev, "tp": self.tp, **self.report,
            "tensors": rows,
            "param_bytes": sum(r["bytes"] for r in rows),
            "param_bytes_per_device":
                sum(r["bytes_per_device"] for r in rows),
            "replicated_bytes":
                sum(r["bytes"] for r in rows if not r["sharded"]),
            "warnings": list(self.warnings),
        }


# ------------------------------------------------------ memory preflight

def _struct_bytes(s) -> int:
    return int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize


def estimate_device_bytes(model, *, tp: int, parallel: str = "exact",
                          n_pages: int, page_size: int,
                          n_slots: int) -> dict:
    """Per-device byte budget for serving ``model`` at width ``tp``:
    weights shard + paged-KV-pool shard + fused-step workspace.

    Pure arithmetic over the parameter template and the mesh-free rule
    table (``decode_rule_table``) — no mesh, no device allocation — so
    the engine preflight prices the layout *before* touching HBM and
    the dry-run min-tp report sweeps tp ladders over 300B-param configs
    instantly.

    The workspace term is a deliberate over-estimate of the fused
    step's dominant transients: two f32 logits-sized buffers (the
    lm_head output + the categorical's scaled copy) at the largest
    batch bucket, plus one f32 MLP hidden buffer — each divided by tp
    when its producing GEMM is sharded.
    """
    cfg = model.cfg
    rules, report = decode_rule_table(cfg, tp, parallel=parallel)
    rows = shard_bytes_table(model.template(), rules, tp,
                             fallbacks=report["fallbacks"])
    weights = sum(r["bytes_per_device"] for r in rows)

    pool_div = tp if rules.get("pool_kv") else 1
    kv_pool = 0
    cache_shapes = model.paged_cache_shapes(n_pages, page_size, n_slots)
    for key, val in cache_shapes.items():
        leaves = jax.tree.leaves(val)
        nbytes = sum(_struct_bytes(s) for s in leaves)
        kv_pool += nbytes // pool_div if key in ("k", "v") else nbytes

    # largest fused batch bucket the engine can trace (floor 8, capped
    # at n_slots — mirrors _decode_fused's pow2 ladder)
    nb = pow2_bucket(n_slots, floor=8, cap=max(n_slots, 1))
    vocab_div = tp if rules.get("vocab") else 1
    mlp_div = tp if rules.get("mlp") else 1
    workspace = 2 * nb * cfg.padded_vocab * 4 // vocab_div \
        + nb * max(cfg.d_ff // mlp_div, cfg.d_model) * 4
    return {
        "tp": tp,
        "parallel": parallel,
        "weights_bytes": int(weights),
        "kv_pool_bytes": int(kv_pool),
        "workspace_bytes": int(workspace),
        "total_bytes": int(weights + kv_pool + workspace),
        "replicated_bytes": int(sum(r["bytes"] for r in rows
                                    if not r["sharded"])),
        "report": report,
    }
