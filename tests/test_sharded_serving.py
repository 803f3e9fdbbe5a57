"""Mesh-parallel serving: sharded-vs-single-device parity.

The tentpole claim of ``repro.serving.sharded``: running the engine on a
mesh (sharded paged KV pool + expert-parallel MoE, everything else
replicated — the exact ``decode_rules`` set) is *bit-identical* to the
single-device engine.  Not close — identical: the rules shard only
batch-like einsum dims, so every per-slice GEMM keeps its unsharded
shape and no float contraction crosses a shard boundary.

The matrix: {dense, moe} x {fused, orchestrated} x {swap, recompute} x
mesh {1x1, 2, 4, 8}, stochastic sampling (temperature 0.7), with the
capacity squeezed so preemption fires mid-decode.  Every sharded run
must emit the same token streams as the no-mesh engine, preserve the KV
accounting invariants, and (fused) stay within the pow2-bucket compile
bound.

tp > 1 requires host devices: CI's mesh job sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before importing
jax; on a plain single-device run those cells skip.

Also here: regression coverage for ``launch.mesh.make_local_mesh``
(host-platform fallback + axis-size validation) and the engine's
mesh/tp consistency checks.
"""

import functools
import gc
import weakref

import jax
import numpy as np
import pytest

from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.core import (LengthDistribution, OraclePredictor, Scheduler,
                        make_policy)
from repro.launch.mesh import make_local_mesh
from repro.models import build_model
from repro.serving import RequestState, ServeRequest, ServingEngine
from repro.testing import assert_engine_quiesced, assert_tokens_close

# Head/expert counts are overridden so every mesh width in the matrix
# divides them — the fallback (non-dividing) path gets its own test.
ARCHS = {
    "dense": ("qwen2-1.5b", dict(n_heads=8, n_kv_heads=8)),
    "moe": ("olmoe-1b-7b", dict(n_heads=8, n_kv_heads=8, n_experts=8)),
}
MESH_WIDTHS = [1, 2, 4, 8]

POOL_SPEC_SHARDED = P(None, None, None, "model", None)


def _need_devices(tp):
    if jax.device_count() < tp:
        pytest.skip(f"needs {tp} devices, jax sees {jax.device_count()} "
                    "(CI mesh job sets "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def _run(fam, *, step_mode, pmode="swap", tp=None, temperature=0.7,
         decode_steps=1, sharing=False, chunk=None, n=3, cap=None,
         overrides=None, parallel="exact"):
    """test_decode_hot_loop's forcing workload (2 slots + a capacity
    squeeze tight enough that both families preempt mid-decode) on an
    optionally-meshed engine.  ``tp=None`` is the plain single-device
    baseline; ``tp=1`` builds a degenerate 1x1 mesh so the plan path
    itself is exercised."""
    arch, ov = ARCHS[fam]
    if cap is None:
        cap = 32  # squeezed so every family x step_mode preempts mid-run
    cfg = get_config(arch, reduced=True).with_overrides(
        **(overrides if overrides is not None else ov))
    o = OraclePredictor()
    for i in range(n):
        o.register(f"p{i}", LengthDistribution(np.array([6 + 2 * i]),
                                               np.array([1.0])))
    eng = ServingEngine(
        model=build_model(cfg),
        scheduler=Scheduler(policy=make_policy("sagesched"), predictor=o),
        n_slots=2, max_seq_len=96, capacity_tokens=cap, block_size=8,
        preemption_mode=pmode, prefill_chunk=chunk, seed=0,
        step_mode=step_mode, decode_steps=decode_steps,
        prefix_sharing=sharing, parallel=parallel,
        mesh=None if tp is None else make_local_mesh(tp=tp))
    rng = np.random.default_rng(7)
    reqs = []
    for i in range(n):
        base = [] if not sharing else _shared_prefix(cfg)
        toks = base + [int(t) for t in rng.integers(
            3, cfg.vocab_size, int(rng.integers(6, 11)))]
        reqs.append(ServeRequest(f"r{i}", f"p{i}", toks,
                                 max_new_tokens=6 + 2 * i,
                                 temperature=temperature, eos_token=1,
                                 arrival=float(i) * 1e-3))
    eng.submit_batch(reqs)
    eng.run_until_done(max_steps=8000)
    assert all(r.state == RequestState.FINISHED for r in reqs)
    eng.kv.assert_conserved()
    assert_engine_quiesced(eng)
    return eng, [tuple(r.output_tokens) for r in reqs]


@functools.lru_cache(maxsize=None)
def _shared_prefix_cached(vocab):
    rng = np.random.default_rng(11)
    return tuple(int(t) for t in rng.integers(3, vocab, 24))


def _shared_prefix(cfg):
    return list(_shared_prefix_cached(cfg.vocab_size))


@functools.lru_cache(maxsize=None)
def _baseline(fam, step_mode, pmode, decode_steps=1, sharing=False,
              chunk=None, cap=None, temperature=0.7):
    """Single-device reference streams, computed once per cell family."""
    _, want = _run(fam, step_mode=step_mode, pmode=pmode, tp=None,
                   decode_steps=decode_steps, sharing=sharing, chunk=chunk,
                   cap=cap, temperature=temperature)
    return want


# ------------------------------------------------------- parity matrix

@pytest.mark.parametrize("tp", MESH_WIDTHS)
@pytest.mark.parametrize("pmode", ["swap", "recompute"])
@pytest.mark.parametrize("step_mode", ["fused", "orchestrated"])
@pytest.mark.parametrize("fam", ["dense", "moe"])
def test_mesh_parity(fam, step_mode, pmode, tp):
    """The acceptance criterion: sharded token streams are identical to
    the unsharded engine's — stochastic sampling, preemption mid-decode
    and all — while the pool actually lives sharded and the fused
    compile set stays within its bound."""
    _need_devices(tp)
    want = _baseline(fam, step_mode, pmode)
    eng, got = _run(fam, step_mode=step_mode, pmode=pmode, tp=tp)
    assert got == want, f"{fam}/{step_mode}/{pmode}/tp={tp} diverged"
    assert eng.metrics.preemptions > 0

    assert eng.plan is not None and eng.tp == tp
    report = eng.sharding_report()
    assert report["devices"] == tp and report["tp"] == tp
    # the report reflects divisibility, not width: a 1x1 mesh still uses
    # the sharded layout (the 'model' axis just has size 1)
    assert report["attention"] == "sharded"
    if fam == "moe":
        assert report["experts"] == "sharded"
    # physical pages are striped over the kv-head dim (the spec is
    # compared by equivalence: jax normalizes size-1 axes away)
    pool = eng._cache["k"]
    from jax.sharding import NamedSharding
    assert pool.sharding.is_equivalent_to(
        NamedSharding(eng.mesh, POOL_SPEC_SHARDED), pool.ndim)
    n_kv = eng.model.cfg.n_kv_heads
    assert pool.addressable_shards[0].data.shape[3] == n_kv // tp

    if step_mode == "fused":
        assert eng.metrics.fused_steps > 0
        assert 0 < eng.fused_compile_count <= eng.max_fused_compiles()


@pytest.mark.parametrize("fam", ["dense", "moe"])
def test_mesh_multi_step_fused(fam):
    """decode_steps=4 batches four decode tokens per host round-trip
    inside lax.fori_loop; the donated, shard-pinned pool round-trip must
    not perturb the streams."""
    _need_devices(2)
    want = _baseline(fam, "fused", "swap", decode_steps=4)
    _, got = _run(fam, step_mode="fused", tp=2, decode_steps=4)
    assert got == want


@pytest.mark.parametrize("fam", ["dense", "moe"])
def test_mesh_prefix_sharing_parity(fam):
    """CoW prefix sharing adopts pool pages by refcount; per-shard pages
    make adoption a shard-local no-op, so reuse accounting and streams
    must match the unsharded sharing-on engine."""
    _need_devices(2)
    want = _baseline(fam, "fused", "swap", sharing=True, chunk=16, cap=96)
    eng, got = _run(fam, step_mode="fused", tp=2, sharing=True, chunk=16,
                    cap=96)
    assert got == want
    assert eng.metrics.prefill_tokens_reused > 0


def test_mesh_chunked_prefill_parity():
    """Chunked prefill scatters each chunk's KV into the sharded pool
    through the same per-shard slice path decode uses."""
    _need_devices(2)
    want = _baseline("dense", "fused", "swap", chunk=4)
    _, got = _run("dense", step_mode="fused", tp=2, chunk=4)
    assert got == want


def test_mesh_swap_equals_recompute_sharded():
    """Sampling keys fold (request seed, position) only — preemption
    history is invisible to the stream even when the swap payload is a
    per-shard gather/scatter."""
    _need_devices(2)
    es, a = _run("dense", step_mode="fused", tp=2, pmode="swap")
    er, b = _run("dense", step_mode="fused", tp=2, pmode="recompute")
    assert a == b
    assert es.metrics.preemptions > 0 and er.metrics.preemptions > 0


def test_mesh_fallback_replicates_non_dividing_heads():
    """Heads that don't divide the mesh axis fall back to a replicated
    pool (correct, just not parallel) and the report says so."""
    _need_devices(4)
    eng, got = _run("dense", step_mode="fused", tp=4,
                    overrides=dict(n_heads=6, n_kv_heads=6))
    _, want = _run("dense", step_mode="fused", tp=None,
                   overrides=dict(n_heads=6, n_kv_heads=6))
    assert got == want
    report = eng.sharding_report()
    assert report["attention"] == "replicated"
    pool = eng._cache["k"]
    from jax.sharding import NamedSharding
    assert pool.sharding.is_equivalent_to(
        NamedSharding(eng.mesh, P()), pool.ndim)
    assert pool.addressable_shards[0].data.shape[3] == 6


# --------------------------------------------- make_local_mesh regressions

def test_make_local_mesh_defaults_to_1x1():
    mesh = make_local_mesh()
    assert mesh.axis_names == ("data", "model")
    assert dict(mesh.shape) == {"data": 1, "model": 1}


def test_make_local_mesh_uses_host_devices():
    n = jax.device_count()
    mesh = make_local_mesh(tp=n)
    assert int(mesh.shape["model"]) == n
    assert mesh.devices.size == n


def test_make_local_mesh_validates_against_device_count():
    n = jax.device_count()
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        make_local_mesh(tp=n + 1)
    with pytest.raises(ValueError, match="bad axis sizes"):
        make_local_mesh(tp=0)
    with pytest.raises(ValueError, match="bad axis sizes"):
        make_local_mesh(data=-1)


def test_engine_rejects_tp_mesh_contradiction():
    arch, ov = ARCHS["dense"]
    cfg = get_config(arch, reduced=True).with_overrides(**ov)
    with pytest.raises(ValueError, match="contradicts"):
        ServingEngine(
            model=build_model(cfg),
            scheduler=Scheduler(policy=make_policy("fcfs")),
            n_slots=2, max_seq_len=96, tp=2, mesh=make_local_mesh(tp=1))


@pytest.mark.parametrize("fam", ["dense", "moe"])
def test_meshed_engine_is_freed_after_use(fam):
    """A meshed engine's jitted steps close over the engine; once the
    caller drops it, garbage collection must free it and its device
    buffers, so one process can build engine after engine."""
    eng, _ = _run(fam, step_mode="fused", tp=1)
    assert eng.plan is not None
    engine_ref = weakref.ref(eng)
    param_refs = [weakref.ref(a) for a in jax.tree.leaves(eng.params)]
    del eng
    gc.collect()
    assert engine_ref() is None
    assert all(r() is None for r in param_refs)


def test_decode_rules_reject_data_parallel_mesh():
    """The serving engine manages the batch host-side; a data axis > 1
    on the decode mesh is a configuration error, not a silent no-op."""
    if jax.device_count() < 2:
        pytest.skip("needs 2 devices")
    from repro.sharding.partitioning import decode_rules
    arch, ov = ARCHS["dense"]
    cfg = get_config(arch, reduced=True).with_overrides(**ov)
    mesh = make_local_mesh(tp=1, data=2)
    with pytest.raises(ValueError, match="non-'model' mesh axis"):
        decode_rules(cfg, mesh)


# ------------------------------------------- efficient (Megatron) parallel
#
# parallel="efficient" flips the projection weight axes onto the mesh
# (column-parallel qkv/up/gate, row-parallel wo/down, vocab-sharded
# lm_head) and keeps parity under the *tolerance* contract
# (repro.testing.assert_tokens_close) instead of bit-identity: psum /
# vocab-reduction orders differ per width, so last-ulp drift may flip a
# coin-toss token.  At tp=1 there is nothing to reorder, so efficient
# mode must still be bit-identical.

@pytest.mark.parametrize("tp", MESH_WIDTHS)
@pytest.mark.parametrize("pmode", ["swap", "recompute"])
@pytest.mark.parametrize("step_mode", ["fused", "orchestrated"])
@pytest.mark.parametrize("fam", ["dense", "moe"])
def test_efficient_tolerance_matrix(fam, step_mode, pmode, tp):
    """The PR-8 parity matrix, rerun under parallel='efficient': streams
    match the single-device engine under the tolerance contract, the
    Megatron components actually shard, and the fused compile set stays
    on the same pow2 ladder as exact mode.

    The contract is stated for GREEDY decoding (temperature 0): Megatron
    psum reordering drifts bf16 logits by ~1 ulp, which under stochastic
    sampling shifts the inverse-CDF thresholds by ~the same relative
    mass — a per-step flip chance far above the greedy near-tie rate,
    and more than a short CI stream can absorb at the 0.999 bar.  Greedy
    is what the 0.999 rate is calibrated for; sampled streams get bit
    identity only from parallel='exact' (PR-8 matrix above)."""
    _need_devices(tp)
    want = _baseline(fam, step_mode, pmode, temperature=0.0)
    eng, got = _run(fam, step_mode=step_mode, pmode=pmode, tp=tp,
                    parallel="efficient", temperature=0.0)
    assert_tokens_close(got, want, bit_identical=(tp == 1),
                        label=f"{fam}/{step_mode}/{pmode}/tp={tp}")
    assert eng.metrics.preemptions > 0

    report = eng.sharding_report()
    assert report["parallel"] == "efficient"
    assert report["attention"] == "sharded"
    assert report["vocab"] == "sharded"
    assert report["mlp"] == "sharded"
    if fam == "moe":
        assert report["experts"] == "sharded"
    # the Megatron weights really live sharded: per-device param bytes
    # shrink with width (norm scales are the only replicated leaves)
    if tp > 1:
        assert report["param_bytes_per_device"] < report["param_bytes"]
        assert report["replicated_bytes"] < 0.05 * report["param_bytes"]
    if step_mode == "fused":
        assert 0 < eng.fused_compile_count <= eng.max_fused_compiles()


def test_efficient_lse_split_non_dividing_heads():
    """Heads that don't divide the mesh keep the pool replicated but
    still parallelize attention compute: the logical page axis is
    striped over the mesh and per-stripe flash partials merge by LSE
    combine.  Parity stays within tolerance."""
    _need_devices(4)
    ov = dict(n_heads=6, n_kv_heads=6)
    _, want = _run("dense", step_mode="fused", tp=None, overrides=ov,
                   temperature=0.0)
    eng, got = _run("dense", step_mode="fused", tp=4, overrides=ov,
                    parallel="efficient", temperature=0.0)
    assert_tokens_close(got, want, label="lse-split/tp=4")
    report = eng.sharding_report()
    assert report["attention"] == "lse-split"
    assert report["attn_splits"] == 4
    assert set(report["fallbacks"]) == {"heads", "heads_out", "kv"}
    # projections that do divide still shard
    assert report["vocab"] == "sharded" and report["mlp"] == "sharded"


def test_engine_rejects_bad_parallel():
    arch, ov = ARCHS["dense"]
    cfg = get_config(arch, reduced=True).with_overrides(**ov)
    with pytest.raises(ValueError, match="bad parallel"):
        ServingEngine(model=build_model(cfg),
                      scheduler=Scheduler(policy=make_policy("fcfs")),
                      n_slots=2, max_seq_len=96, parallel="megatron")


def test_memory_preflight_refuses_and_diagnoses():
    """An over-budget engine fails *before* allocating anything, with
    the per-component breakdown in the message; a fitting budget stores
    the estimate on ``engine.preflight``."""
    arch, ov = ARCHS["dense"]
    cfg = get_config(arch, reduced=True).with_overrides(**ov)

    def build(budget):
        return ServingEngine(
            model=build_model(cfg),
            scheduler=Scheduler(policy=make_policy("fcfs")),
            n_slots=2, max_seq_len=96, block_size=8,
            device_memory_gb=budget)

    with pytest.raises(ValueError) as ei:
        build(1e-6)
    msg = str(ei.value)
    assert "does not fit" in msg and "weights" in msg \
        and "KV pool" in msg and "workspace" in msg

    eng = build(8.0)
    pf = eng.preflight
    assert pf is not None and pf["total_bytes"] <= 8 * 2**30
    assert pf["total_bytes"] == (pf["weights_bytes"] + pf["kv_pool_bytes"]
                                 + pf["workspace_bytes"])


def test_sharding_report_tensor_rows():
    """describe() itemizes every weight: spec, bytes, per-device bytes,
    and whether a divisibility fallback forced replication — and a
    weight above REPLICATION_WARN_BYTES that fell back warns loudly."""
    _need_devices(2)
    import warnings as _w

    import repro.serving.sharded as sharded
    arch, ov = ARCHS["dense"]
    cfg = get_config(arch, reduced=True).with_overrides(**ov)
    eng = ServingEngine(model=build_model(cfg),
                        scheduler=Scheduler(policy=make_policy("fcfs")),
                        n_slots=2, max_seq_len=96, block_size=8,
                        tp=2, parallel="efficient")
    report = eng.sharding_report()
    rows = report["tensors"]
    assert rows and all({"name", "shape", "spec", "bytes",
                         "bytes_per_device", "sharded", "fallback"}
                        <= set(r) for r in rows)
    by_name = {r["name"]: r for r in rows}
    wq = next(r for n, r in by_name.items() if "wq" in n)
    assert wq["sharded"] and wq["bytes_per_device"] == wq["bytes"] // 2
    assert report["replicated_bytes"] == sum(
        r["bytes"] for r in rows if not r["sharded"])
    assert report["warnings"] == []

    # big non-dividing weights trip the replication warning
    old = sharded.REPLICATION_WARN_BYTES
    sharded.REPLICATION_WARN_BYTES = 0
    try:
        with _w.catch_warnings(record=True) as caught:
            _w.simplefilter("always")
            eng2 = ServingEngine(
                model=build_model(cfg.with_overrides(
                    n_heads=3, n_kv_heads=3)),
                scheduler=Scheduler(policy=make_policy("fcfs")),
                n_slots=2, max_seq_len=96, block_size=8,
                tp=2, parallel="efficient")
        assert any("replicat" in str(w.message) for w in caught)
        assert eng2.sharding_report()["warnings"]
    finally:
        sharded.REPLICATION_WARN_BYTES = old
