"""Memory-hybrid execution layer: paged KV + swap preemption + chunked
prefill, unified across the real engine and the simulator.

The load-bearing assertions:

  * paged decode (block-table indirection) is BIT-identical to the dense
    per-slot decode path, and chunked prefill is bit-identical to atomic
    prefill for dense models;
  * swap-mode preemption produces token-identical greedy outputs to
    recompute mode while performing ZERO re-prefills on readmission;
  * decode growth past capacity (grow() -> False) is surfaced and forces
    eviction instead of silently unaccounted growth;
  * engine and simulator charge preemption through the SAME
    ServiceModel.swap_time / block-table accounting.
"""

from contextlib import nullcontext

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (LengthDistribution, OraclePredictor, Scheduler,
                        make_policy)
from repro.models import build_model
from repro.serving import RequestState, ServeRequest, ServingEngine
from repro.simulator import NodeSpec, ServiceModel, generate_workload, \
    make_profile, simulate
from repro.simulator.simulator import NodeSimulator


# --------------------------------------------------------- model parity

def _dense_setup(arch="llama3.2-1b", S=23, seed=1):
    cfg = get_config(arch, reduced=True)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(0)
    toks = rng.integers(3, cfg.vocab_size, (1, S)).astype(np.int32)
    return cfg, m, params, toks


def test_chunked_prefill_matches_atomic_dense():
    """Chunk boundaries must not change the computed KV (dense model:
    bit-identical; MoE capacity routing legitimately regroups tokens, so
    only dense is held to equality)."""
    cfg, m, params, toks = _dense_setup()
    S = toks.shape[1]
    _, cache = m.prefill(params, {"tokens": jnp.asarray(toks)})
    want_k = np.asarray(cache["k"], np.float32)[:, 0]
    L, _, KV, dh = want_k.shape
    empty = jnp.zeros((L, 1, 0, KV, dh), jnp.bfloat16)
    # one-shot chunk
    k1, _ = m.prefill_chunk(params, jnp.asarray(toks), empty, empty,
                            jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(k1, np.float32)[:, 0], want_k)
    # two chunks, second fed the first's (padded) KV as its prefix
    c1 = 12
    ka, va = m.prefill_chunk(params, jnp.asarray(toks[:, :c1]), empty,
                             empty, jnp.int32(0))
    pk = np.zeros((L, 1, 16, KV, dh), np.float32)
    pv = np.zeros_like(pk)
    pk[:, :, :c1] = np.asarray(ka, np.float32)
    pv[:, :, :c1] = np.asarray(va, np.float32)
    kb, _ = m.prefill_chunk(params, jnp.asarray(toks[:, c1:]),
                            jnp.asarray(pk, jnp.bfloat16),
                            jnp.asarray(pv, jnp.bfloat16), jnp.int32(c1))
    got = np.concatenate([np.asarray(ka, np.float32),
                          np.asarray(kb, np.float32)], axis=2)[:, 0]
    np.testing.assert_array_equal(got, want_k)


@pytest.mark.parametrize("arch", ["llama3.2-1b", "olmoe-1b-7b"])
def test_paged_decode_matches_dense_decode(arch):
    """Block-table indirection must be a pure relayout: logits from the
    paged decode step equal the dense decode step bit-for-bit."""
    cfg, m, params, toks = _dense_setup(arch)
    S = toks.shape[1]
    _, cache = m.prefill(params, {"tokens": jnp.asarray(toks)})
    kd = np.asarray(cache["k"], np.float32)
    vd = np.asarray(cache["v"], np.float32)
    L, _, _, KV, dh = kd.shape
    page, P, n_pages = 8, 8, 16
    blocks = [3, 1, 4, 2]                       # deliberately non-contiguous
    bt = np.zeros((2, P), np.int32)
    bt[0, :4] = blocks
    phys = np.array([blocks[p // page] * page + p % page for p in range(S)])
    flatk = np.zeros((L, n_pages * page, KV, dh), np.float32)
    flatv = np.zeros_like(flatk)
    flatk[:, phys] = kd[:, 0, :S]
    flatv[:, phys] = vd[:, 0, :S]
    pcache = {
        "k": jnp.asarray(flatk.reshape(L, n_pages, page, KV, dh),
                         jnp.bfloat16),
        "v": jnp.asarray(flatv.reshape(L, n_pages, page, KV, dh),
                         jnp.bfloat16),
    }
    dk = np.zeros((L, 2, 64, KV, dh), np.float32)
    dv = np.zeros_like(dk)
    dk[:, 0, :S] = kd[:, 0, :S]
    dv[:, 0, :S] = vd[:, 0, :S]
    dcache = {"k": jnp.asarray(dk, jnp.bfloat16),
              "v": jnp.asarray(dv, jnp.bfloat16)}
    cl = jnp.asarray(np.array([S - 1, 0]), jnp.int32)
    tok = jnp.asarray(np.array([[toks[0, -1]], [0]]), jnp.int32)
    btj = jnp.asarray(bt)
    for _ in range(4):
        ld, dcache = m.decode_step(params, tok, dcache, cl)
        lp, pcache = m.decode_step_paged(params, tok, pcache, cl, btj,
                                         page_size=page)
        np.testing.assert_array_equal(np.asarray(ld[0], np.float32),
                                      np.asarray(lp[0], np.float32))
        nxt = int(np.argmax(np.asarray(ld[0], np.float32)))
        tok = jnp.asarray(np.array([[nxt], [0]]), jnp.int32)
        cl = cl + jnp.asarray(np.array([1, 0]), jnp.int32)


# ------------------------------------------------- preemption equivalence

def _engine(mode, *, policy="sagesched", cap=56, chunk=None, n=6,
            block=8, n_slots=2, temperature=0.0):
    cfg = get_config("llama3.2-1b", reduced=True)
    o = OraclePredictor()
    for i in range(n):
        o.register(f"p{i}", LengthDistribution(np.array([8 + 3 * i]),
                                               np.array([1.0])))
    eng = ServingEngine(
        model=build_model(cfg),
        scheduler=Scheduler(policy=make_policy(policy), predictor=o),
        n_slots=n_slots, max_seq_len=96, capacity_tokens=cap,
        block_size=block, preemption_mode=mode, prefill_chunk=chunk,
        seed=0)
    rng = np.random.default_rng(3)
    reqs = []
    for i in range(n):
        toks = [int(t) for t in rng.integers(3, cfg.vocab_size,
                                             int(rng.integers(6, 14)))]
        reqs.append(ServeRequest(
            request_id=f"r{i}", prompt=f"p{i}", prompt_tokens=toks,
            max_new_tokens=8 + 3 * i, temperature=temperature, eos_token=1,
            arrival=float(i) * 1e-3))
    eng.submit_batch(reqs)
    eng.run_until_done(max_steps=5000)
    return eng, reqs


def test_swap_equals_recompute_and_skips_reprefill():
    """The acceptance criterion: greedy generations are token-identical
    under recompute vs swap preemption with forced eviction, and swap
    restores skip re-prefill (metrics.prefills stays at one per
    request)."""
    es, rs_s = _engine("swap")
    er, rs_r = _engine("recompute")
    assert es.metrics.preemptions > 0, "scenario must force preemption"
    assert er.metrics.preemptions > 0
    for a, b in zip(rs_s, rs_r):
        assert a.output_tokens == b.output_tokens, a.request_id
        assert a.state == RequestState.FINISHED
    # swap mode: one prefill per request, restores via swap-in
    assert es.metrics.prefills == len(rs_s)
    assert es.metrics.swap_ins > 0
    assert sum(r.n_swap_restores for r in rs_s) == es.metrics.swap_ins
    # recompute mode: every readmission re-prefills
    assert er.metrics.prefills == len(rs_r) + er.metrics.preemptions
    assert er.metrics.swap_ins == 0


def test_chunked_engine_matches_atomic_engine():
    """Chunked prefill is an execution-plan change, not a semantic one:
    greedy outputs equal the atomic engine's (dense model)."""
    ea, rs_a = _engine("swap", cap=96, chunk=None)
    ec, rs_c = _engine("swap", cap=96, chunk=4)
    for a, b in zip(rs_a, rs_c):
        assert a.output_tokens == b.output_tokens, a.request_id
    assert ec.metrics.prefill_chunks > ea.metrics.prefill_chunks
    assert ec.metrics.prefills == len(rs_c)


def test_selection_budget_prevents_organic_grow_failure():
    """The unified block budget (selection reserves blocks_for(ctx+1)
    against the SAME accessor grow() draws from) makes over-capacity
    growth impossible in normal operation — the seed engine's silently
    ignored grow()==False can no longer even occur organically."""
    eng, reqs = _engine("swap", policy="fcfs", cap=48, block=8, n=4)
    assert all(r.state == RequestState.FINISHED for r in reqs)
    assert eng.metrics.preemptions > 0          # capacity was tight
    assert eng.metrics.grow_failures == 0


def test_grow_failure_surfaces_and_forces_eviction():
    """When blocks vanish out from under the engine anyway (here: an
    external allocation hogging the pool), grow()'s False return is
    surfaced as grow_failures and relieved by memory-aware forced
    eviction — not silently dropped like the seed engine did."""
    cfg = get_config("llama3.2-1b", reduced=True)
    eng = ServingEngine(
        model=build_model(cfg),
        scheduler=Scheduler(policy=make_policy("fcfs")),
        n_slots=3, max_seq_len=96, capacity_tokens=96, block_size=8,
        preemption_mode="swap", seed=0)
    rng = np.random.default_rng(4)
    reqs = []
    for i in range(2):
        toks = [int(t) for t in rng.integers(3, cfg.vocab_size, 9)]
        reqs.append(ServeRequest(f"g{i}", f"prompt {i}", toks,
                                 max_new_tokens=30, temperature=0.0,
                                 eos_token=1, arrival=float(i) * 1e-3))
    eng.submit_batch(reqs)
    for _ in range(3):
        eng.step()                      # both prefilled and decoding
    # hog every remaining block behind the manager's back
    hog = eng.kv.free_blocks * eng.kv.block_size
    eng.kv.allocate("__hog__", hog)
    eng.run_until_done(max_steps=3000)
    assert all(r.state == RequestState.FINISHED for r in reqs)
    assert eng.metrics.grow_failures > 0
    assert eng.metrics.forced_evictions > 0
    assert eng.metrics.completed == len(reqs)


def test_mixed_prefill_decode_token_budget():
    """max_tokens_per_step bounds chunk tokens + decode tokens per
    iteration: the engine still completes and runs chunked."""
    cfg = get_config("llama3.2-1b", reduced=True)
    eng = ServingEngine(
        model=build_model(cfg),
        scheduler=Scheduler(policy=make_policy("fcfs")),
        n_slots=4, max_seq_len=96, block_size=8,
        prefill_chunk=8, max_tokens_per_step=12, seed=0)
    rng = np.random.default_rng(5)
    reqs = []
    for i in range(5):
        toks = [int(t) for t in rng.integers(3, cfg.vocab_size, 20)]
        reqs.append(ServeRequest(f"q{i}", f"prompt {i}", toks,
                                 max_new_tokens=6, temperature=0.0,
                                 eos_token=1, arrival=float(i) * 1e-3))
    eng.submit_batch(reqs)
    eng.run_until_done(max_steps=4000)
    assert all(r.state == RequestState.FINISHED for r in reqs)
    # 20-token prompts through 8-token chunks: >= 3 chunks each
    assert eng.metrics.prefill_chunks >= 3 * len(reqs)


# -------------------------------------------------- shared cost model

def test_swap_cost_shared_between_engine_and_simulator():
    """Engine and simulator charge preemption from ONE model:
    ServiceModel.swap_time with block-table (block-aligned) token
    accounting."""
    sm = ServiceModel()
    # block alignment: 100 tokens in 16-token blocks transfer 112 tokens
    assert sm.swap_time(100, block_size=16) == sm.swap_time(112)
    assert sm.swap_time(112, block_size=16) == sm.swap_time(112)
    # the engine's modeled swap seconds are exactly that function applied
    # to its swap events (block size from its own KVCacheManager)
    eng, _ = _engine("swap")
    assert eng.metrics.swap_outs == eng.metrics.swap_ins == 1
    expect = sm.swap_time(eng.metrics.swapped_out_tokens,
                          eng.kv.block_size) \
        + sm.swap_time(eng.metrics.swapped_in_tokens, eng.kv.block_size)
    assert eng.metrics.modeled_swap_s == pytest.approx(expect)
    # the simulator charges through the same call: a NodeSimulator with
    # the same block size prices one swap-in identically
    node = NodeSimulator(Scheduler(policy=make_policy("fcfs")),
                         block_size=eng.kv.block_size)
    t = int(eng.metrics.swapped_in_tokens)
    assert node.model.swap_time(t, node.block_size) \
        == sm.swap_time(t, eng.kv.block_size)


def test_simulator_chunked_prefill_and_memory_eviction():
    profiles = [make_profile(n) for n in ("sharegpt", "alpaca", "write")]
    reqs = generate_workload(profiles, 80, rps=12.0, seed=2)
    atomic = simulate(reqs, Scheduler(policy=make_policy("sagesched")))
    chunked = simulate(reqs, Scheduler(policy=make_policy("sagesched")),
                       prefill_chunk=256)
    assert len(chunked.metrics) == 80
    for m in chunked.metrics:
        assert np.isfinite(m.ttft) and np.isfinite(m.ttlt)
        assert m.ttft <= m.ttlt + 1e-9
    # chunking splits prefills into more iterations
    assert chunked.n_iterations > atomic.n_iterations
    # memory-aware eviction under a tiny KV budget still completes all
    spec = NodeSpec(hbm_bytes=70e9, weight_bytes=64e9)
    res = simulate(reqs[:50], Scheduler(policy=make_policy("sagesched")),
                   spec, memory_weight=0.5, block_size=16)
    assert len(res.metrics) == 50
    assert res.n_evictions > 0


def test_scheduler_eviction_order_memory_term():
    """memory_weight=0 reverses order(); a positive weight prefers the
    cheap-to-restore victim among equally-ranked tails."""
    sched = Scheduler(policy=make_policy("fcfs"))
    for i, rid in enumerate(("a", "b", "c")):
        sched.admit(rid, f"p {rid}", 10, arrival=float(i))
    base = sched.eviction_order(["a", "b", "c"],
                                held_tokens={"a": 10, "b": 10, "c": 10})
    assert base == sched.order(["a", "b", "c"])[::-1]
    # c is least urgent (FCFS, latest arrival) but holds a huge KV; with
    # a strong memory term the small holder b gets evicted first
    held = {"a": 5000, "b": 1, "c": 5000}
    sm = ServiceModel()
    out = sched.eviction_order(
        ["a", "b", "c"], held_tokens=held,
        swap_cost=lambda t: sm.swap_time(t, 16), memory_weight=2.0)
    assert out[0] == "b"


# ------------------------------------------- recurrent families + edges

@pytest.mark.parametrize("arch", ["mamba2-2.7b", "zamba2-1.2b"])
def test_recurrent_families_swap_equals_recompute(arch):
    """SSM/hybrid engine paths (atomic prefill with slot-state write,
    ssm payload swap round-trip, hybrid paged group decode): swap mode
    is token-identical to recompute mode and restores without
    re-prefill."""
    cfg = get_config(arch, reduced=True)

    def run(mode):
        o = OraclePredictor()
        for i in range(3):
            o.register(f"p{i}", LengthDistribution(
                np.array([6 + 3 * i]), np.array([1.0])))
        eng = ServingEngine(
            model=build_model(cfg),
            scheduler=Scheduler(policy=make_policy("sagesched"),
                                predictor=o),
            n_slots=1, max_seq_len=64, capacity_tokens=32, block_size=8,
            preemption_mode=mode, seed=0)
        rng = np.random.default_rng(9)
        reqs = []
        for i in range(3):
            toks = [int(t) for t in rng.integers(3, cfg.vocab_size, 7)]
            reqs.append(ServeRequest(f"s{i}", f"p{i}", toks,
                                     max_new_tokens=6 + 3 * i,
                                     temperature=0.0, eos_token=1,
                                     arrival=float(i) * 1e-3))
        eng.submit_batch(reqs)
        eng.run_until_done(max_steps=3000)
        return eng, reqs

    es, rs_s = run("swap")
    er, rs_r = run("recompute")
    assert all(r.state == RequestState.FINISHED for r in rs_s + rs_r)
    for a, b in zip(rs_s, rs_r):
        assert a.output_tokens == b.output_tokens, (arch, a.request_id)
    if es.metrics.preemptions:
        assert es.metrics.prefills == len(rs_s)
        assert er.metrics.prefills \
            == len(rs_r) + er.metrics.preemptions


def test_infeasible_prompt_rejected_not_livelocked():
    """A prompt larger than the whole physical pool is aborted (with the
    scheduler notified) instead of spinning in WAITING forever."""
    cfg = get_config("llama3.2-1b", reduced=True)
    eng = ServingEngine(
        model=build_model(cfg),
        scheduler=Scheduler(policy=make_policy("fcfs")),
        n_slots=2, max_seq_len=96, capacity_tokens=32, block_size=8,
        seed=0)
    rng = np.random.default_rng(6)
    giant = ServeRequest("giant", "giant prompt",
                         [int(t) for t in rng.integers(3, cfg.vocab_size,
                                                       60)],
                         max_new_tokens=4, temperature=0.0, eos_token=1)
    small = ServeRequest("small", "small prompt",
                         [int(t) for t in rng.integers(3, cfg.vocab_size,
                                                       8)],
                         max_new_tokens=4, temperature=0.0, eos_token=1)
    eng.submit_batch([giant, small])
    eng.run_until_done(max_steps=2000)
    assert giant.state == RequestState.ABORTED
    assert small.state == RequestState.FINISHED
    assert "giant" not in eng.scheduler


def test_prefill_time_chunked_consistent():
    """The closed-form chunked prefill total equals the sum of the
    per-chunk charges the simulator actually applies, and collapses to
    the atomic prefill_time without chunking."""
    sm = ServiceModel()
    assert sm.prefill_time_chunked(700, None) == sm.prefill_time(700)
    assert sm.prefill_time_chunked(700, 1000) == sm.prefill_time(700)
    total, done = 0.0, 0
    while done < 700:
        take = min(256, 700 - done)
        total += sm.prefill_chunk_time(take, done)
        done += take
    assert sm.prefill_time_chunked(700, 256) == pytest.approx(total)
    # chunking trades fixed overhead for a smaller attention term
    assert sm.prefill_time_chunked(700, 256) != sm.prefill_time(700)


# ------------------------------------- paged attention dispatch, counters

@pytest.mark.parametrize("backend,n_splits,plan,kernel", [
    ("tpu", 1, False, True),
    ("cpu", 1, False, False),
    ("tpu", 2, False, False),      # efficient mode's page split
    ("tpu", 1, True, True),        # a mesh plan: the kernel per shard
], ids=["tpu", "cpu", "tpu-split", "tpu-mesh-plan"])
def test_paged_decode_dispatch(monkeypatch, backend, n_splits, plan,
                               kernel):
    """On TPU ``decode_attention_paged`` runs the Pallas kernel (here in
    interpret mode) and agrees with the gather; under a mesh plan the
    kernel runs per shard and equals the single-device kernel bit for
    bit.  The CPU and the split path keep the gather."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.kernels.decode_attention import kernel as kmod
    from repro.models import attention
    from repro.sharding.context import serving_sharding
    calls = []

    def spy(*args, **kw):
        calls.append(args[1].shape)
        return kmod.decode_attention_paged_kernel(*args, interpret=True,
                                                  **kw)

    rng = np.random.default_rng(5)
    B, H, KV, dh, page, P_, n_pages = 3, 12, 2, 128, 16, 4, 16
    q = jnp.asarray(rng.normal(0, 1, (B, 1, H, dh)), jnp.bfloat16)
    kp = jnp.asarray(rng.normal(0, 1, (n_pages, page, KV, dh)),
                     jnp.bfloat16)
    vp = jnp.asarray(rng.normal(0, 1, (n_pages, page, KV, dh)),
                     jnp.bfloat16)
    bt = jnp.asarray(rng.permutation(np.arange(1, n_pages))[:B * P_]
                     .reshape(B, P_), jnp.int32)
    cl = jnp.asarray([1, 33, 64], jnp.int32)
    want = attention.decode_attention_paged(q, kp, vp, bt, cl)

    monkeypatch.setattr(attention, "decode_attention_paged_kernel", spy)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    mesh = Mesh(np.array(jax.devices()[:1]), ("model",))
    pool = NamedSharding(mesh, P(None, None, None, "model", None))
    with (serving_sharding(lambda x: x, pool_sharding=pool) if plan
          else nullcontext()):
        got = attention.decode_attention_paged(q, kp, vp, bt, cl,
                                               n_splits=n_splits)
    assert calls == ([kp.shape] if kernel else [])
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-2, atol=1e-2)
    if plan:
        one = spy(q[:, 0], kp, vp, bt, cl)[:, None]
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(one, np.float32))


def test_decode_kv_page_counters():
    """``decode_kv_pages`` sums the pages holding each ready lane's
    context over fused calls, ``decode_kv_pages_padded`` the calls'
    nb x pb, and ``summary()`` reports their ratio."""
    cfg = get_config("llama3.2-1b", reduced=True)
    o = OraclePredictor()
    o.register("p", LengthDistribution(np.array([4]), np.array([1.0])))
    eng = ServingEngine(
        model=build_model(cfg),
        scheduler=Scheduler(policy=make_policy("fcfs"), predictor=o),
        n_slots=4, max_seq_len=96, block_size=8, seed=0)
    # prompts of 5, 13 and 20 tokens, 4 new tokens each: every request
    # prefills in the first step and the three decode in lockstep
    reqs = [ServeRequest(f"r{i}", "p", list(range(3, 3 + n)),
                         max_new_tokens=4, eos_token=-1,
                         arrival=i * 1e-3)
            for i, n in enumerate((5, 13, 20))]
    eng.submit_batch(reqs)
    m = eng.metrics
    seen = []
    while eng.has_work:
        eng.step()
        seen.append((m.fused_steps, m.decode_kv_pages,
                     m.decode_kv_pages_padded))
    # prefill caches all but the last prompt token, so four fused calls
    # attend contexts of (5, 13, 20) up to (8, 16, 23) tokens: pages
    # (1 + 2 + 3) each call; 3 lanes pad to nb 4 and the longest table
    # (3 pages) to the ladder's floor of 4 pages
    assert seen == [(1, 6, 16), (2, 12, 32), (3, 18, 48), (4, 24, 64)]
    s = eng.metrics.summary(reqs)
    assert (s["decode_kv_pages"], s["decode_kv_pages_padded"]) == (24, 64)
    assert s["decode_kv_read_share"] == pytest.approx(24 / 64)
