"""Device-resident decode hot loop: one jitted, donated, bucket-stable
engine step.

Load-bearing assertions:

  * the fused jitted step (``step_mode="fused"``) is token-identical to
    the orchestrated per-step engine for every servable model family
    (dense / vlm / moe / ssm / hybrid), in both swap and recompute
    preemption modes, including chunked-prefill mixed steps (greedy);
  * the ``lax.fori_loop`` multi-step variant (N decode tokens per host
    round-trip) emits the same streams while issuing fewer device calls;
  * the compile set is bounded: the fused step compiles at most once per
    (family, bucket) across a churny admit/evict/finish workload —
    counted against the REAL jit cache — and decode logits are
    bit-identical across neighboring batch/page bucket sizes;
  * SSM/hybrid prefill over pow2-padded buffers with the true-length
    mask is bit-identical to the unpadded scan (ROADMAP item), and the
    engine's atomic-prefill jit compiles once per bucket, not once per
    distinct context length;
  * fused stochastic sampling is seeded per (request, position), so swap
    and recompute preemption produce identical streams even at
    temperature > 0 — a guarantee the host-RNG orchestrated path never
    had.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (LengthDistribution, OraclePredictor, Scheduler,
                        make_policy)
from repro.models import build_model
from repro.models.ssm import mamba2_block
from repro.serving import RequestState, ServeRequest, ServingEngine
from repro.serving.engine import _ladder_size, _pow2_bucket

FAMILIES = ["llama3.2-1b", "internvl2-76b", "olmoe-1b-7b", "mamba2-2.7b",
            "zamba2-1.2b"]


def _run_engine(arch, *, step_mode, preemption_mode="swap", decode_steps=1,
                n=3, cap=40, n_slots=2, temperature=0.0, chunk=None,
                mtps=None, max_steps=6000):
    cfg = get_config(arch, reduced=True)
    o = OraclePredictor()
    for i in range(n):
        o.register(f"p{i}", LengthDistribution(np.array([6 + 2 * i]),
                                               np.array([1.0])))
    eng = ServingEngine(
        model=build_model(cfg),
        scheduler=Scheduler(policy=make_policy("sagesched"), predictor=o),
        n_slots=n_slots, max_seq_len=96, capacity_tokens=cap,
        block_size=8, preemption_mode=preemption_mode, prefill_chunk=chunk,
        max_tokens_per_step=mtps, seed=0, step_mode=step_mode,
        decode_steps=decode_steps)
    rng = np.random.default_rng(7)
    reqs = []
    for i in range(n):
        toks = [int(t) for t in rng.integers(3, cfg.vocab_size,
                                             int(rng.integers(6, 11)))]
        reqs.append(ServeRequest(f"r{i}", f"p{i}", toks,
                                 max_new_tokens=6 + 2 * i,
                                 temperature=temperature, eos_token=1,
                                 arrival=float(i) * 1e-3))
    eng.submit_batch(reqs)
    eng.run_until_done(max_steps=max_steps)
    assert all(r.state == RequestState.FINISHED for r in reqs)
    return eng, [r.output_tokens for r in reqs]


# ------------------------------------------------------ engine parity

@pytest.mark.parametrize("arch", FAMILIES)
@pytest.mark.parametrize("pmode", ["swap", "recompute"])
def test_fused_matches_orchestrated(arch, pmode):
    """The acceptance criterion: the fused jitted step is token-identical
    to the orchestrated per-step path for every family and both
    preemption modes (greedy)."""
    _, want = _run_engine(arch, step_mode="orchestrated",
                          preemption_mode=pmode)
    ef, got = _run_engine(arch, step_mode="fused", preemption_mode=pmode)
    assert got == want
    assert ef.metrics.fused_steps > 0
    assert ef.metrics.decode_tokens == sum(len(t) for t in got)


def test_fused_matches_orchestrated_chunked_mixed():
    """Chunked-prefill steps mix prefill chunks with the fused decode
    batch under one token budget; streams still match the orchestrated
    engine."""
    _, want = _run_engine("llama3.2-1b", step_mode="orchestrated", cap=96,
                          n=5, n_slots=4, chunk=4, mtps=12)
    _, got = _run_engine("llama3.2-1b", step_mode="fused", cap=96,
                         n=5, n_slots=4, chunk=4, mtps=12)
    _, got_multi = _run_engine("llama3.2-1b", step_mode="fused", cap=96,
                               n=5, n_slots=4, chunk=4, mtps=12,
                               decode_steps=4)
    assert got == want
    assert got_multi == want


def test_multi_step_matches_single_and_batches_roundtrips():
    """decode_steps=N decodes N tokens per host round-trip inside one
    lax.fori_loop device call: same greedy streams, fewer fused calls,
    fewer scheduler interventions — even under forced preemption."""
    e1, want = _run_engine("llama3.2-1b", step_mode="fused", n=4)
    e4, got = _run_engine("llama3.2-1b", step_mode="fused", n=4,
                          decode_steps=4)
    assert got == want
    assert e4.metrics.fused_steps < e1.metrics.fused_steps
    assert e4.metrics.decode_tokens == e1.metrics.decode_tokens


def test_fused_stochastic_swap_equals_recompute():
    """Sampling keys are folded from (request seed, position), never the
    slot or the preemption history: stochastic streams survive
    preemption-mode changes bit-for-bit."""
    es, a = _run_engine("llama3.2-1b", step_mode="fused", temperature=0.7)
    er, b = _run_engine("llama3.2-1b", step_mode="fused", temperature=0.7,
                        preemption_mode="recompute")
    assert a == b
    assert es.metrics.preemptions > 0 and er.metrics.preemptions > 0


# -------------------------------------------------- bucket stability

def test_compile_counter_bounded_under_churn():
    """A churny admit/evict/finish workload walks the active-lane and
    page counts up and down; the fused step may compile at most once per
    bucket (the real jit cache is the counter), and a second wave of
    churn adds ZERO new compiles."""
    cfg = get_config("llama3.2-1b", reduced=True)
    o = OraclePredictor()
    eng = ServingEngine(
        model=build_model(cfg),
        scheduler=Scheduler(policy=make_policy("sagesched"), predictor=o),
        n_slots=8, max_seq_len=96, capacity_tokens=192, block_size=8,
        seed=0, step_mode="fused")
    def wave(tag, n):
        # fresh identically-seeded rng per wave: wave "b" replays wave
        # "a"'s exact shapes and token values (ids differ), so the jit
        # cache growing on it would be a genuine recompile regression
        rng = np.random.default_rng(11)
        reqs = []
        for i in range(n):
            o.register(f"{tag}{i}", LengthDistribution(
                np.array([3 + (i % 7)]), np.array([1.0])))
            toks = [int(t) for t in rng.integers(3, cfg.vocab_size,
                                                 int(rng.integers(4, 20)))]
            reqs.append(ServeRequest(f"{tag}{i}", f"{tag}{i}", toks,
                                     max_new_tokens=3 + (i % 7),
                                     temperature=0.0, eos_token=1,
                                     arrival=float(i) * 1e-3))
        eng.submit_batch(reqs)
        eng.run_until_done(max_steps=8000)
        return reqs

    wave("a", 12)
    bound = eng.max_fused_compiles()
    first = eng.fused_compile_count
    assert 0 < first <= bound
    # same shapes revisited -> the jit cache must not grow
    wave("b", 12)
    assert eng.fused_compile_count == first


def test_fused_compile_bound_is_ladder_product():
    assert _pow2_bucket(1) == 1 and _pow2_bucket(3) == 4
    assert _pow2_bucket(5, cap=6) == 6
    assert _ladder_size(8) == 4          # 1, 2, 4, 8
    assert _ladder_size(12) == 5         # 1, 2, 4, 8, 12(capped)


def test_decode_bit_identical_across_neighbor_buckets():
    """Lane gathering + table-width bucketing must be pure relayouts: a
    request's decode logits are bit-identical whether it rides a 1-, 2-
    or 4-wide batch bucket and a tight or padded page bucket."""
    cfg = get_config("llama3.2-1b", reduced=True)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(2))
    rng = np.random.default_rng(5)
    S, page = 13, 8
    toks = rng.integers(3, cfg.vocab_size, (1, S)).astype(np.int32)
    _, cache = m.prefill(params, {"tokens": jnp.asarray(toks)})
    kd = np.asarray(cache["k"], np.float32)[:, 0]        # (L, S, KV, dh)
    vd = np.asarray(cache["v"], np.float32)[:, 0]
    L, _, KV, dh = kd.shape
    n_pages = 8
    blocks = [2, 5]                                      # pages for S=13
    phys = np.array([blocks[p // page] * page + p % page
                     for p in range(S)])
    flatk = np.zeros((L, n_pages * page, KV, dh), np.float32)
    flatv = np.zeros_like(flatk)
    flatk[:, phys] = kd[:, :S]
    flatv[:, phys] = vd[:, :S]
    pool_k = flatk.reshape(L, n_pages, page, KV, dh)
    pool_v = flatv.reshape(L, n_pages, page, KV, dh)

    outs = []
    for B, P in ((1, 2), (2, 2), (4, 4), (4, 8)):
        bt = np.zeros((B, P), np.int32)
        bt[0, :2] = blocks
        cl = np.zeros(B, np.int32)
        cl[0] = S - 1
        tok = np.zeros((B, 1), np.int32)
        tok[0, 0] = toks[0, -1]
        pc = {"k": jnp.asarray(pool_k, jnp.bfloat16),
              "v": jnp.asarray(pool_v, jnp.bfloat16)}
        logits, _ = m.decode_step_paged(
            params, jnp.asarray(tok), pc, jnp.asarray(cl),
            jnp.asarray(bt), page_size=page)
        outs.append(np.asarray(logits[0], np.float32))
    for other in outs[1:]:
        np.testing.assert_array_equal(outs[0], other)


# ------------------------------------------- padded recurrent prefill

@pytest.mark.parametrize("arch", ["mamba2-2.7b", "zamba2-1.2b"])
def test_padded_prefill_bit_identical_to_unpadded(arch):
    """ROADMAP item: the true-length mask (dt = 0 at pads, decay exactly
    1) makes every Mamba2 block's pow2-padded prefill bit-identical to
    the unpadded scan — recurrent state and conv tail — even when the
    pad rows hold garbage.

    Each layer's block is fed the same valid rows in both runs.  Chained
    through the whole model the claim would also need XLA's CPU GEMM to
    be row-invariant in the row count, and it is not: a (23, F) and a
    (64, F) bf16 product can round one element differently, which then
    reaches the next layer's state.  That is the backend, not the mask.

    So the whole model's padded ``prefill(lengths=...)`` — and the
    remat branch of the same scan — is held to the unpadded one within
    that CPU rounding: ssd to 1e-3 (drift seen 3.3e-5; pads scanned
    without the mask move it by 2.6e-2), conv tail and attention KV to
    two bf16 ulps at their scale of ~1.3 (drift seen one ulp; unmasked
    pads move the conv tail by 1.8)."""
    cfg = get_config(arch, reduced=True)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(1))
    rng = np.random.default_rng(0)
    S = 23
    u = rng.standard_normal((1, 64, cfg.d_model)).astype(np.float32)
    plain = jax.jit(lambda p, x: mamba2_block(p, x, cfg)[1])
    masked = jax.jit(lambda p, x, n: mamba2_block(p, x, cfg, lengths=n)[1])
    for layer in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[layer], params["layers"]["ssm"])
        want = plain(lp, jnp.asarray(u[:, :S], jnp.bfloat16))
        for spad in (32, 64):
            got = masked(lp, jnp.asarray(u[:, :spad], jnp.bfloat16),
                         jnp.asarray([S], jnp.int32))
            np.testing.assert_array_equal(np.asarray(want["ssd"]),
                                          np.asarray(got["ssd"]))
            np.testing.assert_array_equal(
                np.asarray(want["conv"], np.float32),
                np.asarray(got["conv"], np.float32))

    toks = rng.integers(3, cfg.vocab_size, (1, S)).astype(np.int32)
    _, want = m.prefill(params, {"tokens": jnp.asarray(toks)})
    atol = {"ssd": 1e-3, "conv": 1.6e-2, "k": 1.6e-2, "v": 1.6e-2}
    for spad in (32, 64):
        tp = np.zeros((1, spad), np.int32)
        tp[0, :S] = toks
        batch = {"tokens": jnp.asarray(tp),
                 "lengths": jnp.asarray([S], jnp.int32)}
        _, prefilled = m.prefill(params, batch)
        _, rematted, _ = m.forward(params, batch, collect_cache=True,
                                   remat=True)
        for got in (prefilled, rematted):
            for key in ("ssd", "conv"):
                np.testing.assert_allclose(
                    np.asarray(got["ssm"][key], np.float32),
                    np.asarray(want["ssm"][key], np.float32),
                    rtol=0, atol=atol[key], err_msg=f"{key} spad={spad}")
            for key in ("k", "v") if "k" in want else ():
                np.testing.assert_allclose(
                    np.asarray(got[key], np.float32)[:, :, :S],
                    np.asarray(want[key], np.float32)[:, :, :S],
                    rtol=0, atol=atol[key], err_msg=f"{key} spad={spad}")


def test_recurrent_prefill_compiles_per_bucket_not_per_length():
    """Distinct context lengths inside one pow2 bucket share one XLA
    compile of the engine's atomic-prefill jit (the seed engine compiled
    once per distinct length)."""
    cfg = get_config("mamba2-2.7b", reduced=True)
    eng = ServingEngine(
        model=build_model(cfg),
        scheduler=Scheduler(policy=make_policy("fcfs")),
        n_slots=2, max_seq_len=96, seed=0)
    rng = np.random.default_rng(3)
    reqs = []
    for i, plen in enumerate((7, 9, 12, 14)):     # all in the 32-bucket
        toks = [int(t) for t in rng.integers(3, cfg.vocab_size, plen)]
        reqs.append(ServeRequest(f"c{i}", f"c{i}", toks, max_new_tokens=3,
                                 temperature=0.0, eos_token=1,
                                 arrival=float(i) * 1e-3))
    eng.submit_batch(reqs)
    eng.run_until_done(max_steps=2000)
    assert all(r.state == RequestState.FINISHED for r in reqs)
    assert eng._prefill_fn._cache_size() == 1
