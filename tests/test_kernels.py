"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(the kernel body executes in Python on CPU; on TPU the same code lowers
natively)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gittins_index_batch
from repro.kernels.decode_attention.kernel import \
    decode_attention_paged_kernel
from repro.kernels.decode_attention.ops import decode_attention_op
from repro.kernels.decode_attention.ref import (
    decode_attention_paged_reference, decode_attention_reference)
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_reference
from repro.kernels.gittins.ops import (PAD_SUPPORT, gittins_attained_op,
                                       gittins_op)
from repro.kernels.ssd_scan.ops import ssd_scan_op
from repro.kernels.ssd_scan.ref import ssd_reference
from repro.models.ssm import ssd_chunked

RNG = np.random.default_rng(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,KV,dh,causal,window", [
    (2, 256, 4, 2, 64, True, 0),      # GQA
    (1, 256, 4, 4, 128, True, 0),     # MHA
    (2, 200, 4, 1, 64, True, 0),      # MQA + ragged seq (padding path)
    (1, 256, 4, 2, 64, False, 0),     # bidirectional (encoder)
    (1, 384, 4, 2, 64, True, 128),    # sliding window
])
def test_flash_attention_vs_oracle(B, S, H, KV, dh, causal, window, dtype):
    q = jnp.asarray(RNG.normal(0, 1, (B, S, H, dh)), dtype)
    k = jnp.asarray(RNG.normal(0, 1, (B, S, KV, dh)), dtype)
    v = jnp.asarray(RNG.normal(0, 1, (B, S, KV, dh)), dtype)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          force_pallas=True)
    want = attention_reference(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,KV,dh,window,blk", [
    (2, 512, 8, 2, 64, 0, 128),
    (3, 1024, 4, 1, 128, 0, 256),     # MQA (granite-style)
    (2, 512, 8, 8, 64, 512, 128),     # ring buffer (sliding window)
    (1, 640, 4, 4, 64, 0, 128),
])
def test_decode_attention_vs_oracle(B, S, H, KV, dh, window, blk, dtype):
    q = jnp.asarray(RNG.normal(0, 1, (B, H, dh)), dtype)
    k = jnp.asarray(RNG.normal(0, 1, (B, S, KV, dh)), dtype)
    v = jnp.asarray(RNG.normal(0, 1, (B, S, KV, dh)), dtype)
    hi = S + 200 if window else S
    cl = jnp.asarray(RNG.integers(1, hi, (B,)), jnp.int32)
    got = decode_attention_op(q, k, v, cl, window=window, block_s=blk,
                              force_pallas=True)
    want = decode_attention_reference(q, k, v, cl, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KV,dh,page,P,n_pages,window", [
    (2, 8, 2, 64, 16, 8, 32, 0),      # GQA
    (3, 4, 1, 128, 32, 4, 16, 0),     # MQA
    (2, 8, 8, 64, 16, 8, 32, 40),     # logical sliding window
])
def test_paged_decode_attention_vs_oracle(B, H, KV, dh, page, P, n_pages,
                                          window, dtype):
    """Paged kernel (pages copied through the scalar-prefetched block
    table) vs the gather-based oracle, non-contiguous physical pages."""
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(0, 1, (B, H, dh)), dtype)
    kp = jnp.asarray(rng.normal(0, 1, (n_pages, page, KV, dh)), dtype)
    vp = jnp.asarray(rng.normal(0, 1, (n_pages, page, KV, dh)), dtype)
    bt = jnp.asarray(rng.permutation(np.arange(1, n_pages))[:B * P]
                     .reshape(B, P), jnp.int32)
    cl = jnp.asarray(rng.integers(1, P * page, (B,)), jnp.int32)
    got = decode_attention_paged_kernel(q, kp, vp, bt, cl, window=window,
                                        interpret=True)
    want = decode_attention_paged_reference(q, kp, vp, bt, cl,
                                            window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


# The paged kernel at served widths (dh 128, page 16, bf16 pools):
# (H, KV, table pages P, cache_len of each real lane, window).  Pages
# per block follow from the page's bytes: 16 at qwen2-1.5b's 2 KV heads
# (a block is 256 tokens), 2 at olmoe-1b-7b's 16 (32 tokens).  Every
# batch also carries a pad lane: one token on the scratch page.
LIVE_PAGES = [
    # 1 token, a partial last page, a block boundary, the full rung
    pytest.param(12, 2, 32, [1, 40, 256, 512, 300], 0, id="qwen2-ragged"),
    pytest.param(16, 16, 8, [1, 40, 32, 128, 77], 0, id="olmoe-ragged"),
    pytest.param(12, 2, 32, [1, 40, 256, 512, 300], 40, id="qwen2-window"),
    pytest.param(16, 16, 8, [1, 40, 32, 128, 77], 24, id="olmoe-window"),
    # pages per block do not divide the table: 16 into 24, 2 into 5
    pytest.param(12, 2, 24, [384, 257, 1, 100], 0, id="qwen2-ppb-24"),
    pytest.param(16, 16, 5, [80, 33, 1], 0, id="olmoe-ppb-5"),
]


@pytest.mark.parametrize("H,KV,P,lens,window", LIVE_PAGES)
def test_paged_kernel_reads_live_pages_vs_f32_oracle(H, KV, P, lens,
                                                     window):
    """The paged kernel against the float32 gather oracle, with block
    tables scattered out of order over the pool.  Every page that holds
    no live token of any lane (past a lane's cache_len, before its
    window, or in no table) is NaN: a kernel that read one would return
    NaN."""
    rng = np.random.default_rng(11)
    dh, page, n_pages = 128, 16, 200
    lens = np.array(lens + [1], np.int32)            # last: the pad lane
    b = len(lens)
    perm = rng.permutation(np.arange(1, n_pages))
    bt = perm[:(b - 1) * P].reshape(b - 1, P)
    bt = np.concatenate([bt, np.zeros((1, P), np.int64)]).astype(np.int32)
    kp = rng.normal(0, 1, (n_pages, page, KV, dh)).astype(np.float32)
    vp = rng.normal(0, 1, (n_pages, page, KV, dh)).astype(np.float32)
    live = np.zeros(n_pages, bool)
    for row, n in zip(bt, lens):
        first = max(n - window, 0) // page if window else 0
        live[row[first:-(-n // page)]] = True
    q = jnp.asarray(rng.normal(0, 1, (b, H, dh)), jnp.bfloat16)
    kb = jnp.asarray(kp, jnp.bfloat16)
    vb = jnp.asarray(vp, jnp.bfloat16)
    want = decode_attention_paged_reference(
        q.astype(jnp.float32), kb.astype(jnp.float32),
        vb.astype(jnp.float32), jnp.asarray(bt), jnp.asarray(lens),
        window=window)
    dead = jnp.asarray(~live)[:, None, None, None]
    got = decode_attention_paged_kernel(
        q, jnp.where(dead, jnp.nan, kb), jnp.where(dead, jnp.nan, vb),
        jnp.asarray(bt), jnp.asarray(lens), window=window, interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=1e-2, atol=1e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n_splits", [2, 4])
def test_paged_lse_kernel_stripes_merge_to_full(n_splits, dtype):
    """The (out, lse) Pallas variant run per page-stripe, merged by
    ``combine_lse_partials``, equals the full paged kernel AND the
    oracle — the device-side half of the sharded lse-split path."""
    from repro.kernels.decode_attention import decode_attention_paged_lse_op
    from repro.kernels.decode_attention.ref import (
        decode_attention_paged_lse_reference)
    from repro.models.attention import combine_lse_partials
    rng = np.random.default_rng(9)
    B, H, KV, dh, page, P, n_pages = 2, 8, 2, 64, 16, 8, 32
    q = jnp.asarray(rng.normal(0, 1, (B, H, dh)), dtype)
    kp = jnp.asarray(rng.normal(0, 1, (n_pages, page, KV, dh)), dtype)
    vp = jnp.asarray(rng.normal(0, 1, (n_pages, page, KV, dh)), dtype)
    bt = jnp.asarray(rng.permutation(np.arange(1, n_pages))[:B * P]
                     .reshape(B, P), jnp.int32)
    cl = jnp.asarray(rng.integers(1, P * page, (B,)), jnp.int32)

    want = decode_attention_paged_reference(q, kp, vp, bt, cl)
    # full-call (out, lse) pallas vs the lse oracle
    out_full, lse_full = decode_attention_paged_lse_op(
        q, kp, vp, bt, cl, force_pallas=True)
    _, lse_ref = decode_attention_paged_lse_reference(q, kp, vp, bt, cl)
    np.testing.assert_allclose(np.asarray(out_full, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(lse_full), np.asarray(lse_ref),
                               rtol=1e-5, atol=1e-5)

    # striped partials (some stripes fully masked for short rows) merge
    # back to the full result
    sp = P // n_splits
    outs, lses = [], []
    for s in range(n_splits):
        o, l = decode_attention_paged_lse_op(
            q, kp, vp, bt[:, s * sp:(s + 1) * sp],
            jnp.clip(cl - s * sp * page, 0), force_pallas=True)
        outs.append(o.astype(jnp.float32))
        lses.append(l)
    got, _ = combine_lse_partials(jnp.stack(outs), jnp.stack(lses))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_paged_decode_matches_dense_on_gathered_cache():
    """Paged oracle == dense oracle when the pool is gathered through the
    block table — the indirection is a pure relayout."""
    rng = np.random.default_rng(8)
    B, H, KV, dh, page, P, n_pages = 2, 4, 2, 64, 16, 4, 16
    q = jnp.asarray(rng.normal(0, 1, (B, H, dh)), jnp.float32)
    kp = rng.normal(0, 1, (n_pages, page, KV, dh)).astype(np.float32)
    vp = rng.normal(0, 1, (n_pages, page, KV, dh)).astype(np.float32)
    bt = rng.permutation(np.arange(1, n_pages))[:B * P].reshape(B, P)
    cl = jnp.asarray(rng.integers(1, P * page, (B,)), jnp.int32)
    tok = (bt * page)[:, :, None] + np.arange(page)
    kd = kp.reshape(-1, KV, dh)[tok.reshape(B, -1)]
    vd = vp.reshape(-1, KV, dh)[tok.reshape(B, -1)]
    got = decode_attention_paged_reference(
        q, jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(bt, jnp.int32), cl)
    want = decode_attention_reference(q, jnp.asarray(kd), jnp.asarray(vd),
                                      cl)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (2, 128, 4, 32, 16, 32),
    (1, 200, 8, 64, 32, 64),          # ragged (padding path)
    (2, 64, 2, 16, 8, 64),            # single chunk
])
def test_ssd_kernel_vs_sequential_oracle(B, S, H, P, N, chunk):
    x = jnp.asarray(RNG.normal(0, 1, (B, S, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 1.0, (B, S, H)), jnp.float32)
    a = jnp.asarray(RNG.uniform(0.5, 0.999, (B, S, H)), jnp.float32)
    bm = jnp.asarray(RNG.normal(0, 0.5, (B, S, N)), jnp.float32)
    cm = jnp.asarray(RNG.normal(0, 0.5, (B, S, N)), jnp.float32)
    got = ssd_scan_op(x, dt, a, bm, cm, chunk=chunk, force_pallas=True)
    want = ssd_reference(x, dt, a, bm, cm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_model_ssd_chunked_matches_oracle():
    """The model-side chunked scan (used by mamba2/zamba2 forward) agrees
    with the sequential recurrence too."""
    B, S, H, P, N = 2, 96, 4, 32, 16
    x = jnp.asarray(RNG.normal(0, 1, (B, S, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 1.0, (B, S, H)), jnp.float32)
    a = jnp.asarray(RNG.uniform(0.5, 0.999, (B, S, H)), jnp.float32)
    bm = jnp.asarray(RNG.normal(0, 0.5, (B, S, N)), jnp.float32)
    cm = jnp.asarray(RNG.normal(0, 0.5, (B, S, N)), jnp.float32)
    got, _ = ssd_chunked(x, dt, a, bm, cm, chunk=32)
    want = ssd_reference(x, dt, a, bm, cm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n,k", [(7, 8), (256, 32), (777, 16)])
def test_gittins_kernel_vs_numpy(n, k):
    sup = np.sort(RNG.uniform(1, 1e6, (n, k)), axis=1).astype(np.float32)
    probs = RNG.dirichlet(np.ones(k), n).astype(np.float32)
    got = gittins_op(jnp.asarray(sup), jnp.asarray(probs), force_pallas=True)
    want = gittins_index_batch(sup, probs)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5)


@pytest.mark.parametrize("pad_value", [np.inf, PAD_SUPPORT])
def test_gittins_kernel_ragged_padding_no_nan(pad_value):
    """Regression: padded columns (prob 0) used to poison the cumsum with
    inf * 0 = NaN.  The kernel must stay finite and match the oracle for
    both +inf and large-finite pads."""
    rng = np.random.default_rng(21)   # own rng: order-independent data
    n, k_real, k = 33, 6, 16
    sup = np.sort(rng.uniform(1, 1e5, (n, k_real)), axis=1)
    probs = rng.dirichlet(np.ones(k_real), n)
    sup_p = np.pad(sup, ((0, 0), (0, k - k_real)),
                   constant_values=pad_value).astype(np.float32)
    probs_p = np.pad(probs, ((0, 0), (0, k - k_real))).astype(np.float32)
    got = np.asarray(gittins_op(jnp.asarray(sup_p), jnp.asarray(probs_p),
                                force_pallas=True))
    assert np.isfinite(got).all()
    want = gittins_index_batch(sup, probs)
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_gittins_attained_op_matches_numpy():
    """The scheduler-facing op (pow2 persistent padding + conditioning)
    agrees with the float64 oracle, including exhausted rows."""
    rng = np.random.default_rng(22)   # own rng: order-independent data
    n, k = 100, 12
    sup = np.sort(rng.uniform(1, 1e5, (n, k)), axis=1)
    probs = rng.dirichlet(np.ones(k), n)
    att = rng.uniform(0, 2e5, n) * (rng.random(n) > 0.3)  # some exhausted
    got = np.asarray(gittins_attained_op(sup, probs, att,
                                         force_pallas=True))
    want = gittins_index_batch(sup, probs, att)
    np.testing.assert_allclose(got, want, rtol=1e-4)


def test_flash_kernel_jit_composes():
    """pallas_call must be jittable (interpret mode) inside larger fns."""
    q = jnp.asarray(RNG.normal(0, 1, (1, 128, 2, 64)), jnp.float32)

    @jax.jit
    def f(q):
        return flash_attention(q, q[:, :, :1], q[:, :, :1],
                               force_pallas=True).sum()

    assert np.isfinite(float(f(q)))
