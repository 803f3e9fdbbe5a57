"""The Pallas kernels compiled for a TPU v5e at real widths, without the
chip: the TPU compiler is installed here and compiles for a described
topology.  A compile that passes is not a run; it shows the kernel is
not refused (tiling, memory spaces, unsupported primitives) before any
chip time is spent.

Only one process may load the TPU library at a time, so the topology is
described inside a module fixture — never at import — and all of these
tests live in this one file.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.decode_attention.kernel import (
    decode_attention_paged_kernel, decode_attention_paged_lse_kernel)
from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.gittins.kernel import gittins_kernel
from repro.kernels.ssd_scan.kernel import ssd_scan_kernel


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # keep the compiler's logs out of the temp directory
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("k", [8, 256])   # the scheduler's bucket range
def test_gittins_compiles_for_v5e(one_chip, k):
    hlo = _compile(gittins_kernel, one_chip,
                   ((1024, k), jnp.float32), ((1024, k), jnp.float32))
    assert "tpu_custom_call" in hlo


# (H, KV) at qwen2-1.5b and olmoe-1b-7b widths; dh 128, page 16
PAGED = [pytest.param(12, 2, id="qwen2-1.5b"),
         pytest.param(16, 16, id="olmoe-1b-7b")]


@pytest.mark.parametrize("lse", [False, True], ids=["out", "out+lse"])
@pytest.mark.parametrize("h,kv", PAGED)
def test_paged_decode_compiles_for_v5e(one_chip, h, kv, lse):
    kernel = decode_attention_paged_lse_kernel if lse \
        else decode_attention_paged_kernel
    b, dh, page, n_pages, logical = 8, 128, 16, 1024, 64
    hlo = _compile(kernel, one_chip,
                   ((b, h, dh), jnp.bfloat16),
                   ((n_pages, page, kv, dh), jnp.bfloat16),
                   ((n_pages, page, kv, dh), jnp.bfloat16),
                   ((b, logical), jnp.int32), ((b,), jnp.int32))
    assert "tpu_custom_call" in hlo


# the qwen2-1.5b.chat-overload cell's top rung: 64 lanes, 256 table
# pages of 16 tokens, a pool of 196,608 tokens (12,288 pages)
RUNG_B, RUNG_P, POOL_PAGES = 64, 256, 196_608 // 16


@pytest.mark.parametrize("h,kv", PAGED)
def test_paged_decode_compiles_at_top_rung_for_v5e(one_chip, h, kv):
    hlo = _compile(decode_attention_paged_kernel, one_chip,
                   ((RUNG_B, h, 128), jnp.bfloat16),
                   ((POOL_PAGES, 16, kv, 128), jnp.bfloat16),
                   ((POOL_PAGES, 16, kv, 128), jnp.bfloat16),
                   ((RUNG_B, RUNG_P), jnp.int32), ((RUNG_B,), jnp.int32))
    assert "tpu_custom_call" in hlo
    # the pools reach the kernel where they lie: no copy of either
    assert f"bf16[{POOL_PAGES},16,{kv},128]{{3,2,1,0:T(2,128)(2,1)}} copy" \
        not in hlo


def test_paged_decode_per_shard_compiles_for_v5e_2x2(topo, monkeypatch):
    """olmoe-1b-7b's decode attention at the top rung under a tp=4 mesh
    plan, as four chips would trace it (the backend reported as TPU):
    the kernel runs per shard, on 4 of the 16 KV heads of each page."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.models.attention import decode_attention_paged
    from repro.sharding.context import serving_sharding

    mesh = Mesh(np.array(topo.devices), ("model",))
    pool = NamedSharding(mesh, P(None, None, None, "model", None))

    def step(q, k, v, bt, cl):
        with serving_sharding(lambda x: x, pool_sharding=pool):
            return decode_attention_paged(q, k, v, bt, cl)

    def spec(shape, dtype, *axes):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(*axes)))

    pages = (POOL_PAGES, 16, 16, 128)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hlo = jax.jit(step).lower(
        spec((RUNG_B, 1, 16, 128), jnp.bfloat16),
        spec(pages, jnp.bfloat16, None, None, "model", None),
        spec(pages, jnp.bfloat16, None, None, "model", None),
        spec((RUNG_B, RUNG_P), jnp.int32),
        spec((RUNG_B,), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert f"bf16[{POOL_PAGES},16,4,128]" in hlo
    assert "all-gather" not in hlo


def test_fused_decode_step_runs_paged_kernel_for_v5e(one_chip, monkeypatch):
    """The engine's fused decode step at the cell's top rung, lowered
    as the chip would trace it (the backend reported as TPU): decode
    attention is the Pallas kernel and no lane's whole padded table is
    gathered.  Two of qwen2-1.5b's 28 layers keep the compile short;
    every width is the published one."""
    from repro.configs import get_config
    from repro.core import Scheduler, make_policy
    from repro.models import build_model
    from repro.serving import ServingEngine

    cfg = get_config("qwen2-1.5b").with_overrides(n_layers=2)
    model = build_model(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    # a small engine: its own pool is never used, the lowering takes the
    # rung's shapes
    eng = ServingEngine(model=model,
                        scheduler=Scheduler(policy=make_policy("fcfs")),
                        n_slots=8, max_seq_len=RUNG_P * 16,
                        capacity_tokens=256, params=params)
    pool = (cfg.n_layers, POOL_PAGES, 16, cfg.n_kv_heads, cfg.head_dim)

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hlo = eng._fused_fn.lower(
        jax.tree.map(spec, params),
        {"k": spec(jax.ShapeDtypeStruct(pool, jnp.bfloat16)),
         "v": spec(jax.ShapeDtypeStruct(pool, jnp.bfloat16))},
        *map(spec, eng._fused_lanes(RUNG_B, RUNG_P)),
        n_steps=1, all_greedy=False).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert f"bf16[{RUNG_B},{RUNG_P * 16},{cfg.n_kv_heads},128]" not in hlo


def test_flash_prefill_compiles_for_v5e(one_chip):
    # qwen2-1.5b: 12 query heads grouped over 2 kv heads, 1024 tokens
    s, dh = 1024, 128
    hlo = _compile(lambda q, k, v: flash_attention_kernel(q, k, v),
                   one_chip, ((12, s, dh), jnp.bfloat16),
                   ((2, s, dh), jnp.bfloat16), ((2, s, dh), jnp.bfloat16))
    assert "tpu_custom_call" in hlo


def test_ssd_scan_is_refused_for_v5e(one_chip):
    """The SSD scan kernel is on no serving path and Mosaic has no
    ``cumsum`` lowering, so the TPU compiler refuses it (ROADMAP C2).
    This pins that finding: when the kernel is repaired or deleted, this
    test goes with it."""
    # mamba2-2.7b: 80 heads x 64, state 128, one 256-token chunk
    with pytest.raises(NotImplementedError, match="cumsum"):
        _compile(lambda *a: ssd_scan_kernel(*a, chunk=256), one_chip,
                 ((1, 256, 80, 64), jnp.bfloat16),
                 ((1, 256, 80), jnp.float32), ((1, 256, 80), jnp.float32),
                 ((1, 256, 128), jnp.bfloat16), ((1, 256, 128), jnp.bfloat16))
