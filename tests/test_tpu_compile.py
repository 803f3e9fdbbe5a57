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
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    # keep the compiler's logs out of the temp directory
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
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
