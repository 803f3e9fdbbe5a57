"""``work.py`` against hand counts for qwen2-1.5b, and the peaks table."""

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import work                              # noqa: E402

CFG = json.loads((Path(__file__).resolve().parents[1] / "configs"
                  / "qwen2-1.5b.json").read_text())

# by hand: per layer q 1536x1536, k and v 1536x256 each, o 1536x1536,
# biases 1536 + 2 x 256, MLP 3 x 1536 x 8960, two norms of 1536;
# 28 layers, a tied 151936 x 1536 embedding and the final norm
LAYER = 1536 * 1536 * 2 + 1536 * 256 * 2 + 2048 + 3 * 1536 * 8960 + 2 * 1536
PARAMS = 28 * LAYER + 151936 * 1536 + 1536


def test_param_count():
    assert PARAMS == 1_543_714_304
    assert work.param_count(CFG) == PARAMS


def test_kv_bytes_per_token():
    # k and v, 28 layers, 2 kv heads of 128, bf16
    assert work.kv_bytes_per_token(CFG) == 2 * 28 * 2 * 128 * 2 == 28_672


def test_token_flops():
    mat = 28 * (LAYER - 2048 - 2 * 1536)        # matmul weights only
    attn = 4 * 28 * 12 * 128                    # QK and PV per key
    assert work.token_flops(CFG, 0, logits=False) == 2 * mat + attn
    assert work.token_flops(CFG, 99) == 2 * mat + 100 * attn \
        + 2 * 1536 * 151936
    # a prompt's positions sum exactly
    assert work.prompt_flops(CFG, 3, 10) == sum(
        work.token_flops(CFG, p, logits=False) for p in range(3, 10))


def test_decode_call_bytes():
    f, b = work.decode_call(CFG, [9, 99])
    weights = 2 * (28 * (LAYER - 2048 - 2 * 1536) + 1536 * 151936)
    small = 4 * (28 * 2 * 1536 + 1536 + 28 * 2048)
    assert b == weights + small + 2 * 1536 * 2 + 28_672 * (10 + 100)
    assert f == work.token_flops(CFG, 9) + work.token_flops(CFG, 99)


def test_peaks_keyed_by_device_kind():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")
