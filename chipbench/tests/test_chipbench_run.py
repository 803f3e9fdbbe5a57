"""A whole run of the benchmark at a size the CPU holds: the chip check
refuses the CPU, and with the check skipped, a sound run is correct while
each fault planted in the timed path comes out not correct."""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import run as R                          # noqa: E402
from chipbench.spec import Cell, Spec                   # noqa: E402

CELL = "qwen2-1.5b.chat-overload"


class _Device:
    """Stands in for the chip: the benchmark's device check is skipped."""
    platform = "cpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return {}


def tiny_cell() -> Cell:
    """The cell's configuration and mix at a size the CPU runs in
    seconds: widths cut, and weights scaled up (std 0.2 over 64
    features) so that two layers spread the logits about as far as the
    full model's 28 do, which puts the control's gap above the limit."""
    c = Spec().cell(CELL)
    cfg = dict(c.config, hidden_size=64, intermediate_size=128,
               num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=2, vocab_size=512,
               initializer_range=0.2)
    mix = json.loads(json.dumps(c.traffic))
    mix["serve"].update(n_slots=8, max_seq_len=128, pool_tokens=None)
    for d in mix["datasets"]:
        d.update(max_input=96, input_median=40, output_median=30)
    mix.update(rate_rps=6.0, warm_s=1, history_records=100,
               greedy_share=0.5, check_min_tokens=20)
    return Cell(c.name, cfg, mix, 1, c.end_to_end, c.per_layer)


def run_tiny(seed=2 ** 31 + 5):
    return R.measure(tiny_cell(), seed, 3.0, False, [_Device()],
                     time.monotonic())


def test_refuses_a_host_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload",
         CELL, "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_sound_run_is_correct():
    res, checks = run_tiny()
    assert R.correct(checks), checks
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"output_tokens_per_s", "setup_s"}


def test_fp8_control_is_not_correct():
    """The control, the reference one precision below the program's
    bf16 (float8 e4m3 weights per output channel), put first on the
    program's own served streams, comes out not correct through the
    run's own checks, while the program's readings come out correct."""
    from chipbench import harness as H
    from chipbench import limits
    cell = tiny_cell()
    seed = 2 ** 31 + 9
    engine, _ = H.build(cell, seed)
    H.warm(engine, cell.config["vocab_size"])
    got = limits.read_seed(cell, engine, seed, 3.0, time.monotonic())
    assert got["tokens"] >= cell.traffic["check_min_tokens"]
    assert got["correct"] and not got["fp8_correct"], got
    fp8 = R.checks_for(cell, got, got["window_compiles"],
                       got["ledger_violations"], prefix="fp8_")
    assert not R.correct(fp8), fp8


def _token_altered(orig):
    def step(self, *a, **k):
        logits, cache = orig(self, *a, **k)
        return jnp.roll(logits, 1, axis=-1), cache
    return step


def _state_unchanged(orig):
    def step(self, params, token, cache, *a, **k):
        logits, _ = orig(self, params, token, cache, *a, **k)
        return logits, cache
    return step


def _half_batch(orig):
    """The first half of the batch left out: its lanes get the logits of
    the second half's.  Live lanes are packed from lane 0, so every step
    that serves a request loses it (a fault on the second half would go
    unseen whenever no more than half the lanes are live)."""
    def step(self, *a, **k):
        logits, cache = orig(self, *a, **k)
        h = logits.shape[0] // 2
        return logits.at[:h].set(logits[logits.shape[0] - h:]), cache
    return step


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _half_batch])
def test_fault_in_the_timed_path_is_not_correct(fault, monkeypatch):
    from repro.models.model import Model
    monkeypatch.setattr(Model, "decode_step_paged",
                        fault(Model.decode_step_paged))
    _, checks = run_tiny()
    assert not R.correct(checks), checks
