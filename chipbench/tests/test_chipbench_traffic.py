"""The benchmark's traffic generator: deterministic per seed, the same
work for every seed in another order."""

import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import traffic as T                      # noqa: E402
from chipbench.spec import Spec                         # noqa: E402

CELLS = [w["name"] for w in Spec().doc["workloads"]]


def _mix(cell):
    return Spec().cell(cell).traffic


@pytest.mark.parametrize("cell", CELLS)
def test_same_seed_same_inputs(cell):
    mix = _mix(cell)
    pop = T.population(mix, 30)
    a = T.assign(pop, 2 ** 31 + 12345, 151936)
    b = T.assign(T.population(mix, 30), 2 ** 31 + 12345, 151936)
    assert [(r.due_s, r.prompt, r.input_len, r.output_len, r.greedy)
            for r in a] == [(r.due_s, r.prompt, r.input_len, r.output_len,
                             r.greedy) for r in b]
    assert all(np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))


@pytest.mark.parametrize("cell", CELLS)
def test_seeds_permute_the_same_work(cell):
    mix = _mix(cell)
    pop = T.population(mix, 30)
    a = T.assign(pop, 1, 151936)
    b = T.assign(pop, -(2 ** 40), 151936)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    for seg in ("warm", "window", "tail"):
        sa = Counter((r.input_len, r.output_len, r.greedy) for r in a
                     if r.segment == seg)
        sb = Counter((r.input_len, r.output_len, r.greedy) for r in b
                     if r.segment == seg)
        assert sa == sb and sum(sa.values()) > 0
    assert [r.input_len for r in a] != [r.input_len for r in b]
    assert not np.array_equal(a[0].tokens, b[0].tokens) \
        or len(a[0].tokens) != len(b[0].tokens)


@pytest.mark.parametrize("cell", CELLS)
def test_lengths_fit_the_deployment(cell):
    mix = _mix(cell)
    cap = mix["serve"]["max_seq_len"]
    for r in T.assign(T.population(mix, 30), 7, 1000):
        assert 8 <= r.input_len and r.input_len + r.output_len <= cap - 1
        assert r.output_len >= 1
        assert r.tokens.min() >= 3 and r.tokens.max() < 1000


def test_bursts_raise_the_rate():
    mix = _mix("qwen2-1.5b.chat-overload")
    pop = T.population(mix, 200)
    period, duty = mix["burst"]["period_s"], mix["burst"]["duty"]
    inside = sum(1 for r in pop if (r.due_s % period) < duty * period)
    rate_in = inside / (duty * len(pop) / len(pop))
    share = inside / len(pop)
    # a 3x rate over 20% of the time gives 0.6 / 1.4 of the arrivals
    assert 0.35 < share < 0.5, (share, rate_in)


def test_history_is_a_disjoint_draw():
    mix = _mix("qwen2-1.5b.chat-overload")
    prompts, ins, outs = T.history_records(mix)
    assert len(prompts) == mix["history_records"] == len(ins) == len(outs)
    pop = {r.prompt for r in T.population(mix, 30)}
    assert len(pop & set(prompts)) < 0.05 * len(pop)
