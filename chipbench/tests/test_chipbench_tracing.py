"""The reduction from a profiler trace to the per-layer numbers, on a
hand-made trace whose answers are known, and the readers that use it."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import tracing, work                      # noqa: E402
from chipbench.spec import reader                        # noqa: E402

MS = 1e6   # nanoseconds


def hand_trace() -> tracing.Trace:
    """A 100 ms window on one device: two fused calls of 20 ms, one
    prefill chunk of 10 ms and its 2 ms scatter; the host steps around
    them and runs the scheduler in the gaps."""
    mods = [("jit_fused_steps(1)", 10 * MS, 30 * MS),
            ("jit_chunk(2)", 40 * MS, 50 * MS),
            ("jit_scatter(3)", 50 * MS, 52 * MS),
            ("jit_fused_steps(1)", 60 * MS, 80 * MS)]
    ops = [("fusion.1", 10 * MS, 25 * MS), ("fusion.2", 25 * MS, 30 * MS),
           ("conv.3", 40 * MS, 50 * MS), ("scatter.4", 50 * MS, 52 * MS),
           ("fusion.1", 60 * MS, 80 * MS)]
    host = [(tracing.WINDOW_SPAN, 0.0, 100 * MS),
            ("gateway.step", 5 * MS, 35 * MS),
            ("scheduler.order", 35 * MS, 40 * MS),
            ("gateway.step", 38 * MS, 85 * MS),
            ("gateway.offer", 90 * MS, 95 * MS)]
    return tracing.Trace(devices=[{tracing.MODULES_LINE: mods,
                                   tracing.OPS_LINE: ops}], host=host)


def test_union_clips_and_merges():
    got = tracing.union([("a", 0, 10), ("b", 5, 20), ("c", 30, 40),
                         ("d", 90, 120)], 2, 100)
    assert got == [(2, 20), (30, 40), (90, 100)]


def test_reduce_hand_trace():
    red = tracing.reduce(hand_trace())
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.052)        # 20 + 12 + 20 ms
    assert red["top_ops"][0] == ["fusion.1", pytest.approx(0.035)]
    idle = dict(red["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(0.1 - 0.052)
    # 0-5 ms no span, 5-10 step, 30-35 step, 35-40 scheduler (inside the
    # second step from 38 ms, but the shorter span), 52-60 step, 80-85
    # step, 85-90 none, 90-95 offer, 95-100 none
    assert idle["gateway.step"] == pytest.approx(0.005 + 0.005 + 0.008
                                                 + 0.005)
    assert idle["scheduler.order"] == pytest.approx(0.005)
    assert idle["gateway.offer"] == pytest.approx(0.005)
    assert idle["host (no span)"] == pytest.approx(0.015)


class _Rec:
    """What the trace readers take from a run's record."""

    def __init__(self, red):
        self.trace = red
        self.trace_steps = []
        self.trace_counters = ({"prefill_tokens": 0},
                               {"prefill_tokens": 2000})


def test_readers_on_hand_trace():
    rec = _Rec(tracing.reduce(hand_trace()))
    assert reader("decode_step_ms").read(rec) == pytest.approx(20.0)
    # device idle from the end of the first call to the start of the
    # second: 30-40 and 52-60 ms
    assert reader("host_gap_ms").read(rec) == pytest.approx(18.0)
    # chunk 10 ms + its scatter 2 ms over 2k prompt tokens
    assert reader("prefill_ms_per_ktok").read(rec) == pytest.approx(6.0)
    assert reader("device_idle_share").read(rec) == pytest.approx(48.0)


def test_decode_roofline_on_hand_trace():
    """Both decode calls take 20 ms on the device; the roofline is the
    mean least time of the traced steps that decoded, over 20 ms."""
    cfg = json.loads((Path(__file__).resolve().parents[1] / "configs"
                      / "qwen2-1.5b.json").read_text())
    rec = _Rec(tracing.reduce(hand_trace()))
    rec.peaks = work.peaks("TPU v5 lite")
    rec.cell = SimpleNamespace(config=cfg)
    rec.trace_steps = [SimpleNamespace(positions=[99] * 8),
                       SimpleNamespace(positions=[]),
                       SimpleNamespace(positions=[199] * 16)]
    least = []
    for pos in ([99] * 8, [199] * 16):
        f, b = work.decode_call(cfg, pos)
        least.append(max(f / 197e12, b / 819e9))
    want = 100 * (sum(least) / 2) / 0.020
    assert reader("decode_step_roofline").read(rec) == pytest.approx(want)
    assert 0 < want < 100


def test_readers_find_nothing_without_a_trace():
    rec = _Rec({})
    rec.trace = None
    for name in ("decode_step_ms", "host_gap_ms", "prefill_ms_per_ktok",
                 "device_idle_share", "decode_step_roofline"):
        assert reader(name).read(rec) is None


def test_json_round_trip(tmp_path):
    tr = hand_trace()
    tracing.dump_json(tr, str(tmp_path / "t.json"))
    back = tracing.load_json(str(tmp_path / "t.json"))
    assert tracing.reduce(back) == tracing.reduce(tr)


RECORDED = Path(__file__).resolve().parent / "data" / \
    "v5e_chat_bursty_slice.json"


def test_recorded_v5e_slice():
    """120 ms of a ``--trace 1`` run of qwen2-1.5b.chat-overload on one TPU
    v5 lite: one fused decode call at 64 lanes and one prefill chunk
    with its scatter, read as the benchmark reads every trace."""
    red = tracing.reduce(tracing.load_json(str(RECORDED)))
    assert red["window_s"] == pytest.approx(0.12)
    assert red["busy_s"] == pytest.approx(0.102456595)
    idle = sum(t for _, t in red["idle_gaps"])
    assert idle == pytest.approx(red["window_s"] - red["busy_s"])
    assert red["top_ops"][0] == ["%while.17", pytest.approx(0.054263327)]
    rec = _Rec(red)
    rec.trace_counters = ({"prefill_tokens": 0}, {"prefill_tokens": 1000})
    assert reader("decode_step_ms").read(rec) == pytest.approx(71.982225)
    assert reader("prefill_ms_per_ktok").read(rec) == \
        pytest.approx(9.002317)
    assert reader("device_idle_share").read(rec) == \
        pytest.approx(14.6195042)
    # one fused call: no gap between two of them to read
    assert reader("host_gap_ms").read(rec) is None

