"""The host gap between fused calls split by the program's spans, and the
readers of the program's stamps and lane counter, each on a hand-made
record whose answers are worked out below."""

import math
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import host_gap, tracing                  # noqa: E402
from chipbench.spec import reader                        # noqa: E402

MS = 1e6   # nanoseconds
PARTS = ["host_gap_ms." + p for p in host_gap.PARTS]


def program_trace() -> tracing.Trace:
    """A 100 ms window on one device: three fused calls (10-30, 50-70,
    85-95 ms) and a prefill chunk with its scatter (41-43.5 ms); three
    host steps, each with the program's phase spans inside the
    harness's ``gateway.step``, and an offer between the last two.

    Gap 1 (30-50 ms, 17.5 ms idle): decode_wait 1, decode_commit 2,
    scheduler.on_progress_many 1, no span 1, gateway.tick 1,
    gateway.step alone 1, select 1, scheduler.order 1, admit 0.5,
    relieve 0.5, prefill 1.5 (around the chunk), decode_prepare 6.
    Gap 2 (70-85 ms, 15 ms idle): decode_wait 1, decode_commit 2,
    gateway.offer 2, gateway.tick 1, select 1, admit 0.5, relieve 0.5,
    prefill 0.5, decode_prepare 6.5."""
    mods = [("jit_fused_steps(1)", 10 * MS, 30 * MS),
            ("jit_chunk(2)", 41 * MS, 43 * MS),
            ("jit_scatter(3)", 43 * MS, 43.5 * MS),
            ("jit_fused_steps(1)", 50 * MS, 70 * MS),
            ("jit_fused_steps(1)", 85 * MS, 95 * MS)]
    spans = [
        (tracing.WINDOW_SPAN, 0, 100),
        ("gateway.step", 2, 34), ("gateway.tick", 2, 2.5),
        ("engine.select", 2.5, 3), ("engine.admit", 3, 3.5),
        ("engine.relieve", 3.5, 3.8), ("engine.prefill", 3.8, 4),
        ("engine.decode_prepare", 4, 10), ("engine.decode_wait", 10, 31),
        ("engine.decode_commit", 31, 34),
        ("scheduler.on_progress_many", 33, 34),
        ("gateway.step", 35, 73), ("gateway.tick", 35, 36),
        ("engine.select", 37, 39), ("scheduler.order", 37.5, 38.5),
        ("engine.admit", 39, 39.5), ("engine.relieve", 39.5, 40),
        ("engine.prefill", 40, 44), ("engine.decode_prepare", 44, 50),
        ("engine.decode_wait", 50, 71), ("engine.decode_commit", 71, 73),
        ("gateway.offer", 73, 75),
        ("gateway.step", 75, 98), ("gateway.tick", 75, 76),
        ("engine.select", 76, 77), ("engine.admit", 77, 77.5),
        ("engine.relieve", 77.5, 78), ("engine.prefill", 78, 78.5),
        ("engine.decode_prepare", 78.5, 85),
        ("engine.decode_wait", 85, 96), ("engine.decode_commit", 96, 98)]
    host = sorted(((n, s * MS, e * MS) for n, s, e in spans),
                  key=lambda x: x[1])
    return tracing.Trace(devices=[{tracing.MODULES_LINE: mods,
                                   tracing.OPS_LINE: mods}], host=host)


class _Rec:
    """What the readers take from a run's record: the reduced slice,
    with the slice's host spans under ``host``."""

    def __init__(self, tr, with_host=True):
        self.trace = tracing.reduce(tr)
        if with_host:
            self.trace["host"] = tr.host
        else:
            self.trace.pop("host", None)
        self.trace_steps = []
        self.trace_counters = ()


def test_parts_by_hand():
    rec = _Rec(program_trace())
    assert reader("host_gap_ms").read(rec) == pytest.approx(16.25)
    want = {"prepare": (6 + 6.5) / 2, "commit": (2 + 2) / 2,
            # select, order, on_progress_many, admit, relieve; then
            # select, admit, relieve
            "schedule": (1 + 1 + 1 + 0.5 + 0.5 + 1 + 0.5 + 0.5) / 2,
            "prefill": (1.5 + 0.5) / 2}
    for part, ms in want.items():
        assert reader(f"host_gap_ms.{part}").read(rec) == pytest.approx(ms)
    # gateway.step alone 1 ms and no span 1 ms, of 32.5 ms
    assert reader("host_gap_unattributed_share").read(rec) == \
        pytest.approx(100 * 2 / 32.5)


def _partition(rec):
    """The four parts, the other named spans and the unattributed idle,
    each as mean ms per gap, and ``host_gap_ms``."""
    by_span, n = host_gap.idle_by_span(rec)
    parts = sum(reader(p).read(rec) for p in PARTS)
    claimed = [s for s in by_span if host_gap._in(s, sum(
        host_gap.PARTS.values(), ()) + host_gap.UNATTRIBUTED)]
    others = sum(t for s, t in by_span.items() if s not in claimed) \
        / n * 1e-6
    share = reader("host_gap_unattributed_share").read(rec) / 100
    total = reader("host_gap_ms").read(rec)
    return parts, others, share * total, total


def test_parts_others_and_unattributed_make_host_gap():
    parts, others, unattributed, total = _partition(_Rec(program_trace()))
    # decode_wait, gateway.tick and gateway.offer: (2 + 2 + 2) / 2 ms
    assert others == pytest.approx(3.0)
    assert parts + others + unattributed == pytest.approx(total,
                                                          rel=1e-9)


def test_whole_slice_names_the_program_phases():
    """``tracing.reduce`` names the slice's idle time by the innermost
    span, the program's phases among them, and loses none of it."""
    red = tracing.reduce(program_trace())
    idle = dict(red["idle_gaps"])
    assert idle["engine.decode_prepare"] == pytest.approx(0.006 + 0.0065
                                                          + 0.006)
    assert sum(t for _, t in tracing.reduce(program_trace(), top=99)
               ["idle_gaps"]) == pytest.approx(red["window_s"]
                                               - red["busy_s"])


def test_gap_readers_find_nothing_without_program_spans():
    """Without the slice's host spans, as the harness records it today,
    and without a trace at all, each gap reader returns None."""
    for rec in (_Rec(program_trace(), with_host=False),
                SimpleNamespace(trace=None)):
        for name in PARTS + ["host_gap_unattributed_share"]:
            assert reader(name).read(rec) is None


def _served(due, submitted=math.nan, admitted=math.nan, stamps=True):
    sr = SimpleNamespace(submitted=submitted, admitted=admitted) if stamps \
        else SimpleNamespace()
    return SimpleNamespace(due=due, sr=sr)


def _stamped_rec(reqs, end=100.0):
    return SimpleNamespace(window_reqs=lambda: reqs, end=end)


def test_wait_readers_by_hand():
    """Gateway wait: due to submit, or to the run's end if never
    submitted; engine wait: submit to first slot, or to the end if
    never admitted, over submitted requests only."""
    reqs = [_served(10.0, 10.5, 11.0), _served(20.0, 20.0, 26.0),
            _served(30.0, 34.0), _served(40.0)]
    rec = _stamped_rec(reqs)
    gw = [0.5, 0.0, 4.0, 60.0]
    eng = [0.5, 6.0, 66.0]
    assert reader("gateway_wait_p95_s").read(rec) == pytest.approx(
        sorted(gw)[2] + 0.85 * (sorted(gw)[3] - sorted(gw)[2]))
    assert reader("engine_wait_p95_s").read(rec) == pytest.approx(
        6.0 + 0.9 * (66.0 - 6.0))


def test_wait_readers_find_nothing_without_stamps():
    """A program without the stamps (as before they existed) leaves the
    metrics out rather than raising."""
    rec = _stamped_rec([_served(1.0, stamps=False)])
    for name in ("gateway_wait_p95_s", "engine_wait_p95_s"):
        assert reader(name).read(rec) is None
        assert reader(name).read(_stamped_rec([])) is None


def test_decode_lanes_per_call():
    rec = SimpleNamespace(
        counters0={"fused_steps": 10, "decode_lanes": 500},
        counters1={"fused_steps": 30, "decode_lanes": 1780})
    assert reader("decode_lanes_per_call").read(rec) == pytest.approx(64.0)
    rec.counters1 = {"fused_steps": 10, "decode_lanes": 500}
    assert reader("decode_lanes_per_call").read(rec) is None
    # the harness's counters as they are: no decode_lanes
    rec = SimpleNamespace(counters0={"fused_steps": 1},
                          counters1={"fused_steps": 2})
    assert reader("decode_lanes_per_call").read(rec) is None


RECORDED = Path(__file__).resolve().parent / "data" / \
    "v5e_chat_bursty_slice.json"


def test_old_slice_keeps_every_existing_value():
    """The slice recorded before the program had spans reads as it did:
    every trace reader the benchmark lists gives the same value, and
    the gap readers find no gap to split."""
    red = tracing.reduce(tracing.load_json(str(RECORDED)))
    rec = _Rec(tracing.load_json(str(RECORDED)))
    rec.trace_counters = ({"prefill_tokens": 0}, {"prefill_tokens": 1000})
    assert rec.trace["busy_s"] == pytest.approx(0.102456595)
    assert {k: v for k, v in rec.trace.items() if k != "host"} == \
        {k: v for k, v in red.items() if k != "host"}
    assert reader("decode_step_ms").read(rec) == pytest.approx(71.982225)
    assert reader("prefill_ms_per_ktok").read(rec) == \
        pytest.approx(9.002317)
    assert reader("device_idle_share").read(rec) == \
        pytest.approx(14.6195042)
    assert reader("host_gap_ms").read(rec) is None
    for name in PARTS + ["host_gap_unattributed_share"]:
        assert reader(name).read(rec) is None


SPANS = Path(__file__).resolve().parent / "data" / \
    "v5e_chat_overload_spans_slice.json"


def test_recorded_slice_with_program_spans():
    """161 ms of a ``--trace 1`` run of qwen2-1.5b.chat-overload on one
    TPU v5 lite, with the program's spans: two fused decode calls (the
    run averaged 61.7 lanes a call) and the 15.4 ms gap between them,
    the gap nearest the median of its 3 s slice, with no prefill in
    it.  The existing readers give what the
    parent's code gives, and the gap splits into the program's phases
    with 10.8% left under ``gateway.step`` alone or no span."""
    rec = _Rec(tracing.load_json(str(SPANS)))
    rec.trace_counters = ({"prefill_tokens": 0}, {"prefill_tokens": 1000})
    assert rec.trace["window_s"] == pytest.approx(0.161380039)
    assert rec.trace["busy_s"] == pytest.approx(0.143987498)
    assert rec.trace["top_ops"][0] == ["%while.17",
                                       pytest.approx(0.108543556)]
    assert reader("decode_step_ms").read(rec) == pytest.approx(71.9941265)
    assert reader("host_gap_ms").read(rec) == pytest.approx(15.391786)
    assert reader("device_idle_share").read(rec) == \
        pytest.approx(10.7773806)
    assert reader("prefill_ms_per_ktok").read(rec) is None
    want = {"prepare": 6.497391, "commit": 1.58463,
            "schedule": 2.21819 + 0.01525 + 0.00362 + 0.56737 + 0.10171,
            "prefill": 0.05015}
    for part, ms in want.items():
        assert reader(f"host_gap_ms.{part}").read(rec) == pytest.approx(ms)
    assert reader("host_gap_unattributed_share").read(rec) == \
        pytest.approx(100 * (0.98498 + 0.67789) / 15.391786)
    parts, others, unattributed, total = _partition(rec)
    # engine.decode_wait 2.035975 ms, gateway.tick 0.65463 ms
    assert others == pytest.approx(2.035975 + 0.65463)
    assert parts + others + unattributed == pytest.approx(total,
                                                          rel=1e-9)
    idle = dict(rec.trace["idle_gaps"])
    assert max(idle, key=idle.get) == "engine.decode_prepare"
