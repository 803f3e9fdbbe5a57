"""The serving path's own observability: ``engine.*`` and
``gateway.tick`` profiler spans, one per phase per step; the
``submitted``/``admitted`` stamps on each request; the ``decode_lanes``
counter; the summary's wait quantiles; and re-lowering the fused step
from what the engine keeps of its last call."""

import glob
import math
import os

import jax
import numpy as np
import pytest
from jax.profiler import TraceAnnotation

from repro.configs import get_config
from repro.core import (LengthDistribution, OraclePredictor, Scheduler,
                        make_policy)
from repro.models import build_model
from repro.serving import (Gateway, GatewayConfig, RequestState, ServeRequest,
                           ServingEngine)
from repro.testing import VirtualClock

CFG = get_config("llama3.2-1b", reduced=True)
STEP_PHASES = ("engine.select", "engine.admit", "engine.relieve",
               "engine.prefill")
DECODE_PHASES = ("engine.decode_prepare", "engine.decode_wait",
                 "engine.decode_commit")


def _reqs(n, *, max_new=6, seed=0, prefix="q"):
    rng = np.random.default_rng(seed)
    return [ServeRequest(
        request_id=f"{prefix}{i}", prompt=f"p{i}",
        prompt_tokens=[int(t) for t in rng.integers(
            3, CFG.vocab_size, int(rng.integers(6, 14)))],
        max_new_tokens=max_new + 3 * i, temperature=0.0, eos_token=1,
        arrival=float(i) * 1e-3) for i in range(n)]


def _host_spans(logdir):
    """(name, start_ns, end_ns, stats) of every ``engine.``/``gateway.``
    host event in the profile written under ``logdir``."""
    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    assert files, "the profiler wrote no trace"
    pd = jax.profiler.ProfileData.from_file(files[0])
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.split(".")[0] in ("engine", "gateway"):
                    out.append((e.name, e.start_ns, e.end_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda x: x[1])


def test_phase_spans_once_per_step_in_order(tmp_path):
    """Served through Gateway -> ServingEngine under the profiler, every
    step holds ``gateway.tick`` and the four step phases once each, then
    the three decode phases once when a lane decodes, in that order and
    inside the step's own span (the last step, whose tick only reaps,
    holds the tick alone); the spans carry their stats."""
    eng = ServingEngine(model=build_model(CFG),
                        scheduler=Scheduler(policy=make_policy("fcfs")),
                        n_slots=2, max_seq_len=96, seed=0)
    gw = Gateway(eng, GatewayConfig(max_inflight=4))
    gw.offer_batch(_reqs(3))
    gw.step()                      # compile outside the profile
    m = eng.metrics
    chunks0, tokens0, lanes0 = m.prefill_chunks, m.prefill_tokens, \
        m.decode_lanes
    n_steps = 0
    with jax.profiler.trace(str(tmp_path)):
        while not gw.drained:
            with TraceAnnotation("gateway.step"):
                gw.step()
            n_steps += 1
    spans = _host_spans(str(tmp_path))
    steps = [s for s in spans if s[0] == "gateway.step"]
    assert len(steps) == n_steps > 3
    head = ("gateway.tick",) + STEP_PHASES
    decoded = 0
    for i, (_, lo, hi, _) in enumerate(steps):
        inside = [s for s in spans if lo <= s[1] and s[2] <= hi
                  and s[0] != "gateway.step"]
        names = tuple(s[0] for s in inside)
        if i == len(steps) - 1 and names == head[:1]:
            continue                   # the tick that reaped the last one
        assert names[:len(head)] == head
        if names[len(head):]:
            assert names[len(head):] == DECODE_PHASES
            decoded += 1
            prep = inside[len(head)][3]
            assert (prep["nb"], prep["pb"]) == (2, 4)   # n_slots, floor
            assert 1 <= prep["lanes"] <= 2
        for a, b in zip(inside, inside[1:]):
            assert a[2] <= b[1]            # one after another, no overlap
    assert decoded >= n_steps - 1
    # the stats count what the profiled steps ran
    pre = [s[3] for s in spans if s[0] == "engine.prefill"]
    assert sum(p["chunks"] for p in pre) == m.prefill_chunks - chunks0 > 0
    assert sum(p["tokens"] for p in pre) == m.prefill_tokens - tokens0
    assert sum(s[3]["lanes"] for s in spans
               if s[0] == "engine.decode_prepare") == \
        m.decode_lanes - lanes0


def _preempting_engine(clock, mode="recompute", step_mode="fused"):
    o = OraclePredictor()
    for i in range(6):
        o.register(f"p{i}", LengthDistribution(np.array([8 + 3 * i]),
                                               np.array([1.0])))
    return ServingEngine(
        model=build_model(CFG),
        scheduler=Scheduler(policy=make_policy("sagesched"), predictor=o),
        n_slots=2, max_seq_len=96, capacity_tokens=56, block_size=8,
        preemption_mode=mode, step_mode=step_mode, seed=0, clock=clock)


@pytest.mark.parametrize("mode", ["recompute", "swap"])
def test_stamps_first_admission_survives_preemption(mode):
    """``submitted`` is the engine's clock at ``submit_batch``;
    ``admitted`` the clock of the step that first bound the request to a
    slot, kept when a preempted request is readmitted."""
    clock = VirtualClock(start=100.0)
    eng = _preempting_engine(clock, mode)
    reqs = _reqs(6, max_new=8, seed=3, prefix="r")
    for r in reqs:
        assert math.isnan(r.submitted) and math.isnan(r.admitted)
    eng.submit_batch(reqs)
    assert all(r.submitted == 100.0 for r in reqs)
    binds = []                     # (rid, clock) of every slot binding
    bind = eng._bind_slot

    def logged(r, slot):
        binds.append((r.request_id, clock()))
        bind(r, slot)
    eng._bind_slot = logged
    for _ in range(5000):
        if not eng.has_work:
            break
        clock.advance(1.0)
        eng.step()
    assert not eng.has_work
    assert eng.metrics.preemptions > 0
    rebound = [rid for rid in {b[0] for b in binds}
               if sum(b[0] == rid for b in binds) > 1]
    assert rebound, "scenario must readmit a preempted request"
    for r in reqs:
        assert r.state == RequestState.FINISHED
        first = next(t for rid, t in binds if rid == r.request_id)
        assert r.admitted == first
        assert r.submitted < r.admitted
    # waits in the summary come from the same stamps
    s = eng.metrics.summary(reqs)
    waits = np.array([r.admitted - r.submitted for r in reqs])
    assert s["p50_engine_wait_s"] == pytest.approx(np.quantile(waits, 0.5))
    assert s["p95_engine_wait_s"] == pytest.approx(np.quantile(waits, 0.95))
    gw = np.array([r.submitted - r.arrival for r in reqs])
    assert s["p95_gateway_wait_s"] == pytest.approx(np.quantile(gw, 0.95))


def test_gateway_queue_wait_is_submitted_minus_arrival():
    """A request the gateway queues is submitted when the pump lets it
    in, so its gateway wait is the time it sat in the queue."""
    clock = VirtualClock(start=10.0)
    eng = ServingEngine(model=build_model(CFG),
                        scheduler=Scheduler(policy=make_policy("fcfs")),
                        n_slots=1, max_seq_len=96, seed=0, clock=clock)
    gw = Gateway(eng, GatewayConfig(max_inflight=1), clock=clock)
    a, b = _reqs(2, max_new=4)
    a.arrival = b.arrival = clock()
    gw.offer_batch([a, b])
    assert a.submitted == 10.0 and math.isnan(b.submitted)
    gw.run_until_drained(max_steps=500, step_dt=0.5)
    assert a.admitted == 10.0
    assert b.submitted > 10.0 and b.admitted >= b.submitted
    s = eng.metrics.summary([a, b])
    assert s["p50_gateway_wait_s"] == pytest.approx(
        (b.submitted - 10.0) / 2)


def test_summary_waits_without_stamps_are_nan():
    eng = ServingEngine(model=build_model(CFG),
                        scheduler=Scheduler(policy=make_policy("fcfs")),
                        n_slots=1, max_seq_len=96, seed=0)
    s = eng.metrics.summary(_reqs(2))
    assert s["completed"] == 0
    for k in ("p50_gateway_wait_s", "p95_gateway_wait_s",
              "p50_engine_wait_s", "p95_engine_wait_s"):
        assert math.isnan(s[k])


@pytest.mark.parametrize("step_mode", ["fused", "orchestrated"])
def test_decode_lanes_counts_ready_lanes(step_mode):
    """``decode_lanes`` adds the ready lanes of every fused call (every
    orchestrated iteration): with one token per call it equals the
    tokens decoded."""
    eng = ServingEngine(model=build_model(CFG),
                        scheduler=Scheduler(policy=make_policy("fcfs")),
                        n_slots=2, max_seq_len=96, seed=0,
                        step_mode=step_mode)
    reqs = _reqs(3)
    eng.submit_batch(reqs)
    eng.run_until_done()
    m = eng.metrics
    calls = m.fused_steps if step_mode == "fused" else m.decode_iterations
    assert calls > 0
    assert m.decode_lanes == m.decode_tokens
    assert calls < m.decode_lanes <= 2 * calls
    assert m.summary(reqs)["decode_lanes"] == m.decode_lanes


def test_fused_call_keeps_shapes_only_and_relowers():
    """What the engine keeps of a fused call is its lane and page
    buckets and static args, no arrays; ``lower_fused_hlo`` rebuilds the
    abstract call from the engine's own params and pool."""
    eng = ServingEngine(model=build_model(CFG),
                        scheduler=Scheduler(policy=make_policy("fcfs")),
                        n_slots=2, max_seq_len=96, seed=0)
    assert eng.lower_fused_hlo() is None
    eng.submit_batch(_reqs(2))
    while eng.metrics.fused_steps == 0:
        eng.step()
    nb, pb, static = eng._last_fused_call
    assert (nb, pb) == (2, 4)
    assert static == {"n_steps": 1, "all_greedy": True}
    hlo = eng.lower_fused_hlo()
    assert "while" in hlo
    # the re-lowered call matches the served one: no new compile
    n = eng.fused_compile_count
    eng.run_until_done()
    assert eng.fused_compile_count == n
