"""One run of one cell: build the served path, warm it, offer the mix as
an open loop, measure a window, check what it served.

The served path is the program's own: ``Gateway(ServingEngine(model,
Scheduler(policy="sagesched")))`` as ``repro.launch.serve.start`` builds
it, with the engine's defaults for everything the deployment does not
state.  Weights come from the benchmark's reference module, made on the
device from ``--seed``.  Each request is offered through
``Gateway.offer_batch`` once its due time has passed, stamped with the
due time as its arrival; ``Gateway.step`` runs in between, and after
every step the harness stamps each new output token with the host clock.
"""

from __future__ import annotations

import math
import shutil
import sys
import time
from dataclasses import dataclass, field

import numpy as np

from chipbench.spec import REPO, Cell
from chipbench import traffic as T
from chipbench import work

sys.path.insert(0, str(REPO / "src"))

__all__ = ["RunRecord", "Served", "build", "gateway_for", "warm",
           "install_scheduler", "serve", "finish_run", "sample_for_check"]

SCHED_CALLS = ("order", "refresh", "admit_batch", "on_progress_many",
               "eviction_order")
# requests at this temperature are sampled by the fused step's sampler,
# which then returns the argmax: bf16 logits that differ differ by at
# least 2**-8 of their size, a million times the Gumbel noise's reach at
# 1e-6, so only exact ties are broken at random.  Every lane then shares
# the mixed-sampling program (no all-greedy specialization to warm).
GREEDY_TEMPERATURE = 1e-6
GRACE_S = 30.0
# the engine bakes its sampling key into the fused step as a constant, so
# a per-run engine seed would make every run compile that step anew; the
# sampled streams still differ by request, prompt and weights
ENGINE_SEED = 0
OUT_DIR = REPO / ".chipbench_out"


@dataclass
class Served:
    """One offered request as the harness saw it."""
    tr: T.TrafficRequest
    sr: object                       # the program's ServeRequest
    due: float                       # host clock
    running: float = math.nan        # first observed RUNNING
    times: list = field(default_factory=list)   # one stamp per token
    prefilled: int = 0               # prompt positions counted as work


@dataclass
class Step:
    t0: float
    t1: float
    positions: list                  # position of each token emitted
    prompt_flops: int
    used_blocks: int
    sched_s: float


@dataclass
class RunRecord:
    cell: Cell
    seed: int
    seconds: float
    device_kind: str
    peaks: dict
    setup_s: float = 0.0
    w0: float = 0.0
    w1: float = 0.0
    end: float = 0.0
    reqs: list = field(default_factory=list)
    steps: list = field(default_factory=list)
    n_blocks: int = 1
    counters0: dict = field(default_factory=dict)
    counters1: dict = field(default_factory=dict)
    window_compiles: int = 0
    lateness: list = field(default_factory=list)
    trace: dict | None = None
    trace_steps: list = field(default_factory=list)   # Step per traced span
    trace_counters: tuple = ()

    def window_reqs(self) -> list:
        return [r for r in self.reqs if r.tr.segment == "window"]

    def window_steps(self) -> list:
        return [s for s in self.steps if self.w0 <= s.t0 and s.t1 <= self.w1]


class CompileCounter:
    """Counts the programs JAX obtained, compiled or loaded from the
    persistent cache, from its own monitoring events: one inside the
    window means a shape was not warmed."""

    def __init__(self):
        import jax.monitoring as mon
        self.n = 0           # every program obtained
        self.loaded = 0      # of those, found in the persistent cache

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.loaded += 1
        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    @property
    def compiled(self) -> int:
        return self.n - self.loaded


class SchedTimer:
    """Host seconds inside the scheduler's per-step calls, timed at the
    outermost call only, each wrapped in a ``scheduler.<call>`` span."""

    def __init__(self, sched, annotate):
        self.total = 0.0
        self._depth = 0
        for name in SCHED_CALLS:
            fn = getattr(sched, name, None)
            if fn is not None:
                setattr(sched, name, self._wrap(name, fn, annotate))

    def _wrap(self, name, fn, annotate):
        span = f"scheduler.{name}"

        def timed(*a, **k):
            if self._depth:
                return fn(*a, **k)
            self._depth = 1
            t = time.perf_counter()
            try:
                with annotate(span):
                    return fn(*a, **k)
            finally:
                self.total += time.perf_counter() - t
                self._depth = 0
        return timed


def counters(engine) -> dict:
    m = engine.metrics
    return {k: getattr(m, k) for k in (
        "prefill_tokens", "decode_tokens", "fused_steps", "preemptions",
        "swap_outs", "swap_ins", "shed", "aborted", "completed")}


# ------------------------------------------------------------------ build

def build(cell: Cell, seed: int):
    """Weights from the seed, and the program's engine and gateway over
    them.  The engine's scheduler here only serves the shape warm-up;
    ``install_scheduler`` replaces it before traffic."""
    from repro.core import Scheduler, make_policy
    from repro.models import build_model
    from repro.serving import ServingEngine

    ref, prog = cell.reference(), cell.program()
    cfg, serve = cell.config, cell.traffic["serve"]
    pcfg = prog.model_config(cfg)
    params = prog.params(ref.make_weights(cfg, seed, prog.vocab_rows(cfg)))
    engine = ServingEngine(
        model=build_model(pcfg),
        scheduler=Scheduler(policy=make_policy("sagesched")),
        n_slots=serve["n_slots"], max_seq_len=serve["max_seq_len"],
        capacity_tokens=serve.get("pool_tokens"), params=params,
        preemption_mode=serve["preemption_mode"], seed=ENGINE_SEED)
    return engine, gateway_for(engine)


def gateway_for(engine):
    """A fresh gateway over ``engine`` with the launcher's bounds."""
    from repro.serving import Gateway, GatewayConfig
    return Gateway(engine, GatewayConfig(
        max_queue_per_tenant=64, max_total_queue=256, shed_policy="cost",
        max_retries=2))


def install_scheduler(engine, cell: Cell) -> None:
    """A fresh SageSched scheduler whose predictor remembers a disjoint
    draw of the cell's own mix, as a deployment that has been serving
    this traffic would (the warm-up's requests leave no history)."""
    from repro.core import Scheduler, make_policy
    sched = Scheduler(policy=make_policy("sagesched"))
    prompts, ins, outs = T.history_records(cell.traffic)
    if prompts:
        sched.predictor.seed(prompts, ins, outs)
    engine.scheduler = sched


def _request(i: int, prompt: str, tokens, n_out: int, temperature: float):
    from repro.serving import ServeRequest
    return ServeRequest(
        request_id=f"r{i:06d}", prompt=prompt,
        prompt_tokens=[int(t) for t in tokens], max_new_tokens=int(n_out),
        temperature=temperature, eos_token=-1)


def warm(engine, vocab: int) -> None:
    """Run every fused-decode and prefill shape the cell can reach, through
    the engine's own step: for each table-width rung P and batch rung B,
    3B/4 lanes of which one holds a 3P/4-page prompt, one new token each.
    Preemption recomputes (the cells' ``preemption_mode``), so a
    preempted request comes back through the same prefill rungs."""
    from repro.kernels.bucketing import pow2_bucket

    page = engine.block_size
    max_pages = -(-engine.max_seq_len // page)
    b_rungs = sorted({pow2_bucket(n, 8, engine.n_slots)
                      for n in range(1, engine.n_slots + 1)})
    p_rungs = sorted({pow2_bucket(n, 4, max_pages)
                      for n in range(1, max_pages + 1)})
    rng = np.random.default_rng(0)
    k = 0
    for p in p_rungs:
        long_len = min(3 * p * page // 4, engine.max_seq_len - 2)
        for b in b_rungs:
            lanes = min(max(1, 3 * b // 4), engine.n_slots)
            reqs = []
            for j in range(lanes):
                n = long_len if j == 0 else 8
                reqs.append(_request(
                    10 ** 6 + k, "warm up the engine shapes",
                    rng.integers(3, vocab, n), 1, 0.6))
                k += 1
            engine.submit_batch(reqs)
            while engine.has_work:
                engine.step()
    print(f"warm: {len(p_rungs)} table rungs x {len(b_rungs)} batch rungs "
          f"served; fused compiles {engine.fused_compile_count}",
          file=sys.stderr, flush=True)


# ------------------------------------------------------------------ serve

def serve(cell: Cell, seed: int, seconds: float, rec: RunRecord, engine,
          gateway, reqs: list, *, trace_slice: float = 0.0,
          annotate=None, t_proc0: float = 0.0,
          grace_s: float = GRACE_S, compiles=None) -> None:
    """The open loop: warm traffic, the window, then a grace period in
    which the loop keeps offering until every window request has its
    first token (at most ``grace_s``)."""
    import jax
    from repro.serving import RequestState

    mix = cell.traffic
    cfg = cell.config
    sched_timer = SchedTimer(engine.scheduler, annotate) \
        if annotate is not None else None
    ann = annotate or _no_span
    cc = compiles or CompileCounter()
    running = RequestState.RUNNING
    greedy_t = GREEDY_TEMPERATURE
    sample_t = float(mix.get("temperature", 0.6))
    rec.n_blocks = engine.kv.n_blocks

    clock = time.monotonic
    start = clock()
    warm_s = float(mix["warm_s"])
    rec.w0, rec.w1 = start + warm_s, start + warm_s + seconds
    # the slice ends with the window, so that writing the trace out
    # stalls the loop only after the window has closed
    trace_t0 = rec.w1 - min(trace_slice, seconds)
    tracing = None
    pending = list(reversed(reqs))          # pop() from the end
    open_: list[Served] = []
    window_left = sum(1 for r in reqs if r.segment == "window")
    in_window = False
    compiles0 = 0
    while True:
        now = clock()
        if not in_window and now >= rec.w0:
            in_window = True
            rec.setup_s = now - t_proc0
            rec.counters0 = counters(engine)
            compiles0 = cc.n
        if in_window and rec.counters1 == {} and now >= rec.w1:
            rec.counters1 = counters(engine)
            rec.window_compiles = cc.n - compiles0
        if now >= rec.w1 and (window_left == 0 or now >= rec.w1 + grace_s):
            break
        if trace_slice and tracing is None and now >= trace_t0:
            tracing = _Trace(cell.name, seed, rec, engine)
        if tracing is not None and tracing.active and now >= rec.w1:
            tracing.stop(rec, engine)
        batch = []
        while pending and start + pending[-1].due_s <= now:
            tr = pending.pop()
            s = Served(tr, _request(tr.index, tr.prompt, tr.tokens,
                                    tr.output_len,
                                    greedy_t if tr.greedy else sample_t),
                       start + tr.due_s)
            s.sr.arrival = s.due
            batch.append(s)
        if batch:
            with ann("gateway.offer"):
                gateway.offer_batch([s.sr for s in batch])
            for s in batch:
                rec.lateness.append(now - s.due)
            open_.extend(batch)
            rec.reqs.extend(batch)
        if gateway.drained:
            nxt = start + pending[-1].due_s if pending else now + 0.001
            time.sleep(max(0.0, min(nxt - clock(), 0.002)))
            continue
        sched0 = sched_timer.total if sched_timer else 0.0
        t0 = clock()
        with ann("gateway.step"):
            gateway.step()
        t1 = clock()
        positions, pflops, still = [], 0, []
        for s in open_:
            sr = s.sr
            n = len(sr.output_tokens)
            seen = len(s.times)
            if n > seen:
                if not s.times and s.tr.segment == "window":
                    window_left -= 1
                p0 = s.tr.input_len - 1
                positions.extend(range(p0 + seen, p0 + n))
                s.times.extend([t1] * (n - seen))
            if math.isnan(s.running) and (sr.state == running or s.times):
                s.running = t1
            pp = min(sr.prefill_pos, s.tr.input_len - 1)
            if pp > s.prefilled:
                pflops += work.prompt_flops(cfg, s.prefilled, pp)
                s.prefilled = pp
            if not sr.done:
                still.append(s)
            elif not s.times and s.tr.segment == "window":
                window_left -= 1            # failed without a first token
        open_ = still
        st = Step(t0, t1, positions, pflops, engine.kv.used_blocks,
                  (sched_timer.total - sched0) if sched_timer else 0.0)
        rec.steps.append(st)
        if tracing is not None and tracing.active:
            tracing.steps.append(st)
    if tracing is not None:
        if tracing.active:
            tracing.stop(rec, engine)
        tracing.reduce(rec)
    if rec.counters1 == {}:
        rec.counters1 = counters(engine)
        rec.window_compiles = cc.n - compiles0
    rec.end = clock()


class _no_span:
    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


class _Trace:
    """A profiler slice inside the window, with a host span around it."""

    def __init__(self, cell: str, seed: int, rec: RunRecord, engine):
        import jax
        from chipbench.tracing import WINDOW_SPAN
        self.dir = OUT_DIR / f"trace-{cell}-{seed}"
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        jax.profiler.start_trace(str(self.dir))
        self.span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self.span.__enter__()
        self.c0 = counters(engine)
        self.steps: list[Step] = []
        self.active = True

    def stop(self, rec: RunRecord, engine) -> None:
        import jax
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False
        rec.trace_counters = (self.c0, counters(engine))

    def reduce(self, rec: RunRecord) -> None:
        from chipbench import tracing
        tr = tracing.load(str(self.dir))
        shutil.rmtree(self.dir, ignore_errors=True)
        rec.trace = tracing.reduce(tr)
        rec.trace_steps = self.steps


def finish_run(rec: RunRecord, engine, gateway) -> dict:
    """Host-side checks of the served state once the loop has stopped:
    the gateway's ledger (every offered request finished, in flight or
    failed, with a reason) and KV block conservation."""
    try:
        gateway.check_invariants()
        bad = 0
    except Exception as e:                       # noqa: BLE001
        print(f"ledger: {e}", file=sys.stderr)
        bad = 1
    return {"ledger_violations": bad}


def sample_for_check(rec: RunRecord, seed: int, n_max: int) -> list:
    """Greedy requests the loop saw finish, drawn by the seed, the one
    with the most served tokens always among them."""
    from repro.serving import RequestState
    done = [s for s in rec.reqs if s.tr.greedy
            and s.sr.state == RequestState.FINISHED and s.sr.output_tokens]
    if not done:
        return []
    done.sort(key=lambda s: (-len(s.sr.output_tokens), s.tr.index))
    rest = done[1:]
    rng = T.seed_rng(seed, salt=2)
    pick = [rest[i] for i in rng.permutation(len(rest))[:n_max - 1]]
    return [done[0]] + pick


