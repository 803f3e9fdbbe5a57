"""Continuous-batching serving engine driving a real JAX model.

vLLM-style iteration loop, scheduled by repro.core.Scheduler (SageSched or
any baseline policy).  The execution layer is *memory-hybrid* (the
paper's second axis): KV residency, capacity-forced eviction and swap IO
are first-class, shared with the discrete-event simulator.

    submit() -> scheduler.admit (predict + cost + Gittins)
    each step() builds an iteration plan:
        1. select the running set: scheduler priority order under the
           KVCacheManager *block* budget (one authoritative accessor,
           shared with can_admit) + slot limit, with hysteresis against
           priority thrashing (Sec. 3.3);
        2. preempt displaced requests — swap mode gathers their KV blocks
           to the host pool (modeled cost: ServiceModel.swap_time over
           block-aligned tokens, the SAME function the simulator
           charges); recompute mode drops them;
        3. admit newcomers: swapped requests are restored by scattering
           their saved blocks back (NO re-prefill); fresh/recompute
           requests prefill — Sarathi-style chunks mixed with the decode
           batch under one token budget (``max_tokens_per_step``);
        4. relieve capacity pressure: decode growth that found no free
           block (grow() -> False) forces eviction, victims picked by
           ``Scheduler.eviction_order`` — priority *plus* the memory
           term (held KV ~ predicted swap cost);
        5. one decode iteration over all decode-ready slots through the
           paged pool (block-table indirection);
        6. ONE vectorized sampling pass over all slots (argmax /
           inverse-CDF categorical), completions fed back to the
           scheduler's history window.

In the default ``step_mode="fused"``, stages 5-6 plus per-lane
EOS/length bookkeeping are ONE jitted, buffer-donated device call: a
``lax.fori_loop`` decodes up to ``decode_steps`` tokens per host
round-trip with on-device sampling, and the host gets back a single
(tokens, emitted, finished) transfer.  Traced shapes ride pow2 bucket
ladders (active lanes, table width, prefill padding) so batch churn
never grows the compile set past ``max_fused_compiles()``.
``step_mode="orchestrated"`` keeps the per-step host loop as the parity
oracle and benchmark baseline.

KV memory is a paged pool: (L, n_pages, page, KV, dh) tensors shared by
the batch, a per-slot block table mapping logical positions to physical
pages (page 0 = scratch, where masked lanes write), and a host swap pool
holding preempted requests' KV.  See docs/serving_engine.md.

The engine is single-host: the CPU in tests, one TPU chip or a
four-chip host's mesh on the chip (``chip_smoke.py`` at the repo root
runs both).
"""

from __future__ import annotations

import functools
import math
import time
import zlib
from collections import Counter
from dataclasses import dataclass, field
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..core.scheduler import Scheduler
from ..kernels.bucketing import ladder_size as _ladder_size
from ..kernels.bucketing import pow2_bucket as _pow2_bucket
from ..models import Model
from ..simulator.service_model import ServiceModel
from .kv_cache import SCRATCH_BLOCK, KVCacheManager
from .metrics import EngineMetrics
from .request import RequestState, ServeRequest

__all__ = ["ServingEngine", "EngineStallError"]


class EngineStallError(RuntimeError):
    """``run_until_done`` exhausted its step budget with work still live.

    The message carries the full stall diagnosis (per-state request
    counts, queue depth, block-pool occupancy, pressure set) so a
    livelock — e.g. an injected fault that wedged admission — fails
    loudly instead of timing out silently."""


def _pad_len(n: int, quantum: int = 64) -> int:
    """pow2 bucket with a floor — prefill chunk/prefix padding ladder."""
    return _pow2_bucket(n, floor=quantum)


def _rid_seed(request_id: str) -> int:
    """Stable per-request RNG seed: sampling draws depend on (request,
    position), never on slot assignment or preemption history, so swap
    and recompute schedules sample identical streams."""
    return zlib.crc32(request_id.encode())


@dataclass
class ServingEngine:
    model: Model
    scheduler: Scheduler
    n_slots: int = 8
    max_seq_len: int = 512
    capacity_tokens: int | None = None
    preemption_hysteresis: float = 0.5
    seed: int = 0
    params: dict | None = None
    block_size: int = 16                   # KV page size, tokens
    preemption_mode: str = "swap"          # "swap" | "recompute"
    prefill_chunk: int | None = None       # tokens per chunk; None = atomic
    max_tokens_per_step: int | None = None  # mixed prefill+decode budget
    memory_weight: float = 0.5             # eviction memory term (0 = off)
    swap_capacity_tokens: int | None = None
    service_model: ServiceModel | None = None
    step_mode: str = "fused"               # "fused" | "orchestrated"
    decode_steps: int = 1                  # decode tokens per host round-trip
    # Copy-on-write prefix sharing: admission matches an incoming
    # prompt's longest indexed block-chain prefix and adopts those KV
    # pages by refcount instead of re-prefilling them (chunked prefill
    # resumes at the divergence point).  Requires chunked prefill
    # (prefill_chunk set + a family that supports it) — silently inert
    # otherwise, so enabling it on an SSM family changes nothing.
    prefix_sharing: bool = False
    # Injectable time source (TTFT/TTLT stamps, arrival defaults).  The
    # gateway's deadline enforcement shares this clock, so tests and
    # benchmarks drive deadline storms deterministically with a virtual
    # clock instead of racing wall time.
    clock: Callable[[], float] = time.monotonic
    # Mesh-parallel execution (repro.serving.sharded).  Pass a Mesh
    # whose 'model' axis is the tensor/expert-parallel width, or just
    # ``tp=N`` to build a local host-device mesh.  The default
    # (mesh=None, tp=1) is the plain single-device path, unchanged.
    # Sharded output is bit-identical to unsharded (exact decomposition
    # — docs/sharded_serving.md), so every parity/selection invariant
    # holds under the mesh too.
    mesh: object | None = None
    tp: int = 1
    # "exact" (default) shards only what preserves bit-identity (KV pool
    # + expert buffers); "efficient" flips the Megatron weight axes on
    # too (column-parallel qkv/up/gate, row-parallel wo/down, vocab-
    # sharded lm_head, LSE-split attention when heads don't divide) and
    # trades bit-identity for a tolerance contract
    # (testing.assert_tokens_close; docs/sharded_serving.md).
    parallel: str = "exact"
    # Per-device HBM budget for the admission-time memory preflight:
    # when set, __post_init__ refuses to build an engine whose per-shard
    # weights + KV pool + fused-step workspace exceed it, *before* any
    # device allocation happens.  None skips the check.
    device_memory_gb: float | None = None

    _requests: dict[str, ServeRequest] = field(default_factory=dict)
    _running: list[str] = field(default_factory=list)

    def __post_init__(self):
        if self.preemption_mode not in ("swap", "recompute"):
            raise ValueError(f"bad preemption_mode {self.preemption_mode!r}")
        if self.step_mode not in ("fused", "orchestrated"):
            raise ValueError(f"bad step_mode {self.step_mode!r}")
        if self.decode_steps < 1:
            raise ValueError("decode_steps must be >= 1")
        if not self.model.supports_paged:
            raise ValueError(
                f"{self.model.cfg.family} models are not servable through "
                "the paged engine")
        if self.tp < 1:
            raise ValueError(f"tp must be >= 1, got {self.tp}")
        if self.parallel not in ("exact", "efficient"):
            raise ValueError(
                f"bad parallel {self.parallel!r}: expected 'exact' or "
                "'efficient'")
        self.plan = None
        if self.mesh is None and self.tp > 1:
            from ..launch.mesh import make_local_mesh
            self.mesh = make_local_mesh(tp=self.tp)
        if self.mesh is not None:
            from .sharded import ShardingPlan
            self.plan = ShardingPlan.build(self.model, self.mesh,
                                           parallel=self.parallel)
            if self.tp > 1 and self.tp != self.plan.tp:
                raise ValueError(
                    f"tp={self.tp} contradicts mesh model axis "
                    f"{self.plan.tp}")
            self.tp = self.plan.tp
        # KVCacheManager is pure host bookkeeping — built before the
        # memory preflight so pool_blocks feeds the per-shard estimate
        # without having allocated anything on device yet.
        self.kv = KVCacheManager(
            self.n_slots, self.max_seq_len, self.capacity_tokens,
            block_size=self.block_size,
            swap_capacity_tokens=self.swap_capacity_tokens)
        self._preflight_memory()
        # parameters and pool are built where they live: on the plan's
        # shardings on a mesh, on the default device otherwise.  Pool
        # pages live per-shard (split over the kv-head dim); the
        # host-side block tables below stay authoritative and
        # shard-agnostic
        if self.params is None:
            self.params = self.model.init(
                jax.random.PRNGKey(self.seed),
                None if self.plan is None else self.plan.param_shardings)
        elif self.plan is not None:
            self.params = self.plan.place_params(self.params)
        if self.service_model is None:
            self.service_model = ServiceModel()
        self.metrics = EngineMetrics()
        self._rng = np.random.default_rng(self.seed)
        shapes = self.model.paged_cache_shapes(
            self.kv.pool_blocks, self.block_size, self.n_slots)
        self._cache = self.model.init_paged_cache(
            self.kv.pool_blocks, self.block_size, self.n_slots,
            None if self.plan is None else self.plan.cache_shardings(shapes))
        self._has_kv = "k" in self._cache
        self._max_pages = -(-self.max_seq_len // self.block_size)
        self._block_tables = np.full((self.n_slots, self._max_pages),
                                     SCRATCH_BLOCK, np.int32)
        # cache_len < 0 marks a slot that is not decode-ready (free, or
        # still prefilling); the decode step masks it to 0
        self._cache_len = np.full(self.n_slots, -1, np.int64)
        self._last_token = np.zeros(self.n_slots, np.int64)
        self._slot_rid: dict[int, str] = {}
        self._needs_grow: set[str] = set()
        page = self.block_size
        # plan-aware jit: on a mesh, traces run under the plan's hook
        # context and cache-typed outputs are pinned back to the pool
        # layout (cc / ckv below), which keeps the donated round-trips
        # shard-stable; on the default path all three are identity/jax.jit
        jit = jax.jit if self.plan is None else self.plan.wrap_jit
        cc = (lambda c: c) if self.plan is None else self.plan.constrain_cache
        ckv = (lambda x: x) if self.plan is None else self.plan.constrain_kv

        def decode_step(p, t, c, cl, bt):
            logits, c2 = self.model.decode_step_paged(p, t, c, cl, bt,
                                                      page_size=page)
            return logits, cc(c2)

        def prefill(p, b):
            logits, c2 = self.model.prefill(p, b)
            return logits, cc(c2)

        def chunk(p, t, pk, pv, s):
            k_c, v_c = self.model.prefill_chunk(p, t, pk, pv, s)
            return ckv(k_c), ckv(v_c)

        self._decode_fn = jit(decode_step, donate_argnums=(2,))
        self._prefill_fn = jit(prefill)
        self._chunk_fn = jit(chunk)

        @functools.partial(jit, donate_argnums=(0, 1))
        def scatter(pk, pv, ks, vs, idx):
            fk = pk.reshape((pk.shape[0], -1) + pk.shape[3:])
            fv = pv.reshape((pv.shape[0], -1) + pv.shape[3:])
            fk = fk.at[:, idx].set(ks[:, 0].astype(fk.dtype))
            fv = fv.at[:, idx].set(vs[:, 0].astype(fv.dtype))
            return ckv(fk.reshape(pk.shape)), ckv(fv.reshape(pv.shape))

        @jit
        def gather(pk, pv, idx):
            fk = pk.reshape((pk.shape[0], -1) + pk.shape[3:])
            fv = pv.reshape((pv.shape[0], -1) + pv.shape[3:])
            return ckv(fk[:, None, idx]), ckv(fv[:, None, idx])

        self._scatter_fn = scatter
        self._gather_fn = gather

        # ------------------------------------------------ fused decode step
        # One jitted, buffer-donated device function per (B bucket, P
        # bucket, n_steps): paged attention over all layers, sampling,
        # KV/state writes, and per-lane length/EOS/finished bookkeeping
        # run on-device inside a lax.fori_loop; the host gets back ONE
        # small (tokens, emitted, finished) transfer per call.  Recurrent
        # families carry per-slot state inside the cache, so their lanes
        # are slot-positional (B = n_slots, a single batch bucket); the
        # attention families bucket active lanes to the pow2 ladder.
        self._slot_state = "ssm" in self._cache
        model = self.model
        base_key = jax.random.PRNGKey(self.seed)

        @functools.partial(jit,
                           static_argnames=("n_steps", "all_greedy"),
                           donate_argnums=(1,))
        def fused_steps(params, cache, last, cl, tables, budgets, caps,
                        eos, temps, seeds, counters, *, n_steps: int,
                        all_greedy: bool):
            nb = last.shape[0]
            greedy = temps <= 0.0
            safe_t = jnp.where(greedy, 1.0, temps)

            def body(i, st):
                cache, last, cl, emitted, fin, buf = st
                act = (~fin) & (i < budgets)
                # inactive lanes (finished mid-loop, budget-paused, pad)
                # ride the scratch page: their KV write lands harmlessly
                bt = jnp.where(act[:, None], tables, SCRATCH_BLOCK)
                old_ssm = cache.get("ssm")
                logits, cache = model.decode_step_paged(
                    params, last[:, None], cache, cl, bt, page_size=page)
                if old_ssm is not None:
                    # recurrent state has no scratch page — freeze the
                    # rows of inactive lanes explicitly
                    cache = dict(cache)
                    cache["ssm"] = jax.tree.map(
                        lambda new, old: jnp.where(
                            act.reshape((1, nb) + (1,) * (new.ndim - 2)),
                            new, old),
                        cache["ssm"], old_ssm)
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                if not all_greedy:
                    # categorical draws are keyed by (request seed,
                    # position) — invariant to slot and preemption
                    # history.  Skipped entirely (statically) when every
                    # lane is greedy: at production vocab sizes the
                    # per-lane Gumbel draw is the single largest cost in
                    # the step after the forward itself.
                    keys = jax.vmap(
                        lambda s, c: jax.random.fold_in(
                            jax.random.fold_in(base_key, s), c)
                    )(seeds, (counters + i).astype(jnp.uint32))
                    st_tok = jax.vmap(jax.random.categorical)(
                        keys, logits.astype(jnp.float32) / safe_t[:, None])
                    tok = jnp.where(greedy, tok, st_tok.astype(jnp.int32))
                emitted = emitted + act.astype(jnp.int32)
                fin = fin | (act & ((tok == eos) | (emitted >= caps)))
                last = jnp.where(act, tok, last)
                cl = cl + act.astype(cl.dtype)
                buf = buf.at[:, i].set(jnp.where(act, tok, -1))
                return (cache, last, cl, emitted, fin, buf)

            st0 = (cache, last, cl, jnp.zeros((nb,), jnp.int32),
                   jnp.zeros((nb,), bool), jnp.full((nb, n_steps), -1,
                                                    jnp.int32))
            cache, last, cl, emitted, fin, buf = jax.lax.fori_loop(
                0, n_steps, body, st0)
            return buf, emitted, fin, cc(cache)

        self._fused_fn = fused_steps
        # abstract (shape/dtype/sharding) args of the last fused call —
        # lower_fused_hlo() re-lowers them for the roofline bench
        self._last_fused_call = None

    def _preflight_memory(self) -> None:
        """Refuse to build an engine that cannot fit one shard on one
        device.  Pure arithmetic over parameter templates and pool
        shapes (``sharded.estimate_device_bytes``) — runs before any
        device allocation, so an over-budget config fails with a
        diagnostic instead of an allocator OOM mid-init."""
        self.preflight = None
        if self.device_memory_gb is None:
            return
        from .sharded import estimate_device_bytes
        est = estimate_device_bytes(
            self.model, tp=self.tp, parallel=self.parallel,
            n_pages=self.kv.pool_blocks, page_size=self.block_size,
            n_slots=self.n_slots)
        budget = int(self.device_memory_gb * (1 << 30))
        if est["total_bytes"] > budget:
            gib = 1 << 30
            fixes = "raise tp or shrink the KV pool" \
                if self.parallel == "efficient" \
                else "raise tp, switch parallel='efficient', or shrink " \
                     "the KV pool"
            raise ValueError(
                f"model {self.model.cfg.name!r} does not fit: per-device "
                f"need {est['total_bytes'] / gib:.2f} GiB "
                f"(weights {est['weights_bytes'] / gib:.2f} + "
                f"KV pool {est['kv_pool_bytes'] / gib:.2f} + "
                f"workspace {est['workspace_bytes'] / gib:.2f}) "
                f"> budget {self.device_memory_gb:.2f} GiB at "
                f"tp={self.tp} parallel={self.parallel!r}; {fixes} "
                f"(replicated bytes: {est['replicated_bytes'] / gib:.2f} "
                "GiB)")
        self.preflight = est

    # ------------------------------------------------------------ frontend

    def submit(self, request: ServeRequest) -> None:
        """Enqueue one request — the B = 1 case of ``submit_batch``."""
        self.submit_batch([request])

    def submit_batch(self, requests: list[ServeRequest],
                     length_dists: list | None = None) -> None:
        """Enqueue a burst of requests through one batched admission:
        a single ``Scheduler.admit_batch`` call (one predict_batch over
        the burst's prompts, one BatchState append).  Unstamped arrivals
        (``arrival == 0.0``) share one clock reading — the burst arrived
        together.  ``length_dists`` forwards caller-side predictions
        (the gateway predicts once for shed scoring and hands the same
        distributions down, instead of predicting twice)."""
        if not requests:
            return
        now = self.clock()
        arrivals = [now if r.arrival == 0.0 else r.arrival
                    for r in requests]
        # admit first: admit_batch rejects duplicates before mutating any
        # state, so a failed burst leaves no ghost entries in _requests
        self.scheduler.admit_batch(
            [r.request_id for r in requests],
            [r.prompt for r in requests],
            [r.input_len for r in requests],
            arrivals=arrivals, length_dists=length_dists,
            tenants=[r.tenant for r in requests])
        for r, arrival in zip(requests, arrivals):
            r.arrival = arrival
            self._requests[r.request_id] = r

    def abort(self, request_id: str, reason: str = "abort") -> None:
        """Terminate a request in ANY non-terminal lifecycle state —
        waiting, mid-chunked-prefill, decoding, pressure-stalled, or
        swapped out — releasing every device block, the slot, and any
        host swap payload.  Tokens already decoded for it are accounted
        as wasted (goodput != throughput)."""
        r = self._requests.get(request_id)
        if r and not r.done:
            self._release(r)
            r.state = RequestState.ABORTED
            r.finish_reason = reason
            self.metrics.aborted += 1
            self.metrics.wasted_tokens += r.generated
            if reason.endswith("_deadline"):
                self.metrics.timeout_aborts += 1
            self.scheduler.on_abort(request_id)

    @property
    def has_work(self) -> bool:
        return any(not r.done for r in self._requests.values())

    # -------------------------------------------------------- slot plumbing

    def _clear_slot(self, r: ServeRequest) -> None:
        if r.slot >= 0:
            self._slot_rid.pop(r.slot, None)
            self._cache_len[r.slot] = -1
            self._block_tables[r.slot] = SCRATCH_BLOCK
            r.slot = -1
        if r.request_id in self._running:
            self._running.remove(r.request_id)
        self._needs_grow.discard(r.request_id)

    def _release(self, r: ServeRequest) -> None:
        """Drop every engine-side resource (completion / abort)."""
        if self.kv.holds(r.request_id):
            self.kv.release(r.request_id)
        self.kv.drop_swapped(r.request_id)
        r.prefill_pos = 0
        self._clear_slot(r)

    def _bind_slot(self, r: ServeRequest, slot: int) -> None:
        r.slot = slot
        self._slot_rid[slot] = r.request_id
        row = np.full(self._max_pages, SCRATCH_BLOCK, np.int32)
        blocks = self.kv.block_table(r.request_id)
        row[:len(blocks)] = blocks
        self._block_tables[slot] = row
        if r.request_id not in self._running:
            self._running.append(r.request_id)
        r.state = RequestState.RUNNING

    def _sync_block_table(self, r: ServeRequest) -> None:
        """Refresh a slot's table row after ``grow`` appended blocks."""
        blocks = self.kv.block_table(r.request_id)
        self._block_tables[r.slot, :len(blocks)] = blocks

    # ------------------------------------------------------------ swap plane

    def _gather_payload(self, r: ServeRequest, blocks: list[int]) -> dict:
        slot = r.slot
        payload = {
            "cache_len": int(self._cache_len[slot]),
            "last_token": int(self._last_token[slot]),
            "prefill_pos": r.prefill_pos,
        }
        if self._has_kv:
            idx = jnp.asarray(blocks)
            payload["k"] = np.asarray(self._cache["k"][:, idx])
            payload["v"] = np.asarray(self._cache["v"][:, idx])
        if "ssm" in self._cache:
            payload["ssm"] = jax.tree.map(
                lambda a: np.asarray(a[:, slot]), self._cache["ssm"])
        return payload

    def _restore_payload(self, r: ServeRequest, payload: dict) -> None:
        slot = r.slot
        blocks = self.kv.block_table(r.request_id)
        # leading blocks re-adopted from the prefix index at swap_in
        # already hold this prefix's KV on device — scatter only the rest
        skip = self.kv.adopted_blocks_of(r.request_id)
        if self._has_kv and len(blocks) > skip:
            idx = jnp.asarray(blocks[skip:])
            self._cache["k"] = self._cache["k"].at[:, idx].set(
                jnp.asarray(payload["k"])[:, skip:])
            self._cache["v"] = self._cache["v"].at[:, idx].set(
                jnp.asarray(payload["v"])[:, skip:])
        if "ssm" in self._cache:
            self._cache["ssm"] = jax.tree.map(
                lambda big, small: big.at[:, slot].set(jnp.asarray(small)),
                self._cache["ssm"], payload["ssm"])
        self._cache_len[slot] = payload["cache_len"]
        self._last_token[slot] = payload["last_token"]
        r.prefill_pos = payload["prefill_pos"]
        # eager scatters above leave sharding propagation to XLA; re-pin
        # the pool so the next jitted call sees the plan layout (no-op
        # copy when it already matches, and always on the plain path)
        self._commit_cache()

    def _commit_cache(self) -> None:
        if self.plan is not None:
            self._cache = self.plan.place_cache(self._cache)

    def _preempt(self, r: ServeRequest) -> None:
        rid = r.request_id
        swapped = False
        if (self.preemption_mode == "swap" and self.kv.holds(rid)
                and self.kv.can_swap_out(rid)):
            blocks = self.kv.block_table(rid)
            payload = self._gather_payload(r, blocks)
            tokens = self.kv.swap_out(rid, payload)
            self.metrics.swap_outs += 1
            self.metrics.swapped_out_tokens += tokens
            self.metrics.modeled_swap_s += self.service_model.swap_time(
                tokens, self.kv.block_size)
            swapped = True
        elif self.kv.holds(rid):
            self.kv.release(rid)
        if not swapped:
            r.prefill_pos = 0      # recompute mode: replay the context
        self._clear_slot(r)
        r.state = RequestState.SWAPPED
        r.n_preemptions += 1
        self.metrics.preemptions += 1

    # --------------------------------------------------------------- select

    def _select_running(self) -> list[str]:
        """Scheduler-priority admission under the slot limit and the
        KVCacheManager's *block* budget (``budget_blocks`` — the same
        accessor ``can_admit`` uses, so engine selection and manager
        admission can never drift).  Ranking happens inside the
        scheduler: preemptive policies scale running priorities by the
        hysteresis factor, non-preemptive ones pin the running set."""
        live = [rid for rid, r in self._requests.items() if not r.done]
        if not live:
            return []
        running = set(self._running)
        if self.scheduler.preemptive:
            order = self.scheduler.order(
                live, running=running,
                hysteresis=self.preemption_hysteresis)
        else:
            order = self.scheduler.order(live, running=running,
                                         pin_running=True)
        selected, used_blocks = [], 0.0
        budget = self.kv.budget_blocks
        for rid in order:
            if len(selected) >= self.n_slots:
                break
            need = float(self.kv.blocks_for(
                self._requests[rid].context_len + 1))
            if self.kv.holds(rid):
                # resident: charge owned (refcount-weighted) blocks, so
                # N requests sharing a prefix pay for it once, not N
                # times (identical to raw held blocks when private)
                need -= self.kv.shared_excess_blocks(rid)
            elif self._sharing:
                # waiting: discount the blocks a prefix match would
                # adopt (kept >= 1 so every request charges something)
                m, _, _ = self.kv.match_prefix(
                    self._requests[rid].prompt_tokens)
                need -= min(m // self.block_size, need - 1)
            if used_blocks + need <= budget:
                selected.append(rid)
                used_blocks += need
        if not selected:
            # nothing fits (e.g. one giant prompt): force the top request
            # so the engine cannot stall; if its context exceeds even the
            # physical pool, step()'s admit guard rejects it outright
            selected = [order[0]]
        return selected

    # --------------------------------------------------------------- admit

    @property
    def _sharing(self) -> bool:
        """Prefix sharing is live only when the family can resume a
        prefill mid-context (chunked prefill) through the paged KV pool.
        Recurrent-state families cannot start at a divergence point, so
        the flag is inert for them — tokens never change either way."""
        return (self.prefix_sharing and self._has_kv
                and self.model.supports_chunked_prefill
                and self.prefill_chunk is not None)

    def _match_prompt(self, r: ServeRequest) -> tuple[int, list[int],
                                                      list[int]]:
        """Longest adoptable shared-block prefix of ``r``'s prompt,
        capped twice: (a) strictly below the last context position — the
        decode path re-emits from ``context_len - 1`` (see
        ``_finalize_prefill``'s rewind), so the block holding it must be
        private, which also makes runtime copy-on-write forks
        unnecessary in the engine (the cap IS the fork point, taken
        before any divergent write exists); (b) down to the prefill
        chunk grid, so the remaining chunks land on exactly the
        boundaries a from-scratch prefill would use and the computed KV
        (and therefore every sampled token) is bit-identical to the
        sharing-off run."""
        if not self._sharing:
            return 0, [], []
        matched, blocks, hashes = self.kv.match_prefix(r.prompt_tokens)
        if not matched:
            return 0, [], []
        grid = self.prefill_chunk * self.block_size \
            // math.gcd(self.prefill_chunk, self.block_size)
        m = (min(matched, r.context_len - 1) // grid) * grid
        k = m // self.block_size
        return m, blocks[:k], hashes[:k]

    def _admit(self, r: ServeRequest) -> None:
        rid = r.request_id
        if self.preemption_mode == "swap" and self.kv.is_swapped(rid):
            try:
                slot, payload = self.kv.swap_in(rid)
            except RuntimeError:
                # capacity shortfalls resolve next step (re-raise: the
                # step loop leaves the request queued) — but a failure
                # while the pool HAD room is a faulty payload/IO path;
                # drop the host copy and recompute instead of
                # livelocking on a restore that can never succeed
                need = self.kv.blocks_for(self.kv.swapped_tokens_of(rid))
                if self.kv.free_slots == 0 or need > self.kv.free_blocks:
                    raise
                self.metrics.swap_in_faults += 1
                self.kv.drop_swapped(rid)
                r.prefill_pos = 0
            else:
                self._restore_swapped(r, slot, payload)
                return
        self.kv.drop_swapped(rid)
        ctx_len = r.context_len      # replay prompt + outputs on recompute
        matched, shared, hashes = self._match_prompt(r)
        if matched:
            slot = self.kv.allocate_shared(rid, ctx_len, shared, hashes)
            r.prefill_pos = matched  # chunks resume at the divergence point
            self.metrics.prefill_tokens_reused += matched
        else:
            slot = self.kv.allocate(rid, ctx_len)
            r.prefill_pos = 0
        self._bind_slot(r, slot)
        self._cache_len[slot] = -1   # not decode-ready until prefilled

    def _restore_swapped(self, r: ServeRequest, slot: int,
                         payload: dict) -> None:
        rid = r.request_id
        tokens = self.kv.tokens_of(rid)
        r.slot = slot
        self._bind_slot(r, slot)
        self._restore_payload(r, payload)
        r.n_swap_restores += 1
        self.metrics.swap_ins += 1
        self.metrics.swapped_in_tokens += tokens
        self.metrics.modeled_swap_s += self.service_model.swap_time(
            tokens, self.kv.block_size)
        # a request preempted while awaiting a growth block comes
        # back one block short of its next write position — re-grow
        # (or re-mark the pressure) before it may decode again
        if self._cache_len[slot] >= 0 \
                and self.kv.tokens_of(rid) <= self._cache_len[slot]:
            if self.kv.grow(rid, 1):
                self._sync_block_table(r)
            else:
                self.metrics.grow_failures += 1
                self._needs_grow.add(rid)

    # -------------------------------------------------------------- prefill

    def _phys_positions(self, r: ServeRequest, lo: int, hi: int,
                        pad_to: int) -> np.ndarray:
        """Flat pool token indices for logical positions [lo, hi), padded
        to ``pad_to`` entries pointing at the scratch page."""
        page = self.block_size
        table = self._block_tables[r.slot]
        pos = np.arange(lo, lo + pad_to)
        phys = table[np.minimum(pos // page, self._max_pages - 1)] * page \
            + pos % page
        phys[pos >= hi] = SCRATCH_BLOCK * page
        return phys.astype(np.int32)

    def _finalize_prefill(self, r: ServeRequest, ctx: list[int]) -> None:
        # the prefill may have run over a padded buffer, so its
        # last-position logits are not trustworthy; rewind one position
        # and let the shared decode path re-emit from the true last
        # context token (the cache holds positions < len(ctx)).
        # Identical for fresh prompts and recompute-mode readmissions —
        # ctx already includes any previously generated tokens.
        self._cache_len[r.slot] = len(ctx) - 1
        self._last_token[r.slot] = ctx[-1]
        self.metrics.prefills += 1
        # publish this prompt's full blocks for later prompts to adopt
        # (first writer wins; positions at/after the rewind point above
        # are never published — the manager excludes the last prompt
        # position's block)
        if self._sharing:
            self.kv.register_prefix(r.request_id, r.prompt_tokens)

    def _prefill_chunk_step(self, r: ServeRequest, take: int) -> None:
        """Advance one Sarathi chunk: run [prefill_pos, prefill_pos+take)
        against the pool-resident prefix, scatter the chunk's KV."""
        ctx = r.prompt_tokens + r.output_tokens
        s0, s1 = r.prefill_pos, r.prefill_pos + take
        cpad = _pad_len(take)
        toks = np.zeros((1, cpad), np.int32)
        toks[0, :take] = ctx[s0:s1]
        if s0 == 0:
            shp = self._cache["k"].shape
            past_k = jnp.zeros((shp[0], 1, 0) + shp[3:], jnp.bfloat16)
            past_v = past_k
        else:
            past_pad = _pad_len(s0)
            idx = jnp.asarray(self._phys_positions(r, 0, s0, past_pad))
            past_k, past_v = self._gather_fn(self._cache["k"],
                                             self._cache["v"], idx)
        k_c, v_c = self._chunk_fn(self.params, jnp.asarray(toks),
                                  past_k, past_v, jnp.int32(s0))
        out_idx = jnp.asarray(self._phys_positions(r, s0, s1, cpad))
        self._cache["k"], self._cache["v"] = self._scatter_fn(
            self._cache["k"], self._cache["v"], k_c, v_c, out_idx)
        r.prefill_pos = s1
        self.metrics.prefill_chunks += 1
        self.metrics.prefill_tokens += take  # tokens actually computed
        if s1 >= len(ctx):
            self._finalize_prefill(r, ctx)

    def _prefill_atomic(self, r: ServeRequest) -> None:
        """Whole-context prefill for families without chunk support
        (SSM / hybrid recurrent state cannot replay a chunk), padded to a
        pow2 bucket.  The true length rides along as a mask threaded
        through the recurrent scan (``mamba2_block`` forces dt = 0 at pad
        positions, so decay is exactly 1 and the state is bit-identical
        to an unpadded run) — one XLA compile per *bucket*, not per
        distinct context length.  KV (hybrid) is scattered into the pool
        for valid positions only; pad positions land in scratch."""
        ctx = r.prompt_tokens + r.output_tokens
        n = len(ctx)
        spad = _pad_len(n, quantum=32)
        toks = np.zeros((1, spad), np.int32)
        toks[0, :n] = ctx
        _, cache = self._prefill_fn(
            self.params, {"tokens": jnp.asarray(toks),
                          "lengths": jnp.asarray([n], jnp.int32)})
        if self._has_kv:
            phys = jnp.asarray(self._phys_positions(r, 0, n, spad))
            self._cache["k"], self._cache["v"] = self._scatter_fn(
                self._cache["k"], self._cache["v"], cache["k"], cache["v"],
                phys)
        if "ssm" in self._cache:
            slot = r.slot
            self._cache["ssm"] = jax.tree.map(
                lambda big, small: big.at[:, slot].set(
                    small[:, 0].astype(big.dtype)),
                self._cache["ssm"], cache["ssm"])
            self._commit_cache()
        r.prefill_pos = len(ctx)
        self.metrics.prefill_chunks += 1
        self.metrics.prefill_tokens += len(ctx)
        self._finalize_prefill(r, ctx)

    def _run_prefills(self) -> None:
        """Advance every prefilling slot under the step's token budget:
        chunked prefill mixes with the decode batch — decode-ready slots
        each consume one budget token, the remainder goes to chunks."""
        prefilling = [rid for rid in self._running
                      if self._cache_len[self._requests[rid].slot] < 0]
        if not prefilling:
            return
        budget = None
        if self.max_tokens_per_step is not None:
            n_decoding = len(self._running) - len(prefilling)
            budget = max(0, self.max_tokens_per_step - n_decoding)
        for rid in prefilling:
            r = self._requests[rid]
            if not self.model.supports_chunked_prefill:
                self._prefill_atomic(r)
                continue
            remaining = r.context_len - r.prefill_pos
            cap = self.prefill_chunk or remaining
            if budget is not None:
                cap = min(cap, budget)
            take = min(cap, remaining)
            if take <= 0:
                continue            # budget exhausted: resume next step
            self._prefill_chunk_step(r, take)
            if budget is not None:
                budget -= take

    # ------------------------------------------------------------- pressure

    def _finish(self, r: ServeRequest, reason: str = "eos") -> None:
        r.state = RequestState.FINISHED
        r.finish_reason = reason
        r.ttlt = self.clock() - r.arrival
        self._release(r)
        self.scheduler.on_complete(r.request_id, r.generated)
        self.metrics.completed += 1
        if hasattr(self.scheduler, "calibration_summary"):
            # per-tenant coverage / CRPS over the rolling window — kept
            # current on every completion so metrics snapshots mid-run
            # see live calibration, not just the final state
            self.metrics.calibration = self.scheduler.calibration_summary()

    def _relieve_pressure(self) -> None:
        """Decode growth that returned ``grow() == False`` is surfaced
        here: force eviction until the growth fits, victims chosen by the
        scheduler's memory-aware eviction order (priority + held-KV /
        swap-cost term — the paper's hybrid true-service-cost).  Until a
        request's growth fits, its slot sits out the decode batch (the
        sampling loop skips ``_needs_grow`` members)."""
        while self._needs_grow:
            rid = next(iter(self._needs_grow))
            r = self._requests.get(rid)
            if r is None or r.done or not self.kv.holds(rid):
                self._needs_grow.discard(rid)
                continue
            if self.kv.grow(rid, 1):
                self._sync_block_table(r)
                self._needs_grow.discard(rid)
                continue
            candidates = [x for x in self._running if self.kv.holds(x)]
            if candidates == [rid]:
                # sole resident request and still no room: its context has
                # filled the physical pool — terminate by truncation, the
                # same way the max_seq_len guard ends an endless request
                self._finish(r, reason="truncated")
                continue
            if not candidates:
                break
            victims = self.scheduler.eviction_order(
                candidates,
                # owned (refcount-weighted) tokens: a heavy sharer frees
                # little real memory when evicted, so it ranks cheap to
                # keep; equals block-aligned held tokens when private
                held_tokens={x: self.kv.owned_tokens_of(x)
                             for x in candidates},
                swap_cost=lambda t: self.service_model.swap_time(
                    t, self.kv.block_size),
                memory_weight=self.memory_weight)
            self._preempt(self._requests[victims[0]])
            self.metrics.forced_evictions += 1

    # ------------------------------------------------------------- sampling

    def _sample_batch(self, logits: np.ndarray, slots: list[int],
                      temps: np.ndarray) -> np.ndarray:
        """ONE vectorized sampling pass over all decode-ready slots:
        argmax for greedy rows, inverse-CDF categorical for the rest."""
        rows = logits[slots].astype(np.float64)
        out = np.empty(len(slots), np.int64)
        greedy = temps <= 0
        if greedy.any():
            out[greedy] = rows[greedy].argmax(axis=1)
        stoch = ~greedy
        if stoch.any():
            x = rows[stoch] / temps[stoch, None]
            x -= x.max(axis=1, keepdims=True)
            p = np.exp(x)
            p /= p.sum(axis=1, keepdims=True)
            u = self._rng.random(p.shape[0])
            cdf = np.cumsum(p, axis=1)
            out[stoch] = np.minimum((cdf < u[:, None]).sum(axis=1),
                                    p.shape[1] - 1)
        return out

    # ----------------------------------------------------------------- step

    def step(self) -> int:
        """One engine iteration. Returns number of running requests."""
        now = self.clock()
        self.scheduler.set_now(now)
        selected = self._select_running()
        sel = set(selected)

        # preempt displaced requests (swap mode keeps their KV on host)
        for rid in list(self._running):
            if rid not in sel:
                self._preempt(self._requests[rid])

        # admit newcomers: swap-ins restore KV, others (re-)prefill
        for rid in selected:
            r = self._requests[rid]
            if r.state != RequestState.RUNNING:
                try:
                    self._admit(r)
                except RuntimeError:
                    if self.kv.blocks_for(r.context_len + 1) \
                            > self.kv.n_blocks:
                        # the context can NEVER fit the physical pool:
                        # reject instead of livelocking in WAITING
                        self.abort(rid, reason="infeasible_prompt")
                        continue
                    # transient shortfall (e.g. forced-top guard racing
                    # an external hog): leave the request queued
                    continue

        # capacity pressure from the previous decode's growth
        self._relieve_pressure()

        # chunked prefill, mixed with the decode batch under one budget
        self._run_prefills()

        if not self._running:
            return 0

        # decode-ready slots.  _relieve_pressure drains _needs_grow every
        # step before this point (a pressured resident request is always
        # grown, evicted, or truncation-finished), so the filter below is
        # a defensive invariant guard: if a future path ever leaves a
        # pressured slot resident, sampling it would append a token whose
        # KV write lands in scratch and is lost.
        ready = [(slot, rid) for slot, rid in sorted(self._slot_rid.items())
                 if self._cache_len[slot] >= 0
                 and rid not in self._needs_grow]
        if not ready:
            return len(self._running)

        if self.step_mode == "fused":
            self._decode_fused(ready)
        else:
            self._decode_orchestrated(ready)
        return len(self._running)

    def _decode_orchestrated(self, ready: list[tuple[int, str]]) -> None:
        """Python-orchestrated decode iteration (the pre-fused path, kept
        as the fused step's parity oracle and benchmark baseline): one
        full-width device forward, logits shipped to the host, sampling
        and per-slot bookkeeping in numpy."""
        # one decode iteration over all slots.  Slots that are mid-prefill
        # (or free) are masked by pointing their table rows at the scratch
        # page for this call: their lane's write lands in scratch instead
        # of clobbering KV the chunked prefill already scattered.
        tokens = jnp.asarray(self._last_token[:, None], jnp.int32)
        cache_len = jnp.asarray(np.maximum(self._cache_len, 0), jnp.int32)
        tables_np = self._block_tables
        not_ready = self._cache_len < 0
        if not_ready.any():
            tables_np = tables_np.copy()
            tables_np[not_ready] = SCRATCH_BLOCK
        tables = jnp.asarray(tables_np)
        logits, self._cache = self._decode_fn(self.params, tokens,
                                              self._cache, cache_len,
                                              tables)
        logits_np = np.asarray(logits, np.float32)
        self.metrics.decode_iterations += 1

        slots = [s for s, _ in ready]
        rids = [rid for _, rid in ready]
        temps = np.array([self._requests[rid].temperature for rid in rids])
        toks = self._sample_batch(logits_np, slots, temps)

        progressing, progressed = [], []
        for slot, rid, tok in zip(slots, rids, toks):
            r = self._requests[rid]
            tok = int(tok)
            self._cache_len[slot] += 1
            self._last_token[slot] = tok
            r.output_tokens.append(tok)
            self.metrics.decode_tokens += 1
            if np.isnan(r.ttft):
                r.ttft = self.clock() - r.arrival
            if tok == r.eos_token:
                self._finish(r, reason="eos")
                continue
            if r.generated >= r.max_new_tokens \
                    or r.context_len >= self.max_seq_len - 1:
                self._finish(r, reason="length")
                continue
            progressing.append(rid)
            progressed.append(r.generated)
            # reserve the next token's block now; a False return is
            # surfaced as capacity pressure and forces eviction at the
            # next select (previously this return value was dropped and
            # over-capacity growth went unaccounted)
            if self.kv.grow(rid, 1):
                self._sync_block_table(r)
            else:
                self.metrics.grow_failures += 1
                self._needs_grow.add(rid)
        self.scheduler.on_progress_many(progressing, progressed)

    def _decode_fused(self, ready: list[tuple[int, str]]) -> None:
        """Fused decode: ONE jitted, donated device call advances every
        ready lane by up to ``decode_steps`` tokens (attention, sampling,
        KV/state writes, EOS/length bookkeeping all on-device in a
        fori_loop); the host gets back one (tokens, emitted, finished)
        transfer and only does block accounting + scheduler feedback.

        Lane layout: recurrent families are slot-positional (their state
        lives per-slot inside the cache); attention families gather the
        ready slots into a pow2 batch bucket.  Table width rides its own
        pow2 ladder, so batch/page churn never changes the traced shapes
        beyond the bounded bucket set."""
        n_steps = self.decode_steps
        # per-lane step budgets: cap = tokens until forced finish
        # (max_new_tokens / max_seq_len), grant = KV reserved ahead of the
        # call (a short grant pauses the lane rather than overrunning)
        plan = []                              # (slot, rid, budget, cap)
        for slot, rid in ready:
            r = self._requests[rid]
            cap = min(r.max_new_tokens - r.generated,
                      (self.max_seq_len - 1) - r.context_len)
            cap = max(1, cap)
            want = min(n_steps, cap)
            grant = self.kv.grow_upto(rid, want - 1) if want > 1 else 0
            if grant:
                self._sync_block_table(r)
            plan.append((slot, rid, grant + 1, cap))

        # ladder floors (8 lanes / 4 pages): padding a tiny batch up to
        # the floor costs almost nothing to execute, but every ladder
        # rung below it is a whole XLA compile of the fused loop — the
        # floors keep short-lived small engines from spending their
        # entire run compiling rungs they graduate out of
        if self._slot_state:
            nb = self.n_slots
            lane_of = {slot: slot for slot, _ in ready}
        else:
            nb = _pow2_bucket(len(ready), floor=8, cap=self.n_slots)
            lane_of = {slot: j for j, (slot, _) in enumerate(ready)}
        p_used = max(len(self.kv.block_table(rid)) for _, rid in ready)
        pb = _pow2_bucket(p_used, floor=4, cap=self._max_pages)

        last = np.zeros(nb, np.int32)
        cl = np.zeros(nb, np.int32)
        tables = np.full((nb, pb), SCRATCH_BLOCK, np.int32)
        budgets = np.zeros(nb, np.int32)
        caps = np.ones(nb, np.int32)
        eos = np.full(nb, -1, np.int32)
        temps = np.zeros(nb, np.float32)
        seeds = np.zeros(nb, np.uint32)
        counters = np.zeros(nb, np.int32)
        for slot, rid, budget, cap in plan:
            r = self._requests[rid]
            lane = lane_of[slot]
            last[lane] = self._last_token[slot]
            cl[lane] = self._cache_len[slot]
            tables[lane] = self._block_tables[slot, :pb]
            budgets[lane] = budget
            caps[lane] = cap
            eos[lane] = r.eos_token
            temps[lane] = r.temperature
            seeds[lane] = _rid_seed(rid)
            counters[lane] = r.generated

        dev_args = (self.params, self._cache, jnp.asarray(last),
                    jnp.asarray(cl), jnp.asarray(tables),
                    jnp.asarray(budgets), jnp.asarray(caps),
                    jnp.asarray(eos), jnp.asarray(temps),
                    jnp.asarray(seeds), jnp.asarray(counters))
        static = dict(n_steps=n_steps,
                      all_greedy=bool((temps <= 0.0).all()))
        def _abs(a):
            # host-built args (tokens, tables, budgets) carry a default
            # single-device placement; on a mesh the stash must record
            # them as replicated or a later re-lower sees a device-set
            # mismatch against the mesh-sharded params/pool
            sh = a.sharding
            if self.plan is not None and len(sh.device_set) != \
                    self.plan.mesh.size:
                sh = self.plan.replicated
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
        self._last_fused_call = (jax.tree.map(_abs, dev_args), static)
        buf, emitted, fin, self._cache = self._fused_fn(*dev_args, **static)
        # the ONE batched device->host transfer for this (multi-)step
        buf, emitted, fin = jax.device_get((buf, emitted, fin))
        self.metrics.decode_iterations += n_steps
        self.metrics.fused_steps += 1

        progressing, progressed = [], []
        for slot, rid, _, _ in plan:
            lane = lane_of[slot]
            e = int(emitted[lane])
            if e == 0:
                continue
            r = self._requests[rid]
            toks = [int(t) for t in buf[lane, :e]]
            r.output_tokens.extend(toks)
            self._cache_len[slot] += e
            self._last_token[slot] = toks[-1]
            self.metrics.decode_tokens += e
            if np.isnan(r.ttft):
                r.ttft = self.clock() - r.arrival
            if fin[lane]:
                self._finish(r, reason="eos" if toks[-1] == r.eos_token
                             else "length")
                continue
            progressing.append(rid)
            progressed.append(r.generated)
            # restore the reserve-one-ahead invariant for the next write;
            # a False return is capacity pressure, relieved by forced
            # eviction at the next select — same contract as the
            # orchestrated path's per-token grow
            if self.kv.grow(rid, 1):
                self._sync_block_table(r)
            else:
                self.metrics.grow_failures += 1
                self._needs_grow.add(rid)
        self.scheduler.on_progress_many(progressing, progressed)

    # ------------------------------------------------------ compile budget

    @property
    def fused_compile_count(self) -> int:
        """Actual XLA compile count of the fused step: jax's (private,
        but the only per-function counter there is) jit cache size."""
        return self._fused_fn._cache_size()

    def max_fused_compiles(self, n_steps_variants: int = 1) -> int:
        """Upper bound on fused-step compiles: the bucket-ladder product.
        Batch churn (admit/evict/finish) can only move shapes along the
        pow2 ladders, so the jit cache can never exceed this.  The
        final factor 2 is the all-greedy / mixed-sampling static
        specialization."""
        b_ladder = 1 if self._slot_state \
            else _ladder_size(self.n_slots, floor=8)
        return b_ladder * _ladder_size(self._max_pages, floor=4) \
            * n_steps_variants * 2

    def lower_fused_hlo(self) -> str | None:
        """Compiled HLO text of the most recent fused-step call (None
        before any decode).  Re-lowers from the stashed abstract args —
        shape/dtype/sharding only, so this is safe after donation — for
        the roofline bench's ``collective_bytes`` accounting."""
        if self._last_fused_call is None:
            return None
        abstract, static = self._last_fused_call
        return self._fused_fn.lower(*abstract, **static).compile().as_text()

    def sharding_report(self) -> dict | None:
        """Per-component sharding outcome on this engine's mesh (None on
        the single-device path) — see ShardingPlan.describe()."""
        return None if self.plan is None else self.plan.describe()

    def stall_report(self) -> dict:
        """Live-state diagnosis: per-state request counts, queue depth,
        pool occupancy, pressure set — the payload of EngineStallError."""
        states = Counter(r.state.name for r in self._requests.values())
        waiting = [rid for rid, r in self._requests.items()
                   if not r.done and rid not in self._running]
        return {
            "request_states": dict(states),
            "queue_depth": len(waiting),
            "running": list(self._running),
            "needs_grow": sorted(self._needs_grow),
            "kv": self.kv.conservation(),
        }

    def run_until_done(self, max_steps: int = 100_000) -> None:
        for _ in range(max_steps):
            if not self.has_work:
                return
            self.step()
        if not self.has_work:
            return
        raise EngineStallError(
            f"run_until_done: step budget ({max_steps}) exhausted with "
            f"work still live — {self.stall_report()}")
