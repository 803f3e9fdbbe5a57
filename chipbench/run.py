#!/usr/bin/env python3
"""The chip benchmark: one cell of ``BENCHMARK.json``, one run.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Builds the cell's served path on the chip (weights from ``--seed``),
warms every shape the cell reaches, runs warm traffic, measures an
open-loop window of ``--seconds``, checks what it served against the
configuration's float32 reference, and prints one JSON object as its
last line: the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics (and the trace's ``breakdown``) with ``--trace 1``.
It exits non-zero with no JSON line when JAX's first device is not a
TPU or there are fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_PROC0 = time.monotonic()

import argparse                                         # noqa: E402
import gc                                               # noqa: E402
import json                                             # noqa: E402
import os                                               # noqa: E402
import sys                                              # noqa: E402
from pathlib import Path                                # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent / "src")]
# the compile cache lives at a fixed path inside the checkout; JAX reads
# the variable when it first compiles
CACHE_DIR = HERE.parent / ".jax_cache"
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(n: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chipbench: JAX's first device is "
                         f"{devices[0].platform!r}, not a TPU; no result")
    if len(devices) < n:
        raise SystemExit(f"chipbench: the cell needs {n} chips, JAX sees "
                         f"{len(devices)}; no result")
    return devices


def configure_jax() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def measure(cell, seed: int, seconds: float, trace: bool, devices,
            t_proc0: float) -> tuple[dict, list]:
    """The whole run after the device check: returns (result, checks)."""
    import jax
    from chipbench import harness as H
    from chipbench import traffic as T
    from chipbench import work
    from chipbench.check import gaps
    from chipbench.spec import reader

    kind = devices[0].device_kind
    peaks = work.peaks(kind)
    rec = H.RunRecord(cell, seed, seconds, kind, peaks)
    cc = H.CompileCounter()
    engine, gateway = H.build(cell, seed)
    log(f"[{time.monotonic() - t_proc0:.1f} s] built: weights and engine")
    H.warm(engine, cell.config["vocab_size"])
    log(f"[{time.monotonic() - t_proc0:.1f} s] every shape warmed: "
        f"{cc.compiled} programs compiled, {cc.loaded} loaded from the "
        f"persistent cache")
    H.install_scheduler(engine, cell)
    pop = T.population(cell.traffic, seconds)
    reqs = T.assign(pop, seed, cell.config["vocab_size"])
    annotate = jax.profiler.TraceAnnotation if trace else None
    H.serve(cell, seed, seconds, rec, engine, gateway, reqs,
            trace_slice=float(cell.traffic.get("trace_slice_s", 3.0))
            if trace else 0.0, annotate=annotate, t_proc0=t_proc0,
            compiles=cc)
    log(f"[{time.monotonic() - t_proc0:.1f} s] loop done: setup "
        f"{rec.setup_s:.1f} s, {len(rec.reqs)} offered, "
        f"{len(rec.window_reqs())} in the window, "
        f"{len(rec.window_steps())} window steps, window compiles "
        f"{rec.window_compiles}, counters {rec.counters0} -> "
        f"{rec.counters1}")
    late = sorted(rec.lateness)
    log(f"generator lateness (offer - due): median "
        f"{late[len(late) // 2]:.6f} s, max {late[-1]:.6f} s over "
        f"{len(late)} offers")
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices[:cell.chips])
    host = H.finish_run(rec, engine, gateway)
    sample = H.sample_for_check(rec, seed, int(cell.traffic.get(
        "check_requests", 8)))
    streams = [(list(s.sr.prompt_tokens), list(s.sr.output_tokens))
               for s in sample]
    del engine, gateway
    gc.collect()
    # the reference runs on the seed's weights made anew, after the
    # program's state is gone
    ref, prog = cell.reference(), cell.program()
    w = ref.make_weights(cell.config, seed, prog.vocab_rows(cell.config))
    got = gaps(ref, cell.config, w, streams,
               pad_to=cell.traffic["serve"]["max_seq_len"])
    del w
    log(f"[{time.monotonic() - t_proc0:.1f} s] reference done")
    checks = checks_for(cell, got, rec.window_compiles,
                        host["ledger_violations"])
    nan = float("nan")
    log(f"reference: {len(streams)} greedy requests, {got['tokens']} "
        f"served tokens; {got.get('argmax_share', 0):.4f} of them the "
        f"reference's argmax; mean gap {got.get('mean_gap', nan):.6e}; "
        f"widest gap as a share of the row's spread "
        f"{got.get('gap_share', nan):.6e}")
    names = [m["name"] for m in (cell.per_layer if trace else
                                 cell.end_to_end)]
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = reader(m["name"]).read(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    attempted = len(rec.window_reqs())
    failed = sum(1 for s in rec.window_reqs() if s.sr.state.value in
                 ("shed", "aborted"))
    device = {"platform": devices[0].platform, "kind": kind,
              "count": cell.chips, "memory_peak_bytes": peak}
    result = {"attempted": attempted, "failed": failed,
              "metrics": {k: metrics[k] for k in names if k in metrics},
              "device": device}
    if trace and rec.trace:
        device["busy_s"] = rec.trace["busy_s"]
        device["window_s"] = rec.trace["window_s"]
        result["breakdown"] = {"device_ops": rec.trace["top_ops"],
                               "idle_gaps": rec.trace["idle_gaps"]}
    return result, checks


def checks_for(cell, got: dict, window_compiles: int,
               ledger_violations: int, prefix: str = "") -> list:
    """The numbers compared, each with its kind and limit, from the
    reference's readings ``got`` (``prefix`` reads a control's)."""
    limits = cell.config["limits"]
    inf = float("inf")
    return [
        ("served_logit_gap", got.get(prefix + "gap", inf), "max",
         limits["served_logit_gap"]),
        ("tokens_compared", got["tokens"], "min",
         int(cell.traffic.get("check_min_tokens", 200))),
        ("window_compiles", window_compiles, "max", 0),
        ("ledger_violations", ledger_violations, "max", 0),
    ]


def correct(checks) -> bool:
    """Every compared number within its limit."""
    return all((v <= lim) if kind == "max" else (v >= lim)
               for _, v, kind, lim in checks)


def _finite(v):
    """JSON has no infinity: a number that never came reads as null."""
    return v if isinstance(v, int) or (v == v and abs(v) != float("inf")) \
        else None


def main(argv=None) -> int:
    args = parse_args(argv)
    from chipbench.spec import Spec
    cell = Spec().cell(args.workload)
    devices = require_chips(cell.chips)
    configure_jax()
    result, checks = measure(cell, args.seed, args.seconds, bool(args.trace),
                             devices, T_PROC0)
    for name, v, kind, lim in checks:
        log(f"check {name}: {v} ({kind} {lim})")
    out = {"correct": correct(checks), **result,
           "checks": {name: {"value": _finite(v), kind: lim}
                      for name, v, kind, lim in checks}}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
