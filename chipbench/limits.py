#!/usr/bin/env python3
"""The readings a cell's correctness limit is set from, on the chip.

    python chipbench/limits.py --workload <cell> --seeds 11,12,13

One process builds and warms the cell's served path once.  For each seed
it puts that seed's weights in the engine's place, offers the cell's mix
at the cell's own rate through the same open loop as ``run.py``, with
the cell's own warm traffic and window, and once the window has closed reads, on the greedy
requests drawn for the check, the widest and the mean gap by which a
served token's logit lies below the float32 reference's best (the
program's readings), and the same gaps for the token that each control
(the reference one precision lower: int8 or float8 weights) puts first
at each position of the same streams (the controls' readings).  Each
reading goes through ``run.checks_for`` and ``run.correct`` as a run's
would (``correct``, ``<control>_correct``).  It prints one JSON line per
seed.  The benchmark's runs never call this; the limits it supports are
written into the configuration file by hand.
"""

from __future__ import annotations

import time

T_PROC0 = time.monotonic()

import argparse                                         # noqa: E402
import gc                                               # noqa: E402
import json                                             # noqa: E402
import sys                                              # noqa: E402
from pathlib import Path                                # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from chipbench import run as R                          # noqa: E402

CONTROLS = ("int8", "fp8")
CONTROL_PREFIXES = tuple(f"{m}_" for m in CONTROLS)


def read_seed(cell, engine, seed: int, seconds: float,
              t_proc0: float) -> dict:
    """Serve one seed's traffic on that seed's weights and read both
    gaps.  The engine's KV pool is dropped while the reference runs and
    made anew after it."""
    from chipbench import harness as H
    from chipbench import traffic as T
    from chipbench.check import gaps

    ref, prog = cell.reference(), cell.program()
    cfg, mix = cell.config, cell.traffic
    w = ref.make_weights(cfg, seed, prog.vocab_rows(cfg))
    engine.params = prog.params(w)
    H.install_scheduler(engine, cell)
    gateway = H.gateway_for(engine)
    rec = H.RunRecord(cell, seed, seconds, "", {})
    reqs = T.assign(T.population(mix, seconds), seed, cfg["vocab_size"])
    H.serve(cell, seed, seconds, rec, engine, gateway, reqs,
            t_proc0=t_proc0)
    for s in rec.reqs:
        if not s.sr.done:
            engine.abort(s.sr.request_id, reason="limits_reset")
    ledger = H.finish_run(rec, engine, gateway)
    sample = H.sample_for_check(rec, seed, int(mix.get("check_requests", 8)))
    streams = [(list(s.sr.prompt_tokens), list(s.sr.output_tokens))
               for s in sample]
    engine._cache = None
    gc.collect()
    got = gaps(ref, cfg, w, streams, pad_to=mix["serve"]["max_seq_len"],
               controls=CONTROLS)
    engine._cache = engine.model.init_paged_cache(
        engine.kv.pool_blocks, engine.block_size, engine.n_slots)
    verdicts = {f"{p}correct": R.correct(R.checks_for(
        cell, got, rec.window_compiles, ledger["ledger_violations"],
        prefix=p)) for p in ("", *CONTROL_PREFIXES)}
    return {"seed": seed, "window_requests": len(rec.window_reqs()),
            "window_compiles": rec.window_compiles, **ledger, **got,
            **verdicts}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    from chipbench.spec import Spec
    spec = Spec()
    cell = spec.cell(args.workload)
    R.require_chips(cell.chips)
    R.configure_jax()
    from chipbench import harness as H

    seconds = float(spec.doc["run_seconds"])
    seeds = [int(x) for x in args.seeds.split(",")]
    engine, _ = H.build(cell, seeds[0])
    H.warm(engine, cell.config["vocab_size"])
    R.log(f"[{time.monotonic() - T_PROC0:.1f} s] warmed")
    for seed in seeds:
        out = read_seed(cell, engine, seed, seconds, T_PROC0)
        print(json.dumps(out), flush=True)
        R.log(f"[{time.monotonic() - T_PROC0:.1f} s] seed {seed} read")
    return 0


if __name__ == "__main__":
    sys.exit(main())
