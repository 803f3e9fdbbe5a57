#!/usr/bin/env python3
"""Find a cell's knee once, on the chip: the highest offered rate at
which the backlog does not grow.

    python chipbench/sweep.py --workload <cell> --seed <n> \
        --rates 2,3,4,5 --seconds 40 [--pick chat-overload=1.25]

One process builds and warms the cell's served path once, then offers the
cell's mix at each base rate in turn (bursts and all, a fresh scheduler
and gateway each time, the previous rate's requests aborted), and prints
one JSON line per rate: output tokens/s, TTFT median and tail, and the
backlog (requests due but not yet running) at the window's start, middle
and end.  Its last line is the knee (``knee``): the highest rate swept
whose backlog at the window's end is at most ``SUSTAINED_BACKLOG`` and
that shed nothing, bracketed by a higher swept rate that is not
sustained.  With ``--pick``, each named traffic mix gets ``rate_rps`` =
factor x knee.  Where the sweep does not bracket the knee it picks
nothing and exits non-zero.  The benchmark's runs never call this.
"""

from __future__ import annotations

import time

T_PROC0 = time.monotonic()

import argparse                                         # noqa: E402
import json                                             # noqa: E402
import re                                               # noqa: E402
import sys                                              # noqa: E402
from pathlib import Path                                # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from chipbench import run as R                          # noqa: E402


SUSTAINED_BACKLOG = 5


def knee(rows: list[dict]) -> float | None:
    """The highest swept rate that is sustained, when a higher swept rate
    is not; None where the sweep does not bracket the knee."""
    def ok(r):
        return (r["backlog_start_mid_end"][2] <= SUSTAINED_BACKLOG
                and r["shed"] == 0)
    rates = sorted(rows, key=lambda r: r["rate_rps"])
    best = None
    for r in rates:
        if not ok(r):
            break
        best = r["rate_rps"]
    if best is None or all(ok(r) for r in rates):
        return None
    return best


def pick(traffic_dir: Path, k: float, factors: dict) -> dict:
    """Write factor x knee as ``rate_rps`` into each named mix file."""
    out = {}
    for name, f in factors.items():
        p = traffic_dir / f"{name}.json"
        rate = round(f * k, 2)
        text, n = re.subn(r'"rate_rps": [0-9.]+', f'"rate_rps": {rate}',
                          p.read_text())
        if n != 1:
            raise ValueError(f"{p}: expected one rate_rps")
        p.write_text(text)
        out[name] = rate
    return out


def backlog(rec, t: float) -> int:
    return sum(1 for s in rec.reqs if s.due <= t
               and not (s.running == s.running and s.running <= t)
               and s.sr.state.value not in ("shed", "aborted"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--pick", default="",
                    help="mix=factor,... : write factor x knee into each")
    args = ap.parse_args(argv)
    from chipbench.spec import Spec
    cell = Spec().cell(args.workload)
    devices = R.require_chips(cell.chips)
    R.configure_jax()
    from chipbench import harness as H
    from chipbench import readers
    from chipbench import traffic as T

    engine, _ = H.build(cell, args.seed)
    H.warm(engine, cell.config["vocab_size"])
    R.log(f"[{time.monotonic() - T_PROC0:.1f} s] warmed")
    rows = []
    for rate in [float(x) for x in args.rates.split(",")]:
        mix = dict(cell.traffic, rate_rps=rate)
        c = H.Cell(cell.name, cell.config, mix, cell.chips, cell.end_to_end,
                   cell.per_layer)
        H.install_scheduler(engine, c)
        gateway = H.gateway_for(engine)
        rec = H.RunRecord(c, args.seed, args.seconds,
                          devices[0].device_kind, {})
        reqs = T.assign(T.population(mix, args.seconds), args.seed,
                        cell.config["vocab_size"])
        H.serve(c, args.seed, args.seconds, rec, engine, gateway, reqs,
                t_proc0=T_PROC0, grace_s=0.0)
        mid = (rec.w0 + rec.w1) / 2
        out = {
            "rate_rps": rate,
            "offered_in_window": len(rec.window_reqs()),
            "output_tokens_per_s": readers.window_tokens(rec)
            / (rec.w1 - rec.w0),
            "ttft_p50_s": readers.quantile(readers.ttfts(rec), 0.5),
            "ttft_p95_s": readers.quantile(readers.ttfts(rec), 0.95),
            "itl_p95_ms": 1e3 * (readers.quantile(readers.itl_gaps(rec),
                                                  0.95) or 0.0),
            "backlog_start_mid_end": [backlog(rec, rec.w0),
                                      backlog(rec, mid),
                                      backlog(rec, rec.w1)],
            "shed": rec.counters1["shed"] - rec.counters0["shed"],
            "preemptions": rec.counters1["preemptions"]
            - rec.counters0["preemptions"],
            "window_compiles": rec.window_compiles,
            "mean_lanes": sum(len(s.positions) for s in rec.window_steps())
            / max(1, len(rec.window_steps())),
            "steps_per_s": len(rec.window_steps()) / (rec.w1 - rec.w0),
        }
        print(json.dumps(out), flush=True)
        rows.append(out)
        for s in rec.reqs:
            if not s.sr.done:
                engine.abort(s.sr.request_id, reason="sweep_reset")
        engine.step()
    k = knee(rows)
    factors = {kv.split("=")[0]: float(kv.split("=")[1])
               for kv in args.pick.split(",") if kv}
    picked = pick(Path(__file__).resolve().parent / "traffic", k, factors) \
        if k is not None else {}
    print(json.dumps({"knee": k, "picked": picked}), flush=True)
    return 0 if k is not None else 1


if __name__ == "__main__":
    sys.exit(main())
