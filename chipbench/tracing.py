"""The profiler slice of a ``--trace 1`` run and its reduction.

``load`` reads the profiler's ``.xplane.pb`` into plain lists: device
events per line of each TPU plane, and the host spans this benchmark
writes (``TraceAnnotation`` names with a dot, such as ``gateway.step``).
``reduce`` turns those lists into the numbers the per-layer readers and
the ``device``/``breakdown`` keys use.  Both work on a recorded trace
(``tests/data``), so every PR reduces a trace the same way.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
from dataclasses import dataclass, field

__all__ = ["Trace", "load", "load_json", "dump_json", "union", "reduce",
           "idle_between", "WINDOW_SPAN"]

WINDOW_SPAN = "chipbench.window"      # host span around the traced slice
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Trace:
    # line name -> [(name, start_ns, end_ns)] per device plane
    devices: list[dict[str, list[tuple[str, float, float]]]] = \
        field(default_factory=list)
    host: list[tuple[str, float, float]] = field(default_factory=list)


def load(logdir: str) -> Trace:
    """The newest ``.xplane.pb`` under ``logdir``."""
    import jax
    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [(_short(e.name), e.start_ns,
                                         e.end_ns) for e in line.events]
            tr.devices.append(lines)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if "." in e.name and " " not in e.name \
                            and e.name.split(".")[0] in (
                                "gateway", "scheduler", "harness",
                                "chipbench"):
                        tr.host.append((e.name, e.start_ns, e.end_ns))
    tr.host.sort(key=lambda x: x[1])
    return tr


def _short(name: str) -> str:
    """An operation's HLO name without its text (``%fusion.3 = ...``)."""
    return name.split(" = ", 1)[0]


def dump_json(tr: Trace, path: str) -> None:
    with open(path, "w") as f:
        json.dump({"devices": tr.devices, "host": tr.host}, f)


def load_json(path: str) -> Trace:
    with open(path) as f:
        d = json.load(f)
    return Trace(devices=[{k: [tuple(e) for e in v] for k, v in dev.items()}
                          for dev in d["devices"]],
                 host=[tuple(e) for e in d["host"]])


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals clipped to [lo, hi]."""
    out: list[list[float]] = []
    for _, s, e in sorted(intervals, key=lambda x: x[1]):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _timeline(host, lo: float, hi: float) -> list[tuple[float, float, str]]:
    """[lo, hi] cut at every host span boundary, each stretch with the
    innermost (shortest) span other than the window that covers it, or
    "host (no span)"."""
    spans = sorted((s, e, n) for n, s, e in host
                   if n != WINDOW_SPAN and e > lo and s < hi)
    cuts = sorted({lo, hi} | {t for s, e, _ in spans for t in (s, e)
                              if lo < t < hi})
    out, active, j = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        while j < len(spans) and spans[j][0] <= mid:
            active.append(spans[j])
            j += 1
        active = [x for x in active if x[1] >= mid]
        name = min(active, key=lambda x: x[1] - x[0])[2] if active \
            else "host (no span)"
        out.append((a, b, name))
    return out


def _idle_by_span(busy, host, lo: float, hi: float) -> dict[str, float]:
    """Nanoseconds in [lo, hi] outside ``busy`` (merged, sorted), by the
    innermost host span they fell in."""
    idle, out, k = [], {}, 0
    prev = lo
    for s, e in busy + [(hi, hi)]:
        if s > prev:
            idle.append((prev, s))
        prev = max(prev, e)
    for a, b, name in _timeline(host, lo, hi):
        while k < len(idle) and idle[k][1] <= a:
            k += 1
        i = k
        while i < len(idle) and idle[i][0] < b:
            d = min(b, idle[i][1]) - max(a, idle[i][0])
            if d > 0:
                out[name] = out.get(name, 0.0) + d
            i += 1
    return out


def reduce(tr: Trace, top: int = 10) -> dict:
    """Window, busy time (averaged over devices), per-module events, the
    top device operations and the idle time by host span."""
    win = [(s, e) for n, s, e in tr.host if n == WINDOW_SPAN]
    if not win or not tr.devices:
        return {}
    lo, hi = win[0]
    busy_total, ops_total = 0.0, {}
    modules: dict[str, list[tuple[float, float]]] = {}
    busy0: list[tuple[float, float]] = []
    for i, dev in enumerate(tr.devices):
        ops = dev.get(OPS_LINE) or dev.get(MODULES_LINE) or []
        merged = union(ops, lo, hi)
        busy_total += sum(e - s for s, e in merged)
        if i == 0:
            busy0 = merged
            for name, s, e in dev.get(MODULES_LINE, []):
                if lo <= s and e <= hi:
                    modules.setdefault(name, []).append((s, e))
            for name, s, e in dev.get(OPS_LINE, []):
                if lo <= s and e <= hi:
                    ops_total[name] = ops_total.get(name, 0.0) + (e - s)
    # device 0's idle time, named by what the host was doing
    idle_by_span = _idle_by_span(busy0, tr.host, lo, hi)
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": busy_total / len(tr.devices) * ns,
        "busy0": busy0,
        "modules": modules,
        "top_ops": sorted(([n, t * ns] for n, t in ops_total.items()),
                          key=lambda x: -x[1])[:top],
        "idle_gaps": sorted(([n, t * ns] for n, t in idle_by_span.items()),
                            key=lambda x: -x[1])[:top],
    }


def idle_between(busy, a: float, b: float) -> float:
    """Device-idle nanoseconds inside [a, b]; ``busy`` is merged and
    sorted, as ``union`` returns it."""
    covered = 0.0
    i = bisect.bisect_left(busy, a, key=lambda x: x[1])
    while i < len(busy) and busy[i][0] < b:
        s, e = busy[i]
        covered += min(e, b) - max(s, a)
        i += 1
    return max(0.0, (b - a) - covered)
