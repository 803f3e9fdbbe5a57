"""Shared arithmetic of the metric readers in ``metrics/``.  Each reader
is one file, ``read(rec) -> float | None``; None leaves the metric out
of the run's line."""

from __future__ import annotations

import numpy as np

from chipbench import work

__all__ = ["quantile", "ttfts", "queue_waits", "itl_gaps", "window_tokens",
           "fused_events", "prefill_ns", "traced_prefill_tokens", "window_flops"]

FUSED = "fused_steps"      # the engine's jitted fused decode step
CHUNK = "jit_chunk"        # its prefill chunk program
SCATTER = "jit_scatter"    # the program that writes a chunk's KV


def quantile(values, q: float) -> float | None:
    v = np.asarray(values, float)
    return float(np.quantile(v, q)) if v.size else None


def ttfts(rec) -> list[float]:
    """Due time to first token of every request due in the window; one
    without a first token counts the time it had waited when the run
    stopped waiting."""
    return [(s.times[0] if s.times else rec.end) - s.due
            for s in rec.window_reqs()]


def queue_waits(rec) -> list[float]:
    out = []
    for s in rec.window_reqs():
        t = s.running if s.running == s.running else rec.end
        out.append(t - s.due)
    return out


def itl_gaps(rec) -> list[float]:
    """Every gap between successive tokens of one request whose later
    token was stamped inside the window."""
    out = []
    for s in rec.reqs:
        t = s.times
        for a, b in zip(t, t[1:]):
            if rec.w0 <= b < rec.w1:
                out.append(b - a)
    return out


def window_tokens(rec) -> int:
    return sum(1 for s in rec.reqs for t in s.times if rec.w0 <= t < rec.w1)


def _module_name(name: str) -> str:
    return name.split("(")[0]


def fused_events(rec) -> list[tuple[float, float]]:
    if not rec.trace:
        return []
    out = []
    for name, evs in rec.trace["modules"].items():
        if FUSED in _module_name(name):
            out.extend(evs)
    return sorted(out)


def prefill_ns(rec) -> float | None:
    """Device time of each prefill chunk program and of the scatter
    program that writes its KV, the next scatter the device runs."""
    if not rec.trace:
        return None
    evs = sorted((s, e, _module_name(n)) for n, es in
                 rec.trace["modules"].items() for s, e in es)
    total, n = 0.0, 0
    for i, (s, e, name) in enumerate(evs):
        if name == CHUNK:
            total += e - s
            n += 1
            nxt = next((x for x in evs[i + 1:] if x[2] == SCATTER), None)
            if nxt is not None:
                total += nxt[1] - nxt[0]
    return total if n else None


def traced_prefill_tokens(rec) -> int:
    if not rec.trace_counters:
        return 0
    c0, c1 = rec.trace_counters
    return c1["prefill_tokens"] - c0["prefill_tokens"]


def window_flops(rec) -> int:
    cfg = rec.cell.config
    f = 0
    for st in rec.window_steps():
        f += st.prompt_flops
        f += sum(work.token_flops(cfg, p) for p in st.positions)
    return f
