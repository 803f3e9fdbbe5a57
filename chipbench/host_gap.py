"""The host gap between consecutive fused decode calls, split by phase.

For each pair of consecutive fused calls in the traced slice, the
device-idle stretches between them go to the innermost host span that
covers them, by the rule ``tracing.reduce`` names ``idle_gaps`` with.
The program writes ``engine.<phase>`` and ``gateway.tick`` spans; the
harness writes ``gateway.step``, ``gateway.offer`` and
``scheduler.<call>``.  The readers ``host_gap_ms.<part>`` and
``host_gap_unattributed_share`` read the result; with every other named
span they add up to ``host_gap_ms``.  They need the slice's host spans
under ``rec.trace["host"]`` and find nothing without them.
"""

from __future__ import annotations

from chipbench import tracing
from chipbench.readers import fused_events

__all__ = ["PARTS", "UNATTRIBUTED", "idle_by_span", "part_ms",
           "unattributed_share"]

# the spans each part of the gap is made of; a name ending in "." is a
# prefix
PARTS = {
    "prepare": ("engine.decode_prepare",),
    "commit": ("engine.decode_commit",),
    "schedule": ("engine.select", "engine.admit", "engine.relieve",
                 "scheduler."),
    "prefill": ("engine.prefill",),
}
# idle that no span finer than the harness's whole step names
UNATTRIBUTED = ("gateway.step", "host (no span)")


def idle_by_span(rec) -> tuple[dict[str, float], int] | None:
    """Device-idle nanoseconds between consecutive fused calls, by the
    innermost host span, and the number of gaps; None without two fused
    calls or without the slice's host spans."""
    host = (rec.trace or {}).get("host")
    ev = fused_events(rec)
    if host is None or len(ev) < 2:
        return None
    busy, out = rec.trace["busy0"], {}
    for (_, a), (b, _) in zip(ev, ev[1:]):
        if b <= a:
            continue
        for name, t in tracing._idle_by_span(busy, host, a, b).items():
            out[name] = out.get(name, 0.0) + t
    return out, len(ev) - 1


def _in(name: str, spans) -> bool:
    return any(name.startswith(s) if s.endswith(".") else name == s
               for s in spans)


def part_ms(rec, part: str) -> float | None:
    """Mean idle per gap (ms) under the spans of ``PARTS[part]``."""
    got = idle_by_span(rec)
    if got is None:
        return None
    by_span, n = got
    return sum(t for s, t in by_span.items() if _in(s, PARTS[part])) \
        / n * 1e-6


def unattributed_share(rec) -> float | None:
    """Share (%) of the gaps' idle time that no span finer than
    ``gateway.step`` names."""
    got = idle_by_span(rec)
    if got is None:
        return None
    by_span, _ = got
    total = sum(by_span.values())
    if total <= 0:
        return None
    return 100.0 * sum(t for s, t in by_span.items()
                       if _in(s, UNATTRIBUTED)) / total
