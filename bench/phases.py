"""Device idle time charged to the engine's host phases.

``Engine.step`` runs as one ``engine.step`` profiler span tiled by phase
spans (``engine.plan``, ``engine.inputs``, ``engine.launch``,
``engine.wait``, ``engine.stats``, ``engine.emit``; DESIGN.md §9). For each
traced step (its ``bench.step#i`` span inside ``bench.traced``), the
device's idle intervals inside the step are intersected with the union of
the named phase spans that lie inside it; the mean over chips and steps
is the idle the host spent in those phases.
"""
from __future__ import annotations

import bisect

from bench import stats, trace as tr


def idle_ms(run, phases: tuple) -> float | None:
    """Mean device idle per traced step inside ``phases``, ms. None where
    the trace holds no span of one of ``phases`` (a program that does not
    record them) or no traced step."""
    if run.trace is None:
        return None
    spans = [s for s in run.trace.spans if s[0] in phases]
    if {s[0] for s in spans} != set(phases):
        return None
    lo, hi = run.traced
    steps = [(s, e) for s, e in run.traced_steps.values()
             if s >= lo and e <= hi]
    if not steps:
        return None
    total = 0.0
    for chip in run.trace.chips:
        busy = tr.busy(chip, lo, hi)      # once per chip: it merges every op
        starts = [a for a, _ in busy]
        for s, e in steps:
            near = busy[max(bisect.bisect_right(starts, s) - 1, 0):
                        bisect.bisect_left(starts, e)]
            idle = stats.gaps(near, s, e)
            inside = stats.merge_intervals(
                (a, b) for _, a, b in spans if a >= s and b <= e)
            total += sum(stats.covered(stats.clip_intervals(idle, a, b))
                         for a, b in inside)
    return total / len(steps) / len(run.trace.chips) / 1e6
