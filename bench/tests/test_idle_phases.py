"""The idle_ms.* readers (bench/phases.py) on a synthetic trace with known
answers, and on the recorded v5e step, whose program records no phase
spans."""
import gzip
import json
import pathlib
from types import SimpleNamespace

import pytest

from bench import harness, stats, trace

FIX = pathlib.Path(__file__).parent / "fixtures" / "decode_step_v5e.json.gz"
LAUNCH, SYNC, HOST = (harness.load_reader(f"idle_ms.{m}")
                      for m in ("launch", "sync", "host"))


def run_of(tr):
    win = tr.span("bench.traced")
    steps = {int(n.split("#")[1]): (s, e) for n, s, e in tr.spans
             if n.startswith("bench.step#")}
    return SimpleNamespace(trace=tr, traced=(win[1], win[2]),
                           traced_steps=steps)


def synthetic():
    # step 0 (10-500) is traced; step 1 ends past the traced window. Chip 0
    # runs a stray op inside engine.plan and the program 110-380; chip 1
    # the program 110-400.
    spans = [("bench.traced", 0, 1000), ("bench.step#0", 10, 500),
             ("engine.step", 20, 490), ("engine.plan", 20, 40),
             ("engine.inputs", 40, 100), ("engine.launch", 100, 120),
             ("engine.wait", 120, 400), ("engine.stats", 400, 420),
             ("engine.emit", 420, 490), ("bench.step#1", 995, 1100),
             ("engine.step", 996, 1099), ("engine.wait", 996, 1099)]
    chips = [trace._chip("/device:TPU:0", [],
                         [(30, -5, "%fusion.1"), (110, -270, "%fusion.2")]),
             trace._chip("/device:TPU:1", [], [(110, -290, "%fusion.2")])]
    return trace.Trace(chips, spans)


def test_phase_idle_known_answers():
    run = run_of(synthetic())
    # chip 0: idle 40-110 in inputs+launch, 380-400 in wait, 20-30 and
    # 35-40 in plan, 400-490 in stats+emit; chip 1: 40-110, none, 20-40,
    # 400-490. Idle 10-20 and 490-500 lies in no phase.
    assert LAUNCH(run) == pytest.approx(70e-6)
    assert SYNC(run) == pytest.approx(10e-6)
    assert HOST(run) == pytest.approx(107.5e-6)


def test_phase_idle_within_the_window_idle():
    run = run_of(synthetic())
    lo, hi = run.traced
    idle = sum(stats.covered(stats.gaps(trace.busy(c, lo, hi), lo, hi))
               for c in run.trace.chips) / len(run.trace.chips) / 1e6
    steps = [1 for s, e in run.traced_steps.values() if s >= lo and e <= hi]
    assert (LAUNCH(run) + SYNC(run) + HOST(run)) * len(steps) <= idle


def test_no_phase_spans_reads_nothing():
    tr = synthetic()
    tr.spans = [s for s in tr.spans
                if s[0] not in ("engine.inputs", "engine.launch",
                                "engine.wait", "engine.stats", "engine.emit")]
    run = run_of(tr)
    assert (LAUNCH(run), SYNC(run), HOST(run)) == (None, None, None)
    run.trace = None
    assert LAUNCH(run) is None


def test_recorded_step_without_phase_spans_reads_nothing():
    tr = trace.undump(json.loads(gzip.decompress(FIX.read_bytes())))
    tr.spans += [("bench.traced", 7312.0e6, 7512.0e6),
                 ("bench.step#0", 7312.0e6, 7512.0e6)]
    run = run_of(tr)
    assert (LAUNCH(run), SYNC(run), HOST(run)) == (None, None, None)


def test_busy_is_merged_once_per_chip(monkeypatch):
    # the merge walks every op of the trace; once per step it took minutes
    # on a real window
    calls = []
    real = trace.busy
    monkeypatch.setattr(trace, "busy",
                        lambda c, lo, hi: calls.append(c) or real(c, lo, hi))
    tr = synthetic()
    tr.spans += [("bench.step#2", 500, 990), ("engine.wait", 600, 700)]
    run = run_of(tr)
    assert SYNC(run) == pytest.approx(55e-6)   # (10 + 100) / 2 steps
    assert len(calls) == len(tr.chips)
