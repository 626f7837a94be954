"""Trace schema v2 on a checked-in fixture + writer crash safety.

- a checked-in v2 step-record fixture stays valid under the validator, the
  CLI, and the ``roofline.py --obs`` summary path;
- the validator rejects what it must: a v1 record (no ``rec``), unknown
  versions, a bad ``rec``;
- TraceWriter lands the buffered tail when the process dies on an
  unhandled exception (atexit fallback, exercised in a subprocess) and
  when the engine loop errors mid-run (flush-on-error).
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.obs.trace import validate_event, validate_file
from repro.obs.trace import main as trace_main

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_v2.jsonl")


# ---------------------------------------------------------------------------
# the checked-in fixture
# ---------------------------------------------------------------------------

def test_v1_fixture_validates(capsys):
    assert validate_file(FIXTURE) == []
    assert trace_main([FIXTURE]) == 0
    assert "5 records (step=5)" in capsys.readouterr().out


def test_v1_fixture_summarizes_in_roofline(capsys):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.roofline import trace_summary
    with open(FIXTURE) as f:
        events = [json.loads(ln) for ln in f]
    rows = trace_summary(events)
    kinds = {r["kind"] for r in rows}
    assert kinds == {"prefill", "mixed", "decode"}   # idle dropped
    decode = next(r for r in rows if r["kind"] == "decode")
    assert decode["steps"] == 2
    assert decode["tokens_per_step"] == pytest.approx(2.5)
    # optional devstat fields may be absent (obs off)
    assert decode["pages_churn_per_step"] == pytest.approx(1.0)


def test_version_dispatch():
    with open(FIXTURE) as f:
        v2 = json.loads(f.readline())
    assert validate_event(v2) == []
    # a v1 record (the flat step event before ``rec``: v == 1) is
    # rejected, with or without its version field
    v1 = {k: v for k, v in v2.items() if k != "rec"}
    assert any("is not 2" in e for e in validate_event(dict(v1, v=1)))
    unversioned = {k: v for k, v in v1.items() if k != "v"}
    assert any("is not 2" in e for e in validate_event(unversioned))
    # v2 requires the discriminator, and rejects unknown versions
    assert any("bad rec" in e for e in validate_event(dict(v1, v=2)))
    assert any("bad rec" in e
               for e in validate_event(dict(v2, rec="span")))
    assert any("is not 2" in e for e in validate_event(dict(v2, v=3)))
    assert any("unknown" in e for e in validate_event(dict(v2, extra=1)))


# ---------------------------------------------------------------------------
# crash safety
# ---------------------------------------------------------------------------

def test_writer_atexit_lands_tail_on_unhandled_exception(tmp_path):
    """Buffered records survive a crash: the writer's atexit fallback
    flushes the tail when the interpreter dies on an uncaught exception,
    with close() never called."""
    out = tmp_path / "crash.jsonl"
    prog = textwrap.dedent(f"""
        from repro.obs.trace import TraceWriter, TRACE_SCHEMA_VERSION
        w = TraceWriter({str(out)!r}, flush_every=10_000)   # never auto-flush
        for i in range(7):
            w.emit({{"v": TRACE_SCHEMA_VERSION, "rec": "step",
                     "step": i + 1, "kind": "decode", "t_ms": 1.0,
                     "plan_ms": 0.1, "step_ms": 0.9, "decode_rows": 1,
                     "prefill_rows": 0, "reset_rows": 0, "adopt_rows": 0,
                     "tokens": 1, "programs": 2, "finished": 0}})
        raise RuntimeError("mid-run crash")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", prog], env=env,
                       cwd=os.path.join(os.path.dirname(__file__), ".."),
                       capture_output=True, text=True)
    assert r.returncode != 0 and "mid-run crash" in r.stderr
    assert validate_file(str(out)) == []
    tail = [json.loads(ln) for ln in out.read_text().splitlines()]
    assert [e["step"] for e in tail] == list(range(1, 8))


def test_writer_close_is_idempotent_and_unregisters(tmp_path):
    from repro.obs.trace import TraceWriter
    p = tmp_path / "t.jsonl"
    w = TraceWriter(str(p), flush_every=100)
    w.emit({"v": 2, "rec": "step"})
    w.close()
    w.close()                                    # no-op
    assert len(p.read_text().splitlines()) == 1
    with pytest.raises(ValueError):
        w.emit({})


def test_engine_run_flushes_trace_on_error(tmp_path, monkeypatch):
    """An exception inside the engine loop must not lose the buffered
    step records: run() flushes before propagating, so the trace ends at
    the failing step."""
    import jax
    from repro.configs import ASSIGNED_ARCHS, CacheConfig
    from repro.models import init_model
    from repro.obs import ObsConfig
    from repro.serving import Engine, SamplingParams

    trace = tmp_path / "t.jsonl"
    cfg = ASSIGNED_ARCHS["qwen2.5-3b"].reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    ccfg = CacheConfig(page_size=8, cache_budget=32, policy="paged_eviction",
                       dtype="float32")
    eng = Engine(cfg, params, cache_cfg=ccfg, max_batch=2,
                 max_prompt_len=32, max_new_tokens=8,
                 sampling=SamplingParams(greedy=True), chunk_size=16,
                 obs=ObsConfig(trace_path=str(trace)))
    rng = np.random.default_rng(0)
    for _ in range(2):
        eng.submit(rng.integers(0, cfg.vocab_size, size=20)
                   .astype(np.int32))
    real_plan, calls = eng.scheduler.plan, [0]

    def dying_plan():
        calls[0] += 1
        if calls[0] > 3:
            raise RuntimeError("scheduler died")
        return real_plan()

    monkeypatch.setattr(eng.scheduler, "plan", dying_plan)
    with pytest.raises(RuntimeError, match="scheduler died"):
        eng.run()
    # default flush_every is 64 — without flush-on-error the file is empty
    assert validate_file(str(trace)) == []
    steps = [json.loads(ln) for ln in trace.read_text().splitlines()]
    assert len(steps) == 3
