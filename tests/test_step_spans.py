"""The engine's host phase spans and the step program's named parts
(DESIGN.md §9): with ``ObsConfig.profiler_annotations`` every
``Engine.step`` is one ``engine.step`` profiler span tiled by six phase
spans; the two step programs carry distinct ``jit__step_*`` names and
their ops carry ``jax.named_scope`` names in their metadata."""
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, CacheConfig
from repro.models import init_model
from repro.obs import ObsConfig, annotation
from repro.serving import Engine, SamplingParams

PHASES = ("engine.plan", "engine.inputs", "engine.launch", "engine.wait",
          "engine.stats", "engine.emit")
SCOPES = ("pool", "attn", "evict", "mlp", "sample", "stats")


@pytest.fixture(scope="module")
def model():
    cfg = ASSIGNED_ARCHS["qwen2.5-3b"].reduced()
    return cfg, init_model(jax.random.PRNGKey(0), cfg)


def _engine(model, annotations=False):
    cfg, params = model
    ccfg = CacheConfig(page_size=8, cache_budget=32, policy="paged_eviction",
                       dtype="float32")
    return Engine(cfg, params, cache_cfg=ccfg, max_batch=2,
                  max_prompt_len=32, max_new_tokens=6,
                  sampling=SamplingParams(greedy=True), chunk_size=16,
                  obs=ObsConfig(profiler_annotations=annotations))


def _submit(eng, n=3, seed=0):
    rng = np.random.default_rng(seed)
    return [eng.submit(rng.integers(0, eng.cfg.vocab_size, size=20 + 3 * i)
                       .astype(np.int32)) for i in range(n)]


def _host_spans(tmp_path, eng, steps):
    """Run ``steps`` engine steps under the profiler; the host plane's
    ``engine.*`` events as [(name, start, end, stats)], by start."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        for _ in range(steps):
            eng.step()
    path, = tmp_path.rglob("*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(path))
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("engine.")]
    return sorted(spans, key=lambda s: (s[1], -s[2]))


def _lowered_hlo(eng, program, T):
    B = eng.max_batch
    zeros = jnp.zeros((B,), jnp.int32)
    no = jnp.zeros((B,), bool)
    return program.lower(
        eng.params, jnp.zeros((B, T), jnp.int32), zeros, no, no, no,
        jnp.full((B,), -1, jnp.int32), zeros, eng.cache,
        jax.random.PRNGKey(0))


def test_phase_spans_tile_each_step_in_order(model, tmp_path):
    eng = _engine(model, annotations=True)
    _submit(eng)
    eng.step()                                  # compile outside the trace
    spans = _host_spans(tmp_path, eng, steps=4)
    outer = [s for s in spans if s[0] == "engine.step"]
    assert len(outer) == 4
    for name, lo, hi, meta in outer:
        inner = [s for s in spans if s[0] != "engine.step"
                 and lo <= s[1] and s[2] <= hi]
        assert tuple(s[0] for s in inner) == PHASES
        # consecutive, non-overlapping phases
        assert all(a[2] <= b[1] for a, b in zip(inner, inner[1:]))
        assert meta["kind"] in ("mixed", "prefill", "decode")
        assert meta["decode_rows"] + meta["prefill_rows"] >= 1
    assert [m["step"] for *_, m in outer] == [2, 3, 4, 5]
    phases = [s for s in spans if s[0] != "engine.step"]
    assert len(phases) == 4 * len(PHASES)     # none outside a step


def test_no_spans_when_annotations_off(model, tmp_path):
    eng = _engine(model, annotations=False)
    _submit(eng)
    eng.step()
    assert _host_spans(tmp_path, eng, steps=3) == []


def test_annotation_off_is_one_shared_noop():
    off = annotation("engine.plan", False)
    assert off is annotation("engine.wait", False)
    with off:
        with off:                               # reentrant
            pass


@pytest.mark.parametrize("width", ["decode", "mixed"])
def test_step_programs_carry_scope_names(model, width):
    eng = _engine(model)
    program, T = ((eng._step_decode, 1) if width == "decode"
                  else (eng._step_mixed, eng.chunk_size))
    hlo = _lowered_hlo(eng, program, T).as_text(dialect="hlo",
                                                 debug_info=True)
    assert f"HloModule jit__step_{width}" in hlo
    parts = {p for name in re.findall(r'op_name="([^"]*)"', hlo)
             for p in name.split("/")}
    assert set(SCOPES) <= parts


def test_two_named_programs_compile_once_each(model):
    eng = _engine(model)
    _submit(eng)
    eng.run()
    assert eng.stats.decode_steps and eng.stats.steps > eng.stats.decode_steps
    assert eng.num_compiled_programs() == 2
    assert eng._step_decode._cache_size() == 1
    assert eng._step_mixed._cache_size() == 1
    names = {_lowered_hlo(eng, p, T).as_text(dialect="hlo").split(",")[0]
             for p, T in ((eng._step_decode, 1),
                          (eng._step_mixed, eng.chunk_size))}
    assert names == {"HloModule jit__step_decode",
                     "HloModule jit__step_mixed"}


def test_greedy_tokens_identical_with_annotations(model, tmp_path):
    out = []
    for on in (False, True):
        eng = _engine(model, annotations=on)
        reqs = _submit(eng, seed=3)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with jax.profiler.trace(str(tmp_path / str(on)),
                                profiler_options=opts):
            eng.run()
        out.append([r.output_tokens for r in reqs])
        assert eng.num_compiled_programs() == 2
    assert out[0] == out[1]
    assert all(len(t) == 6 for t in out[0])


def test_submit_dates_a_request_from_its_arrival_time(model):
    eng = _engine(model)
    t_arrive = time.perf_counter() - 5.0
    late = eng.submit(np.arange(1, 21, dtype=np.int32),
                      arrival_time=t_arrive)
    now = eng.submit(np.arange(21, 41, dtype=np.int32))
    assert late.arrival_time == t_arrive
    assert now.arrival_time > t_arrive + 4.0          # default: submit time
    eng.run()
    assert late.ttft == pytest.approx(late.first_token_time - t_arrive)
    assert late.ttft > 5.0 and late.queue_time > 5.0
    assert now.ttft < late.ttft - 4.0
    h = eng.metrics_snapshot()
    assert h["engine.ttft_s"]["max"] == pytest.approx(late.ttft)
    assert h["engine.queue_s"]["max"] == pytest.approx(late.queue_time)
