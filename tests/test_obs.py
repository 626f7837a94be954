"""Telemetry subsystem (repro.obs + core.devstats; DESIGN.md §9).

- histogram bucket math: interpolated p50/p90/p99 vs numpy percentiles
  within one log-bucket width; exact count/sum/min/max
- counter monotonicity, gauge last-write-wins, snapshot JSON round-trip
- trace JSONL: schema round-trip through a TraceWriter, validator catches
  malformed events, CLI entry point
- device stats vector vs HOST-recomputed pool accounting: exact per-step
  match of the conservation identities across a churned mixed workload
  (prefix-sharing adoptions, CoW forks, page evictions, force-evicts) for
  both structured and unstructured policies
- zero host callbacks inside the jitted step; with obs disabled the cache
  pytree is byte-identical in structure to the pre-obs engine (stats
  leaves are None, which vanish from the pytree)
- TTFT accounting under prefix sharing (ISSUE 8 satellite): adopters'
  TTFT stays ARRIVAL-based — deferral/queueing time cannot be hidden by
  the shorter prefill — and admission/first-token stamps are ordered
"""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, CacheConfig
from repro.core import devstats
from repro.core import paged_cache as pc
from repro.models import init_model
from repro.obs import (MetricsRegistry, ObsConfig, TraceWriter,
                       validate_event, validate_file)
from repro.obs.metrics import Histogram
from repro.obs.trace import TRACE_SCHEMA_VERSION
from repro.serving import Engine, SamplingParams


# ---------------------------------------------------------------------------
# metrics primitives
# ---------------------------------------------------------------------------

def test_histogram_percentiles_vs_numpy():
    rng = np.random.default_rng(0)
    # latency-shaped draws spanning several buckets
    xs = np.exp(rng.normal(np.log(5e-3), 1.0, size=5000))
    h = Histogram("t")
    for x in xs:
        h.observe(float(x))
    assert h.count == len(xs)
    assert h.min == xs.min() and h.max == xs.max()
    assert h.sum == pytest.approx(xs.sum())
    width = 10 ** (1 / 8)      # LATENCY_BOUNDS_S: 8 buckets per decade
    for q in (0.5, 0.9, 0.99):
        est, ref = h.quantile(q), float(np.percentile(xs, q * 100))
        assert ref / width <= est <= ref * width, (q, est, ref)
    assert h.quantile(0.0) == xs.min()
    assert h.quantile(1.0) == xs.max()


def test_histogram_empty_and_overflow():
    h = Histogram("t")
    assert math.isnan(h.quantile(0.5))
    assert h.snapshot()["p50"] is None
    h.observe(1e9)             # beyond the last bound -> overflow bucket
    assert h.snapshot()["overflow"] == 1
    assert h.quantile(0.5) == 1e9     # exact max clamps the overflow bucket


def test_counter_monotone_and_gauge():
    reg = MetricsRegistry()
    c = reg.counter("c")
    c.inc()
    c.inc(5)
    assert c.value == 6
    with pytest.raises(ValueError):
        c.inc(-1)
    assert c.value == 6
    g = reg.gauge("g")
    g.set(3)
    g.set(1)
    assert g.value == 1
    with pytest.raises(TypeError):
        reg.gauge("c")         # name already holds a counter


def test_registry_snapshot_json_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("a.b").inc(2)
    reg.gauge("a.g").set(7)
    reg.histogram("a.h").observe(0.01)
    p = tmp_path / "snap.json"
    reg.to_json(str(p))
    snap = json.loads(p.read_text())
    assert snap["a.b"] == {"type": "counter", "value": 2}
    assert snap["a.g"]["value"] == 7
    assert snap["a.h"]["count"] == 1 and snap["a.h"]["p50"] is not None
    assert reg.render()        # dashboard renders without raising


# ---------------------------------------------------------------------------
# trace writer + schema
# ---------------------------------------------------------------------------

def _event(step=1, **kw):
    ev = {"v": TRACE_SCHEMA_VERSION, "rec": "step", "step": step,
          "kind": "decode",
          "t_ms": 1.0, "plan_ms": 0.1, "step_ms": 0.9, "decode_rows": 2,
          "prefill_rows": 0, "reset_rows": 0, "adopt_rows": 0, "tokens": 2,
          "programs": 2, "finished": 0}
    ev.update(kw)
    return ev


def test_trace_roundtrip_and_validation(tmp_path):
    p = tmp_path / "t.jsonl"
    with TraceWriter(str(p), flush_every=4) as w:
        for i in range(10):
            w.emit(_event(step=i + 1, pages_allocated=i))
    assert validate_file(str(p)) == []
    lines = [json.loads(ln) for ln in p.read_text().splitlines()]
    assert len(lines) == 10
    assert [e["step"] for e in lines] == list(range(1, 11))
    assert lines[3]["pages_allocated"] == 3


def test_trace_validator_catches_bad_events(tmp_path):
    assert validate_event(_event()) == []
    assert any("missing" in e for e in validate_event(
        {"v": TRACE_SCHEMA_VERSION, "rec": "step"}))
    assert any("is not" in e for e in validate_event({"v": 1}))
    assert any("kind" in e for e in validate_event(_event(kind="bogus")))
    assert any("unknown" in e for e in validate_event(_event(zzz=1)))
    assert any("expected int" in e for e in validate_event(_event(tokens=1.5)))
    p = tmp_path / "bad.jsonl"
    p.write_text('{"v": 1}\nnot json\n')
    errs = validate_file(str(p))
    assert errs and any("not JSON" in e for e in errs)
    from repro.obs.trace import main as trace_main
    assert trace_main([str(p)]) == 1
    good = tmp_path / "good.jsonl"
    with TraceWriter(str(good)) as w:
        w.emit(_event())
    assert trace_main([str(good)]) == 0


def test_trace_writer_buffers(tmp_path):
    p = tmp_path / "b.jsonl"
    w = TraceWriter(str(p), flush_every=100)
    w.emit(_event())
    assert p.read_text() == ""          # buffered, not yet written
    w.close()
    assert len(p.read_text().splitlines()) == 1
    with pytest.raises(ValueError):
        w.emit(_event())                # closed


# ---------------------------------------------------------------------------
# device stats vector — unit identities on raw pool ops
# ---------------------------------------------------------------------------

def test_devstats_bump_disabled_is_none():
    assert devstats.bump(None, devstats.PAGES_ALLOCATED, jnp.ones(3)) is None


def test_devstats_identities_raw_ops():
    cache = pc.init_layer_cache(4, 6, 4, 2, 8, jnp.float32, track_stats=True)
    ref0, free0 = int(cache.ref_count.sum()), int(cache.num_free())
    for t in range(10):
        k = jnp.ones((4, 2, 8))
        cache = pc.chunk_rollover(cache, cache.cur_off >= cache.page_size)
        cache = pc.write_token(cache, k, k, jnp.full((4,), t, jnp.int32),
                               jnp.ones((4,)))
    cache = pc.release_rows(cache, jnp.array([False, False, False, True]))
    cache = pc.adopt_prefix(cache, jnp.array([-1, -1, -1, 0]),
                            jnp.array([0, 0, 0, 2]))
    cache = pc.evict_token(cache, jnp.array([0, 0, 0, 1]),
                           enable=jnp.array([False, False, False, True]))
    cache = pc.evict_page(cache, jnp.array([1, 1, 1, 1]),
                          enable=jnp.array([True, False, False, False]))
    d = devstats.to_dict(np.asarray(cache.stats))
    ref1, free1 = int(cache.ref_count.sum()), int(cache.num_free())
    mapped = int((np.asarray(cache.block_table) >= 0).sum())
    assert ref1 - ref0 == (d["pages_allocated"] + d["pages_adopted"]
                           - d["pages_released"])
    assert free1 - free0 == d["pages_freed"] - d["pages_allocated"]
    assert mapped == ref1                       # F2: one ref per bt entry
    assert d["pages_forked"] == 1               # the CoW fork under evict
    assert d["tokens_evicted"] == 1
    assert d["tokens_written"] == 40


# ---------------------------------------------------------------------------
# engine-level: device stats vs host-recomputed pool accounting
# ---------------------------------------------------------------------------

def _make_engine(policy, *, max_batch=3, budget=32, page=8, chunk=16,
                 new_tokens=6, prompt_max=48, obs=None, sharing=True):
    cfg = ASSIGNED_ARCHS["qwen2.5-3b"].reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    ccfg = CacheConfig(page_size=page, cache_budget=budget, policy=policy,
                       dtype="float32")
    return cfg, Engine(cfg, params, cache_cfg=ccfg, max_batch=max_batch,
                       max_prompt_len=prompt_max, max_new_tokens=new_tokens,
                       sampling=SamplingParams(greedy=True), chunk_size=chunk,
                       prefix_sharing=sharing, obs=obs)


def _host_pool_state(eng):
    """(ref_sum, free, mapped) summed over every attention layer (incl.
    stacked pattern reps) — recomputed from device arrays, independent of
    the stats vector."""
    ref_sum = free = mapped = 0
    for lc in list(eng.cache.pattern) + list(eng.cache.tail):
        if lc.kv is None:
            continue
        ref = np.asarray(jax.device_get(lc.kv.ref_count))
        bt = np.asarray(jax.device_get(lc.kv.block_table))
        ref_sum += int(ref.sum())
        free += int((ref == 0).sum())
        mapped += int((bt >= 0).sum())
    return ref_sum, free, mapped


def _pool_counters(eng):
    reg = eng.obs.registry
    return {name: reg.counter(f"pool.{name}").value
            for name in devstats.STAT_NAMES}


@pytest.mark.parametrize("policy", ["paged_eviction", "streaming_llm"])
def test_device_stats_match_host_pool_accounting(policy):
    """Across a churned mixed workload — shared-prefix admissions (adopt +
    CoW forks under token eviction), page evictions, retirements and
    re-admissions — the device stats vector reconciles EXACTLY with pool
    deltas recomputed on the host after every single step."""
    _, eng = _make_engine(policy)
    rng = np.random.default_rng(7)
    vocab = eng.cfg.vocab_size
    prefix = rng.integers(0, vocab, size=24)
    for i in range(6):
        tail = rng.integers(0, vocab, size=int(rng.integers(6, 20)))
        eng.submit(np.concatenate([prefix, tail]).astype(np.int32))
    steps = 0
    prev = _host_pool_state(eng)
    prev_ctr = _pool_counters(eng) if eng.stats.steps else \
        {n: 0 for n in devstats.STAT_NAMES}
    while eng.step() and steps < 200:
        steps += 1
        cur = _host_pool_state(eng)
        ctr = _pool_counters(eng)
        d = {n: ctr[n] - prev_ctr[n] for n in ctr}
        ref_d = cur[0] - prev[0]
        free_d = cur[1] - prev[1]
        assert ref_d == (d["pages_allocated"] + d["pages_adopted"]
                         - d["pages_released"]), (steps, d, prev, cur)
        assert free_d == d["pages_freed"] - d["pages_allocated"], \
            (steps, d, prev, cur)
        assert cur[2] == cur[0], (steps, cur)      # F2 over the fleet
        # the engine's running occupancy estimate never drifts
        assert eng._free_pages_est == cur[1], (steps, eng._free_pages_est, cur)
        prev, prev_ctr = cur, ctr
    assert len(eng.scheduler.finished) == 6
    final = _pool_counters(eng)
    assert final["pages_adopted"] > 0, "workload never exercised adoption"
    if policy == "paged_eviction":
        assert final["pages_evicted"] > 0, "workload never exercised eviction"
    else:   # token policy: evicts single tokens, CoW-forking shared pages
        assert final["tokens_evicted"] > 0
        assert final["pages_forked"] > 0, \
            "token eviction on shared pages must CoW-fork"
    assert eng._free_pages_est == eng.pool_stats()["free_pages"]


def test_forced_evictions_counted():
    """inverse_key_l2 under a starved pool scatters survivors one-per-page
    until rollover finds no free page — the force-evict path must land in
    the counter."""
    _, eng = _make_engine("inverse_key_l2", max_batch=4, budget=16, page=8,
                          chunk=8, new_tokens=20, prompt_max=32,
                          sharing=False)
    rng = np.random.default_rng(3)
    for _ in range(4):
        eng.submit(rng.integers(0, eng.cfg.vocab_size, size=30)
                   .astype(np.int32))
    eng.run(max_steps=300)
    assert eng._free_pages_est == eng.pool_stats()["free_pages"]
    assert eng.stats.tokens_evicted > 0


def test_engine_stats_eviction_fields_live():
    """EngineStats.pages_evicted/tokens_evicted/forced_evictions were dead
    fields before the obs PR — they must now track the device counters."""
    _, eng = _make_engine("paged_eviction")
    rng = np.random.default_rng(1)
    for _ in range(3):
        eng.submit(rng.integers(0, eng.cfg.vocab_size, size=40)
                   .astype(np.int32))
    eng.run()
    ctr = _pool_counters(eng)
    assert eng.stats.pages_evicted == ctr["pages_evicted"] > 0
    assert eng.stats.tokens_evicted == ctr["tokens_evicted"]
    assert eng.stats.forced_evictions == ctr["forced_evictions"]


# ---------------------------------------------------------------------------
# hot path stays clean: no callbacks, unchanged structure when disabled
# ---------------------------------------------------------------------------

def test_no_host_callbacks_inside_jit():
    _, eng = _make_engine("paged_eviction")
    B, T = eng.max_batch, 1
    args = (eng.params, jnp.zeros((B, T), jnp.int32),
            jnp.ones((B,), jnp.int32), jnp.ones((B,), bool),
            jnp.zeros((B,), bool), jnp.zeros((B,), bool),
            jnp.full((B,), -1, jnp.int32), jnp.zeros((B,), jnp.int32),
            eng.cache, jax.random.PRNGKey(0))
    jaxpr = str(jax.make_jaxpr(eng._step_impl)(*args))
    for prim in ("pure_callback", "io_callback", "python_callback",
                 "debug_callback"):
        assert prim not in jaxpr, f"host callback {prim} on the hot path"


def test_disabled_obs_restores_bare_pytree():
    """obs=ObsConfig(metrics=False): every stats leaf is None — the cache
    pytree structure (and therefore the compiled step) is identical to the
    pre-telemetry engine; the step output differs only by the trailing
    None stats slot."""
    _, off = _make_engine("paged_eviction",
                          obs=ObsConfig(metrics=False))
    _, on = _make_engine("paged_eviction")
    for lc in list(off.cache.pattern) + list(off.cache.tail):
        if lc.kv is not None:
            assert lc.kv.stats is None
    for lc in list(on.cache.pattern) + list(on.cache.tail):
        if lc.kv is not None:
            assert lc.kv.stats is not None
    # None leaves vanish from the pytree: the disabled cache's treedef has
    # strictly fewer leaves, and matches a cache built before this PR
    leaves_off = len(jax.tree_util.tree_leaves(off.cache))
    leaves_on = len(jax.tree_util.tree_leaves(on.cache))
    assert leaves_off < leaves_on
    rng = np.random.default_rng(0)
    p = rng.integers(0, off.cfg.vocab_size, size=20).astype(np.int32)
    for eng in (off, on):
        eng.submit(p.copy())
        eng.run()
    a = [r.output_tokens for r in off.scheduler.finished]
    b = [r.output_tokens for r in on.scheduler.finished]
    assert a == b, "telemetry changed sampled tokens"


# ---------------------------------------------------------------------------
# trace + snapshot from a real engine run
# ---------------------------------------------------------------------------

def test_engine_trace_and_snapshot(tmp_path):
    trace = tmp_path / "trace.jsonl"
    _, eng = _make_engine("paged_eviction",
                          obs=ObsConfig(trace_path=str(trace)))
    rng = np.random.default_rng(5)
    prefix = rng.integers(0, eng.cfg.vocab_size, size=16)
    for _ in range(4):
        tail = rng.integers(0, eng.cfg.vocab_size, size=12)
        eng.submit(np.concatenate([prefix, tail]).astype(np.int32))
    eng.run()
    eng.close()
    assert validate_file(str(trace)) == []
    events = [json.loads(ln) for ln in trace.read_text().splitlines()]
    real = [e for e in events if e["kind"] != "idle"]
    assert len(real) == eng.stats.steps
    assert sum(e["finished"] for e in events) == 4
    assert sum(e["tokens"] for e in events) > 0
    # per-step device counters in the trace sum to the registry totals
    ctr = _pool_counters(eng)
    for name in devstats.STAT_NAMES:
        assert sum(e.get(name, 0) for e in events) == ctr[name], name
    assert all(e["free_pages"] >= 0 for e in real)
    snap = eng.metrics_snapshot()
    for h in ("engine.ttft_s", "engine.itl_s", "engine.tpot_s",
              "engine.step_wall_s", "engine.plan_s"):
        assert snap[h]["count"] > 0, h
        assert snap[h]["p50"] is not None and snap[h]["p99"] is not None, h
    assert snap["engine.programs"]["value"] == 2
    assert snap["engine.requests_finished"]["value"] == 4


# ---------------------------------------------------------------------------
# TTFT accounting under prefix sharing (satellite regression)
# ---------------------------------------------------------------------------

def test_ttft_dates_from_arrival_not_first_chunk():
    """Adopters skip their shared prefill chunks, and batched same-prefix
    arrivals are DEFERRED until the owner finishes prefilling the prefix.
    The TTFT interval must still start at arrival: an adopter's measured
    TTFT includes its queueing/deferral time, and the stamp ordering
    arrival <= admission < first_token holds for every request."""
    _, eng = _make_engine("paged_eviction", max_batch=4, budget=64,
                          prompt_max=64, chunk=8, new_tokens=4)
    rng = np.random.default_rng(11)
    prefix = rng.integers(0, eng.cfg.vocab_size, size=32)
    reqs = []
    for _ in range(3):
        tail = rng.integers(0, eng.cfg.vocab_size, size=10)
        reqs.append(eng.submit(np.concatenate([prefix, tail])
                               .astype(np.int32)))
    eng.run()
    assert eng.stats.shared_prefix_hits >= 2   # followers adopted
    for r in reqs:
        assert r.arrival_time <= r.admission_time < r.first_token_time
        assert r.ttft == pytest.approx(r.first_token_time - r.arrival_time)
        assert r.ttft >= r.queue_time >= 0.0
    owner, followers = reqs[0], reqs[1:]
    for f in followers:
        assert f.shared_tokens > 0
        # the adopted pages cost no prefill compute ...
        assert f.prefill_time < owner.prefill_time
        # ... but deferral time is NOT hidden: the follower's first token
        # can only exist after the owner finished writing the prefix, so
        # its arrival-based TTFT is >= its own (shorter) prefill time
        assert f.ttft > f.prefill_time
    snap = eng.metrics_snapshot()
    assert snap["engine.queue_s"]["count"] == 3
    assert snap["engine.ttft_s"]["count"] == 3
