"""Continuous-batching serving engine (the vLLM-shaped runtime).

ONE unified step program (`models.transformer.forward_step`): each engine
iteration the scheduler packs up to ``token_budget`` tokens — one decode
token per RUNNING slot plus up to ``chunk_size`` prompt tokens per
PREFILLING slot — and a single jitted program appends them all straight
into the shared page pool, attends through block tables (paged
flash-prefill kernel on TPU), runs Alg.3 eviction on decode rows and
incremental Alg.2 compression at prefill chunk boundaries, and samples.
Decode-only iterations reuse the same function at T == 1, so a full mixed
workload compiles exactly two programs — there is no separate prefill
forward, no per-slot-specialized insert splice, and a long prompt never
stalls the decode slots sharing its batch (TTFT/ITL under mixed load is
what `benchmarks/latency.py` measures).

The eviction policy is a constructor argument — the paper's PagedEviction,
any baseline, or ``full``. Because every policy statically bounds the
per-request block table (budget + chunk headroom) and the pool is sized
for the full batch, admission can never over-commit HBM (DESIGN.md §2,
§6); pages a request evicts — or releases when it retires — return to the
SHARED free list and become headroom for every other request.

Telemetry (DESIGN.md §9): the engine is instrumented end to end through
``repro.obs``. Each step, pool-event counts (pages allocated / freed /
evicted / forked / adopted, tokens written / evicted, force-evicts) ride
OUT of the jitted program as a tiny int32 stats vector accumulated by the
``paged_cache`` mutators themselves — no host callbacks on the hot path —
and are reconciled into a host :class:`~repro.obs.MetricsRegistry`
(latency histograms with real p50/p90/p99 for TTFT, ITL, TPOT, step wall
time, scheduler plan time; counters; gauges). Optionally every iteration
emits one JSONL trace event (step kind, batch mix, tokens, page counters,
pool occupancy, program-cache size) through a buffered
:class:`~repro.obs.TraceWriter`. A recompile sentinel tracks the
compiled-program count against the known ceiling (2: T == chunk and
T == 1) and flags any unexpected compile once through the trace. The
legacy :class:`EngineStats` scalars and :meth:`Engine.pool_stats`
(fleet-level pool occupancy, host-recomputed from ref counts) remain the
benchmark-facing summaries. Under a profiler, each step's host phases are
profiler spans and the step program's parts are named scopes (see
:meth:`Engine.step`); what they cost on a TPU v5e is in PERF.md §5.
"""
from __future__ import annotations

import functools
import time
import warnings
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import CacheConfig, ModelConfig
from repro.core import devstats
from repro.core.paged_cache import lineage_snapshot
from repro.core.policies import EvictionPolicy, get_policy
from repro.models.transformer import (
    ModelCache,
    collect_step_stats,
    forward_step,
    init_decode_caches,
    init_model,
    intact_prefix_pages,
)
from repro.obs import EngineObs, ObsConfig
from repro.obs.lineage import StepPlanContext
from repro.obs.regret import (REGRET_BOUNDS, ShadowState, probe_record,
                              run_probe)
from repro.obs.trace import TRACE_SCHEMA_VERSION, annotation
from repro.serving.request import Request, RequestStatus, SamplingParams
from repro.serving.sampler import sample_tokens
from repro.serving.scheduler import Scheduler


@dataclass
class EngineStats:
    steps: int = 0               # every unified step (mixed + decode-only)
    decode_steps: int = 0        # decode-only steps — the ones whose wall
                                 # time lands in decode_s
    tokens_generated: int = 0    # every emitted token (mixed steps included)
    decode_tokens: int = 0       # tokens from decode-only steps
    pages_evicted: int = 0
    tokens_evicted: int = 0
    forced_evictions: int = 0
    chunk_append_fallbacks: int = 0   # append_chunk calls that took the
                                      # per-token loop (summed over layers)
    shared_prefix_hits: int = 0   # admissions that adopted resident pages
    shared_prefix_tokens: int = 0  # prompt tokens whose prefill was skipped
    prefill_s: float = 0.0
    decode_s: float = 0.0

    @property
    def decode_tok_per_s(self) -> float:
        return self.decode_tokens / self.decode_s if self.decode_s else 0.0


# the step program's two jitted names (T == 1, T == chunk): profiles show
# them as jit__step_decode / jit__step_mixed
_PROGRAMS = ("_step_decode", "_step_mixed")


def _named(fn, name: str):
    """``fn`` under ``name``, the name ``jax.jit`` gives its program."""
    @functools.wraps(fn)
    def step(*args):
        return fn(*args)
    step.__name__ = step.__qualname__ = name
    return step


def init_params(cfg: ModelConfig, key, mesh=None):
    """Seeded random weights for serving, made by one compiled
    ``init_model``. Given a tensor-parallel ``mesh`` (``make_tp_mesh``),
    every device creates only its own shards, so no device ever holds the
    whole model — what a model larger than one chip's memory needs."""
    init = lambda k: init_model(k, cfg)
    if mesh is None:
        return jax.jit(init)(key)
    from repro.sharding import rules
    shardings = rules.tp_param_shardings(mesh, jax.eval_shape(init, key))
    return jax.jit(init, out_shardings=shardings)(key)


class Engine:
    def __init__(self, cfg: ModelConfig, params, *, cache_cfg: CacheConfig,
                 max_batch: int = 8, max_prompt_len: int = 256,
                 max_new_tokens: int = 128, sampling: SamplingParams | None = None,
                 use_pallas: bool | None = None, seed: int = 0,
                 chunk_size: int = 64, token_budget: int | None = None,
                 prefix_sharing: bool = True, decode_splits: int = 1,
                 fused_scores: bool | None = None,
                 obs: ObsConfig | None = None, tp: int = 1, mesh=None):
        self.cfg = cfg
        self.params = params
        self.ccfg = cache_cfg
        # tensor parallelism (DESIGN.md §11): tp > 1 serves the unified step
        # shard_map'd over a (1, tp) device mesh — KV-head-sharded pool and
        # kernels, replicated metadata/scheduler. tp == 1 is the unchanged
        # single-device path (no mesh, no shard_map, bit-identical HLO).
        self.tp = tp
        self._tp_axis = "model" if tp > 1 else None
        if tp > 1:
            from repro.sharding import rules as _rules
            _rules.validate_tp(cfg, tp)
        self.mesh = mesh
        self.policy: EvictionPolicy = get_policy(cache_cfg.policy,
                                                 tp_axis=self._tp_axis)
        self.max_batch = max_batch
        self.max_prompt_len = max_prompt_len
        self.max_new_tokens = max_new_tokens
        self.total_len = max_prompt_len + max_new_tokens
        self.sampling = sampling or SamplingParams()
        # attention kernels follow the platform: the Pallas kernels on a
        # TPU, the jnp reference elsewhere. An explicit True off-TPU runs
        # them in the Pallas interpreter (the CPU parity tests).
        if use_pallas is None:
            use_pallas = jax.default_backend() == "tpu"
        self.use_pallas = use_pallas
        # split-K decode (DESIGN.md §8): partition the page walk of the
        # Pallas decode kernel; 1 == off. Fused eviction scores default to
        # riding along whenever the Pallas kernels run (they emit the score
        # epilogue for free); pass False to force the stored-score path.
        self.decode_splits = decode_splits
        self.fused_scores = use_pallas if fused_scores is None else fused_scores
        self.chunk_size = min(chunk_size, max_prompt_len)
        # prefix sharing needs every layer's prompt state to live in paged
        # KV: recurrent mixers (mamba/xLSTM) and cross-attention state can't
        # be adopted page-wise, so sharing stays off for those archs
        self._sharing_ok = (prefix_sharing
                            and all(s.mixer == "attn"
                                    for s in cfg.layer_pattern())
                            and not cfg.cross_attention)
        self.scheduler = Scheduler(
            max_batch, chunk_size=self.chunk_size, token_budget=token_budget,
            page_size=cache_cfg.page_size if self._sharing_ok else None,
            prefix_probe=self._prefix_probe if self._sharing_ok else None)
        self.stats = EngineStats()
        self._key = jax.random.PRNGKey(seed)
        self._next_id = 0

        # telemetry (DESIGN.md §9): metrics default ON — the device stats
        # vector + registry. obs=ObsConfig(metrics=False) restores the bare
        # pre-obs pytree.
        self.obs = EngineObs(obs if obs is not None else ObsConfig())
        self._t_start = time.perf_counter()
        self._programs_seen = 0
        self._warned_compile = False
        # forensics (DESIGN.md §10): per-request timeline hooks, lineage
        # snapshot function, and the regret shadow cache. ``_want_taps`` is
        # python-static — False compiles the exact pre-forensics program.
        self._want_taps = self.obs.cfg.regret_every > 0
        if self._want_taps and tp > 1:
            raise ValueError("regret shadow probes are not supported under "
                             "tensor parallelism (tp > 1): the tap pytree "
                             "would need per-shard out_specs; probe at tp=1")
        self._shadow: ShadowState | None = None
        if self.obs.timeline is not None:
            self.scheduler.on_admit = self._on_admit

        # batch-wide state (block tables carry chunk headroom: a prefilling
        # row transiently holds budget + chunk tokens between boundaries)
        def make_cache() -> ModelCache:
            return init_decode_caches(
                cfg, max_batch, self.total_len, self.policy, self.ccfg,
                chunk_tokens=self.chunk_size,
                track_stats=self.obs.cfg.metrics)

        if tp > 1:
            self._init_tp(make_cache)
        else:
            self.cache: ModelCache = make_cache()
            self._step_decode, self._step_mixed = (
                jax.jit(_named(self._step_impl, n)) for n in _PROGRAMS)
        self.cur_tokens = np.zeros((max_batch,), np.int32)

        # running pool occupancy, maintained from the device stats deltas
        # (Δfree == freed - allocated) so per-step trace events never pay a
        # pool_stats() device_get. Initial state is static: each attention
        # layer starts with `batch` pre-mapped working pages.
        total = free = 0
        for lc in list(self.cache.pattern) + list(self.cache.tail):
            if lc.kv is None:
                continue
            shp = lc.kv.ref_count.shape        # (R, N) stacked or (N,) tail
            reps, n = (shp if len(shp) == 2 else (1, shp[0]))
            total += reps * n
            free += reps * (n - max_batch)
        self._pool_pages_total = total
        self._free_pages_est = free
        self._probe_fn = jax.jit(intact_prefix_pages)
        # lineage ledger: one jitted gather of the FIRST attention layer's
        # pool view per step (block table, ref counts, per-page tokens /
        # base positions / policy scores)
        self._lineage_fn = (jax.jit(self._lineage_impl)
                            if self.obs.ledger is not None else None)

    def _init_tp(self, make_cache) -> None:
        """Build the tensor-parallel step: create the cache straight into
        its manual shardings (params too, unless :func:`init_params`
        already placed them) and wrap ``_step_impl`` in shard_map over the
        (1, tp) mesh (DESIGN.md §11). Everything host-side — the scheduler,
        radix prefix index, free-list estimate, lineage ledger — keeps
        reading the replicated metadata leaves exactly as at tp=1."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.launch.mesh import make_tp_mesh
        from repro.models.moe import _shard_map
        from repro.sharding import rules

        if self.mesh is None:
            self.mesh = make_tp_mesh(self.tp)
        mesh = self.mesh
        p_specs = rules.tp_param_specs(self.params)
        self.params = jax.device_put(
            self.params, rules.tp_param_shardings(mesh, self.params))
        cache_shapes = jax.eval_shape(make_cache)
        c_specs = rules.tp_cache_specs(cache_shapes)
        c_shardings = rules.tp_cache_shardings(mesh, cache_shapes)
        self.cache = jax.jit(make_cache, out_shardings=c_shardings)()
        rep = P()
        in_specs = (p_specs, rep, rep, rep, rep, rep, rep, rep, c_specs, rep)
        # outputs: (next_tok replicated, cache, stats replicated-or-None,
        # taps always None under TP — gated in __init__)
        stats_spec = rep if self.obs.cfg.metrics else None
        out_specs = (rep, c_specs, stats_spec, None)
        # pin the returned cache to the shardings it was created with: left
        # to the compiler, replicated leaves come back with an equivalent
        # but unequal spec, and the next step's call misses the jit cache
        rep_sharding = NamedSharding(mesh, rep)
        step = _shard_map(self._step_impl, mesh, in_specs=in_specs,
                          out_specs=out_specs, manual_axes=("data", "model"))
        self._step_decode, self._step_mixed = (
            jax.jit(_named(step, n), out_shardings=(
                rep_sharding, c_shardings, rep_sharding, rep_sharding))
            for n in _PROGRAMS)

    @staticmethod
    def _lineage_impl(cache: ModelCache):
        for lc in cache.pattern:
            if lc.kv is not None:
                # stacked pattern slots: rep 0 is the first attention layer
                return lineage_snapshot(
                    jax.tree.map(lambda a: a[0], lc.kv))
        for lc in cache.tail:
            if lc.kv is not None:
                return lineage_snapshot(lc.kv)
        return None

    # ---------------------------------------------------------------- jitted
    def _step_impl(self, params, tokens, n_tok, decode_mask, prefill_mask,
                   reset_mask, share_src, share_pages, cache, key):
        """The unified step: append + attend + evict + sample. Jitted twice,
        under two program names (``jit__step_mixed`` for T == chunk_size
        mixed/prefill steps, ``jit__step_decode`` for T == 1 decode-only
        steps), so a profile tells the two apart by name. Its parts carry
        ``jax.named_scope`` names (``pool``/``attn``/``evict``/``mlp`` in
        each layer, ``sample`` and ``stats`` here) in the ops' metadata.

        Third output: the summed device stats vector ((devstats.NSTATS,)
        int32, this step's pool events across every attention layer), or
        None when the caches don't track stats — summing happens INSIDE the
        jit so telemetry costs one reduction + one tiny transfer, never a
        host callback.

        Fourth output: the regret-probe taps (per-attention-layer k/v/q/o +
        live positions; obs/regret.py), or None when probes are off —
        ``_want_taps`` is static, so the probes-off program is bit-identical
        to the never-instrumented one."""
        out = forward_step(
            params, self.cfg, tokens, n_tok, cache, self.policy, self.ccfg,
            decode_mask=decode_mask, prefill_mask=prefill_mask,
            reset_mask=reset_mask, share_src=share_src,
            share_pages=share_pages, use_pallas=self.use_pallas,
            decode_splits=self.decode_splits, fused_scores=self.fused_scores,
            want_taps=self._want_taps, tp_axis=self._tp_axis)
        logits, cache = out[0], out[1]
        taps = out[2] if self._want_taps else None
        s = self.sampling
        with jax.named_scope("sample"):
            next_tok = sample_tokens(key, logits, temperature=s.temperature,
                                     top_k=s.top_k, top_p=s.top_p,
                                     greedy=s.greedy)
        with jax.named_scope("stats"):
            st = collect_step_stats(cache)
            if st is not None and self._tp_axis is not None:
                # sharding-aware devstats: metadata mutations run replicated
                # on every shard, so a plain sum over the mesh would count
                # each pool event tp times and break the pool's conservation
                # identities (DESIGN.md §9). Keep shard 0's vector and psum:
                # a true mesh collective whose result still reconciles
                # EXACTLY with host pool accounting.
                idx = jax.lax.axis_index(self._tp_axis)
                st = jax.lax.psum(jnp.where(idx == 0, st, 0), self._tp_axis)
        return next_tok, cache, st, taps

    def _prefix_probe(self, slot: int) -> int:
        """Device half of prefix-sharing admission (scheduler callback):
        how many leading full prompt pages of batch row ``slot`` survive
        intact in every attention layer."""
        return int(self._probe_fn(self.cache, jnp.int32(slot)))

    # ------------------------------------------------------------------- api
    def submit(self, prompt: np.ndarray, *, max_new_tokens: int | None = None,
               eos_token_id: int | None = None,
               arrival_time: float | None = None) -> Request:
        """Queue a request. ``arrival_time`` (``time.perf_counter`` clock)
        dates a request whose caller knows when it arrived; TTFT and queue
        time count from it. Default: now."""
        assert 0 < len(prompt) <= self.max_prompt_len, (
            f"prompt len {len(prompt)} not in (0, {self.max_prompt_len}]")
        req = Request(request_id=self._next_id,
                      prompt=np.asarray(prompt, np.int32),
                      max_new_tokens=max_new_tokens or self.max_new_tokens,
                      eos_token_id=eos_token_id)
        if arrival_time is not None:
            req.arrival_time = arrival_time
        self._next_id += 1
        self.scheduler.add(req)
        if self.obs.timeline is not None:
            self.obs.timeline.request_submitted(req.request_id,
                                                req.arrival_time)
        return req

    def _on_admit(self, slot: int, req: Request) -> None:
        """Scheduler admission hook → timeline (queue span ends here)."""
        self.obs.timeline.request_admitted(
            req.request_id, req.admission_time, slot=slot,
            shared_tokens=req.shared_tokens,
            shared_pages=(req.shared_tokens // self.ccfg.page_size
                          if req.shared_tokens else 0),
            prompt_tokens=len(req.prompt))

    def _maybe_finish(self, req: Request) -> None:
        last = req.output_tokens[-1] if req.output_tokens else None
        if req.eos_token_id is not None and last == req.eos_token_id:
            req.status = RequestStatus.FINISHED_STOPPED
        elif req.num_generated >= req.max_new_tokens:
            req.status = RequestStatus.FINISHED_LENGTH
        if req.finished:
            if self.obs.timeline is not None:
                self.obs.timeline.request_finished(
                    req.request_id, time.perf_counter(),
                    tokens=req.num_generated, reason=req.status.value)
            self.scheduler.retire(req)
            if self.obs.cfg.metrics:
                reg = self.obs.registry
                reg.counter("engine.requests_finished").inc()
                if req.decode_times:
                    reg.histogram("engine.tpot_s").observe(
                        sum(req.decode_times) / len(req.decode_times))

    # ------------------------------------------------------------- telemetry
    def _check_recompile(self) -> bool:
        """Recompile sentinel: returns True iff this step grew the compiled-
        program cache PAST the known ceiling (2 programs: T == chunk and
        T == 1). The first unexpected compile warns once; every one bumps
        the counter and flags the step's trace event."""
        n = self.num_compiled_programs()
        if n < 0:                         # no _cache_size introspection
            return False
        grew, self._programs_seen = n > self._programs_seen, n
        unexpected = grew and n > self.obs.cfg.program_ceiling
        if self.obs.cfg.metrics:
            self.obs.registry.gauge("engine.programs").set(n)
            if unexpected:
                self.obs.registry.counter("engine.unexpected_compiles").inc()
        if unexpected and not self._warned_compile:
            self._warned_compile = True
            warnings.warn(
                f"engine step compiled program #{n} (ceiling "
                f"{self.obs.cfg.program_ceiling}) — an operand shape or "
                f"static argument is varying across steps", stacklevel=3)
        return unexpected

    def _emit_trace(self, kind: str, plan, plan_dt: float, step_dt: float,
                    tokens: int, st, finished: int, unexpected: bool) -> None:
        ev = {
            "v": TRACE_SCHEMA_VERSION,
            "rec": "step",
            "step": self.stats.steps,
            "kind": kind,
            "t_ms": (time.perf_counter() - self._t_start) * 1e3,
            "plan_ms": plan_dt * 1e3,
            "step_ms": step_dt * 1e3,
            "decode_rows": len(plan.decode),
            "prefill_rows": len(plan.prefill),
            "reset_rows": len(plan.reset),
            "adopt_rows": len(plan.adopt),
            "tokens": tokens,
            "programs": max(self._programs_seen, 0),
            "finished": finished,
        }
        if st is not None:
            for i, name in enumerate(devstats.STAT_NAMES):
                ev[name] = int(st[i])
            ev["pool_pages"] = self._pool_pages_total
            ev["free_pages"] = self._free_pages_est
        if unexpected:
            ev["unexpected_compile"] = True
        self.obs.writer.emit(ev)

    def step(self) -> bool:
        """One engine iteration: plan a unified step (admission + decode
        tokens + prompt chunks) and run it. Returns whether work remains.

        With ``ObsConfig.profiler_annotations`` the iteration is one
        ``engine.step`` profiler span (metadata: kind, step, row counts)
        tiled by its phases, in order: ``engine.plan``, ``engine.inputs``
        (step arrays, key split, host-to-device copies), ``engine.launch``
        (the asynchronous program call), ``engine.wait`` (``device_get`` of
        the sampled tokens: the host blocked on the device),
        ``engine.stats`` (stats-vector transfer and reconcile) and
        ``engine.emit`` (tokens to requests, finishes, registry, trace
        record, timeline, forensics)."""
        on = self.obs.cfg.profiler_annotations
        span = annotation("engine.step", on)
        with span:
            return self._step(span if on else None)

    def _step(self, span) -> bool:
        oc = self.obs.cfg
        on = span is not None
        t_plan0 = time.perf_counter()
        with annotation("engine.plan", on):
            plan = self.scheduler.plan()
            plan_dt = time.perf_counter() - t_plan0
            if oc.metrics:
                self.obs.registry.histogram("engine.plan_s").observe(plan_dt)
        if plan.empty:
            if self.obs.writer is not None:
                with annotation("engine.emit", on):
                    self._emit_trace("idle", plan, plan_dt, 0.0, 0, None, 0,
                                     False)
            return self.scheduler.has_work()
        with annotation("engine.inputs", on):
            kind = "mixed" if (plan.prefill and plan.decode) else (
                "prefill" if plan.prefill else "decode")
            if on:
                span.set_metadata(kind=kind, step=self.stats.steps + 1,
                                  decode_rows=len(plan.decode),
                                  prefill_rows=len(plan.prefill))
            B = self.max_batch
            T = self.chunk_size if plan.prefill else 1
            tokens = np.zeros((B, T), np.int32)
            n_tok = np.zeros((B,), np.int32)
            decode_mask = np.zeros((B,), bool)
            prefill_mask = np.zeros((B,), bool)
            reset_mask = np.zeros((B,), bool)
            reset_mask[plan.reset] = True
            share_src = np.full((B,), -1, np.int32)
            share_pages = np.zeros((B,), np.int32)
            for slot, src, n_pages in plan.adopt:
                share_src[slot] = src
                share_pages[slot] = n_pages
                self.stats.shared_prefix_hits += 1
                self.stats.shared_prefix_tokens += \
                    n_pages * self.ccfg.page_size
            for slot, req in plan.decode:
                tokens[slot, 0] = self.cur_tokens[slot]
                n_tok[slot] = 1
                decode_mask[slot] = True
            for slot, req, chunk, _ in plan.prefill:
                tokens[slot, :len(chunk)] = chunk
                n_tok[slot] = len(chunk)
                prefill_mask[slot] = True
                req.prefill_pos += len(chunk)

            # EngineStats times t0 .. dt: key split, copies, program, wait
            t0 = time.perf_counter()
            self._key, sk = jax.random.split(self._key)
            inputs = [jnp.asarray(a) for a in (
                tokens, n_tok, decode_mask, prefill_mask, reset_mask,
                share_src, share_pages)]
            program = self._step_mixed if plan.prefill else self._step_decode
        with annotation("engine.launch", on):
            next_tok, self.cache, stats_dev, taps = program(
                self.params, *inputs, self.cache, sk)
        with annotation("engine.wait", on):
            next_np = np.asarray(jax.device_get(next_tok))
            dt = time.perf_counter() - t0
            now = time.perf_counter()

        with annotation("engine.stats", on):
            unexpected = self._check_recompile()
            self.stats.steps += 1
            if plan.prefill:
                self.stats.prefill_s += dt
            else:
                self.stats.decode_s += dt
                self.stats.decode_steps += 1
            # reconcile this step's device pool events (one (NSTATS,)
            # transfer)
            st = None
            if stats_dev is not None:
                st = np.asarray(jax.device_get(stats_dev))
                self.stats.pages_evicted += int(st[devstats.PAGES_EVICTED])
                self.stats.tokens_evicted += int(st[devstats.TOKENS_EVICTED])
                self.stats.forced_evictions += \
                    int(st[devstats.FORCED_EVICTIONS])
                self.stats.chunk_append_fallbacks += \
                    int(st[devstats.CHUNK_APPEND_FALLBACKS])
                self._free_pages_est += int(st[devstats.PAGES_FREED]) - \
                    int(st[devstats.PAGES_ALLOCATED])

        with annotation("engine.emit", on):
            # forensics (DESIGN.md §10) — all host-side, plan-
            # contextualized. Runs BEFORE the finish loops below so slot ->
            # request attribution still sees this step's owners.
            step_no = self.stats.steps
            lin_events = []
            if self.obs.ledger is not None:
                snap = jax.device_get(self._lineage_fn(self.cache))
                ctx = StepPlanContext(
                    reset_slots=frozenset(plan.reset),
                    adopt={slot: (src, n_pages)
                           for slot, src, n_pages in plan.adopt})
                lin_events = self.obs.ledger.observe_step(step_no, snap,
                                                          ctx)
                if self.obs.writer is not None:
                    for evn in lin_events:
                        self.obs.writer.emit(evn.to_record())
            if taps is not None:
                self._observe_regret(plan, taps, n_tok, step_no)
            tl = self.obs.timeline
            if tl is not None:
                tl.engine_step(step_no, kind, t0, dt,
                               tokens=int(n_tok.sum()))
                for slot, req in plan.decode:
                    tl.decode_step(req.request_id, t0)
                for slot, req, chunk, _ in plan.prefill:
                    tl.prefill_chunk(req.request_id, t0, t0 + dt,
                                     tokens=len(chunk), step=step_no)
                if st is not None and int(st[devstats.PAGES_EVICTED]) > 0:
                    tl.engine_instant(now, "pages_evicted",
                                      count=int(st[devstats.PAGES_EVICTED]))
                for evn in lin_events:
                    if evn.etype == "evict":
                        owner = self.scheduler.slots[evn.slot]
                        if owner is not None:
                            tl.request_evicted_page(
                                owner.request_id, now, page=evn.page,
                                lpi=evn.lpi, score=evn.score)

            reg = self.obs.registry if oc.metrics else None
            if reg is not None:
                reg.histogram("engine.step_wall_s").observe(dt)
                reg.counter("engine.steps").inc()
                reg.counter("engine.tokens").inc(int(n_tok.sum()))
                if st is not None:
                    for i, name in enumerate(devstats.STAT_NAMES):
                        reg.counter(f"pool.{name}").inc(int(st[i]))
                    reg.gauge("pool.free_pages").set(self._free_pages_est)
                    reg.gauge("pool.total_pages").set(
                        self._pool_pages_total)
                for slot in plan.reset:
                    r = self.scheduler.slots[slot]
                    if r is not None:
                        reg.histogram("engine.queue_s").observe(
                            r.queue_time)

            finished_before = len(self.scheduler.finished)
            for slot, req in plan.decode:
                req.output_tokens.append(int(next_np[slot]))
                req.decode_times.append(dt)
                self.cur_tokens[slot] = next_np[slot]
                self.stats.tokens_generated += 1
                if not plan.prefill:
                    self.stats.decode_tokens += 1
                if reg is not None:
                    reg.histogram("engine.itl_s").observe(dt)
                self._maybe_finish(req)
            for slot, req, chunk, completes in plan.prefill:
                req.prefill_time += dt
                if completes:
                    # the sampled token at the prompt's last position is
                    # this request's FIRST output token (its TTFT moment,
                    # dated from ARRIVAL — an adopter's shorter prefill must
                    # not hide its queueing/deferral time; see Request.ttft)
                    req.output_tokens.append(int(next_np[slot]))
                    req.first_token_time = now
                    self.cur_tokens[slot] = next_np[slot]
                    req.status = RequestStatus.RUNNING
                    self.stats.tokens_generated += 1
                    if reg is not None:
                        reg.histogram("engine.ttft_s").observe(
                            now - req.arrival_time)
                    self._maybe_finish(req)
            if self.obs.writer is not None:
                self._emit_trace(
                    kind, plan, plan_dt, dt, int(n_tok.sum()), st,
                    len(self.scheduler.finished) - finished_before,
                    unexpected)
        return self.scheduler.has_work()

    def _observe_regret(self, plan, taps, n_tok, step_no: int) -> None:
        """Shadow-probe bookkeeping (obs/regret.py): device taps → host
        shadow history mirroring the pool's lifecycle, then a sampled
        full-cache recompute on this step's flagged decode rows."""
        taps = jax.device_get(taps)
        layers = []
        for tp in taps["pattern"]:
            if tp is None:
                continue
            reps = tp["k"].shape[0]        # stacked over pattern repetitions
            for r in range(reps):
                layers.append({k: v[r] for k, v in tp.items()})
        layers += [tp for tp in taps["tail"] if tp is not None]
        if not layers:
            return
        positions = np.asarray(taps["positions"])
        if self._shadow is None:
            KV, hd = layers[0]["k"].shape[-2:]
            self._shadow = ShadowState(len(layers), self.max_batch,
                                       self.total_len, KV, hd)
        sh = self._shadow
        for slot in plan.reset:
            sh.reset_row(slot)
        for slot, src, n_pages in plan.adopt:
            sh.adopt(slot, src, n_pages * self.ccfg.page_size)
        sh.record_step(layers, positions, n_tok)
        every = self.obs.cfg.regret_every
        rows, by_slot = [], {}
        for slot, req in plan.decode:
            if req.probe and len(req.decode_times) % every == 0:
                rows.append(slot)
                by_slot[slot] = req
        if not rows:
            return
        reg = self.obs.registry if self.obs.cfg.metrics else None
        for s in run_probe(sh, layers, positions, n_tok, rows):
            req = by_slot[s["slot"]]
            req.regret_samples.append(s)
            if self.obs.writer is not None:
                self.obs.writer.emit(probe_record(
                    s, step=step_no, request_id=req.request_id))
            if reg is not None:
                reg.histogram("engine.eviction_regret",
                              bounds=REGRET_BOUNDS).observe(
                                  float(np.mean(s["divergence"])))
                reg.histogram("engine.evicted_attention_mass",
                              bounds=REGRET_BOUNDS).observe(
                                  float(np.mean(s["evicted_mass"])))

    def shadow_nbytes(self) -> int:
        """Host bytes held by the regret shadow cache (0 when probes off)."""
        return self._shadow.nbytes() if self._shadow is not None else 0

    def run(self, max_steps: int = 100_000) -> list[Request]:
        """Drive :meth:`step` to completion. Crash safety: an exception
        anywhere in the loop flushes the buffered trace tail before
        propagating, so the trace ends at the failing step — plus the
        writer's own atexit fallback for exits that bypass this frame."""
        steps = 0
        try:
            while self.step() and steps < max_steps:
                steps += 1
        except BaseException:
            if self.obs.writer is not None:
                self.obs.writer.flush()
            raise
        return self.scheduler.finished

    def num_compiled_programs(self) -> int:
        """Distinct compiled executables behind the engine (the per-slot
        recompilation family is dead: expect 2 — T == chunk and T == 1).
        The recompile sentinel mirrors this into the ``engine.programs``
        gauge and counts ceiling crossings in ``engine.unexpected_compiles``."""
        sizes = [getattr(f, "_cache_size", None)
                 for f in (self._step_decode, self._step_mixed)]
        if not all(callable(size) for size in sizes):
            return -1
        return sum(int(size()) for size in sizes)

    def metrics_snapshot(self) -> dict:
        """JSON-safe snapshot of every metric (see MetricsRegistry)."""
        return self.obs.registry.snapshot()

    def close(self) -> None:
        """Flush and close the trace writer (idempotent)."""
        self.obs.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def export_timeline(self, path: str) -> int:
        """Write the per-request Perfetto/Chrome-trace timeline; returns the
        event count. Requires ``ObsConfig(timeline=True)``."""
        if self.obs.timeline is None:
            raise ValueError("engine was not run with ObsConfig(timeline=True)")
        return self.obs.timeline.export(path)

    def pool_stats(self) -> dict:
        """Fleet-level page-pool occupancy, aggregated over attention layers:
        total physical pages, pages on the free list, utilization, and the
        prefix-sharing telemetry — pages mapped by more than one block table
        and the physical pages sharing saves (sum of ref_count - 1)."""
        total = free = shared = extra = 0
        for lc in list(self.cache.pattern) + list(self.cache.tail):
            if lc.kv is None:
                continue
            ref = np.asarray(jax.device_get(lc.kv.ref_count)).reshape(-1)
            total += ref.size
            free += int((ref == 0).sum())
            shared += int((ref > 1).sum())
            extra += int((ref[ref > 1] - 1).sum())
        return {"pool_pages": total, "free_pages": free,
                "utilization": (total - free) / total if total else 0.0,
                "shared_pages": shared, "pages_saved_by_sharing": extra}

    def pool_bytes(self) -> dict:
        """HBM accounting for the page-pool PAYLOAD (K/V tensors + int8
        scales — the bytes that scale with budget, and the bytes TP divides;
        pool metadata is replicated by design and reported separately).
        ``per_device_max`` is measured from the real array shards, so the
        benchmark gate ``per_device_max <= total/tp + page`` checks what the
        runtime actually holds, not what the specs promise."""
        total = meta = 0
        per_dev: dict[int, int] = {}
        for lc in list(self.cache.pattern) + list(self.cache.tail):
            if lc.kv is None:
                continue
            kv = lc.kv
            for leaf in (kv.k, kv.v, kv.k_scale, kv.v_scale):
                if leaf is None:
                    continue
                total += leaf.nbytes
                for sh in leaf.addressable_shards:
                    d = sh.device.id
                    per_dev[d] = per_dev.get(d, 0) + sh.data.nbytes
            for leaf in (kv.pos, kv.score, kv.block_table, kv.ref_count,
                         kv.cur_page, kv.cur_off, kv.stats):
                if leaf is not None:
                    meta += leaf.nbytes
        return {"payload_total": total,
                "per_device_max": max(per_dev.values()) if per_dev else 0,
                "metadata_total": meta,
                "devices": len(per_dev)}
