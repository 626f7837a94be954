"""One benchmark run of one cell: build it from its files, warm up, serve
the traffic for a measured window, check what was served against the plain
reference, and reduce the timings (and, when traced, the profiler trace).

A cell is a ``workloads`` entry of ``BENCHMARK.json``; it names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``). A configuration's architecture is the
family file of its ``model_type`` (``bench/arch/<model_type>.py``), and
its tensor-parallel degree its ``tp`` (absent: 1). Per-layer metrics are
readers in ``bench/metrics/<metric>.py``. Adding a cell, a mix, a family or
a metric adds files and entries; nothing here changes.

A model larger than one chip: at ``tp`` > 1 the weights are drawn straight
into the program's tensor-parallel placement over the cell's first ``tp``
chips (``bench/weights.py``), so no chip ever holds more than its share,
and the engine takes them where they lie. The reference then runs on those
same split weights, ``reference.ROWS`` requests per pass, and never gathers
a whole matrix onto one chip; ``bench/rehearse.py`` prints what the draw
and each reference program need per chip.
"""
from __future__ import annotations

import importlib.util
import json
import os
import pathlib
import shutil
import tempfile
import time
from dataclasses import dataclass, field

import numpy as np

from bench import arch

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
DRAIN_S = 60.0       # longest wait past the window for a first token


@dataclass
class Cell:
    name: str
    chips: int
    config: dict             # bench/configs/<config>.json
    mix: dict                # bench/traffic/<traffic>.json
    check: dict              # bench/checks/<workload>.json: limits
    end_to_end: list         # BENCHMARK.json metrics that apply to the cell
    per_layer: list


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: pathlib.Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    w = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if w is None:
        raise SystemExit(f"unknown workload {workload!r}")
    bench = root / "bench"
    cell = Cell(
        name=workload, chips=int(w["chips"]),
        config=json.loads((bench / "configs" / f"{w['config']}.json")
                          .read_text()),
        mix=json.loads((bench / "traffic" / f"{w['traffic']}.json")
                       .read_text()),
        check=json.loads((bench / "checks" / f"{workload}.json").read_text()),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)])
    arch.load(cell.config["config"]["model_type"], bench)
    tp = tensor_parallel(cell.config)
    if tp > cell.chips:
        raise SystemExit(f"{workload}: tp={tp} exceeds the cell's "
                         f"{cell.chips} chips")
    kv = model_config(cell.config).num_kv_heads
    if kv % tp:
        raise SystemExit(f"{workload}: tp={tp} does not divide the "
                         f"{kv} KV heads")
    return cell


def model_config(c: dict):
    """The program's ModelConfig for a configuration file (its family's)."""
    return arch.of(c["config"]).program_config(c)


def tensor_parallel(c: dict) -> int:
    """A configuration file's tensor-parallel degree."""
    return int(c.get("tp", 1))


def load_reader(name: str, root: pathlib.Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclass
class Step:
    """One engine step as the benchmark saw it."""
    t0: float                # host clock before Engine.step()
    t1: float                # after it returned
    engine_s: float          # the engine's own dt (step program + device_get)
    rows: list               # ("d", pos) decode rows, ("p", start, n) chunks
    index: int

    @property
    def kind(self) -> str:
        return "mixed" if any(r[0] == "p" for r in self.rows) else "decode"


@dataclass
class Run:
    """What a run measured; the per-layer readers take it."""
    cell: Cell
    model: dict              # the configuration's published sizes
    cache: dict
    chips: int               # the chips the step runs on: its tp
    peaks: dict
    steps: list = field(default_factory=list)
    trace: object = None
    traced: tuple = None     # (lo_ns, hi_ns) of the traced window
    traced_steps: dict = None  # step index -> (lo_ns, hi_ns) in trace time


class _Clock:
    """Per-request token times, kept as the engine emits. Tokens and gaps
    count where they fall in ``window`` (host clock, open at start)."""

    def __init__(self):
        self.window = (float("inf"), float("inf"))
        self.seen: dict[int, int] = {}
        self.first: dict[int, float] = {}
        self.last: dict[int, float] = {}
        self.gaps: list[float] = []
        self.tokens_in_window = 0

    def observe(self, reqs, now: float):
        lo, hi = self.window
        for r in reqs:
            n = len(r.output_tokens)
            k = self.seen.get(r.request_id, 0)
            if n == k:
                continue
            self.seen[r.request_id] = n
            self.first.setdefault(r.request_id, now)
            last = self.last.get(r.request_id)
            if lo < now <= hi:
                self.tokens_in_window += n - k
                if last is not None and last > lo:
                    self.gaps.append(now - last)
            self.last[r.request_id] = now


# the numbers over the gaps of every served token compared that a cell's
# check file may hold to a limit (``<name>_limit``)
GAP_NUMBERS = {"logit_gap": np.max, "logit_gap_mean": np.mean}


def judge(check: dict, gaps: list) -> tuple[dict, bool]:
    """Each gap number the check file limits, as [value, limit], and whether
    all are within their limits. ``gaps``: per request, the reference's best
    logit minus its logit of the token served, at every served position.
    The control goes through here too (``bench/control.py``)."""
    g = np.concatenate(gaps) if gaps else None
    out = {k: [None if g is None else float(f(g)), float(check[f"{k}_limit"])]
           for k, f in GAP_NUMBERS.items() if f"{k}_limit" in check}
    ok = g is not None and bool(out) and all(v <= lim
                                              for v, lim in out.values())
    return out, ok


def check_devices(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise SystemExit(f"cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def enable_compile_cache():
    import jax

    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_process: float, cell: Cell | None = None, devices=None,
        log=print, keep: dict | None = None) -> dict:
    """One run. ``devices``: the chips to use (the CLI checks they are
    TPUs); ``cell`` overrides loading it from the files (tests); ``keep``,
    when given, receives the weights and the checked sequences."""
    import jax

    from bench import reference, peaks as peaks_mod, stats
    from bench.traffic.generate import Req, generate
    from bench.weights import make_params
    from repro.configs.base import CacheConfig
    from repro.obs import ObsConfig
    from repro.serving import Engine, SamplingParams

    cell = cell or load_cell(workload)
    devices = devices if devices is not None else jax.devices()[:cell.chips]
    c, mix = cell.config, cell.mix
    cc = c["cache"]
    mcfg = model_config(c)
    tp = tensor_parallel(c)
    mesh = (jax.make_mesh((1, tp), ("data", "model"), devices=devices[:tp])
            if tp > 1 else None)
    params = make_params(mcfg, seed, getattr(arch.of(c["config"]), "STD",
                                             None), mesh=mesh)
    reqs = generate(mix, seed, -float(mix.get("preroll_s", 0.0)),
                    seconds + DRAIN_S, mcfg.vocab_size)
    max_prompt = int(mix["prompt_len"]["max"])
    max_new = max(r.max_new_tokens for r in reqs)
    ccfg = CacheConfig(page_size=cc["page_size"], cache_budget=cc["cache_budget"],
                       policy=cc["policy"], dtype=cc["dtype"])
    eng = Engine(mcfg, params, cache_cfg=ccfg, max_batch=int(mix["max_batch"]),
                 max_prompt_len=max_prompt, max_new_tokens=max_new,
                 sampling=SamplingParams(greedy=True), seed=0,
                 chunk_size=cc["chunk_size"],
                 token_budget=int(mix["token_budget"]),
                 obs=ObsConfig(profiler_annotations=trace), tp=tp, mesh=mesh)
    clock = _Clock()
    inflight: list = []
    served: list = []

    def step(record):
        before = None
        if record is not None and record["annotate"]:
            before = [(r, r.prefill_pos, r.status.value, len(r.output_tokens))
                      for r in inflight]
        e0 = eng.stats.prefill_s + eng.stats.decode_s
        t0 = time.perf_counter()
        if before is not None:
            with jax.profiler.TraceAnnotation(f"bench.step#{len(record['steps'])}"):
                eng.step()
        else:
            eng.step()
        now = time.perf_counter()
        clock.observe(inflight, now)
        if before is not None:
            rows = []
            for r, pp, status, n_out in before:
                if status == "running":
                    rows.append(("d", len(r.prompt) + n_out - 1))
                elif r.prefill_pos > pp:
                    rows.append(("p", pp, r.prefill_pos - pp))
        if record is not None:
            record["steps"].append(Step(
                t0, now, eng.stats.prefill_s + eng.stats.decode_s - e0,
                rows if before is not None else [], len(record["steps"])))
        for r in [r for r in inflight if r.finished]:
            inflight.remove(r)
            served.append(r)

    def submit(q):
        r = eng.submit(q.prompt, max_new_tokens=q.max_new_tokens)
        inflight.append(r)
        return r

    # ---- set-up: warm every shape the window uses -------------------------
    closed = mix["loop"] == "closed"
    if closed:
        window_reqs = [submit(q) for q in reqs]
        while any(r.status.value != "running" for r in window_reqs):
            step(None)
        for _ in range(2):                  # compile + warm the T=1 program
            step(None)
    else:
        window_reqs = []
        rng = np.random.default_rng(seed)
        warm = submit(Req(
            rng.integers(0, mcfg.vocab_size, cc["chunk_size"] + 1)
            .astype(np.int32), 3, 0.0))
        while not warm.finished:
            step(None)
        served.clear()
    evicted_setup = eng.stats.pages_evicted
    programs_setup = eng.num_compiled_programs()

    # ---- the measured window ----------------------------------------------
    # Open loop: arrivals start ``preroll_s`` before the window opens, so
    # it opens on a loaded system; after it closes, arrivals go on and the
    # engine serves until every request due in the window has its first
    # token (at most DRAIN_S), so each one's TTFT is measured, not cut.
    record = {"steps": [], "annotate": trace}
    preroll = 0.0 if closed else float(mix["preroll_s"])
    t_open = time.perf_counter() + preroll
    clock.window = (t_open, t_open + seconds if not closed else float("inf"))
    trace_dir = None
    if trace:       # the window's last seconds; stop_trace blocks, so it
        tr_lo = seconds - float(mix["trace_seconds"])    # runs after the drain
    tracing = profiling = False
    due = [] if closed else [(q.due, q) for q in reqs]
    due_reqs = []
    lateness = []
    i = 0
    while True:
        now = time.perf_counter() - t_open
        while i < len(due) and due[i][0] <= now:
            r = submit(due[i][1])
            r.due = t_open + due[i][0]
            lateness.append(now - due[i][0])
            if 0.0 <= due[i][0] < seconds:
                due_reqs.append(r)
            i += 1
        if trace and trace_dir is None and tr_lo <= now < seconds:
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            profiling = True
        elif profiling and not tracing and now < seconds:
            # one step under the profiler before the traced span opens:
            # the first step after start_trace stalls the device
            tspan = jax.profiler.TraceAnnotation("bench.traced")
            tspan.__enter__()
            tracing = True
        if tracing and now >= seconds:
            tspan.__exit__(None, None, None)
            tracing = False
        if now >= seconds:
            if closed:
                break
            if all(r.request_id in clock.first for r in due_reqs) or \
                    now >= seconds + DRAIN_S:
                break
        if eng.scheduler.has_work():
            step(record if now >= 0 else None)
        else:
            nxt = due[i][0] if i < len(due) else seconds + DRAIN_S
            time.sleep(max(0.0, nxt - now))
    t_end = time.perf_counter()
    if profiling:
        jax.profiler.stop_trace()
    window_s = (t_end if closed else clock.window[1]) - t_open
    setup_s = t_open - t_process

    # ---- end-to-end metrics -----------------------------------------------
    e2e = {"setup_s": setup_s}
    if clock.gaps:
        e2e["itl_p95_ms"] = stats.percentile(clock.gaps, 95) * 1e3
    e2e["output_tok_s"] = clock.tokens_in_window / window_s
    if not closed and due_reqs:
        tt = [clock.first.get(r.request_id, t_end) - r.due for r in due_reqs]
        e2e["ttft_p90_s"] = stats.percentile(tt, 90)
    evicted_window = eng.stats.pages_evicted - evicted_setup
    forced = eng.stats.forced_evictions
    programs = eng.num_compiled_programs()
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devices)
    log(json.dumps({"eviction": {
        "pages_evicted_setup": evicted_setup,
        "pages_evicted_window": evicted_window,
        "forced_evictions": forced,
        "expect": mix["evicts"]}}))
    log(json.dumps({"window": {
        "seconds": window_s, "steps": len(record["steps"]),
        "requests_due": len(due_reqs), "requests_finished": len(served),
        "waiting_at_end": len(eng.scheduler.waiting),
        "tokens": clock.tokens_in_window, "itl_samples": len(clock.gaps),
        "generator_late_p95_s": (stats.percentile(lateness, 95)
                                 if lateness else 0.0),
        "programs_setup": programs_setup, "programs_end": programs}}))

    # ---- correctness: the served tokens against the reference -------------
    pool = list(window_reqs) if closed else list(served)
    rng = np.random.default_rng([seed, 7])
    chk = cell.check
    sample = []
    if pool:
        longest = max(pool, key=lambda r: len(r.prompt) + len(r.output_tokens))
        rest = [r for r in pool if r is not longest]
        order = rng.permutation(len(rest))
        sample = [longest] + [rest[j] for j in order[:chk["requests"] - 1]]
    seqs = [(np.asarray(r.prompt), np.asarray(r.output_tokens, np.int32))
            for r in sample]
    n_checked = sum(len(s) for _, s in seqs)
    if keep is not None:
        keep.update(params=params, seqs=seqs)
    eng.cache = None                      # free the pool before the reference
    t_ref = time.perf_counter()
    gaps = (reference.served_gaps(params, c["config"], cc, seqs)
            if seqs else [])
    ref_s = time.perf_counter() - t_ref
    ref_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
    evict_ok = {"window": evicted_window > 0,
                "never": evicted_setup + evicted_window == 0}[mix["evicts"]]
    rest_ok = (n_checked >= chk["min_tokens"] and forced == 0 and evict_ok
               and programs == programs_setup)
    gap_checks, gaps_ok = judge(chk, gaps)
    if keep is not None:
        keep.update(gaps=gaps, rest_ok=rest_ok)
    checks = {
        **gap_checks,
        "tokens_checked": [n_checked, int(chk["min_tokens"])],
        "forced_evictions": [forced, 0],
        "pages_evicted_window": [evicted_window,
                                 ">0" if mix["evicts"] == "window" else 0],
        "programs": [programs, programs_setup],
    }
    correct = bool(gaps_ok and rest_ok)

    devs = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(mem)}
    # a request due in the window with no first token by the drain's end
    # (a minute past the close) has failed
    result = {"correct": correct,
              "attempted": len(due_reqs) if not closed else len(reqs),
              "failed": sum(r.request_id not in clock.first
                            for r in (due_reqs if not closed else window_reqs))}
    if not trace:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end if m["name"] in e2e}
    else:
        from bench import trace as trace_mod
        pk = peaks_mod.chip_peaks(devices[0].device_kind)
        run_rec = Run(cell, c["config"], cc, tp, pk,
                      steps=record["steps"])
        files = list(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
        tr = trace_mod.load(str(files[0]))
        shutil.rmtree(trace_dir, ignore_errors=True)
        tr.chips = tr.chips[:tp]            # the chips the step runs on
        run_rec.trace = tr
        win = tr.span("bench.traced")
        run_rec.traced = (win[1], win[2])
        run_rec.traced_steps = {int(n.split("#")[1]): (s, e)
                                for n, s, e in tr.spans
                                if n.startswith("bench.step#")}
        metrics = {}
        for m in cell.per_layer:
            v = load_reader(m["name"])(run_rec)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        result["metrics"] = metrics
        lo, hi = run_rec.traced
        devs["busy_s"] = float(np.mean([trace_mod.busy_ns(ch, lo, hi)
                                        for ch in tr.chips]) / 1e9)
        devs["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = {
            "device_ops": trace_mod.top_ops(tr.chips, lo, hi),
            "idle_gaps": trace_mod.idle_gaps(tr, tr.chips[0], lo, hi)}
    result["device"] = devs
    result["checks"] = checks
    log(json.dumps({"reference": {"requests": len(seqs), "tokens": n_checked,
                                  "seconds": ref_s,
                                  "peak_bytes_after": int(ref_peak)}}))
    return result
