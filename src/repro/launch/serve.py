"""Serving driver: continuous batching with a selectable eviction policy.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --reduced \
        --policy paged_eviction --budget 64 --page 8 --requests 8 \
        --trace /tmp/trace.jsonl --snapshot /tmp/metrics.json

Prints the obs metrics dashboard (latency histograms with p50/p90/p99,
pool counters) after the run; ``--trace`` additionally writes one JSONL
event per engine step (schema: repro.obs.trace, validate with
``python -m repro.obs.trace FILE``)."""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import CacheConfig, get_arch
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_tp_mesh
from repro.obs import ObsConfig
from repro.serving import Engine, SamplingParams, init_params


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--policy", default="paged_eviction")
    ap.add_argument("--budget", type=int, default=64)
    ap.add_argument("--page", type=int, default=8)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=96)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--chunk", type=int, default=64,
                    help="prefill chunk size (tokens/step/request)")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="max tokens per unified step (default "
                         "max_batch + chunk)")
    ap.add_argument("--greedy", action="store_true", default=True)
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable CoW prefix sharing across requests")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="give every request this many common leading "
                         "prompt tokens (exercises prefix sharing)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="FILE",
                    help="write a per-step JSONL trace here")
    ap.add_argument("--timeline", default=None, metavar="FILE",
                    help="write a Chrome-trace/Perfetto JSON timeline of "
                         "per-request spans here (load in chrome://tracing "
                         "or ui.perfetto.dev)")
    ap.add_argument("--lineage", action="store_true",
                    help="keep a host-side page-lineage ledger (emits v2 "
                         "'event' records into --trace and prints a "
                         "reconciliation + per-request loss summary)")
    ap.add_argument("--regret-every", type=int, default=0, metavar="N",
                    help="probe eviction regret every N decode steps per "
                         "request against an uncompressed shadow cache "
                         "(0 = off; emits v2 'probe' records into --trace)")
    ap.add_argument("--snapshot", default=None, metavar="FILE",
                    help="write the final metrics snapshot (JSON) here")
    ap.add_argument("--tp", type=int, default=1, metavar="N",
                    help="tensor-parallel degree: serve the unified step "
                         "shard_map'd over an N-device (1, N) mesh — KV-head-"
                         "sharded pool/kernels, replicated scheduler "
                         "(DESIGN.md §11). On CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N first")
    ap.add_argument("--no-metrics", action="store_true",
                    help="disable all engine instrumentation (the bare "
                         "baseline the BENCH_obs overhead gate compares to)")
    ap.add_argument("--profile-annotations", action="store_true",
                    help="wrap plan/step in jax.profiler.TraceAnnotation")
    args = ap.parse_args()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced(tp=args.tp)
    if cfg.num_codebooks > 1:
        raise SystemExit("serve driver targets text archs; see examples/ for "
                         "audio decode")
    enable_compile_cache()
    mesh = make_tp_mesh(args.tp) if args.tp > 1 else None
    params = init_params(cfg, jax.random.PRNGKey(args.seed), mesh)
    ccfg = CacheConfig(page_size=args.page, cache_budget=args.budget,
                       policy=args.policy,
                       dtype="float32" if args.reduced else "bfloat16")
    obs = ObsConfig(metrics=not args.no_metrics, trace_path=args.trace,
                    profiler_annotations=args.profile_annotations,
                    timeline=args.timeline is not None,
                    lineage=args.lineage,
                    regret_every=args.regret_every)
    eng = Engine(cfg, params, cache_cfg=ccfg, max_batch=args.max_batch,
                 max_prompt_len=args.prompt_len,
                 max_new_tokens=args.new_tokens,
                 sampling=SamplingParams(greedy=args.greedy),
                 chunk_size=args.chunk, token_budget=args.token_budget,
                 prefix_sharing=not args.no_prefix_sharing, obs=obs,
                 tp=args.tp, mesh=mesh)

    rng = np.random.default_rng(args.seed)
    shared = rng.integers(0, cfg.vocab_size,
                          size=min(args.shared_prefix, args.prompt_len - 1))
    for _ in range(args.requests):
        n = int(rng.integers(args.prompt_len // 2, args.prompt_len))
        tail = rng.integers(0, cfg.vocab_size, size=max(n - len(shared), 1))
        eng.submit(np.concatenate([shared, tail]).astype(np.int32))
    t0 = time.perf_counter()
    done = eng.run()
    dt = time.perf_counter() - t0
    s = eng.stats
    print(f"policy={args.policy} budget={args.budget} page={args.page}")
    print(f"finished {len(done)} requests, {s.tokens_generated} tokens "
          f"in {dt:.1f}s ({s.tokens_generated/dt:.1f} tok/s incl. compile)")
    print(f"decode-only throughput: {s.decode_tok_per_s:.1f} tok/s; "
          f"steps={s.steps}; programs={eng.num_compiled_programs()}")
    if args.tp > 1:
        pb = eng.pool_bytes()
        print(f"tp={args.tp}: pool payload {pb['payload_total'] / 1e6:.2f} MB"
              f" total, {pb['per_device_max'] / 1e6:.2f} MB max/device "
              f"across {pb['devices']} devices")
    if s.shared_prefix_hits:
        print(f"prefix sharing: {s.shared_prefix_hits} adoptions, "
              f"{s.shared_prefix_tokens} prompt tokens skipped; "
              f"pool={eng.pool_stats()}")
    ttfts = [r.ttft for r in done if r.ttft > 0]
    if ttfts:
        print(f"ttft: mean={1e3 * np.mean(ttfts):.1f}ms "
              f"max={1e3 * np.max(ttfts):.1f}ms (chunk={args.chunk})")
    if args.timeline:
        n = eng.export_timeline(args.timeline)
        print(f"wrote {args.timeline} ({n} timeline events)")
    if args.lineage and eng.obs.ledger is not None:
        led = eng.obs.ledger
        print(f"lineage: {led.counts()}")
        for slot in range(args.max_batch):
            rep = led.request_loss_report(slot)
            if rep["pages_lost"]:
                score = rep["mean_evict_score"]
                print(f"  slot {slot}: lost {rep['pages_lost']} pages / "
                      f"{rep['tokens_lost']} tokens at {rep['positions']} "
                      f"(mean victim score "
                      f"{'n/a' if score is None else format(score, '.3g')})")
    if args.regret_every:
        for req in done:
            summ = req.regret_summary()
            if summ:
                print(f"  req {req.request_id}: {summ['probes']} probes, "
                      f"divergence mean={summ['mean_divergence']:.3g} "
                      f"max={summ['max_divergence']:.3g}, evicted mass "
                      f"mean={summ['mean_evicted_mass']:.3g}")
    eng.close()
    if not args.no_metrics:
        print(eng.obs.registry.render())
    if args.snapshot:
        with open(args.snapshot, "w") as f:
            json.dump(eng.metrics_snapshot(), f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.snapshot}")
    if args.trace:
        print(f"wrote {args.trace} ({eng.obs.writer.events_written} events)")


if __name__ == "__main__":
    main()
