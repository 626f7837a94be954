"""Benchmark entry point — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full]

Prints ``name,us_per_call,derived`` CSV lines (plus per-benchmark detail).
Quick mode (default) keeps CPU wall time tractable; --full runs the
paper-scaled sweeps used for EXPERIMENTS.md.
"""
from __future__ import annotations

import argparse
import time


def _section(name):
    print(f"== {name} ==", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full sweeps (slow); default is quick mode")
    args = ap.parse_args()
    quick = not args.full

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (accuracy, eviction_overhead, kernels, latency,
                            obs_overhead, page_size_ablation, paper_claims,
                            roofline, throughput)

    t0 = time.perf_counter()
    _section("throughput vs budget (paper Fig. 3a-c)")
    rows = throughput.run(quick=quick)
    for r in rows:
        print(f"throughput_{r.policy}_b{r.budget},"
              f"{1e6 / max(r.throughput_tok_s, 1e-9):.0f},"
              f"{r.throughput_tok_s:.1f} tok/s")

    _section("TPOT vs model size (paper Fig. 3d)")
    for tag, pol, r in latency.run(quick=quick):
        print(f"tpot_{tag}_{pol},{r.tpot_ms * 1000:.0f},{r.tpot_ms:.2f} ms")

    _section("TTFT/ITL under mixed load: chunked vs monolithic prefill")
    for mode, r in latency.run_prefill_modes().items():
        if mode == "setup":
            continue
        print(f"ttft_{mode},{r['long_ttft_ms'] * 1000:.0f},"
              f"{r['long_ttft_ms']:.1f} ms ttft / "
              f"{r['decoder_itl_max_ms']:.1f} ms itl_max")

    _section("eviction bookkeeping overhead (paper Limitation 4)")
    for pol, us, meta_us, _free in eviction_overhead.run(quick=quick):
        print(f"evict_overhead_{pol},{us:.0f},us/step "
              f"(metadata {meta_us:.0f} us)")

    _section("accuracy vs budget on long-context recall (paper Fig. 2 proxy)")
    full_acc, results = accuracy.run(quick=quick)
    print(f"accuracy_full_cache,0,{full_acc:.3f}")
    for (pol, budget), acc in results.items():
        print(f"accuracy_{pol}_b{budget},0,{acc:.3f}")

    _section("page-size ablation (paper Fig. 4)")
    page_size_ablation.run(quick=quick)

    _section("TPU-scale TPOT/throughput claims from dry-runs (paper Fig. 3)")
    paper_claims.run(quick=quick)

    _section("kernel perf pass: split-K / G-fold / fused epilogue (§8)")
    kres = kernels.run(quick=quick)
    for name, ok in kres["gates"].items():
        print(f"kernel_gate_{name},0,{'PASS' if ok else 'FAIL'}")

    _section("telemetry overhead gate: instrumented vs bare TPOT (§9)")
    ores = obs_overhead.run(quick=quick)
    print(f"obs_overhead_gate,{ores['overhead_pct'] * 100:.0f},"
          f"{'PASS' if ores['gate_pass'] else 'FAIL'} "
          f"(median ratio {ores['median_ratio']:.4f} <= "
          f"{obs_overhead.GATE_RATIO}; middle column = basis points)")
    if not ores["gate_pass"]:
        raise SystemExit("obs overhead gate FAILED: telemetry costs more "
                         f"than {(obs_overhead.GATE_RATIO - 1) * 100:.0f}% "
                         "TPOT — see BENCH_obs.json")

    _section("roofline terms from dry-run artifacts (assignment g)")
    roofline.run(quick=quick)

    print(f"total_bench_seconds,{(time.perf_counter() - t0) * 1e6:.0f},"
          f"{time.perf_counter() - t0:.1f}s")


if __name__ == "__main__":
    main()
