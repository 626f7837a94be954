import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

"""TP scaling benchmark + memory gate (BENCH_tp.json).

For tp in {1, 2, 4} serve the same churned shared-prefix workload on the
SAME reduced(tp=4) config (gemma3 GQA + mixtral MoE) and record, per degree:

  - per-device pool payload bytes (``Engine.pool_bytes()``) — the point of
    TP serving: the pool splits over the KV-head axis, so per-device bytes
    must fall ~1/tp,
  - decode step latency (mean ms/step; CPU-mesh numbers are for trend
    lines, not absolutes),
  - modelled collective bytes per step from the compiled HLO of the decode
    program (ring all-reduce model, ``launch.analysis.parse_collectives``),
  - greedy-token parity vs tp=1.

Exit code IS the gate (CI mesh tier):
  1. parity: every tp degree reproduces the tp=1 tokens exactly;
  2. memory: per_device_max <= payload_total/tp + one page of slack.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
    PYTHONPATH=src python -m benchmarks.tp_scaling --quick
"""
import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import CacheConfig, get_arch
from repro.launch.analysis import parse_collectives
from repro.models.transformer import init_model
from repro.obs import ObsConfig
from repro.serving import Engine, SamplingParams

ARCHS = ("gemma3-27b", "mixtral-8x7b")
TP_DEGREES = (1, 2, 4)


def _build(arch, params, tp, *, budget, page, new_tokens):
    cfg = get_arch(arch).reduced(tp=4)
    ccfg = CacheConfig(page_size=page, cache_budget=budget,
                       policy="paged_eviction", dtype="float32")
    return Engine(cfg, params, cache_cfg=ccfg, max_batch=3,
                  max_prompt_len=48, max_new_tokens=new_tokens,
                  sampling=SamplingParams(greedy=True), chunk_size=16,
                  seed=0, tp=tp, obs=ObsConfig())


def _workload(eng, n_reqs):
    rng = np.random.default_rng(0)
    shared = rng.integers(0, eng.cfg.vocab_size, size=16)
    for i in range(n_reqs):
        tail = rng.integers(0, eng.cfg.vocab_size, size=8 + i)
        eng.submit(np.concatenate([shared, tail]).astype(np.int32))


def _decode_hlo(eng):
    """Compiled HLO of the decode-only (T=1) program, for the collective
    traffic model."""
    B = eng.max_batch
    args = (eng.params, jnp.zeros((B, 1), jnp.int32),
            jnp.zeros((B,), jnp.int32), jnp.zeros((B,), bool),
            jnp.zeros((B,), bool), jnp.zeros((B,), bool),
            jnp.full((B,), -1, jnp.int32), jnp.zeros((B,), jnp.int32),
            eng.cache, jax.random.PRNGKey(0))
    return eng._step_decode.lower(*args).compile().as_text()


def run_arch(arch, *, n_reqs, new_tokens, budget=32, page=4):
    cfg = get_arch(arch).reduced(tp=4)
    params = init_model(jax.random.PRNGKey(0), cfg)
    rows, ref_tokens = [], None
    for tp in TP_DEGREES:
        eng = _build(arch, params, tp, budget=budget, page=page,
                     new_tokens=new_tokens)
        _workload(eng, n_reqs)
        t0 = time.perf_counter()
        done = eng.run(max_steps=1000)
        wall = time.perf_counter() - t0
        toks = {r.request_id: list(r.output_tokens) for r in done}
        if ref_tokens is None:
            ref_tokens = toks
        pb = eng.pool_bytes()
        cs = parse_collectives(_decode_hlo(eng), default_group=tp)
        s = eng.stats
        rows.append({
            "tp": tp,
            "pool_pages": eng.pool_stats()["pool_pages"],
            "devices": pb["devices"],
            "pool_payload_total_bytes": pb["payload_total"],
            "pool_bytes_per_device": pb["per_device_max"],
            "pool_metadata_bytes": pb["metadata_total"],
            "decode_step_ms": (1e3 * s.decode_s / s.decode_steps
                               if s.decode_steps else None),
            "wall_s": round(wall, 3),
            "steps": s.steps,
            "collectives_per_decode_step": cs.counts,
            "collective_result_bytes": cs.result_bytes,
            "modelled_collective_traffic_bytes": int(cs.traffic_bytes),
            "tokens_match_tp1": toks == ref_tokens,
        })
        eng.close()
    return rows


def gate(arch, rows, errors):
    base = rows[0]
    assert base["tp"] == 1
    # one page of per-layer payload: total / pool_pages-per-layer — derive
    # from totals so the slack needs no model introspection
    # ISSUE gate: per-device bytes <= (tp=1 bytes)/tp + one page of slack.
    # pool_pages counts pages across all attention layers, so total/pages
    # IS one page of payload.
    slack = base["pool_payload_total_bytes"] // max(1, base["pool_pages"])
    for r in rows:
        if not r["tokens_match_tp1"]:
            errors.append(f"{arch} tp={r['tp']}: token parity FAILED")
        bound = base["pool_payload_total_bytes"] // r["tp"] + slack
        if r["pool_bytes_per_device"] > bound:
            errors.append(
                f"{arch} tp={r['tp']}: {r['pool_bytes_per_device']} B/device"
                f" > gate {bound} B (= total/{r['tp']} + slack)")
        if r["tp"] > 1 and not r["collectives_per_decode_step"]:
            errors.append(f"{arch} tp={r['tp']}: no collectives in the "
                          "sharded step (spec regression?)")
        unexpected = set(r["collectives_per_decode_step"]) - {"all-reduce"}
        if unexpected:
            errors.append(f"{arch} tp={r['tp']}: unexpected collective ops "
                          f"{sorted(unexpected)} (step must be psum-only)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized workload (fewer requests/tokens)")
    ap.add_argument("--json", default="BENCH_tp.json")
    args = ap.parse_args()

    if len(jax.devices()) < max(TP_DEGREES):
        print(f"need {max(TP_DEGREES)} devices, found {len(jax.devices())} "
              "(set XLA_FLAGS=--xla_force_host_platform_device_count=4)")
        sys.exit(2)

    n_reqs, new_tokens = (4, 6) if args.quick else (6, 12)
    out, errors = {"archs": {}}, []
    for arch in ARCHS:
        rows = run_arch(arch, n_reqs=n_reqs, new_tokens=new_tokens)
        out["archs"][arch] = rows
        gate(arch, rows, errors)
        for r in rows:
            lat = (f"{r['decode_step_ms']:.1f}ms/step"
                   if r["decode_step_ms"] else "n/a")
            print(f"{arch:14s} tp={r['tp']}: "
                  f"{r['pool_bytes_per_device'] / 1e6:6.3f} MB/device "
                  f"(total {r['pool_payload_total_bytes'] / 1e6:.3f} MB), "
                  f"decode {lat}, AR traffic "
                  f"{r['modelled_collective_traffic_bytes']} B/step, "
                  f"parity={'OK' if r['tokens_match_tp1'] else 'FAIL'}")
    out["gate_errors"] = errors
    with open(args.json, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {args.json}")
    if errors:
        print("GATE FAILED:")
        for e in errors:
            print(f"  {e}")
        sys.exit(1)
    print("gate passed: per-device pool bytes <= total/tp + slack, parity "
          "exact, step is all-reduce-only")


if __name__ == "__main__":
    main()
