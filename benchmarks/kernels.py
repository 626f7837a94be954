"""Kernel perf pass gate (DESIGN.md §8) -> BENCH_kernels.json.

Covers the three PR optimisations with before/after roofline rows:

  1. split-K flash-decode — decode latency model at contexts {256, 1k, 4k}
     for split factors {1, 2, 4, 8} on the same pool, plus interpret-mode
     parity wall-clock. The primary latency figures are the ROOFLINE MODEL
     (serial grid-chain x per-step latency + combine), the same analytic
     practice as paper_claims.py: interpret mode executes every grid step
     in Python sequentially, so it cannot exhibit the split-axis
     parallelism (megacore `dimension_semantics`, or the model-axis pool
     shard; sharding/rules.py carries the partial specs). Measured
     interpret numbers ride alongside, clearly labeled.
  2. G-fold prefill fetch — HBM bytes moved per chunk-prefill call from
     EXACT BlockSpec accounting (count the tile DMAs each grid executes),
     per-Q-head vs G-fold, on the mixtral / gemma3 GQA head geometries;
     the roofline memory term drops ~Gx.
  3. fused eviction-score epilogue — metadata bytes/latency of the
     standalone block_score pool pass vs the epilogue's marginal outputs
     (two (B, KV, P, page) f32 norm tiles the kernel writes from data
     already in VMEM), plus measured interpret wall-clock of both paths.

Model constants come from repro.launch.mesh (v5p-class chip); the
per-step latency term is the sequential-grid step cost (DMA issue +
(G, hd) x (page, hd) tile on the VPU — latency-bound at decode shapes,
not bandwidth-bound).
"""
from __future__ import annotations

import argparse
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import timeit_call
from repro.launch.mesh import V5E, chip_peaks

BENCH_JSON = pathlib.Path(__file__).resolve().parent.parent / \
    "BENCH_kernels.json"

PAGE = 16
CONTEXTS = [256, 1024, 4096]
SPLITS = [1, 2, 4, 8]
# sequential-grid per-step latency (s): DMA issue + one decode tile on the
# VPU. Decode steps move ~8-16 KB (tens of ns at HBM_BW) — the fixed
# per-step cost dominates, which is exactly why the serial page walk is
# the long-context bottleneck the split shortens.
STEP_LAT_S = 1e-6
# split combine: one (S, G, hd) f32 renormalisation on already-resident
# partials — a handful of VPU ops + max/sum reduces
COMBINE_LAT_S = 2e-6
# the latency model is of a TPU v5e, whatever host runs this script
HBM_BW = chip_peaks(V5E)["hbm_bw"]

F32 = 4


# ---------------------------------------------------------------------------
# 1. split-K decode latency
# ---------------------------------------------------------------------------

def decode_latency_model_us(P: int, splits: int, *, kv: int, g: int, hd: int,
                            page: int = PAGE, itemsize: int = F32) -> float:
    """Roofline latency of one decode call: the splits execute in parallel
    (split axis is grid-parallel / sharded), each walking ceil(P/S) pages
    sequentially; S > 1 pays one combine."""
    pps = -(-P // splits)
    tile_bytes = 2 * page * hd * itemsize            # K + V page per step
    step_s = STEP_LAT_S + tile_bytes / HBM_BW
    combine_s = COMBINE_LAT_S if splits > 1 else 0.0
    # per-(b, kv-head) chain; heads are grid-parallel in the model
    del kv, g
    return (pps * step_s + combine_s) * 1e6


def _synthetic_pool(key, B, KV, hd, P, page, steps=None):
    """Fully-mapped random pool + block tables (no eviction churn — parity
    on churned pools is tests/test_kernel_perf.py's job; the bench only
    needs representative shapes)."""
    N = B * P + 2
    ks = jax.random.split(key, 4)
    kp = jax.random.normal(ks[0], (KV, N, page, hd), jnp.float32)
    vp = jax.random.normal(ks[1], (KV, N, page, hd), jnp.float32)
    bt = jax.random.permutation(ks[2], N)[:B * P].reshape(B, P).astype(jnp.int32)
    pos = np.full((N, page), -1, np.int32)
    btn = np.asarray(bt)
    for b in range(B):
        for p in range(P):
            pos[btn[b, p]] = np.arange(p * page, (p + 1) * page)
    return kp, vp, jnp.asarray(pos), bt


def bench_split_k(quick: bool = True) -> dict:
    B, KV, G, hd = 1, 1, 4, 64
    iters = 3 if quick else 10
    out = {"page_size": PAGE, "B": B, "KV": KV, "G": G, "hd": hd,
           "model": {"step_lat_s": STEP_LAT_S, "combine_lat_s": COMBINE_LAT_S,
                     "hbm_bw": HBM_BW},
           "contexts": {}}
    from repro.kernels.paged_attention import paged_attention_kernel
    for ctx in CONTEXTS:
        P = ctx // PAGE
        kp, vp, pos, bt = _synthetic_pool(jax.random.PRNGKey(ctx), B, KV, hd,
                                          P, PAGE)
        q = jax.random.normal(jax.random.PRNGKey(1), (B, KV, G, hd))
        cur = jnp.full((B,), ctx - 1, jnp.int32)
        row = {}
        base = None
        for s in SPLITS:
            call = jax.jit(lambda q, kp, vp, pos, bt, cur, s=s:
                           paged_attention_kernel(q, kp, vp, pos, bt, cur,
                                                  num_splits=s))
            meas = timeit_call(call, q, kp, vp, pos, bt, cur,
                               iters=iters, warmup=1)
            model = decode_latency_model_us(P, s, kv=KV, g=G, hd=hd)
            if s == 1:
                base = (model, meas)
            row[str(s)] = {"model_latency_us": model,
                           "measured_interpret_us": meas,
                           "model_speedup_vs_split1": base[0] / model}
        out["contexts"][str(ctx)] = row
        print(f"  splitk,ctx={ctx},split8_model_speedup="
              f"{row['8']['model_speedup_vs_split1']:.2f}x")
    return out


# ---------------------------------------------------------------------------
# 2. G-fold prefill HBM bytes
# ---------------------------------------------------------------------------

def prefill_hbm_bytes(B: int, KV: int, G: int, T: int, P: int, *, hd: int,
                      page: int = PAGE, itemsize: int = F32,
                      per_qhead: bool = False) -> int:
    """Exact tile-DMA accounting of one paged chunk-prefill call from the
    kernel's BlockSpecs. per-Q-head grid (B, H, P) re-fetches each K/V page
    per Q head; the G-fold grid (B, KV, P) fetches it once per KV-head
    group. Q/O tiles revisit the same block across the page walk, so Pallas
    fetches/writes them once per (b, head-group)."""
    H = KV * G
    kv_steps = (B * H * P) if per_qhead else (B * KV * P)
    kv_bytes = kv_steps * 2 * page * hd * itemsize       # K + V tiles
    pos_bytes = kv_steps * page * 4                      # kpos tile per step
    q_rows = T if per_qhead else G * T
    groups = (B * H) if per_qhead else (B * KV)
    q_bytes = groups * q_rows * hd * itemsize            # q fetched once
    o_bytes = groups * q_rows * hd * itemsize            # o written once
    qpos_bytes = groups * q_rows * 4
    return kv_bytes + pos_bytes + q_bytes + o_bytes + qpos_bytes


def bench_gfold(quick: bool = True) -> dict:
    from repro.kernels.flash_prefill import (
        paged_flash_prefill_kernel,
        paged_flash_prefill_kernel_per_qhead,
    )
    # production head geometries (bytes model) + reduced interpret run
    GEOMS = {"mixtral-8x7b": dict(KV=8, G=4, hd=128),
             "gemma3-27b": dict(KV=16, G=2, hd=128)}
    T, P, Bm = 128, 256, 8                                # model shape (4k ctx)
    out = {"model_shape": {"B": Bm, "T": T, "P": P, "page": PAGE}, "geoms": {}}
    for name, gm in GEOMS.items():
        before = prefill_hbm_bytes(Bm, gm["KV"], gm["G"], T, P, hd=gm["hd"],
                                   per_qhead=True)
        after = prefill_hbm_bytes(Bm, gm["KV"], gm["G"], T, P, hd=gm["hd"],
                                  per_qhead=False)
        out["geoms"][name] = {
            **gm,
            "hbm_bytes_per_qhead": before,
            "hbm_bytes_gfold": after,
            "bytes_ratio": before / after,
            "memory_s_per_qhead": before / HBM_BW,
            "memory_s_gfold": after / HBM_BW,
        }
        print(f"  gfold,{name},G={gm['G']},bytes_ratio="
              f"{before / after:.2f}x")
    # interpret-mode wall clock + bit parity at reduced scale
    B, KV, G, hd, Tr, Pr = 1, 2, 4, 64, 16, 16
    kp, vp, pos, bt = _synthetic_pool(jax.random.PRNGKey(0), B, KV, hd,
                                      Pr, PAGE)
    q = jax.random.normal(jax.random.PRNGKey(1), (B, Tr, KV * G, hd))
    qpos = jnp.broadcast_to(
        jnp.arange(Pr * PAGE - Tr, Pr * PAGE, dtype=jnp.int32), (B, Tr))
    iters = 3 if quick else 10
    old = jax.jit(lambda *a: paged_flash_prefill_kernel_per_qhead(*a))
    new = jax.jit(lambda *a: paged_flash_prefill_kernel(*a))
    us_old = timeit_call(old, q, kp, vp, pos, bt, qpos, iters=iters, warmup=1)
    us_new = timeit_call(new, q, kp, vp, pos, bt, qpos, iters=iters, warmup=1)
    bitpar = bool(jnp.all(old(q, kp, vp, pos, bt, qpos) ==
                          new(q, kp, vp, pos, bt, qpos)))
    out["interpret"] = {"per_qhead_us": us_old, "gfold_us": us_new,
                        "bit_parity": bitpar}
    print(f"  gfold,interpret,{us_old:.0f}us -> {us_new:.0f}us,"
          f"bit_parity={bitpar}")
    return out


# ---------------------------------------------------------------------------
# 3. fused eviction-score epilogue
# ---------------------------------------------------------------------------

def bench_fused_epilogue(quick: bool = True) -> dict:
    from repro.kernels.block_score import block_score_kernel
    from repro.kernels.paged_attention import paged_attention_kernel
    B, KV, G, hd, P = 2, 2, 2, 64, 16
    N = B * P + 2
    kp, vp, pos, bt = _synthetic_pool(jax.random.PRNGKey(7), B, KV, hd,
                                      P, PAGE)
    q = jax.random.normal(jax.random.PRNGKey(2), (B, KV, G, hd))
    cur = jnp.full((B,), P * PAGE - 1, jnp.int32)
    iters = 3 if quick else 10

    # bytes model: the standalone pass re-reads the whole pool; the fused
    # epilogue only WRITES the two norm tiles (K/V already in VMEM for
    # attention — zero extra reads)
    standalone_bytes = N * PAGE * KV * hd * 2 * F32 + N * PAGE * 4
    fused_extra_bytes = 2 * B * KV * P * PAGE * F32
    ratio = fused_extra_bytes / standalone_bytes

    # pool layout for block_score is (N, page, KV, hd)
    kp_n = jnp.moveaxis(kp, 0, 2)
    vp_n = jnp.moveaxis(vp, 0, 2)
    standalone = jax.jit(lambda k, v, p: block_score_kernel(k, v, p))
    us_standalone = timeit_call(standalone, kp_n, vp_n, pos,
                                iters=iters, warmup=1)
    plain = jax.jit(lambda *a: paged_attention_kernel(*a))
    fused = jax.jit(lambda *a: paged_attention_kernel(*a, return_scores=True))
    us_plain = timeit_call(plain, q, kp, vp, pos, bt, cur,
                           iters=iters, warmup=1)
    us_fused = timeit_call(fused, q, kp, vp, pos, bt, cur,
                           iters=iters, warmup=1)
    out = {
        "shape": {"B": B, "KV": KV, "hd": hd, "P": P, "page": PAGE,
                  "pool_pages": N},
        "standalone_hbm_bytes": standalone_bytes,
        "fused_extra_hbm_bytes": fused_extra_bytes,
        "model_overhead_ratio": ratio,
        "interpret": {
            "standalone_block_score_us": us_standalone,
            "decode_us": us_plain,
            "decode_with_scores_us": us_fused,
            "marginal_us": max(us_fused - us_plain, 0.0),
        },
    }
    print(f"  fused_epilogue,model_overhead={100 * ratio:.1f}% of "
          f"standalone pass,interpret_marginal="
          f"{out['interpret']['marginal_us']:.0f}us")
    return out


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------

def run(quick: bool = True) -> dict:
    print("  [split-K decode]")
    splitk = bench_split_k(quick)
    print("  [G-fold prefill]")
    gfold = bench_gfold(quick)
    print("  [fused score epilogue]")
    fused = bench_fused_epilogue(quick)
    print("  [eviction metadata (Limitation 4) with fused scores]")
    from benchmarks import eviction_overhead
    meta_rows = [
        {"policy": p, "step_us": us, "metadata_us": mus, "pool_free": free}
        for (p, us, mus, free) in eviction_overhead.run(quick=quick)
    ]

    ctx4k = splitk["contexts"]["4096"]
    mx = gfold["geoms"]["mixtral-8x7b"]
    roofline_rows = [
        {"name": "split_k_decode_4k",
         "unit": "us (model latency)",
         "before": ctx4k["1"]["model_latency_us"],
         "after": ctx4k["8"]["model_latency_us"],
         "improvement": ctx4k["8"]["model_speedup_vs_split1"]},
        {"name": "gfold_prefill_mixtral_memory_term",
         "unit": "s (roofline memory term)",
         "before": mx["memory_s_per_qhead"],
         "after": mx["memory_s_gfold"],
         "improvement": mx["bytes_ratio"]},
        {"name": "fused_epilogue_metadata_bytes",
         "unit": "bytes per score refresh",
         "before": fused["standalone_hbm_bytes"],
         "after": fused["fused_extra_hbm_bytes"],
         "improvement": fused["standalone_hbm_bytes"] /
         max(fused["fused_extra_hbm_bytes"], 1)},
    ]
    result = {
        "split_k_decode": splitk,
        "gfold_prefill": gfold,
        "fused_epilogue": fused,
        "eviction_metadata": meta_rows,
        "roofline_rows": roofline_rows,
        "gates": {
            "splitk_4k_speedup_ge_1p5": ctx4k["8"]["model_speedup_vs_split1"]
            >= 1.5,
            "gfold_bytes_ratio_near_G": all(
                g["bytes_ratio"] > 0.7 * g["G"]
                for g in gfold["geoms"].values()),
            "fused_overhead_le_10pct": fused["model_overhead_ratio"] <= 0.10,
        },
    }
    BENCH_JSON.write_text(json.dumps(result, indent=2) + "\n")
    print(f"wrote {BENCH_JSON}")
    for k, v in result["gates"].items():
        print(f"  gate,{k},{'PASS' if v else 'FAIL'}")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    run(quick=not args.full)


if __name__ == "__main__":
    main()
