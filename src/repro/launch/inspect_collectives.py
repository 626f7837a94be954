import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")

"""Dump the collective set (op, group, dtype, bytes) of a compiled program.

Two modes:

**Serving** (``--serve-tp N``): lower the engine's actual tensor-parallel
unified step — the shard_map'd ``Engine._step_impl`` over the (1, N) serving
mesh (DESIGN.md §11) — for both the mixed/prefill program (T = chunk) and
the decode-only program (T = 1), and print every psum/all-gather XLA emitted.
``--json FILE`` writes the set as a stable artifact so CI can diff it: the
sharded step must stay all-reduce-only (no all-gathers, no all-to-alls —
those would mean a spec regression reassembling the pool or the logits).

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
    PYTHONPATH=src python -m repro.launch.inspect_collectives \\
        --arch gemma3-27b --serve-tp 4 --json /tmp/collectives.json

**Dry-run** (``--shape``): the original production-mesh profiler — dump the
largest collectives from a compiled (arch x shape x mesh x policy)
combination, the 'profile' the §Perf hillclimb iterates against.

    PYTHONPATH=src python -m repro.launch.inspect_collectives \\
        --arch mixtral-8x7b --shape train_4k --top 15
"""

import argparse
import json
from collections import Counter

from repro.launch.analysis import _COLL_RE, _group_size, _type_bytes


def collective_lines(hlo_text: str, top: int = 20):
    rows = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m or "-done(" in line:
            continue
        op = m.group(2)
        b = _type_bytes(m.group(1))
        g = _group_size(line, 16)
        name = line.strip().split(" = ")[0][-60:]
        rows.append((b, op, g, m.group(1)[:60], name))
    rows.sort(reverse=True)
    return rows[:top]


def collective_set(hlo_text: str, default_group: int) -> dict:
    """Regression-able summary: per-op counts and result bytes, plus the
    sorted multiset of (op, group, dtype-shape) signatures. Stable across
    runs of the same build (no SSA names, no ordering dependence)."""
    counts: Counter = Counter()
    result_bytes: Counter = Counter()
    sigs = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m or "-done(" in line:
            continue
        op = m.group(2)
        g = _group_size(line, default_group)
        counts[op] += 1
        result_bytes[op] += _type_bytes(m.group(1))
        sigs.append(f"{op} group={g} {m.group(1)}")
    return {"counts": dict(sorted(counts.items())),
            "result_bytes": dict(sorted(result_bytes.items())),
            "signatures": sorted(sigs)}


def lower_serving_step(arch: str, tp: int, policy: str, budget: int,
                       page: int, use_pallas: bool):
    """Build a reduced serving engine at the requested TP degree and lower
    its shard_map'd unified step for T = chunk (mixed) and T = 1 (decode).
    Returns {program_name: hlo_text}."""
    import jax
    import jax.numpy as jnp

    from repro.configs import CacheConfig, get_arch
    from repro.models.transformer import init_model
    from repro.obs import ObsConfig
    from repro.serving import Engine, SamplingParams

    cfg = get_arch(arch).reduced(tp=max(tp, 2))
    params = init_model(jax.random.PRNGKey(0), cfg)
    ccfg = CacheConfig(page_size=page, cache_budget=budget, policy=policy,
                       dtype="float32")
    eng = Engine(cfg, params, cache_cfg=ccfg, max_batch=2,
                 max_prompt_len=4 * page, max_new_tokens=4,
                 sampling=SamplingParams(greedy=True), chunk_size=2 * page,
                 seed=0, tp=tp, use_pallas=use_pallas, obs=ObsConfig())
    B = eng.max_batch
    key = jax.random.PRNGKey(0)
    texts = {}
    for name, T, program in (("mixed", eng.chunk_size, eng._step_mixed),
                             ("decode", 1, eng._step_decode)):
        args = (eng.params, jnp.zeros((B, T), jnp.int32),
                jnp.zeros((B,), jnp.int32), jnp.zeros((B,), bool),
                jnp.zeros((B,), bool), jnp.zeros((B,), bool),
                jnp.full((B,), -1, jnp.int32), jnp.zeros((B,), jnp.int32),
                eng.cache, key)
        texts[name] = program.lower(*args).compile().as_text()
    eng.close()
    return texts


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default=None,
                    help="dry-run mode: production-mesh shape name")
    ap.add_argument("--serve-tp", type=int, default=0, metavar="N",
                    help="serving mode: lower the engine's unified step "
                         "shard_map'd at tp=N and print its collectives")
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--policy", default=None)
    ap.add_argument("--budget", type=int, default=4096)
    ap.add_argument("--page", type=int, default=16)
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--use-pallas", action="store_true")
    ap.add_argument("--top", type=int, default=20)
    ap.add_argument("--json", default=None, metavar="FILE",
                    help="write the collective set here (regression diff)")
    args = ap.parse_args()

    if bool(args.shape) == bool(args.serve_tp):
        ap.error("exactly one of --shape (dry-run) or --serve-tp (serving) "
                 "is required")

    if args.serve_tp:
        pol = args.policy or "paged_eviction"
        budget = args.budget if args.budget != 4096 else 32
        texts = lower_serving_step(args.arch, args.serve_tp, pol, budget,
                                   args.page if args.page != 16 else 4,
                                   args.use_pallas)
        out = {}
        for name, txt in texts.items():
            cs = collective_set(txt, args.serve_tp)
            out[name] = cs
            print(f"== serving step collectives: {args.arch} tp={args.serve_tp}"
                  f" x {pol} x {name} ==")
            if not cs["signatures"]:
                print("  (none)")
            for sig in cs["signatures"]:
                print(f"  {sig}")
            print(f"  totals: {cs['counts']} result_bytes="
                  f"{cs['result_bytes']}")
        if args.json:
            with open(args.json, "w") as f:
                json.dump({"arch": args.arch, "tp": args.serve_tp,
                           "policy": pol, "programs": out},
                          f, indent=1, sort_keys=True)
                f.write("\n")
            print(f"wrote {args.json}")
        return

    from repro.launch.dryrun import build_lowerable, default_policy
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
    pol = args.policy or default_policy(args.shape)
    with mesh:
        jfn, fargs = build_lowerable(args.arch, args.shape, mesh, pol,
                                     args.budget, args.page, args.zero1)
        compiled = jfn.lower(*fargs).compile()
    txt = compiled.as_text()
    print(f"== top collectives: {args.arch} x {args.shape} x {args.mesh} "
          f"x {pol} ==")
    for b, op, g, ty, name in collective_lines(txt, args.top):
        print(f"  {b / 1e9:8.2f} GB  {op:18s} group={g:3d}  {ty}  {name}")


if __name__ == "__main__":
    main()
