"""Aggregate dry-run artifacts into the §Roofline table.

Reads experiments/dryrun/*.json (written by repro.launch.dryrun) and prints
the per-(arch x shape x policy) roofline terms + dominant bottleneck. This
is the source for EXPERIMENTS.md §Roofline.

Also the before/after gate for kernel perf work: ``--diff OLD_DIR NEW_DIR``
matches artifacts between two dry-run dirs on (arch, shape, mesh, policy,
variant) and prints per-term deltas, so a kernel PR can show its roofline
movement from two artifact snapshots (DESIGN.md §8).

``--obs TRACE.jsonl`` joins the table with MEASURED step timings from a
serving trace (repro.obs.trace schema): per step kind it prints wall-time
percentiles, tokens/step and pool churn, and for roofline rows of the
same policy the measured-vs-modelled step-time ratio (DESIGN.md §9)."""
from __future__ import annotations

import argparse
import glob
import json
import os
import statistics

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments", "dryrun")

COLS = ["arch", "shape", "mesh", "policy", "compute_s", "memory_s",
        "collective_s", "dominant", "useful_flops_ratio"]

_HEAD = ("| arch | shape | mesh | policy | variant | compute (s) | "
         "memory (s) | collective (s) | dominant | useful |\n"
         "| --- | --- | --- | --- | --- | --- | --- | --- | --- | --- |")


def load_rows(art_dir: str = ART_DIR) -> list[dict]:
    rows = []
    for path in sorted(glob.glob(os.path.join(art_dir, "*.json"))):
        with open(path) as f:
            rows.append(json.load(f))
    return rows


def _variant(r: dict) -> str:
    notes = r.get("notes", "")
    tags = []
    if "zero1=True" in notes:
        tags.append("zero1")
    if "cache_dtype=int8" in notes:
        tags.append("int8")
    return "+".join(tags) or "-"


def _key(r: dict) -> tuple:
    return (r["arch"], r["shape"], r["mesh"], r["policy"], _variant(r))


def fmt_row(r: dict) -> str:
    return (f"| {r['arch']} | {r['shape']} | {r['mesh']} | {r['policy']} | "
            f"{_variant(r)} | "
            f"{r['compute_s']:.2e} | {r['memory_s']:.2e} | "
            f"{r['collective_s']:.2e} | **{r['dominant']}** | "
            f"{r['useful_flops_ratio']:.2f} |")


def markdown_table(rows: list[dict]) -> str:
    order = {"train_4k": 0, "prefill_32k": 1, "decode_32k": 2, "long_500k": 3}
    rows = sorted(rows, key=lambda r: (r["arch"], order.get(r["shape"], 9),
                                       r["mesh"], r["policy"], _variant(r)))
    return "\n".join([_HEAD] + [fmt_row(r) for r in rows])


def diff_rows(old_rows: list[dict], new_rows: list[dict]) -> list[dict]:
    """Match artifacts on (arch, shape, mesh, policy, variant); return one
    record per matched pair with per-term before/after and ratios."""
    old = {_key(r): r for r in old_rows}
    out = []
    for r in new_rows:
        o = old.get(_key(r))
        if o is None:
            continue
        rec = {"arch": r["arch"], "shape": r["shape"], "mesh": r["mesh"],
               "policy": r["policy"], "variant": _variant(r)}
        for term in ("compute_s", "memory_s", "collective_s"):
            rec[f"{term}_before"] = o[term]
            rec[f"{term}_after"] = r[term]
            rec[f"{term}_ratio"] = (r[term] / o[term]) if o[term] else 1.0
        rec["dominant_before"] = o["dominant"]
        rec["dominant_after"] = r["dominant"]
        out.append(rec)
    return out


def diff_table(recs: list[dict]) -> str:
    head = ("| arch | shape | mesh | policy | variant | compute | memory | "
            "collective | dominant |\n"
            "| --- | --- | --- | --- | --- | --- | --- | --- | --- |")
    lines = [head]
    for d in recs:
        cells = []
        for term in ("compute_s", "memory_s", "collective_s"):
            cells.append(f"{d[term + '_before']:.2e} -> "
                         f"{d[term + '_after']:.2e} "
                         f"({d[term + '_ratio']:.2f}x)")
        dom = d["dominant_before"]
        if d["dominant_after"] != dom:
            dom += f" -> {d['dominant_after']}"
        lines.append(f"| {d['arch']} | {d['shape']} | {d['mesh']} | "
                     f"{d['policy']} | {d['variant']} | " +
                     " | ".join(cells) + f" | **{dom}** |")
    return "\n".join(lines)


def run(quick: bool = False, art_dir: str = ART_DIR):
    rows = load_rows(art_dir)
    if not rows:
        # degrade loudly, not silently: say why the table is empty, print
        # the (empty) table anyway so downstream parsers see the schema
        reason = ("artifact dir missing" if not os.path.isdir(art_dir)
                  else "artifact dir empty")
        print(f"  roofline: no dry-run artifacts ({reason}: {art_dir}) — "
              "run `python -m repro.launch.dryrun --all` to generate them")
        print(_HEAD)
        print("  roofline,artifacts=0,dominants={}")
        return []
    print(markdown_table(rows))
    doms = {}
    for r in rows:
        doms[r["dominant"]] = doms.get(r["dominant"], 0) + 1
    print(f"  roofline,artifacts={len(rows)},dominants={doms}")
    return rows


def run_diff(old_dir: str, new_dir: str) -> list[dict]:
    old_rows, new_rows = load_rows(old_dir), load_rows(new_dir)
    if not old_rows or not new_rows:
        which = old_dir if not old_rows else new_dir
        print(f"  roofline-diff: no artifacts in {which} — nothing to diff")
        return []
    recs = diff_rows(old_rows, new_rows)
    if not recs:
        print("  roofline-diff: no matching (arch, shape, mesh, policy, "
              "variant) rows between the two dirs")
        return []
    print(diff_table(recs))
    print(f"  roofline-diff,matched={len(recs)},"
          f"unmatched={len(new_rows) - len(recs)}")
    return recs


def _pct(xs: list[float], q: float) -> float:
    if len(xs) == 1:
        return xs[0]
    qs = statistics.quantiles(sorted(xs), n=100, method="inclusive")
    return qs[min(98, max(0, int(round(q * 100)) - 1))]


def trace_summary(events: list[dict]) -> list[dict]:
    """One row per step kind: wall-time percentiles + per-step averages of
    the device pool counters carried in the trace."""
    by_kind: dict = {}
    for ev in events:
        # traces interleave "event" (page lineage) and "probe" (eviction
        # regret) records with the per-step records; the timing summary
        # only consumes steps
        if ev["rec"] != "step":
            continue
        if ev["kind"] == "idle":
            continue
        by_kind.setdefault(ev["kind"], []).append(ev)
    rows = []
    for kind in ("prefill", "mixed", "decode"):
        evs = by_kind.get(kind)
        if not evs:
            continue
        ts = [e["step_ms"] for e in evs]
        n = len(evs)
        rows.append({
            "kind": kind, "steps": n,
            "step_ms_p50": _pct(ts, 0.50), "step_ms_p90": _pct(ts, 0.90),
            "step_ms_p99": _pct(ts, 0.99),
            "step_ms_mean": statistics.mean(ts),
            "plan_ms_mean": statistics.mean(e["plan_ms"] for e in evs),
            "tokens_per_step": sum(e["tokens"] for e in evs) / n,
            "pages_churn_per_step": sum(
                e.get("pages_allocated", 0) + e.get("pages_evicted", 0)
                for e in evs) / n,
        })
    return rows


def run_obs(trace_path: str, art_dir: str = ART_DIR,
            policy: str | None = None) -> list[dict]:
    """Join trace-derived step timings with the roofline table."""
    from repro.obs.trace import validate_file
    errs = validate_file(trace_path)
    if errs:
        print(f"  roofline-obs: {trace_path} fails trace schema:")
        for e in errs[:5]:
            print(f"    {e}")
        return []
    with open(trace_path) as f:
        events = [json.loads(ln) for ln in f]
    rows = trace_summary(events)
    print("| kind | steps | step p50 (ms) | p90 | p99 | plan (ms) | "
          "tok/step | page churn/step |\n"
          "| --- | --- | --- | --- | --- | --- | --- | --- |")
    for r in rows:
        print(f"| {r['kind']} | {r['steps']} | {r['step_ms_p50']:.2f} | "
              f"{r['step_ms_p90']:.2f} | {r['step_ms_p99']:.2f} | "
              f"{r['plan_ms_mean']:.2f} | {r['tokens_per_step']:.1f} | "
              f"{r['pages_churn_per_step']:.1f} |")
    # join: modelled decode-step time (compute+memory+collective, which a
    # roofline treats as the slowest-term bound) vs measured decode p50
    decode = next((r for r in rows if r["kind"] == "decode"), None)
    art_rows = load_rows(art_dir)
    if policy:
        art_rows = [r for r in art_rows if r["policy"] == policy]
    joined = []
    if decode and art_rows:
        for a in art_rows:
            if not a["shape"].startswith("decode"):
                continue
            model_ms = max(a["compute_s"], a["memory_s"],
                           a["collective_s"]) * 1e3
            rec = {**{k: a[k] for k in ("arch", "shape", "mesh", "policy")},
                   "model_step_ms": model_ms,
                   "measured_step_ms_p50": decode["step_ms_p50"],
                   "measured_over_model":
                       decode["step_ms_p50"] / model_ms if model_ms else None}
            joined.append(rec)
            print(f"  roofline-obs,{a['arch']},{a['shape']},{a['policy']},"
                  f"model={model_ms:.3f}ms,"
                  f"measured_p50={decode['step_ms_p50']:.3f}ms,"
                  f"ratio={rec['measured_over_model']:.2f}")
    if not joined:
        print("  roofline-obs: no decode-shape artifacts to join "
              "(trace summary above stands alone)")
    return rows + joined


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--diff", nargs=2, metavar=("OLD_DIR", "NEW_DIR"),
                    help="diff two dry-run artifact dirs (before/after gate)")
    ap.add_argument("--obs", metavar="TRACE_JSONL",
                    help="join the table with step timings from a serving "
                         "trace (repro.obs.trace schema)")
    ap.add_argument("--policy", default=None,
                    help="restrict the --obs join to one policy's rows")
    args = ap.parse_args()
    if args.diff:
        run_diff(*args.diff)
    elif args.obs:
        run_obs(args.obs, policy=args.policy)
    else:
        run()


if __name__ == "__main__":
    main()
