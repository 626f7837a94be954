"""Roofline analysis from compiled dry-run artifacts.

Sources:
  compiled.cost_analysis()  -> HLO flops / bytes accessed (per device — the
                               partitioned module is what is analyzed)
  compiled.as_text()        -> post-SPMD optimized HLO; collective bytes are
                               summed from result types of all-gather /
                               all-reduce / reduce-scatter / all-to-all /
                               collective-permute ops with ring-traffic
                               factors (see _RING_FACTORS below).

Terms (seconds), against the TPU v5e peaks (``mesh.PEAKS``) — the dry-run
compiles for placeholder devices, so the chip being modelled is named:
  compute    = flops_per_device / flops_bf16
  memory     = bytes_per_device / hbm_bw
  collective = collective_bytes_per_device / ici_bw
"""
from __future__ import annotations

import json
import re
from dataclasses import asdict, dataclass, field

from repro.launch.mesh import V5E, chip_peaks

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}


# ---------------------------------------------------------------------------
# jaxpr-based flop/byte counting (trip-count aware)
#
# XLA's HLO cost analysis counts a while-loop body ONCE regardless of trip
# count, which silently undercounts every scanned-layer model. The closed
# jaxpr preserves `length` on scan primitives, so this walker multiplies
# nested bodies correctly. flops: dot_general exact (2*M*N*K*batch), other
# ops ~1 flop/output element. bytes: operand+result sizes per op — an
# unfused upper bound on HBM traffic (fusion lowers it; relative ordering
# of the roofline terms is what matters).
# ---------------------------------------------------------------------------

def _aval_bytes(aval) -> int:
    try:
        return int(aval.size) * aval.dtype.itemsize
    except Exception:
        return 0


def _dot_flops(eqn) -> float:
    dn = eqn.params["dimension_numbers"]
    (lc, rc), (lb, rb) = dn
    a = eqn.invars[0].aval
    b = eqn.invars[1].aval
    batch = 1
    for d in lb:
        batch *= a.shape[d]
    contract = 1
    for d in lc:
        contract *= a.shape[d]
    m = 1
    for d in range(a.ndim):
        if d not in lc and d not in lb:
            m *= a.shape[d]
    n = 1
    for d in range(b.ndim):
        if d not in rc and d not in rb:
            n *= b.shape[d]
    return 2.0 * batch * m * n * contract


def _jaxpr_cost(jaxpr) -> tuple[float, float]:
    """Returns (flops, bytes) for one execution of `jaxpr` (open jaxpr)."""
    flops = 0.0
    byts = 0.0
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        sub = None
        mult = 1.0
        if prim == "scan":
            sub = eqn.params["jaxpr"].jaxpr
            mult = float(eqn.params["length"])
        elif prim == "shard_map":
            # body shapes are PER-SHARD: scale by the manual shard count to
            # keep the global-flops convention
            cj = eqn.params["jaxpr"]
            sub = cj.jaxpr if hasattr(cj, "jaxpr") else cj
            mesh = eqn.params["mesh"]
            mult = 1.0
            for ax in eqn.params.get("manual_axes", ()):  # frozenset of names
                mult *= float(mesh.shape[ax])
        elif prim == "while":
            sub = eqn.params["body_jaxpr"].jaxpr      # trip count unknown: x1
        elif prim == "cond":
            f, b_ = 0.0, 0.0
            for br in eqn.params["branches"]:
                bf, bb = _jaxpr_cost(br.jaxpr)
                f, b_ = max(f, bf), max(b_, bb)
            flops += f
            byts += b_
            continue
        elif "jaxpr" in eqn.params:
            cj = eqn.params["jaxpr"]       # ClosedJaxpr OR open Jaxpr (remat2)
            sub = cj.jaxpr if hasattr(cj, "jaxpr") else cj
        elif "call_jaxpr" in eqn.params:
            cj = eqn.params["call_jaxpr"]
            sub = cj.jaxpr if hasattr(cj, "jaxpr") else cj
        elif "fun_jaxpr" in eqn.params:    # custom_jvp/vjp calls
            cj = eqn.params["fun_jaxpr"]
            sub = cj.jaxpr if hasattr(cj, "jaxpr") else cj
        if sub is not None:
            sf, sb = _jaxpr_cost(sub)
            flops += mult * sf
            byts += mult * sb
            continue
        out_b = sum(_aval_bytes(v.aval) for v in eqn.outvars)
        in_b = sum(_aval_bytes(v.aval) for v in eqn.invars
                   if hasattr(v, "aval"))
        if prim in ("scatter", "scatter-add", "scatter_add", "scatter_mul",
                    "scatter_min", "scatter_max", "dynamic_update_slice"):
            # in-place update: traffic = updates + indices (+ result slice),
            # NOT the whole (aliased) operand
            upd = sum(_aval_bytes(v.aval) for v in eqn.invars[1:]
                      if hasattr(v, "aval"))
            byts += 2 * upd
        elif prim in ("gather", "dynamic_slice"):
            idx = sum(_aval_bytes(v.aval) for v in eqn.invars[1:]
                      if hasattr(v, "aval"))
            byts += 2 * out_b + idx
        else:
            byts += in_b + out_b
        if prim == "dot_general":
            flops += _dot_flops(eqn)
        elif prim in ("conv_general_dilated",):
            flops += 2.0 * sum(_aval_bytes(v.aval) / max(v.aval.dtype.itemsize, 1)
                               for v in eqn.outvars)  # rough
        else:
            flops += sum(int(v.aval.size) for v in eqn.outvars
                         if hasattr(v, "aval"))
    return flops, byts


def jaxpr_cost(closed_jaxpr) -> tuple[float, float]:
    """(total flops, total bytes) for a ClosedJaxpr — trip-count aware."""
    return _jaxpr_cost(closed_jaxpr.jaxpr)

# ring-collective traffic per device, as a multiple of the RESULT size
# (N = participant count; factors below use (N-1)/N ~= 1 for N >= 8):
#   all-gather      result is the full tensor; each device receives ~result
#   all-reduce      reduce-scatter + all-gather: ~2x tensor
#   reduce-scatter  each device sends ~full input = result * N
#   all-to-all      ~result
#   collective-permute  result
_COLL_RE = re.compile(
    r"=\s*(?:\(?)((?:\w+\[[0-9,]*\][^ ]*(?:,\s*)?)+)\)?\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_TYPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([0-9, ]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _type_bytes(type_str: str) -> int:
    total = 0
    for m in _TYPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:  # replica_groups=[G,N] iota form: N participants per group
        return max(int(m.group(2)), 1)
    m = _GROUPS_RE.search(line)
    if m:
        return max(len(m.group(1).split(",")), 1)
    return default


@dataclass
class CollectiveStats:
    counts: dict = field(default_factory=dict)        # op -> count
    result_bytes: dict = field(default_factory=dict)  # op -> sum result bytes
    traffic_bytes: float = 0.0                        # ring-model bytes/device


def parse_collectives(hlo_text: str, default_group: int = 16) -> CollectiveStats:
    stats = CollectiveStats()
    seen_start = set()
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        if "-done(" in line:
            continue  # paired with -start; count once
        op = m.group(2)
        rbytes = _type_bytes(m.group(1))
        n = _group_size(line, default_group)
        frac = (n - 1) / n if n > 1 else 0.0
        if op == "all-gather":
            traffic = rbytes * frac
        elif op == "all-reduce":
            traffic = 2 * rbytes * frac
        elif op == "reduce-scatter":
            traffic = rbytes * n * frac
        elif op == "all-to-all":
            traffic = rbytes * frac
        else:  # collective-permute
            traffic = rbytes
        stats.counts[op] = stats.counts.get(op, 0) + 1
        stats.result_bytes[op] = stats.result_bytes.get(op, 0) + rbytes
        stats.traffic_bytes += traffic
    return stats


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    policy: str
    kind: str
    chips: int
    flops_per_device: float        # jaxpr-derived (trip-count aware) / chips
    bytes_per_device: float        # jaxpr-derived unfused bound / chips
    xla_flops_per_device: float    # compiled cost_analysis (scans counted x1)
    xla_bytes_per_device: float
    collective_bytes: float
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float
    useful_flops_ratio: float      # MODEL_FLOPS / (flops_per_device * chips)
    collective_counts: dict
    memory_analysis: dict
    compile_seconds: float
    notes: str = ""

    def table_row(self) -> str:
        return (f"| {self.arch} | {self.shape} | {self.policy} | "
                f"{self.compute_s:.3e} | {self.memory_s:.3e} | "
                f"{self.collective_s:.3e} | {self.dominant} | "
                f"{self.useful_flops_ratio:.2f} |")


def analyze(compiled, *, arch: str, shape: str, mesh_name: str, policy: str,
            kind: str, chips: int, model_flops: float, compile_seconds: float,
            default_group: int = 16, notes: str = "",
            jaxpr_flops: float | None = None,
            jaxpr_bytes: float | None = None) -> Roofline:
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, list):       # some backends return [dict]
        cost = cost[0] if cost else {}
    xla_flops = float(cost.get("flops", 0.0))
    xla_bytes = float(cost.get("bytes accessed", 0.0))
    # jaxpr numbers are GLOBAL; assume even sharding across chips
    flops = (jaxpr_flops / chips) if jaxpr_flops else xla_flops
    byts = (jaxpr_bytes / chips) if jaxpr_bytes else xla_bytes
    coll = parse_collectives(compiled.as_text(), default_group)

    mem = {}
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "alias_size_in_bytes",
                      "generated_code_size_in_bytes"):
                v = getattr(ma, k, None)
                if v is not None:
                    mem[k] = int(v)
    except Exception as e:  # CPU backend may not implement it
        mem["error"] = str(e)

    peak = chip_peaks(V5E)
    compute_s = flops / peak["flops_bf16"]
    memory_s = byts / peak["hbm_bw"]
    collective_s = coll.traffic_bytes / peak["ici_bw"]
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    total = flops * chips
    return Roofline(
        arch=arch, shape=shape, mesh=mesh_name, policy=policy, kind=kind,
        chips=chips, flops_per_device=flops, bytes_per_device=byts,
        xla_flops_per_device=xla_flops, xla_bytes_per_device=xla_bytes,
        collective_bytes=coll.traffic_bytes, compute_s=compute_s,
        memory_s=memory_s, collective_s=collective_s, dominant=dominant,
        model_flops=model_flops,
        useful_flops_ratio=(model_flops / total) if total else 0.0,
        collective_counts={k: [coll.counts[k], coll.result_bytes[k]]
                           for k in coll.counts},
        memory_analysis=mem, compile_seconds=compile_seconds, notes=notes)


def save_roofline(path: str, r: Roofline) -> None:
    with open(path, "w") as f:
        json.dump(asdict(r), f, indent=1)


def model_flops_estimate(cfg, shape_cfg) -> float:
    """MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE); decode: D = batch
    tokens per step."""
    n = cfg.active_param_count()
    if shape_cfg.kind == "train":
        tokens = shape_cfg.seq_len * shape_cfg.global_batch
        return 6.0 * n * tokens
    if shape_cfg.kind == "prefill":
        tokens = shape_cfg.seq_len * shape_cfg.global_batch
        return 2.0 * n * tokens
    return 2.0 * n * shape_cfg.global_batch      # decode: one token/request
