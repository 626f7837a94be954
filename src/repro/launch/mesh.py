"""Production mesh construction.

Kept as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
``--xla_force_host_platform_device_count`` before first jax init.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False, layout: str = "2d"):
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod: (2, 16, 16) = 512 chips, axes (pod, data, model) — the pod
    axis crosses the (slow) inter-pod links, so shardings fold it into the
    data-parallel dimension (DESIGN.md §5).

    ``layout="ep"``: the same chips factored as (data, expert, tp) =
    (16, 8, 2) — expert-parallel MoE (experts live on the "expert" axis,
    tokens move via all-to-all; expert-internal d_ff splits over "tp").
    Non-MoE weights shard over the combined ("expert","tp") 16-way axes, so
    dense layers are unchanged."""
    if layout == "ep":
        shape = (2, 16, 8, 2) if multi_pod else (16, 8, 2)
        axes = ("pod", "data", "expert", "tp") if multi_pod else \
            ("data", "expert", "tp")
        return jax.make_mesh(shape, axes)
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many devices exist (tests)."""
    return jax.make_mesh((data, model), ("data", "model"))


def make_tp_mesh(tp: int):
    """Serving tensor-parallel mesh: (1, tp) with axes ("data", "model").

    The size-1 data axis is kept (rather than a model-only mesh) so every
    sharding helper that asks for batch axes keeps resolving; the engine's
    shard_map runs manual over both axes (DESIGN.md §11). Requires at
    least ``tp`` visible devices — CPU CI forces 4 via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` before the
    first jax import."""
    ndev = len(jax.devices())
    if ndev < tp:
        raise ValueError(
            f"tp={tp} needs {tp} devices, found {ndev} (on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={tp} before "
            f"importing jax)")
    return jax.make_mesh((1, tp), ("data", "model"))


# --- per-chip peaks (roofline denominators), keyed by jax ``device_kind`` ---
# Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s bf16,
# 819 GB/s HBM, 1,600 Gbit/s chip-to-chip interconnect over 4 links).
V5E = "TPU v5 lite"
PEAKS = {
    V5E: {"flops_bf16": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def chip_peaks(device_kind: str) -> dict:
    """Peak FLOP/s (bf16), HBM bytes/s and ICI bytes/s per link of one chip.
    A device missing from :data:`PEAKS` is an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}") from None
