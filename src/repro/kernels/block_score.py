"""Pallas TPU kernel for the paper's page scoring (Alg. 1, block mode).

Computes S_j = mean_{i in page j, valid} ( mean_h ||V_i|| / mean_h ||K_i|| )
directly from the PHYSICAL page pool. Since the kernel perf pass
(DESIGN.md §8) this standalone pass is OFF the hot paths: the decode and
prefill attention kernels emit the same per-token K/V norms as a byproduct
epilogue (the tiles are already in VMEM), and
``importance.page_scores_from_norms`` reduces them to identical page
scores for free. This kernel survives as the parity oracle
(tests/test_kernel_perf.py) and the fallback for windowed layers, whose
fused scores would go stale when out-of-window tokens drop.
Scoring the pool (not per-request views) means each physical page is
reduced exactly once no matter how many block tables map it — the wrapper
(ops.py) gathers pool scores into (B, P) through the block table, and
dequantizes int8 pools first so the oracle matches the epilogue's
dequantized-tile norms.

Grid: (pool_page,). Each step reduces one (page, KV, hd) K and V tile to a
single page score. Empty pages score +inf (never the eviction argmin).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode
from repro.kernels.paged_attention import unit_rows

_EPS = 1e-6


def _block_score_kernel(k_ref, v_ref, pos_ref, o_ref):
    """k_ref, v_ref: (page, KV, hd); pos_ref: (1, page); o_ref: (1, 1)."""
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    pos = pos_ref[0, :]                                    # (page,)
    kn = jnp.sqrt(jnp.sum(k * k, axis=-1))                 # (page, KV)
    vn = jnp.sqrt(jnp.sum(v * v, axis=-1))
    tok = jnp.mean(vn, axis=-1) / jnp.maximum(jnp.mean(kn, axis=-1), _EPS)
    valid = pos >= 0
    cnt = jnp.sum(valid.astype(jnp.float32))
    ssum = jnp.sum(jnp.where(valid, tok, 0.0))
    o_ref[...] = jnp.full((1, 1), jnp.where(cnt > 0,
                                            ssum / jnp.maximum(cnt, 1.0),
                                            jnp.float32(jnp.inf)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def block_score_kernel(k_pool, v_pool, pos, *, interpret: bool | None = None):
    """k_pool, v_pool: (N, page, KV, hd); pos: (N, page) int32
    -> per-physical-page scores (N,) f32."""
    N, page, KV, hd = k_pool.shape
    # per-page rows and scores carry a unit axis so each block spans its
    # array's two trailing dims (Mosaic's block tiling rule)
    out = pl.pallas_call(
        _block_score_kernel,
        grid=(N,),
        in_specs=[
            pl.BlockSpec((None, page, KV, hd), lambda n: (n, 0, 0, 0)),
            pl.BlockSpec((None, page, KV, hd), lambda n: (n, 0, 0, 0)),
            pl.BlockSpec((None, 1, page), lambda n: (n, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, 1), lambda n: (n, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, 1, 1), jnp.float32),
        interpret=interpret_mode(interpret),
    )(k_pool, v_pool, unit_rows(pos))
    return out[:, 0, 0]
