"""Jit'd public wrappers around the Pallas kernels.

Each ``pl.pallas_call`` lowers to Mosaic on a TPU backend and runs in the
Pallas interpreter on any other (the CPU tests); the kernels decide at
trace time (:func:`repro.kernels.interpret_mode`).

The paged-attention wrappers only permute the page POOL into the kernel's
(KV, N_pool, page, hd) tile layout — the per-request view is never
materialized; indirection happens inside the kernel through the
scalar-prefetched block table (DESIGN.md §2).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.importance import page_scores_from_norms
from repro.core.paged_cache import PagedLayerCache
from repro.kernels.block_score import block_score_kernel
from repro.kernels.flash_prefill import (
    flash_attention_kernel,
    paged_flash_prefill_kernel,
)
from repro.kernels.paged_attention import paged_attention_kernel


def _pool_layout(arr):
    """(N, page, KV, hd) -> (KV, N, page, hd) contiguous page tiles."""
    return jnp.moveaxis(arr, 2, 0)


def _epilogue_scores(cache: PagedLayerCache, norms, tp_axis=None):
    """(kn, vn) epilogue outputs (B, KV, P, page) -> Alg.1 page scores
    (B, P); identical to the standalone block_score pass (the oracle).
    Under TP the kernel only saw the LOCAL KV heads; ``tp_axis`` pmeans
    the head means across the mesh so every shard scores globally."""
    kn, vn = norms
    return page_scores_from_norms(kn, vn, cache.pos_view(),
                                  cache.mapped_mask(), axis_name=tp_axis)


def paged_attention(q, cache: PagedLayerCache, *, cur_pos, window: int = 0,
                    scale: float | None = None, num_splits: int = 1,
                    return_scores: bool = False, tp_axis: str | None = None):
    """Decode attention over a pooled paged cache via the Pallas kernel.

    q: (B, H, hd) current-token queries -> (B, H, hd), or
    ``(out, page_scores)`` with page_scores (B, P) when ``return_scores``
    (the fused eviction-score epilogue, DESIGN.md §8). The kernel walks
    the block table a block of pages per grid step; ``num_splits``
    partitions that walk into independent split-K chunks (DESIGN.md §8).
    """
    B, H, hd = q.shape
    KV = cache.k.shape[2]
    G = H // KV
    if cache.quantized:
        # int8-native: K/V stream to VMEM as int8 and dequantize in-register
        # (HBM traffic ~0.53x of bf16 — the quantized-KV composition the
        # paper cites as future work)
        from repro.kernels.paged_attention import paged_attention_kernel_int8
        res = paged_attention_kernel_int8(
            q.reshape(B, KV, G, hd),
            _pool_layout(cache.k), _pool_layout(cache.v),
            jnp.moveaxis(cache.k_scale, 2, 0),
            jnp.moveaxis(cache.v_scale, 2, 0),
            cache.pos, cache.block_table, cur_pos,
            window=window, scale=scale,
            num_splits=num_splits, return_scores=return_scores)
    else:
        res = paged_attention_kernel(
            q.reshape(B, KV, G, hd),
            _pool_layout(cache.k), _pool_layout(cache.v),
            cache.pos, cache.block_table, cur_pos,
            window=window, scale=scale,
            num_splits=num_splits, return_scores=return_scores)
    if return_scores:
        out, norms = res
        return out.reshape(B, H, hd), _epilogue_scores(cache, norms, tp_axis)
    return res.reshape(B, H, hd)


def paged_prefill_attention(q, cache: PagedLayerCache, *, q_pos,
                            window: int = 0, scale: float | None = None,
                            return_scores: bool = False,
                            tp_axis: str | None = None):
    """Chunked-prefill attention over a pooled paged cache via the Pallas
    paged flash-prefill kernel (the unified-step hot path, G-fold fetch).

    q: (B, T, H, hd) chunk queries; q_pos: (B, T) int32 (-1 == padding)
    -> (B, T, H, hd), or ``(out, page_scores)`` with page_scores (B, P)
    when ``return_scores``. The chunk's K/V must already be appended to
    the pool (write-then-attend). int8 caches dequantize pool-side before
    the call (the chunk kernel is f32-tile only; an int8-native variant is
    the same follow-up the decode kernel already landed)."""
    if cache.quantized:
        k_pool, v_pool = cache.k_dequant(), cache.v_dequant()
    else:
        k_pool, v_pool = cache.k, cache.v
    res = paged_flash_prefill_kernel(
        q, _pool_layout(k_pool), _pool_layout(v_pool),
        cache.pos, cache.block_table, q_pos,
        window=window, scale=scale, return_scores=return_scores)
    if return_scores:
        out, norms = res
        return out, _epilogue_scores(cache, norms, tp_axis)
    return res


def page_scores(cache: PagedLayerCache):
    """Standalone page scoring (paper Alg.1 block mode): (B, P) f32. Each
    physical page is reduced once on the pool, then gathered per request.

    Since the fused epilogue (DESIGN.md §8) this is the slow/oracle path —
    the hot paths get the same scores as attention byproducts. int8 pools
    dequantize first so both paths score identical values (the kernels'
    epilogue norms are taken on dequantized VMEM tiles)."""
    if cache.quantized:
        k_pool, v_pool = cache.k_dequant(), cache.v_dequant()
    else:
        k_pool, v_pool = cache.k, cache.v
    pool = block_score_kernel(k_pool, v_pool, cache.pos)    # (N,)
    return jnp.where(cache.mapped_mask(),
                     jnp.take(pool, jnp.maximum(cache.block_table, 0)),
                     jnp.inf)


def flash_attention(q, k, v, *, window: int = 0, scale: float | None = None,
                    block_q: int = 128, block_k: int = 128):
    """Causal GQA flash attention. q: (B,S,H,hd); k,v: (B,S,KV,hd)."""
    return flash_attention_kernel(q, k, v, window=window, scale=scale,
                                  block_q=block_q, block_k=block_k)
