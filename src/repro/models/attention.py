"""GQA attention layer — contiguous (train/prefill) and paged (decode) paths.

Supports: QKV bias (qwen), qk-norm (chameleon/gemma3), sliding-window
(mixtral) and local/global interleave (gemma3), cross-attention to a static
conditioning cache (musicgen), logit soft-capping.

Decode attends against a :class:`PagedLayerCache` via either the pure-jnp
reference (``repro.kernels.ref``-equivalent, used on CPU) or the Pallas
paged-attention kernel (``repro.kernels.ops``, the TPU hot path).
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.core.paged_cache import PagedLayerCache
from repro.models.common import (
    apply_rope,
    causal_attention,
    dense_init,
    rms_head_norm,
)


class StaticKVCache(NamedTuple):
    """Non-growing KV over conditioning (cross-attention); exempt from
    eviction — it is O(cond_len) and shared across all decode steps."""
    k: jax.Array  # (B, Sc, KV, hd)
    v: jax.Array  # (B, Sc, KV, hd)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, cross: bool = False):
    hd = cfg.resolved_head_dim
    D, H, KV = cfg.d_model, cfg.num_heads, cfg.num_kv_heads
    dt = jnp.float32 if cfg.dtype == "float32" else jnp.bfloat16
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], D, H * hd, dt),
        "wk": dense_init(ks[1], D, KV * hd, dt),
        "wv": dense_init(ks[2], D, KV * hd, dt),
        "wo": dense_init(ks[3], H * hd, D, dt, scale=1.0 / math.sqrt(H * hd)),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((H * hd,), dt)
        p["bk"] = jnp.zeros((KV * hd,), dt)
        p["bv"] = jnp.zeros((KV * hd,), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dt)
        p["k_norm"] = jnp.ones((hd,), dt)
    return p


# ---------------------------------------------------------------------------
# projections
# ---------------------------------------------------------------------------

def project_qkv(params, cfg: ModelConfig, x, positions, rope: bool = True):
    """x: (B, S, D) -> q (B,S,H,hd), k, v (B,S,KV,hd). RoPE + qk-norm applied.

    Head counts come from the projection widths, not the config: under
    tensor parallelism (shard_map manual region) wq/wk/wv are column
    shards holding H/tp and KV/tp heads, and the reshape must follow the
    LOCAL width. At TP=1 the two are identical."""
    B, S, D = x.shape
    hd = cfg.resolved_head_dim
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if "bq" in params:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    H, KV = q.shape[-1] // hd, k.shape[-1] // hd
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    if "q_norm" in params:
        q = rms_head_norm(q, params["q_norm"])
        k = rms_head_norm(k, params["k_norm"])
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


# ---------------------------------------------------------------------------
# contiguous path (train / prefill)
# ---------------------------------------------------------------------------

def attention_forward(params, cfg: ModelConfig, spec: LayerSpec, x, positions,
                      return_kv: bool = False, use_pallas: bool = False):
    """Causal self-attention over a contiguous sequence.

    Returns (out (B,S,D), (k, v) post-rope if return_kv else None).
    ``use_pallas``: route through the Pallas flash kernel (TPU hot path;
    proper triangle/window block skipping); a shape the kernel cannot tile
    raises rather than silently taking the jnp path.
    """
    q, k, v = project_qkv(params, cfg, x, positions)
    window = 0
    if spec.attn_kind == "swa":
        window = cfg.sliding_window
    elif spec.attn_kind == "local":
        window = cfg.local_window
    B, S = x.shape[:2]
    if use_pallas:
        from repro.kernels.ops import flash_attention
        out = flash_attention(q, k, v, window=window)
    else:
        out = causal_attention(q, k, v, q_positions=positions,
                               kv_positions=positions, window=window)
    out = out.reshape(B, S, -1) @ params["wo"]
    return out, ((k, v) if return_kv else None)


def cross_attention_forward(params, cfg: ModelConfig, x, cache: StaticKVCache):
    """Cross-attention to static conditioning KV (no causality, no rope)."""
    B, S, D = x.shape
    hd = cfg.resolved_head_dim
    H, KV = cfg.num_heads, cfg.num_kv_heads
    q = (x @ params["wq"]).reshape(B, S, H, hd)
    G = H // KV
    qg = q.reshape(B, S, KV, G, hd)
    scale = 1.0 / math.sqrt(hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                   cache.k.astype(jnp.float32)) * scale
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, cache.v.astype(jnp.float32))
    o = o.reshape(B, S, H * hd).astype(x.dtype)
    return o @ params["wo"]


def make_cross_cache(params, cfg: ModelConfig, cond) -> StaticKVCache:
    """cond: (B, Sc, D) conditioning embeddings -> static KV."""
    B, Sc, D = cond.shape
    hd = cfg.resolved_head_dim
    KV = cfg.num_kv_heads
    k = (cond @ params["wk"]).reshape(B, Sc, KV, hd)
    v = (cond @ params["wv"]).reshape(B, Sc, KV, hd)
    return StaticKVCache(k=k, v=v)


# ---------------------------------------------------------------------------
# paged decode path
# ---------------------------------------------------------------------------

def paged_attention_ref(q, cache: PagedLayerCache, *, cur_pos, window: int = 0,
                        sink_keep: int = 0, scale: float | None = None,
                        soft_cap: float = 0.0):
    """Single-token GQA attention over a paged cache (pure-jnp oracle).

    q: (B, H, hd) — the current token's query (RoPE'd at cur_pos).
    cur_pos: (B,) int32 current position (new token's position).
    Masks: invalid slots (pos<0), future slots (pos>cur_pos), and for
    windowed layers pos <= cur_pos - window (sinks exempt).
    Returns (B, H, hd).
    """
    B, H, hd = q.shape
    P, page, KV = cache.num_pages, cache.page_size, cache.k.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    # gather the shared pool into this request's logical view (the pure-jnp
    # oracle materializes the indirection the Pallas kernel streams)
    kf = cache.k_view().reshape(B, P * page, KV, hd)
    vf = cache.v_view().reshape(B, P * page, KV, hd)
    pos = cache.pos_view().reshape(B, P * page)
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32),
                   kf.astype(jnp.float32)) * scale
    if soft_cap:
        s = jnp.tanh(s / soft_cap) * soft_cap
    mask = (pos >= 0) & (pos <= cur_pos[:, None])
    if window:
        in_win = pos > (cur_pos[:, None] - window)
        if sink_keep:
            in_win |= pos < sink_keep
        mask &= in_win
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    o = jnp.einsum("bkgs,bskd->bkgd", p, vf.astype(jnp.float32))
    return o.reshape(B, H, hd).astype(q.dtype)


def paged_attention_chunk_ref(q, cache: PagedLayerCache, *, q_pos,
                              window: int = 0, scale: float | None = None):
    """Chunked-prefill GQA attention over a paged cache (the unified-step
    CPU path). q: (B, T, H, hd) — a contiguous chunk of queries, RoPE'd at
    q_pos; q_pos: (B, T) int32, -1 marks padding queries (rows shorter
    than the chunk), which return zeros. The chunk's own K/V must already
    be appended to the pool (write-then-attend), so intra-chunk causality
    is just pos <= q_pos. Returns (B, T, H, hd).

    Gathers the pool into the request view and delegates to the single
    chunk-attention oracle in ``kernels/ref.py`` — one copy of the
    masking/causality logic, shared with the Pallas kernel's parity tests.
    """
    from repro.kernels.ref import paged_prefill_attention_ref

    B, T, H, hd = q.shape
    KV = cache.k.shape[2]
    G = H // KV
    kg = jnp.moveaxis(cache.k_view(), 3, 1)        # (B, KV, P, page, hd)
    vg = jnp.moveaxis(cache.v_view(), 3, 1)
    out = paged_prefill_attention_ref(q.reshape(B, T, KV, G, hd), kg, vg,
                                      cache.pos_view(), q_pos,
                                      window=window, scale=scale)
    return out.reshape(B, T, H, hd)


def decode_attention(q, cache: PagedLayerCache, *, cur_pos, window: int = 0,
                     use_pallas: bool = False, num_splits: int = 1,
                     want_scores: bool = False, tp_axis: str | None = None):
    """Single-token attention dispatch: Pallas split-K decode kernel or the
    pure-jnp oracle. q: (B, H, hd). Returns ``(o, page_scores)`` where
    page_scores is the fused eviction-score epilogue (B, P) when
    ``want_scores`` and the kernel ran, else None (callers fall back to the
    stored-score path). ``num_splits`` partitions the page walk
    (DESIGN.md §8); the oracle ignores it (math is split-invariant).
    ``tp_axis``: mesh axis the KV heads are sharded over — the fused score
    epilogue pmeans its per-head norms across it (attention itself needs no
    collective: each query group attends only its own local KV heads)."""
    if use_pallas:
        from repro.kernels.ops import paged_attention
        if want_scores:
            return paged_attention(q, cache, cur_pos=cur_pos, window=window,
                                   num_splits=num_splits, return_scores=True,
                                   tp_axis=tp_axis)
        return paged_attention(q, cache, cur_pos=cur_pos, window=window,
                               num_splits=num_splits), None
    return paged_attention_ref(q, cache, cur_pos=cur_pos, window=window), None


def step_attention(q, cache: PagedLayerCache, *, q_pos, window: int = 0,
                   use_pallas: bool = False, decode_splits: int = 1,
                   want_scores: bool = False, tp_axis: str | None = None):
    """Unified-step attention dispatch (the hot-path switch that used to
    live inline in ``transformer._step_layer``). q: (B, T, H, hd), q_pos:
    (B, T). T == 1 routes to the split-K decode kernel — one query row
    shouldn't pay the chunk kernel's tile shape, and the split-K walk
    shortens the serial chain; otherwise the G-fold chunked-prefill kernel
    (each K/V page DMA'd once per KV-head group) or the jnp chunk oracle.
    Returns ``(o (B, T, H, hd), page_scores (B, P) | None)``."""
    B, T = q.shape[:2]
    if use_pallas and T == 1:
        o, ps = decode_attention(q[:, 0], cache, cur_pos=q_pos[:, 0],
                                 window=window, use_pallas=True,
                                 num_splits=decode_splits,
                                 want_scores=want_scores, tp_axis=tp_axis)
        return o[:, None], ps
    if use_pallas:
        from repro.kernels.ops import paged_prefill_attention
        if want_scores:
            return paged_prefill_attention(q, cache, q_pos=q_pos,
                                           window=window, return_scores=True,
                                           tp_axis=tp_axis)
        return paged_prefill_attention(q, cache, q_pos=q_pos,
                                       window=window), None
    return paged_attention_chunk_ref(q, cache, q_pos=q_pos,
                                     window=window), None


def decode_project_qkv(params, cfg: ModelConfig, x, cur_pos):
    """x: (B, D) single token -> q (B,H,hd), k, v (B,KV,hd), RoPE at cur_pos.

    Head counts derive from the projection widths (shard-local under TP,
    matching :func:`project_qkv`)."""
    B, D = x.shape
    hd = cfg.resolved_head_dim
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if "bq" in params:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    H, KV = q.shape[-1] // hd, k.shape[-1] // hd
    q = q.reshape(B, H, hd)
    k = k.reshape(B, KV, hd)
    v = v.reshape(B, KV, hd)
    if "q_norm" in params:
        q = rms_head_norm(q, params["q_norm"])
        k = rms_head_norm(k, params["k_norm"])
    # apply_rope expects (..., seq, heads, hd); lift to seq=1 then squeeze
    q = apply_rope(q[:, None], cur_pos[:, None], cfg.rope_theta)[:, 0]
    k = apply_rope(k[:, None], cur_pos[:, None], cfg.rope_theta)[:, 0]
    return q, k, v
