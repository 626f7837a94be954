"""Generic decoder stack assembled from a ModelConfig's layer pattern.

Four execution modes:
  forward_train   contiguous causal forward, logits over the whole sequence
  forward_prefill contiguous forward that *builds the paged KV caches*
                  (paper Alg.2 one-shot compression per layer before paging
                  — offline / whole-prompt flows)
  forward_step    UNIFIED mixed-batch step (the serving hot path, DESIGN.md
                  §6): up to T tokens per request — decode rows append 1,
                  prefilling rows append a prompt chunk — written straight
                  into the shared page pool (``append_chunk``), attended
                  write-then-attend through block tables, with Alg.3
                  eviction on decode rows and incremental Alg.2 compression
                  (``chunk_prefill_evict``) at each prefill chunk boundary
  decode_step     one token for every request (the T == 1 specialization,
                  kept as the standalone single-token API)

Deep stacks are lowered as ``lax.scan`` over repetitions of the layer
pattern with stacked parameters: HLO size is O(pattern period), not
O(num_layers) (gemma3: 6, jamba: 8, dense: 1). The remainder
(num_layers mod period) is unrolled ("tail").
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import CacheConfig, LayerSpec, ModelConfig
from repro.core import devstats
from repro.core.paged_cache import (
    PagedLayerCache,
    adopt_prefix,
    append_chunk,
    chunk_rollover,
    release_rows,
    row_intact_prefix_pages,
    write_token,
)
from repro.core.policies import EvictionPolicy
from repro.core.prefill import compress_and_page
from repro.models import attention as attn_mod
from repro.models import mamba as mamba_mod
from repro.models import xlstm as xlstm_mod
from repro.models.attention import StaticKVCache
from repro.models.common import apply_norm, dtype_of, embed_init, init_norm
from repro.models.mlp import init_mlp, mlp_forward
from repro.models.moe import init_moe, moe_forward, moe_forward_decode

Identity = lambda x: x


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_layer(key, cfg: ModelConfig, spec: LayerSpec):
    ks = jax.random.split(key, 8)
    dt = dtype_of(cfg.dtype)
    p: dict[str, Any] = {"norm1": init_norm(cfg.norm, cfg.d_model, dt)}
    if spec.mixer == "attn":
        p["attn"] = attn_mod.init_attention(ks[0], cfg)
        if cfg.cross_attention:
            p["xattn"] = attn_mod.init_attention(ks[1], cfg, cross=True)
            p["norm_x"] = init_norm(cfg.norm, cfg.d_model, dt)
    elif spec.mixer == "mamba":
        p["mamba"] = mamba_mod.init_mamba(ks[0], cfg)
    elif spec.mixer == "mlstm":
        p["mlstm"] = xlstm_mod.init_mlstm(ks[0], cfg)
    elif spec.mixer == "slstm":
        p["slstm"] = xlstm_mod.init_slstm(ks[0], cfg)
    else:
        raise ValueError(spec.mixer)
    if spec.mlp == "dense":
        p["norm2"] = init_norm(cfg.norm, cfg.d_model, dt)
        p["mlp"] = init_mlp(ks[2], cfg)
    elif spec.mlp == "moe":
        p["norm2"] = init_norm(cfg.norm, cfg.d_model, dt)
        p["moe"] = init_moe(ks[2], cfg)
    return p


def init_model(key, cfg: ModelConfig):
    cfg.validate()
    dt = dtype_of(cfg.dtype)
    pat = cfg.layer_pattern()
    P, R, rem = cfg.pattern_period, cfg.full_pattern_reps, cfg.remainder_layers
    keys = jax.random.split(key, 4)
    params: dict[str, Any] = {}
    if cfg.num_codebooks > 1:
        params["embed"] = jax.vmap(
            lambda k: embed_init(k, cfg.vocab_size, cfg.d_model, dt)
        )(jax.random.split(keys[0], cfg.num_codebooks))
    else:
        params["embed"] = embed_init(keys[0], cfg.vocab_size, cfg.d_model, dt)

    # pattern slots, each stacked over R repetitions
    def slot_init(slot_key, spec):
        return jax.vmap(lambda k: init_layer(k, cfg, spec))(
            jax.random.split(slot_key, R))

    slot_keys = jax.random.split(keys[1], P)
    params["pattern"] = [slot_init(slot_keys[i], pat[i]) for i in range(P)] \
        if R > 0 else []
    tail_keys = jax.random.split(keys[2], max(rem, 1))
    params["tail"] = [init_layer(tail_keys[i], cfg, pat[i]) for i in range(rem)]
    params["final_norm"] = init_norm(cfg.norm, cfg.d_model, dt)
    if not cfg.tie_embeddings:
        if cfg.num_codebooks > 1:
            params["lm_head"] = jax.vmap(
                lambda k: embed_init(k, cfg.vocab_size, cfg.d_model, dt)
            )(jax.random.split(keys[3], cfg.num_codebooks))
        else:
            params["lm_head"] = embed_init(keys[3], cfg.vocab_size, cfg.d_model, dt)
    return params


# ---------------------------------------------------------------------------
# embeddings / logits (modality-aware; stubs documented in multimodal.py)
# ---------------------------------------------------------------------------

def embed_tokens(params, cfg: ModelConfig, tokens):
    """text/vlm: tokens (B, S) -> (B, S, D). audio: (B, K, S) -> sum of
    per-codebook embeddings (MusicGen-style)."""
    if cfg.num_codebooks > 1:
        # tokens: (B, K, S); embed: (K, V, D) — per-codebook lookup, summed
        per_cb = jax.vmap(lambda emb, tok: jnp.take(emb, tok, axis=0),
                          in_axes=(0, 1))(params["embed"], tokens)  # (K, B, S, D)
        return jnp.sum(per_cb, axis=0)
    return jnp.take(params["embed"], tokens, axis=0)


def lm_logits(params, cfg: ModelConfig, x):
    """x: (B, [S,] D) -> logits (B, [S,] vocab) or (B, [S,] K, vocab)."""
    x = apply_norm(params["final_norm"], x)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    if cfg.num_codebooks > 1:
        out = jnp.einsum("...d,kvd->...kv", x, head)
    else:
        out = jnp.einsum("...d,vd->...v", x, head)
    from repro.models.common import soft_cap
    return soft_cap(out.astype(jnp.float32), cfg.logit_soft_cap)


# ---------------------------------------------------------------------------
# per-layer forward (contiguous)
# ---------------------------------------------------------------------------

def _spec_window(cfg: ModelConfig, spec: LayerSpec) -> int:
    if spec.attn_kind == "swa":
        return cfg.sliding_window
    if spec.attn_kind == "local":
        return cfg.local_window
    return 0


def layer_forward(lp, cfg: ModelConfig, spec: LayerSpec, x, positions,
                  cond=None, ac: Callable = Identity, return_kv: bool = False,
                  return_state: bool = False, use_pallas: bool = False):
    """One decoder layer over a contiguous sequence.

    Returns (x, aux_loss, extras) where extras carries KV (attn) or the
    final recurrent state (mamba/xlstm) when requested.
    """
    x = ac(x)
    h = apply_norm(lp["norm1"], x)
    extras = None
    aux = jnp.zeros((), jnp.float32)
    if spec.mixer == "attn":
        a, kv = attn_mod.attention_forward(
            lp["attn"], cfg, spec, h, positions, return_kv=return_kv,
            use_pallas=use_pallas)
        x = x + a
        if cond is not None and "xattn" in lp:
            hx = apply_norm(lp["norm_x"], x)
            xc = attn_mod.make_cross_cache(lp["xattn"], cfg, cond)
            x = x + attn_mod.cross_attention_forward(lp["xattn"], cfg, hx, xc)
        extras = kv
    elif spec.mixer == "mamba":
        if return_state:
            m, st = mamba_mod.mamba_prefill(lp["mamba"], cfg, h)
            extras = st
        else:
            m = mamba_mod.mamba_forward(lp["mamba"], cfg, h, ac=ac)
        x = x + m
    elif spec.mixer == "mlstm":
        if return_state:
            m, st = xlstm_mod.mlstm_chunkwise(lp["mlstm"], cfg, h,
                                              return_state=True)
            extras = st
        else:
            m = xlstm_mod.mlstm_chunkwise(lp["mlstm"], cfg, h)
        x = x + m
    elif spec.mixer == "slstm":
        if return_state:
            m, st = xlstm_mod.slstm_forward(lp["slstm"], cfg, h,
                                            return_state=True)
            extras = st
        else:
            m = xlstm_mod.slstm_forward(lp["slstm"], cfg, h)
        x = x + m
    if spec.mlp == "dense":
        h2 = apply_norm(lp["norm2"], x)
        x = x + mlp_forward(lp["mlp"], cfg, h2)
    elif spec.mlp == "moe":
        h2 = apply_norm(lp["norm2"], x)
        mo, stats = moe_forward(lp["moe"], cfg, h2, ac=ac)
        x = x + mo
        aux = stats.aux_loss
    return x, aux, extras


# ---------------------------------------------------------------------------
# train forward
# ---------------------------------------------------------------------------

def forward_train(params, cfg: ModelConfig, tokens, cond=None,
                  ac: Callable = Identity, remat: bool = True,
                  use_pallas: bool = False):
    """tokens: (B, S) [or (B, K, S) audio] -> (logits, aux_loss)."""
    x = embed_tokens(params, cfg, tokens)
    B, S = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    pat = cfg.layer_pattern()
    P = cfg.pattern_period

    def rep_body(carry, slot_params):
        x, aux = carry
        for p in range(P):
            x, a, _ = layer_forward(slot_params[p], cfg, pat[p], x, positions,
                                    cond=cond, ac=ac, use_pallas=use_pallas)
            aux = aux + a
        return (x, aux), None

    body = jax.checkpoint(rep_body, prevent_cse=False) if remat else rep_body
    carry = (x, jnp.zeros((), jnp.float32))
    if params["pattern"]:
        carry, _ = lax.scan(body, carry, tuple(params["pattern"]))
    x, aux = carry
    for i, lp in enumerate(params["tail"]):
        x, a, _ = layer_forward(lp, cfg, pat[i], x, positions, cond=cond,
                                ac=ac, use_pallas=use_pallas)
        aux = aux + a
    return lm_logits(params, cfg, x), aux


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

class LayerCaches(NamedTuple):
    """Per-layer decode state for one pattern slot (or tail layer). Exactly
    one of the fields is populated, matching the slot's mixer kind; ``xattn``
    rides along with ``kv`` for cross-attention archs."""
    kv: Any = None        # PagedLayerCache (attn)
    xattn: Any = None     # StaticKVCache (attn + cross_attention)
    mamba: Any = None     # MambaState
    mlstm: Any = None     # MLSTMState
    slstm: Any = None     # SLSTMState


class ModelCache(NamedTuple):
    pattern: Any          # list over P slots; leaves stacked (R, ...)
    tail: Any             # list over remainder layers (unstacked)
    cur_pos: jax.Array    # (B,) int32 — next token position per request


def _layer_cache_shapes(cfg: ModelConfig, spec: LayerSpec, batch: int,
                        seq_len: int, policy: EvictionPolicy,
                        ccfg: CacheConfig, chunk_tokens: int = 0):
    """Slab sizing for one layer (window-aware; see DESIGN.md §3).

    ``chunk_tokens``: chunked-prefill headroom — a row transiently holds up
    to budget + chunk tokens between chunk boundaries (``append_chunk``
    never evicts mid-chunk), so the block table gets ``ceil(chunk/page)``
    extra logical slots. The pool stays ``N = B * P``, so admission still
    cannot over-commit HBM (DESIGN.md §6)."""
    window = _spec_window(cfg, spec)
    hint = seq_len if not window else min(seq_len, window + ccfg.page_size)
    pages = policy.slab_pages(ccfg, hint)
    if chunk_tokens:
        total = -(-seq_len // ccfg.page_size)
        extra = -(-chunk_tokens // ccfg.page_size)
        pages = policy._round_slab(ccfg, min(pages + extra, max(total, pages)))
    return pages


def init_decode_caches(cfg: ModelConfig, batch: int, seq_len: int,
                       policy: EvictionPolicy, ccfg: CacheConfig,
                       cond=None, dtype=None, chunk_tokens: int = 0,
                       track_stats: bool = False):
    """Empty caches for decode-from-scratch (or dry-run ShapeDtype specs).
    ``chunk_tokens``: size block tables for chunked prefill (see
    :func:`_layer_cache_shapes`). ``track_stats``: attach the per-layer
    devstats telemetry vector (DESIGN.md §9); the unified step re-zeroes it
    each iteration, and :func:`collect_step_stats` sums it over layers."""
    from repro.core.paged_cache import init_layer_cache
    dt = dtype or dtype_of(ccfg.dtype)
    pat = cfg.layer_pattern()
    P, R, rem = cfg.pattern_period, cfg.full_pattern_reps, cfg.remainder_layers
    hd = cfg.resolved_head_dim

    def one(spec) -> LayerCaches:
        if spec.mixer == "attn":
            pages = _layer_cache_shapes(cfg, spec, batch, seq_len, policy,
                                        ccfg, chunk_tokens=chunk_tokens)
            kv = init_layer_cache(batch, pages, ccfg.page_size,
                                  cfg.num_kv_heads, hd, dt,
                                  track_stats=track_stats)
            xa = None
            if cfg.cross_attention:
                xa = StaticKVCache(
                    k=jnp.zeros((batch, cfg.cond_len, cfg.num_kv_heads, hd), dt),
                    v=jnp.zeros((batch, cfg.cond_len, cfg.num_kv_heads, hd), dt))
            return LayerCaches(kv=kv, xattn=xa)
        if spec.mixer == "mamba":
            return LayerCaches(mamba=mamba_mod.mamba_init_state(cfg, batch, dt))
        if spec.mixer == "mlstm":
            return LayerCaches(mlstm=xlstm_mod.mlstm_init_state(cfg, batch, dt))
        return LayerCaches(slstm=xlstm_mod.slstm_init_state(cfg, batch))

    stack = lambda c: jax.tree.map(lambda a: jnp.broadcast_to(a, (R,) + a.shape), c)
    pattern = [stack(one(pat[p])) for p in range(P)] if R > 0 else []
    tail = [one(pat[i]) for i in range(rem)]
    return ModelCache(pattern=pattern, tail=tail,
                      cur_pos=jnp.zeros((batch,), jnp.int32))


# ---------------------------------------------------------------------------
# unified mixed-batch step (chunked prefill + decode in ONE program)
# ---------------------------------------------------------------------------
# This replaces the old prefill->insert splice (forward a whole padded
# prompt into a private B=1 pool, then copy it into the batch through a
# per-slot-specialized jitted insert): requests now prefill IN PLACE, chunk
# by chunk, through the same block tables decode uses, so a long prompt
# never stalls the decode slots sharing its batch.

def _scan_recurrent(step_fn, state, init_state, h_seq, n_tok, reset_mask):
    """Run a per-token decode step over a (B, T, D) chunk. Rows past their
    ``n_tok`` freeze their state and emit zeros; ``reset_mask`` rows start
    from ``init_state`` (slot handed to a new request — note xLSTM inits
    are NOT all-zero: the max-stabilizer m starts at -inf). Chunked prefill
    of a recurrent mixer is sequential by nature — O(T) small steps; the
    attention layers are the hot path."""
    B, T = h_seq.shape[:2]
    fresh = lambda init, a: jnp.where(
        jnp.reshape(reset_mask, (B,) + (1,) * (a.ndim - 1)),
        init.astype(a.dtype), a)
    state = jax.tree.map(fresh, init_state, state)

    def body(st, xs):
        h_t, t = xs
        out, st2 = step_fn(h_t, st)
        act = t < n_tok
        keep = lambda a, b: jnp.where(
            jnp.reshape(act, (B,) + (1,) * (a.ndim - 1)), a, b)
        return jax.tree.map(keep, st2, st), jnp.where(act[:, None], out, 0.0)

    state, outs = lax.scan(body, state,
                           (jnp.swapaxes(h_seq, 0, 1), jnp.arange(T)))
    return jnp.swapaxes(outs, 0, 1), state


def _step_layer(lp, cfg, spec, x, cache: LayerCaches, positions, n_tok,
                policy: EvictionPolicy, ccfg: CacheConfig, decode_mask,
                prefill_mask, reset_mask, share_src, share_pages,
                use_pallas: bool = False, decode_splits: int = 1,
                fused_scores: bool = False, want_taps: bool = False,
                tp_axis: str | None = None):
    """One layer of the unified step. x: (B, T, D); positions: (B, T) int32
    with -1 past each row's ``n_tok``. Returns (x, LayerCaches, tap).

    ``want_taps`` (static; obs/regret.py shadow probes) makes attention
    layers also return a tap dict — the k/v written this step, the q used,
    the attention output pre-projection, and the cache's live positions AT
    ATTENTION TIME (post-append, pre-eviction). False (the default) returns
    ``tap = None`` and traces HLO identical to the pre-taps code.

    ``tp_axis`` (DESIGN.md §11): mesh axis name when the layer runs inside
    a tensor-parallel shard_map region — heads/KV-heads/d_ff arrive as
    local shards; attention and MLP/MoE outputs are psum'd here so the
    residual stream stays replicated. None (default) is the single-device
    path, traced identically to before."""
    B, T, _ = x.shape
    tap = None
    # the step's parts carry jax.named_scope names in their ops' metadata
    # (attn / pool / evict / mlp), so a profile can charge each device op
    # to one of them; the ops themselves are unchanged
    with jax.named_scope(spec.mixer):
        h = apply_norm(lp["norm1"], x)
    if spec.mixer == "attn":
        with jax.named_scope("attn"):
            q, k, v = attn_mod.project_qkv(lp["attn"], cfg, h,
                                           jnp.maximum(positions, 0))
        kvc: PagedLayerCache = cache.kv
        with jax.named_scope("pool"):
            # telemetry: the stats vector holds per-STEP counts — zero it
            # at layer entry so collect_step_stats sees only this iteration
            if kvc.stats is not None:
                kvc = kvc._replace(stats=devstats.zeros())
            # rows starting a new request free the previous occupant's
            # pages back to the shared pool before their first chunk
            # allocates
            kvc = release_rows(kvc, reset_mask)
            # prefix sharing: an adopting row maps the source row's resident
            # prompt-prefix pages (ref_count bumped, prefill skips those
            # tokens) before its first non-shared chunk appends — DESIGN.md §7
            kvc = adopt_prefix(kvc, share_src, share_pages, enable=reset_mask)
            score = policy.write_score(k, v, positions)         # (B, T)
            kvc = append_chunk(kvc, k, v, positions, score, n_tok)
        window = _spec_window(cfg, spec)
        with jax.named_scope("attn"):
            o, pscores = attn_mod.step_attention(
                q, kvc, q_pos=positions, window=window, use_pallas=use_pallas,
                decode_splits=decode_splits,
                want_scores=fused_scores and use_pallas, tp_axis=tp_axis)
        if want_taps:
            tap = {"k": k, "v": v, "q": q, "o": o,
                   "live_pos": kvc.pos_view()}
        # Alg.3 bookkeeping for decode rows, incremental Alg.2 compression
        # for rows that consumed a prompt chunk — disjoint masks, both
        # skipped via lax.cond when their mask is all-False. When the fused
        # epilogue ran, both hooks rank pages by the scores the attention
        # pass already produced (DESIGN.md §8).
        with jax.named_scope("evict"):
            kvc = policy.post_write(kvc, ccfg, active=decode_mask,
                                    page_scores=pscores).cache
            kvc = policy.chunk_prefill_evict(kvc, ccfg, active=prefill_mask,
                                             window=window,
                                             page_scores=pscores)
        with jax.named_scope("attn"):
            o2 = o.reshape(B, T, -1) @ lp["attn"]["wo"]
            if tp_axis is not None:
                o2 = jax.lax.psum(o2, tp_axis)
            x = x + o2
            if cache.xattn is not None:
                hx = apply_norm(lp["norm_x"], x)
                x = x + attn_mod.cross_attention_forward(lp["xattn"], cfg,
                                                         hx, cache.xattn)
        cache = cache._replace(kv=kvc)
    elif spec.mixer == "mamba":
        m, st = _scan_recurrent(
            lambda h_t, st: mamba_mod.mamba_decode_step(lp["mamba"], cfg,
                                                        h_t, st),
            cache.mamba,
            mamba_mod.mamba_init_state(cfg, B, cache.mamba.conv.dtype),
            h, n_tok, reset_mask)
        x = x + m
        cache = cache._replace(mamba=st)
    elif spec.mixer == "mlstm":
        m, st = _scan_recurrent(
            lambda h_t, st: xlstm_mod.mlstm_decode_step(lp["mlstm"], cfg,
                                                        h_t, st),
            cache.mlstm,
            xlstm_mod.mlstm_init_state(cfg, B, cache.mlstm.conv.dtype),
            h, n_tok, reset_mask)
        x = x + m
        cache = cache._replace(mlstm=st)
    elif spec.mixer == "slstm":
        m, st = _scan_recurrent(
            lambda h_t, st: xlstm_mod.slstm_decode_step(lp["slstm"], cfg,
                                                        h_t, st),
            cache.slstm, xlstm_mod.slstm_init_state(cfg, B),
            h, n_tok, reset_mask)
        x = x + m
        cache = cache._replace(slstm=st)
    with jax.named_scope("mlp"):
        if spec.mlp == "dense":
            h2 = apply_norm(lp["norm2"], x)
            x = x + mlp_forward(lp["mlp"], cfg, h2, tp_axis=tp_axis)
        elif spec.mlp == "moe":
            # per-token dense-combine MoE: padding tokens cannot steal
            # expert capacity from live ones, so results are
            # chunking-invariant
            h2 = apply_norm(lp["norm2"], x)
            mo = moe_forward_decode(lp["moe"], cfg, h2.reshape(B * T, -1),
                                    tp_axis=tp_axis)
            x = x + mo.reshape(B, T, -1)
    return x, cache, tap


def forward_step(params, cfg: ModelConfig, tokens, n_tok, cache: ModelCache,
                 policy: EvictionPolicy, ccfg: CacheConfig, decode_mask=None,
                 prefill_mask=None, reset_mask=None, share_src=None,
                 share_pages=None, ac: Callable = Identity,
                 use_pallas: bool = False, decode_splits: int = 1,
                 fused_scores: bool = False, want_taps: bool = False,
                 tp_axis: str | None = None):
    """Unified mixed-batch step: up to T tokens per request in ONE program.

    tokens      : (B, T) int32 — row b's live tokens are tokens[b, :n_tok[b]]
                  (decode rows carry 1, prefilling rows a prompt chunk,
                  idle rows 0), appended at positions cur_pos[b] + t
    n_tok       : (B,) int32
    decode_mask : (B,) bool — rows decoding (Alg.3 post_write runs)
    prefill_mask: (B,) bool — rows that consumed a prompt chunk
                  (chunk-boundary compression runs; defaults to
                  ``n_tok > 0 & ~decode_mask``)
    reset_mask  : (B,) bool — rows starting a NEW request this step (the
                  previous occupant's pages are freed, recurrent state and
                  cur_pos reset)
    share_src   : (B,) int32 — prefix sharing: source batch row whose first
                  ``share_pages[b]`` prompt pages a resetting row adopts
                  (ref-count bump, no copy; -1 == no sharing). Only
                  meaningful on reset rows; the engine probes the source's
                  intactness (``intact_prefix_pages``) before setting this.
    share_pages : (B,) int32 — FULL prompt-prefix pages to adopt; the row's
                  cur_pos starts at ``share_pages * page_size`` and prefill
                  covers only the remaining tokens
    decode_splits: split-K factor for the Pallas decode kernel's page walk
                  (long contexts; DESIGN.md §8). Static; 1 == no split.
    fused_scores: rank PagedEviction's page eviction by the attention
                  kernels' fused score epilogue instead of the stored-score
                  reduction. Pallas-only (the flag is ignored on the jnp
                  path); numerically identical for f32 pools, so defaults
                  off only to keep pallas-vs-ref comparisons exact on int8
                  (stored scores predate quantization).

    want_taps   : static (obs/regret.py): additionally return per-attention-
                  layer taps {"k","v","q","o","live_pos"} — pattern-slot
                  taps stacked over reps — plus the step's ``positions``.
                  False leaves returns AND traced HLO unchanged.
    tp_axis     : static (DESIGN.md §11): mesh axis name when this step is
                  traced inside a tensor-parallel shard_map region. The
                  caller must pass weight/pool shards consistent with
                  ``sharding.rules.tp_*_specs`` and a policy built with
                  ``get_policy(name, tp_axis=...)``; layer outputs psum
                  over the axis so the residual stream (and hence logits
                  and sampling) is replicated on every shard.

    Returns (logits (B, vocab) at each row's last live token, cache), plus
    the taps dict when ``want_taps``. Rows with n_tok == 0 return logits of
    stale garbage — callers mask.
    """
    with jax.named_scope("embed"):
        x = embed_tokens(params, cfg, tokens)               # (B, T, D)
    B, T = x.shape[0], x.shape[1]
    if decode_mask is None:
        decode_mask = jnp.zeros((B,), bool)
    if prefill_mask is None:
        prefill_mask = (n_tok > 0) & ~decode_mask
    if reset_mask is None:
        reset_mask = jnp.zeros((B,), bool)
    if share_src is None:
        share_src = jnp.full((B,), -1, jnp.int32)
    if share_pages is None:
        share_pages = jnp.zeros((B,), jnp.int32)
    cur_pos = jnp.where(reset_mask, share_pages * ccfg.page_size,
                        cache.cur_pos)
    positions = cur_pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    positions = jnp.where(jnp.arange(T)[None, :] < n_tok[:, None],
                          positions, -1)
    pat = cfg.layer_pattern()
    P = cfg.pattern_period

    def rep_body(x, xs):
        slot_params, slot_caches = xs
        new_caches, slot_taps = [], []
        for p in range(P):
            x, c, tp = _step_layer(slot_params[p], cfg, pat[p], ac(x),
                                   slot_caches[p], positions, n_tok, policy,
                                   ccfg, decode_mask, prefill_mask,
                                   reset_mask, share_src, share_pages,
                                   use_pallas, decode_splits, fused_scores,
                                   want_taps, tp_axis)
            new_caches.append(c)
            slot_taps.append(tp)
        if want_taps:
            return x, (tuple(new_caches), tuple(slot_taps))
        return x, tuple(new_caches)

    pattern_taps: list = []
    if params["pattern"]:
        x, ys = lax.scan(
            rep_body, x, (tuple(params["pattern"]), tuple(cache.pattern)))
        if want_taps:
            pattern_caches, pattern_taps = list(ys[0]), list(ys[1])
        else:
            pattern_caches = list(ys)
    else:
        pattern_caches = []
    tail_caches, tail_taps = [], []
    for i, lp in enumerate(params["tail"]):
        x, c, tp = _step_layer(lp, cfg, pat[i], ac(x), cache.tail[i],
                               positions, n_tok, policy, ccfg, decode_mask,
                               prefill_mask, reset_mask, share_src,
                               share_pages, use_pallas, decode_splits,
                               fused_scores, want_taps, tp_axis)
        tail_caches.append(c)
        tail_taps.append(tp)
    with jax.named_scope("logits"):
        last = jnp.maximum(n_tok - 1, 0)
        x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        logits = lm_logits(params, cfg, x_last)
    out_cache = ModelCache(pattern=pattern_caches, tail=tail_caches,
                           cur_pos=cur_pos + n_tok)
    if want_taps:
        taps = {"pattern": pattern_taps, "tail": tail_taps,
                "positions": positions}
        return logits, out_cache, taps
    return logits, out_cache


def collect_step_stats(cache: ModelCache):
    """Sum every attention layer's devstats vector -> (devstats.NSTATS,)
    int32, or None when the caches don't track stats. Pure jnp — the engine
    calls this INSIDE its jitted step so the whole telemetry path costs one
    tiny reduction plus one (NSTATS,) transfer per step (DESIGN.md §9).
    Call AFTER the step (each layer zeroes its vector at entry, so the sum
    is exactly this iteration's events across the stack)."""
    vecs = []
    for lc in cache.pattern:
        if lc.kv is None or lc.kv.stats is None:
            continue
        vecs.append(jnp.sum(lc.kv.stats, axis=0))   # stats stacked (R, NSTATS)
    for lc in cache.tail:
        if lc.kv is None or lc.kv.stats is None:
            continue
        vecs.append(lc.kv.stats)
    if not vecs:
        return None
    out = vecs[0]
    for v in vecs[1:]:
        out = out + v
    return out


def intact_prefix_pages(cache: ModelCache, row) -> jax.Array:
    """() int32 — how many leading FULL prompt pages of batch row ``row``
    are intact in EVERY attention layer's cache (min over layers; stacked
    pattern slots vmapped over their repetitions). This is the device half
    of the prefix-sharing admission probe: the scheduler's radix index says
    which resident row textually shares a prompt prefix; this says how much
    of that prefix actually survives eviction. 0 when the model has no
    attention layers (recurrent state cannot be adopted page-wise)."""
    runs = []
    for lc in cache.pattern:
        if lc.kv is None:
            continue
        per_rep = jax.vmap(lambda c: row_intact_prefix_pages(c, row))(lc.kv)
        runs.append(jnp.min(per_rep))
    for lc in cache.tail:
        if lc.kv is None:
            continue
        runs.append(row_intact_prefix_pages(lc.kv, row))
    if not runs:
        return jnp.zeros((), jnp.int32)
    out = runs[0]
    for r in runs[1:]:
        out = jnp.minimum(out, r)
    return out


# ---------------------------------------------------------------------------
# prefill forward (build caches)
# ---------------------------------------------------------------------------

def _prefill_layer(lp, cfg, spec, x, positions, valid, cond, policy, ccfg,
                   seq_len_hint, ac: Callable = Identity,
                   use_pallas: bool = False) -> tuple:
    """Layer forward that also produces its decode cache."""
    x, aux, extras = layer_forward(
        lp, cfg, spec, x, positions, cond=cond, ac=ac,
        return_kv=(spec.mixer == "attn"), return_state=(spec.mixer != "attn"),
        use_pallas=use_pallas)
    if spec.mixer == "attn":
        k, v = extras
        window = _spec_window(cfg, spec)
        hint = seq_len_hint if not window else min(
            seq_len_hint, window + ccfg.page_size)
        kv_valid = valid
        if window:
            # windowed layers never attend past the window again: drop
            # out-of-window tokens at paging time (keeps slab small)
            cur = jnp.max(jnp.where(valid, positions, -1), axis=-1, keepdims=True)
            kv_valid = valid & (positions > cur - window)
        cache = compress_and_page(k, v, positions, kv_valid, policy, ccfg,
                                  seq_len_hint=hint,
                                  cache_dtype=dtype_of(ccfg.dtype))
        xa = None
        if cond is not None and "xattn" in lp:
            xa = attn_mod.make_cross_cache(lp["xattn"], cfg, cond)
        return x, aux, LayerCaches(kv=cache, xattn=xa)
    if spec.mixer == "mamba":
        return x, aux, LayerCaches(mamba=extras)
    if spec.mixer == "mlstm":
        return x, aux, LayerCaches(mlstm=extras)
    return x, aux, LayerCaches(slstm=extras)


def forward_prefill(params, cfg: ModelConfig, tokens, policy: EvictionPolicy,
                    ccfg: CacheConfig, cond=None, valid=None,
                    ac: Callable = Identity, total_seq_hint: int | None = None,
                    use_pallas: bool = False):
    """Process the prompt, compress each attn layer's KV per Alg.2, return
    (last-token logits, ModelCache).

    ``total_seq_hint``: expected prompt+generation length — sizes the page
    slabs so decode can continue in-place (defaults to the prompt length)."""
    x = embed_tokens(params, cfg, tokens)
    B, S = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    if valid is None:
        valid = jnp.ones((B, S), bool)
    positions = jnp.where(valid, positions, -1)
    pat = cfg.layer_pattern()
    P = cfg.pattern_period
    hint = total_seq_hint or S

    def rep_body(carry, slot_params):
        x, aux = carry
        caches = []
        for p in range(P):
            x, a, c = _prefill_layer(slot_params[p], cfg, pat[p], x, positions,
                                     valid, cond, policy, ccfg, hint, ac=ac,
                                     use_pallas=use_pallas)
            aux = aux + a
            caches.append(c)
        return (x, aux), tuple(caches)

    carry = (x, jnp.zeros((), jnp.float32))
    if params["pattern"]:
        carry, pattern_caches = lax.scan(rep_body, carry, tuple(params["pattern"]))
        pattern_caches = list(pattern_caches)
    else:
        pattern_caches = []
    x, aux = carry
    tail_caches = []
    for i, lp in enumerate(params["tail"]):
        x, a, c = _prefill_layer(lp, cfg, pat[i], x, positions, valid, cond,
                                 policy, ccfg, hint, ac=ac,
                                 use_pallas=use_pallas)
        aux = aux + a
        tail_caches.append(c)

    # last valid token's hidden state -> next-token logits
    last_idx = jnp.maximum(jnp.sum(valid.astype(jnp.int32), axis=-1) - 1, 0)
    x_last = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]
    logits = lm_logits(params, cfg, x_last)
    next_pos = jnp.sum(valid.astype(jnp.int32), axis=-1)
    cache = ModelCache(pattern=pattern_caches, tail=tail_caches,
                       cur_pos=next_pos)
    return logits, cache


# ---------------------------------------------------------------------------
# decode step
# ---------------------------------------------------------------------------

def _decode_layer(lp, cfg, spec, x, cache: LayerCaches, cur_pos,
                  policy: EvictionPolicy, ccfg: CacheConfig, active,
                  use_pallas: bool = False, decode_splits: int = 1,
                  fused_scores: bool = False):
    """One layer, one token. x: (B, D). Returns (x, LayerCaches)."""
    h = apply_norm(lp["norm1"], x)
    if spec.mixer == "attn":
        q, k, v = attn_mod.decode_project_qkv(lp["attn"], cfg, h, cur_pos)
        kvc: PagedLayerCache = cache.kv
        if kvc.stats is not None:
            kvc = kvc._replace(stats=devstats.zeros())
        score = policy.write_score(k, v, cur_pos)
        # lazy rollover: chunked prefill parks the head at cur_off ==
        # page_size when a chunk ends exactly on a page boundary — the
        # first decode write then allocates the working page (post_write
        # keeps rolling eagerly afterwards, so this is a no-op mid-stream)
        kvc = chunk_rollover(kvc, active & (kvc.cur_off >= kvc.page_size))
        kvc = write_token(kvc, k, v, cur_pos, score, active=active)
        window = _spec_window(cfg, spec)
        o, pscores = attn_mod.decode_attention(
            q, kvc, cur_pos=cur_pos, window=window, use_pallas=use_pallas,
            num_splits=decode_splits,
            want_scores=fused_scores and use_pallas)
        outcome = policy.post_write(kvc, ccfg, active=active,
                                    page_scores=pscores)
        kvc = outcome.cache
        B = x.shape[0]
        o = o.reshape(B, -1) @ lp["attn"]["wo"]
        x = x + o
        if cache.xattn is not None:
            hx = apply_norm(lp["norm_x"], x[:, None, :])
            o2 = attn_mod.cross_attention_forward(lp["xattn"], cfg, hx,
                                                  cache.xattn)
            x = x + o2[:, 0]
        cache = cache._replace(kv=kvc)
    elif spec.mixer == "mamba":
        m, st = mamba_mod.mamba_decode_step(lp["mamba"], cfg, h, cache.mamba)
        x = x + m
        cache = cache._replace(mamba=st)
    elif spec.mixer == "mlstm":
        m, st = xlstm_mod.mlstm_decode_step(lp["mlstm"], cfg, h, cache.mlstm)
        x = x + m
        cache = cache._replace(mlstm=st)
    elif spec.mixer == "slstm":
        m, st = xlstm_mod.slstm_decode_step(lp["slstm"], cfg, h, cache.slstm)
        x = x + m
        cache = cache._replace(slstm=st)
    if spec.mlp == "dense":
        h2 = apply_norm(lp["norm2"], x)
        x = x + mlp_forward(lp["mlp"], cfg, h2)
    elif spec.mlp == "moe":
        h2 = apply_norm(lp["norm2"], x)
        x = x + moe_forward_decode(lp["moe"], cfg, h2)
    return x, cache


def decode_step(params, cfg: ModelConfig, tokens, cache: ModelCache,
                policy: EvictionPolicy, ccfg: CacheConfig, active=None,
                use_pallas: bool = False, ac: Callable = Identity,
                decode_splits: int = 1, fused_scores: bool = False):
    """One decode step. tokens: (B,) [or (B, K) audio] -> (logits, cache).
    ``decode_splits`` / ``fused_scores``: see :func:`forward_step`."""
    if cfg.num_codebooks > 1:
        # tokens: (B, K); embed: (K, V, D)
        per_cb = jax.vmap(lambda emb, tok: jnp.take(emb, tok, axis=0),
                          in_axes=(0, 1))(params["embed"], tokens)  # (K, B, D)
        x = jnp.sum(per_cb, axis=0)
    else:
        x = jnp.take(params["embed"], tokens, axis=0)        # (B, D)
    B = x.shape[0]
    if active is None:
        active = jnp.ones((B,), bool)
    cur_pos = cache.cur_pos
    pat = cfg.layer_pattern()
    P = cfg.pattern_period

    def rep_body(x, xs):
        slot_params, slot_caches = xs
        new_caches = []
        for p in range(P):
            x, c = _decode_layer(slot_params[p], cfg, pat[p], ac(x),
                                 slot_caches[p], cur_pos, policy, ccfg,
                                 active, use_pallas, decode_splits,
                                 fused_scores)
            new_caches.append(c)
        return x, tuple(new_caches)

    if params["pattern"]:
        x, pattern_caches = lax.scan(
            rep_body, x, (tuple(params["pattern"]), tuple(cache.pattern)))
        pattern_caches = list(pattern_caches)
    else:
        pattern_caches = []
    tail_caches = []
    for i, lp in enumerate(params["tail"]):
        x, c = _decode_layer(lp, cfg, pat[i], ac(x), cache.tail[i], cur_pos,
                             policy, ccfg, active, use_pallas, decode_splits,
                             fused_scores)
        tail_caches.append(c)
    logits = lm_logits(params, cfg, x)
    new_pos = jnp.where(active, cur_pos + 1, cur_pos)
    return logits, ModelCache(pattern=pattern_caches, tail=tail_caches,
                              cur_pos=new_pos)
