"""Eviction policies (the paper's technique + all its baselines).

Every policy is a stateless, hashable strategy object with three hooks:

  write_score(k_tok, v_tok, pos)        score stored with each written token
  prefill_keep(k, v, positions, valid)  paper Alg.2, one-shot form — token-
                                        level prompt compression to the
                                        budget *before* paging (offline /
                                        whole-prompt flows)
  chunk_prefill_evict(cache, cfg, ...)  paper Alg.2, incremental form — at
                                        each chunked-prefill boundary,
                                        compress the pooled cache back to
                                        budget (PagedEviction: evict whole
                                        COMPLETED pages; token policies:
                                        keep the top-C tokens). Evicting the
                                        minimum-score completed page whenever
                                        the count exceeds budget_pages is a
                                        running top-K, so the surviving page
                                        set is chunk-size invariant.
  post_write(cache, cfg, active)        paper Alg.3 — decode-time bookkeeping
                                        after each appended token: page
                                        rollover, eviction, block-table update

Both eviction hooks accept an optional ``page_scores`` (B, P) array — the
attention kernels' fused score epilogue (DESIGN.md §8). When provided and
usable, PagedEviction ranks pages by it instead of touching
``cache.page_scores()``, so eviction metadata costs nothing beyond the
attention pass the step already ran. Policies that don't rank by page
score ignore it; windowed chunk eviction falls back to the stored path
(out-of-window drops invalidate scores computed at attention time).

Telemetry (DESIGN.md §9): policies need no instrumentation of their own —
every pool mutation they invoke (``evict_page``, ``evict_token[_mask]``,
``rollover_to_free_page`` force-evicts, CoW forks) bumps the cache's
device stats vector inside ``paged_cache.py``, so per-policy eviction
counts fall out of the ``pool.*`` counters for free.

Policies:
  paged_eviction   the paper: structured block-wise eviction at page-full
                   boundaries using S = ||V||/||K|| page means
  full             no eviction (slab sized to the sequence)
  streaming_llm    sinks + sliding window; one token evicted per step
  inverse_key_l2   unstructured: evict highest ||K|| token per step
  keydiff          unstructured: evict least-diverse key per step (global
                   cosine-vs-mean recomputed each step — deliberately costly,
                   reproducing the paper's overhead comparison)

All hooks are shape-static and jit/vmap/scan-safe.

Shared pages (prefix sharing, DESIGN.md §7): no policy needs to know about
``ref_count > 1`` — the primitives they compose enforce the semantics.
Page-level eviction (``evict_pages_mask``, the paper's Alg.2/Alg.3 path)
of a shared page is an unmap: this request's budget drops by a page but the
data stays live for the other mappers, and the physical page is only
recycled when the last mapper lets go. Token-level eviction
(``evict_token`` / ``evict_token_mask``, the unstructured baselines)
copy-on-write-forks a shared page before mutating — at most one fork per
row per call, so a baseline that targets many shared pages converges over
a few steps, transiently exceeding budget rather than ever corrupting a
sharer's view.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import CacheConfig
from repro.core import importance
from repro.core.paged_cache import (
    PagedLayerCache,
    alloc_pages,
    evict_page,
    evict_pages_mask,
    evict_token,
    evict_token_mask,
    find_free_slot,
    reclaim_empty_pages,
    rollover_to_free_page,
    start_new_page,
)


class EvictionOutcome(NamedTuple):
    cache: PagedLayerCache
    pages_evicted: jax.Array    # (B,) bool — a full page was evicted
    tokens_evicted: jax.Array   # (B,) bool — a single token was evicted
    forced_evictions: jax.Array  # (B,) bool — fragmentation forced a page out
    # forensics (obs/lineage.py): which logical page lost the argmin and at
    # what policy score. Only meaningful where pages_evicted is True; None
    # for policies that never evict whole pages (token-granular baselines).
    victim_page: jax.Array | None = None    # (B,) int32 logical page index
    victim_score: jax.Array | None = None   # (B,) f32 score at eviction


def _no_evict(cache):
    B = cache.batch
    false = jnp.zeros((B,), bool)
    return false, false


def _rollover_to_free_page(cache: PagedLayerCache, need):
    """Where ``need``, allocate a fresh physical page from the SHARED pool,
    map it into the first unmapped logical slot, and move the write head
    there. Fully-emptied mapped pages (token-level eviction holes) are
    reclaimed to the free list first, so one request's evictions become
    every other request's headroom. If a request has no unmapped slot or the
    pool has no free page (unstructured fragmentation / overcommit),
    force-evict its fullest-but-not-current page with the fewest valid
    tokens, which releases both a slot and a physical page.

    The whole body runs under ``lax.cond`` on ``any(need)``: pages fill once
    per page_size steps, so the reclaim/alloc bookkeeping is skipped on the
    other page_size - 1 steps (the overhead benchmarks measure this). The
    branches are module-level functions so eager callers hit the cond's
    compile cache across steps."""
    return jax.lax.cond(jnp.any(need), _rollover_body, _rollover_noop,
                        (cache, need))


def _page_age(cache: PagedLayerCache) -> jax.Array:
    """(B, P) int32 — the position in each logical page's first slot; for a
    full page, the only kind page eviction ranks, its oldest, as pages fill
    in position order. The tie-break of page eviction: equal mean scores
    go to the OLDER page, as an eviction order over positions has it; slot
    order is no order at all, since freed slots are refilled as pages
    come."""
    return cache.pos[cache._phys(), 0]


def _rollover_noop(args):
    cache, need = args
    return cache, jnp.zeros((cache.batch,), bool)


def _out_of_window(cache: PagedLayerCache, window: int, active):
    """(B, P, page) bool — live tokens a windowed layer can never attend
    again (pos <= newest - window). Dropping them at a chunk boundary is
    exactly equivalence-preserving: any later query's window mask excludes
    them too, so no attention result changes."""
    pos = cache.pos_view()
    valid = pos >= 0
    cur = jnp.max(jnp.where(valid, pos, -1), axis=(1, 2), keepdims=True)
    return valid & (pos <= cur - window) & active[:, None, None]


def _rollover_body(args):
    cache, need = args
    return rollover_to_free_page(cache, need)


class EvictionPolicy:
    name: str = "base"
    structured: bool = True

    def __init__(self, tp_axis: str | None = None):
        # Tensor parallelism (DESIGN.md §11): when the KV-head axis is
        # sharded over a shard_map mesh axis, score reductions over KV
        # heads must pmean across it so every shard ranks tokens/pages by
        # the GLOBAL score and eviction picks identical victims. None (the
        # registry singletons) keeps all reductions local — byte-identical
        # to the pre-TP behaviour.
        self.tp_axis = tp_axis

    # --- slab sizing --------------------------------------------------------
    def _round_slab(self, cfg: CacheConfig, pages: int) -> int:
        m = max(cfg.slab_multiple, 1)
        return -(-pages // m) * m

    def slab_pages(self, cfg: CacheConfig, seq_len: int) -> int:
        total = -(-seq_len // cfg.page_size)
        return self._round_slab(cfg, min(total, cfg.budget_pages + 1))

    # --- scores -------------------------------------------------------------
    def write_score(self, k_tok, v_tok, pos_tok):
        """k_tok, v_tok: (B, KV, hd) -> (B,) f32."""
        raise NotImplementedError

    def prefill_scores(self, k, v, positions):
        """k, v: (B, S, KV, hd); positions (B, S) -> (B, S) f32."""
        raise NotImplementedError

    # --- Alg.2: prefill compression ------------------------------------------
    def prefill_keep(self, k, v, positions, valid, cfg: CacheConfig):
        """Select ``keep = min(budget, S_pad)`` tokens. Returns
        (indices (B, keep) in ascending position order, scores (B, S))."""
        B, S = positions.shape
        keep = min(cfg.cache_budget, S)
        scores = self.prefill_scores(k, v, positions)
        scores = jnp.where(valid, scores, -jnp.inf)
        _, idx = jax.lax.top_k(scores, keep)               # (B, keep)
        idx = jnp.sort(idx, axis=-1)                       # restore order
        return idx, scores

    # --- Alg.2, incremental: chunk-boundary compression ----------------------
    def _evict_scores(self, cache: PagedLayerCache, cfg: CacheConfig):
        """(B, P, page) dynamic importance used by chunk/token eviction;
        defaults to the stored write scores."""
        return cache.score_view()

    def chunk_prefill_evict(self, cache: PagedLayerCache, cfg: CacheConfig,
                            active=None, window: int = 0,
                            page_scores=None) -> PagedLayerCache:
        """Compress the pooled cache back to the budget at a chunked-prefill
        boundary (incremental Alg.2). ``active``: (B,) bool — rows that
        consumed a prompt chunk this step; ``window``: the layer's attention
        window (out-of-window tokens are dropped first — they can never be
        attended again); ``page_scores``: optional (B, P) fused-epilogue
        scores (see module docstring). The whole body runs under
        ``lax.cond`` so pure-decode steps skip it."""
        if active is None:
            active = jnp.ones((cache.batch,), bool)
        return jax.lax.cond(
            jnp.any(active),
            lambda c: self._chunk_evict_body(c, cfg, active, window,
                                             page_scores),
            lambda c: c, cache)

    def _chunk_evict_body(self, cache, cfg: CacheConfig, active, window: int,
                          page_scores=None):
        """Token-level default: keep the top-C live tokens by eviction score
        (rank via stable argsort — ties keep the older token), then return
        fully-emptied pages to the shared free list. Token policies rank
        per-token, so the fused page_scores don't apply."""
        del page_scores
        B, P, page = cache.batch, cache.num_pages, cache.page_size
        if window:
            cache = evict_token_mask(cache, _out_of_window(cache, window,
                                                           active))
        valid = cache.valid_mask()
        scores = jnp.where(valid, self._evict_scores(cache, cfg), -jnp.inf)
        order = jnp.argsort(-scores.reshape(B, -1), axis=-1)
        ranks = jnp.argsort(order, axis=-1)                 # 0 == best
        evict = valid.reshape(B, -1) & (ranks >= cfg.cache_budget) & \
            active[:, None]
        cache = evict_token_mask(cache, evict.reshape(B, P, page))
        return reclaim_empty_pages(cache)

    # --- Alg.3: decode bookkeeping -------------------------------------------
    def post_write(self, cache: PagedLayerCache, cfg: CacheConfig,
                   active=None, page_scores=None) -> EvictionOutcome:
        raise NotImplementedError

    # ------------------------------------------------------------------ misc
    def __hash__(self):
        return hash((self.name, self.tp_axis))

    def __eq__(self, other):
        return (type(self) is type(other)
                and self.tp_axis == getattr(other, "tp_axis", None))

    def __repr__(self):
        if self.tp_axis is not None:
            return f"{type(self).__name__}(tp_axis={self.tp_axis!r})"
        return f"{type(self).__name__}()"


# ---------------------------------------------------------------------------
# Full cache (no eviction)
# ---------------------------------------------------------------------------

class FullCache(EvictionPolicy):
    name = "full"
    structured = True

    def slab_pages(self, cfg, seq_len):
        return self._round_slab(cfg, -(-seq_len // cfg.page_size))

    def write_score(self, k_tok, v_tok, pos_tok):
        return jnp.zeros(k_tok.shape[:-2], jnp.float32)

    def prefill_scores(self, k, v, positions):
        # recency: irrelevant when nothing is dropped; for windowed layers
        # the slab-capacity cap (compress_and_page) then keeps the newest
        return importance.recency_score(positions)

    def prefill_keep(self, k, v, positions, valid, cfg):
        B, S = positions.shape
        idx = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        return idx, jnp.where(valid, self.prefill_scores(k, v, positions),
                              -jnp.inf)

    def _chunk_evict_body(self, cache, cfg, active, window: int,
                          page_scores=None):
        # no budget: only windowed layers shed (never-again-attendable) tokens
        del page_scores
        if window:
            cache = evict_token_mask(cache, _out_of_window(cache, window,
                                                           active))
            cache = reclaim_empty_pages(cache)
        return cache

    def post_write(self, cache, cfg, active=None, page_scores=None):
        del page_scores
        if active is None:
            active = jnp.ones((cache.batch,), bool)
        need = active & (cache.cur_off >= cache.page_size)
        cache = jax.lax.cond(jnp.any(need), _full_grow_body, _full_grow_noop,
                             (cache, need))
        t, f = _no_evict(cache)
        return EvictionOutcome(cache, t, t, f)


def _full_grow_noop(args):
    return args[0]


def _full_grow_body(args):
    cache, need = args
    slot, slot_ok = find_free_slot(cache)
    cache, phys, ok = alloc_pages(cache, need & slot_ok)
    grow = need & slot_ok & ok
    cache = start_new_page(cache, slot, phys, enable=grow)
    # saturated (block table exhausted — callers size slabs so this only
    # happens after the final token): never evict; park the head on the
    # full current page with off reset, mirroring the old clamp
    return cache._replace(cur_off=jnp.where(need & ~grow, 0, cache.cur_off))


# ---------------------------------------------------------------------------
# PagedEviction (the paper)
# ---------------------------------------------------------------------------

class PagedEviction(EvictionPolicy):
    """Structured block-wise eviction (paper Alg. 1-3)."""
    name = "paged_eviction"
    structured = True

    def write_score(self, k_tok, v_tok, pos_tok):
        return importance.vk_ratio_score(k_tok, v_tok, axis_name=self.tp_axis)

    def prefill_scores(self, k, v, positions):
        return importance.vk_ratio_score(k, v, axis_name=self.tp_axis)

    def _chunk_evict_body(self, cache, cfg, active, window: int,
                          page_scores=None):
        """Structured chunk-boundary compression: evict the lowest-mean-score
        COMPLETED pages until at most ``budget_pages`` remain (the partial
        working page rides free, mirroring Alg.3's budget+page slack).
        Because candidacy is by completion and the minimum is always evicted
        first, the surviving page set equals the overall top-K — chunk-size
        invariant whenever attention inputs are (see DESIGN.md §6).

        ``page_scores``: fused-epilogue scores from the attention pass this
        step already ran (DESIGN.md §8) — used instead of the stored-score
        reduction when the layer is unwindowed. Windowed layers drop
        out-of-window tokens first, which changes page means, so they fall
        back to scoring the post-drop cache."""
        if window:
            page_scores = None      # stale after the out-of-window drop
            cache = evict_token_mask(cache, _out_of_window(cache, window,
                                                           active))
        full = cache.tokens_per_page() >= cache.page_size   # (B, P) completed
        if cfg.protect_recent:
            B, P = full.shape
            full &= ~jax.nn.one_hot(cache.cur_page, P, dtype=bool)
        m = jnp.maximum(jnp.sum(full, axis=-1) - cfg.budget_pages, 0)  # (B,)
        pscores = cache.page_scores() if page_scores is None else page_scores
        cand = jnp.where(full, pscores, jnp.inf)
        order = jnp.lexsort((_page_age(cache), cand), axis=-1)
        ranks = jnp.argsort(order, axis=-1)                 # 0 == worst
        evict = full & (ranks < m[:, None]) & active[:, None]
        cache = evict_pages_mask(cache, evict)
        return reclaim_empty_pages(cache)

    def post_write(self, cache, cfg, active=None, page_scores=None):
        if active is None:
            active = jnp.ones((cache.batch,), bool)
        page_full = active & (cache.cur_off >= cache.page_size)
        over = cache.total_valid() > cfg.cache_budget
        do_evict = page_full & over
        # page score = mean ||V||/||K|| over the page (Alg.1 block mode);
        # only *full* pages compete (the working page is the one just filled,
        # already full; under-filled pages only exist transiently). The
        # fused-epilogue scores, when passed, are this exact reduction
        # computed for free inside the attention kernel (DESIGN.md §8).
        pscores = cache.page_scores() if page_scores is None else page_scores
        full_pages = cache.tokens_per_page() >= cache.page_size
        if cfg.protect_recent:
            B, P = pscores.shape
            cur = jax.nn.one_hot(cache.cur_page, P, dtype=bool)
            full_pages &= ~cur
        cand = jnp.where(full_pages, pscores, jnp.inf)
        lowest = cand == jnp.min(cand, axis=-1, keepdims=True)
        victim = jnp.argmin(jnp.where(lowest, _page_age(cache),
                                      jnp.iinfo(jnp.int32).max),
                            axis=-1).astype(jnp.int32)
        vscore = jnp.take_along_axis(pscores, victim[:, None],
                                     axis=-1)[:, 0].astype(jnp.float32)
        cache = evict_page(cache, victim, enable=do_evict)
        cache, forced = _rollover_to_free_page(cache, page_full)
        return EvictionOutcome(cache, do_evict,
                               jnp.zeros((cache.batch,), bool), forced,
                               victim_page=victim, victim_score=vscore)


# ---------------------------------------------------------------------------
# StreamingLLM (sinks + sliding window; token-per-step)
# ---------------------------------------------------------------------------

class StreamingLLM(EvictionPolicy):
    name = "streaming_llm"
    structured = True  # paper classifies it as structured (within-block order)

    def slab_pages(self, cfg, seq_len):
        total = -(-seq_len // cfg.page_size)
        # sinks pin their page forever -> one extra slot of headroom
        return self._round_slab(cfg, min(total, cfg.budget_pages + 2))

    def write_score(self, k_tok, v_tok, pos_tok):
        return importance.recency_score(pos_tok)

    def prefill_scores(self, k, v, positions):
        return importance.recency_score(positions)

    def prefill_keep(self, k, v, positions, valid, cfg):
        B, S = positions.shape
        keep = min(cfg.cache_budget, S)
        # sinks get +inf so they always survive; others ranked by recency
        scores = importance.recency_score(positions)
        scores = jnp.where(positions < cfg.num_sink_tokens, jnp.inf, scores)
        scores = jnp.where(valid, scores, -jnp.inf)
        _, idx = jax.lax.top_k(scores, keep)
        return jnp.sort(idx, axis=-1), scores

    def _evict_scores(self, cache, cfg):
        # sinks pinned with +inf so budget compression never drops them;
        # everything else ranked by the stored recency score
        return jnp.where(cache.pos_view() < cfg.num_sink_tokens,
                         jnp.inf, cache.score_view())

    def post_write(self, cache, cfg, active=None, page_scores=None):
        del page_scores                                     # ranks by recency
        if active is None:
            active = jnp.ones((cache.batch,), bool)
        over = active & (cache.total_valid() > cfg.cache_budget)
        valid = cache.valid_mask()
        B, P, page = valid.shape
        # oldest non-sink token
        pos = cache.pos_view()
        cand = jnp.where(valid & (pos >= cfg.num_sink_tokens),
                         pos, jnp.iinfo(jnp.int32).max)
        flat = cand.reshape(B, P * page)
        victim = jnp.argmin(flat, axis=-1).astype(jnp.int32)
        cache = evict_token(cache, victim, enable=over)
        need = active & (cache.cur_off >= cache.page_size)
        cache, forced = _rollover_to_free_page(cache, need)
        return EvictionOutcome(cache, jnp.zeros((B,), bool), over, forced)


# ---------------------------------------------------------------------------
# Unstructured baselines (token-per-step across pages)
# ---------------------------------------------------------------------------

class _UnstructuredTokenPolicy(EvictionPolicy):
    structured = False

    def slab_pages(self, cfg, seq_len):
        total = -(-seq_len // cfg.page_size)
        # token-level holes fragment pages (paper Limitation 1/Fig. 6): a page
        # frees only when *all* its tokens have been individually evicted, so
        # the working set needs headroom beyond budget/page_size.
        return self._round_slab(cfg, min(total, 2 * cfg.budget_pages + 2))

    def post_write(self, cache, cfg, active=None, page_scores=None):
        del page_scores                                     # ranks per-token
        if active is None:
            active = jnp.ones((cache.batch,), bool)
        over = active & (cache.total_valid() > cfg.cache_budget)
        valid = cache.valid_mask()
        B, P, page = valid.shape
        scores = jnp.where(valid, self._evict_scores(cache, cfg), jnp.inf)
        victim = jnp.argmin(scores.reshape(B, P * page), axis=-1).astype(jnp.int32)
        cache = evict_token(cache, victim, enable=over)
        need = active & (cache.cur_off >= cache.page_size)
        cache, forced = _rollover_to_free_page(cache, need)
        return EvictionOutcome(cache, jnp.zeros((B,), bool), over, forced)


class InverseKeyL2(_UnstructuredTokenPolicy):
    name = "inverse_key_l2"

    def write_score(self, k_tok, v_tok, pos_tok):
        return importance.inverse_key_l2_score(k_tok, axis_name=self.tp_axis)

    def prefill_scores(self, k, v, positions):
        return importance.inverse_key_l2_score(k, axis_name=self.tp_axis)


class KeyDiff(_UnstructuredTokenPolicy):
    name = "keydiff"

    def write_score(self, k_tok, v_tok, pos_tok):
        # keydiff importance is global (needs the mean key) -> computed at
        # eviction time from the live cache; stored score is unused.
        return jnp.zeros(k_tok.shape[:-2], jnp.float32)

    def prefill_scores(self, k, v, positions):
        mean = jnp.mean(k.astype(jnp.float32), axis=1, keepdims=True)
        return importance.keydiff_score(k, mean, axis_name=self.tp_axis)

    def _evict_scores(self, cache, cfg):
        valid = cache.valid_mask()                          # (B,P,page)
        kf = cache.k_view().astype(jnp.float32)
        w = valid[..., None, None].astype(jnp.float32)
        # per-KV-head mean over tokens — shard-local under TP (each shard
        # owns whole heads); only the final cos mean crosses heads
        mean = jnp.sum(kf * w, axis=(1, 2)) / jnp.maximum(
            jnp.sum(w, axis=(1, 2)), 1.0)                   # (B,KV,hd)
        return importance.keydiff_score(kf, mean[:, None, None],
                                        axis_name=self.tp_axis)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

POLICIES: dict[str, EvictionPolicy] = {
    p.name: p
    for p in (FullCache(), PagedEviction(), StreamingLLM(), InverseKeyL2(), KeyDiff())
}


def get_policy(name: str, tp_axis: str | None = None) -> EvictionPolicy:
    """Look up a policy. ``tp_axis`` (tensor-parallel serving only) returns
    a fresh instance whose KV-head score reductions pmean over that mesh
    axis; the default returns the shared local-reduction singleton."""
    try:
        pol = POLICIES[name]
    except KeyError:
        raise KeyError(f"unknown policy {name!r}; available: {sorted(POLICIES)}") from None
    if tp_axis is None:
        return pol
    return type(pol)(tp_axis=tp_axis)
