"""Functional paged KV cache — the TPU/JAX analogue of vLLM's block pool.

Layout (per attention layer; see DESIGN.md §2):
    k, v        : (N_pool, page, KV, hd)  ONE physical page pool shared by
                                          every request in the batch
    pos         : (N_pool, page) int32    original token position; -1 invalid
    score       : (N_pool, page) float32  per-token policy score (higher==keep)
    block_table : (B, P) int32            logical page -> physical pool page;
                                          -1 == unmapped slot
    ref_count   : (N_pool,) int32         pages mapped by a block table;
                                          0 == on the free list
    cur_page, cur_off : (B,) int32        write head (LOGICAL page slot, offset)

The free list is the ``ref_count == 0`` mask; :func:`alloc_pages` always
hands out the lowest-index free pages (deterministic, batch-safe — the i-th
allocating request gets the i-th free page). ``ref_count`` is a true count:
:func:`adopt_prefix` maps one physical page under SEVERAL block tables
(prefix sharing), so releasing a page means *decrementing* — the page's
data is only invalidated (and the page recycled) when the count reaches 0.
Every release path funnels through :func:`_unref_pages`, which enforces the
unmap-vs-free split and clamps at 0 so a double-release can never drive a
slot negative (and never clobbers a page some other table still maps).

Under an eviction policy with budget C and page size Bp, P is statically
``C/Bp + 1`` per request and ``N_pool = B * P`` by default — the budget makes
the working set a *static* shape, which is exactly what XLA wants (vLLM
needs a dynamic allocator for the same thing; see DESIGN.md §2). Unlike the
old per-request slab, a page evicted by one request returns to the SHARED
free list, so it is immediately available as headroom for any other request
— eviction is fleet-level memory reclamation, not per-request bookkeeping.

Evicting a page == zeroing its validity and pushing the physical page back
on the free list. No data movement, ever (the paper's point).

Invariants (tests/test_pool_invariants.py):
    F1  allocated + free == N_pool          (free-list conservation)
    F2  ref_count[p] == number of block-table entries mapping p (ACROSS all
        requests — shared prefix pages legitimately carry counts > 1)
    F3  no physical page is mapped twice by the SAME block table (cross-
        request double-mapping is exactly what prefix sharing is)
    F4  free pages hold no live tokens (their pos rows are all -1)

Sharing semantics (DESIGN.md §7): shared pages are always COMPLETE prompt
pages and are immutable — the write head never points at one (adopt_prefix
parks the head full so the next append rolls onto a fresh exclusive page).
Page-level eviction of a shared page is an unmap: the evicting request
drops its mapping and one reference; k/v/pos/score survive untouched for
every other mapper. Token-level eviction inside a shared page must
copy-on-write first (:func:`fork_page`) — the fork gives the mutating
request a private copy and releases one reference on the original.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import devstats


class PagedLayerCache(NamedTuple):
    k: jax.Array           # (N, page, KV, hd) — bf16/f32, or int8 (quantized)
    v: jax.Array           # (N, page, KV, hd)
    pos: jax.Array         # (N, page) int32, -1 invalid
    score: jax.Array       # (N, page) f32, -inf invalid
    block_table: jax.Array  # (B, P) int32, -1 unmapped
    ref_count: jax.Array   # (N,) int32, 0 == free
    cur_page: jax.Array    # (B,) int32 — logical page slot
    cur_off: jax.Array     # (B,) int32
    # int8 mode (beyond-paper: the quantized-KV composition the paper cites
    # as future work): absmax scale per (token, head); None when not quantized
    k_scale: jax.Array | None = None   # (N, page, KV) f32
    v_scale: jax.Array | None = None   # (N, page, KV) f32
    # telemetry (repro.core.devstats / DESIGN.md §9): per-step event counts
    # accumulated by the pool mutators as pure jnp scatter-adds. None == off
    # (a static Python value, so the disabled path traces unchanged HLO).
    stats: jax.Array | None = None     # (devstats.NSTATS,) int32

    # ----------------------------------------------------------- derived
    @property
    def batch(self) -> int:
        return self.block_table.shape[0]

    @property
    def num_pages(self) -> int:
        """Logical pages per request (block-table width)."""
        return self.block_table.shape[1]

    @property
    def pool_pages(self) -> int:
        """Physical pages in the shared pool."""
        return self.k.shape[0]

    @property
    def page_size(self) -> int:
        return self.k.shape[1]

    # -------------------------------------------------- block-table views
    def mapped_mask(self) -> jax.Array:
        """(B, P) bool — which logical slots hold a physical page."""
        return self.block_table >= 0

    def _phys(self) -> jax.Array:
        """(B, P) int32 — physical ids, clamped to 0 where unmapped."""
        return jnp.maximum(self.block_table, 0)

    def gather_pages(self, pool_arr: jax.Array) -> jax.Array:
        """Gather (N, page, ...) pool data into per-request (B, P, page, ...)
        layout through the block table. Unmapped slots carry page 0's data —
        callers must mask with :meth:`mapped_mask` / :meth:`pos_view`."""
        return jnp.take(pool_arr, self._phys(), axis=0)

    def pos_view(self) -> jax.Array:
        """(B, P, page) int32 — per-request positions; -1 where unmapped."""
        return jnp.where(self.mapped_mask()[..., None],
                         self.gather_pages(self.pos), -1)

    def score_view(self) -> jax.Array:
        """(B, P, page) f32 — per-request scores; -inf where unmapped."""
        return jnp.where(self.mapped_mask()[..., None],
                         self.gather_pages(self.score), -jnp.inf)

    def k_view(self) -> jax.Array:
        """(B, P, page, KV, hd) dequantized per-request K (garbage where
        unmapped — mask with valid_mask())."""
        return self.gather_pages(self.k_dequant())

    def v_view(self) -> jax.Array:
        return self.gather_pages(self.v_dequant())

    # ----------------------------------------------------- token accounting
    def valid_mask(self) -> jax.Array:
        """(B, P, page) bool — which cache slots hold live tokens."""
        return self.pos_view() >= 0

    def tokens_per_page(self) -> jax.Array:
        """(B, P) int32 — live tokens in each logical page."""
        return jnp.sum(self.valid_mask(), axis=-1).astype(jnp.int32)

    def total_valid(self) -> jax.Array:
        """(B,) int32 — live tokens per request."""
        return jnp.sum(self.valid_mask(), axis=(1, 2)).astype(jnp.int32)

    def page_scores(self) -> jax.Array:
        """(B, P) f32 — mean token score per page (paper Alg. 1, block mode).
        Pages with no valid tokens score +inf (never the eviction argmin).

        This is the STORED-score reduction (write-time scores). On the
        Pallas hot paths the attention kernels emit the same reduction as a
        fused epilogue (DESIGN.md §8) and the policies take it via their
        ``page_scores=`` argument, skipping this read entirely."""
        valid = self.valid_mask()
        cnt = jnp.sum(valid, axis=-1)
        ssum = jnp.sum(jnp.where(valid, self.score_view(), 0.0), axis=-1)
        return jnp.where(cnt > 0, ssum / jnp.maximum(cnt, 1), jnp.inf)

    # --------------------------------------------------------- free list
    def free_mask(self) -> jax.Array:
        """(N,) bool — pages on the free list."""
        return self.ref_count == 0

    def num_free(self) -> jax.Array:
        """() int32 — pages currently on the free list (fleet headroom)."""
        return jnp.sum(self.free_mask()).astype(jnp.int32)

    # ------------------------------------------------------- quantization
    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    def k_dequant(self) -> jax.Array:
        """K pool in f32/compute dtype (identity when not quantized)."""
        if not self.quantized:
            return self.k
        return self.k.astype(jnp.float32) * (self.k_scale / 127.0)[..., None]

    def v_dequant(self) -> jax.Array:
        if not self.quantized:
            return self.v
        return self.v.astype(jnp.float32) * (self.v_scale / 127.0)[..., None]


def quantize_absmax(x, axis: int = -1):
    """x: (..., hd) -> (int8 values, (...,) f32 absmax scales)."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=axis)
    q = jnp.round(xf / jnp.maximum(scale, 1e-8)[..., None] * 127.0)
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale


def init_layer_cache(batch: int, num_pages: int, page_size: int,
                     num_kv_heads: int, head_dim: int, dtype,
                     pool_pages: int | None = None,
                     track_stats: bool = False) -> PagedLayerCache:
    """Empty cache: pool of ``pool_pages`` (default batch*num_pages) physical
    pages, per-request block tables of ``num_pages`` logical slots.

    Logical slot 0 of request b is pre-mapped to physical page b so the write
    head always points at a mapped page (the working page).

    ``track_stats`` attaches the (devstats.NSTATS,) int32 telemetry vector;
    the pool mutators then accumulate event counts into it (DESIGN.md §9).
    Off by default: raw-core callers see the exact pre-telemetry pytree."""
    N = pool_pages if pool_pages is not None else batch * num_pages
    assert N >= batch, (N, batch)
    quantized = dtype in ("int8", jnp.int8)
    dt = jnp.int8 if quantized else dtype
    shape = (N, page_size, num_kv_heads, head_dim)
    sshape = (N, page_size, num_kv_heads)
    bt = jnp.full((batch, num_pages), -1, jnp.int32)
    bt = bt.at[:, 0].set(jnp.arange(batch, dtype=jnp.int32))
    ref = jnp.zeros((N,), jnp.int32).at[:batch].set(1)
    return PagedLayerCache(
        k=jnp.zeros(shape, dt),
        v=jnp.zeros(shape, dt),
        pos=jnp.full((N, page_size), -1, jnp.int32),
        score=jnp.full((N, page_size), -jnp.inf, jnp.float32),
        block_table=bt,
        ref_count=ref,
        cur_page=jnp.zeros((batch,), jnp.int32),
        cur_off=jnp.zeros((batch,), jnp.int32),
        k_scale=jnp.zeros(sshape, jnp.float32) if quantized else None,
        v_scale=jnp.zeros(sshape, jnp.float32) if quantized else None,
        stats=devstats.zeros() if track_stats else None,
    )


# ---------------------------------------------------------------------------
# free-list allocator
# ---------------------------------------------------------------------------
# Scatter targets use the pool size N as an out-of-bounds sentinel: JAX drops
# out-of-bounds scatter updates, which makes every batched op below mask-free
# (no where-with-old-value dance, no duplicate-index hazards).

def alloc_pages(cache: PagedLayerCache, need):
    """Pop one free physical page per request where ``need``.

    need: (B,) bool. Returns (cache', phys (B,) int32, ok (B,) bool); ``phys``
    is the pool sentinel N where not ok. The i-th needing request receives the
    i-th lowest-index free page, so simultaneous allocations never collide.
    O(N) via a cumsum + searchsorted over the free mask (no pool sort)."""
    N = cache.pool_pages
    free = cache.free_mask()                          # (N,)
    csum = jnp.cumsum(free.astype(jnp.int32))         # free pages seen so far
    rank = jnp.cumsum(need.astype(jnp.int32)) - 1     # (B,) alloc position
    ok = need & (rank < csum[-1])
    # index of the (rank+1)-th free page
    found = jnp.searchsorted(csum, rank + 1, side="left")
    phys = jnp.where(ok, found, N).astype(jnp.int32)
    ref = cache.ref_count.at[phys].add(1)             # OOB sentinel dropped
    return cache._replace(
        ref_count=ref,
        stats=devstats.bump(cache.stats, devstats.PAGES_ALLOCATED, ok),
    ), phys, ok


def _unref_pages(cache: PagedLayerCache, tgt) -> PagedLayerCache:
    """Release one reference per entry of ``tgt`` (flattened physical ids;
    the pool size N is the masked-out sentinel). The single funnel for EVERY
    release path, enforcing the unmap-vs-free split:

    - ref_count decrements are clamped at 0 — a double-release (the latent
      underflow at the old ``add(-1)`` sites) can never drive a slot
      negative and thereby fake an allocated page.
    - pos/score are invalidated ONLY for pages whose count reaches 0. A page
      some other block table still maps (ref stays > 0 — a shared prefix
      page) keeps its k/v/pos/score intact: releasing is unmapping, never
      data destruction, so :func:`alloc_pages` (free == ref_count 0) can
      never recycle a page whose refcount is still positive.

    Duplicate targets (several rows releasing the same shared page in one
    batched op) accumulate correctly via scatter-add."""
    N = cache.pool_pages
    dec = jnp.zeros((N + 1,), jnp.int32).at[tgt].add(1)[:N]
    new_ref = jnp.maximum(cache.ref_count - dec, 0)
    newly_free = (dec > 0) & (cache.ref_count > 0) & (new_ref == 0)
    # RELEASED counts the decrements that actually landed (the clamp means
    # dec > ref is over-asking), so Δ sum(ref_count) reconciles exactly
    stats = devstats.bump(cache.stats, devstats.PAGES_RELEASED,
                          jnp.minimum(dec, cache.ref_count))
    stats = devstats.bump(stats, devstats.PAGES_FREED, newly_free)
    return cache._replace(
        pos=jnp.where(newly_free[:, None], -1, cache.pos),
        score=jnp.where(newly_free[:, None], -jnp.inf, cache.score),
        ref_count=new_ref,
        stats=stats,
    )


def _free_phys(cache: PagedLayerCache, phys, enable) -> PagedLayerCache:
    """Release one reference on (B,) physical pages where ``enable``; data is
    invalidated only if the page's count reaches 0 (see _unref_pages)."""
    return _unref_pages(cache, jnp.where(enable, phys, cache.pool_pages))


def find_free_slot(cache: PagedLayerCache):
    """(B,) first UNMAPPED logical slot per request + (B,) bool existence."""
    unmapped = ~cache.mapped_mask()                   # (B, P)
    idx = jnp.argmax(unmapped, axis=-1).astype(jnp.int32)
    exists = jnp.any(unmapped, axis=-1)
    return idx, exists


def start_new_page(cache: PagedLayerCache, slot, phys, enable=None
                   ) -> PagedLayerCache:
    """Map logical ``slot`` -> physical ``phys`` (freshly allocated via
    :func:`alloc_pages`) and move the write head there."""
    B = cache.batch
    b = jnp.arange(B)
    if enable is None:
        enable = jnp.ones((B,), bool)
    bt = cache.block_table.at[b, slot].set(
        jnp.where(enable, phys.astype(jnp.int32), cache.block_table[b, slot]))
    return cache._replace(
        block_table=bt,
        cur_page=jnp.where(enable, slot.astype(jnp.int32), cache.cur_page),
        cur_off=jnp.where(enable, 0, cache.cur_off),
    )


def reclaim_empty_pages(cache: PagedLayerCache, include_current=None
                        ) -> PagedLayerCache:
    """Unmap every logical slot whose page holds zero live tokens and return
    the physical page to the shared free list. The current write page is
    exempt unless ``include_current`` (B,) bool says the row is rolling over
    anyway. Empty mapped pages arise from token-level eviction (unstructured
    baselines) and from evicting the just-filled working page."""
    B, P = cache.block_table.shape
    N = cache.pool_pages
    if include_current is None:
        include_current = jnp.zeros((B,), bool)
    is_cur = jax.nn.one_hot(cache.cur_page, P, dtype=bool)
    dead = cache.mapped_mask() & (cache.tokens_per_page() == 0) & \
        (~is_cur | include_current[:, None])          # (B, P)
    # empty pages already hold pos == -1 everywhere (F4): freeing is just
    # a clamped ref_count decrement + block-table unmap
    tgt = jnp.where(dead, cache._phys(), N).reshape(-1)
    cache = _unref_pages(cache, tgt)
    return cache._replace(block_table=jnp.where(dead, -1, cache.block_table))


# ---------------------------------------------------------------------------
# writes
# ---------------------------------------------------------------------------

def write_token(cache: PagedLayerCache, k_tok, v_tok, pos_tok, score_tok,
                active=None) -> PagedLayerCache:
    """Append one token per request at the write head.

    k_tok, v_tok: (B, KV, hd); pos_tok: (B,) int32; score_tok: (B,) f32.
    ``active``: optional (B,) bool — requests not active are left untouched
    (continuous batching: finished / empty slots).
    Caller must ensure cur_off < page_size (policies roll the page over)."""
    B = cache.batch
    b = jnp.arange(B)
    N = cache.pool_pages
    if active is None:
        active = jnp.ones((B,), bool)
    phys = cache.block_table[b, cache.cur_page]       # (B,) physical page
    ok = active & (phys >= 0)
    tgt = jnp.where(ok, phys, N)                      # OOB drop when masked
    o = cache.cur_off

    def upd(dst, val):
        return dst.at[tgt, o].set(val.astype(dst.dtype))

    if cache.quantized:
        kq, ks = quantize_absmax(k_tok)
        vq, vs = quantize_absmax(v_tok)
        k = upd(cache.k, kq)
        v = upd(cache.v, vq)
        cache = cache._replace(k_scale=upd(cache.k_scale, ks),
                               v_scale=upd(cache.v_scale, vs))
    else:
        k = upd(cache.k, k_tok)
        v = upd(cache.v, v_tok)
    pos = cache.pos.at[tgt, o].set(pos_tok.astype(jnp.int32))
    score = cache.score.at[tgt, o].set(score_tok.astype(jnp.float32))
    off = jnp.where(ok, o + 1, o)
    return cache._replace(
        k=k, v=v, pos=pos, score=score, cur_off=off,
        stats=devstats.bump(cache.stats, devstats.TOKENS_WRITTEN, ok))


def write_prompt_pages(cache: PagedLayerCache, k_sel, v_sel, pos_sel, score_sel,
                       ) -> PagedLayerCache:
    """Bulk-write C selected prompt tokens (already compressed by the prefill
    policy) into logical pages [0 .. C/page). C must be a multiple of
    page_size. RESETS the whole cache: every request row is rewritten, all
    previous mappings are discarded. Being a wholesale reset it does NOT
    emit devstats events (the conservation identities of DESIGN.md §9 hold
    across the incremental mutators only; the engine's unified step never
    calls this — it is the offline/bench path).

    Physical placement is row-major over the first B*(n+1) pool pages —
    deterministic, so prefill results are bit-stable regardless of what the
    pool held before. One extra page per request is mapped (and left empty)
    as the decode working page wherever the block table has room.

    k_sel, v_sel: (B, C, KV, hd); pos_sel: (B, C) (-1 = padding/invalid);
    score_sel: (B, C)."""
    B, C = pos_sel.shape
    page = cache.page_size
    P = cache.num_pages
    N = cache.pool_pages
    assert C % page == 0, (C, page)
    n = C // page
    assert n <= P, (n, P)
    KV, hd = k_sel.shape[2], k_sel.shape[3]
    # map an empty working page after the prompt pages when a slot exists;
    # when the prompt exactly fills the block table, park the head on the
    # last page with cur_off == page_size (writes drop until rollover)
    extra = 1 if n < P else 0
    stride = n + extra
    assert B * stride <= N, (B, stride, N)

    phys = (jnp.arange(B, dtype=jnp.int32)[:, None] * stride +
            jnp.arange(stride, dtype=jnp.int32)[None, :])      # (B, stride)
    bt = jnp.full((B, P), -1, jnp.int32)
    bt = lax.dynamic_update_slice(bt, phys, (0, 0))
    ref = jnp.zeros((N,), jnp.int32).at[phys.reshape(-1)].set(1)

    def scatter_prompt(reset_pool, val):
        """Write the (B*n, ...) prompt pages into the freshly-reset pool at
        rows b*stride + j."""
        idx = (jnp.arange(B, dtype=jnp.int32)[:, None] * stride +
               jnp.arange(n, dtype=jnp.int32)[None, :]).reshape(-1)
        return reset_pool.at[idx].set(val.astype(reset_pool.dtype))

    if cache.quantized:
        kq, ks = quantize_absmax(k_sel)
        vq, vs = quantize_absmax(v_sel)
        k = scatter_prompt(jnp.zeros_like(cache.k),
                           kq.reshape(B * n, page, KV, hd))
        v = scatter_prompt(jnp.zeros_like(cache.v),
                           vq.reshape(B * n, page, KV, hd))
        cache = cache._replace(
            k_scale=scatter_prompt(jnp.zeros_like(cache.k_scale),
                                   ks.reshape(B * n, page, KV)),
            v_scale=scatter_prompt(jnp.zeros_like(cache.v_scale),
                                   vs.reshape(B * n, page, KV)))
    else:
        k = scatter_prompt(jnp.zeros_like(cache.k),
                           k_sel.reshape(B * n, page, KV, hd))
        v = scatter_prompt(jnp.zeros_like(cache.v),
                           v_sel.reshape(B * n, page, KV, hd))
    pos_pages = pos_sel.reshape(B * n, page).astype(jnp.int32)
    score_pages = jnp.where(pos_sel.reshape(B * n, page) >= 0,
                            score_sel.reshape(B * n, page).astype(jnp.float32),
                            -jnp.inf)
    pos = scatter_prompt(jnp.full_like(cache.pos, -1), pos_pages)
    score = scatter_prompt(jnp.full_like(cache.score, -jnp.inf), score_pages)
    return cache._replace(
        k=k, v=v, pos=pos, score=score, block_table=bt, ref_count=ref,
        cur_page=jnp.full((B,), min(n, P - 1), jnp.int32),
        cur_off=jnp.full((B,), 0 if extra else page, jnp.int32),
    )


# ---------------------------------------------------------------------------
# page-level operations (used by eviction policies)
# ---------------------------------------------------------------------------

def evict_page(cache: PagedLayerCache, page_idx, enable=None) -> PagedLayerCache:
    """Evict an entire LOGICAL page per request: invalidate its tokens,
    return the physical page to the shared free list, unmap the slot.
    page_idx: (B,) int32 logical slot. ``enable``: (B,) bool."""
    B = cache.batch
    b = jnp.arange(B)
    if enable is None:
        enable = jnp.ones((B,), bool)
    phys = cache.block_table[b, page_idx]             # (B,)
    en = enable & (phys >= 0)
    cache = _free_phys(cache, jnp.maximum(phys, 0), en)
    bt = cache.block_table.at[b, page_idx].set(
        jnp.where(en, -1, cache.block_table[b, page_idx]))
    return cache._replace(
        block_table=bt,
        stats=devstats.bump(cache.stats, devstats.PAGES_EVICTED, en))


def fork_page(cache: PagedLayerCache, slot, enable=None):
    """Copy-on-write fork: where ``enable`` and the physical page mapped at
    logical ``slot`` is SHARED (ref_count > 1), copy its k/v/pos/score (and
    int8 scales) onto a freshly allocated pool page, remap this row's slot to
    the copy, and release one reference on the original. Rows whose page is
    exclusive or unmapped are untouched (fork is the identity there).

    slot: (B,) int32 logical slots. Returns (cache, forked (B,) bool).
    If the pool is dry the fork silently does not happen (forked stays
    False) — callers must then skip their mutation of that row, because the
    un-forked page is another request's live data. Two rows forking the same
    source page in one call each get their own copy; if every mapper forks
    away, the source's count reaches 0 and it returns to the free list."""
    B = cache.batch
    b = jnp.arange(B)
    N = cache.pool_pages
    if enable is None:
        enable = jnp.ones((B,), bool)
    phys = cache.block_table[b, slot]                     # (B,)
    src = jnp.maximum(phys, 0)
    need = enable & (phys >= 0) & (cache.ref_count[src] > 1)
    cache, newp, ok = alloc_pages(cache, need)
    do = need & ok
    tgt = jnp.where(do, newp, N)                          # OOB drop when masked

    def cp(arr):
        return arr.at[tgt].set(arr[src])

    cache = cache._replace(
        k=cp(cache.k), v=cp(cache.v), pos=cp(cache.pos), score=cp(cache.score),
        k_scale=cp(cache.k_scale) if cache.quantized else None,
        v_scale=cp(cache.v_scale) if cache.quantized else None,
        block_table=cache.block_table.at[b, slot].set(
            jnp.where(do, newp.astype(jnp.int32), phys)),
        stats=devstats.bump(cache.stats, devstats.PAGES_FORKED, do),
    )
    # release one reference on the source (was > 1, so this never invalidates
    # unless EVERY mapper forked away in this very call — then it frees)
    return _unref_pages(cache, jnp.where(do, src, N)), do


def _shared_slots(cache: PagedLayerCache) -> jax.Array:
    """(B, P) bool — logical slots whose physical page is mapped by more
    than one block-table entry."""
    return cache.mapped_mask() & (cache.ref_count[cache._phys()] > 1)


def _cow_slots_mask(cache: PagedLayerCache, slot_mask) -> PagedLayerCache:
    """CoW barrier token-level mutation paths run before writing: for each
    row, fork the FIRST (row, slot) in the (B, P) bool mask whose page is
    shared. At most one fork per row per call keeps the decode-step graph
    small; remaining shared slots stay un-forked this round and their
    mutation is skipped by the callers' exclusive-page gate, then forked on
    the next step's barrier — lazy CoW, same invariants, budget transiently
    exceeded at worst. Runs unconditionally (fork_page is the identity when
    nothing targeted is shared): a data-dependent cond here would re-trace
    its branches on every eager call, and under jit XLA pays the small fork
    graph either way."""
    hit = slot_mask & _shared_slots(cache)                # (B, P)
    slot = jnp.argmax(hit, axis=-1).astype(jnp.int32)     # first shared slot
    cache, _ = fork_page(cache, slot, enable=jnp.any(hit, axis=-1))
    return cache


def evict_token(cache: PagedLayerCache, flat_idx, enable=None) -> PagedLayerCache:
    """Invalidate a single token per request addressed by flattened LOGICAL
    (P*page) index. flat_idx: (B,) int32. The physical page stays mapped
    (unstructured fragmentation — the paper's Limitation 1); fully-emptied
    pages return to the pool at the next rollover via reclaim_empty_pages.

    Mutating a SHARED page would corrupt the sharer's view, so the page is
    CoW-forked first; if the fork is starved (pool dry) the eviction is
    skipped this round — the budget is transiently exceeded rather than
    another request's cache corrupted."""
    B = cache.batch
    page = cache.page_size
    N = cache.pool_pages
    b = jnp.arange(B)
    if enable is None:
        enable = jnp.ones((B,), bool)
    pi, oi = flat_idx // page, flat_idx % page
    cache, _ = fork_page(cache, pi, enable=enable)
    phys = cache.block_table[b, pi]
    en = enable & (phys >= 0) & (cache.ref_count[jnp.maximum(phys, 0)] <= 1)
    tgt = jnp.where(en, jnp.maximum(phys, 0), N)
    # count only evictions that invalidated a LIVE token (clamped read of
    # row N-1 for masked rows is harmless — en gates it out)
    live = en & (cache.pos[jnp.minimum(tgt, N - 1), oi] >= 0)
    return cache._replace(
        pos=cache.pos.at[tgt, oi].set(-1),
        score=cache.score.at[tgt, oi].set(-jnp.inf),
        stats=devstats.bump(cache.stats, devstats.TOKENS_EVICTED, live),
    )


# ---------------------------------------------------------------------------
# chunked append (prefill writes straight into the shared pool)
# ---------------------------------------------------------------------------
# The old continuous-batching path prefilled a request into a private B=1
# pool and spliced it into the batch (``insert_request``). That splice — and
# its per-slot-specialized compiled program — is gone: requests now prefill
# in place, chunk by chunk, through the same block tables decode uses.

def release_rows(cache: PagedLayerCache, enable) -> PagedLayerCache:
    """Free EVERY page the selected batch rows map (request retired — its
    slot is being handed to a new request) and reset their write heads.
    ``enable``: (B,) bool. Runs inside the unified step for rows that start
    prefilling this step, so the leaving request's pages return to the
    SHARED free list before the newcomer's first chunk allocates. Pages the
    retiring row shared with a still-resident request only lose one
    reference — their data stays live for the sharer (_unref_pages)."""
    B, P = cache.block_table.shape
    N = cache.pool_pages
    dead = cache.mapped_mask() & enable[:, None]          # (B, P)
    tgt = jnp.where(dead, cache._phys(), N).reshape(-1)
    cache = _unref_pages(cache, tgt)
    return cache._replace(
        block_table=jnp.where(dead, -1, cache.block_table),
        cur_page=jnp.where(enable, 0, cache.cur_page),
        # park the head "full" on the unmapped slot: the first append's lazy
        # rollover then allocates the row's first page from the free list
        cur_off=jnp.where(enable, cache.page_size, cache.cur_off),
    )


def adopt_prefix(cache: PagedLayerCache, src, n_pages, enable=None
                 ) -> PagedLayerCache:
    """Map the first ``n_pages`` logical slots of row ``src`` into each
    enabled row's block table, bumping the shared pages' ref counts — the
    device half of prefix sharing (the host half is the scheduler's radix
    lookup plus the engine's intactness probe; DESIGN.md §7).

    src: (B,) int32 source batch row (-1 == no sharing); n_pages: (B,) int32.
    Preconditions the caller (forward_step's reset path) guarantees:
    the enabled row was just released (empty block table), ``src`` is a
    live, different row, and its first ``n_pages`` slots are mapped FULL
    pages holding the contiguous token prefix [0, n_pages*page_size) — the
    engine probes exactly this before scheduling the adoption.

    The write head parks FULL on the last adopted slot, so the adopting
    row's first appended token lazily rolls onto a fresh exclusive page:
    shared pages are never written, only read — and unmapped or CoW-forked
    by the eviction paths."""
    B, P = cache.block_table.shape
    N = cache.pool_pages
    if enable is None:
        enable = jnp.ones((B,), bool)
    en = enable & (src >= 0) & (n_pages > 0)
    src_bt = cache.block_table[jnp.maximum(src, 0)]       # (B, P) source rows
    take = en[:, None] & (jnp.arange(P)[None, :] < n_pages[:, None]) & \
        (src_bt >= 0)
    bt = jnp.where(take, src_bt, cache.block_table)
    tgt = jnp.where(take, jnp.maximum(src_bt, 0), N).reshape(-1)
    return cache._replace(
        block_table=bt,
        ref_count=cache.ref_count.at[tgt].add(1),
        stats=devstats.bump(cache.stats, devstats.PAGES_ADOPTED, take),
        cur_page=jnp.where(en, jnp.maximum(n_pages - 1, 0).astype(jnp.int32),
                           cache.cur_page),
        cur_off=jnp.where(en, cache.page_size, cache.cur_off),
    )


def rollover_to_free_page(cache: PagedLayerCache, need):
    """Where ``need``, move the write head onto a fresh physical page:
    reclaim fully-emptied mapped pages, pick the first unmapped logical
    slot, pop a free pool page, map it. If a row has no unmapped slot or
    the pool is dry, force-evict that row's fewest-token (but > 0) page —
    never the current write page — which releases both a slot and a
    physical page, so the next write ALWAYS lands. Returns
    (cache, must_force (B,) bool). Shared by decode post_write rollover
    (`policies._rollover_to_free_page`, which reports the telemetry) and
    the chunked-append path."""
    c = reclaim_empty_pages(cache, include_current=need)
    slot, slot_ok = find_free_slot(c)
    rank = jnp.cumsum(need.astype(jnp.int32)) - 1
    phys_ok = rank < c.num_free()
    must_force = need & (~slot_ok | ~phys_ok)
    tpp = c.tokens_per_page().astype(jnp.float32)         # (B, P)
    B, P = tpp.shape
    cur_onehot = jax.nn.one_hot(c.cur_page, P, dtype=bool)
    # prefer EXCLUSIVE pages as force-victims: unmapping a shared page frees
    # a logical slot but no physical page (the sharer keeps it), so it only
    # helps when no exclusively-owned candidate exists at all
    shared_penalty = jnp.where(_shared_slots(c), 1e6, 0.0)
    cand = jnp.where((tpp > 0) & ~cur_onehot, tpp + shared_penalty, jnp.inf)
    victim = jnp.argmin(cand, axis=-1).astype(jnp.int32)
    c = c._replace(stats=devstats.bump(c.stats, devstats.FORCED_EVICTIONS,
                                       must_force))
    c = evict_page(c, victim, enable=must_force)
    slot2, _ = find_free_slot(c)
    slot = jnp.where(must_force, slot2, slot)
    c, phys, ok = alloc_pages(c, need)
    return start_new_page(c, slot, phys, enable=need & ok), must_force


def _chunk_roll_noop(args):
    return args[0]


def _chunk_roll_body(args):
    cache, need = args
    return rollover_to_free_page(cache, need)[0]


def chunk_rollover(cache: PagedLayerCache, need) -> PagedLayerCache:
    """Where ``need``, move the write head onto a fresh physical page from
    the SHARED free list (reclaiming fully-emptied mapped pages first).
    Chunked prefill sizes block tables with ``ceil(chunk/page)`` slots of
    headroom (``transformer.init_decode_caches``), so structured policies
    never run dry mid-chunk; unstructured token policies CAN (their top-C
    survivors scatter one-per-page), in which case the fewest-token page is
    force-evicted so the incoming tokens always land."""
    return lax.cond(jnp.any(need), _chunk_roll_body, _chunk_roll_noop,
                    (cache, need))


def _without_pool(cache: PagedLayerCache) -> PagedLayerCache:
    """The cache with its payload (k, v, int8 scales) emptied to zero-size
    arrays: what a ``lax.cond`` over the allocator may carry — a branch
    that takes the pool makes XLA copy it. Shapes and page size hold."""
    N, page = cache.pos.shape
    empty = jnp.zeros((N, page, 0, 0), cache.k.dtype)
    return cache._replace(k=empty, v=empty, k_scale=None, v_scale=None)


def _allocate_chunk(meta: PagedLayerCache, rolls_at_0, fresh, t_roll,
                    fits):
    """Reclaim once, then map every fresh page of a chunk in one pass.

    ``meta``: :func:`_without_pool` of the cache; ``rolls_at_0`` (B,) rows
    parked full that roll over at the chunk's first token; ``fresh``
    (B, K) fresh page k of row b is needed; ``t_roll`` (B, K) the token at
    which it is opened; ``fits`` (() bool) the plan's other condition.
    Returns (meta', phys (B, K), fits'): fresh page k of row b is pool page
    ``phys[b, k]``, mapped at the row's k-th unmapped slot; ``fits'`` adds
    that every row has the slots and the pool the pages (else the
    per-token form must force-evict, and ``meta`` comes back as it was)."""
    B, P = meta.block_table.shape
    K = fresh.shape[1]
    b = jnp.arange(B)
    c = reclaim_empty_pages(meta, include_current=rolls_at_0)
    unmapped = ~c.mapped_mask()                                   # (B, P)
    need = jnp.sum(fresh, axis=1)
    fits &= (jnp.all(need <= jnp.sum(unmapped, axis=1))
             & (jnp.sum(need) <= c.num_free()))
    # at one token rows allocate in row order: rank by (token, row)
    key = jnp.where(fresh, t_roll * B + b[:, None], jnp.iinfo(jnp.int32).max)
    order = jnp.argsort(key.reshape(-1))
    c, phys, _ = alloc_pages(c, fresh.reshape(-1)[order])
    phys = jnp.zeros_like(phys).at[order].set(phys).reshape(B, K)
    # the k-th unmapped slot of each row (rank K and beyond: dropped)
    rank = jnp.where(unmapped, jnp.cumsum(unmapped, axis=1) - 1, K)
    slot = jnp.zeros((B, K), jnp.int32).at[b[:, None], rank].set(
        jnp.arange(P, dtype=jnp.int32)[None])
    last = jnp.maximum(need - 1, 0)
    c = c._replace(
        block_table=c.block_table.at[b[:, None], jnp.where(fresh, slot, P)]
        .set(phys),
        cur_page=jnp.where(need > 0, slot[b, last], c.cur_page))
    return jax.tree.map(lambda x, y: jnp.where(fits, x, y), c, meta), \
        phys, fits


def _allocate_none(meta: PagedLayerCache, rolls_at_0, fresh, t_roll, fits):
    """:func:`_allocate_chunk` where no row opens a fresh page: nothing."""
    del rolls_at_0, t_roll
    return meta, jnp.zeros(fresh.shape, jnp.int32), fits


def _plan_chunk(cache: PagedLayerCache, pos_chunk, n_tok):
    """What the per-token loop of :func:`append_chunk` does to the pool's
    metadata, in closed form.

    Row b's head page takes its first ``page - cur_off`` tokens; its k-th
    rollover comes at token ``(page - cur_off) + k*page`` and maps a fresh
    page at its k-th unmapped slot. The loop reclaims empty pages at its
    first rollover (once: nothing empties mid-chunk) and, at one token,
    allocates in row order, so the fresh pages go out in (token, row)
    order — in one :func:`_allocate_chunk`, skipped where no row rolls.
    Returns ``(head, tgt, off, fits)``: ``fits`` (() bool) says whether the
    plan IS the loop's result — no row force-evicts, and no written token
    has ``pos < 0`` (its page could empty and be reclaimed mid-chunk);
    then ``head`` holds the metadata fields after the chunk and
    ``tgt``/``off`` (B, T) each token's pool page (N where it is not
    written) and offset, else the fields as they were and every ``tgt``
    N."""
    B, T = pos_chunk.shape
    page, N = cache.page_size, cache.pool_pages
    K = -(-T // page)                         # most fresh pages a row needs
    b = jnp.arange(B)
    t = jnp.arange(T)
    head_phys = cache.block_table[b, cache.cur_page]
    off0 = cache.cur_off
    # a head on an unmapped slot with room left drops every write
    # (write_token), so that row never rolls over either
    n = jnp.where((head_phys < 0) & (off0 < page), 0, jnp.clip(n_tok, 0, T))
    need = (jnp.maximum(off0 + n - page, 0) + page - 1) // page   # (B,)
    fresh = jnp.arange(K)[None] < need[:, None]                   # (B, K)
    t_roll = (page - off0)[:, None] + jnp.arange(K)[None] * page
    active = t[None] < n[:, None]                                 # (B, T)
    meta, phys, fits = lax.cond(
        jnp.any(need > 0), _allocate_chunk, _allocate_none,
        _without_pool(cache), (n > 0) & (off0 >= page), fresh, t_roll,
        ~jnp.any(active & (pos_chunk < 0)))
    active &= fits
    # token t: head page at cur_off + t, else fresh page j // page at j % page
    j = t[None] - (page - off0)[:, None]                          # (B, T)
    in_head = j < 0
    fresh_phys = jnp.take_along_axis(phys, jnp.clip(j // page, 0, K - 1), 1)
    tgt = jnp.where(active, jnp.where(in_head, head_phys[:, None], fresh_phys),
                    N)
    off = jnp.where(in_head, off0[:, None] + t[None], j % page)
    head = dict(
        pos=meta.pos, score=meta.score, block_table=meta.block_table,
        ref_count=meta.ref_count, cur_page=meta.cur_page,
        cur_off=jnp.where(~fits, off0, jnp.where(
            need > 0, (off0 + n - 1) % page + 1, off0 + n)).astype(jnp.int32),
        stats=devstats.bump(meta.stats, devstats.TOKENS_WRITTEN, active))
    return head, tgt, off, fits


def append_chunk(cache: PagedLayerCache, k_chunk, v_chunk, pos_chunk,
                 score_chunk, n_tok) -> PagedLayerCache:
    """Append up to T tokens per request at the write head, allocating fresh
    pages from the shared free list as pages fill.

    k_chunk, v_chunk : (B, T, KV, hd)
    pos_chunk        : (B, T) int32, -1 for padding past ``n_tok``
    score_chunk      : (B, T) f32 policy write scores
    n_tok            : (B,) int32 — row b appends tokens [0, n_tok[b])

    NO eviction happens mid-chunk: the policy compresses at the chunk
    boundary (``EvictionPolicy.chunk_prefill_evict`` — the incremental form
    of the paper's Alg. 2), so a row transiently holds up to
    budget + chunk tokens. A decode row is just the T == 1 (or n_tok == 1)
    case of the same op — the unified step program has no separate insert
    or prefill write path.

    The chunk is planned in closed form (:func:`_plan_chunk`: reclaim once,
    allocate every fresh page in one pass) and written with one scatter
    per pool array (int8 pools quantize the whole chunk: scales are per
    token and head). That is bit-identical to writing token by token — a
    lazy :func:`chunk_rollover` and a :func:`write_token` per slot — which
    runs instead only where a row must force-evict (unstructured survivors
    can pin every slot); ``CHUNK_APPEND_FALLBACKS`` counts those calls. The
    per-token loop's trip count is 0 when the plan fits, where a
    ``lax.cond`` holding the pool would copy it."""
    T = pos_chunk.shape[1]
    head, tgt, off, fits = _plan_chunk(cache, pos_chunk, n_tok)
    cache = cache._replace(**head)

    def put(dst, val):
        return dst.at[tgt, off].set(val.astype(dst.dtype))

    if cache.quantized:
        kq, ks = quantize_absmax(k_chunk)
        vq, vs = quantize_absmax(v_chunk)
        cache = cache._replace(k=put(cache.k, kq), v=put(cache.v, vq),
                               k_scale=put(cache.k_scale, ks),
                               v_scale=put(cache.v_scale, vs))
    else:
        cache = cache._replace(k=put(cache.k, k_chunk), v=put(cache.v, v_chunk))
    cache = cache._replace(pos=put(cache.pos, pos_chunk),
                           score=put(cache.score, score_chunk))

    def body(t, c):
        act = t < n_tok
        c = chunk_rollover(c, act & (c.cur_off >= c.page_size))
        return write_token(c, k_chunk[:, t], v_chunk[:, t], pos_chunk[:, t],
                           score_chunk[:, t], active=act)

    cache = lax.fori_loop(0, jnp.where(fits, 0, T), body, cache)
    return cache._replace(stats=devstats.bump(
        cache.stats, devstats.CHUNK_APPEND_FALLBACKS, ~fits))


# ---------------------------------------------------------------------------
# masked bulk eviction (chunk-boundary compression)
# ---------------------------------------------------------------------------

def evict_token_mask(cache: PagedLayerCache, mask) -> PagedLayerCache:
    """Invalidate every token selected by a LOGICAL (B, P, page) bool mask.
    Physical pages stay mapped; fully-emptied pages return to the pool via
    :func:`reclaim_empty_pages` (the chunk hook calls it after this).

    Slots whose page is SHARED are CoW-forked before the write (the sharer's
    view must not change); a slot whose fork was starved by a dry pool is
    skipped — budget transiently exceeded, never cross-request corruption."""
    B, P, page = mask.shape
    N = cache.pool_pages
    cache = _cow_slots_mask(cache, jnp.any(mask, axis=-1))
    phys = jnp.broadcast_to(cache._phys()[..., None], (B, P, page))
    exclusive = cache.ref_count[cache._phys()] <= 1       # (B, P)
    en = mask & (cache.mapped_mask() & exclusive)[..., None]
    tgt = jnp.where(en, phys, N).reshape(-1)
    off = jnp.broadcast_to(jnp.arange(page, dtype=jnp.int32), (B, P, page)
                           ).reshape(-1)
    live = en & (cache.pos_view() >= 0)   # only live slots count as evicted
    return cache._replace(
        pos=cache.pos.at[tgt, off].set(-1),
        score=cache.score.at[tgt, off].set(-jnp.inf),
        stats=devstats.bump(cache.stats, devstats.TOKENS_EVICTED, live),
    )


def evict_pages_mask(cache: PagedLayerCache, mask) -> PagedLayerCache:
    """Evict every LOGICAL page selected by a (B, P) bool mask: unmap the
    slot and release one reference; tokens are invalidated (and the physical
    page returns to the shared free list) only when no other block table
    still maps the page. The multi-victim form of :func:`evict_page` — chunk
    boundaries can owe up to ceil(chunk/page) evictions at once. Evicting a
    SHARED prefix page is therefore purely local: the evicting request's
    view shrinks (valid_mask follows mapped_mask), the sharer's view is
    untouched."""
    N = cache.pool_pages
    en = mask & cache.mapped_mask()                       # (B, P)
    tgt = jnp.where(en, cache._phys(), N).reshape(-1)
    cache = _unref_pages(cache, tgt)
    return cache._replace(
        block_table=jnp.where(en, -1, cache.block_table),
        stats=devstats.bump(cache.stats, devstats.PAGES_EVICTED, en))


def row_intact_prefix_pages(cache: PagedLayerCache, row) -> jax.Array:
    """() int32 — length of the leading run of batch row ``row``'s logical
    slots that hold COMPLETE, position-contiguous prompt pages (slot i holds
    exactly positions [i*page, (i+1)*page)). This is what makes a prefix
    adoptable: eviction may have punched holes in the owner's prefix (or a
    windowed layer shed it), and a partially-written working page never
    qualifies. Capped at P-1 so an adopting row always keeps an unmapped
    slot for its own working page. The engine's prefix-sharing probe takes
    the min of this over every attention layer (transformer.intact_prefix_pages)."""
    P = cache.num_pages
    page = cache.page_size
    bt = cache.block_table[row]                           # (P,)
    pos = cache.pos[jnp.maximum(bt, 0)]                   # (P, page)
    want = (jnp.arange(P, dtype=jnp.int32)[:, None] * page +
            jnp.arange(page, dtype=jnp.int32)[None, :])
    ok = (bt >= 0) & jnp.all(pos == want, axis=-1)
    run = jnp.sum(jnp.cumprod(ok.astype(jnp.int32)))
    return jnp.minimum(run, P - 1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# gather to contiguous (tests / reference paths)
# ---------------------------------------------------------------------------

def to_contiguous(cache: PagedLayerCache):
    """Return (k, v, pos, mask) flattened over logical pages:
    (B, P*page, KV, hd), dequantized if needed. Order is physical-within-
    logical, not position order — attention is permutation-invariant given
    correct positions, which tests exploit."""
    B, P, page = cache.batch, cache.num_pages, cache.page_size
    KV, hd = cache.k.shape[2], cache.k.shape[3]
    return (cache.k_view().reshape(B, P * page, KV, hd),
            cache.v_view().reshape(B, P * page, KV, hd),
            cache.pos_view().reshape(B, P * page),
            cache.valid_mask().reshape(B, P * page))


# ---------------------------------------------------------------------------
# forensics view (obs/lineage.py)
# ---------------------------------------------------------------------------

def lineage_snapshot(cache: PagedLayerCache) -> dict:
    """Pure-jnp forensics view of one layer's pool, jitted by the engine and
    pulled to host once per step when the lineage ledger is on. The ledger
    diffs consecutive snapshots (plus the step plan) into alloc / adopt /
    fork / evict / release events and reconciles its replayed state against
    ``block_table`` / ``ref_count`` exactly (DESIGN.md §10).

    ``page_scores`` is the PRE-mutation policy ranking from the *previous*
    step's snapshot that prices an eviction observed this step — the ledger
    reads scores from ``prev``, never ``cur``."""
    return {
        "block_table": cache.block_table,            # (B, P) int32
        "ref_count": cache.ref_count,                # (N,) int32
        "cur_page": cache.cur_page,                  # (B,) int32 working lpi
        "tokens_per_page": cache.tokens_per_page(),  # (B, P) int32
        "page_scores": cache.page_scores(),          # (B, P) f32, inf=empty
        "pos_base": jnp.where(                       # (B, P) int32, -1=empty
            cache.tokens_per_page() > 0,
            jnp.min(jnp.where(cache.valid_mask(), cache.pos_view(),
                              jnp.iinfo(jnp.int32).max), axis=-1),
            -1).astype(jnp.int32),
    }
