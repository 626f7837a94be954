"""Pallas TPU paged-attention decode kernel with block-table indirection.

The TPU-native replacement for vLLM's CUDA PagedAttention (DESIGN.md §2):
one query token per request attends over the SHARED page pool, walking its
block table page by page, with flash (online-softmax) accumulation in VMEM
scratch.

Grid: (batch, kv_head, split, page_in_split) — SPLIT-K flash-decode
(DESIGN.md §8). Long-context decode is latency-bound on a single query
token walking pages serially, so the logical-page axis is partitioned into
``num_splits`` independent chunks. Each chunk accumulates its OWN
(m, l, acc) flash state over its page range (TPU grid execution is
sequential over the minor-most dimension, so the scratch accumulates across
``page_in_split`` and resets at each split boundary), and the kernel emits
the UN-normalized partial state per split. A second lightweight combine
step (plain jnp in the wrapper — O(S·G·hd) elementwise, negligible next to
the page walk) rescales the partials to a common max and normalizes:

    m* = max_s m_s;  o = Σ_s e^{m_s − m*}·acc_s / Σ_s e^{m_s − m*}·l_s

— the xformers ``ops/fmha/triton.py`` split-K idiom ported to the Pallas
TPU sequential-grid model. On hardware the split axis is embarrassingly
parallel (no scratch carried across it), so ``num_splits`` shortens the
serial chain from P to ceil(P/S) page steps; ``num_splits=1`` reproduces
the old single-chain walk exactly (the combine degenerates to the old
finalize's ``acc / max(l, eps)``). Empty splits are safe by construction:
they emit m = NEG_INF, l = 0, acc = 0, and e^{NEG_INF − m*} underflows to
exactly 0 in the combine.

Indirection is gather-free: the block table rides in as a scalar-prefetch
operand (``pltpu.PrefetchScalarGridSpec``), so each BlockSpec ``index_map``
reads ``bt[b, p]`` and DMAs exactly one (page_size, head_dim) physical K/V
tile from the pool — the working set is O(page) regardless of context
length or pool size, and no (B, P, page, ...) gathered copy of the cache is
ever materialized. Unmapped slots (bt[b, p] < 0) clamp their DMA to pool
page 0 and are masked inside the kernel body via the same scalar ref —
essential, because a freed physical page may already hold ANOTHER request's
live tokens. The masking is per (b, h, split, i) step, so freed/reallocated
pages stay correctly masked no matter which split walks them. Logical pages
past P (padding steps when P % num_splits != 0) clamp to slot P - 1 and
mask everything — they contribute exactly nothing.

Fused score epilogue (``return_scores=True``): the K/V tiles are already
live in VMEM (dequantized for int8 pools), so the per-token L2 norms that
``kernels/block_score.py`` recomputes in a separate full pass over the pool
come out as byproduct outputs kn/vn: (B, KV, P, page) — one (1, page) tile
per (b, h, p) step, written unmasked (the wrapper-side combine masks by
block table + pos and reduces to the paper's Alg.1 page score, see
``importance.page_scores_from_norms``). Eviction metadata is then free:
zero extra HBM reads, one extra VPU reduction per tile the kernel already
fetched. The standalone ``block_score`` kernel survives only as the parity
oracle.

Prefix sharing (DESIGN.md §7) needs no extra masking here: a physical page
mapped under several block tables is always a COMPLETE prompt-prefix page
holding the SAME positions [slot*page, (slot+1)*page) for every mapper (the
adoption probe enforces it), so the existing mapped / pos >= 0 / pos <=
cur_pos masks are already correct for shared pages. What sharing does rule
out is any assumption that bt rows are disjoint — two requests' tables may
point the same tile, and the kernel must treat each (b, p) step
independently (it does: all per-step state is derived from bt[b, p]).
Epilogue outputs are indexed by LOGICAL slot (b, p), so two sharers of one
physical page each write their own copy of its norms — identical values,
no conflict.

Layout: the wrapper (ops.py) permutes the pool to (KV, N_pool, page, hd) so
each block is a contiguous (page, hd) tile — page_size 16 x head_dim 128 is
MXU/VPU aligned. Per-page rows (``pos``, int8 scales, the norm outputs)
carry a unit axis, ``(N, 1, page)``, so each ``(1, page)`` block spans its
array's two trailing dims, as Mosaic requires of blocks that are not
(8, 128) multiples; ``cur_pos`` rides in SMEM as a second scalar-prefetch
operand (DESIGN.md §2).

TPU page sizes: every kernel of this package compiles for a v5e in bf16 at
page 8, 16 and 32, with head_dim 64 and 128 (``tests/test_tpu_compile.py``
pins page 16). Page 8 is half a bf16 sublane tile (16 rows) and compiles;
only page 16 has run on a chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1e30


def _flash_update(s, valid, v, m_scr, l_scr, acc_scr):
    """One online-softmax update of the (m, l, acc) scratch state.

    s: (rows, page) masked scores; valid: (rows, page) bool; v: (page, hd).
    """
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_scr[:, 0:1]                              # (rows, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    pexp = jnp.exp(s - m_new)
    pexp = jnp.where(valid, pexp, 0.0)
    l_new = alpha * l_scr[:, 0:1] + jnp.sum(pexp, axis=-1, keepdims=True)
    acc_new = alpha * acc_scr[...] + jax.lax.dot_general(
        pexp, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
    acc_scr[...] = acc_new


def _decode_step_body(bt_ref, cp_ref, q_ref, k, v, pos_ref, refs, *,
                      pages_per_split: int, num_pages: int, window: int,
                      scale: float, with_scores: bool):
    """Shared split-K body for the f32 and int8 decode kernels. ``k``/``v``
    arrive as dequantized f32 (page, hd) tiles."""
    if with_scores:
        acc_ref, m_ref, l_ref, kn_ref, vn_ref, m_scr, l_scr, acc_scr = refs
    else:
        acc_ref, m_ref, l_ref, m_scr, l_scr, acc_scr = refs
    b = pl.program_id(0)
    sp = pl.program_id(2)
    i = pl.program_id(3)
    p = sp * pages_per_split + i                        # logical page slot
    pc = jnp.minimum(p, num_pages - 1)                  # clamped (padding)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32)                  # (G, hd)
    pos = pos_ref[...]                                  # (1, page) int32
    cur = cp_ref[b]
    # this step's slot holds a live page AND is not split padding
    mapped = (p < num_pages) & (bt_ref[b, pc] >= 0)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    valid = mapped & (pos >= 0) & (pos <= cur)
    if window > 0:
        valid &= pos > (cur - window)
    _flash_update(s, valid, v, m_scr, l_scr, acc_scr)

    if with_scores:
        # byproduct epilogue: per-token K/V norms of the tile already in
        # VMEM. Padding steps (p >= P) recompute slot P-1's tile (the DMA
        # clamps the same way) and rewrite identical values — no guard
        # needed. Masking/means happen wrapper-side.
        kn_ref[...] = _row_norms(k)
        vn_ref[...] = _row_norms(v)

    @pl.when(i == pages_per_split - 1)
    def _finalize():
        # UN-normalized split partials; the wrapper's combine step reduces
        # across splits (num_splits == 1 degenerates to plain normalization)
        acc_ref[...] = acc_scr[...]
        m_ref[...] = m_scr[...]
        l_ref[...] = l_scr[...]


def _row_norms(x):
    """(page, hd) f32 tile -> (1, page) per-token L2 norms, lane-major to
    match the (1, page) norm output block."""
    return jnp.sqrt(jnp.sum(x * x, axis=-1))[None, :]


def _paged_attn_kernel(bt_ref, cp_ref, q_ref, k_ref, v_ref, pos_ref,
                       *refs, pages_per_split: int, num_pages: int,
                       window: int, scale: float, with_scores: bool):
    """One (batch, kv_head, split, page_in_split) step.

    bt_ref  : (B, P) int32 block tables (scalar prefetch, SMEM)
    cp_ref  : (B,) int32 current decode positions (scalar prefetch, SMEM)
    q_ref   : (G, hd)      this kv-head's query group
    k_ref   : (page, hd)   one PHYSICAL page of keys (block-table indexed)
    v_ref   : (page, hd)   one physical page of values
    pos_ref : (1, page)    token positions of that physical page (-1 invalid)
    outputs : acc (G, hd), m (G, 128), l (G, 128) split partials (written on
              the split's last page step); with_scores adds kn/vn (1, page)
    scratch : m (G, 128), l (G, 128), acc (G, hd) f32
    """
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    _decode_step_body(bt_ref, cp_ref, q_ref, k, v, pos_ref, refs,
                      pages_per_split=pages_per_split, num_pages=num_pages,
                      window=window, scale=scale, with_scores=with_scores)


def _paged_attn_kernel_int8(bt_ref, cp_ref, q_ref, k_ref, v_ref, ks_ref,
                            vs_ref, pos_ref, *refs, pages_per_split: int,
                            num_pages: int, window: int, scale: float,
                            with_scores: bool):
    """int8 variant: K/V tiles arrive quantized; dequantization happens in
    VMEM (one multiply per tile) so HBM traffic is the int8 bytes + scales —
    the fused memory win the paper's future-work section points at. The
    fused epilogue norms are computed on the DEQUANTIZED tiles, so they
    match ``block_score`` of the dequantized pool.

    ks_ref, vs_ref: (1, page) f32 absmax scales for this physical page."""
    k = k_ref[...].astype(jnp.float32) * (ks_ref[0, :] / 127.0)[:, None]
    v = v_ref[...].astype(jnp.float32) * (vs_ref[0, :] / 127.0)[:, None]
    _decode_step_body(bt_ref, cp_ref, q_ref, k, v, pos_ref, refs,
                      pages_per_split=pages_per_split, num_pages=num_pages,
                      window=window, scale=scale, with_scores=with_scores)


def unit_rows(x):
    """(R, ..., n) -> (R, ..., 1, n): per-row operands whose (1, n) blocks
    then span the array's two trailing dims (Mosaic's block tiling rule
    for an n that is not a multiple of 128)."""
    return x.reshape(*x.shape[:-1], 1, x.shape[-1])


def _pool_index(bt_ref, b, p):
    """Physical page id for (request b, logical slot p); clamped so unmapped
    slots DMA pool page 0 (masked in the kernel body)."""
    return jnp.maximum(bt_ref[b, p], 0)


def combine_splits(acc, m, l):
    """Reduce split-K partial softmaxes to the final attention output.

    acc: (B, KV, S, G, hd) un-normalized partial values; m/l: (B, KV, S, G,
    lanes) split max / normalizer (lane-broadcast; lane 0 is read).
    -> (B, KV, G, hd) f32. Empty splits (m == NEG_INF, l == 0) contribute
    exactly 0; a fully-empty row divides 0 by the 1e-30 floor -> zeros,
    matching the single-chain kernel's finalize."""
    m = m[..., 0]                                       # (B, KV, S, G)
    l = l[..., 0]
    m_max = jnp.max(m, axis=2)                          # (B, KV, G)
    coef = jnp.exp(m - m_max[:, :, None, :])            # (B, KV, S, G)
    l_tot = jnp.sum(coef * l, axis=2)                   # (B, KV, G)
    o = jnp.sum(coef[..., None] * acc, axis=2)          # (B, KV, G, hd)
    return o / jnp.maximum(l_tot, 1e-30)[..., None]


def _split_grid(P: int, num_splits: int):
    S = max(1, min(int(num_splits), P))
    return S, -(-P // S)                                # (splits, pages/split)


def _decode_out_shapes(B, KV, S, G, hd, P, page, with_scores):
    shapes = [
        jax.ShapeDtypeStruct((B, KV, S, G, hd), jnp.float32),   # acc
        jax.ShapeDtypeStruct((B, KV, S, G, 128), jnp.float32),  # m
        jax.ShapeDtypeStruct((B, KV, S, G, 128), jnp.float32),  # l
    ]
    if with_scores:
        # norms carry a unit axis so each (1, page) block spans the array's
        # two trailing dims (Mosaic's block tiling rule)
        shapes += [jax.ShapeDtypeStruct((B, KV, P, 1, page), jnp.float32),
                   jax.ShapeDtypeStruct((B, KV, P, 1, page), jnp.float32)]
    return tuple(shapes)


def _decode_out_specs(G, hd, P, page, pps, with_scores):
    part = lambda b, h, sp, i, bt, cp: (b, h, sp, 0, 0)
    specs = [
        pl.BlockSpec((None, None, None, G, hd), part),
        pl.BlockSpec((None, None, None, G, 128), part),
        pl.BlockSpec((None, None, None, G, 128), part),
    ]
    if with_scores:
        norm = lambda b, h, sp, i, bt, cp: \
            (b, h, jnp.minimum(sp * pps + i, P - 1), 0, 0)
        specs += [pl.BlockSpec((None, None, None, 1, page), norm),
                  pl.BlockSpec((None, None, None, 1, page), norm)]
    return tuple(specs)


def _decode_call(kernel, q, k_pool, v_pool, scales, pos, block_table,
                 cur_pos, *, num_splits: int, return_scores: bool,
                 interpret: bool | None):
    """Shared pallas_call of the f32 and int8 decode kernels. ``scales``:
    () or the int8 pool's (k_scales, v_scales), each (KV, N_pool, page)."""
    B, KV, G, hd = q.shape
    page = pos.shape[1]
    P = block_table.shape[1]
    S, pps = _split_grid(P, num_splits)
    kernel = functools.partial(kernel, pages_per_split=pps, num_pages=P,
                               with_scores=return_scores)

    def page_of(b, sp, i, bt):
        return _pool_index(bt, b, jnp.minimum(sp * pps + i, P - 1))

    def kv_map(b, h, sp, i, bt, cp):
        return (h, page_of(b, sp, i, bt), 0, 0)

    # per-page rows come in as unit_rows, one (1, page) block per page
    meta_spec = pl.BlockSpec((None, None, 1, page), kv_map)
    pos_spec = pl.BlockSpec(
        (None, 1, page),
        lambda b, h, sp, i, bt, cp: (page_of(b, sp, i, bt), 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, S, pps),
        in_specs=[
            pl.BlockSpec((None, None, G, hd),
                         lambda b, h, sp, i, bt, cp: (b, h, 0, 0)),
            pl.BlockSpec((None, None, page, hd), kv_map),
            pl.BlockSpec((None, None, page, hd), kv_map),
            *[meta_spec for _ in scales],
            pos_spec,
        ],
        out_specs=_decode_out_specs(G, hd, P, page, pps, return_scores),
        scratch_shapes=[
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    res = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_decode_out_shapes(B, KV, S, G, hd, P, page, return_scores),
        interpret=interpret_mode(interpret),
    )(block_table, cur_pos.astype(jnp.int32), q, k_pool, v_pool,
      *map(unit_rows, scales), unit_rows(pos))
    out = combine_splits(res[0], res[1], res[2]).astype(q.dtype)
    if return_scores:
        return out, (res[3][:, :, :, 0], res[4][:, :, :, 0])
    return out


@functools.partial(jax.jit, static_argnames=(
    "window", "scale", "interpret", "num_splits", "return_scores"))
def paged_attention_kernel(q, k_pool, v_pool, pos, block_table, cur_pos, *,
                           window: int = 0, scale: float | None = None,
                           interpret: bool | None = None, num_splits: int = 1,
                           return_scores: bool = False):
    """q: (B, KV, G, hd); k_pool/v_pool: (KV, N_pool, page, hd);
    pos: (N_pool, page) int32; block_table: (B, P) int32;
    cur_pos: (B,) int32 -> (B, KV, G, hd) [, (kn, vn) each (B, KV, P, page)
    when ``return_scores``].

    ``num_splits``: split-K factor — the page walk runs as ceil(P/S)
    sequential steps per split instead of P, with a jnp combine across
    splits. 1 == the classic single-chain walk (bit-compatible combine).
    ``interpret``: None compiles on TPU and interprets elsewhere
    (:func:`repro.kernels.interpret_mode`)."""
    hd = q.shape[-1]
    scale = scale if scale is not None else hd ** -0.5
    kernel = functools.partial(_paged_attn_kernel, window=window, scale=scale)
    return _decode_call(kernel, q, k_pool, v_pool, (), pos, block_table,
                        cur_pos, num_splits=num_splits,
                        return_scores=return_scores, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "window", "scale", "interpret", "num_splits", "return_scores"))
def paged_attention_kernel_int8(q, k_pool, v_pool, k_scales, v_scales, pos,
                                block_table, cur_pos, *, window: int = 0,
                                scale: float | None = None,
                                interpret: bool | None = None,
                                num_splits: int = 1,
                                return_scores: bool = False):
    """q: (B, KV, G, hd) f32/bf16; k_pool/v_pool: (KV, N_pool, page, hd) int8;
    k_scales/v_scales: (KV, N_pool, page) f32; pos: (N_pool, page) int32;
    block_table: (B, P) int32. Split-K + fused epilogue as the f32 kernel;
    epilogue norms are of the dequantized tiles."""
    hd = q.shape[-1]
    scale = scale if scale is not None else hd ** -0.5
    kernel = functools.partial(_paged_attn_kernel_int8, window=window,
                               scale=scale)
    return _decode_call(kernel, q, k_pool, v_pool, (k_scales, v_scales), pos,
                        block_table, cur_pos, num_splits=num_splits,
                        return_scores=return_scores, interpret=interpret)
