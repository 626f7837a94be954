"""Pallas TPU paged-attention decode kernel with block-table indirection.

The TPU-native replacement for vLLM's CUDA PagedAttention (DESIGN.md §2):
one query token per request attends over the SHARED page pool, walking its
block table a block of pages at a time, with flash (online-softmax)
accumulation in VMEM scratch.

Blocked walk (DESIGN.md §8). The grid is (batch, kv_head, split,
block_in_split); each step covers ``ppb`` consecutive logical slots of the
row's block table (``decode_block_pages`` picks ``ppb`` from the shapes).
K/V stay in HBM (``memory_space=ANY``): the kernel copies the block's
physical pages into a ``(2, ppb, page, hd)`` VMEM buffer itself, one async
copy per page, double-buffered across grid steps. Step n first issues the
copies of step n+1 (the next block of this row and head, or the first block
of the next (b, h, split)) into the other half of the buffer, then waits
for its own, which step n-1 issued, and does ONE online-softmax update over
the whole block: ``s = q @ K_blockᵀ`` of shape (G, ppb·page) and a single
(G, ppb·page) @ (ppb·page, hd) product. A per-page grid step costs a fixed
~0.45 µs on a v5e, far more than its 4 KiB of K/V take to stream, so the
walk's time follows its step count, not its bytes.

What is not live costs nothing: an unmapped slot (bt < 0, including the
slots that pad P up to whole blocks) issues no DMA, and its half-buffer
pages are written with zeros instead, so neither scores nor the epilogue
norms ever read uninitialised VMEM. A block whose slots are all unmapped
skips the wait and the compute and writes zero norms. Short rows therefore
pay for their live pages only.

Masks. Per-token positions come in lane-dense, gathered through the block
table in the wrapper as ``(B, 1, P_pad·page)`` with -1 on unmapped slots,
one ``(1, ppb·page)`` block per step. So the kernel's masks are the same
as the per-page walk's: mapped (pos -1 where bt < 0), pos >= 0, pos <= cur
and the window. The mapped test matters: a freed physical page may already
hold ANOTHER request's live tokens.

Split-K (``num_splits``): the logical blocks are partitioned into S
independent chunks, each with its own (m, l, acc) flash state; the kernel
emits the UN-normalized partials per split and ``combine_splits`` (jnp, in
the wrapper) rescales them to a common max and normalizes:

    m* = max_s m_s;  o = Σ_s e^{m_s − m*}·acc_s / Σ_s e^{m_s − m*}·l_s

``num_splits=1`` is the single-chain walk (the combine degenerates to
``acc / max(l, eps)``). Empty splits emit m = NEG_INF, l = 0, acc = 0 and
drop out. On a one-core v5e the split axis runs serially like the others,
so splitting does not shorten the walk.

Fused score epilogue (``return_scores=True``): the K/V blocks are already
live in VMEM (dequantized for int8 pools), so the per-token L2 norms that
``kernels/block_score.py`` recomputes in a separate pass over the pool come
out as byproduct outputs kn/vn: (B, KV, P, page), written lane-dense as a
(1, ppb·page) block per step and reshaped by the wrapper (the wrapper-side
combine masks by block table + pos and reduces to the paper's Alg.1 page
score, see ``importance.page_scores_from_norms``). The standalone
``block_score`` kernel survives only as the parity oracle.

int8 pools: K/V pages copy as int8 and convert in VMEM; the per-token
absmax scales come in lane-dense like the positions and scale the scores
and the probabilities (``s·k_scale``, ``(p·v_scale) @ V``), which equals
attending over the dequantized pages.

Prefix sharing (DESIGN.md §7) needs no extra masking: a physical page
mapped under several block tables is always a COMPLETE prompt-prefix page
holding the SAME positions for every mapper, and every per-step quantity
is derived from the row's own bt[b, p]. Epilogue outputs are indexed by
LOGICAL slot, so two sharers of one physical page each write their own
copy of its norms.

Layout: the wrapper (ops.py) permutes the pool to (KV, N_pool, page, hd) so
each page is a contiguous (page, hd) tile — page 16 x head_dim 128 is a
whole bf16 tile. Block table and ``cur_pos`` ride in SMEM as scalar
prefetch (DESIGN.md §2); the block table is padded with -1 to whole blocks.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1e30

# Tokens per grid step of the decode walk, before the VMEM cap. A multiple
# of 128, so each step's per-token rows (positions, scales, norms) fill
# whole lanes.
BLOCK_TOKENS = 128
# VMEM for the double-buffered K and V pages of one block.
_BLOCK_VMEM_BYTES = 4 << 20
# the per-page loops below are unrolled: ppb is small and static, and the
# unrolled walk ran 12% faster on a v5e at decode-evict's shapes


def decode_block_pages(P: int, page: int, hd: int, dtype) -> int:
    """Pages per grid step of the decode walk over a P-slot block table.

    A multiple of the smallest page count whose tokens fill whole 128-lane
    rows, about ``BLOCK_TOKENS`` tokens, at most what ``_BLOCK_VMEM_BYTES``
    holds of double-buffered K and V pages, and no more whole lane units
    than P needs. The table is padded with unmapped slots to a multiple of
    the result."""
    unit = 128 // math.gcd(page, 128)
    lanes = -(-hd // 128) * 128
    per_unit = 2 * 2 * unit * page * lanes * jnp.dtype(dtype).itemsize
    units = max(1, min(BLOCK_TOKENS // (unit * page),
                       _BLOCK_VMEM_BYTES // per_unit,
                       -(-max(P, 1) // unit)))
    return units * unit


def decode_grid(P: int, page: int, hd: int, dtype, num_splits: int = 1):
    """The decode walk's geometry: (splits, blocks per split, pages per
    block); the grid is (B, KV, splits, blocks per split)."""
    S = max(1, min(int(num_splits), P))
    pps = -(-P // S)
    ppb = decode_block_pages(pps, page, hd, dtype)
    return S, -(-pps // ppb), ppb


def _flash_update(s, valid, v, m_scr, l_scr, acc_scr, v_scale=None):
    """One online-softmax update of the (m, l, acc) scratch state.

    s: (rows, n) scores; valid: (rows | 1, n) bool; v: (n, hd);
    v_scale: None or (1, n) per-token factors of v's rows.
    """
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_scr[:, 0:1]                              # (rows, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    pexp = jnp.exp(s - m_new)
    pexp = jnp.where(valid, pexp, 0.0)
    l_new = alpha * l_scr[:, 0:1] + jnp.sum(pexp, axis=-1, keepdims=True)
    if v_scale is not None:
        pexp = pexp * v_scale
    acc_new = alpha * acc_scr[...] + jax.lax.dot_general(
        pexp, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
    acc_scr[...] = acc_new


def _row_norms(x):
    """(n, hd) f32 -> (1, n) per-token L2 norms, lane-major to match the
    (1, n) norm output block."""
    return jnp.sqrt(jnp.sum(x * x, axis=-1))[None, :]


def _decode_kernel(bt_ref, cp_ref, q_ref, k_hbm, v_hbm, *refs, ppb: int,
                   bps: int, window: int, scale: float, quantized: bool,
                   with_scores: bool):
    """One (batch, kv_head, split, block_in_split) step.

    bt_ref : (B, P_pad) int32 block tables, -1 padded (scalar prefetch)
    cp_ref : (B,) int32 current decode positions (scalar prefetch)
    q_ref  : (G, hd) this kv-head's query group
    k_hbm, v_hbm : (KV, N_pool, page, hd) pools, left in HBM
    then, blocked (1, ppb·page) rows: int8 only ks, vs (f32 absmax scales
    of the block's tokens); pos (int32, -1 unmapped)
    outputs: acc (G, hd), m (G, 128), l (G, 128) split partials (written
    on the split's last block); with_scores adds kn, vn (1, ppb·page)
    scratch: K and V buffers (2, ppb, page, hd), their DMA semaphores
    (2, 2), m (G, 128), l (G, 128), acc (G, hd) f32
    """
    n_rows = 3 if quantized else 1
    rows, refs = refs[:n_rows], refs[n_rows:]
    n_out = 5 if with_scores else 3
    outs, refs = refs[:n_out], refs[n_out:]
    kbuf, vbuf, sems, m_scr, l_scr, acc_scr = refs
    acc_ref, m_ref, l_ref = outs[:3]
    pos_ref = rows[-1]

    b, h, sp, i = (pl.program_id(a) for a in range(4))
    nb, nh, ns = (pl.num_programs(a) for a in range(3))
    step = ((b * nh + h) * ns + sp) * bps + i
    slot = jax.lax.rem(step, 2)
    page, hd = kbuf.shape[2], kbuf.shape[3]

    def copies(bb, hh, blk, sl, j):
        src = jnp.maximum(bt_ref[bb, blk * ppb + j], 0)
        return (pltpu.make_async_copy(k_hbm.at[hh, src], kbuf.at[sl, j],
                                      sems.at[0, sl]),
                pltpu.make_async_copy(v_hbm.at[hh, src], vbuf.at[sl, j],
                                      sems.at[1, sl]))

    def fetch(bb, hh, blk, sl):
        zeros = jnp.zeros((page, hd), kbuf.dtype)

        def one(j, carry):
            mapped = bt_ref[bb, blk * ppb + j] >= 0

            @pl.when(mapped)
            def _start():
                for c in copies(bb, hh, blk, sl, j):
                    c.start()

            @pl.when(jnp.logical_not(mapped))
            def _zero():
                kbuf[sl, j] = zeros
                vbuf[sl, j] = zeros
            return carry
        jax.lax.fori_loop(0, ppb, one, 0, unroll=True)

    @pl.when(step == 0)
    def _first():
        fetch(b, h, sp * bps + i, slot)

    # the next step in grid order (block, then split, head, row) fetches
    # into the other half while this one computes
    last = i == bps - 1
    n_i = jnp.where(last, 0, i + 1)
    n_sp = jnp.where(last, sp + 1, sp)
    wrap = n_sp == ns
    n_sp = jnp.where(wrap, 0, n_sp)
    n_h = jnp.where(wrap, h + 1, h)
    wrap = n_h == nh
    n_h = jnp.where(wrap, 0, n_h)
    n_b = jnp.where(wrap, b + 1, b)

    @pl.when(n_b < nb)
    def _prefetch():
        fetch(n_b, n_h, n_sp * bps + n_i, 1 - slot)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    blk = sp * bps + i
    live = jax.lax.fori_loop(
        0, ppb, lambda j, acc: acc | (bt_ref[b, blk * ppb + j] >= 0), False,
        unroll=True)

    @pl.when(live)
    def _attend():
        def one(j, carry):
            @pl.when(bt_ref[b, blk * ppb + j] >= 0)
            def _wait():
                for c in copies(b, h, blk, slot, j):
                    c.wait()
            return carry
        jax.lax.fori_loop(0, ppb, one, 0, unroll=True)

        k = kbuf[slot].astype(jnp.float32).reshape(ppb * page, hd)
        v = vbuf[slot].astype(jnp.float32).reshape(ppb * page, hd)
        q = q_ref[...].astype(jnp.float32)              # (G, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        ks = vs = None
        if quantized:
            ks = rows[0][...] * (1.0 / 127.0)           # (1, ppb·page)
            vs = rows[1][...] * (1.0 / 127.0)
            s = s * ks
        pos = pos_ref[...]                              # (1, ppb·page)
        cur = cp_ref[b]
        valid = (pos >= 0) & (pos <= cur)
        if window > 0:
            valid &= pos > (cur - window)
        _flash_update(s, valid, v, m_scr, l_scr, acc_scr, v_scale=vs)
        if with_scores:
            kn, vn = _row_norms(k), _row_norms(v)
            if quantized:
                kn, vn = kn * ks, vn * vs
            outs[3][...] = kn
            outs[4][...] = vn

    if with_scores:
        @pl.when(jnp.logical_not(live))
        def _no_norms():
            outs[3][...] = jnp.zeros_like(outs[3])
            outs[4][...] = jnp.zeros_like(outs[4])

    @pl.when(i == bps - 1)
    def _finalize():
        # UN-normalized split partials; the wrapper's combine step reduces
        # across splits (num_splits == 1 degenerates to plain normalization)
        acc_ref[...] = acc_scr[...]
        m_ref[...] = m_scr[...]
        l_ref[...] = l_scr[...]


def unit_rows(x):
    """(R, ..., n) -> (R, ..., 1, n): per-row operands whose (1, n) blocks
    then span the array's two trailing dims (Mosaic's block tiling rule
    for an n that is not a multiple of 128)."""
    return x.reshape(*x.shape[:-1], 1, x.shape[-1])


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


def _decode_call(q, k_pool, v_pool, scales, pos, block_table, cur_pos, *,
                 window: int, scale: float, num_splits: int,
                 return_scores: bool, interpret: bool | None):
    """Shared pallas_call of the bf16/f32 and int8 decode walks.
    ``scales``: () or the int8 pool's (k_scales, v_scales), each
    (KV, N_pool, page)."""
    B, KV, G, hd = q.shape
    page = pos.shape[1]
    P = block_table.shape[1]
    lanes = -(-hd // 128) * 128
    if lanes != hd:
        # a page copies as whole 128-lane rows: zero lanes add nothing to
        # scores, values or norms (and fuse into the copy that lays the
        # pool out by head, ops._pool_layout)
        pad = [(0, 0)] * 3 + [(0, lanes - hd)]
        q, k_pool, v_pool = (jnp.pad(x, pad) for x in (q, k_pool, v_pool))
    S, bps, ppb = decode_grid(P, page, hd, k_pool.dtype, num_splits)
    P_pad, T = S * bps * ppb, ppb * page
    bt = jnp.pad(block_table, ((0, 0), (0, P_pad - P)), constant_values=-1)
    mapped = (bt >= 0)[..., None]                       # (B, P_pad, 1)
    phys = jnp.maximum(bt, 0)
    # per-token rows through the block table, lane-dense: (B, 1, P_pad·page)
    pos_rows = jnp.where(mapped, jnp.take(pos, phys, axis=0), -1)
    pos_rows = pos_rows.reshape(B, 1, P_pad * page)
    scale_rows = [
        jnp.where(mapped[:, None], jnp.moveaxis(jnp.take(sc, phys, axis=1),
                                                0, 1), 0.0)
        .reshape(B, KV, 1, P_pad * page) for sc in scales]

    blocks = lambda b, h, sp, i, bt, cp: (b, h, 0, sp * bps + i)
    part = lambda b, h, sp, i, bt, cp: (b, h, sp, 0, 0)
    out_shape = [jax.ShapeDtypeStruct((B, KV, S, G, lanes), jnp.float32),
                 jax.ShapeDtypeStruct((B, KV, S, G, 128), jnp.float32),
                 jax.ShapeDtypeStruct((B, KV, S, G, 128), jnp.float32)]
    out_specs = [pl.BlockSpec((None, None, None, G, lanes), part),
                 pl.BlockSpec((None, None, None, G, 128), part),
                 pl.BlockSpec((None, None, None, G, 128), part)]
    if return_scores:
        out_shape += [jax.ShapeDtypeStruct((B, KV, 1, P_pad * page),
                                           jnp.float32)] * 2
        out_specs += [pl.BlockSpec((None, None, 1, T), blocks)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, S, bps),
        in_specs=[
            pl.BlockSpec((None, None, G, lanes),
                         lambda b, h, sp, i, bt, cp: (b, h, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec(memory_space=pltpu.HBM),
            *[pl.BlockSpec((None, None, 1, T), blocks) for _ in scales],
            pl.BlockSpec((None, 1, T),
                         lambda b, h, sp, i, bt, cp: (b, 0, sp * bps + i)),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page, lanes), k_pool.dtype),
            pltpu.VMEM((2, ppb, page, lanes), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, lanes), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel, ppb=ppb, bps=bps, window=window, scale=scale,
        quantized=bool(scales), with_scores=return_scores)
    res = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        # steps hand DMAs to their successor: the grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 4),
        interpret=interpret_mode(interpret),
    )(bt, cur_pos.astype(jnp.int32), q, k_pool, v_pool, *scale_rows,
      pos_rows)
    out = combine_splits(res[0], res[1], res[2])[..., :hd].astype(q.dtype)
    if return_scores:
        kn, vn = (r.reshape(B, KV, P_pad, page)[:, :, :P] for r in res[3:])
        return out, (kn, vn)
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

    ``num_splits``: split-K factor — the block walk is cut into S chains
    with a jnp combine across them. 1 == the single-chain walk.
    ``interpret``: None compiles on TPU and interprets elsewhere
    (:func:`repro.kernels.interpret_mode`)."""
    hd = q.shape[-1]
    scale = scale if scale is not None else hd ** -0.5
    return _decode_call(q, k_pool, v_pool, (), pos, block_table, cur_pos,
                        window=window, scale=scale, num_splits=num_splits,
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
    block_table: (B, P) int32. Split-K + fused epilogue as the bf16 walk;
    epilogue norms are of the dequantized pages."""
    hd = q.shape[-1]
    scale = scale if scale is not None else hd ** -0.5
    return _decode_call(q, k_pool, v_pool, (k_scales, v_scales), pos,
                        block_table, cur_pos, window=window, scale=scale,
                        num_splits=num_splits, return_scores=return_scores,
                        interpret=interpret)
