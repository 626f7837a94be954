"""Pallas TPU flash-attention kernels for the prefill hot paths.

Three kernels:

``flash_attention_kernel`` — contiguous causal flash (train / offline
whole-prompt prefill). Standard online-softmax flash with GQA support and
optional sliding window. Grid: (batch, q_head, q_block, kv_block) with kv
minor-most — (m, l, acc) scratch accumulates across kv blocks. Causally-
skippable kv blocks are skipped with ``pl.when`` (block never contributes
compute); with a sliding window, out-of-window blocks are likewise skipped
— the triangle-skipping the blocked pure-jnp path cannot express (it masks
but still multiplies; see EXPERIMENTS.md §Perf).

``paged_flash_prefill_kernel`` — CHUNKED prefill against the shared page
pool (the unified-step hot path, DESIGN.md §6) with G-FOLD fetch
(DESIGN.md §8): Q is a contiguous (T, hd) chunk per request, K/V are
PHYSICAL pool pages gathered via the scalar-prefetched block table exactly
like the decode kernel (``paged_attention.py``). The grid is (B, KV, P) —
one step per KV head group, NOT per Q head — and the G query heads of the
group ride folded into one (G*T, hd) query tile (row g*T + t is head g,
chunk token t). Each physical K/V page is therefore DMA'd ONCE per KV-head
group and reused across all G query heads, cutting prefill HBM traffic by
~G× on GQA configs versus the per-Q-head fetch. This retires the PR 2
follow-up note; the old per-Q-head instantiation survives as
``paged_flash_prefill_kernel_per_qhead`` (the bit-parity oracle and the
before/after benchmark baseline — per-row dot/exp order is unchanged by
the fold, so outputs are bitwise identical).

Unmapped slots clamp to pool page 0 and are masked in-kernel off the same
scalar ref — a freed physical page may already hold ANOTHER request's live
tokens. Masking is by token position: kv pos <= q pos (+ optional window),
so intra-chunk causality falls out of write-then-attend; padding queries
(q_pos < 0) mask everything and emit zeros.

Fused score epilogue (``return_scores=True``, G-fold kernel only): per-
token K/V norms of each fetched page tile come out as byproduct outputs
kn/vn (B, KV, P, page), exactly as the decode kernel's epilogue
(DESIGN.md §8) — chunk-boundary eviction then reads the paper's Alg.1
page scores for free instead of re-walking the pool with ``block_score``.

Prefix sharing (DESIGN.md §7): an adopted page is a complete prompt-prefix
page whose positions are [slot*page, (slot+1)*page) for EVERY request
mapping it, and an adopting row's first chunk starts at q_pos ==
shared_tokens — so the kv-pos <= q-pos mask attends shared pages exactly as
if the row had prefilled them itself. No kernel change; the only retired
assumption is block-table-row disjointness, which neither kernel relied on.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode
from repro.kernels.paged_attention import unit_rows

NEG_INF = -1e30


def _pool_index(bt_ref, b, p):
    """Physical page id for (request b, logical slot p); clamped so unmapped
    slots DMA pool page 0 (masked in the kernel body)."""
    return jnp.maximum(bt_ref[b, p], 0)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  num_kv_blocks: int, block_q: int, block_k: int,
                  window: int, scale: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    # causal: kv block relevant iff k_start <= q_end; window: skip blocks
    # entirely below the window of every query row in the block
    relevant = k_start <= q_start + block_q - 1
    if window > 0:
        relevant &= (k_start + block_k - 1) > (q_start - window)

    @pl.when(relevant)
    def _compute():
        q = q_ref[...].astype(jnp.float32)                  # (bq, hd)
        k = k_ref[...].astype(jnp.float32)                  # (bk, hd)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos <= qpos
        if window > 0:
            mask &= kpos > (qpos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        l_new = alpha * l_scr[:, 0:1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = alpha * acc_scr[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
        acc_scr[...] = acc_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        o_ref[...] = (acc_scr[...] /
                      jnp.maximum(l_scr[:, 0:1], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "window", "scale", "block_q", "block_k", "interpret"))
def flash_attention_kernel(q, k, v, *, window: int = 0, scale: float | None = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool | None = None):
    """Causal GQA flash attention.

    q: (B, S, H, hd); k, v: (B, S, KV, hd) -> (B, S, H, hd).
    S must be a multiple of the block sizes (pad upstream).
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = scale if scale is not None else hd ** -0.5
    bq = min(block_q, S)
    bk = min(block_k, S)
    if S % bq or S % bk:
        raise ValueError(f"sequence {S} is not a multiple of the blocks "
                         f"({bq}, {bk}); pad upstream")
    nq, nk = S // bq, S // bk

    # layout: heads major so blocks are (block, hd) tiles
    qT = jnp.swapaxes(q, 1, 2)                              # (B, H, S, hd)
    kT = jnp.swapaxes(k, 1, 2)                              # (B, KV, S, hd)
    vT = jnp.swapaxes(v, 1, 2)

    kernel = functools.partial(_flash_kernel, num_kv_blocks=nk, block_q=bq,
                               block_k=bk, window=window, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((None, None, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((None, None, bk, hd),
                         lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((None, None, bk, hd),
                         lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, bq, hd),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(qT, kT, vT)
    return jnp.swapaxes(out, 1, 2)


# ---------------------------------------------------------------------------
# paged chunked prefill (block-table indirection, scalar prefetch)
# ---------------------------------------------------------------------------

def _row_spec(n, row_of):
    """BlockSpec of one (1, n) row of a :func:`unit_rows` operand, row
    chosen by ``row_of(b, h, p, bt)``."""
    return pl.BlockSpec((None, 1, n),
                        lambda b, h, p, bt: (row_of(b, h, p, bt), 0, 0))


def _paged_prefill_kernel(bt_ref, q_ref, k_ref, v_ref, qpos_ref, kpos_ref,
                          *refs, num_pages: int, window: int, scale: float,
                          with_scores: bool):
    """One (batch, head-group, logical_page) step. Shared by the G-fold
    instantiation (rows = G*T query rows of one KV-head group) and the
    legacy per-Q-head one (rows = T) — the body only sees a (rows, hd)
    query tile; per-row masking makes the fold transparent.

    bt_ref   : (B, P) int32 block tables (scalar prefetch, SMEM)
    q_ref    : (rows, hd)  query tile
    k_ref    : (page, hd)  one PHYSICAL page of keys (block-table indexed)
    v_ref    : (page, hd)  one physical page of values
    qpos_ref : (1, rows)   per-row token positions (-1 == padding query)
    kpos_ref : (1, page)   token positions of that physical page (-1 invalid)
    outputs  : o (rows, hd) (written on the last page step); with_scores
               adds kn/vn (1, page) byproduct norm tiles
    scratch  : m (rows, 128), l (rows, 128), acc (rows, hd) f32
    """
    if with_scores:
        o_ref, kn_ref, vn_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32)                  # (rows, hd)
    k = k_ref[...].astype(jnp.float32)                  # (page, hd)
    v = v_ref[...].astype(jnp.float32)
    qpos = qpos_ref[0, :]                               # (rows,)
    kpos = kpos_ref[0, :]                               # (page,)
    mapped = bt_ref[b, p] >= 0

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    # (rows, page): pool slot live AND causally visible from this query row
    valid = mapped & (kpos[None, :] >= 0) & (qpos[:, None] >= 0) & \
        (kpos[None, :] <= qpos[:, None])
    if window > 0:
        valid &= kpos[None, :] > (qpos[:, None] - window)
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

    if with_scores:
        # byproduct epilogue (DESIGN.md §8): per-token norms of the K/V tile
        # already in VMEM; each (b, kv, p) block is written once per group
        kn_ref[0, :] = jnp.sqrt(jnp.sum(k * k, axis=-1))
        vn_ref[0, :] = jnp.sqrt(jnp.sum(v * v, axis=-1))

    @pl.when(p == num_pages - 1)
    def _finalize():
        # padding queries have l == 0 -> emit zeros, not NaN
        o_ref[...] = (acc_scr[...] /
                      jnp.maximum(l_scr[:, 0:1], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "window", "scale", "interpret", "return_scores"))
def paged_flash_prefill_kernel(q, k_pool, v_pool, pos, block_table, q_pos, *,
                               window: int = 0, scale: float | None = None,
                               interpret: bool | None = None,
                               return_scores: bool = False):
    """Chunked-prefill attention over the shared page pool, G-fold fetch.

    q: (B, T, H, hd) — a contiguous chunk of queries per request (RoPE'd);
    k_pool/v_pool: (KV, N_pool, page, hd); pos: (N_pool, page) int32;
    block_table: (B, P) int32; q_pos: (B, T) int32 (-1 == padding)
    -> (B, T, H, hd) [, (kn, vn) each (B, KV, P, page) when
    ``return_scores``]. The chunk's own K/V must already be in the pool
    (write-then-attend).

    Grid is (B, KV, P): each physical K/V page is DMA'd once per KV-head
    GROUP; the group's G query heads are folded into one (G*T, hd) query
    tile (row g*T + t <-> head kv*G + g, token t) and reuse the tile —
    prefill HBM traffic is ~G× lower than the retired per-Q-head fetch
    (kept as :func:`paged_flash_prefill_kernel_per_qhead`, the bit-parity
    oracle). T == 1 callers should still use the decode kernel — its
    split-K walk shortens the serial chain (transformer dispatches so)."""
    B, T, H, hd = q.shape
    KV = k_pool.shape[0]
    G = H // KV
    page = k_pool.shape[2]
    P = block_table.shape[1]
    scale = scale if scale is not None else hd ** -0.5
    kernel = functools.partial(_paged_prefill_kernel, num_pages=P,
                               window=window, scale=scale,
                               with_scores=return_scores)

    def kv_map(b, h, p, bt):
        return (h, _pool_index(bt, b, p), 0, 0)

    # fold heads: (B, T, H, hd) -> (B, H, T, hd) -> (B, KV, G*T, hd);
    # row g*T + t of group kv is (head kv*G + g, chunk token t)
    qf = jnp.swapaxes(q, 1, 2).reshape(B, KV, G * T, hd)
    qpos_f = jnp.tile(q_pos, (1, G))                        # (B, G*T)

    out_specs = [pl.BlockSpec((None, None, G * T, hd),
                              lambda b, h, p, bt: (b, h, 0, 0))]
    out_shapes = [jax.ShapeDtypeStruct((B, KV, G * T, hd), q.dtype)]
    if return_scores:
        # unit axis: each (1, page) norm block spans the trailing dims
        norm = lambda b, h, p, bt: (b, h, p, 0, 0)
        out_specs += [pl.BlockSpec((None, None, None, 1, page), norm),
                      pl.BlockSpec((None, None, None, 1, page), norm)]
        out_shapes += [
            jax.ShapeDtypeStruct((B, KV, P, 1, page), jnp.float32),
            jax.ShapeDtypeStruct((B, KV, P, 1, page), jnp.float32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KV, P),
        in_specs=[
            pl.BlockSpec((None, None, G * T, hd),
                         lambda b, h, p, bt: (b, h, 0, 0)),
            pl.BlockSpec((None, None, page, hd), kv_map),
            pl.BlockSpec((None, None, page, hd), kv_map),
            _row_spec(G * T, lambda b, h, p, bt: b),
            _row_spec(page, lambda b, h, p, bt: _pool_index(bt, b, p)),
        ],
        out_specs=tuple(out_specs),
        scratch_shapes=[
            pltpu.VMEM((G * T, 128), jnp.float32),
            pltpu.VMEM((G * T, 128), jnp.float32),
            pltpu.VMEM((G * T, hd), jnp.float32),
        ],
    )
    res = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=tuple(out_shapes),
        interpret=interpret_mode(interpret),
    )(block_table, qf, k_pool, v_pool, unit_rows(qpos_f), unit_rows(pos))
    out = res[0]
    # unfold: (B, KV, G*T, hd) -> (B, KV, G, T, hd) -> (B, T, H, hd)
    out = jnp.swapaxes(out.reshape(B, KV * G, T, hd), 1, 2)
    if return_scores:
        return out, (res[1][:, :, :, 0], res[2][:, :, :, 0])
    return out


@functools.partial(jax.jit, static_argnames=("window", "scale", "interpret"))
def paged_flash_prefill_kernel_per_qhead(q, k_pool, v_pool, pos, block_table,
                                         q_pos, *, window: int = 0,
                                         scale: float | None = None,
                                         interpret: bool | None = None):
    """The retired per-Q-head instantiation — grid (B, H, P), each physical
    page DMA'd once per Q HEAD (G× the G-fold kernel's traffic on GQA).
    Kept as the bit-parity oracle for the fold (same kernel body, per-row
    math identical) and the before/after baseline in benchmarks/kernels.py.
    Signature/semantics match :func:`paged_flash_prefill_kernel`."""
    B, T, H, hd = q.shape
    KV = k_pool.shape[0]
    G = H // KV
    page = k_pool.shape[2]
    P = block_table.shape[1]
    scale = scale if scale is not None else hd ** -0.5
    kernel = functools.partial(_paged_prefill_kernel, num_pages=P,
                               window=window, scale=scale, with_scores=False)

    def kv_map(b, h, p, bt):
        return (h // G, _pool_index(bt, b, p), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, P),
        in_specs=[
            pl.BlockSpec((None, None, T, hd), lambda b, h, p, bt: (b, h, 0, 0)),
            pl.BlockSpec((None, None, page, hd), kv_map),
            pl.BlockSpec((None, None, page, hd), kv_map),
            _row_spec(T, lambda b, h, p, bt: b),
            _row_spec(page, lambda b, h, p, bt: _pool_index(bt, b, p)),
        ],
        out_specs=pl.BlockSpec((None, None, T, hd),
                               lambda b, h, p, bt: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((T, 128), jnp.float32),
            pltpu.VMEM((T, 128), jnp.float32),
            pltpu.VMEM((T, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, T, hd), q.dtype),
        interpret=interpret_mode(interpret),
    )(block_table, jnp.swapaxes(q, 1, 2), k_pool, v_pool, unit_rows(q_pos),
      unit_rows(pos))
    return jnp.swapaxes(out, 1, 2)
