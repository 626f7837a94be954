"""Kernel perf pass parity (DESIGN.md §8).

Three coordinated optimizations, each pinned to an oracle:

* split-K flash-decode — the logical-page walk partitioned into independent
  flash-state chunks whose un-normalized partial softmaxes are combined
  host-side. Splits {1,2,4,8} x {f32,int8} must match the dense reference
  (and split=1) on CHURNED pools: caches decode-traced past their budget so
  freed-and-reallocated physical pages sit behind the block tables.
* G-fold prefill fetch — the paged prefill grid walks (B, KV, P) instead of
  (B, H, P), DMA-ing each K/V page once per KV-head group. The fold only
  rearranges which rows share a tile; per-row math is untouched, so the
  result is BIT-identical to the retired per-Q-head instantiation
  (``paged_flash_prefill_kernel_per_qhead``, kept as the oracle).
* fused eviction-score epilogue — decode and prefill kernels emit per-page
  K/V norm statistics as byproducts; ``ops`` reduces them to Alg.1 page
  scores that must match the standalone ``block_score`` pass
  (``ops.page_scores``) to 1e-4, including on CoW-shared prefix pages.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.flash_prefill import (
    paged_flash_prefill_kernel,
    paged_flash_prefill_kernel_per_qhead,
)
from repro.kernels.paged_attention import (
    BLOCK_TOKENS,
    decode_block_pages,
    paged_attention_kernel,
    paged_attention_kernel_int8,
)

from tests.test_block_table_kernel import (
    _dense_reference,
    _driven_cache,
    _holed_cache,
)
from tests.test_prefix_sharing import _adopt, _filled_cache

SPLITS = [1, 2, 4, 8]

# reduced GQA geometries of the two assigned grouped-query archs
# (arch tag, KV heads, group size G)
GQA_CONFIGS = [("mixtral-8x7b", 1, 4), ("gemma3-27b", 2, 2)]


# ---------------------------------------------------------------------------
# split-K flash-decode
# ---------------------------------------------------------------------------

def _scene(scene, policy, dtype, seed=0):
    """A churned pool: a decode trace (``driven``) or the 73-slot table with
    eviction holes and a wholly unmapped block (``holed``)."""
    if scene == "holed":
        return _holed_cache(dtype, seed=seed)
    return _driven_cache(policy, 8, dtype, seed=seed)


# (splits, scene, window): the driven cases keep their ids
DECODE_CASES = [pytest.param(s, "driven", 0, id=str(s)) for s in SPLITS] + [
    pytest.param(s, "holed", w, id=f"{s}-holed" + ("-window" if w else ""))
    for w in (0, 40) for s in (1, 4)]


@pytest.mark.parametrize("dtype", ["float32", "int8"])
@pytest.mark.parametrize("splits,scene,window", DECODE_CASES)
def test_splitk_decode_matches_dense_ref(splits, scene, window, dtype):
    """Every split count reproduces the dense oracle on a churned pool
    (freed + reallocated pages behind the block table; for ``holed``, a
    table that is not a whole number of decode blocks, holes inside a
    block and a block with no page mapped), with and without a window."""
    cache, steps = _scene(scene, "paged_eviction", dtype)
    B, KV, hd, G = 2, 2, 64, 2
    q = jax.random.normal(jax.random.PRNGKey(11), (B, KV * G, hd))
    cur = jnp.full((B,), steps - 1, jnp.int32)
    out = np.asarray(
        ops.paged_attention(q, cache, cur_pos=cur, window=window,
                            num_splits=splits), np.float32)
    exp = np.asarray(_dense_reference(q, cache, cur, window=window),
                     np.float32)
    tol = 1e-4 if dtype == "float32" else 5e-4
    np.testing.assert_allclose(out, exp, atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_splitk_decode_split_invariant(dtype):
    """All split counts agree with split=1 to float accumulation noise —
    the combine is a pure reassociation of the same flash reduction."""
    cache, steps = _driven_cache("streaming_llm", 8, dtype, seed=5)
    q = jax.random.normal(jax.random.PRNGKey(13), (2, 4, 64))
    cur = jnp.full((2,), steps - 1, jnp.int32)
    base = np.asarray(ops.paged_attention(q, cache, cur_pos=cur,
                                          num_splits=1), np.float32)
    for s in SPLITS[1:]:
        out = np.asarray(ops.paged_attention(q, cache, cur_pos=cur,
                                             num_splits=s), np.float32)
        np.testing.assert_allclose(out, base, atol=2e-5, rtol=2e-5,
                                   err_msg=f"splits={s}")


def test_splitk_decode_windowed():
    """Split boundaries compose with the sliding-window mask."""
    cache, steps = _driven_cache("paged_eviction", 8, "float32", seed=7)
    q = jax.random.normal(jax.random.PRNGKey(17), (2, 4, 64))
    cur = jnp.full((2,), steps - 1, jnp.int32)
    for s in (1, 4):
        out = np.asarray(ops.paged_attention(q, cache, cur_pos=cur,
                                             window=8, num_splits=s))
        assert np.isfinite(out).all()
        if s == 1:
            base = out
        else:
            np.testing.assert_allclose(out, base, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("P", [1, 7, 60, 73])
def test_decode_block_pages_from_shapes(P):
    """Pages per decode block: at least one, lane-aligned (a block's tokens
    fill whole 128-lane rows), padding P by less than one block, and the
    double-buffered K/V block within its VMEM cap."""
    for page, hd, dtype in ((16, 128, jnp.bfloat16), (8, 64, jnp.float32),
                            (32, 128, jnp.int8), (16, 256, jnp.float32)):
        ppb = decode_block_pages(P, page, hd, dtype)
        assert ppb >= 1 and (ppb * page) % 128 == 0
        assert -(-P // ppb) * ppb - P < ppb
        assert 4 * ppb * page * max(hd, 128) * jnp.dtype(dtype).itemsize \
            <= 4 << 20
    # the served geometry: BLOCK_TOKENS per block once P allows it
    assert decode_block_pages(73, 16, 128, jnp.bfloat16) * 16 == BLOCK_TOKENS


def test_holed_scene_covers_block_edges():
    """The ``holed`` scene holds what the blocked walk must get right at the
    decode block size: P not a multiple of it, a hole inside a live block,
    and a block with no page mapped before the row's last live block."""
    cache, _ = _holed_cache("float32")
    B, P = cache.block_table.shape
    ppb = decode_block_pages(P, 16, 64, jnp.float32)
    assert P % ppb
    nblk = -(-P // ppb)
    bt = np.full((B, nblk * ppb), -1)
    bt[:, :P] = np.asarray(cache.block_table)
    mapped = (bt >= 0).reshape(B, nblk, ppb)
    live = mapped.any(-1)
    assert (live & ~mapped.all(-1)).any()
    assert (~live[0, :np.nonzero(live[0])[0][-1]]).any()


# ---------------------------------------------------------------------------
# G-fold prefill fetch
# ---------------------------------------------------------------------------

def _gqa_pool(key, B, KV, G, hd, P, T, page=8):
    """Synthetic fully-churned prefill scene: pool + block table with an
    unmapped slot per row, plus chunk queries with one padding row."""
    ks = jax.random.split(key, 5)
    N = B * P + 1
    k_pool = jax.random.normal(ks[0], (KV, N, page, hd))
    v_pool = jax.random.normal(ks[1], (KV, N, page, hd))
    pos = jnp.broadcast_to(jnp.arange(page, dtype=jnp.int32)[None],
                           (N, page)) + \
        jax.random.randint(ks[2], (N, 1), 0, 3) * page
    bt = jax.random.permutation(ks[3], N - 1)[:B * P] \
        .reshape(B, P).astype(jnp.int32)
    bt = bt.at[:, P - 1].set(-1)                     # unmapped slot per row
    q = jax.random.normal(ks[4], (B, T, KV * G, hd))
    q_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None],
                             (B, T)) + 2 * page
    q_pos = q_pos.at[0, T - 1].set(-1)               # padding query
    return q, k_pool, v_pool, pos, bt, q_pos


@pytest.mark.parametrize("arch,KV,G", GQA_CONFIGS)
def test_gfold_bit_parity_with_per_qhead_kernel(arch, KV, G):
    """The G-fold grid is BIT-identical to the per-Q-head oracle on the
    reduced GQA geometry of each assigned grouped-query arch."""
    q, k_pool, v_pool, pos, bt, q_pos = _gqa_pool(
        jax.random.PRNGKey(hash(arch) % 2**31), B=2, KV=KV, G=G, hd=64,
        P=3, T=8)
    folded = paged_flash_prefill_kernel(q, k_pool, v_pool, pos, bt, q_pos)
    per_qhead = paged_flash_prefill_kernel_per_qhead(
        q, k_pool, v_pool, pos, bt, q_pos)
    np.testing.assert_array_equal(np.asarray(folded), np.asarray(per_qhead))


def test_gfold_bit_parity_windowed():
    q, k_pool, v_pool, pos, bt, q_pos = _gqa_pool(
        jax.random.PRNGKey(23), B=1, KV=2, G=2, hd=64, P=4, T=8)
    folded = paged_flash_prefill_kernel(q, k_pool, v_pool, pos, bt, q_pos,
                                        window=12)
    per_qhead = paged_flash_prefill_kernel_per_qhead(
        q, k_pool, v_pool, pos, bt, q_pos, window=12)
    np.testing.assert_array_equal(np.asarray(folded), np.asarray(per_qhead))


@pytest.mark.parametrize("arch,KV,G", GQA_CONFIGS)
def test_gfold_on_live_pool_matches_dense_ref(arch, KV, G):
    """Decode-path cross-check on a REAL churned cache: the prefill kernel
    evaluated on a single-token chunk equals the decode dense oracle."""
    cache, steps = _driven_cache("paged_eviction", 8, "float32",
                                 KV=KV, seed=2)
    B, hd = 2, 64
    q = jax.random.normal(jax.random.PRNGKey(29), (B, 1, KV * G, hd))
    q_pos = jnp.full((B, 1), steps - 1, jnp.int32)
    out = ops.paged_prefill_attention(q, cache, q_pos=q_pos)
    exp = _dense_reference(q[:, 0], cache,
                           jnp.full((B,), steps - 1, jnp.int32))
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(exp),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# fused eviction-score epilogue
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "int8"])
@pytest.mark.parametrize("splits,scene", [
    pytest.param(s, sc, id=str(s) + ("-holed" if sc == "holed" else ""))
    for sc in ("driven", "holed") for s in (1, 4)])
def test_fused_decode_scores_match_block_score_oracle(splits, scene, dtype):
    cache, steps = _scene(scene, "paged_eviction", dtype, seed=4)
    q = jax.random.normal(jax.random.PRNGKey(31), (2, 4, 64))
    cur = jnp.full((2,), steps - 1, jnp.int32)
    plain = ops.paged_attention(q, cache, cur_pos=cur, num_splits=splits)
    out, scores = ops.paged_attention(q, cache, cur_pos=cur,
                                      num_splits=splits, return_scores=True)
    # the epilogue must not perturb the attention output
    np.testing.assert_array_equal(np.asarray(out), np.asarray(plain))
    oracle = ops.page_scores(cache)
    np.testing.assert_allclose(np.asarray(scores), np.asarray(oracle),
                               atol=1e-4, rtol=1e-4)
    # slots the walk never fetched carry zero norms, not stale VMEM
    unmapped = ~np.asarray(cache.mapped_mask())
    for norms in _kernel_norms(q, cache, cur, splits):
        assert (np.asarray(norms).transpose(0, 2, 1, 3)[unmapped] == 0).all()


def _kernel_norms(q, cache, cur, splits):
    """The decode kernel's own (kn, vn) epilogue outputs, (B, KV, P, page)."""
    B, H, hd = q.shape
    KV = cache.k.shape[2]
    args = (q.reshape(B, KV, H // KV, hd), ops._pool_layout(cache.k),
            ops._pool_layout(cache.v))
    if cache.quantized:
        kernel = paged_attention_kernel_int8
        args += (jnp.moveaxis(cache.k_scale, 2, 0),
                 jnp.moveaxis(cache.v_scale, 2, 0))
    else:
        kernel = paged_attention_kernel
    _, norms = kernel(*args, cache.pos, cache.block_table, cur,
                      num_splits=splits, return_scores=True)
    return norms


@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_fused_prefill_scores_match_block_score_oracle(dtype):
    cache, steps = _driven_cache("streaming_llm", 8, dtype, seed=6)
    q = jax.random.normal(jax.random.PRNGKey(37), (2, 4, 4, 64))
    q_pos = jnp.broadcast_to(jnp.arange(4, dtype=jnp.int32)[None],
                             (2, 4)) + steps - 4
    plain = ops.paged_prefill_attention(q, cache, q_pos=q_pos)
    out, scores = ops.paged_prefill_attention(q, cache, q_pos=q_pos,
                                              return_scores=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(plain))
    oracle = ops.page_scores(cache)
    np.testing.assert_allclose(np.asarray(scores), np.asarray(oracle),
                               atol=1e-4, rtol=1e-4)


def test_fused_scores_on_cow_shared_pages():
    """Fused scores follow each row's own block-table VIEW of the shared
    pool: adopted prefix pages score identically for both mappers; after a
    CoW fork + token eviction the forked row's score diverges while the
    sharer's stays put — all still matching the standalone oracle."""
    from repro.core import evict_token

    cache = _filled_cache(B=2, P=3, page=4, KV=1, hd=8, rows=(0,),
                          n_tokens=8)
    cache = _adopt(cache, dst=1, src=0, n_pages=2)

    def fused(c):
        q = jax.random.normal(jax.random.PRNGKey(41), (2, 2, 8))
        _, s = ops.paged_attention(q, c, cur_pos=jnp.full((2,), 7,
                                                          jnp.int32),
                                   return_scores=True)
        return np.asarray(s)

    shared = fused(cache)
    np.testing.assert_allclose(shared, np.asarray(ops.page_scores(cache)),
                               atol=1e-4, rtol=1e-4)
    # both mappers of the shared prefix see the same page statistics
    np.testing.assert_allclose(shared[0, :2], shared[1, :2], atol=1e-6)

    # row 1 evicts a token on shared page 0 -> auto CoW fork
    cache = evict_token(cache, jnp.full((2,), 2, jnp.int32),
                        enable=jnp.asarray([False, True]))
    forked = fused(cache)
    np.testing.assert_allclose(forked, np.asarray(ops.page_scores(cache)),
                               atol=1e-4, rtol=1e-4)
    # sharer's score is untouched; the forked row's page 0 diverged
    np.testing.assert_allclose(forked[0, 0], shared[0, 0], atol=1e-6)
    assert not np.allclose(forked[1, 0], shared[1, 0])


def test_fused_scores_unmapped_slots_are_inf():
    cache = _filled_cache(B=2, P=3, page=4, KV=1, hd=8, rows=(0,),
                          n_tokens=8)
    q = jax.random.normal(jax.random.PRNGKey(43), (2, 2, 8))
    _, s = ops.paged_attention(q, cache,
                               cur_pos=jnp.full((2,), 7, jnp.int32),
                               return_scores=True)
    s = np.asarray(s)
    bt = np.asarray(cache.block_table)
    assert np.isinf(s[bt < 0]).all()
    assert np.isfinite(s[bt >= 0]).any()
