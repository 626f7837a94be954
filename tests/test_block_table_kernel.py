"""Kernel/reference parity for the block-table decode kernel.

Drives REAL decode traces (write -> evict -> rollover through the shared
pool) so the caches under test contain freed-and-reallocated physical
pages, then checks the Pallas block-table kernel against the dense
attention oracle in ``kernels/ref.py`` to atol=1e-4 across
policies x page sizes x dtypes (f32 and int8), in interpret mode.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import CacheConfig
from repro.core import decode_append, get_policy, init_layer_cache
from repro.core.paged_cache import quantize_absmax
from repro.kernels import ops, ref
from repro.models.attention import paged_attention_ref as model_ref

POLICIES = ["paged_eviction", "streaming_llm", "full"]
ATOL = 1e-4


def _driven_cache(policy, page, dtype, steps=None, B=2, KV=2, hd=64, seed=0):
    """Decode-trace a cache well past its budget so pages get evicted,
    returned to the pool, and reallocated."""
    budget = 2 * page
    cfg = CacheConfig(page_size=page, cache_budget=budget, policy=policy,
                      dtype=dtype)
    pol = get_policy(policy)
    steps = steps if steps is not None else budget + 3 * page + 3
    pages = pol.slab_pages(cfg, steps)
    cache = init_layer_cache(B, pages, page, KV, hd,
                             "int8" if dtype == "int8" else jnp.float32)
    rng = jax.random.PRNGKey(seed)
    evicted = 0
    for t in range(steps):
        rng, k1, k2 = jax.random.split(rng, 3)
        out = decode_append(cache, jax.random.normal(k1, (B, KV, hd)),
                            jax.random.normal(k2, (B, KV, hd)),
                            jnp.full((B,), t), pol, cfg)
        cache = out.cache
        evicted += int(np.asarray(out.pages_evicted).sum()) + \
            int(np.asarray(out.tokens_evicted).sum())
    if policy != "full":
        assert evicted > 0, "trace must exercise eviction + reallocation"
    return cache, steps


def _holed_cache(dtype, seed=0, B=2, P=73, page=16, KV=2, hd=64):
    """A 73-slot table, not a whole number of decode blocks, with the holes
    eviction leaves: row 0 maps slots 0-31 but for holes at 3 and 12 (mid
    block), leaves 32-63 unmapped (a whole block at 8, 16 or 32 pages per
    block) and maps 64-70; row 1 is short (slots 0-30). Some tokens of row
    0's mapped pages are evicted (pos -1). Every pool page no table maps,
    as evicted pages are once freed or handed to another row, holds tokens
    whose positions pass both rows' masks, so a leak through an unmapped
    slot would show. Same contract as :func:`_driven_cache`."""
    rng = np.random.RandomState(seed)
    N = B * P
    perm = rng.permutation(N)
    bt = np.full((B, P), -1, np.int32)
    row0 = [s for s in range(71) if s not in (3, 12) and not 32 <= s < 64]
    bt[0, row0] = perm[:len(row0)]
    bt[1, :31] = perm[len(row0):len(row0) + 31]
    pos = np.tile(np.arange(page, dtype=np.int32), (N, 1))
    for b, s in zip(*np.nonzero(bt >= 0)):
        pos[bt[b, s]] = s * page + np.arange(page)
    pos[bt[0, 5], 3:7] = -1
    k = jnp.asarray(rng.randn(N, page, KV, hd), jnp.float32)
    v = jnp.asarray(rng.randn(N, page, KV, hd), jnp.float32)
    quant = dtype == "int8"
    cache = init_layer_cache(B, P, page, KV, hd,
                             "int8" if quant else jnp.float32)
    if quant:
        (k, ks), (v, vs) = quantize_absmax(k), quantize_absmax(v)
        cache = cache._replace(k_scale=ks, v_scale=vs)
    cache = cache._replace(
        k=k, v=v, pos=jnp.asarray(pos), block_table=jnp.asarray(bt),
        ref_count=jnp.zeros((N,), jnp.int32).at[bt[bt >= 0]].set(1))
    return cache, 71 * page


def _dense_reference(q, cache, cur, window=0):
    """Dense oracle from kernels/ref.py on the gathered (dequantized) view."""
    B, H, hd = q.shape
    KV = cache.k.shape[2]
    G = H // KV
    kg = jnp.moveaxis(cache.k_view(), 3, 1)        # (B, KV, P, page, hd)
    vg = jnp.moveaxis(cache.v_view(), 3, 1)
    return ref.paged_attention_ref(q.reshape(B, KV, G, hd), kg, vg,
                                   cache.pos_view(), cur,
                                   window=window).reshape(B, H, hd)


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("page", [8, 16])
@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_block_table_kernel_matches_dense_ref(policy, page, dtype):
    cache, steps = _driven_cache(policy, page, dtype)
    B, KV, hd, G = 2, 2, 64, 2
    q = jax.random.normal(jax.random.PRNGKey(99), (B, KV * G, hd))
    cur = jnp.full((B,), steps - 1, jnp.int32)
    out = np.asarray(ops.paged_attention(q, cache, cur_pos=cur), np.float32)
    exp = np.asarray(_dense_reference(q, cache, cur), np.float32)
    tol = ATOL if dtype == "float32" else 5e-4   # int8: quantization noise
    np.testing.assert_allclose(out, exp, atol=tol, rtol=tol)


@pytest.mark.parametrize("policy", POLICIES)
def test_block_table_kernel_matches_model_oracle(policy):
    """ops.paged_attention == models.attention.paged_attention_ref on the
    same live pooled cache (integration of layouts)."""
    cache, steps = _driven_cache(policy, 8, "float32", seed=3)
    q = jax.random.normal(jax.random.PRNGKey(7), (2, 4, 64))
    cur = jnp.full((2,), steps - 1, jnp.int32)
    a = np.asarray(ops.paged_attention(q, cache, cur_pos=cur))
    b = np.asarray(model_ref(q, cache, cur_pos=cur))
    np.testing.assert_allclose(a, b, atol=ATOL)


def test_kernel_isolates_requests_sharing_the_pool():
    """Two requests' pages interleave arbitrarily in the physical pool after
    eviction churn; each request's attention must only see its own block
    table (no cross-request leakage through reallocated pages)."""
    cache, steps = _driven_cache("paged_eviction", 8, "float32", B=3, seed=5)
    q = jax.random.normal(jax.random.PRNGKey(11), (3, 4, 64))
    cur = jnp.full((3,), steps - 1, jnp.int32)
    batched = np.asarray(ops.paged_attention(q, cache, cur_pos=cur))
    for b in range(3):
        # request b alone, over the SAME pool, through only its block table
        solo = np.asarray(ops.paged_attention(q[b:b + 1], _restrict(cache, b),
                                              cur_pos=cur[b:b + 1]))
        np.testing.assert_allclose(batched[b:b + 1], solo, atol=ATOL)


def _restrict(cache, b):
    """View of one request over the SAME pool (row-sliced block table)."""
    return cache._replace(
        block_table=cache.block_table[b:b + 1],
        cur_page=cache.cur_page[b:b + 1],
        cur_off=cache.cur_off[b:b + 1],
    )


def test_window_masking_on_reallocated_pages():
    cache, steps = _driven_cache("streaming_llm", 8, "float32", seed=9)
    q = jax.random.normal(jax.random.PRNGKey(13), (2, 4, 64))
    cur = jnp.full((2,), steps - 1, jnp.int32)
    for w in (0, 8, 16):
        a = np.asarray(ops.paged_attention(q, cache, cur_pos=cur, window=w))
        b = np.asarray(model_ref(q, cache, cur_pos=cur, window=w))
        np.testing.assert_allclose(a, b, atol=ATOL)
