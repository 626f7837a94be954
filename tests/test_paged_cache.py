"""Unit tests for the functional pooled paged KV cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import devstats
from repro.core import paged_cache as pc


def _cache(B=2, P=4, page=4, KV=2, hd=8):
    return pc.init_layer_cache(B, P, page, KV, hd, jnp.float32)


def test_init_premaps_working_page():
    c = _cache()
    bt = np.asarray(c.block_table)
    np.testing.assert_array_equal(bt[:, 0], [0, 1])      # distinct pool pages
    assert (bt[:, 1:] == -1).all()
    assert int(c.num_free()) == c.pool_pages - 2
    assert c.pool_pages == 2 * 4


def test_write_token_places_at_head():
    c = _cache()
    B, KV, hd = 2, 2, 8
    k = jnp.ones((B, KV, hd))
    v = 2 * jnp.ones((B, KV, hd))
    c = pc.write_token(c, k, v, jnp.array([0, 0]), jnp.array([1.0, 2.0]))
    assert int(c.cur_off[0]) == 1
    np.testing.assert_array_equal(np.asarray(c.pos_view()[:, 0, 0]), [0, 0])
    assert float(c.score_view()[1, 0, 0]) == 2.0
    assert int(c.total_valid()[0]) == 1


def test_write_token_respects_active_mask():
    c = _cache()
    k = jnp.ones((2, 2, 8))
    c = pc.write_token(c, k, k, jnp.array([5, 5]), jnp.zeros(2),
                       active=jnp.array([True, False]))
    assert int(c.total_valid()[0]) == 1
    assert int(c.total_valid()[1]) == 0
    assert int(c.cur_off[1]) == 0


def test_page_scores_mean_and_inf_for_empty():
    c = _cache()
    for i in range(4):
        c = pc.write_token(c, jnp.ones((2, 2, 8)), jnp.ones((2, 2, 8)),
                           jnp.full((2,), i), jnp.full((2,), float(i)))
    ps = np.asarray(c.page_scores())
    assert np.allclose(ps[:, 0], 1.5)              # mean(0,1,2,3)
    assert np.isinf(ps[:, 1:]).all()


def test_evict_page_returns_to_free_list():
    c = _cache()
    for i in range(4):
        c = pc.write_token(c, jnp.ones((2, 2, 8)), jnp.ones((2, 2, 8)),
                           jnp.full((2,), i), jnp.zeros(2))
    free_before = int(c.num_free())
    c = pc.evict_page(c, jnp.array([0, 0]))
    assert int(c.total_valid()[0]) == 0
    assert int(c.num_free()) == free_before + 2    # both pages back in pool
    assert (np.asarray(c.block_table)[:, 0] == -1).all()
    # the freed physical pages hold no live tokens (invariant F4)
    ref = np.asarray(c.ref_count)
    assert (np.asarray(c.pos)[ref == 0] == -1).all()
    # and can be re-allocated
    c2, phys, ok = pc.alloc_pages(c, jnp.array([True, True]))
    assert bool(ok.all())
    assert len(set(np.asarray(phys).tolist())) == 2


def test_alloc_pages_distinct_and_bounded():
    c = _cache(B=3, P=2)                            # pool = 6, 3 pre-mapped
    c, phys, ok = pc.alloc_pages(c, jnp.array([True, False, True]))
    p = np.asarray(phys)
    assert bool(ok[0]) and not bool(ok[1]) and bool(ok[2])
    assert p[0] != p[2] and p[1] == c.pool_pages    # sentinel where not needed
    # exhaust the pool: only 1 free page left now
    c, phys2, ok2 = pc.alloc_pages(c, jnp.array([True, True, True]))
    assert int(np.asarray(ok2).sum()) == 1


def test_evict_token_flat_index():
    c = _cache()
    for i in range(6):                              # fills page0 + 2 of page1
        c = pc.write_token(c, jnp.ones((2, 2, 8)), jnp.ones((2, 2, 8)),
                           jnp.full((2,), i), jnp.zeros(2))
        if int(c.cur_off[0]) == c.page_size:
            c2, phys, ok = pc.alloc_pages(c, jnp.ones((2,), bool))
            c = pc.start_new_page(c2, jnp.array([1, 1]), phys, ok)
    c = pc.evict_token(c, jnp.array([2, 5]))        # page0/off2 ; page1/off1
    pos = np.asarray(c.pos_view())
    assert pos[0, 0, 2] == -1 and pos[1, 1, 1] == -1
    assert int(c.total_valid()[0]) == 5


def test_reclaim_empty_pages():
    c = _cache()
    for i in range(4):
        c = pc.write_token(c, jnp.ones((2, 2, 8)), jnp.ones((2, 2, 8)),
                           jnp.full((2,), i), jnp.zeros(2))
    c2, phys, ok = pc.alloc_pages(c, jnp.ones((2,), bool))
    c = pc.start_new_page(c2, jnp.array([1, 1]), phys, ok)
    # token-evict page 0 empty, one token at a time (stays mapped)
    for j in range(4):
        c = pc.evict_token(c, jnp.array([j, j]))
    assert (np.asarray(c.block_table)[:, 0] >= 0).all()
    c = pc.reclaim_empty_pages(c)
    assert (np.asarray(c.block_table)[:, 0] == -1).all()
    ref = np.asarray(c.ref_count)
    mapped = np.asarray(c.block_table)
    assert int((ref > 0).sum()) == (mapped >= 0).sum()


def test_to_contiguous_roundtrip():
    c = _cache()
    for i in range(4):
        c = pc.write_token(c, jnp.full((2, 2, 8), float(i)),
                           jnp.full((2, 2, 8), float(i)),
                           jnp.full((2,), i), jnp.zeros(2))
    k, v, pos, mask = pc.to_contiguous(c)
    assert k.shape == (2, 16, 2, 8)
    assert int(mask.sum()) == 8
    got = sorted(np.asarray(pos[0])[np.asarray(mask[0])].tolist())
    assert got == [0, 1, 2, 3]


def test_write_prompt_pages_layout():
    c = _cache(P=4, page=4)
    C = 8
    k = jnp.arange(2 * C * 2 * 8, dtype=jnp.float32).reshape(2, C, 2, 8)
    pos = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32), (2, C))
    score = jnp.ones((2, C))
    c = pc.write_prompt_pages(c, k, k, pos, score)
    assert int(c.cur_page[0]) == 2 and int(c.cur_off[0]) == 0
    assert int(c.total_valid()[0]) == C
    pv = np.asarray(c.pos_view())
    np.testing.assert_array_equal(pv[0, 0], [0, 1, 2, 3])
    np.testing.assert_array_equal(pv[0, 1], [4, 5, 6, 7])
    assert np.isinf(np.asarray(c.page_scores())[0, 2:]).all()
    # the decode working page is mapped (so write_token has a target), and
    # block tables never share physical pages
    bt = np.asarray(c.block_table)
    assert (bt[:, :3] >= 0).all() and (bt[:, 3] == -1).all()
    mapped = bt[bt >= 0]
    assert len(mapped) == len(set(mapped.tolist()))


def _seq_append(c, k, v, pos, score, n_tok):
    """The per-token reference: a lazy rollover, then one write, per slot."""
    for t in range(pos.shape[1]):
        act = t < n_tok
        c = pc.chunk_rollover(c, act & (c.cur_off >= c.page_size))
        c = pc.write_token(c, k[:, t], v[:, t], pos[:, t], score[:, t],
                           active=act)
    return c


def _chunk(c, n_tok, T, seed):
    """Random k/v/score for a (B, T) chunk; positions continue each row."""
    B = c.batch
    KV, hd = c.k.shape[2], c.k.shape[3]
    rng = jax.random.PRNGKey(seed)
    k = jax.random.normal(rng, (B, T, KV, hd))
    v = jax.random.normal(jax.random.fold_in(rng, 1), (B, T, KV, hd))
    score = jax.random.normal(jax.random.fold_in(rng, 2), (B, T))
    n_tok = jnp.asarray(n_tok, jnp.int32)
    start = jnp.max(c.pos_view(), axis=(1, 2)) + 1
    pos = start[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    pos = jnp.where(jnp.arange(T)[None] < n_tok[:, None], pos, -1)
    return k, v, pos, score, n_tok


def _fill(c, n_tok, T, seed):
    return _seq_append(c, *_chunk(c, n_tok, T, seed))


def _state_two_rows():
    return pc.init_layer_cache(2, 4, 4, 2, 8, jnp.float32,
                               track_stats=True), [10, 7], 10


def _state_mixed_n_tok():
    # heads at offsets 0..3 and full; chunk lengths 0, 1, page-1, page, T
    c = pc.init_layer_cache(5, 7, 4, 2, 8, jnp.float32, track_stats=True)
    c = _fill(c, [0, 1, 2, 3, 4], 4, 1)
    return c, [0, 1, 3, 4, 10], 10


def _state_parked_after_release():
    c = pc.init_layer_cache(3, 6, 4, 2, 8, jnp.float32, track_stats=True)
    c = _fill(c, [6, 9, 3], 9, 1)
    c = pc.release_rows(c, jnp.array([False, True, False]))
    return c, [9, 9, 2], 9


def _state_adopted_prefix():
    c = pc.init_layer_cache(3, 6, 4, 2, 8, jnp.float32, track_stats=True)
    c = _fill(c, [10, 3, 5], 10, 1)
    c = pc.release_rows(c, jnp.array([False, True, False]))
    c = pc.adopt_prefix(c, jnp.array([-1, 0, -1]), jnp.array([0, 2, 0]),
                        enable=jnp.array([False, True, False]))
    return c, [3, 7, 8], 8


def _state_int8():
    c = pc.init_layer_cache(3, 5, 4, 2, 8, "int8", track_stats=True)
    c = _fill(c, [3, 5, 0], 6, 1)
    return c, [9, 4, 8], 9


def _state_emptied_pages():
    # row 0: its partly written head page emptied (stays: it is current);
    # row 1: a full non-current page emptied (reclaimed at the first
    # rollover); row 2: its full head page emptied (reclaimed as it rolls)
    B, P, page = 3, 6, 4
    c = pc.init_layer_cache(B, P, page, 2, 8, jnp.float32, track_stats=True)
    c = _fill(c, [6, 10, 8], 10, 1)
    mask = np.zeros((B, P, page), bool)
    mask[0, 1, :2] = True
    mask[1, 1, :] = True
    mask[2, 1, :] = True
    c = pc.evict_token_mask(c, jnp.asarray(mask))
    return c, [5, 3, 6], 6


def _state_same_t_rollover():
    # every row rolls at the same tokens, onto a free list with holes
    c = pc.init_layer_cache(4, 6, 4, 2, 8, jnp.float32, track_stats=True)
    c = _fill(c, [6, 6, 6, 6], 6, 1)
    c = pc.release_rows(c, jnp.array([False, True, False, False]))
    c = _fill(c, [2, 2, 0, 0], 2, 2)
    return c, [10, 10, 10, 10], 10


def _state_decode_t1():
    c = pc.init_layer_cache(4, 5, 4, 2, 8, jnp.float32, track_stats=True)
    c = _fill(c, [3, 4, 1, 8], 8, 1)
    c = pc.release_rows(c, jnp.array([False, False, True, False]))
    return c, [1, 1, 1, 0], 1


@pytest.mark.parametrize("state", [
    _state_two_rows, _state_mixed_n_tok, _state_parked_after_release,
    _state_adopted_prefix, _state_int8, _state_emptied_pages,
    _state_same_t_rollover, _state_decode_t1])
def test_append_chunk_matches_sequential_writes(state):
    """append_chunk (the unified-step write path) must produce exactly the
    cache a per-token write_token + rollover sequence produces — pages
    filled in order, fresh pages from the free list at each boundary, in
    the order the sequence takes them — field by field, under jit, and
    through the one-scatter path (its fallback counter stays 0)."""
    c, n_tok, T = state()
    k, v, pos, score, n_tok = _chunk(c, n_tok, T, 7)
    out = jax.jit(pc.append_chunk)(c, k, v, pos, score, n_tok)
    seq = _seq_append(c, k, v, pos, score, n_tok)
    for name in pc.PagedLayerCache._fields:
        a, b = getattr(out, name), getattr(seq, name)
        assert (a is None) == (b is None), name
        if a is not None:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)
    assert int(out.stats[devstats.CHUNK_APPEND_FALLBACKS]) == 0
    np.testing.assert_array_equal(np.asarray(out.total_valid()),
                                  np.asarray(c.total_valid() + n_tok))


def test_append_chunk_allocates_from_shared_free_list():
    """A chunk spanning several pages draws distinct pool pages per rollover
    and conserves the free list (F1-F3)."""
    B, P, page = 2, 4, 4
    c = _cache(B=B, P=P, page=page)
    T = 3 * page
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    c = pc.append_chunk(c, jnp.ones((B, T, 2, 8)), jnp.ones((B, T, 2, 8)),
                        pos, jnp.zeros((B, T)), jnp.full((B,), T))
    assert (np.asarray(c.total_valid()) == T).all()
    bt = np.asarray(c.block_table)
    mapped = bt[bt >= 0]
    assert len(mapped) == len(set(mapped.tolist()))          # F3
    ref = np.asarray(c.ref_count)
    np.testing.assert_array_equal(np.bincount(mapped, minlength=c.pool_pages),
                                  ref)                       # F2
    assert int((ref > 0).sum()) + int(c.num_free()) == c.pool_pages  # F1


def test_release_rows_returns_pages_and_rearms_head():
    """release_rows frees a retiring row's pages to the SHARED pool and
    parks the head so the next append re-allocates from the free list."""
    B, P, page = 2, 4, 4
    c = _cache(B=B, P=P, page=page)
    T = 2 * page
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    c = pc.append_chunk(c, jnp.ones((B, T, 2, 8)), jnp.ones((B, T, 2, 8)),
                        pos, jnp.zeros((B, T)), jnp.full((B,), T))
    free0 = int(c.num_free())
    c = pc.release_rows(c, jnp.array([True, False]))
    assert int(c.num_free()) == free0 + 2   # both full pages back in the pool
    assert (np.asarray(c.block_table)[0] == -1).all()
    assert int(c.total_valid()[0]) == 0
    assert int(c.total_valid()[1]) == T     # other row untouched
    # a fresh request appends into the released row: first write rolls onto
    # a freshly allocated page (no dangling head)
    c = pc.append_chunk(c, jnp.ones((B, 3, 2, 8)), jnp.ones((B, 3, 2, 8)),
                        jnp.broadcast_to(jnp.arange(3, dtype=jnp.int32), (B, 3)),
                        jnp.zeros((B, 3)), jnp.array([3, 0]))
    assert int(c.total_valid()[0]) == 3
    bt = np.asarray(c.block_table)
    mapped = bt[bt >= 0]
    assert len(mapped) == len(set(mapped.tolist()))


def test_append_chunk_force_evicts_when_pool_dry():
    """Unstructured token policies can pin every logical slot with
    one-token survivor pages; the chunk rollover must then force-evict the
    fewest-token page rather than silently drop the incoming K/V. Only the
    per-token fallback force-evicts, and its counter says it ran."""
    B, P, page = 1, 3, 4
    c = pc.init_layer_cache(B, P, page, 2, 8, jnp.float32,
                            track_stats=True)       # pool == 3 pages
    T = 3 * page
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    c = pc.append_chunk(c, jnp.ones((B, T, 2, 8)), jnp.ones((B, T, 2, 8)),
                        pos, jnp.zeros((B, T)), jnp.full((B,), T))
    # fragment: keep exactly one token per page (offsets 1..3 evicted)
    frag = jnp.broadcast_to(jnp.arange(page) > 0, (B, P, page))
    c = pc.evict_token_mask(c, frag)
    assert int(c.total_valid()[0]) == P
    assert int(c.num_free()) == 0                   # every slot pinned
    new_pos = T + jnp.arange(page, dtype=jnp.int32)[None]
    c = pc.append_chunk(c, jnp.ones((B, page, 2, 8)),
                        jnp.ones((B, page, 2, 8)), new_pos,
                        jnp.zeros((B, page)), jnp.full((B,), page))
    got = np.asarray(c.pos_view()[0]).reshape(-1)
    for p_ in range(T, T + page):                   # the chunk LANDED
        assert p_ in got, (p_, got)
    # one survivor page was force-evicted to make room
    assert int(c.total_valid()[0]) == P - 1 + page
    ref = np.asarray(c.ref_count)
    bt = np.asarray(c.block_table)
    mapped = bt[bt >= 0]
    np.testing.assert_array_equal(np.bincount(mapped, minlength=c.pool_pages),
                                  ref)
    assert (np.asarray(c.pos)[ref == 0] == -1).all()
    st = np.asarray(c.stats)
    assert st[devstats.FORCED_EVICTIONS] == 1
    assert st[devstats.CHUNK_APPEND_FALLBACKS] == 1
    # structured PagedEviction never needs the fallback: the chunk-prefill
    # setting (decode churned past budget, then a chunk with a short row)
    from tests.test_chunked_prefill import _churned_cache
    c, steps = _churned_cache(page=8)
    c = c._replace(stats=devstats.zeros())
    B, T = c.batch, 16
    n_tok = jnp.array([T, T - 5])
    pos = steps + jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    pos = jnp.where(jnp.arange(T)[None] < n_tok[:, None], pos, -1)
    kv = jnp.ones((B, T) + c.k.shape[2:])
    c = pc.append_chunk(c, kv, kv, pos, jnp.zeros((B, T)), n_tok)
    assert int(c.stats[devstats.CHUNK_APPEND_FALLBACKS]) == 0
    assert int(c.stats[devstats.TOKENS_WRITTEN]) == 2 * T - 5


def test_evict_pages_mask_multi_victim():
    B, P, page = 2, 4, 4
    c = _cache(B=B, P=P, page=page)
    T = 3 * page
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    c = pc.append_chunk(c, jnp.ones((B, T, 2, 8)), jnp.ones((B, T, 2, 8)),
                        pos, jnp.zeros((B, T)), jnp.full((B,), T))
    mask = jnp.array([[True, True, False, False],
                      [False, False, False, False]])
    free0 = int(c.num_free())
    c = pc.evict_pages_mask(c, mask)
    assert int(c.num_free()) == free0 + 2
    assert int(c.total_valid()[0]) == page
    assert int(c.total_valid()[1]) == T
    ref = np.asarray(c.ref_count)
    assert (np.asarray(c.pos)[ref == 0] == -1).all()         # F4
