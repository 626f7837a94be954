"""Policy behaviour tests — the paper's algorithms as executable claims."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import CacheConfig
from repro.core import decode_append, get_policy, init_layer_cache, POLICIES
from repro.core.prefill import compress_and_page


def _ccfg(policy, page=4, budget=16, **kw):
    return CacheConfig(page_size=page, cache_budget=budget, policy=policy,
                       dtype="float32", **kw)


def _run_decode(policy_name, steps=40, B=2, KV=2, hd=8, budget=16, page=4,
                key=0):
    pol = get_policy(policy_name)
    cfg = _ccfg(policy_name, page=page, budget=budget)
    pages = pol.slab_pages(cfg, steps)
    cache = init_layer_cache(B, pages, page, KV, hd, jnp.float32)
    rng = jax.random.PRNGKey(key)
    outcomes = []
    for t in range(steps):
        rng, k1, k2 = jax.random.split(rng, 3)
        k = jax.random.normal(k1, (B, KV, hd))
        v = jax.random.normal(k2, (B, KV, hd))
        out = decode_append(cache, k, v, jnp.full((B,), t), pol, cfg)
        cache = out.cache
        outcomes.append(out)
    return cache, outcomes, cfg


# ---------------------------------------------------------------------------
# PagedEviction (the paper)
# ---------------------------------------------------------------------------

def test_paged_eviction_budget_bound():
    cache, _, cfg = _run_decode("paged_eviction", steps=60)
    # budget C plus at most one working page may be live transiently
    assert int(cache.total_valid().max()) <= cfg.cache_budget + cfg.page_size


def test_paged_eviction_structured_occupancy():
    """Paper Limitation 1: after any step, every non-working page is either
    FULL or EMPTY — the structural invariant unstructured baselines break."""
    cache, _, cfg = _run_decode("paged_eviction", steps=57)
    tpp = np.asarray(cache.tokens_per_page())           # (B, P)
    cur = np.asarray(cache.cur_page)
    for b in range(tpp.shape[0]):
        for p in range(tpp.shape[1]):
            if p == cur[b]:
                continue
            assert tpp[b, p] in (0, cfg.page_size), (b, p, tpp[b, p])


def test_paged_eviction_frequency_is_block_interval():
    """Paper Limitation 4: evictions happen only when a page fills — once
    every `page_size` steps at steady state, never more often."""
    _, outcomes, cfg = _run_decode("paged_eviction", steps=64)
    ev = [bool(o.pages_evicted.any()) for o in outcomes]
    ev_steps = [i for i, e in enumerate(ev) if e]
    assert all(b - a >= cfg.page_size for a, b in zip(ev_steps, ev_steps[1:]))
    assert len(ev_steps) >= 5  # it does evict at steady state


def test_paged_eviction_evicts_lowest_scoring_page():
    pol = get_policy("paged_eviction")
    cfg = _ccfg("paged_eviction", page=4, budget=8)
    cache = init_layer_cache(1, 3, 4, 1, 4, jnp.float32)
    # page0: low ||v||/||k|| ; page1: high; then trigger eviction via page2
    for t in range(4):
        out = decode_append(cache, jnp.ones((1, 1, 4)), 0.1 * jnp.ones((1, 1, 4)),
                            jnp.array([t]), pol, cfg)
        cache = out.cache
    for t in range(4, 8):
        out = decode_append(cache, jnp.ones((1, 1, 4)), 10.0 * jnp.ones((1, 1, 4)),
                            jnp.array([t]), pol, cfg)
        cache = out.cache
    for t in range(8, 12):
        out = decode_append(cache, jnp.ones((1, 1, 4)), jnp.ones((1, 1, 4)),
                            jnp.array([t]), pol, cfg)
        cache = out.cache
    # after the 12th write the budget (8) is exceeded -> page0 (score 0.1)
    # must be the victim: its positions 0..3 are gone
    live = set(np.asarray(cache.pos_view()).ravel().tolist()) - {-1}
    assert live.isdisjoint({0, 1, 2, 3})
    assert {4, 5, 6, 7}.issubset(live)


@pytest.mark.parametrize("hook", ["post_write", "chunk_prefill_evict"])
def test_paged_eviction_tie_goes_to_older_page(hook):
    """Pages whose mean scores are equal leave oldest first, whatever slots
    they sit in: slot 0 holds positions 8..11 and slot 1 the older 0..3,
    both scoring 1.0; 12 live tokens over a budget of 8 evict one page."""
    pol = get_policy("paged_eviction")
    cfg = _ccfg("paged_eviction", page=4, budget=8)
    c = init_layer_cache(1, 4, 4, 1, 4, jnp.float32)
    first = jnp.array([8, 0, 12, -1])                     # per physical page
    pos = jnp.where(first[:, None] >= 0, first[:, None] + jnp.arange(4), -1)
    score = jnp.where(pos >= 0, jnp.array([1.0, 1.0, 5.0, 0.0])[:, None],
                      -jnp.inf)
    c = c._replace(pos=pos.astype(jnp.int32), score=score,
                   block_table=jnp.array([[0, 1, 2, -1]], jnp.int32),
                   ref_count=jnp.array([1, 1, 1, 0], jnp.int32),
                   cur_page=jnp.array([2], jnp.int32),
                   cur_off=jnp.array([4], jnp.int32))
    if hook == "post_write":
        c = pol.post_write(c, cfg).cache
    else:
        c = pol.chunk_prefill_evict(c, cfg)
    live = set(np.asarray(c.pos_view()).ravel().tolist()) - {-1}
    assert live.isdisjoint(range(4))
    assert set(range(8, 16)) <= live


# ---------------------------------------------------------------------------
# baselines
# ---------------------------------------------------------------------------

def test_full_cache_never_evicts():
    cache, outcomes, _ = _run_decode("full", steps=40)
    assert int(cache.total_valid().min()) == 40
    assert not any(bool(o.pages_evicted.any() or o.tokens_evicted.any())
                   for o in outcomes)


def test_streaming_llm_keeps_sinks_and_recent():
    cache, _, cfg = _run_decode("streaming_llm", steps=50, budget=16)
    pos = np.asarray(cache.pos_view())
    for b in range(pos.shape[0]):
        live = set(pos[b].ravel().tolist()) - {-1}
        for s in range(cfg.num_sink_tokens):
            assert s in live, f"sink {s} evicted"
        for r in range(50 - 8, 50):
            assert r in live, f"recent {r} evicted"
        assert len(live) <= cfg.cache_budget


def test_streaming_llm_evicts_every_step_once_full():
    _, outcomes, cfg = _run_decode("streaming_llm", steps=40, budget=16)
    ev = [bool(o.tokens_evicted.any()) for o in outcomes]
    # paper: one token per step once the budget is hit (overhead claim)
    assert all(ev[17:])
    assert not any(ev[:16])


def test_unstructured_evicts_lowest_score_token():
    pol = get_policy("inverse_key_l2")
    cfg = _ccfg("inverse_key_l2", page=4, budget=8)
    cache = init_layer_cache(1, 6, 4, 1, 4, jnp.float32)
    norms = [1.0] * 8 + [5.0]           # 9th token has a huge key norm
    for t, s in enumerate(norms):
        out = decode_append(cache, s * jnp.ones((1, 1, 4)), jnp.ones((1, 1, 4)),
                            jnp.array([t]), pol, cfg)
        cache = out.cache
    live = set(np.asarray(cache.pos_view()).ravel().tolist()) - {-1}
    assert 8 not in live                 # evicted immediately (highest ||k||)


def test_unstructured_fragmentation_vs_paged():
    """Paper Fig. 6: token-level eviction leaves partially-filled pages;
    PagedEviction does not."""
    frag_cache, _, cfg = _run_decode("inverse_key_l2", steps=60, budget=16)
    tpp = np.asarray(frag_cache.tokens_per_page())
    cur = np.asarray(frag_cache.cur_page)
    partial = sum(1 for b in range(tpp.shape[0]) for p in range(tpp.shape[1])
                  if p != cur[b] and 0 < tpp[b, p] < cfg.page_size)
    assert partial > 0, "unstructured policy should fragment pages"


def test_keydiff_prefers_diverse_keys():
    pol = get_policy("keydiff")
    cfg = _ccfg("keydiff", page=4, budget=8)
    cache = init_layer_cache(1, 6, 4, 1, 4, jnp.float32)
    base = jnp.asarray([[[1.0, 0.0, 0.0, 0.0]]])
    for t in range(8):
        out = decode_append(cache, base, jnp.ones((1, 1, 4)),
                            jnp.array([t]), pol, cfg)
        cache = out.cache
    ortho = jnp.asarray([[[0.0, 1.0, 0.0, 0.0]]])
    out = decode_append(cache, ortho, jnp.ones((1, 1, 4)),
                        jnp.array([8]), pol, cfg)
    cache = out.cache
    live = set(np.asarray(cache.pos_view()).ravel().tolist()) - {-1}
    assert 8 in live, "the diverse key must survive"


# ---------------------------------------------------------------------------
# prefill (Alg. 2)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", sorted(POLICIES))
def test_prefill_compress_budget_and_order(policy):
    key = jax.random.PRNGKey(3)
    B, S, KV, hd = 2, 40, 2, 8
    k = jax.random.normal(key, (B, S, KV, hd))
    v = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KV, hd))
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    valid = jnp.ones((B, S), bool)
    pol = get_policy(policy)
    cfg = _ccfg(policy, page=8, budget=16)
    cache = compress_and_page(k, v, positions, valid, pol, cfg)
    tv = int(cache.total_valid()[0])
    if policy == "full":
        assert tv == S
    else:
        assert tv == cfg.cache_budget
    # retained tokens stay in position order within the slab
    pos = np.asarray(cache.pos_view()[0]).ravel()
    live = pos[pos >= 0]
    assert (np.diff(live) > 0).all()


def test_prefill_paged_eviction_keeps_top_scores():
    key = jax.random.PRNGKey(4)
    B, S, KV, hd = 1, 32, 1, 8
    k = jnp.ones((B, S, KV, hd))
    scales = jnp.linspace(0.1, 3.2, S)               # increasing ||v||
    v = jnp.ones((B, S, KV, hd)) * scales[None, :, None, None]
    positions = jnp.arange(S, dtype=jnp.int32)[None]
    pol = get_policy("paged_eviction")
    cfg = _ccfg("paged_eviction", page=8, budget=16)
    cache = compress_and_page(k, v, positions, jnp.ones((B, S), bool), pol, cfg)
    live = sorted(np.asarray(cache.pos_view()[0]).ravel().tolist())
    live = [p for p in live if p >= 0]
    assert live == list(range(16, 32)), "top-16 by ||v||/||k|| = last 16"


def test_prefill_handles_padding():
    key = jax.random.PRNGKey(5)
    B, S = 2, 24
    k = jax.random.normal(key, (B, S, 1, 8))
    v = jax.random.normal(jax.random.fold_in(key, 1), (B, S, 1, 8))
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    valid = positions < jnp.asarray([[10], [24]])
    pol = get_policy("paged_eviction")
    cfg = _ccfg("paged_eviction", page=8, budget=16)
    cache = compress_and_page(k, v, jnp.where(valid, positions, -1), valid,
                              pol, cfg)
    assert int(cache.total_valid()[0]) == 10
    assert int(cache.total_valid()[1]) == 16
