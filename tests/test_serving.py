"""Serving engine + scheduler + sampler tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, CacheConfig
from repro.models import init_model
from repro.serving import Engine, Request, SamplingParams, Scheduler, sample_tokens
from repro.serving.request import RequestStatus


@pytest.fixture(scope="module")
def small_engine():
    cfg = ASSIGNED_ARCHS["qwen2.5-3b"].reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    ccfg = CacheConfig(page_size=8, cache_budget=32, policy="paged_eviction",
                       dtype="float32")
    return Engine(cfg, params, cache_cfg=ccfg, max_batch=3, max_prompt_len=48,
                  max_new_tokens=10), cfg


def test_engine_continuous_batching(small_engine):
    eng, cfg = small_engine
    rng = np.random.default_rng(0)
    reqs = [eng.submit(rng.integers(0, cfg.vocab_size,
                                    size=rng.integers(4, 48)).astype(np.int32))
            for _ in range(7)]
    done = eng.run()
    assert len(done) >= 7                      # module fixture may accumulate
    for r in reqs:
        assert r.finished
        assert r.num_generated == 10
        assert r.status == RequestStatus.FINISHED_LENGTH


def test_engine_greedy_determinism():
    cfg = ASSIGNED_ARCHS["stablelm-3b"].reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    ccfg = CacheConfig(page_size=8, cache_budget=32, policy="full",
                       dtype="float32")
    prompt = np.arange(20, dtype=np.int32) % cfg.vocab_size

    def gen():
        eng = Engine(cfg, params, cache_cfg=ccfg, max_batch=2,
                     max_prompt_len=32, max_new_tokens=8)
        r = eng.submit(prompt)
        eng.run()
        return r.output_tokens

    assert gen() == gen()


def test_engine_batch_isolation():
    """A request's output must not depend on what shares the batch."""
    cfg = ASSIGNED_ARCHS["stablelm-3b"].reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    ccfg = CacheConfig(page_size=8, cache_budget=32, policy="paged_eviction",
                       dtype="float32")
    prompt = (np.arange(24, dtype=np.int32) * 7) % cfg.vocab_size

    eng1 = Engine(cfg, params, cache_cfg=ccfg, max_batch=2,
                  max_prompt_len=32, max_new_tokens=6)
    r_solo = eng1.submit(prompt)
    eng1.run()

    eng2 = Engine(cfg, params, cache_cfg=ccfg, max_batch=2,
                  max_prompt_len=32, max_new_tokens=6, seed=123)
    rng = np.random.default_rng(5)
    other = rng.integers(0, cfg.vocab_size, size=30).astype(np.int32)
    r_a = eng2.submit(other)
    r_b = eng2.submit(prompt)
    eng2.run()
    assert r_b.output_tokens == r_solo.output_tokens


def test_scheduler_fifo_and_slots():
    s = Scheduler(max_batch=2)
    reqs = [Request(i, np.zeros(4, np.int32)) for i in range(4)]
    for r in reqs:
        s.add(r)
    admitted = s.schedule()
    assert [r.request_id for _, r in admitted] == [0, 1]
    assert s.free_slots() == []
    reqs[0].status = RequestStatus.FINISHED_LENGTH
    s.retire(reqs[0])
    admitted2 = s.schedule()
    assert [r.request_id for _, r in admitted2] == [2]
    assert s.num_active == 2


def test_sampler_modes():
    key = jax.random.PRNGKey(0)
    logits = jnp.asarray([[0.0, 5.0, 1.0, -2.0]] * 3)
    g = sample_tokens(key, logits, greedy=True)
    np.testing.assert_array_equal(np.asarray(g), [1, 1, 1])
    tk = sample_tokens(key, logits, temperature=1.0, top_k=1)
    np.testing.assert_array_equal(np.asarray(tk), [1, 1, 1])
    tp = sample_tokens(key, logits, temperature=1.0, top_p=0.5)
    np.testing.assert_array_equal(np.asarray(tp), [1, 1, 1])
    # full-temperature sampling stays within the vocab and varies
    samples = [int(sample_tokens(jax.random.PRNGKey(i),
                                 logits[:1], temperature=2.0)[0])
               for i in range(20)]
    assert set(samples) <= {0, 1, 2, 3}
    assert len(set(samples)) > 1


def test_engine_eviction_respects_budget(small_engine):
    eng, cfg = small_engine
    # long generation with tight budget: cache never exceeds budget + page
    ccfg = CacheConfig(page_size=8, cache_budget=16, policy="paged_eviction",
                       dtype="float32")
    params = init_model(jax.random.PRNGKey(0), cfg)
    e = Engine(cfg, params, cache_cfg=ccfg, max_batch=1, max_prompt_len=32,
               max_new_tokens=24)
    e.submit(np.arange(30, dtype=np.int32) % cfg.vocab_size)
    e.run()
    for rep in range(ASSIGNED_ARCHS["qwen2.5-3b"].reduced().num_layers):
        kv = jax.tree.map(lambda a: a[rep], e.cache.pattern[0].kv)
        assert int(kv.total_valid().max()) <= 16 + 8


def test_decode_step_pallas_path_matches_ref():
    """decode_step(use_pallas=True) — the Pallas paged-attention hot path —
    must produce the same logits as the pure-jnp reference path."""
    from repro.models import decode_step, forward_prefill, make_inputs
    from repro.models.transformer import init_model as _init
    from repro.core import get_policy

    cfg = ASSIGNED_ARCHS["qwen2.5-3b"].reduced()
    params = _init(jax.random.PRNGKey(0), cfg)
    pol = get_policy("paged_eviction")
    ccfg = CacheConfig(page_size=16, cache_budget=32, policy="paged_eviction",
                       dtype="float32")
    inp = make_inputs(jax.random.PRNGKey(1), cfg, 2, 48)
    lg, cache = forward_prefill(params, cfg, inp["tokens"], pol, ccfg,
                                total_seq_hint=64)
    tok = jnp.argmax(lg, -1).astype(jnp.int32)
    lg_ref, _ = decode_step(params, cfg, tok, cache, pol, ccfg,
                            use_pallas=False)
    lg_pal, _ = decode_step(params, cfg, tok, cache, pol, ccfg,
                            use_pallas=True)
    np.testing.assert_allclose(np.asarray(lg_ref), np.asarray(lg_pal),
                               atol=3e-4, rtol=3e-4)


def test_kernel_path_follows_platform(small_engine):
    """Off a TPU the engine serves with the jnp reference by default and the
    kernels, when asked for, run in the Pallas interpreter; a TPU backend
    would compile them and refuse interpret mode."""
    from repro.kernels import interpret_mode
    eng, _ = small_engine
    on_tpu = jax.default_backend() == "tpu"
    assert eng.use_pallas == on_tpu
    assert eng.fused_scores == on_tpu
    assert interpret_mode() == (not on_tpu)
    assert interpret_mode(False) is False
    if not on_tpu:
        assert interpret_mode(True) is True


def test_init_params_matches_init_model():
    """The compiled initializer makes the same seeded weights."""
    from repro.serving import init_params
    cfg = ASSIGNED_ARCHS["qwen2.5-3b"].reduced()
    key = jax.random.PRNGKey(3)
    got = init_params(cfg, key)
    want = init_model(key, cfg)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-6)
