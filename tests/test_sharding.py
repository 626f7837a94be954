"""Sharding-rule tests: divisibility safety for every arch on the production
mesh shapes (via AbstractMesh — no 256 devices needed) + a real end-to-end
pjit run on a 1x1 mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, CacheConfig, DECODE_32K, TRAIN_4K
from repro.core import get_policy
from repro.models.transformer import init_decode_caches, init_model
from repro.sharding import rules
from repro.training.optimizer import init_adamw

SINGLE = AbstractMesh((16, 16), ("data", "model"))
MULTI = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _check_divisible(tree_shapes, tree_specs, mesh):
    """Every sharded dim must divide by its mesh axes — the property that
    makes .lower() succeed."""
    shapes = jax.tree.leaves(tree_shapes)
    specs = jax.tree.leaves(tree_specs, is_leaf=lambda x: isinstance(x, P))
    assert len(shapes) == len(specs)
    for shp, spec in zip(shapes, specs):
        for d, ax in enumerate(spec):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            size = int(np.prod([mesh.shape[a] for a in axes]))
            assert shp.shape[d] % size == 0, (shp.shape, spec)


def _spec_tree(shardings):
    return jax.tree.map(lambda s: s.spec, shardings)


@pytest.mark.parametrize("arch", sorted(ASSIGNED_ARCHS))
@pytest.mark.parametrize("mesh", [SINGLE, MULTI], ids=["single", "multi"])
def test_param_specs_divisible(arch, mesh):
    cfg = ASSIGNED_ARCHS[arch]
    shapes = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    sh = rules.param_shardings(mesh, cfg, shapes)
    _check_divisible(shapes, _spec_tree(sh), mesh)


@pytest.mark.parametrize("arch", sorted(ASSIGNED_ARCHS))
@pytest.mark.parametrize("mesh", [SINGLE, MULTI], ids=["single", "multi"])
def test_cache_specs_divisible(arch, mesh):
    cfg = ASSIGNED_ARCHS[arch]
    pol = get_policy("full")
    ccfg = CacheConfig(page_size=16, cache_budget=4096, policy="full",
                       slab_multiple=16)
    B = DECODE_32K.global_batch
    shapes = jax.eval_shape(
        lambda: init_decode_caches(cfg, B, DECODE_32K.seq_len, pol, ccfg))
    sh = rules.cache_shardings(mesh, cfg, shapes, B)
    _check_divisible(shapes, _spec_tree(sh), mesh)


@pytest.mark.parametrize("mesh", [SINGLE, MULTI], ids=["single", "multi"])
def test_opt_specs_divisible_zero1(mesh):
    cfg = ASSIGNED_ARCHS["mixtral-8x7b"]
    pshapes = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    oshapes = jax.eval_shape(init_adamw, pshapes)
    psh = rules.param_shardings(mesh, cfg, pshapes)
    osh = rules.opt_shardings(mesh, cfg, oshapes, psh, zero1=True)
    _check_divisible(oshapes.mu, _spec_tree(osh.mu), mesh)


@pytest.mark.parametrize("mesh", [SINGLE, MULTI], ids=["single", "multi"])
def test_step_input_specs_divisible(mesh):
    """Unified-step / paged flash-prefill kernel operand specs (DESIGN.md
    §6): batch over DP, chunk-query heads over model iff divisible."""
    for arch in ("qwen2.5-3b", "mixtral-8x7b", "gemma3-27b"):
        cfg = ASSIGNED_ARCHS[arch]
        B, T = DECODE_32K.global_batch, 256
        sh = rules.step_input_shardings(mesh, cfg, B, T)
        KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        G = cfg.num_heads // KV
        shapes = {
            "tokens": jnp.zeros((B, T), jnp.int32),
            "n_tok": jnp.zeros((B,), jnp.int32),
            "mask": jnp.zeros((B,), bool),
            "share_src": jnp.zeros((B,), jnp.int32),
            "share_pages": jnp.zeros((B,), jnp.int32),
            "q": jnp.zeros((B, T, cfg.num_heads, hd)),
            "q_pos": jnp.zeros((B, T), jnp.int32),
            "block_table": jnp.zeros((B, 64), jnp.int32),
            "page_scores": jnp.zeros((B, 64), jnp.float32),
            "decode_partials": jnp.zeros((B, KV, 8, G, hd), jnp.float32),
            "epilogue_norms": jnp.zeros((B, KV, 64, 16), jnp.float32),
        }
        for name, spec in sh.items():
            _check_divisible([jax.eval_shape(lambda: shapes[name])],
                             [spec], mesh)
        # q heads must actually take the model axis when divisible
        msz = int(np.prod([mesh.shape[a] for a in ("model",)
                           if a in mesh.shape]))
        if cfg.num_heads % msz == 0 and msz > 1:
            assert sh["q"][2] is not None, arch
        # split-K partials / epilogue norms split kv heads iff divisible
        if msz > 1 and KV % msz == 0:
            assert sh["decode_partials"][1] is not None, arch
            assert sh["epilogue_norms"][1] is not None, arch


def test_batch_axes_fallbacks():
    assert rules.batch_axes(SINGLE, 256) == "data"
    assert rules.batch_axes(MULTI, 256) == ("pod", "data")
    assert rules.batch_axes(MULTI, 16) is None or \
        rules.batch_axes(MULTI, 16) == "data"
    assert rules.batch_axes(SINGLE, 1) is None      # long_500k single request


def test_end_to_end_pjit_tiny_mesh():
    """Whole train step through pjit with rule-derived shardings on the one
    real CPU device (semantics check of the sharded program)."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    cfg = ASSIGNED_ARCHS["qwen2.5-3b"].reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    from repro.training import AdamWConfig, make_train_step
    opt = init_adamw(params)
    p_sh = rules.param_shardings(mesh, cfg, jax.eval_shape(lambda: params))
    o_sh = rules.opt_shardings(mesh, cfg, jax.eval_shape(lambda: opt), p_sh)
    step = make_train_step(cfg, AdamWConfig(total_steps=5, warmup_steps=1))
    B, S = 2, 32
    batch = {
        "tokens": jnp.zeros((B, S), jnp.int32),
        "targets": jnp.zeros((B, S), jnp.int32),
        "mask": jnp.ones((B, S), jnp.float32),
    }
    b_sh = rules.data_shardings(mesh, batch)
    with mesh:
        jstep = jax.jit(lambda p, o, b: step(p, o, b),
                        in_shardings=(p_sh, o_sh, b_sh),
                        out_shardings=(p_sh, o_sh, None))
        p2, o2, m = jstep(params, opt, batch)
    assert np.isfinite(float(m["loss"]))
