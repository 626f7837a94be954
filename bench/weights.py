"""Seeded random weights, made on the device in one jitted call, in the
serving dtype (bf16), laid out as the program's parameter tree.

The tree's structure and shapes are the program's interface (taken from
``init_model`` by ``jax.eval_shape``: no values); every value is drawn
here, from the seed, so the reference never reads weights the program made.
A family may draw some leaves with other deviations (its ``STD``).

Given a tensor-parallel mesh, the draw's outputs are placed as the program
places its parameters (``rules.tp_param_shardings``), so each chip draws
only its own shard of a split leaf and a model larger than one chip is
never whole on any chip. The random bits are partitionable, so a leaf's
values do not depend on the mesh.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

STD = {"embed": 0.02, "lm_head": 0.02}


def seed_key(seed: int):
    """A PRNG key for any whole-number seed up to 64 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def drawer(model_cfg, std: dict | None = None, mesh=None):
    """The jitted draw of ``model_cfg``'s parameters (a repro ModelConfig)
    from a key; ``std``: deviations by leaf name over ``STD``; ``mesh``: a
    (1, tp) mesh whose TP placement the outputs take (None: one device)."""
    from repro.models.transformer import init_model

    shapes = jax.eval_shape(lambda k: init_model(k, model_cfg),
                            jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    treedef = jax.tree_util.tree_structure(shapes)
    stds = {**STD, **(std or {})}

    def build(key):
        out = []
        for i, (path, sds) in enumerate(leaves):
            name = _leaf_name(path)
            if name in ("scale",):
                out.append(jnp.ones(sds.shape, sds.dtype))
                continue
            std = stds.get(name, None)
            if std is None:                       # a matrix: (.., in, out)
                std = sds.shape[-2] ** -0.5
            x = jax.random.normal(jax.random.fold_in(key, i), sds.shape,
                                  jnp.float32) * std
            out.append(x.astype(sds.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    if mesh is None:
        return jax.jit(build)
    from repro.sharding import rules
    return jax.jit(build, out_shardings=rules.tp_param_shardings(mesh, shapes))


def make_params(model_cfg, seed: int, std: dict | None = None, mesh=None):
    """Parameters of ``model_cfg`` for ``seed`` (see ``drawer``)."""
    return drawer(model_cfg, std, mesh)(seed_key(seed))
