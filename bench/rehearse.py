"""Compile rehearsal: build a cell's two step programs (T=chunk and T=1)
for a described TPU v5e (at the configuration's ``tp``, over that many of
a described 2x2's chips), with no chip attached, and print what the
compiler says each one needs per device (``memory_analysis()``) and how
many Pallas kernels it holds. Nothing runs.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --workload <name>
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _described(eng_args, tp, mesh):
    """The Engine built from shapes: at tp > 1 it places its parameters and
    creates its cache into the mesh's shardings, which for described
    devices is done as shapes with those shardings."""
    import jax

    from repro.serving import Engine

    if tp == 1:
        return Engine(**eng_args)
    real_put, real_jit = jax.device_put, jax.jit

    def put(x, s=None, **_):
        return jax.tree.map(lambda a, h: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=h), x, s)

    class Jit:
        def __init__(self, f, **kw):
            self.f, self.kw, self.jitted = f, kw, real_jit(f, **kw)

        def __call__(self, *a):
            return put(jax.eval_shape(self.f, *a), self.kw["out_shardings"])

        def lower(self, *a):
            return self.jitted.lower(*a)

    jax.device_put, jax.jit = put, Jit
    try:
        return Engine(**eng_args, mesh=mesh)
    finally:
        jax.device_put, jax.jit = real_put, real_jit


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
        SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    from bench import harness
    from repro.configs.base import CacheConfig
    from repro.kernels import block_score, flash_prefill, paged_attention
    from repro.models.transformer import init_model
    from repro.serving import SamplingParams
    from repro.serving import engine as engine_mod

    cell = harness.load_cell(args.workload)
    for mod in (block_score, flash_prefill, paged_attention):
        mod.interpret_mode = lambda interpret=None: False
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    tp = harness.tensor_parallel(cell.config)
    mesh = Mesh(np.array(topo.devices[:tp]).reshape(1, tp), ("data", "model"))
    if tp == 1:
        real = engine_mod.init_decode_caches
        engine_mod.init_decode_caches = lambda *a, **k: jax.eval_shape(
            lambda: real(*a, **k))
    dev = (SingleDeviceSharding(topo.devices[0]) if tp == 1 else
           NamedSharding(mesh, PartitionSpec()))
    mcfg = harness.model_config(cell.config)
    cc, mix = cell.config["cache"], cell.mix
    params = jax.eval_shape(lambda k: init_model(k, mcfg), jax.random.PRNGKey(0))
    max_new = int(mix.get("max_new_tokens") or mix["output_len"]["max"])
    eng = _described(dict(
        cfg=mcfg, params=params, cache_cfg=CacheConfig(
            page_size=cc["page_size"], cache_budget=cc["cache_budget"],
            policy=cc["policy"], dtype=cc["dtype"]),
        max_batch=int(mix["max_batch"]),
        max_prompt_len=int(mix["prompt_len"]["max"]),
        max_new_tokens=max_new, sampling=SamplingParams(greedy=True),
        chunk_size=cc["chunk_size"], token_budget=int(mix["token_budget"]),
        use_pallas=True, tp=tp), tp, mesh)
    put = lambda t: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=dev), t)
    if tp == 1:
        put_params, put_cache = put(eng.params), put(eng.cache)
    else:
        put_params, put_cache = eng.params, eng.cache
    B = int(mix["max_batch"])
    out = {}
    for T, step in ((cc["chunk_size"], eng._step_mixed), (1, eng._step_decode)):
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=dev)
        b = lambda: jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=dev)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=dev)
        t0 = time.perf_counter()
        comp = step.lower(
            put_params, i32(B, T), i32(B), b(), b(), b(), i32(B), i32(B),
            put_cache, key).compile()
        ma = comp.memory_analysis()
        out[f"T={T}"] = {
            "compile_s": round(time.perf_counter() - t0, 1),
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "tpu_custom_calls": comp.as_text().count("tpu_custom_call")}
        print(json.dumps({args.workload: {f"T={T}": out[f"T={T}"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
