"""Compile-only tests: the serving path's Pallas kernels, built by the Mosaic
compiler for a described TPU v5e (no chip attached), in bf16 at the head
geometries of Llama-3.2-3B (G=3, hd=128) and Llama-3.2-1B (G=4, hd=64),
8 KV heads, page 16, and the decode walk at the served Qwen2.5-3B geometry
(G=8, hd=128, 2 KV heads, a 73-slot block table).

Interpret mode checks none of Mosaic's rules (block tiling, layouts, VMEM),
so these are the only tests that see what the chip's compiler would
refuse. Nothing runs: each test lowers a kernel from shapes and asserts
that the compiled module holds its ``tpu_custom_call``. The topology is
described inside a fixture, never at import: only one process at a time
may load the TPU library, and every test worker imports this file.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels.block_score import block_score_kernel
from repro.kernels.flash_prefill import paged_flash_prefill_kernel
from repro.kernels.paged_attention import (
    paged_attention_kernel,
    paged_attention_kernel_int8,
)

GEOMETRY = {"llama-3.2-3b": (3, 128), "llama-3.2-1b": (4, 64),
            "qwen2.5-3b": (8, 128)}                          # (G, hd)
KV, PAGE, B, SLOTS, T = 8, 16, 8, 65, 64
N = B * SLOTS                                  # pool pages
# the served decode-evict cell: budget 1024 + chunk 128 at page 16
SERVED_KV, SERVED_SLOTS = 2, 73


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _decode(sds, G, hd, *, splits, scores, kv=KV, slots=SLOTS):
    fn = lambda *a: paged_attention_kernel(
        *a, interpret=False, num_splits=splits, return_scores=scores)
    n = B * slots
    return fn, (sds((B, kv, G, hd), jnp.bfloat16),
                sds((kv, n, PAGE, hd), jnp.bfloat16),
                sds((kv, n, PAGE, hd), jnp.bfloat16),
                sds((n, PAGE), jnp.int32), sds((B, slots), jnp.int32),
                sds((B,), jnp.int32))


def _decode_int8(sds, G, hd):
    fn = lambda *a: paged_attention_kernel_int8(
        *a, interpret=False, num_splits=4, return_scores=True)
    return fn, (sds((B, KV, G, hd), jnp.bfloat16),
                sds((KV, N, PAGE, hd), jnp.int8),
                sds((KV, N, PAGE, hd), jnp.int8),
                sds((KV, N, PAGE), jnp.float32),
                sds((KV, N, PAGE), jnp.float32),
                sds((N, PAGE), jnp.int32), sds((B, SLOTS), jnp.int32),
                sds((B,), jnp.int32))


def _prefill(sds, G, hd):
    fn = lambda *a: paged_flash_prefill_kernel(*a, interpret=False,
                                               return_scores=True)
    return fn, (sds((B, T, KV * G, hd), jnp.bfloat16),
                sds((KV, N, PAGE, hd), jnp.bfloat16),
                sds((KV, N, PAGE, hd), jnp.bfloat16),
                sds((N, PAGE), jnp.int32), sds((B, SLOTS), jnp.int32),
                sds((B, T), jnp.int32))


def _block_score(sds, G, hd):
    fn = lambda *a: block_score_kernel(*a, interpret=False)
    return fn, (sds((N, PAGE, KV, hd), jnp.bfloat16),
                sds((N, PAGE, KV, hd), jnp.bfloat16),
                sds((N, PAGE), jnp.int32))


CASES = {
    "decode-split1": lambda s, g, h: _decode(s, g, h, splits=1, scores=False),
    "decode-split4-scores": lambda s, g, h: _decode(s, g, h, splits=4,
                                                    scores=True),
    "decode-split1-scores": lambda s, g, h: _decode(s, g, h, splits=1,
                                                    scores=True),
    "decode-split4": lambda s, g, h: _decode(s, g, h, splits=4, scores=False),
    "decode-int8-split4-scores": _decode_int8,
    "decode-served-split1-scores": lambda s, g, h: _decode(
        s, g, h, splits=1, scores=True, kv=SERVED_KV, slots=SERVED_SLOTS),
    "prefill-T64-scores": _prefill,
    "block-score": _block_score,
}


@pytest.mark.parametrize("case,model", [
    ("decode-split1", "llama-3.2-3b"),
    ("decode-split4-scores", "llama-3.2-3b"),
    ("decode-split1-scores", "llama-3.2-1b"),
    ("decode-split4", "llama-3.2-1b"),
    ("decode-int8-split4-scores", "llama-3.2-3b"),
    ("decode-served-split1-scores", "qwen2.5-3b"),
    ("prefill-T64-scores", "llama-3.2-3b"),
    ("prefill-T64-scores", "llama-3.2-1b"),
    ("block-score", "llama-3.2-3b"),
])
def test_kernel_compiles_for_v5e(one_chip, case, model):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    fn, args = CASES[case](sds, *GEOMETRY[model])
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_tp4_decode_kernel_compiles_under_shard_map(topo):
    """Tensor-parallel serving runs the compiled decode kernel per shard
    inside shard_map over a (1, 4) mesh, KV heads split across chips (2 per
    chip), and reduces the fused-score norms across the mesh: the blocked
    walk's page copies and buffers at the local shapes, single-chain and
    split, at Llama-3.2-3B's heads and at the served Qwen2.5-3B query group
    over a 73-slot table."""
    from repro.models.moe import _shard_map

    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"))
    heads, pool, rep = P(None, "model"), P("model"), P()
    sds = lambda shape, dt, spec: jax.ShapeDtypeStruct(
        shape, dt, sharding=NamedSharding(mesh, spec))
    for model, slots, splits in (("llama-3.2-3b", SLOTS, 4),
                                 ("qwen2.5-3b", SERVED_SLOTS, 1)):
        G, hd = GEOMETRY[model]
        n = B * slots

        def local(q, kp, vp, pos, bt, cur):
            out, (kn, vn) = paged_attention_kernel(
                q, kp, vp, pos, bt, cur, interpret=False,
                num_splits=splits, return_scores=True)
            return out, jax.lax.pmean(kn.mean(1), "model")

        fn = _shard_map(local, mesh,
                        in_specs=(heads, pool, pool, rep, rep, rep),
                        out_specs=(heads, rep), manual_axes=("data", "model"))
        compiled = jax.jit(fn).lower(
            sds((B, KV, G, hd), jnp.bfloat16, heads),
            sds((KV, n, PAGE, hd), jnp.bfloat16, pool),
            sds((KV, n, PAGE, hd), jnp.bfloat16, pool),
            sds((n, PAGE), jnp.int32, rep), sds((B, slots), jnp.int32, rep),
            sds((B,), jnp.int32, rep)).compile()
        text = compiled.as_text()
        assert "tpu_custom_call" in text, model
        assert "all-reduce" in text, model
