"""The plain reference: its PagedEviction schedule by hand, and exact
agreement with the program where the program computes in float32."""
import time

import jax
import numpy as np
import pytest

from bench import harness, reference
from bench.tests.tiny import on_cpu_devices, tiny_cell


def test_evict_steps_prompt_chunks_then_decode():
    # budget 2 pages of 4, chunk 8: prompt of 13 tokens, 7 decoded
    s = np.array([5, 5, 5, 5, 1, 1, 1, 1, 3, 3, 3, 3,   # pages 0..2
                  9, 9, 9, 9,                          # page 3 (p 12..15)
                  2, 2, 2, 2], float)                  # page 4 (p 16..19)
    ev, st = reference.evict_steps(s, 13, budget=8, page=4, chunk=8)
    # chunk 0 (p 0..7) keeps pages 0,1; chunk 1 (p 8..12) completes page 2
    # and evicts page 1 (score 1) after step 1
    assert ev[1] == 1
    # decode starts at step 2 (p 13); page 3 fills at p 15 (step 4): 3 kept
    # full pages > budget -> lowest (page 2, score 3) goes after step 4
    assert ev[2] == 4
    # page 4 fills at p 19 (step 8): lowest of {0, 3, 4} is page 4 itself
    assert ev[4] == 8
    assert ev[0] == ev[3] == reference.BIG
    assert list(st) == [0] * 8 + [1] * 5 + list(range(2, 9))


def test_no_eviction_under_the_budget():
    s = np.random.default_rng(0).random(60)
    ev, _ = reference.evict_steps(s, 30, budget=64, page=16, chunk=32)
    assert (ev == reference.BIG).all()


def test_reference_equals_a_float32_program_exactly():
    """With weights, activations and cache in float32, the program and the
    reference compute the same thing: every served token is the
    reference's argmax, through prompt compression and decode eviction."""
    cell = tiny_cell(dtype="float32", limits={"logit_gap_limit": 1e-5,
                                              "logit_gap_mean_limit": 1e-6})
    res = harness.run("tiny", 2**32 + 5, 1.0, False,
                      t_process=time.perf_counter(), cell=cell,
                      devices=jax.devices()[:1], log=lambda s: None)
    gap, _ = res["checks"]["logit_gap"]
    assert res["checks"]["pages_evicted_window"][0] > 0
    assert res["checks"]["tokens_checked"][0] >= 100
    assert gap == pytest.approx(0.0, abs=1e-5)
    assert res["correct"]


AUTO_VIEW = """
import jax
from jax.sharding import AxisType
from bench import arch, harness, reference
from bench.tests.tiny import on_cpu_devices, tiny_cell
from bench.weights import make_params

c = tiny_cell().config
mesh = jax.make_mesh((1, 4), ("data", "model"), devices=jax.devices()[:4])
drawn = make_params(harness.model_config(c), 2**36 + 3,
                    arch.of(c["config"]).STD, mesh=mesh)
seen = reference.on_auto_axes(drawn)
pairs = list(zip(jax.tree.leaves(drawn), jax.tree.leaves(seen), strict=True))
assert any(a.sharding.mesh.axis_types != (AxisType.Auto,) * 2
           for a, _ in pairs)
for a, b in pairs:
    assert set(b.sharding.mesh.axis_types) == {AxisType.Auto}
    assert b.sharding.spec == a.sharding.spec
    ptr = lambda x: [(s.device, s.data.unsafe_buffer_pointer())
                     for s in x.addressable_shards]
    assert ptr(a) == ptr(b), a.shape
print("ok")
"""


def test_auto_axes_view_shares_the_weights_buffers():
    """The reference sees split weights on an ``Auto``-axes mesh without a
    copy: every shard of the view is the drawn shard's own buffer."""
    assert on_cpu_devices(AUTO_VIEW, timeout=300).strip().endswith("ok")
