"""The draw of the weights over a tensor-parallel mesh: the values of a
one-device draw, each chip holding only its shard as the program places
it. Runs on 4 CPU devices in a process of its own."""
from bench.tests.tiny import on_cpu_devices

CHECK = '''
import jax
import numpy as np
from bench import arch, harness
from bench.tests.tiny import tiny_cell
from bench.weights import make_params
from repro.sharding import rules

c = tiny_cell().config
mcfg = harness.model_config(c)
std = arch.of(c["config"]).STD
seed = 2**40 + 17
one = make_params(mcfg, seed, std)
flat1 = jax.tree_util.tree_leaves_with_path(one)
for tp in (2, 4):
    mesh = jax.make_mesh((1, tp), ("data", "model"),
                         devices=jax.devices()[:tp])
    drawn = make_params(mcfg, seed, std, mesh=mesh)
    specs = jax.tree_util.tree_leaves(rules.tp_param_specs(drawn))
    flat = jax.tree_util.tree_leaves_with_path(drawn)
    assert len(flat) == len(flat1) == len(specs)
    split = 0
    for (path, a), (_, b), spec in zip(flat, flat1, specs, strict=True):
        name = jax.tree_util.keystr(path)
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), name
        assert a.sharding.spec == spec, (name, a.sharding.spec, spec)
        want = tuple(n // tp if s == "model" else n
                     for n, s in zip(a.shape, tuple(spec) +
                                     (None,) * (a.ndim - len(spec))))
        shards = a.addressable_shards
        assert len(shards) == tp, name
        assert all(s.data.shape == want for s in shards), (name, want)
        split += want != a.shape
    assert split == 10, split     # q/k/v and their biases, wo, the MLP
print("ok")
'''


def test_sharded_draw_is_the_one_device_draw_shard_by_shard():
    assert on_cpu_devices(CHECK, timeout=300).strip().endswith("ok")
