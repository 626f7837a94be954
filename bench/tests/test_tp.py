"""A configuration's ``tp`` reaches the engine and the draw: the tiny
closed cell served at tp=2, its weights drawn split over the mesh, gives
the tokens it gives at tp=1 and is correct. Runs on 4 CPU devices in a
process of its own (``on_cpu_devices``)."""
import dataclasses
import time

from bench.tests.tiny import on_cpu_devices


def _served(tp):
    import jax

    from bench import harness
    from bench.tests.tiny import tiny_cell

    cell = tiny_cell(dtype="float32")
    cell.config["tp"] = tp
    cell = dataclasses.replace(cell, chips=tp)
    keep = {}
    res = harness.run("tiny", 5, 1.0, False, t_process=time.perf_counter(),
                      cell=cell, devices=jax.devices()[:tp],
                      log=lambda s: None, keep=keep)
    return res, keep


def check():
    """The comparison, in a process with at least 4 devices."""
    one, keep1 = _served(1)
    two, keep2 = _served(2)
    assert one["correct"] and two["correct"], (one["checks"], two["checks"])
    assert two["device"]["count"] == 2
    w = keep2["params"]["pattern"][0]["mlp"]["w_gate"]
    assert [s.data.shape for s in w.addressable_shards] == \
        [w.shape[:-1] + (w.shape[-1] // 2,)] * 2
    compared = 0
    for (p1, s1), (p2, s2) in zip(keep1["seqs"], keep2["seqs"], strict=True):
        assert (p1 == p2).all()
        n = min(len(s1), len(s2))
        assert (s1[:n] == s2[:n]).all()
        compared += n
    assert compared >= 100
    print("ok")


def test_tp2_serves_the_tokens_of_tp1():
    out = on_cpu_devices("from bench.tests.test_tp import check; check()",
                         timeout=600)
    assert out.strip().endswith("ok")
