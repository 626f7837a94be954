"""A configuration's ``tp`` reaches the engine: the tiny closed cell served
at tp=2 gives the tokens it gives at tp=1. Needs 4 devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=4`` before the first
jax import); skips with fewer."""
import dataclasses
import time

import jax
import pytest

from bench import harness
from bench.tests.tiny import tiny_cell

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs 4 devices (XLA_FLAGS=--xla_force_host_platform_device_"
           "count=4 before jax import)")


def _served(tp):
    cell = tiny_cell(dtype="float32")
    cell.config["tp"] = tp
    cell = dataclasses.replace(cell, chips=tp)
    keep = {}
    res = harness.run("tiny", 5, 1.0, False, t_process=time.perf_counter(),
                      cell=cell, devices=jax.devices()[:tp],
                      log=lambda s: None, keep=keep)
    return res, keep["seqs"]


@needs_mesh
def test_tp2_serves_the_tokens_of_tp1():
    one, seqs1 = _served(1)
    two, seqs2 = _served(2)
    assert one["correct"] and two["correct"], (one["checks"], two["checks"])
    assert two["device"]["count"] == 2
    compared = 0
    for (p1, s1), (p2, s2) in zip(seqs1, seqs2, strict=True):
        assert (p1 == p2).all()
        n = min(len(s1), len(s2))
        assert (s1[:n] == s2[:n]).all()
        compared += n
    assert compared >= 100
