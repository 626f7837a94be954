"""Architecture families and the tensor-parallel degree come with a
configuration's own files: a family is ``bench/arch/<model_type>.py``, a
degree the configuration's ``tp``. Adding either edits no file the
benchmark has."""
import json
import pathlib
import shutil
import time

import jax
import pytest

from bench import arch, flops, harness
from bench.tests.tiny import tiny_cell

ROOT = pathlib.Path(__file__).resolve().parents[2]
QWEN = json.loads((ROOT / "bench" / "configs" / "qwen2.5-3b.json")
                  .read_text())["config"]

TOYFAM = '''"""Family toyfam: qwen2's layers without q/k/v biases."""
import dataclasses

from bench.arch import qwen2
from bench.arch.qwen2 import (attn_flops, hidden, kv_bytes, lm_head_flops,
                              logits, matmul_params, qo_bytes, token_score)


def program_config(c):
    return dataclasses.replace(qwen2.program_config(c), qkv_bias=False)
'''


def _copy(tmp_path, model_type="toyfam", tp=None, chips=1, family=TOYFAM):
    """A checkout of the benchmark with one more cell whose configuration
    is of ``model_type``, made of new files and entries only."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    b = tmp_path / "bench"
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    if family is not None:
        (b / "arch" / f"{model_type}.py").write_text(family)
    cfg = json.loads((b / "configs" / "qwen2.5-3b.json").read_text())
    cfg["name"] = "toy-model"
    cfg["config"]["model_type"] = model_type
    if tp is not None:
        cfg["tp"] = tp
    (b / "configs" / "toy-model.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "chat-nobind.json").read_text())
    (b / "traffic" / "toy-chat.json").write_text(json.dumps(mix))
    (b / "checks" / "toy-model.toy-chat.json").write_text(json.dumps(
        {"requests": 8, "min_tokens": 300, "logit_gap_limit": 0.2}))
    spec["configs"].append({"name": "toy-model", "source": cfg["source"],
                            "file": "bench/configs/toy-model.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "toy-model.toy-chat",
                              "config": "toy-model", "traffic": "toy-chat",
                              "chips": chips, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path


def test_a_family_is_added_with_new_files_alone(tmp_path):
    root = _copy(tmp_path)
    cell = harness.load_cell("toy-model.toy-chat", root=root)
    mcfg = harness.model_config(cell.config)
    assert not mcfg.qkv_bias and mcfg.num_layers == 36
    fam = arch.of(cell.config["config"])
    assert fam.__file__ == str(root / "bench" / "arch" / "toyfam.py")
    cfg = cell.config["config"]
    assert flops.matmul_params(cfg) == flops.matmul_params(QWEN)
    assert flops.step_flops(cfg, [("d", 2000), ("p", 0, 128)], 1024, 16) \
        == flops.step_flops(QWEN, [("d", 2000), ("p", 0, 128)], 1024, 16)


def test_a_tiny_run_of_a_new_family_is_correct(tmp_path):
    """The program without biases against the toy family's reference,
    through the whole run: the family's layers are what the reference
    computes."""
    b = tmp_path / "bench"
    (b / "arch").mkdir(parents=True)
    (b / "arch" / "toyfam.py").write_text(TOYFAM)
    arch.load("toyfam", b)
    cell = tiny_cell()
    cell.config["config"]["model_type"] = "toyfam"
    keep = {}
    res = harness.run("tiny", 11, 1.0, False, t_process=time.perf_counter(),
                      cell=cell, devices=jax.devices()[:1],
                      log=lambda s: None, keep=keep)
    assert "bq" not in keep["params"]["pattern"][0]["attn"]
    assert res["checks"]["pages_evicted_window"][0] > 0
    assert res["correct"], res["checks"]


def test_a_missing_family_names_its_file(tmp_path):
    root = _copy(tmp_path, model_type="nofam", family=None)
    with pytest.raises(SystemExit, match=r"bench/arch/nofam\.py"):
        harness.load_cell("toy-model.toy-chat", root=root)


def test_qwen2_work_counts_are_pinned():
    """The qwen2 family's counts for qwen2.5-3b, as ``bench/flops.py``
    counted them before families had files of their own."""
    assert flops.matmul_params(QWEN) == 2774532096
    assert flops.lm_head_flops(QWEN) == 622329856
    assert [flops.attn_flops(QWEN, k) for k in (1, 17, 1024)] == \
        [8192, 139264, 8388608]
    assert [flops.kv_bytes(QWEN, k) for k in (1, 17, 1024)] == \
        [1024, 17408, 1048576]
    assert [flops.qo_bytes(QWEN, k) for k in (1, 128)] == [8192, 1048576]
    assert [flops.decode_row_work(QWEN, p, 1024, 16)
            for p in (0, 15, 1039, 5000)] == [
        (294912, 331776), (4718592, 884736), (306708480, 38633472),
        (304644096, 38375424)]
    assert [flops.chunk_row_work(QWEN, s, n, 1024, 16)
            for s, n in ((0, 128), (1024, 128), (2048, 37))] == [
        (2434793472, 42467328), (41089499136, 80216064),
        (11380948992, 50024448)]
    assert flops.step_flops(QWEN, [("d", 2000), ("p", 0, 128),
                                   ("p", 1024, 77)], 1024, 16) == 1171850133504


@pytest.mark.parametrize("tp,chips,what", [
    (2, 1, "exceeds the cell's 1 chips"),
    (4, 2, "exceeds the cell's 2 chips"),
    (3, 4, "does not divide the 2 KV heads")])
def test_a_tp_the_cell_cannot_hold_is_refused(tmp_path, tp, chips, what):
    root = _copy(tmp_path, model_type="qwen2", tp=tp, chips=chips,
                 family=None)
    with pytest.raises(SystemExit, match=what):
        harness.load_cell("toy-model.toy-chat", root=root)


def test_a_tp_within_the_cell_loads(tmp_path):
    root = _copy(tmp_path, model_type="qwen2", tp=2, chips=4, family=None)
    cell = harness.load_cell("toy-model.toy-chat", root=root)
    assert harness.tensor_parallel(cell.config) == 2
    assert harness.tensor_parallel({}) == 1
