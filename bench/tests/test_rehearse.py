"""The compile rehearsal reaches a cell that only a scratch checkout
defines (``--root``), and compiles, for a described v5e 2x2 at the cell's
``tp``, the draw into the program's placement and the reference's
programs at the requests of one pass, with no all-gather. Runs
in a process of its own: the rehearsal loads the TPU compiler and sets
the kernels to compile, not interpret."""
import json
import math
import pathlib
import shutil

import pytest

from bench.tests.test_arch import TOYFAM
from bench.tests.tiny import on_cpu_devices, tiny_cell

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _checkout(tmp_path, tp, model_type="qwen2"):
    """This checkout's benchmark plus one tiny cell at ``tp``, as new files
    and entries only."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cell = tiny_cell()
    cfg = dict(cell.config, tp=tp)
    cfg["config"] = dict(cfg["config"], num_key_value_heads=4,
                         hidden_size=256, head_dim=128, model_type=model_type)
    b = tmp_path / "bench"
    if model_type == "toyfam":
        (b / "arch" / "toyfam.py").write_text(TOYFAM)
    (b / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (b / "traffic" / "tiny.json").write_text(json.dumps(cell.mix))
    (b / "checks" / "tiny.closed.json").write_text(json.dumps(cell.check))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tiny.closed", "config": "tiny",
                              "traffic": "tiny", "chips": tp, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return cfg


def _rehearse(root):
    out = on_cpu_devices(
        "import sys; from bench import rehearse; sys.exit(rehearse.main("
        f"['--workload', 'tiny.closed', '--root', {str(root)!r}]))",
        timeout=600)
    return {k: v for line in out.splitlines() if line.startswith("{")
            for k, v in json.loads(line)["tiny.closed"].items()}


@pytest.mark.parametrize("tp", [1, 2])
def test_rehearsal_of_draw_and_reference(tmp_path, tp):
    cfg = _checkout(tmp_path, tp)
    rows = _rehearse(tmp_path)
    h = cfg["config"]
    D, F, V = h["hidden_size"], h["intermediate_size"], h["vocab_size"]
    H, KV, hd, L = (h["num_attention_heads"], h["num_key_value_heads"],
                    h["head_dim"], h["num_hidden_layers"])
    split = L * (D * (H + 2 * KV) * hd + (H + 2 * KV) * hd + H * hd * D
                 + 3 * D * F)
    whole = V * D + (2 * L + 1) * D            # tied embedding, norms
    # each chip's share of the split leaves and all of the others, in bf16
    # (the compiler pads a buffer by a few bytes)
    assert rows["draw"]["output_bytes"] == pytest.approx(
        2 * (split // tp + whole), rel=0.01)
    assert rows["draw"]["temp_bytes"] <= rows["draw"]["output_bytes"]
    ref = {k: v for k, v in rows.items() if k.startswith(("reference.",
                                                         "control."))}
    assert set(ref) == {f"{a}.{b}" for a in ("reference", "control")
                        for b in ("qkv", "attn_mlp", "logits", "pick")}
    assert all(v["all_gathers"] == 0 for v in ref.values())
    N = math.ceil((128 + 160 - 1) / 256) * 256
    assert rows["reference.qkv"]["rows"] == 4      # the check's requests
    assert rows["reference.qkv"]["positions"] == N
    # the residual stream (rows, N, D) float32, whole on every chip
    assert rows["reference.attn_mlp"]["output_bytes"] == 4 * N * D * 4
    assert {"T=32", "T=1"} <= set(rows)


def test_rehearsal_of_a_family_without_the_hook(tmp_path):
    """The hook is optional: the steps and the draw are still rehearsed."""
    _checkout(tmp_path, 2, model_type="toyfam")
    rows = _rehearse(tmp_path)
    assert {"T=32", "T=1", "draw"} <= set(rows)
    assert rows["reference"] == "family has no rehearse hook"
    assert not any(k.startswith(("reference.", "control.")) for k in rows)
