"""Architecture families, one file each: ``bench/arch/<model_type>.py``,
where ``model_type`` is the key of that name in a configuration's
``config``. A family file holds everything of the benchmark that depends
on the architecture:

    program_config(c)       the program's ModelConfig for a configuration
                            file ``c``
    hidden(params, cfg, tok, pos, visible, *, quant)
                            the reference's layer stack over all positions,
                            float32 at ``highest`` precision (``quant``:
                            float8 operands, the control); ``visible(scores)``
                            takes one layer's per-token eviction scores
                            (R, N) and returns which step processes each
                            position and after which step each position's
                            page leaves; returns the final hidden states
    logits(params, cfg, h, *, quant)
                            the final norm and the LM head over ``h``
    token_score(k, v)       the per-token eviction score, from what the
                            family caches
    matmul_params(cfg), lm_head_flops(cfg), attn_flops(cfg, keys),
    kv_bytes(cfg, tokens, itemsize), qo_bytes(cfg, queries, itemsize)
                            its work counts (``bench/flops.py``)
    STD                     optional: standard deviations by leaf name that
                            ``bench/weights.py`` draws with

Adding a family adds its file; nothing else changes.
"""
from __future__ import annotations

import importlib.util
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]
_LOADED: dict = {}       # model_type -> the module last loaded for it


def load(model_type: str, bench: pathlib.Path = BENCH):
    """The family module of ``model_type`` from ``bench/arch/``."""
    path = bench / "arch" / f"{model_type}.py"
    mod = _LOADED.get(model_type)
    if mod is not None and mod.__file__ == str(path):
        return mod
    if not path.is_file():
        raise SystemExit(f"no architecture family for model_type "
                         f"{model_type!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(f"bench_arch_{model_type}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _LOADED[model_type] = mod
    return mod


def of(cfg: dict):
    """The family of a configuration's ``config`` dict: the one loaded for
    its ``model_type`` (``harness.load_cell`` loads it), else the file in
    this checkout."""
    mt = cfg["model_type"]
    return _LOADED.get(mt) or load(mt)
