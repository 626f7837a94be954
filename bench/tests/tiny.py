"""A cell at a size a CPU test run can hold: the same harness, a
two-layer model of the served family's shape, page 16, budget 64, chunk
32, so that prompt compression and decode eviction both fire.

Its limits are set as a cell's are (readings in ``test_control.py``): the
closed cell holds the mean gap, which eviction choices that flip on
near-ties between bfloat16 and float32 scores move less than the widest;
the open one the widest gap, as ``qwen2.5-3b.chat-nobind`` does."""
from bench import harness

LIMITS = {"closed": {"logit_gap_mean_limit": 0.013},
          "open": {"logit_gap_limit": 0.03}}


def tiny_cell(loop="closed", dtype="bfloat16", limits=None):
    cfg = {"name": "tiny", "source": "test",
           "config": {"hidden_act": "silu", "hidden_size": 128,
                      "intermediate_size": 256, "model_type": "qwen2",
                      "num_attention_heads": 4, "num_hidden_layers": 2,
                      "num_key_value_heads": 2, "head_dim": 32,
                      "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
                      "tie_word_embeddings": True, "torch_dtype": dtype,
                      "vocab_size": 512},
           "cache": {"page_size": 16, "cache_budget": 64, "chunk_size": 32,
                     "policy": "paged_eviction", "dtype": dtype}}
    if loop == "closed":
        mix = {"loop": "closed", "requests": 4,
               "prompt_len": {"dist": "uniform", "min": 64, "max": 128},
               "max_new_tokens": 160, "shape_seed": 1, "max_batch": 4,
               "token_budget": 128, "evicts": "window", "trace_seconds": 1}
        check = {"requests": 4, "min_tokens": 100}
    else:
        mix = {"loop": "open", "rate_per_s": 4.0,
               "prompt_len": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                              "min": 16, "max": 40},
               "output_len": {"dist": "uniform", "min": 4, "max": 16},
               "shape_seed": 2, "max_batch": 4, "token_budget": 128,
               "evicts": "never", "trace_seconds": 1, "preroll_s": 0.5}
        check = {"requests": 8, "min_tokens": 30}
    check.update(LIMITS[loop] if limits is None else limits)
    metrics = [{"name": n, "unit": "u"} for n in
               ("ttft_p90_s", "itl_p95_ms", "output_tok_s", "setup_s")]
    return harness.Cell("tiny", 1, cfg, mix, check, metrics, [])


def on_cpu_devices(code: str, n: int = 4, timeout: float = 300) -> str:
    """Run ``code`` in a fresh Python with ``n`` CPU devices (the flag has
    to be set before jax is first imported) and this checkout on its path;
    fails the calling test on a non-zero exit. Returns standard output."""
    import os
    import pathlib
    import subprocess
    import sys

    root = pathlib.Path(__file__).resolve().parents[2]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n}",
               PYTHONPATH=os.pathsep.join([str(root), str(root / "src")]))
    p = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert p.returncode == 0, p.stderr[-4000:]
    return p.stdout
