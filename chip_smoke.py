"""Smoke test of the served path on TPU: full-width models, compiled kernels.

    python chip_smoke.py           # one chip
    python chip_smoke.py --tp 4    # four chips (tensor-parallel serving)

One chip: Llama-3.2-3B at its published widths in bf16 (random weights
from ``--seed``) is served through ``Engine`` with the paper's
PagedEviction policy. The prompts are longer than the 1024-token budget,
so prefill compression (Alg. 2) and decode eviction (Alg. 3) both run. The
script then checks that

  * every request finished and pages were evicted,
  * the engine compiled exactly two step programs (T == chunk, T == 1),
  * the compiled step contains the Pallas kernels (``tpu_custom_call``),
  * one mixed and one decode ``forward_step`` from the same cache state
    give the same logits with the Pallas kernels and with the jnp
    reference, within ``LOGIT_RTOL`` of the largest reference logit.

``--tp 4`` runs only the four-chip path: Llama-3.1-8B (too large for one
v5e) served end to end at tp=4, and Llama-3.2-3B ``forward_step`` logits
at tp=4 against tp=1 from the same cache state, within the same bound.

Every line but the last is progress for a reader. The last line of stdout
is one JSON object: ``{"ok": true, "device": {...}}``. The script exits
non-zero, printing no such line, when JAX finds no TPU or any check fails.
The numbers printed are smoke output from one run, not benchmark results.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

# bf16 carries 8 significant bits (eps 2**-8 ~ 0.0039). Two attention paths
# that round differently drift apart over 28 layers; 0.05 of the largest
# logit is ~13 bf16 eps, far below the O(1) error of a wrong mask or page.
LOGIT_RTOL = 0.05

PAGE, BUDGET, CHUNK = 16, 1024, 128


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def say(*parts) -> None:
    print("chip_smoke:", *parts, flush=True)


def seeded_prompts(seed: int, vocab: int, n: int, lo: int, hi: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=int(m)).astype(np.int32)
            for m in rng.integers(lo, hi + 1, size=n)]


def serve(cfg, params, *, prompts, new_tokens: int, max_batch: int,
          tp: int = 1, mesh=None):
    """Serve ``prompts`` through Engine with PagedEviction; check the run;
    return the engine (its cache is the state the logit checks start
    from)."""
    from repro.configs import CacheConfig
    from repro.serving import Engine, SamplingParams

    ccfg = CacheConfig(page_size=PAGE, cache_budget=BUDGET,
                       policy="paged_eviction", dtype="bfloat16")
    eng = Engine(cfg, params, cache_cfg=ccfg, max_batch=max_batch,
                 max_prompt_len=max(len(p) for p in prompts),
                 max_new_tokens=new_tokens,
                 sampling=SamplingParams(greedy=True), chunk_size=CHUNK,
                 token_budget=max_batch * CHUNK, tp=tp, mesh=mesh)
    check(eng.use_pallas, "the engine did not choose the Pallas kernels")
    for p in prompts:
        eng.submit(p)
    # a step that grew the program cache paid its compile; the others are
    # the steady state
    compile_s = steady_s = 0.0
    steady_tokens = 0
    more = True
    while more:
        n_prog = eng.num_compiled_programs()
        tok0 = eng.stats.tokens_generated
        t0 = time.perf_counter()
        more = eng.step()
        dt = time.perf_counter() - t0
        if eng.num_compiled_programs() > n_prog:
            compile_s += dt
        else:
            steady_s += dt
            steady_tokens += eng.stats.tokens_generated - tok0
    s = eng.stats
    done = eng.scheduler.finished
    say(f"served {cfg.name} tp={tp}: {len(done)} requests, prompts "
        f"{min(map(len, prompts))}-{max(map(len, prompts))} tokens, "
        f"{s.tokens_generated} generated, {s.steps} steps "
        f"({s.decode_steps} decode-only)")
    say(f"compile_s={compile_s:.2f} (steps that compiled, incl. their run)"
        f" steady_s={steady_s:.2f} generated_tok_per_s="
        f"{steady_tokens / steady_s if steady_s else 0.0:.1f}"
        f" programs={eng.num_compiled_programs()}")
    say(f"pages_evicted={s.pages_evicted} tokens_evicted={s.tokens_evicted}"
        f" forced_evictions={s.forced_evictions}")
    check(len(done) == len(prompts), "not every request finished")
    check(all(r.num_generated == new_tokens for r in done),
          "a request stopped short of its token count")
    check(s.pages_evicted > 0, "no page was evicted")
    check(eng.num_compiled_programs() == 2,
          f"expected 2 step programs, got {eng.num_compiled_programs()}")
    return eng


def kernels_in_step(eng) -> None:
    """The compiled unified step, at both token widths, holds Mosaic
    kernels: the attention really ran as Pallas on the chip."""
    import jax
    import jax.numpy as jnp

    B = eng.max_batch
    for T in (eng.chunk_size, 1):
        zeros = jnp.zeros((B,), jnp.int32)
        no = jnp.zeros((B,), bool)
        hlo = eng._step_fn.lower(
            eng.params, jnp.zeros((B, T), jnp.int32), zeros, no, no, no,
            jnp.full((B,), -1, jnp.int32), zeros, eng.cache,
            jax.random.PRNGKey(0)).compile().as_text()
        n = hlo.count("tpu_custom_call")
        say(f"compiled step T={T}: {n} tpu_custom_call sites")
        check(n > 0, f"no Pallas kernel in the compiled T={T} step")


def probe_inputs(B: int, vocab: int, seed: int):
    """Two unified steps to compare from one cache state: a mixed one
    (first half of the rows decode one token, the rest take a CHUNK-token
    prompt chunk) and a decode-only one (every row decodes)."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    dec = np.arange(B) < B // 2
    mixed = dict(
        tokens=jnp.asarray(rng.integers(0, vocab, (B, CHUNK)), jnp.int32),
        n_tok=jnp.asarray(np.where(dec, 1, CHUNK), jnp.int32),
        decode_mask=jnp.asarray(dec), prefill_mask=jnp.asarray(~dec))
    decode = dict(
        tokens=jnp.asarray(rng.integers(0, vocab, (B, 1)), jnp.int32),
        n_tok=jnp.ones((B,), jnp.int32),
        decode_mask=jnp.ones((B,), bool), prefill_mask=jnp.zeros((B,), bool))
    return {"mixed": mixed, "decode": decode}


def logits_fn(cfg, ccfg, *, use_pallas: bool, mesh=None, params=None,
              cache=None):
    """jit'd ``forward_step`` returning only the logits. With a mesh it is
    the tensor-parallel step (shard_map over (1, tp), params and cache
    given with their TP shardings, as ``Engine`` runs it)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.core import get_policy
    from repro.models.moe import _shard_map
    from repro.models.transformer import forward_step
    from repro.sharding import rules

    tp_axis = "model" if mesh is not None else None
    policy = get_policy(ccfg.policy, tp_axis=tp_axis)

    def f(params, tokens, n_tok, decode_mask, prefill_mask, cache):
        logits, _ = forward_step(
            params, cfg, tokens, n_tok, cache, policy, ccfg,
            decode_mask=decode_mask, prefill_mask=prefill_mask,
            use_pallas=use_pallas, fused_scores=use_pallas, tp_axis=tp_axis)
        return logits

    if mesh is None:
        return jax.jit(f)
    rep = P()
    return jax.jit(_shard_map(
        f, mesh, in_specs=(rules.tp_param_specs(params), rep, rep, rep, rep,
                           rules.tp_cache_specs(cache)),
        out_specs=rep, manual_axes=("data", "model")))


def compare_logits(label: str, fn_a, fn_b, args_a, args_b, probes) -> None:
    """Logits of ``fn_a`` vs ``fn_b`` on each probe step; the max absolute
    difference must stay within LOGIT_RTOL of the largest |logit|."""
    import jax
    import numpy as np

    for name, inp in probes.items():
        ins = (inp["tokens"], inp["n_tok"], inp["decode_mask"],
               inp["prefill_mask"])
        a = np.asarray(jax.device_get(fn_a(args_a[0], *ins, args_a[1])),
                       np.float32)
        b = np.asarray(jax.device_get(fn_b(args_b[0], *ins, args_b[1])),
                       np.float32)
        check(bool(np.isfinite(a).all() and np.isfinite(b).all()),
              f"{label} {name}: non-finite logits")
        diff = float(np.max(np.abs(a - b)))
        scale = float(np.max(np.abs(b)))
        agree = float(np.mean(a.argmax(-1) == b.argmax(-1)))
        say(f"{label} {name} step (T={inp['tokens'].shape[1]}): logits "
            f"{a.shape}, max|diff|={diff:.6g}, max|logit|={scale:.6g}, "
            f"rel={diff / scale:.6g} (bound {LOGIT_RTOL}), "
            f"argmax agreement={agree:.3f}")
        check(diff <= LOGIT_RTOL * scale,
              f"{label} {name}: logits differ by {diff} > "
              f"{LOGIT_RTOL} * {scale}")


def one_chip(seed: int) -> None:
    import jax

    from repro.configs import get_arch
    from repro.serving import init_params

    cfg = get_arch("llama-3.2-3b")
    check(cfg.dtype == "bfloat16", "config is not bf16")
    say(f"{cfg.name}: {cfg.num_layers} layers, d_model={cfg.d_model}, "
        f"heads={cfg.num_heads}/{cfg.num_kv_heads} kv, hd={cfg.head_dim}, "
        f"d_ff={cfg.d_ff}, vocab={cfg.vocab_size}, dtype={cfg.dtype}")
    t0 = time.perf_counter()
    params = init_params(cfg, jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    say(f"init_params: {time.perf_counter() - t0:.2f}s")
    prompts = seeded_prompts(seed, cfg.vocab_size, 6, 1536, 3072)
    eng = serve(cfg, params, prompts=prompts, new_tokens=32, max_batch=8)
    kernels_in_step(eng)
    probes = probe_inputs(eng.max_batch, cfg.vocab_size, seed)
    compare_logits(
        "pallas vs jnp reference",
        logits_fn(cfg, eng.ccfg, use_pallas=True),
        logits_fn(cfg, eng.ccfg, use_pallas=False),
        (eng.params, eng.cache), (eng.params, eng.cache), probes)
    mem = jax.devices()[0].memory_stats() or {}
    say(f"peak_bytes_in_use={mem.get('peak_bytes_in_use')} of "
        f"bytes_limit={mem.get('bytes_limit')}")


def four_chips(seed: int) -> None:
    import jax

    from repro.configs import get_arch
    from repro.launch.mesh import make_tp_mesh
    from repro.serving import init_params
    from repro.sharding import rules

    tp = 4
    mesh = make_tp_mesh(tp)
    # (a) a model no single v5e holds, served end to end at tp=4
    cfg8 = get_arch("llama-3.1-8b")
    params8 = init_params(cfg8, jax.random.PRNGKey(seed), mesh)
    prompts = seeded_prompts(seed, cfg8.vocab_size, 4, 1536, 2048)
    serve(cfg8, params8, prompts=prompts, new_tokens=16, max_batch=4,
          tp=tp, mesh=mesh)
    # the engine and its jitted bound step form a cycle: collect it now so
    # the 8B shards leave the chips before the 3B model is placed
    del params8
    gc.collect()

    # (b) llama-3.2-3b logits at tp=4 vs tp=1 from one cache state, filled
    # by tp=1 prefill chunks past the budget (so compression has run)
    from repro.configs import CacheConfig
    from repro.core import get_policy
    from repro.models.transformer import forward_step, init_decode_caches

    cfg = get_arch("llama-3.2-3b")
    ccfg = CacheConfig(page_size=PAGE, cache_budget=BUDGET,
                       policy="paged_eviction", dtype="bfloat16")
    policy = get_policy(ccfg.policy)
    B, fill_steps = 4, 10
    params = init_params(cfg, jax.random.PRNGKey(seed))
    cache = init_decode_caches(cfg, B, (fill_steps + 1) * CHUNK, policy,
                               ccfg, chunk_tokens=CHUNK)

    @jax.jit
    def fill(params, tokens, cache):
        n_tok = jax.numpy.full((B,), CHUNK, jax.numpy.int32)
        return forward_step(params, cfg, tokens, n_tok, cache, policy, ccfg,
                            use_pallas=True, fused_scores=True)[1]

    tokens = probe_inputs(B, cfg.vocab_size, seed + 1)["mixed"]["tokens"]
    for _ in range(fill_steps):
        cache = fill(params, tokens, cache)
    one = logits_fn(cfg, ccfg, use_pallas=True)
    params_tp = jax.device_put(params, rules.tp_param_shardings(mesh, params))
    cache_tp = jax.device_put(cache, rules.tp_cache_shardings(mesh, cache))
    sharded = logits_fn(cfg, ccfg, use_pallas=True, mesh=mesh,
                        params=params_tp, cache=cache_tp)
    compare_logits(f"{cfg.name} tp={tp} vs tp=1", sharded, one,
                   (params_tp, cache_tp), (params, cache),
                   probe_inputs(B, cfg.vocab_size, seed))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tp", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip tensor-parallel path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.tp:
        print(f"chip_smoke: --tp {args.tp} needs {args.tp} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    say(f"device: {devices[0].device_kind} x {len(devices)}; compile cache "
        f"{enable_compile_cache()}")
    t0 = time.perf_counter()
    (four_chips if args.tp == 4 else one_chip)(args.seed)
    say(f"all checks passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
