"""Tensor-parallel serving (DESIGN.md §11): the shard_map'd unified step
over a (1, tp) mesh must be observationally identical to tp=1 — same greedy
tokens, same eviction victims, same pool metadata, exactly-reconciling
devstats and lineage — while holding ~1/tp of the pool payload per device.

The multi-device tests need >= 4 devices; the CI mesh tier provides them
with ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (set before the
first jax import — see .github/workflows). Under the plain 1-device tier
they skip; the validation tests at the bottom always run."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, CacheConfig, get_arch
from repro.core import devstats
from repro.launch.mesh import make_tp_mesh
from repro.models.transformer import init_model
from repro.obs import ObsConfig
from repro.serving import Engine, SamplingParams
from repro.sharding import rules

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs 4 devices (XLA_FLAGS=--xla_force_host_platform_device_"
           "count=4 before jax import)")


def _make_engine(tp, arch="gemma3-27b", policy="paged_eviction",
                 dtype="float32", obs=None, use_pallas=False, budget=32,
                 page=4, new_tokens=6):
    """Every TP degree runs the SAME reduced(tp=4) config — parity compares
    like with like; only the mesh degree varies."""
    cfg = get_arch(arch).reduced(tp=4)
    params = init_model(jax.random.PRNGKey(0), cfg)
    ccfg = CacheConfig(page_size=page, cache_budget=budget, policy=policy,
                       dtype=dtype)
    return Engine(cfg, params, cache_cfg=ccfg, max_batch=3,
                  max_prompt_len=40, max_new_tokens=new_tokens,
                  sampling=SamplingParams(greedy=True), chunk_size=16,
                  seed=0, tp=tp, use_pallas=use_pallas,
                  obs=obs if obs is not None else ObsConfig())


def _submit_churn(eng, seed=0, n_reqs=5):
    """Shared-prefix workload that exercises adoption, CoW forks, eviction
    and slot reuse (n_reqs > max_batch)."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, eng.cfg.vocab_size, size=16)
    for i in range(n_reqs):
        tail = rng.integers(0, eng.cfg.vocab_size, size=8 + i)
        eng.submit(np.concatenate([shared, tail]).astype(np.int32))


def _run_outputs(eng):
    done = eng.run(max_steps=300)
    return {r.request_id: list(r.output_tokens) for r in done}


def _metadata_arrays(eng):
    """Replicated pool metadata per layer, fetched to host."""
    out = []
    for lc in list(eng.cache.pattern) + list(eng.cache.tail):
        if lc.kv is None:
            continue
        out.append({k: np.asarray(jax.device_get(getattr(lc.kv, k)))
                    for k in ("pos", "score", "block_table", "ref_count",
                              "cur_page", "cur_off")})
    return out


# ---------------------------------------------------------------- parity ---

@needs_mesh
@pytest.mark.parametrize("dtype", ["float32", "int8"])
@pytest.mark.parametrize("policy", ["paged_eviction", "streaming_llm",
                                    "full"])
def test_tp_output_parity(policy, dtype):
    """TP in {1, 2, 4} produce identical greedy tokens on a churned
    shared-prefix workload, for page eviction, token eviction and the
    uncompressed baseline, in both f32 and quantised int8 pools."""
    outs = {}
    for tp in (1, 2, 4):
        eng = _make_engine(tp, policy=policy, dtype=dtype)
        _submit_churn(eng)
        outs[tp] = _run_outputs(eng)
        eng.close()
    assert outs[1] == outs[2], (policy, dtype)
    assert outs[1] == outs[4], (policy, dtype)


@needs_mesh
def test_tp_parity_pallas_kernels():
    """The Pallas split-K decode + G-fold prefill kernels run per-shard on
    the KV-head-sharded pool and still match tp=1 exactly."""
    outs = {}
    for tp in (1, 4):
        eng = _make_engine(tp, use_pallas=True)
        _submit_churn(eng)
        outs[tp] = _run_outputs(eng)
        eng.close()
    assert outs[1] == outs[4]


@needs_mesh
def test_tp_parity_moe():
    """Expert-sharded MoE (mixtral): replicated f32 router + psum'd expert
    outputs keep routing and tokens identical across degrees."""
    outs = {}
    for tp in (1, 4):
        eng = _make_engine(tp, arch="mixtral-8x7b")
        _submit_churn(eng)
        outs[tp] = _run_outputs(eng)
        eng.close()
    assert outs[1] == outs[4]


# ------------------------------------------------- pool state under TP ---

@needs_mesh
def test_tp_pool_bytes_scale():
    """TP=N holds <= 1/N of the tp=1 pool payload on every device (exact
    here: the KV-head dim splits evenly), metadata replicated."""
    sizes = {}
    for tp in (1, 2, 4):
        eng = _make_engine(tp)
        sizes[tp] = eng.pool_bytes()
        eng.close()
    total = sizes[1]["payload_total"]
    for tp in (1, 2, 4):
        assert sizes[tp]["payload_total"] == total
        assert sizes[tp]["per_device_max"] == total // tp, (tp, sizes)
        assert sizes[tp]["devices"] == tp


def _iter_reps(md):
    """Pattern layers are scan-stacked: metadata may carry a leading reps
    dim (ref_count (R, P), block_table (R, B, pages), pos (R, P, page)).
    Yield per-rep {ref_count, block_table, pos} dicts either way."""
    ref = md["ref_count"]
    if ref.ndim == 1:
        yield md
        return
    for r in range(ref.shape[0]):
        yield {k: md[k][r] for k in ("ref_count", "block_table", "pos")}


def _assert_pool_invariants(md, ctx=""):
    """F1-F4 from tests/test_pool_invariants.py over one metadata replica."""
    ref, bt, pos = md["ref_count"], md["block_table"], md["pos"]
    pool_pages = ref.shape[0]
    mapped = bt[bt >= 0]
    for b in range(bt.shape[0]):    # F3: no double-mapping within a request
        row = bt[b][bt[b] >= 0]
        assert len(row) == len(set(row.tolist())), (ctx, b, "double-mapped")
    counts = np.bincount(mapped, minlength=pool_pages)
    np.testing.assert_array_equal(counts, ref,
                                  err_msg=f"{ctx}: refcounts")   # F2
    assert (ref >= 0).all(), (ctx, "refcount underflow")
    assert int((ref > 0).sum()) == len(set(mapped.tolist())), (
        ctx, "conservation")                                      # F1
    assert (pos[ref == 0] == -1).all(), (ctx, "free page holds tokens")  # F4


@needs_mesh
def test_tp_pool_invariants_per_shard():
    """After a churned tp=4 run, EVERY device's replica of the pool
    metadata satisfies F1-F4 and all replicas are bit-identical — the
    allocator ran the same trajectory on all shards."""
    eng = _make_engine(4)
    _submit_churn(eng)
    _run_outputs(eng)
    for li, lc in enumerate(list(eng.cache.pattern) + list(eng.cache.tail)):
        if lc.kv is None:
            continue
        per_dev = {}
        for name in ("ref_count", "block_table", "pos"):
            leaf = getattr(lc.kv, name)
            shards = {s.device.id: np.asarray(s.data)
                      for s in leaf.addressable_shards}
            assert len(shards) == 4, (li, name)
            per_dev[name] = shards
        ref = None
        for dev in sorted(per_dev["ref_count"]):
            md = {name: per_dev[name][dev]
                  for name in ("ref_count", "block_table", "pos")}
            for ri, rep in enumerate(_iter_reps(md)):
                _assert_pool_invariants(
                    rep, ctx=f"layer {li} rep {ri} dev {dev}")
            if ref is None:
                ref = md
            else:
                for name, arr in md.items():
                    np.testing.assert_array_equal(
                        arr, ref[name],
                        err_msg=f"layer {li} dev {dev} {name} diverged")
    eng.close()


@needs_mesh
def test_tp_eviction_victims_identical():
    """The pmean'd page scores make PagedEviction's argmin pick the SAME
    victim on every shard and at every degree: final pos/block_table/
    ref_count match tp=1 exactly, lineage evict/free event counts match."""
    state = {}
    for tp in (1, 4):
        eng = _make_engine(tp, obs=ObsConfig(lineage=True), budget=24,
                           new_tokens=8)
        _submit_churn(eng, n_reqs=6)
        _run_outputs(eng)
        state[tp] = (_metadata_arrays(eng), dict(eng.obs.ledger.counts()))
    md1, led1 = state[1]
    md4, led4 = state[4]
    assert led4 == led1 and led1.get("evict", 0) > 0, (led1, led4)
    assert len(md1) == len(md4)
    for li, (a, b) in enumerate(zip(md1, md4)):
        for name in ("pos", "block_table", "ref_count", "cur_page",
                     "cur_off"):
            np.testing.assert_array_equal(a[name], b[name],
                                          err_msg=f"layer {li} {name}")
        np.testing.assert_allclose(a["score"], b["score"], rtol=1e-5,
                                   atol=1e-6, err_msg=f"layer {li} score")


# ------------------------------------------- devstats / lineage under TP ---

def _host_pool_state(eng):
    ref_sum = free = mapped = 0
    for lc in list(eng.cache.pattern) + list(eng.cache.tail):
        if lc.kv is None:
            continue
        ref = np.asarray(jax.device_get(lc.kv.ref_count))
        bt = np.asarray(jax.device_get(lc.kv.block_table))
        ref_sum += int(ref.sum())
        free += int((ref == 0).sum())
        mapped += int((bt >= 0).sum())
    return ref_sum, free, mapped


@needs_mesh
def test_tp_devstats_reconcile_exactly():
    """PR 8's conservation identities hold EXACTLY at tp=4: the stats
    vector is psum'd from one shard's contribution inside the mapped step,
    so replication cannot double-count pool events."""
    eng = _make_engine(4, budget=24, new_tokens=8)
    _submit_churn(eng, n_reqs=6)
    reg = eng.obs.registry
    prev = _host_pool_state(eng)
    prev_ctr = {n: 0 for n in devstats.STAT_NAMES}
    steps = 0
    while eng.step() and steps < 300:
        steps += 1
        cur = _host_pool_state(eng)
        ctr = {n: reg.counter(f"pool.{n}").value
               for n in devstats.STAT_NAMES}
        d = {n: ctr[n] - prev_ctr[n] for n in ctr}
        assert cur[0] - prev[0] == (d["pages_allocated"] + d["pages_adopted"]
                                    - d["pages_released"]), (steps, d)
        assert cur[1] - prev[1] == d["pages_freed"] - d["pages_allocated"], \
            (steps, d)
        assert cur[2] == cur[0], (steps, cur)
        assert eng._free_pages_est == cur[1], (steps,)
        prev, prev_ctr = cur, ctr
    assert eng._free_pages_est == eng.pool_stats()["free_pages"]
    assert prev_ctr["pages_evicted"] > 0, "workload never evicted"
    eng.close()


@needs_mesh
def test_tp_devstats_match_tp1():
    """The cumulative pool counters after the same workload are identical
    at tp=1 and tp=4."""
    ctrs = {}
    for tp in (1, 4):
        eng = _make_engine(tp, budget=24, new_tokens=8)
        _submit_churn(eng, n_reqs=6)
        _run_outputs(eng)
        reg = eng.obs.registry
        ctrs[tp] = {n: reg.counter(f"pool.{n}").value
                    for n in devstats.STAT_NAMES}
        eng.close()
    assert ctrs[1] == ctrs[4]


@needs_mesh
def test_tp_lineage_reconciles_every_step():
    """The host ledger reconciles exactly against the (replicated) device
    snapshot after every tp=4 step — the snapshot gather reads one logical
    copy, never a concatenation of shards."""
    eng = _make_engine(4, obs=ObsConfig(lineage=True), budget=24,
                       new_tokens=8)
    _submit_churn(eng, n_reqs=6)
    steps = 0
    while eng.step() and steps < 300:
        steps += 1
        snap = jax.device_get(eng._lineage_fn(eng.cache))
        assert eng.obs.ledger.reconcile(snap) == [], f"step {steps}"
    assert eng.obs.ledger.counts().get("evict", 0) > 0
    eng.close()


# ----------------------------------------------- validation (always run) ---

def test_validate_tp_divisibility():
    cfg = get_arch("gemma3-27b").reduced()      # KV=2 at tp=1
    with pytest.raises(ValueError, match="not divisible"):
        rules.validate_tp(cfg, 4)
    rules.validate_tp(get_arch("gemma3-27b").reduced(tp=4), 4)


def test_validate_tp_rejects_non_attn_mixers():
    cfg = ASSIGNED_ARCHS["jamba-1.5-large-398b"].reduced(tp=4)
    with pytest.raises(ValueError, match="attention mixers"):
        rules.validate_tp(cfg, 4)


def test_validate_tp_rejects_cross_attention():
    cfg = ASSIGNED_ARCHS["musicgen-medium"].reduced(tp=4)
    with pytest.raises(ValueError, match="cross-attention"):
        rules.validate_tp(cfg, 4)


def test_reduced_tp_widens_heads():
    for name in ("gemma3-27b", "mixtral-8x7b", "qwen2.5-3b"):
        cfg = get_arch(name).reduced(tp=4)
        assert cfg.num_kv_heads % 4 == 0
        assert cfg.num_heads % 4 == 0
        assert cfg.num_heads % cfg.num_kv_heads == 0


def test_make_tp_mesh_requires_devices():
    with pytest.raises(ValueError, match="devices"):
        make_tp_mesh(len(jax.devices()) + 1)


def test_chip_peaks_table():
    """Roofline peaks come from the table by device_kind; an unlisted
    device is an error, not a silent v5e default."""
    from repro.launch.mesh import V5E, chip_peaks
    assert chip_peaks(V5E)["flops_bf16"] == 197e12
    assert chip_peaks(V5E)["hbm_bw"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        chip_peaks("TPU v9 imaginary")


def test_tp_rejects_regret_taps():
    cfg = get_arch("gemma3-27b").reduced(tp=4)
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices to construct a tp=2 engine")
    params = init_model(jax.random.PRNGKey(0), cfg)
    ccfg = CacheConfig(page_size=4, cache_budget=32,
                       policy="paged_eviction", dtype="float32")
    with pytest.raises(ValueError, match="regret"):
        Engine(cfg, params, cache_cfg=ccfg, max_batch=2, max_prompt_len=32,
               max_new_tokens=4, sampling=SamplingParams(greedy=True),
               chunk_size=16, tp=2, obs=ObsConfig(regret_every=2))


def test_tp_param_specs_shape():
    """Spec builders put the KV/head axis where the engine expects it and
    leave everything else replicated."""
    from jax.sharding import PartitionSpec as P
    cfg = get_arch("gemma3-27b").reduced(tp=4)
    params = jax.eval_shape(lambda k: init_model(k, cfg),
                            jax.random.PRNGKey(0))
    specs = rules.tp_param_specs(params)
    flat_p, _ = jax.tree_util.tree_flatten_with_path(params)
    flat_s = {jax.tree_util.keystr(kp): s for kp, s in
              jax.tree_util.tree_flatten_with_path(
                  specs, is_leaf=lambda x: isinstance(x, P))[0]}
    for kp, leaf in flat_p:
        ks = jax.tree_util.keystr(kp)
        spec = flat_s[ks]
        if "embed" in ks or "lm_head" in ks or "norm" in ks:
            assert spec == P(), (ks, spec)
        if "wo" in ks and "attn" in ks:
            assert rules.TP_AXIS in spec, (ks, spec)
