"""Mixture-of-Experts MLP (Mixtral/Jamba style): top-k softmax router with
sort-based capacity dispatch (static shapes, drop-on-overflow).

Dispatch is the TPU-friendly sort-based scheme (cf. MaxText): tokens are
ranked within their (example, expert) group via cummax-over-run-starts,
tokens beyond ``capacity`` are dropped (their residual path passes through
untouched), and experts run as one batched einsum over a stacked
(B, E, capacity, D) buffer.

Distribution (§Perf mixtral/jamba iterations — see EXPERIMENTS.md):
  * the whole dispatch -> experts -> combine block runs inside a
    ``jax.shard_map`` over the data axes (model axis left AUTO): under
    plain GSPMD propagation the scatter/gather pair was materialized
    REPLICATED over data in f32 (measured 68.7 GB/device tensors on jamba
    train); manual data sharding makes that impossible by construction.
  * expert weights stay tensor-sharded (d_ff over "model") inside the
    auto region; expert-parallel over a factored mesh axis is a further
    variant.
  * capacity is per-example so ranking never crosses the batch dim.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.common import activation, dense_init


class MoEStats(NamedTuple):
    load: jax.Array          # (E,) fraction of routed assignments per expert
    dropped: jax.Array       # () fraction of assignments dropped by capacity
    aux_loss: jax.Array      # () load-balance auxiliary loss (Switch-style)


def _shard_map(f, mesh, in_specs, out_specs, manual_axes):
    """``jax.shard_map`` manual over ``manual_axes`` (the rest stay auto),
    replication unchecked."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         axis_names=set(manual_axes), check_vma=False)


def init_moe(key, cfg: ModelConfig):
    dt = jnp.float32 if cfg.dtype == "float32" else jnp.bfloat16
    E, D, F = cfg.num_experts, cfg.d_model, cfg.d_ff
    kr, kg, ku, kd = jax.random.split(key, 4)
    init_e = lambda k, i, o: jax.vmap(
        lambda kk: dense_init(kk, i, o, dt))(jax.random.split(k, E))
    return {
        "router": dense_init(kr, D, E, jnp.float32),
        "w_gate": init_e(kg, D, F),     # (E, D, F)
        "w_up": init_e(ku, D, F),       # (E, D, F)
        "w_down": init_e(kd, F, D),     # (E, F, D)
    }


def moe_capacity(cfg: ModelConfig, tokens_per_group: int) -> int:
    per_expert = tokens_per_group * cfg.num_experts_per_tok / cfg.num_experts
    cap = int(cfg.moe_capacity_factor * per_expert)
    return max(cap - cap % -8, 8)  # round up to a multiple of 8 (TPU lanes)


def _rank_in_expert(flat_e, E: int):
    """flat_e: (B, A) expert id per assignment -> (B, A) rank of each
    assignment within its expert group (per example).

    Sort-based: argsort by expert id groups assignments; rank-within-run
    via cummax of run starts; scatter ranks back. O(B*A) memory — no
    (tokens x experts) cumsum, no cross-shard dependency.
    """
    B, A = flat_e.shape
    perm = jnp.argsort(flat_e, axis=1, stable=True)          # (B, A)
    sorted_e = jnp.take_along_axis(flat_e, perm, axis=1)
    iota = jnp.broadcast_to(jnp.arange(A, dtype=jnp.int32), (B, A))
    start = jnp.concatenate(
        [jnp.ones((B, 1), bool), sorted_e[:, 1:] != sorted_e[:, :-1]], axis=1)
    run_base = jax.lax.cummax(jnp.where(start, iota, -1), axis=1)
    rank_sorted = iota - run_base                            # (B, A)
    rank = jnp.zeros_like(rank_sorted).at[
        jnp.arange(B)[:, None], perm].set(rank_sorted)
    return rank


def _moe_block(params, x, *, cfg: ModelConfig, cap: int, psum_axis=None):
    """The full dispatch -> experts -> combine on a (local) batch.

    x: (B, S, D) -> (out, load (E,), dropped (), aux ()).
    ``psum_axis``: manual-mesh axis name(s) holding the F shards of the
    expert weights — the partial expert outputs are explicitly
    psum-reduced over it (fully-manual Megatron-style schedule)."""
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    A = S * K

    logits = (x.astype(jnp.float32) @ params["router"])       # (B, S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_p, topk_e = jax.lax.top_k(probs, K)                  # (B, S, K)
    topk_p = topk_p / jnp.maximum(jnp.sum(topk_p, axis=-1, keepdims=True), 1e-9)

    flat_e = topk_e.reshape(B, A)
    rank = _rank_in_expert(flat_e, E)
    keep = rank < cap                                         # (B, A)
    dst = jnp.where(keep, flat_e * cap + rank, E * cap)       # drop slot

    # ---- dispatch: (B, E*cap + 1, D) scatter --------------------------------
    token_of = jnp.arange(A, dtype=jnp.int32) // K            # (A,)
    src = x[:, token_of, :]                                   # (B, A, D)
    buf = jnp.zeros((B, E * cap + 1, D), x.dtype).at[
        jnp.arange(B)[:, None], dst].set(src)
    buf = buf[:, :-1, :].reshape(B, E, cap, D)

    # ---- batched expert compute (F stays model-sharded: auto axis) ----------
    act = activation(cfg.act)
    h = act(jnp.einsum("becd,edf->becf", buf, params["w_gate"])) * \
        jnp.einsum("becd,edf->becf", buf, params["w_up"])
    # f32 accumulator for the cross-shard partial sum
    eout = jnp.einsum("becf,efd->becd", h, params["w_down"],
                      preferred_element_type=jnp.float32)
    if psum_axis is not None:
        eout = jax.lax.psum(eout, axis_name=psum_axis)

    # ---- combine (vmapped 1-D take keeps gather indices (B, A)) -------------
    eflat = eout.astype(x.dtype).reshape(B, E * cap, D)
    safe = jnp.minimum(dst, E * cap - 1)
    gathered = jax.vmap(lambda e, s: jnp.take(e, s, axis=0))(eflat, safe)
    gathered = jnp.where(keep[..., None], gathered, 0.0)
    weighted = gathered * topk_p.reshape(B, A, 1).astype(x.dtype)
    out = jnp.sum(weighted.reshape(B, S, K, D), axis=2).astype(x.dtype)

    load = jnp.mean(jax.nn.one_hot(topk_e, E, dtype=jnp.float32), axis=(0, 1, 2))
    importance_frac = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(load * importance_frac)
    dropped = 1.0 - jnp.mean(keep.astype(jnp.float32))
    return out, load, dropped, aux


def _moe_block_ep(params, x, *, cfg: ModelConfig, cap: int, ep: int):
    """Expert-parallel block (inside a fully-manual shard_map region).

    x: LOCAL (B_loc, S, D); params LOCAL: w_gate/w_up (E_loc, D, F_loc),
    w_down (E_loc, F_loc, D), router replicated. Tokens reach their expert
    owner via all-to-all over the "expert" axis; d_ff psums over "tp"."""
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    E_loc = E // ep
    A = S * K

    logits = (x.astype(jnp.float32) @ params["router"])       # (B, S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_p, topk_e = jax.lax.top_k(probs, K)
    topk_p = topk_p / jnp.maximum(jnp.sum(topk_p, axis=-1, keepdims=True), 1e-9)

    flat_e = topk_e.reshape(B, A)
    rank = _rank_in_expert(flat_e, E)
    keep = rank < cap
    dst = jnp.where(keep, flat_e * cap + rank, E * cap)

    token_of = jnp.arange(A, dtype=jnp.int32) // K
    src = x[:, token_of, :]
    buf = jnp.zeros((B, E * cap + 1, D), x.dtype).at[
        jnp.arange(B)[:, None], dst].set(src)
    buf = buf[:, :-1, :].reshape(B, E, cap, D)

    # ---- forward all-to-all: deliver tokens to expert owners ----------------
    t = jnp.moveaxis(buf, 1, 0).reshape(ep, E_loc, B, cap, D)
    t = jax.lax.all_to_all(t, "expert", split_axis=0, concat_axis=0)
    h_in = jnp.moveaxis(t, 1, 0).reshape(E_loc, ep * B * cap, D)

    act = activation(cfg.act)
    h = act(jnp.einsum("end,edf->enf", h_in, params["w_gate"])) * \
        jnp.einsum("end,edf->enf", h_in, params["w_up"])
    eo = jnp.einsum("enf,efd->end", h, params["w_down"],
                    preferred_element_type=jnp.float32)
    eo = jax.lax.psum(eo, axis_name="tp").astype(x.dtype)

    # ---- reverse all-to-all --------------------------------------------------
    eo = jnp.moveaxis(eo.reshape(E_loc, ep, B, cap, D), 1, 0)
    eo = jax.lax.all_to_all(eo, "expert", split_axis=0, concat_axis=0)
    eout = jnp.moveaxis(eo.reshape(E, B, cap, D), 1, 0)       # (B, E, cap, D)

    eflat = eout.reshape(B, E * cap, D)
    safe = jnp.minimum(dst, E * cap - 1)
    gathered = jax.vmap(lambda e, s: jnp.take(e, s, axis=0))(eflat, safe)
    gathered = jnp.where(keep[..., None], gathered, 0.0)
    weighted = gathered * topk_p.reshape(B, A, 1).astype(x.dtype)
    out = jnp.sum(weighted.reshape(B, S, K, D), axis=2).astype(x.dtype)

    load = jnp.mean(jax.nn.one_hot(topk_e, E, dtype=jnp.float32), axis=(0, 1, 2))
    importance_frac = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(load * importance_frac)
    dropped = 1.0 - jnp.mean(keep.astype(jnp.float32))
    return out, load, dropped, aux


def moe_forward(params, cfg: ModelConfig, x, capacity: int | None = None,
                ac=None):
    """x: (B, S, D) -> (out (B, S, D), MoEStats). ``ac``: activation
    constraint from rules.activation_constraint — when it carries a mesh
    and the batch divides the data axes, the block runs under shard_map
    (manual over data, auto over model)."""
    B, S, D = x.shape
    cap = capacity or moe_capacity(cfg, S)
    mesh = getattr(ac, "mesh", None)
    bax = getattr(ac, "batch_axes", None)
    block = partial(_moe_block, cfg=cfg, cap=cap)

    F = params["w_gate"].shape[-1]
    E = cfg.num_experts
    ep_ok = (mesh is not None and "expert" in mesh.shape
             and E % mesh.shape["expert"] == 0
             and F % mesh.shape["tp"] == 0)
    if mesh is not None and bax is not None and ep_ok:
        # --- expert parallelism: tokens travel, experts stay -----------------
        # batch sharded over (data..., expert) = finer DP; each shard routes
        # its local tokens, all-to-all over "expert" delivers each expert
        # owner its tokens, expert compute tp-shards d_ff, reverse a2a +
        # local combine. Dense layers around this region are untouched
        # (their weights shard over the combined ("expert","tp") axes).
        manual = (bax if isinstance(bax, tuple) else (bax,)) + ("expert", "tp")
        ep = mesh.shape["expert"]
        bax_e = (bax if isinstance(bax, tuple) else (bax,)) + ("expert",)

        def local(p, xl):
            out, load, dropped, aux = _moe_block_ep(
                p, xl, cfg=cfg, cap=cap, ep=ep)
            dp = manual[:-2] + ("expert",)
            load = jax.lax.pmean(load, axis_name=dp)
            dropped = jax.lax.pmean(dropped, axis_name=dp)
            aux = jax.lax.pmean(aux, axis_name=dp)
            return out, load, dropped, aux

        pspec = {
            "router": P(),
            "w_gate": P("expert", None, "tp"),
            "w_up": P("expert", None, "tp"),
            "w_down": P("expert", "tp", None),
        }
        out, load, dropped, aux = _shard_map(
            local, mesh,
            in_specs=(pspec, P(bax_e, None, None)),
            out_specs=(P(bax_e, None, None), P(), P(), P()),
            manual_axes=manual)(params, x)
        return out, MoEStats(load, dropped, aux)

    model_ok = (mesh is not None and "model" in mesh.shape
                and F % mesh.shape["model"] == 0)
    if mesh is not None and bax is not None and model_ok:
        # fully-manual region: data AND model manual; expert weights arrive
        # F-sharded; the partial-sum reduction is an explicit f32 psum
        manual = (bax if isinstance(bax, tuple) else (bax,)) + ("model",)
        block_m = partial(_moe_block, cfg=cfg, cap=cap, psum_axis="model")

        def local(p, xl):
            out, load, dropped, aux = block_m(p, xl)
            load = jax.lax.pmean(load, axis_name=manual[:-1])
            dropped = jax.lax.pmean(dropped, axis_name=manual[:-1])
            aux = jax.lax.pmean(aux, axis_name=manual[:-1])
            return out, load, dropped, aux

        pspec = {
            "router": P(),
            "w_gate": P(None, None, "model"),
            "w_up": P(None, None, "model"),
            "w_down": P(None, "model", None),
        }
        out, load, dropped, aux = _shard_map(
            local, mesh,
            in_specs=(pspec, P(bax, None, None)),
            out_specs=(P(bax, None, None), P(), P(), P()),
            manual_axes=manual)(params, x)
        return out, MoEStats(load, dropped, aux)

    out, load, dropped, aux = block(params, x)
    return out, MoEStats(load, dropped, aux)


def moe_forward_decode(params, cfg: ModelConfig, x, tp_axis=None):
    """Single-token MoE (B, D): dense all-expert combine — for decode
    batches every expert's weights are read anyway (memory-bound), and the
    gather/scatter latency is avoided.

    Under tensor parallelism every expert's d_ff is sharded over ``tp_axis``
    (w_gate/w_up on F, w_down's F contraction); the router runs on the
    replicated input in f32 so routing/gates are identical on every shard,
    and the partial expert outputs are psum'd before the gate combine."""
    B, D = x.shape
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    logits = x.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    topk_p, topk_e = jax.lax.top_k(probs, K)
    topk_p = topk_p / jnp.maximum(jnp.sum(topk_p, axis=-1, keepdims=True), 1e-9)
    gate = jnp.zeros((B, E), jnp.float32)
    gate = gate.at[jnp.arange(B)[:, None], topk_e].set(topk_p)   # (B, E)

    act = activation(cfg.act)
    h = act(jnp.einsum("bd,edf->ebf", x, params["w_gate"])) * \
        jnp.einsum("bd,edf->ebf", x, params["w_up"])
    eout = jnp.einsum("ebf,efd->ebd", h, params["w_down"])       # (E, B, D)
    if tp_axis is not None:
        eout = jax.lax.psum(eout.astype(jnp.float32), tp_axis)
    out = jnp.einsum("ebd,be->bd", eout.astype(jnp.float32), gate)
    return out.astype(x.dtype)
