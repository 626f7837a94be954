"""Family ``qwen2``: a decoder-only transformer with grouped-query attention,
rotary positions, RMSNorm, SwiGLU and q/k/v biases (Qwen2, Qwen2.5).

The reference's layers are written from that description and import
nothing of the program. A token's eviction score is mean_h ||V_h|| /
mean_h ||K_h|| over the KV heads (PagedEviction, paper Alg. 1).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.reference import fq, mm

# q/k/v biases N(0, 0.1^2), as the configuration file states
STD = {"bq": 0.1, "bk": 0.1, "bv": 0.1}


def program_config(c: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.configs.base import ModelConfig

    h = c["config"]
    return ModelConfig(
        name=c["name"], arch_type="dense", source=c["source"],
        num_layers=h["num_hidden_layers"], d_model=h["hidden_size"],
        num_heads=h["num_attention_heads"],
        num_kv_heads=h["num_key_value_heads"],
        head_dim=h.get("head_dim") or 0, d_ff=h["intermediate_size"],
        vocab_size=h["vocab_size"], qkv_bias=True,
        rope_theta=float(h["rope_theta"]), norm="rmsnorm", act=h["hidden_act"],
        tie_embeddings=bool(h["tie_word_embeddings"]), dtype=h["torch_dtype"])


# ---- the reference's layers ------------------------------------------------

def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """x: (R, N, heads, hd); rotates pairs (2i, 2i+1) by pos / theta^(2i/hd)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[..., None].astype(jnp.float32) * inv            # (R, N, hd/2)
    c, s = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], -1).reshape(x.shape)


def token_score(k, v):
    """(R, N, KV, hd) keys and values -> (R, N) eviction scores."""
    kn = jnp.mean(jnp.linalg.norm(k, axis=-1), -1)
    vn = jnp.mean(jnp.linalg.norm(v, axis=-1), -1)
    return vn / jnp.maximum(kn, 1e-6)


@functools.partial(jax.jit, static_argnames=("H", "KV", "hd", "eps", "theta",
                                             "quant"))
def _qkv(x, lw, pos, *, H, KV, hd, eps, theta, quant):
    R, N, _ = x.shape
    a = lw["attn"]
    h = _rms(x, lw["norm1"]["scale"], eps)
    q, k, v = (mm(h, a[w], quant) for w in ("wq", "wk", "wv"))
    if "bq" in a:
        q = q + a["bq"].astype(jnp.float32)
        k = k + a["bk"].astype(jnp.float32)
        v = v + a["bv"].astype(jnp.float32)
    q = _rope(q.reshape(R, N, H, hd), pos, theta)
    k = _rope(k.reshape(R, N, KV, hd), pos, theta)
    v = v.reshape(R, N, KV, hd)
    return q, k, v, token_score(k, v)


@functools.partial(jax.jit, static_argnames=("eps", "quant", "qblock"))
def _attn_mlp(x, q, k, v, lw, st, evk, *, eps, quant, qblock):
    R, N, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    kpos = jnp.arange(N)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * qblock, qblock, 1)
        sb = jax.lax.dynamic_slice_in_dim(st, i * qblock, qblock, 1)
        qpos = i * qblock + jnp.arange(qblock)
        qg = qb.reshape(R, qblock, KV, G, hd)
        kk, vv = (fq(k), fq(v)) if quant else (k, v)
        qg = fq(qg) if quant else qg
        s = jnp.einsum("rqkgd,rskd->rkgqs", qg, kk,
                       precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
        seen = (kpos[None, None, :] <= qpos[None, :, None]) & \
            (evk[:, None, :] >= sb[:, :, None])               # (R, qb, N)
        s = jnp.where(seen[:, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        p = fq(p) if quant else p
        o = jnp.einsum("rkgqs,rskd->rqkgd", p, vv,
                       precision=jax.lax.Precision.HIGHEST)
        return o.reshape(R, qblock, H * hd)

    o = jax.lax.map(block, jnp.arange(N // qblock))           # (nb, R, qb, .)
    o = jnp.moveaxis(o, 0, 1).reshape(R, N, H * hd)
    x = x + mm(o, lw["attn"]["wo"], quant)
    m = lw["mlp"]
    h = _rms(x, lw["norm2"]["scale"], eps)
    g = mm(h, m["w_gate"], quant)
    u = mm(h, m["w_up"], quant)
    return x + mm(jax.nn.silu(g) * u, m["w_down"], quant)


QBLOCK = 128        # queries per block of the reference's attention


def _layer_kw(cfg: dict) -> dict:
    return dict(H=cfg["num_attention_heads"], KV=cfg["num_key_value_heads"],
                hd=head_dim(cfg), eps=float(cfg["rms_norm_eps"]),
                theta=float(cfg["rope_theta"]))


def _call(f, *args, **kw):
    return f(*args, **kw)


def _layer(x, lw, pos, visible, kw, quant, call=_call):
    """One layer: ``call(f, *args, **kw)`` runs each jitted part (the
    rehearsal compiles it instead)."""
    q, k, v, ts = call(_qkv, x, lw, pos, quant=quant, **kw)
    st, evk = visible(ts)
    return call(_attn_mlp, x, q, k, v, lw, st, evk, eps=kw["eps"],
                quant=quant, qblock=QBLOCK)


def hidden(params, cfg: dict, tok, pos, visible, *, quant=False):
    """The residual stream after the last layer, (R, N, D) float32."""
    kw = _layer_kw(cfg)
    x = jnp.take(params["embed"], tok, axis=0).astype(jnp.float32)
    stack = params["pattern"][0]
    for layer in range(cfg["num_hidden_layers"]):
        lw = jax.tree.map(lambda a: a[layer], stack)
        x = _layer(x, lw, pos, visible, kw, quant)
    return x


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _logits(h, norm, head, *, eps, quant):
    return mm(_rms(h, norm, eps), head.T, quant)


def logits(params, cfg: dict, h, *, quant=False, call=_call):
    """(R, n, D) final hidden states -> (R, n, V) logits."""
    head = params["embed"] if cfg["tie_word_embeddings"] else params["lm_head"]
    return call(_logits, h, params["final_norm"]["scale"], head,
                eps=float(cfg["rms_norm_eps"]), quant=quant)


def rehearse(params, cfg: dict, x, pos, h, *, quant, call, layer):
    """``hidden``'s layer and ``logits`` run through ``call`` on shapes."""
    lw = jax.tree.map(layer, params["pattern"][0])
    _layer(x, lw, pos, lambda ts: (pos, pos), _layer_kw(cfg), quant, call)
    return logits(params, cfg, h, quant=quant, call=call)


# ---- work counts -----------------------------------------------------------

def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def matmul_params(cfg: dict) -> int:
    """Weights multiplied per token, every layer, without the LM head."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    H, KV, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    per_layer = D * (H + 2 * KV) * hd + H * hd * D + 3 * D * F
    return per_layer * cfg["num_hidden_layers"]


def lm_head_flops(cfg: dict) -> int:
    """One row's logits (the program computes them at one position per row)."""
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def attn_flops(cfg: dict, keys: int) -> int:
    """QK^T and PV over ``keys`` (query, key) pairs, all heads, one layer."""
    return 4 * cfg["num_attention_heads"] * head_dim(cfg) * keys


def kv_bytes(cfg: dict, tokens: int, itemsize: int = 2) -> int:
    """K and V of ``tokens`` cached tokens, one layer."""
    return 2 * tokens * cfg["num_key_value_heads"] * head_dim(cfg) * itemsize


def qo_bytes(cfg: dict, queries: int, itemsize: int = 2) -> int:
    """Queries read and outputs written, one layer."""
    return 2 * queries * cfg["num_attention_heads"] * head_dim(cfg) * itemsize
