"""Plain reference of the served model under PagedEviction, and its
lower-precision control.

The reference computes, in float32 at ``highest`` matmul precision, the
logits of the served model over each request's prompt and served tokens,
with the keys each query may see decided by PagedEviction (paper Alg.
1-3) at the configured page size, budget and prefill chunk:

- a token's importance is its family's score (``bench/arch/<model_type>.py``
  ``token_score``), a page's the mean over its tokens;
- the prompt is processed in chunks; after each chunk, while more than
  budget/page full pages are kept, the lowest-scoring one goes;
- each decoded token is written, attended, and when it fills its page and
  more than ``budget`` tokens are kept, the lowest-scoring full page goes.

A page that goes after step s is still seen by the queries of step s.
The layers are the family's (``hidden``, ``logits``); what is here is
shared by every family. Everything is written from the description: it
imports nothing of the program and reads only the weights the benchmark
drew and the tokens the program served. The control (``quant=True``) is
the same computation with every matmul operand rounded to float8 e4m3
(per-tensor absmax scale), the precision step below the configuration's
bfloat16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import arch

F8_MAX = 448.0
BIG = 1 << 30


def fq(x):
    """Round to float8 e4m3 with a per-tensor absmax scale, back to f32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(a, b, quant):
    """float32 matmul at ``highest`` precision; float8 operands if ``quant``."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if quant:
        a, b = fq(a), fq(b)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


@jax.jit
def _pick(z, probes):
    """z: (R, n, V) -> best logit, argmax token, and each probe set's logit."""
    picked = jnp.take_along_axis(z[None], probes[..., None], axis=-1)[..., 0]
    return jnp.max(z, -1), jnp.argmax(z, -1).astype(jnp.int32), picked


def evict_steps(tok_scores: np.ndarray, L: int, *, budget: int, page: int,
                chunk: int):
    """PagedEviction over one request's M positions (L of prompt).
    Returns (ev, st): the step after which each page leaves (BIG: never)
    and the step that processes each position (prompt chunk c is step c,
    decode position p is step ceil(L/chunk) + p - L)."""
    M = len(tok_scores)
    n_pages = -(-M // page)
    full = M // page
    pscore = tok_scores[:full * page].reshape(full, page).mean(-1)
    ev = np.full(n_pages, BIG, np.int64)
    C = -(-L // chunk)
    st = np.concatenate([np.arange(L) // chunk, C + np.arange(M - L)])
    keep_pages = budget // page
    kept: list[int] = []
    for c in range(C):
        s, e = c * chunk, min((c + 1) * chunk, L)
        kept += range(s // page, e // page)
        excess = len(kept) - keep_pages
        if excess > 0:
            for j in sorted(kept, key=lambda j: pscore[j])[:excess]:
                ev[j] = c
                kept.remove(j)
    for p in range(L, M):
        if (p + 1) % page == 0:
            kept.append(p // page)
            if len(kept) * page > budget:
                j = min(kept, key=lambda j: pscore[j])
                ev[j] = C + p - L
                kept.remove(j)
    return ev, st


def _bucket(n: int, to: int = 256) -> int:
    return -(-n // to) * to


def run(params, cfg: dict, cache: dict, seqs, probes=(), *, quant=False):
    """The reference over ``seqs``: [(prompt, served)], all positions at
    once, layer by layer. ``probes``: token sets shaped like the served
    tokens whose logits to report. Returns, per request, arrays over the
    served positions: best logit, argmax token, and each probe's logit."""
    fam = arch.of(cfg)
    page, budget, chunk = cache["page_size"], cache["cache_budget"], \
        cache["chunk_size"]
    R = len(seqs)
    toks = [np.concatenate([p, s[:-1]]).astype(np.int32) for p, s in seqs]
    Ls = [len(p) for p, _ in seqs]
    N = _bucket(max(len(t) for t in toks))
    n_max = _bucket(max(len(s) for _, s in seqs), 128)
    tok = np.zeros((R, N), np.int32)
    for r, t in enumerate(toks):
        tok[r, :len(t)] = t
    pos = jnp.asarray(np.broadcast_to(np.arange(N, dtype=np.int32), (R, N)))

    def visible(ts):
        """One layer's token scores -> (step of each position, step after
        which each position's page leaves)."""
        ts = np.asarray(jax.device_get(ts))
        st = np.zeros((R, N), np.int32)
        evk = np.full((R, N), -1, np.int32)
        for r, t in enumerate(toks):
            ev, s = evict_steps(ts[r, :len(t)], Ls[r], budget=budget,
                                page=page, chunk=chunk)
            st[r, :len(t)] = s
            evk[r, :len(t)] = np.minimum(ev, BIG)[np.arange(len(t)) // page]
        return jnp.asarray(st), jnp.asarray(evk)

    x = fam.hidden(params, cfg, jnp.asarray(tok), pos, visible, quant=quant)
    idx = np.zeros((R, n_max), np.int32)
    pr = np.zeros((max(len(probes), 1), R, n_max), np.int32)
    for r, (p, s) in enumerate(seqs):
        idx[r, :len(s)] = len(p) - 1 + np.arange(len(s))
        for i, ps in enumerate(probes):
            pr[i, r, :len(s)] = ps[r]
    h = jnp.take_along_axis(x, jnp.asarray(idx)[..., None], axis=1)
    del x
    best, arg, picked = jax.device_get(_pick(
        fam.logits(params, cfg, h, quant=quant), jnp.asarray(pr)))
    out = []
    for r, (_, s) in enumerate(seqs):
        n = len(s)
        out.append({"best": best[r, :n], "argmax": arg[r, :n],
                    "probes": [picked[i, r, :n] for i in range(len(probes))]})
    return out


ROWS = 8        # requests per pass of the reference, so that it fits


def served_gaps(params, cfg, cache, seqs):
    """Per request, the gaps (reference's best logit minus its logit of the
    served token) at every served position."""
    out = []
    for i in range(0, len(seqs), ROWS):
        part = seqs[i:i + ROWS]
        res = run(params, cfg, cache, part, probes=[[s for _, s in part]])
        out += [r["best"] - r["probes"][0] for r in res]
    return out


def control_gaps(params, cfg, cache, seqs):
    """The control in the program's place: per request, the reference's gap
    of the token the float8 computation puts first, at the same positions."""
    out = []
    for i in range(0, len(seqs), ROWS):
        part = seqs[i:i + ROWS]
        ctl = run(params, cfg, cache, part, quant=True)
        res = run(params, cfg, cache, part,
                  probes=[[c["argmax"] for c in ctl]])
        out += [r["best"] - r["probes"][0] for r in res]
    return out
