"""Operations and bytes of the served model's step and of each attention
kernel, computed from shapes. Counts are of the useful work: padded token
slots, and keys a query cannot see, are not counted, so any implementation
is read against the same work.

A row's visible keys follow PagedEviction at the configured budget: a
query at position ``p`` sees the full pages kept (at most budget/page of
them) plus its own page up to itself.
"""
from __future__ import annotations

from bench import arch

# the family's per-layer counts (``bench/arch/<model_type>.py``)


def matmul_params(cfg: dict) -> int:
    """Weights multiplied per token, every layer, without the LM head."""
    return arch.of(cfg).matmul_params(cfg)


def lm_head_flops(cfg: dict) -> int:
    """One row's logits (the program computes them at one position per row)."""
    return arch.of(cfg).lm_head_flops(cfg)


def attn_flops(cfg: dict, keys: int) -> int:
    """Attention over ``keys`` (query, key) pairs, all heads, one layer."""
    return arch.of(cfg).attn_flops(cfg, keys)


def kv_bytes(cfg: dict, tokens: int, itemsize: int = 2) -> int:
    """The cached state of ``tokens`` tokens, one layer."""
    return arch.of(cfg).kv_bytes(cfg, tokens, itemsize)


def qo_bytes(cfg: dict, queries: int, itemsize: int = 2) -> int:
    """Queries read and outputs written, one layer."""
    return arch.of(cfg).qo_bytes(cfg, queries, itemsize)


def visible_keys(p: int, budget: int, page: int) -> int:
    """Keys a query at position p attends to under PagedEviction."""
    return page * min(p // page, budget // page) + p % page + 1


def chunk_visible_keys(start: int, n: int, budget: int, page: int) -> int:
    """Sum of visible keys over the queries of a prompt chunk [start, start+n)
    (chunks start on page boundaries: the kept pages, then causal)."""
    kept = page * min(start // page, budget // page)
    return n * kept + n * (n + 1) // 2


def decode_row_work(cfg: dict, p: int, budget: int, page: int):
    """(flops, bytes) of the decode kernel for one row at position p, summed
    over layers."""
    keys = visible_keys(p, budget, page)
    L = cfg["num_hidden_layers"]
    return (L * attn_flops(cfg, keys),
            L * (kv_bytes(cfg, keys) + qo_bytes(cfg, 1)))


def chunk_row_work(cfg: dict, start: int, n: int, budget: int, page: int):
    """(flops, bytes) of the chunk-prefill kernel for one row's queries at
    [start, start+n), summed over layers. Bytes: the kept pages and the
    chunk's own keys read once, queries read and outputs written once."""
    L = cfg["num_hidden_layers"]
    kept = page * min(start // page, budget // page)
    return (L * attn_flops(cfg, chunk_visible_keys(start, n, budget, page)),
            L * (kv_bytes(cfg, kept + n) + qo_bytes(cfg, n)))


def step_flops(cfg: dict, rows, budget: int, page: int) -> int:
    """Useful model FLOPs of one step. ``rows``: ("d", p) for a decode row
    at position p, ("p", start, n) for a prompt chunk. Every token pays the
    layer matmuls; each row pays one LM-head projection (the program takes
    logits at each row's last token only); attention counts visible keys."""
    total = 0
    for r in rows:
        if r[0] == "d":
            total += 2 * matmul_params(cfg) + lm_head_flops(cfg)
            total += decode_row_work(cfg, r[1], budget, page)[0]
        else:
            _, start, n = r
            total += 2 * matmul_params(cfg) * n + lm_head_flops(cfg)
            total += chunk_row_work(cfg, start, n, budget, page)[0]
    return total
