"""Device-side step statistics — the int32 stats vector (DESIGN.md §9).

The serving hot path must never host-callback out of the jitted step, yet
the telemetry layer (``repro.obs``) needs exact eviction/alloc/fork counts
per step. The contract: every :class:`~repro.core.paged_cache.PagedLayerCache`
optionally carries a tiny ``stats`` vector — shape ``(NSTATS,)`` int32 —
and each pool mutator accumulates its event counts into it with pure
``jnp`` scatter-adds as a byproduct of work it already does (the masks
being summed are values the mutators already computed). The unified step
zeroes each layer's vector on entry, so after one step the vector holds
exactly that step's counts; the engine sums the per-layer vectors on
device (``transformer.collect_step_stats``) and reconciles the single
(NSTATS,) array into the host registry once per step.

``stats is None`` disables tracking entirely (``None`` is a static Python
value under tracing, so the disabled path traces to the exact same HLO as
before this module existed — asserted by tests/test_obs.py).

Index semantics (counts are summed over B rows and, at the engine level,
over attention layers):

    PAGES_ALLOCATED   alloc_pages successes (a free page left the free list)
    PAGES_FREED       ref_count reached 0 (a page returned to the free list)
    PAGES_RELEASED    single-reference releases (block-table unmaps + CoW
                      source drops; the clamped decrements of _unref_pages)
    PAGES_ADOPTED     prefix-sharing block-table mappings (ref bumps)
    PAGES_FORKED      copy-on-write forks that actually copied
    PAGES_EVICTED     policy page-level evictions (incl. forced)
    TOKENS_EVICTED    token-level evictions that invalidated a live token
    FORCED_EVICTIONS  fragmentation force-evicts (rollover found no free page)
    TOKENS_WRITTEN    tokens appended that landed (write_token, append_chunk)
    CHUNK_APPEND_FALLBACKS  append_chunk calls that took the per-token loop
                      (a row had to force-evict; 0 under structured eviction)

Conservation identities (exact; tests/test_obs.py checks them against
host-recomputed pool state every step of a churned mixed workload):

    Δ sum(ref_count)  == PAGES_ALLOCATED + PAGES_ADOPTED - PAGES_RELEASED
    Δ free_pages      == PAGES_FREED - PAGES_ALLOCATED
    Δ mapped_entries  == PAGES_ALLOCATED + PAGES_ADOPTED - PAGES_RELEASED
                         (every block-table entry holds exactly one
                         reference: F2 — forks alloc + release in pairs, so
                         they cancel here, as they must)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

PAGES_ALLOCATED = 0
PAGES_FREED = 1
PAGES_RELEASED = 2
PAGES_ADOPTED = 3
PAGES_FORKED = 4
PAGES_EVICTED = 5
TOKENS_EVICTED = 6
FORCED_EVICTIONS = 7
TOKENS_WRITTEN = 8
CHUNK_APPEND_FALLBACKS = 9
NSTATS = 10

STAT_NAMES = (
    "pages_allocated", "pages_freed", "pages_released", "pages_adopted",
    "pages_forked", "pages_evicted", "tokens_evicted", "forced_evictions",
    "tokens_written", "chunk_append_fallbacks",
)


def zeros() -> jax.Array:
    return jnp.zeros((NSTATS,), jnp.int32)


def bump(stats, idx: int, count):
    """stats.at[idx] += sum(count); identity (None) when tracking is off.
    ``count`` may be a bool/int array of any shape — it is summed."""
    if stats is None:
        return None
    return stats.at[idx].add(jnp.sum(count).astype(jnp.int32))


def to_dict(stats) -> dict:
    """Host-side: (NSTATS,) array/ndarray -> {name: int}."""
    return {name: int(stats[i]) for i, name in enumerate(STAT_NAMES)}
