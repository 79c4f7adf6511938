"""Pure-jnp oracles for every Pallas kernel in this package."""
from __future__ import annotations

import jax.numpy as jnp


def cumsum_lanes(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive cumsum along axis 0 of a [S, K] array (any int/float dtype).

    Oracle for ``lane_cumsum`` — the DFEP step-1 rank hotspot (the segmented
    rank is this cumsum followed by a gather-subtract at segment starts).
    """
    return jnp.cumsum(x, axis=0)


def kreduce_min(state: jnp.ndarray, member: jnp.ndarray) -> jnp.ndarray:
    """Masked min over axis 0: [K, V] x [K, V] bool -> [V].

    Oracle for ``frontier_min`` — the ETSCH aggregation phase (reconcile
    frontier-vertex replicas with a min reduce).
    """
    big = jnp.asarray(jnp.inf, state.dtype)
    return jnp.min(jnp.where(member, state, big), axis=0)


def minplus_relax(dist: jnp.ndarray, src: jnp.ndarray, dst: jnp.ndarray,
                  mask: jnp.ndarray, cost: float = 1.0) -> jnp.ndarray:
    """One undirected min-plus relaxation sweep: for each edge (u, v),
    out[v] = min(out[v], dist[u]+cost) and out[u] = min(out[u], dist[v]+cost).

    Oracle for ``minplus_sweep`` — the ETSCH local-computation phase.
    dist [V] float; src/dst [E] int32; mask [E] bool.
    """
    big = jnp.asarray(jnp.inf, dist.dtype)
    cu = jnp.where(mask, dist[src] + cost, big)
    cv = jnp.where(mask, dist[dst] + cost, big)
    out = dist.at[dst].min(cu)
    out = out.at[src].min(cv)
    return out
