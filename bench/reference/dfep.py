"""DFEP (funding-based edge partitioning, arXiv:1403.6270 Algorithms 3-6)
re-run on the host CPU, for an edge-by-edge check of the program's owner
array.

Written from the paper's rules, round by round, over the canonical edge
list; it shares no code with the program.  It runs as one jitted loop on
JAX's CPU backend because the same rounds in NumPy take longer than a
benchmark window at the sizes that are timed.

The auction is integer arithmetic; the only floats are a stateless hash
tie-break in [0, 1), and they are float32 by the partitioner's own
definition.  ``precision="bfloat16"`` computes those floats in bfloat16:
the control, which must disagree with a correct run.

Conventions shared with the program: edge ids are positions in the
canonical edge list (``graphs.canonical``); a vertex's incident edges are
ranked u-sides first, then v-sides, each in edge-id order, rotated by the
hash; the K starting vertices are
``jax.random.choice(key, V, (K,), replace=False)``.

One round (Algorithms 4-6):
  1. every vertex splits each partition's units over its eligible edges
     (free, or already that partition's): ``units // n`` each, the
     remainder one unit each to the first edges of the rotated ranking;
  2. each free edge goes to the partition committing the most units (at
     least one; ties by the hash); the buyer pays one unit and its rest
     returns half to each endpoint (odd unit to u), losers' units return
     to the endpoints that funded them;
  3. each partition is granted ``min(cap, ceil(|E| / size))`` units,
     spread over the vertices where it funded a still-free edge (else
     everywhere it is present), the remainder by rotated rank.
Rounds repeat until no edge is free, 256 rounds pass without a sale, or
10,000 rounds; leftover free edges then join the least-loaded partition
next to them, over 64 sweeps.
"""
from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

FREE = -1


def _cpu():
    return jax.devices("cpu")[0]


def _hash01(e, i, r, fdt):
    """Per-(edge or vertex, partition, round) tie-break in [0, 1)."""
    u32 = jnp.uint32
    x = ((e.astype(u32) * u32(0x9E3779B1))
         ^ (i.astype(u32) * u32(0x85EBCA77))
         ^ (r.astype(u32) * u32(0xC2B2AE3D)))
    x = (x ^ (x >> 15)) * u32(0x2C1B3C6D)
    x = (x ^ (x >> 12)) * u32(0x297A2D39)
    x = x ^ (x >> 15)
    return x.astype(jnp.float32).astype(fdt) / jnp.asarray(2.0 ** 32, fdt)


@partial(jax.jit, static_argnames=("n", "k", "cap", "max_rounds",
                                   "stall_rounds", "fdt"))
def _run(u, v, seg_id, edge_s, order, inv, seg_first, starts, *, n, k,
         cap,
         max_rounds, stall_rounds, fdt):
    e = u.shape[0]
    parts = jnp.arange(k, dtype=jnp.int32)
    vid = jnp.arange(n, dtype=jnp.int32)[:, None]
    eid = jnp.arange(e, dtype=jnp.int32)[:, None]

    def vsum(at_u, at_v, op=jax.ops.segment_sum):
        """[V, K] per-vertex reduction of values placed at each edge's
        u and v endpoints (``order``: u-sides ++ v-sides -> sorted slots)."""
        vals = jnp.concatenate([at_u, at_v])[order]
        return op(vals, seg_id, num_segments=n, indices_are_sorted=True)

    def round_(owner, mv, rounds):
        free = owner == FREE
        owned_by = owner[:, None] == parts[None, :]
        eligi = (free[:, None] | owned_by).astype(jnp.int32)       # [E, K]
        cnt = vsum(eligi, eligi)                                   # [V, K]
        safe = jnp.maximum(cnt, 1)
        base = mv // safe
        rem = mv - base * safe
        elig_slot = eligi[edge_s]                                  # [2E, K]
        exc = jnp.cumsum(elig_slot, axis=0) - elig_slot
        rank = exc - exc[seg_first]
        rot = (_hash01(vid, parts[None, :], rounds, fdt)
               * safe.astype(fdt)).astype(jnp.int32)               # [V, K]
        rank = (rank + rot[seg_id]) % safe[seg_id]
        contrib = elig_slot * (base[seg_id]
                               + (rank < rem[seg_id]).astype(jnp.int32))
        mv_left = jnp.where(cnt > 0, 0, mv)
        back = contrib[inv]
        cu, cv = back[:e], back[e:]
        me = cu + cv                                               # [E, K]

        tie = _hash01(eid, parts[None, :], rounds, fdt)
        score = me.astype(fdt) + tie
        best = jnp.argmax(score, axis=1).astype(jnp.int32)
        best_amt = jnp.take_along_axis(me, best[:, None], axis=1)[:, 0]
        paid = free & (best_amt >= 1)
        new_owner = jnp.where(paid, best, owner)

        now_owned = new_owner[:, None] == parts[None, :]
        residual = me - (paid[:, None] & now_owned).astype(jnp.int32)
        fu = (cu > 0).astype(jnp.int32)
        fv = (cv > 0).astype(jnp.int32)
        funders = jnp.maximum(fu + fv, 1)
        half = residual // 2
        share = residual // funders
        odd = residual - share * funders
        ref_u = jnp.where(now_owned, residual - half, fu * (share + odd * fu))
        ref_v = jnp.where(now_owned, half,
                          fv * jnp.where(fu > 0, share, share + odd))
        mv_new = mv_left + vsum(ref_u, ref_v)

        sizes = jnp.sum(now_owned, axis=0, dtype=jnp.int32)
        any_free = jnp.any(new_owner == FREE)
        grant = jnp.minimum(cap, -(-e // jnp.maximum(sizes, 1)))
        grant = jnp.where(any_free, grant, 0)
        still = (new_owner == FREE)[:, None]
        frontier = vsum((cu > 0) & still, (cv > 0) & still,
                        jax.ops.segment_max)
        owned_at = vsum(now_owned, now_owned, jax.ops.segment_max)
        presence = (mv_new > 0) | owned_at
        presence = jnp.where(jnp.any(frontier, axis=0)[None, :], frontier,
                             presence)
        pres = presence.astype(jnp.int32)
        n_pres = jnp.maximum(jnp.sum(pres, axis=0), 1)
        p_base = grant // n_pres
        p_rem = grant - p_base * n_pres
        p_rot = (_hash01(jnp.full((1, 1), 7, jnp.int32), parts[None, :],
                         rounds, fdt)
                 * n_pres.astype(fdt)).astype(jnp.int32)
        p_rank = (jnp.cumsum(pres, axis=0) - pres + p_rot) % n_pres[None, :]
        mv_new = mv_new + pres * (p_base[None, :]
                                  + (p_rank < p_rem[None, :]).astype(
                                      jnp.int32))
        return new_owner, mv_new, jnp.any(paid)

    owner0 = jnp.full((e,), FREE, jnp.int32)
    mv0 = jnp.zeros((n, k), jnp.int32).at[starts, parts].set(
        jnp.int32(-(-e // k)))

    def cond(c):
        owner, _, rounds, stalled = c
        return (jnp.any(owner == FREE) & (rounds < max_rounds)
                & (stalled < stall_rounds))

    def body(c):
        owner, mv, rounds, stalled = c
        owner, mv, sold = round_(owner, mv, rounds)
        return owner, mv, rounds + 1, jnp.where(sold, 0, stalled + 1)

    owner, _, rounds, _ = jax.lax.while_loop(
        cond, body, (owner0, mv0, jnp.int32(0), jnp.int32(0)))
    unsold = jnp.sum(owner == FREE)

    def sweep(_, own):
        sizes = jnp.sum(own[:, None] == parts[None, :], axis=0)
        load = jnp.where(own >= 0, sizes[jnp.clip(own, 0)].astype(
            jnp.float32) * (k + 1) + own.astype(jnp.float32), jnp.inf)
        best = jnp.full((n,), jnp.inf).at[u].min(load).at[v].min(load)
        cand = jnp.minimum(best[u], best[v])
        cand = jnp.where(jnp.isfinite(cand),
                         (cand % (k + 1)).astype(jnp.int32), -1)
        return jnp.where((own == FREE) & (cand >= 0), cand, own)

    final = jax.lax.fori_loop(0, 64, sweep, owner)
    final = jnp.where(final == FREE, 0, final)
    owner = jnp.where(unsold > 0, final, owner)
    return owner, rounds, unsold


def starts_of(key: int, n_vertices: int, k: int) -> np.ndarray:
    """The K distinct starting vertices Algorithm 3 draws from ``key``."""
    with jax.default_device(_cpu()):
        return np.asarray(jax.random.choice(
            jax.random.key(key), n_vertices, shape=(k,), replace=False))


def partition(n: int, u: np.ndarray, v: np.ndarray, k: int, key: int, *,
              cap: int = 10, max_rounds: int = 10_000,
              stall_rounds: int = 256, precision: str = "float32"
              ) -> tuple[np.ndarray, dict]:
    """(owner [E], info) for the canonical edge list (u, v)."""
    fdt = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[precision]
    u = np.asarray(u, np.int32)
    v = np.asarray(v, np.int32)
    e = len(u)
    vert = np.concatenate([u, v])
    order = np.argsort(vert, kind="stable").astype(np.int32)
    seg_id = vert[order]
    edge_s = np.concatenate([np.arange(e), np.arange(e)])[order]
    inv = np.empty_like(order)
    inv[order] = np.arange(2 * e, dtype=np.int32)
    first = np.zeros(n, np.int32)
    new_seg = np.ones(2 * e, bool)
    new_seg[1:] = seg_id[1:] != seg_id[:-1]
    first[seg_id[new_seg]] = np.flatnonzero(new_seg)
    seg_first = first[seg_id]
    starts = starts_of(key, n, k)
    cpu = _cpu()
    args = [jax.device_put(np.asarray(a, np.int32), cpu)
            for a in (u, v, seg_id, edge_s, order, inv, seg_first, starts)]
    owner, rounds, unsold = _run(*args, n=n, k=k, cap=cap,
                                 max_rounds=max_rounds,
                                 stall_rounds=stall_rounds, fdt=fdt)
    unsold = int(unsold)
    return np.asarray(owner).astype(np.int64), {
        "rounds": int(rounds), "unsold_at_stop": unsold,
        "finalized": bool(unsold)}
