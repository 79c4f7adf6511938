"""Distributed DFEP: the paper's one-MapReduce-round-per-iteration scheme
mapped onto ``shard_map`` (DESIGN.md §3).

Sharding model — one device == one Hadoop worker:

  * the *edge* set (and its funding slots) is sharded across the mesh axis;
  * the [V, K] vertex-funding matrix is replicated and reconciled once per
    round with a ``psum`` — this is the shuffle of the paper's MR round,
    and the only cross-worker traffic (plus two tiny [K] reductions);
  * the auction (step 2) runs shard-locally: every edge lives on exactly
    one worker;
  * the coordinator (step 3) is O(K) and replicated — every worker computes
    identical grants (cheaper than a host round-trip).

Semantics match the single-host ``dfep.py`` exactly except that step-1
remainder units are ranked among a vertex's *worker-local* eligible slots
(each worker distributes its own remainders — precisely how per-reducer
iteration order behaves in the Hadoop implementation).

At 1000+ node scale the [V, K] replica itself would be sharded over a
second mesh axis (vertex blocks × psum→reduce_scatter); the round structure
is unchanged. See DESIGN.md §6.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..sharding.env import auto_axes
from .dfep import FREE, DfepConfig, _hash01, finalize
from .graph import Graph


class ShardedGraph(NamedTuple):
    """Edge-sharded graph + per-shard slot layout (device-major leading dim).

    All arrays carry a leading [ndev] axis so a plain ``shard_map`` over
    axis 0 gives every worker its contiguous edge block.
    """
    n_vertices: int
    n_edges: int
    src: jax.Array        # [ndev, E_loc]
    dst: jax.Array        # [ndev, E_loc]
    edge_mask: jax.Array  # [ndev, E_loc]
    slot_edge: jax.Array  # [ndev, 2*E_loc] local edge index of sorted slot
    slot_vertex: jax.Array
    slot_seg_first: jax.Array
    slot_inv: jax.Array


def shard_graph(g: Graph, ndev: int) -> ShardedGraph:
    """Host-side: split edges into ``ndev`` contiguous blocks (padded) and
    build each worker's vertex-sorted slot layout."""
    u, v = np.asarray(g.src), np.asarray(g.dst)
    em = np.asarray(g.edge_mask)
    e_pad = g.e_pad
    e_loc = -(-e_pad // ndev)
    tot = e_loc * ndev
    pu = np.zeros(tot, np.int32); pu[:e_pad] = u
    pv = np.zeros(tot, np.int32); pv[:e_pad] = v
    pm = np.zeros(tot, bool); pm[:e_pad] = em
    pu, pv, pm = (x.reshape(ndev, e_loc) for x in (pu, pv, pm))

    se = np.zeros((ndev, 2 * e_loc), np.int32)
    sv = np.zeros((ndev, 2 * e_loc), np.int32)
    sf = np.zeros((ndev, 2 * e_loc), np.int32)
    si = np.zeros((ndev, 2 * e_loc), np.int32)
    for d in range(ndev):
        slot_vertex = np.concatenate([pu[d], pv[d]])
        slot_edge = np.concatenate([np.arange(e_loc), np.arange(e_loc)]).astype(np.int32)
        order = np.argsort(slot_vertex, kind="stable").astype(np.int32)
        svd = slot_vertex[order].astype(np.int32)
        sed = slot_edge[order]
        first = np.zeros(g.n_vertices, np.int32)
        seen = np.ones(len(svd), bool)
        seen[1:] = svd[1:] != svd[:-1]
        first[svd[seen]] = np.flatnonzero(seen)
        inv = np.empty_like(order)
        inv[order] = np.arange(len(order), dtype=np.int32)
        se[d], sv[d], sf[d], si[d] = sed, svd, first[svd], inv
    return ShardedGraph(g.n_vertices, g.n_edges,
                        jnp.asarray(pu), jnp.asarray(pv), jnp.asarray(pm),
                        jnp.asarray(se), jnp.asarray(sv), jnp.asarray(sf),
                        jnp.asarray(si))


def _sizes_local(owner: jax.Array, k: int) -> jax.Array:
    onehot = owner[:, None] == jnp.arange(k, dtype=jnp.int32)[None, :]
    return jnp.sum(onehot.astype(jnp.int32), axis=0)


def run_dfep_sharded(g: Graph, cfg: DfepConfig, key: jax.Array,
                     mesh: Mesh, axis: str = "data"
                     ) -> tuple[jax.Array, dict]:
    """Run DFEP edge-sharded over ``mesh[axis]``. Returns (owner [E_pad], info)."""
    mesh = auto_axes(mesh)
    ndev = mesh.shape[axis]
    sg = shard_graph(g, ndev)
    v_n, k = g.n_vertices, cfg.k
    e_loc = sg.src.shape[1]
    part_ids = jnp.arange(k, dtype=jnp.int32)

    # initial state (replicated mv, sharded owner)
    starts = jax.random.choice(key, v_n, shape=(k,), replace=False)
    funding = cfg.init_funding if cfg.init_funding is not None else -(-g.n_edges // k)
    mv0 = jnp.zeros((v_n, k), jnp.int32).at[starts, part_ids].set(jnp.int32(funding))
    owner0 = jnp.where(sg.edge_mask, jnp.int32(FREE), jnp.int32(-2))  # [ndev, E_loc]

    def worker(src, dst, emask, s_edge, s_vertex, s_first, s_inv,
               owner, mv, carry_rounds, carry_stall):
        """Body of one round; all args are this worker's shard ([1, ...] squeezed)."""
        src, dst, emask = src[0], dst[0], emask[0]
        s_edge, s_vertex = s_edge[0], s_vertex[0]
        s_first, s_inv = s_first[0], s_inv[0]
        owner = owner[0]

        def one_round(state):
            owner, mv, rounds, stalled = state
            free = owner == FREE
            owned_by = owner[:, None] == part_ids[None, :]
            elig = (free[:, None] | owned_by) & emask[:, None]
            if cfg.variant_c:
                sizes0 = jax.lax.psum(_sizes_local(owner, k), axis)
                mean0 = jnp.sum(sizes0) // k
                poor = sizes0 < (mean0 / cfg.poor_p)
                rich_edge = jnp.where(owner >= 0, ~poor[jnp.clip(owner, 0)], False)
                raid = (rich_edge[:, None] & poor[None, :]
                        & ~owned_by & emask[:, None])
                elig = elig | raid

            eligi = elig.astype(jnp.int32)
            cnt_local = (jnp.zeros((v_n, k), jnp.int32)
                         .at[src].add(eligi).at[dst].add(eligi))
            cnt = jax.lax.psum(cnt_local, axis)                  # MR shuffle #1
            safe_cnt = jnp.maximum(cnt, 1)
            base = mv // safe_cnt
            rem = mv - base * safe_cnt

            elig_slot = eligi[s_edge]
            cum = jnp.cumsum(elig_slot, axis=0)
            exc = cum - elig_slot
            rank = exc - exc[s_first]
            # local eligible count per (vertex, partition) for rotation
            my = jax.lax.axis_index(axis).astype(jnp.int32)
            rot = (_hash01(s_vertex[:, None] * 131 + my,
                           part_ids[None, :], rounds)
                   * safe_cnt[s_vertex].astype(jnp.float32)).astype(jnp.int32)
            rank = (rank + rot) % safe_cnt[s_vertex]
            contrib = elig_slot * (base[s_vertex]
                                   + (rank < rem[s_vertex]).astype(jnp.int32))
            mv_left = jnp.where(cnt > 0, 0, mv)

            contrib_uv = contrib[s_inv]
            cu, cv = contrib_uv[:e_loc], contrib_uv[e_loc:]
            me = cu + cv

            tie = _hash01(jnp.arange(e_loc, dtype=jnp.int32)[:, None]
                          + my * e_loc, part_ids[None, :], rounds)
            score = me.astype(jnp.float32) + tie
            best = jnp.argmax(score, axis=1).astype(jnp.int32)
            best_amt = jnp.take_along_axis(me, best[:, None], axis=1)[:, 0]
            can_buy = (best_amt >= 1) & emask
            bought_free = free & can_buy
            if cfg.variant_c:
                steal = ((~free) & can_buy & poor[best]
                         & (best != owner) & rich_edge)
                paid = bought_free | steal
            else:
                paid = bought_free
            new_owner = jnp.where(paid, best, owner)

            now_owned = new_owner[:, None] == part_ids[None, :]
            pay = (paid[:, None] & now_owned).astype(jnp.int32)
            residual = me - pay
            fu = (cu > 0).astype(jnp.int32)
            fv = (cv > 0).astype(jnp.int32)
            funders = jnp.maximum(fu + fv, 1)
            half = residual // 2
            loser_share = residual // funders
            loser_rem = residual - loser_share * funders
            ref_u = jnp.where(now_owned, half + (residual - 2 * half),
                              fu * (loser_share + loser_rem * fu))
            ref_v = jnp.where(now_owned, half,
                              fv * jnp.where(fu > 0, loser_share,
                                             loser_share + loser_rem))
            dmv = (jnp.zeros((v_n, k), jnp.int32)
                   .at[src].add(ref_u).at[dst].add(ref_v))
            mv_new = mv_left + jax.lax.psum(dmv, axis)           # MR shuffle #2

            # step 3 — replicated coordinator
            sizes = jax.lax.psum(_sizes_local(new_owner, k), axis)
            remaining = jax.lax.psum(
                jnp.sum(jnp.where(new_owner == FREE, 1, 0)), axis)
            grant = jnp.minimum(jnp.int32(cfg.cap),
                                -(-jnp.int32(g.n_edges) // jnp.maximum(sizes, 1)))
            grant = jnp.where(remaining > 0, grant, 0)

            still_free = new_owner == FREE
            fr_local = jnp.zeros((v_n, k), jnp.bool_)
            fr_local = fr_local.at[src].max((cu > 0) & still_free[:, None])
            fr_local = fr_local.at[dst].max((cv > 0) & still_free[:, None])
            owned_mask = now_owned & emask[:, None]
            owned_at = (jnp.zeros((v_n, k), jnp.bool_)
                        .at[src].max(owned_mask).at[dst].max(owned_mask))
            fr = jax.lax.psum(fr_local.astype(jnp.int32), axis) > 0
            owned_any = jax.lax.psum(owned_at.astype(jnp.int32), axis) > 0
            presence = (mv_new > 0) | owned_any
            has_frontier = jnp.any(fr, axis=0)
            presence = jnp.where(has_frontier[None, :], fr, presence)
            pres_i = presence.astype(jnp.int32)
            n_pres = jnp.maximum(jnp.sum(pres_i, axis=0), 1)
            p_base = grant // n_pres
            p_rem = grant - p_base * n_pres
            p_rank = jnp.cumsum(pres_i, axis=0) - pres_i
            p_rot = (_hash01(jnp.full((1,), 7, jnp.int32),
                             part_ids[None, :], rounds)
                     * n_pres.astype(jnp.float32)).astype(jnp.int32)
            p_rank = (p_rank + p_rot) % n_pres[None, :]
            mv_new = mv_new + pres_i * (p_base[None, :]
                                        + (p_rank < p_rem[None, :]).astype(jnp.int32))

            progressed = jax.lax.psum(jnp.sum(jnp.where(paid, 1, 0)), axis) > 0
            return (new_owner, mv_new, rounds + 1,
                    jnp.where(progressed, 0, stalled + 1))

        def cond(state):
            owner, _, rounds, stalled = state
            unsold = jax.lax.psum(jnp.sum(jnp.where(owner == FREE, 1, 0)), axis)
            return ((unsold > 0) & (rounds < cfg.max_rounds)
                    & (stalled < cfg.stall_rounds))

        owner, mv, rounds, stalled = jax.lax.while_loop(
            cond, one_round, (owner, mv, carry_rounds, carry_stall))
        return owner[None], mv, rounds, stalled

    spec_e = P(axis)
    fn = jax.shard_map(
        worker, mesh=mesh,
        in_specs=(spec_e, spec_e, spec_e, spec_e, spec_e, spec_e, spec_e,
                  spec_e, P(), P(), P()),
        out_specs=(spec_e, P(), P(), P()),
        check_vma=False,
    )
    owner, mv, rounds, stalled = jax.jit(fn)(
        sg.src, sg.dst, sg.edge_mask, sg.slot_edge, sg.slot_vertex,
        sg.slot_seg_first, sg.slot_inv, owner0, mv0,
        jnp.int32(0), jnp.int32(0))
    owner_flat = owner.reshape(-1)[:g.e_pad]
    unsold = int(jnp.sum(jnp.where(owner_flat == FREE, 1, 0)))
    if unsold:
        owner_flat = finalize(g, owner_flat, cfg.k)
        owner_flat = jnp.where(g.edge_mask, owner_flat, -2)
    info = {"rounds": int(rounds), "unsold_at_stop": unsold,
            "finalized": bool(unsold), "ndev": ndev}
    return owner_flat, info
