"""DFEP — Distributed Funding-based Edge Partitioning (paper §IV) in JAX.

Fully vectorised re-expression of Algorithms 3–6. Funding is kept in
**integer units** — the paper prices every edge at exactly "one unit" and
speaks of units throughout; integer arithmetic is also what keeps the
auction alive: with real-valued equal splits the diffusion equalises every
bid *just below* the 1-unit threshold and the market freezes (we verified
this empirically — max bid 0.77 with 180k liquid units), whereas integer
division with remainder-to-first-edges concentrates at least one whole unit
somewhere and the endgame always progresses.

State per round:
  * ``mv``  [V, K] int32 — units partition *i* holds at vertex *v*;
  * edge commitments are transient within a round (losers refunded, the
    winner's residual flows to the edge endpoints — Algorithm 5).

One round == the paper's (step 1, step 2, step 3):
  step 1  every vertex spreads each partition's units over incident
          *eligible* edges (free, or owned by that partition; DFEP-C
          additionally lets "poor" partitions bid on "rich" edges):
          ``base = mv // n_eligible`` per edge, remainder one extra unit to
          the first ``mv %% n_eligible`` eligible edges in CSR order;
  step 2  every free edge is sold to the highest bidder with ≥ 1 unit
          (ties broken by a per-round hash), winner pays 1, residual splits
          half/half (odd unit to the lower endpoint), losers refunded
          equally over their funding endpoints (odd unit to the first);
  step 3  the coordinator grants each partition ``min(cap, ceil(mean/size))``
          units, one unit each to that many of its presence vertices.

Hardware adaptation (DESIGN.md §3): both endpoint copies of every edge
compute the auction deterministically — the paper's single-MapReduce-round
trick — which here becomes dense [E, K] arithmetic plus a handful of
``segment_sum``-style scatters per round (the only "shuffles").
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from .graph import Graph

FREE = -1  # owner value for unsold edges


class Slots(NamedTuple):
    """Directed slot layout: 2 slots per undirected edge (u-side, v-side),
    sorted by slot vertex so per-vertex ranks are a segmented cumsum."""
    edge: jax.Array        # [2E] int32 — edge id of sorted slot
    vertex: jax.Array      # [2E] int32 — vertex of sorted slot
    seg_first: jax.Array   # [2E] int32 — sorted-index of this vertex's first slot
    inv: jax.Array         # [2E] int32 — sorted idx of (u-sides ++ v-sides) slot


def build_slots(g: Graph) -> Slots:
    u = np.asarray(g.src)
    v = np.asarray(g.dst)
    e = g.e_pad
    slot_vertex = np.concatenate([u, v])
    slot_edge = np.concatenate([np.arange(e), np.arange(e)]).astype(np.int32)
    order = np.argsort(slot_vertex, kind="stable").astype(np.int32)
    sv = slot_vertex[order].astype(np.int32)
    se = slot_edge[order]
    # first sorted index of each vertex segment
    first_of_vertex = np.zeros(g.n_vertices, np.int32)
    seen = np.ones(len(sv), bool)
    seen[1:] = sv[1:] != sv[:-1]
    first_of_vertex[sv[seen]] = np.flatnonzero(seen)
    seg_first = first_of_vertex[sv]
    inv = np.empty_like(order)
    inv[order] = np.arange(len(order), dtype=np.int32)
    return Slots(jnp.asarray(se), jnp.asarray(sv), jnp.asarray(seg_first),
                 jnp.asarray(inv))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class DfepState:
    owner: jax.Array     # [E] int32, FREE where unsold (padding slots: -2)
    mv: jax.Array        # [V, K] int32 vertex funding
    rounds: jax.Array    # scalar int32
    stalled: jax.Array   # scalar int32 — rounds without progress

    def tree_flatten(self):
        return (self.owner, self.mv, self.rounds, self.stalled), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@dataclasses.dataclass(frozen=True)
class DfepConfig:
    k: int                       # number of partitions
    cap: int = 10                # per-round funding cap (paper: 10)
    variant_c: bool = False      # DFEP-C: poor partitions may raid rich ones
    poor_p: float = 2.0          # poor iff size < mean/p  (paper's parameter p)
    max_rounds: int = 10_000
    stall_rounds: int = 256      # no-progress rounds before bailing out
    init_funding: int | None = None  # default ceil(|E|/K) (paper §IV)


def init_state(g: Graph, cfg: DfepConfig, key: jax.Array) -> DfepState:
    """Algorithm 3: K random distinct starting vertices, ceil(|E|/K) units."""
    k = cfg.k
    starts = jax.random.choice(key, g.n_vertices, shape=(k,), replace=False)
    funding = cfg.init_funding if cfg.init_funding is not None else -(-g.n_edges // k)
    mv = jnp.zeros((g.n_vertices, k), jnp.int32)
    mv = mv.at[starts, jnp.arange(k)].set(jnp.int32(funding))
    owner = jnp.where(g.edge_mask, jnp.int32(FREE), jnp.int32(-2))
    return DfepState(owner, mv, jnp.int32(0), jnp.int32(0))


def _hash01(e: jax.Array, i: jax.Array, r: jax.Array) -> jax.Array:
    """Stateless per-(edge, partition, round) tie-break in [0, 1)."""
    x = (e.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
         ^ (i.astype(jnp.uint32) * jnp.uint32(0x85EBCA77))
         ^ (r.astype(jnp.uint32) * jnp.uint32(0xC2B2AE3D)))
    x = (x ^ (x >> 15)) * jnp.uint32(0x2C1B3C6D)
    x = (x ^ (x >> 12)) * jnp.uint32(0x297A2D39)
    x = x ^ (x >> 15)
    return x.astype(jnp.float32) / jnp.float32(2**32)


def _sizes(owner: jax.Array, k: int) -> jax.Array:
    onehot = owner[:, None] == jnp.arange(k, dtype=jnp.int32)[None, :]
    return jnp.sum(onehot.astype(jnp.int32), axis=0)


def _round(g: Graph, slots: Slots, cfg: DfepConfig, state: DfepState,
           active: jax.Array | None = None,
           grant_v: jax.Array | None = None) -> DfepState:
    """One auction round. ``active`` (default: every real edge) restricts
    steps 1–2 to a subset of edges — the bounded local re-auction of the
    streaming subsystem runs the same machinery with ``active`` set to the
    h-hop region around touched vertices and ``grant_v`` restricting step-3
    grants to region vertices. With both None this is exactly the paper's
    full-graph round."""
    k = cfg.k
    u, v = g.src, g.dst
    emask = g.edge_mask if active is None else (g.edge_mask & active)
    owner, mv = state.owner, state.mv
    part_ids = jnp.arange(k, dtype=jnp.int32)

    # ---- step 1: spread units over eligible incident edges ---------------
    with jax.named_scope("dfep.spread"):
        free = owner == FREE                                         # [E]
        owned_by = owner[:, None] == part_ids[None, :]               # [E, K]
        elig = (free[:, None] | owned_by) & emask[:, None]           # [E, K]
        if cfg.variant_c:
            sizes0 = _sizes(owner, k)
            mean0 = jnp.sum(sizes0) // k
            poor = sizes0 < (mean0 / cfg.poor_p)                     # [K]
            rich_edge = jnp.where(owner >= 0, ~poor[jnp.clip(owner, 0)], False)
            raid = (rich_edge[:, None] & poor[None, :] & ~owned_by
                    & emask[:, None])
            elig = elig | raid

        eligi = elig.astype(jnp.int32)
        cnt = jnp.zeros((g.n_vertices, k), jnp.int32)
        cnt = cnt.at[u].add(eligi).at[v].add(eligi)                  # [V, K]
        safe_cnt = jnp.maximum(cnt, 1)
        base = mv // safe_cnt                                        # [V, K]
        rem = mv - base * safe_cnt                                   # [V, K]

        # per-slot rank among this vertex's eligible edges (segmented
        # cumsum), rotated by a per-(vertex, partition, round) hash so the
        # remainder units don't starve late-ranked edges (Hadoop's arbitrary
        # iteration order)
        elig_slot = eligi[slots.edge]                                # [2E, K]
        cum = jnp.cumsum(elig_slot, axis=0)
        exc = cum - elig_slot                                        # exclusive
        rank = exc - exc[slots.seg_first]                            # [2E, K]
        sv = slots.vertex
        rot = (_hash01(sv[:, None], part_ids[None, :], state.rounds)
               * safe_cnt[sv].astype(jnp.float32)).astype(jnp.int32)
        rank = jnp.where(safe_cnt[sv] > 0,
                         (rank + rot) % safe_cnt[sv], rank)
        contrib = elig_slot * (base[sv] + (rank < rem[sv]).astype(jnp.int32))
        moved = cnt > 0
        mv_left = jnp.where(moved, 0, mv)                            # [V, K]

        # back to (u-side, v-side) order
        e_pad = g.e_pad
        contrib_uv = contrib[slots.inv]                              # [2E, K]
        cu, cv = contrib_uv[:e_pad], contrib_uv[e_pad:]              # [E, K]
        me = cu + cv                                                 # committed

    # ---- step 2: auction --------------------------------------------------
    with jax.named_scope("dfep.auction"):
        tie = _hash01(jnp.arange(e_pad, dtype=jnp.int32)[:, None],
                      part_ids[None, :], state.rounds)
        score = me.astype(jnp.float32) + tie
        best = jnp.argmax(score, axis=1).astype(jnp.int32)           # [E]
        best_amt = jnp.take_along_axis(me, best[:, None], axis=1)[:, 0]
        can_buy = (best_amt >= 1) & emask
        bought_free = free & can_buy
        if cfg.variant_c:
            best_is_poor = poor[best]
            steal = ((~free) & can_buy & best_is_poor & (best != owner)
                     & rich_edge)
            paid = bought_free | steal
        else:
            paid = bought_free
        new_owner = jnp.where(paid, best, owner)

        now_owned = new_owner[:, None] == part_ids[None, :]          # [E, K]
        pay = (paid[:, None] & now_owned).astype(jnp.int32)
        residual = me - pay                                          # [E, K] int

        # winner residual: half/half (odd unit to u). losers: equal over
        # funders
        fu = (cu > 0).astype(jnp.int32)
        fv = (cv > 0).astype(jnp.int32)
        funders = jnp.maximum(fu + fv, 1)
        half = residual // 2
        loser_share = residual // funders
        loser_rem = residual - loser_share * funders                 # 0 or 1
        ref_u = jnp.where(now_owned, half + (residual - 2 * half),
                          fu * (loser_share + loser_rem * fu))
        ref_v = jnp.where(now_owned, half,
                          fv * jnp.where(fu > 0, loser_share,
                                         loser_share + loser_rem))
        mv_new = mv_left.at[u].add(ref_u).at[v].add(ref_v)
        progressed = jnp.sum(jnp.where(paid, 1, 0)) > 0

    # ---- step 3: coordinator grants (replicated, O(K)) --------------------
    with jax.named_scope("dfep.grant"):
        # grant_i = min(cap, ceil(|E| / size_i)) — "inversely proportional to
        # the number of edges already bought", with the paper's cap (10)
        # binding for any partition smaller than |E|/cap (i.e. for most of
        # the run, which is what makes the cap meaningful).
        sizes = _sizes(new_owner, k)
        remaining = jnp.sum(jnp.where(new_owner == FREE, 1, 0))
        grant = jnp.minimum(jnp.int32(cfg.cap),
                            -(-jnp.int32(g.n_edges) // jnp.maximum(sizes, 1)))
        grant = jnp.where(remaining > 0, grant, 0)                   # [K]

        # distribute over the vertices where the partition *committed*
        # funding to a still-free edge this round (its active frontier); if
        # it has no such vertex, fall back to its full presence set.
        still_free = new_owner == FREE                               # [E]
        fr_u = jnp.zeros((g.n_vertices, k), jnp.bool_)
        fr_u = fr_u.at[u].max((cu > 0) & still_free[:, None])
        fr_u = fr_u.at[v].max((cv > 0) & still_free[:, None])
        presence = mv_new > 0                                        # [V, K]
        owned_at = jnp.zeros((g.n_vertices, k), jnp.bool_)
        owned_mask = now_owned & emask[:, None]
        owned_at = owned_at.at[u].max(owned_mask).at[v].max(owned_mask)
        presence = presence | owned_at
        has_frontier = jnp.any(fr_u, axis=0)                         # [K]
        presence = jnp.where(has_frontier[None, :], fr_u, presence)
        if grant_v is not None:   # local re-auction: grants stay in the region
            presence = presence & grant_v[:, None]
        pres_i = presence.astype(jnp.int32)
        n_pres = jnp.maximum(jnp.sum(pres_i, axis=0), 1)             # [K]
        p_base = grant // n_pres
        p_rem = grant - p_base * n_pres                              # [K]
        p_rank = jnp.cumsum(pres_i, axis=0) - pres_i                 # [V, K]
        p_rot = (_hash01(jnp.full((1,), 7, jnp.int32), part_ids[None, :],
                         state.rounds)
                 * n_pres.astype(jnp.float32)).astype(jnp.int32)
        p_rank = (p_rank + p_rot) % n_pres[None, :]
        mv_new = mv_new + pres_i * (
            p_base[None, :] + (p_rank < p_rem[None, :]).astype(jnp.int32))

    return DfepState(
        owner=new_owner,
        mv=mv_new,
        rounds=state.rounds + 1,
        stalled=jnp.where(progressed, 0, state.stalled + 1),
    )


@partial(jax.jit, static_argnames=("cfg",))
def run_dfep(g: Graph, slots: Slots, cfg: DfepConfig, key: jax.Array) -> DfepState:
    """Run rounds until every real edge is owned (or stall/round caps hit)."""
    state = init_state(g, cfg, key)

    def cond(s: DfepState):
        unsold = jnp.sum(jnp.where(s.owner == FREE, 1, 0))
        return ((unsold > 0)
                & (s.rounds < cfg.max_rounds)
                & (s.stalled < cfg.stall_rounds))

    return jax.lax.while_loop(cond, lambda s: _round(g, slots, cfg, s), state)


# ---------------------------------------------------------------------------
# Incremental (region-restricted) DFEP — entry points for repro.stream
# ---------------------------------------------------------------------------

def init_region_state(g: Graph, cfg: DfepConfig, owner: jax.Array,
                      active: jax.Array, region_v: jax.Array) -> DfepState:
    """Seed a bounded local re-auction.

    Edges under ``active`` are released (owner -> FREE); each partition gets
    ``ceil(|active| / K)`` units spread over its presence vertices *inside*
    the region (anchoring the auction to its existing territory). A
    partition with no region presence seeds at the first region vertex, like
    Algorithm 3's random start.
    """
    k = cfg.k
    owner0 = jnp.where(active, jnp.int32(FREE), owner)
    n_active = jnp.sum(active.astype(jnp.int32))
    funding = -(-n_active // k)                                      # ceil
    # partition presence at region vertices (from still-owned edges)
    part_ids = jnp.arange(k, dtype=jnp.int32)
    owned = (owner0[:, None] == part_ids[None, :]) & g.edge_mask[:, None]
    pres = jnp.zeros((g.n_vertices, k), jnp.bool_)
    pres = pres.at[g.src].max(owned).at[g.dst].max(owned)
    pres = pres & region_v[:, None]
    pres_i = pres.astype(jnp.int32)
    cnt = jnp.sum(pres_i, axis=0)                                    # [K]
    safe = jnp.maximum(cnt, 1)
    base = funding // safe
    rem = funding - base * safe
    rank = jnp.cumsum(pres_i, axis=0) - pres_i
    mv = pres_i * (base[None, :] + (rank < rem[None, :]).astype(jnp.int32))
    # no-presence fallback: everything at the first region vertex
    fallback = jnp.argmax(region_v).astype(jnp.int32)
    mv = mv.at[fallback].add(jnp.where(cnt == 0, funding, 0))
    return DfepState(owner0, mv, jnp.int32(0), jnp.int32(0))


@partial(jax.jit, static_argnames=("cfg",))
def run_dfep_region(g: Graph, slots: Slots, cfg: DfepConfig,
                    owner: jax.Array, active: jax.Array,
                    region_v: jax.Array) -> DfepState:
    """DFEP steps 1–2 (plus region-restricted step-3 grants) over only the
    ``active`` edges, holding every other assignment fixed. This is the
    bounded local re-auction the streaming subsystem runs when replication
    drift crosses its threshold; cost scales with the region, not |E|."""
    state = init_region_state(g, cfg, owner, active, region_v)

    def cond(s: DfepState):
        unsold = jnp.sum(jnp.where(s.owner == FREE, 1, 0))
        return ((unsold > 0)
                & (s.rounds < cfg.max_rounds)
                & (s.stalled < cfg.stall_rounds))

    return jax.lax.while_loop(
        cond, lambda s: _round(g, slots, cfg, s, active=active,
                               grant_v=region_v), state)


@partial(jax.jit, static_argnames=("k",))
def finalize(g: Graph, owner: jax.Array, k: int, iters: int = 64) -> jax.Array:
    """Assign any leftover FREE edges to the least-loaded adjacent partition
    (fallback so a valid partitioning is always returned; flagged upstream)."""

    def body(_, own):
        sizes = _sizes(own, k).astype(jnp.float32)
        # per-vertex: adjacent partition with the smallest size
        score = jnp.where(own >= 0, sizes[jnp.clip(own, 0)], jnp.inf)
        best_lab = jnp.full((g.n_vertices,), jnp.float32(jnp.inf))
        enc = score * (k + 1) + jnp.where(own >= 0, own, 0).astype(jnp.float32)
        enc = jnp.where(own >= 0, enc, jnp.inf)
        best_lab = best_lab.at[g.src].min(jnp.where(g.edge_mask, enc, jnp.inf))
        best_lab = best_lab.at[g.dst].min(jnp.where(g.edge_mask, enc, jnp.inf))
        cand_enc = jnp.minimum(best_lab[g.src], best_lab[g.dst])
        cand = jnp.where(jnp.isfinite(cand_enc),
                         (cand_enc % (k + 1)).astype(jnp.int32), -1)
        take = (own == FREE) & (cand >= 0)
        return jnp.where(take, cand, own)

    own = jax.lax.fori_loop(0, iters, body, owner)
    return jnp.where(own == FREE, 0, own)


def partition(g: Graph, k: int, key: jax.Array | int = 0,
              variant_c: bool = False, slots: Slots | None = None,
              **kw) -> tuple[jax.Array, dict]:
    """Convenience wrapper: run DFEP and return (owner [E], info dict).

    Recorded as a ``dfep.partition`` span (``k``, ``rounds``,
    ``finalized``) with children ``dfep.slots`` (the host slot layout and
    its upload), ``dfep.run`` (dispatch of the round loop to its sync) and
    ``dfep.finalize``; counter ``dfep.rounds``."""
    from .. import obs   # core's one use of obs (LP003 suppressed here)
    rec = obs.get()
    psid = rec.begin("dfep.partition", k=k)
    if isinstance(key, int):
        key = jax.random.key(key)
    if slots is None:
        sid = rec.begin("dfep.slots", parent=psid)
        slots = build_slots(g)
        rec.end(sid)
    cfg = DfepConfig(k=k, variant_c=variant_c, **kw)
    sid = rec.begin("dfep.run", parent=psid)
    st = run_dfep(g, slots, cfg, key)
    rounds = int(st.rounds)
    rec.end(sid)
    unsold = int(jnp.sum(jnp.where(st.owner == FREE, 1, 0)))
    owner = st.owner
    if unsold:
        sid = rec.begin("dfep.finalize", parent=psid)
        owner = finalize(g, owner, k)
        rec.end(sid)
    owner = jnp.where(g.edge_mask, owner, -2)
    info = {"rounds": rounds, "unsold_at_stop": unsold,
            "finalized": bool(unsold)}
    rec.end(psid, rounds=rounds, finalized=bool(unsold))
    rec.counter("dfep.rounds", rounds)
    return owner, info
