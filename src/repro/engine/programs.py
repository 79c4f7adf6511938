"""Graph programs expressed against the engine API, validated against the
whole-graph oracles in ``core/algorithms.py``:

  * SSSP       — unit-weight shortest paths (paper Algorithm 1),
  * WCC        — connected components via min-label epidemic (Algorithm 2;
                 labels are vertex ids so results are bit-identical to
                 ``reference_cc``),
  * PageRank   — partial in-flow sums per partition, completed across the
                 cut each superstep (§III sketch),
  * wsssp      — weighted shortest paths over the plan's per-half-edge
                 content-hash weights (``plan.edge_w``), via the
                 ``EdgeProgram.edge`` hook,
  * BFS        — hop levels with -1.0 marking unreachable vertices,
  * labelprop  — min-label propagation over an EXTERNAL [V] label plane
                 (vertex property channel; bit-identical to
                 ``reference_label_propagation``),
  * ppr        — personalized PageRank with an external teleport vector
                 (vertex property channel + degree resource),
  * gcn_layer  — one GCN layer forward pass over [V, F] feature planes:
                 ``out = (D^{-1/2} A_w D^{-1/2} X) W`` with a bound weight
                 matrix (dense channel), flowing through the fused Pallas
                 gSpMM via the ``edge_mul`` hook (vector state,
                 ``StateSpec(features=F_out)``),
  * kge_score  — DistMult-style triple scoring over bound entity/relation
                 embedding channels, accumulated per vertex.

Programs are module-level constants (static jit arguments); per-query
values (source vertex, degree vector) travel in the traced ``ctx`` dict.
``multi_source_sssp`` vmaps one compiled superstep loop over a batch of
sources — the serving-oriented batched-query path.

Every program registers ONCE in ``engine.registry`` at the bottom of this
module — through the same public ``registry.register`` call user programs
use — and the serving stack (``repro.gserve``) derives request validation,
batching, caching and dispatch from those entries.  The min-style programs
also carry a ``warm_init`` hook: served queries can repair from a previous
epoch's result after insert-only stream patches (old distances are valid
upper bounds, so min-relaxation tightens them to the exact fixpoint in
fewer supersteps than a cold recompute).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..core import algorithms as _alg
from . import registry
from .kernels import gather_edge_channel, gather_vertex_channel
from .plan import PartitionPlan
from .runtime import EdgeProgram, Engine, EngineResult
from .state import StateSpec

INF = jnp.float32(jnp.inf)
DAMPING = 0.85


# ---------------------------------------------------------------------------
# SSSP
# ---------------------------------------------------------------------------

def _sssp_prepare(plan, kw):
    return {"source": kw["source"]}


def _sssp_init(plan, ctx):
    hit = plan.vmask & (plan.local2global == ctx["source"])
    return jnp.where(hit, 0.0, INF)


def _sssp_pre(state, ctx):
    return state + 1.0


def _min_apply(old, agg, ctx):
    return jnp.minimum(old, agg)


def _sssp_finalize(glob, present, plan, ctx):
    iota = jnp.arange(plan.n_vertices)
    isolated = jnp.where(iota == ctx["source"], 0.0, INF)
    return jnp.where(present, glob, isolated)


def _sssp_warm(plan, prev, ctx):
    """Warm start from a previous epoch's [V] distances.

    Valid whenever the graph changed by *insertions only* since ``prev``
    was computed: old distances are then upper bounds on the true ones, and
    min-relaxation from any upper bound converges to the exact fixpoint —
    in as many supersteps as the *change* needs to propagate, not the whole
    graph. +inf entries mean "no prior information" and reduce to the cold
    init via the min below. (The serving layer tracks insert-only lineage
    and never warm-starts across a deletion.)
    """
    local = jnp.where(plan.vmask, prev[plan.local2global], INF)
    return jnp.minimum(_sssp_init(plan, ctx), local)


SSSP = EdgeProgram(
    name="sssp", mode="replica", combine="min",
    prepare=_sssp_prepare, init=_sssp_init, pre=_sssp_pre, apply=_min_apply,
    finalize=_sssp_finalize, local_fixpoint=True, warm_init=_sssp_warm)


# ---------------------------------------------------------------------------
# WCC (min-label propagation; labels = vertex ids, matching reference_cc)
# ---------------------------------------------------------------------------

def _wcc_prepare(plan, kw):
    # labels live in float32 state; ids above 2^24 would collide silently
    assert plan.n_vertices < 2 ** 24, \
        "WCC float32 labels need n_vertices < 2**24"
    return {}


def _wcc_init(plan, ctx):
    return jnp.where(plan.vmask, plan.local2global.astype(jnp.float32), INF)


def _wcc_pre(state, ctx):
    return state


def _wcc_finalize(glob, present, plan, ctx):
    own = jnp.arange(plan.n_vertices, dtype=jnp.float32)
    return jnp.where(present, glob, own)


WCC = EdgeProgram(
    name="wcc", mode="replica", combine="min",
    prepare=_wcc_prepare, init=_wcc_init, pre=_wcc_pre, apply=_min_apply,
    finalize=_wcc_finalize, local_fixpoint=True)


# ---------------------------------------------------------------------------
# PageRank (partial aggregation across the cut each superstep)
# ---------------------------------------------------------------------------

def _pr_prepare(plan, kw):
    deg = jnp.maximum(kw["degrees"].astype(jnp.float32), 1.0)
    return {"deg_local": deg[plan.local2global],
            "inv_v": jnp.float32(1.0 / plan.n_vertices)}


def _pr_init(plan, ctx):
    return jnp.where(plan.vmask, 1.0 / plan.n_vertices, 0.0)


def _pr_pre(state, ctx):
    return state / ctx["deg_local"]


def _pr_apply(old, inflow, ctx):
    return (1.0 - DAMPING) * ctx["inv_v"] + DAMPING * inflow


def _pr_finalize(glob, present, plan, ctx):
    # a vertex in no partition has no edges: its rank is the teleport term
    return jnp.where(present, glob, (1.0 - DAMPING) / plan.n_vertices)


PAGERANK = EdgeProgram(
    name="pagerank", mode="partial", combine="add",
    prepare=_pr_prepare, init=_pr_init, pre=_pr_pre,
    apply=_pr_apply, finalize=_pr_finalize,
    local_fixpoint=False, default_supersteps=30)


# ---------------------------------------------------------------------------
# Weighted SSSP — per-half-edge weights via the ``edge`` hook. The weights
# are baked into the plan at compile/patch time (plan.edge_w, a content
# hash of the endpoints — core/graph.py::edge_weights), so the weighted
# message stream flows through the same segment-reduce kernels.
# ---------------------------------------------------------------------------

def _ident_pre(state, ctx):
    return state


def _wsssp_edge(msgs, plan, ctx):
    return msgs + plan.edge_w


WEIGHTED_SSSP = EdgeProgram(
    name="wsssp", mode="replica", combine="min",
    prepare=_sssp_prepare, init=_sssp_init, pre=_ident_pre,
    apply=_min_apply, finalize=_sssp_finalize, local_fixpoint=True,
    edge=_wsssp_edge, warm_init=_sssp_warm)


# ---------------------------------------------------------------------------
# BFS hop levels — unit costs through the ``edge`` hook; unreachable
# vertices are finalized to -1.0 (distinguishing the *result space* from
# the +inf-based relaxation state, which warm_init must map back).
# ---------------------------------------------------------------------------

def _bfs_edge(msgs, plan, ctx):
    return msgs + 1.0


def _bfs_finalize(glob, present, plan, ctx):
    iota = jnp.arange(plan.n_vertices)
    isolated = jnp.where(iota == ctx["source"], 0.0, INF)
    d = jnp.where(present, glob, isolated)
    return jnp.where(jnp.isinf(d), -1.0, d)


def _bfs_warm(plan, prev, ctx):
    # the finalized result marks unreachable as -1.0: back to +inf before
    # reuse (a vertex unreachable pre-insert may be reachable now)
    prev = jnp.where(prev < 0.0, INF, prev)
    local = jnp.where(plan.vmask, prev[plan.local2global], INF)
    return jnp.minimum(_sssp_init(plan, ctx), local)


BFS = EdgeProgram(
    name="bfs", mode="replica", combine="min",
    prepare=_sssp_prepare, init=_sssp_init, pre=_ident_pre,
    apply=_min_apply, finalize=_bfs_finalize, local_fixpoint=True,
    edge=_bfs_edge, warm_init=_bfs_warm)


# ---------------------------------------------------------------------------
# Label propagation over an EXTERNAL label plane (vertex property channel).
# The labels come from outside the graph — a [V] (or [V, 1]) float32 plane
# supplied at query time or bound once per epoch — and flow through the
# same min-combine machinery as WCC: every vertex converges to the
# smallest label in its component.  ``prepare`` gathers the global plane
# to the partition-local layout with the slack-aware channel gather, so
# the program is exact on single-device and shard_map paths and across
# stream patches without any plan surgery.
# ---------------------------------------------------------------------------

def _lp_prepare(plan, kw):
    lab = kw["labels"]
    if lab.ndim == 1:
        lab = lab[:, None]
    return {"labels_glob": lab[:, 0],
            "labels_local": gather_vertex_channel(plan, lab)[:, :, 0]}


def _lp_init(plan, ctx):
    return jnp.where(plan.vmask, ctx["labels_local"], INF)


def _lp_warm(plan, prev, ctx):
    # labels only shrink as edges arrive (a bigger component can only
    # lower the min), so a previous epoch's result is a valid upper bound
    # after insert-only patches — identical contract to SSSP warm-start
    local = jnp.where(plan.vmask, prev[plan.local2global], INF)
    return jnp.minimum(_lp_init(plan, ctx), local)


def _lp_finalize(glob, present, plan, ctx):
    return jnp.where(present, glob, ctx["labels_glob"])


LABELPROP = EdgeProgram(
    name="labelprop", mode="replica", combine="min",
    prepare=_lp_prepare, init=_lp_init, pre=_wcc_pre, apply=_min_apply,
    finalize=_lp_finalize, local_fixpoint=True, warm_init=_lp_warm)


# ---------------------------------------------------------------------------
# Personalized PageRank — degree-weighted rank flow with an external
# teleport vector (vertex property channel).  rank <- (1-d)*p + d*inflow,
# with p supplied per query; the channel digest keys the cache, so two
# tenants with different personalization vectors never share an answer.
# ---------------------------------------------------------------------------

def _ppr_prepare(plan, kw):
    p = kw["personalization"]
    if p.ndim == 1:
        p = p[:, None]
    deg = jnp.maximum(kw["degrees"].astype(jnp.float32), 1.0)
    return {"p_glob": p[:, 0],
            "p_local": gather_vertex_channel(plan, p)[:, :, 0],
            "deg_local": deg[plan.local2global]}


def _ppr_init(plan, ctx):
    return jnp.where(plan.vmask, ctx["p_local"], 0.0)


def _ppr_apply(old, inflow, ctx):
    return (1.0 - DAMPING) * ctx["p_local"] + DAMPING * inflow


def _ppr_finalize(glob, present, plan, ctx):
    # a vertex in no partition has no edges: rank settles at its teleport
    return jnp.where(present, glob, (1.0 - DAMPING) * ctx["p_glob"])


PPR = EdgeProgram(
    name="ppr", mode="partial", combine="add",
    prepare=_ppr_prepare, init=_ppr_init, pre=_pr_pre,
    apply=_ppr_apply, finalize=_ppr_finalize,
    local_fixpoint=False, default_supersteps=30)


# ---------------------------------------------------------------------------
# GCN layer — the vector-state flagship: one graph-convolution forward pass
# ``out = (D^{-1/2} A_w D^{-1/2} X) W`` over the plan's content-hash edge
# weights.  State is a [K, Vmax, F_in] feature plane; the sweep runs the
# fused Pallas gSpMM (``edge_mul`` hook: gather · multiply-by-edge_w ·
# segment-reduce in one kernel pass); the bound [F_in, F_out] weight matrix
# (dense channel) applies once at finalize.  Feature widths are static per
# registration — like a deployed model's layer shapes — so every query jits
# to one cache entry.
# ---------------------------------------------------------------------------

GCN_F_IN = 8
GCN_F_OUT = 4


def _gcn_prepare(plan, kw):
    inv_sqrt = 1.0 / jnp.sqrt(jnp.maximum(
        kw["degrees"].astype(jnp.float32), 1.0))
    return {"x_local": gather_vertex_channel(plan, kw["x"]),
            "inv_sqrt_local": jnp.where(
                plan.vmask, inv_sqrt[plan.local2global], 0.0)[:, :, None],
            "weight": kw["weight"]}


def _gcn_init(plan, ctx):
    return ctx["x_local"]           # already vmask-pinned to zero rows


def _gcn_pre(state, ctx):
    return state * ctx["inv_sqrt_local"]


def _gcn_edge_mul(plan, ctx):
    return plan.edge_w


def _gcn_apply(old, agg, ctx):
    return agg * ctx["inv_sqrt_local"]


def _gcn_finalize(glob, present, plan, ctx):
    h = jnp.where(present[:, None], glob, 0.0)
    # full f32 products: the TPU's default matmul precision rounds the
    # operands to bf16, far outside the layer's 1e-5 oracle tolerance
    return jnp.dot(h, ctx["weight"], precision=jax.lax.Precision.HIGHEST)


GCN_LAYER = EdgeProgram(
    name="gcn_layer", mode="partial", combine="add",
    prepare=_gcn_prepare, init=_gcn_init, pre=_gcn_pre,
    apply=_gcn_apply, finalize=_gcn_finalize,
    local_fixpoint=False, default_supersteps=1,
    edge_mul=_gcn_edge_mul, state=StateSpec(features=GCN_F_OUT, fill=0.0))


# ---------------------------------------------------------------------------
# KGE triple scoring — DistMult interaction over bound embedding channels:
# every live edge e = (u, v) scores sum_f ent_u[f]·rel_e[f]·ent_v[f] and the
# score accumulates onto both endpoints.  The relation plane is an EDGE
# channel in graph slot order (slack-aware gather: patched-in edges without
# covered slots score 0); the per-feature ``edge_mul`` planes drive the
# fused gSpMM with [K, Emax, F] weights.  Scalar [V] result state.
# ---------------------------------------------------------------------------

KGE_F = 8


def _kge_prepare(plan, kw):
    return {"ent_local": gather_vertex_channel(plan, kw["entity"]),
            "rel_local": gather_edge_channel(plan, kw["relation"], fill=0.0)}


def _kge_init(plan, ctx):
    return ctx["ent_local"]


def _kge_edge_mul(plan, ctx):
    return ctx["rel_local"]


def _kge_apply(old, agg, ctx):
    return ctx["ent_local"] * agg


def _kge_finalize(glob, present, plan, ctx):
    return jnp.where(present, jnp.sum(glob, axis=1), 0.0)


KGE_SCORE = EdgeProgram(
    name="kge_score", mode="partial", combine="add",
    prepare=_kge_prepare, init=_kge_init, pre=_ident_pre,
    apply=_kge_apply, finalize=_kge_finalize,
    local_fixpoint=False, default_supersteps=1,
    edge_mul=_kge_edge_mul, state=StateSpec(fill=0.0))


# ---------------------------------------------------------------------------
# Convenience entry points
# ---------------------------------------------------------------------------

def engine_sssp(engine: Engine, source: int) -> EngineResult:
    return engine.run(SSSP, source=jnp.int32(source))


def engine_wcc(engine: Engine) -> EngineResult:
    return engine.run(WCC)


def engine_pagerank(engine: Engine, degrees: jax.Array,
                    iters: int = 30) -> EngineResult:
    return engine.run(PAGERANK, max_supersteps=iters, degrees=degrees)


def engine_weighted_sssp(engine: Engine, source: int) -> EngineResult:
    return engine.run(WEIGHTED_SSSP, source=jnp.int32(source))


def engine_bfs(engine: Engine, source: int) -> EngineResult:
    return engine.run(BFS, source=jnp.int32(source))


def multi_source_sssp(engine: Engine, sources) -> EngineResult:
    """Batched multi-source distances: one vmapped superstep loop answers
    every query; ``result.state`` is [S, V]."""
    sources = jnp.asarray(sources, jnp.int32)
    return engine.run_batched(SSSP, {"source": sources})


def engine_label_propagation(engine: Engine, labels) -> EngineResult:
    """Min-label propagation over an external [V] / [V, 1] label plane."""
    return engine.run(LABELPROP, labels=jnp.asarray(labels, jnp.float32))


def engine_personalized_pagerank(engine: Engine, degrees: jax.Array,
                                 personalization,
                                 iters: int = 30) -> EngineResult:
    return engine.run(PPR, max_supersteps=iters, degrees=degrees,
                      personalization=jnp.asarray(personalization,
                                                  jnp.float32))


def engine_gcn_layer(engine: Engine, degrees: jax.Array, x,
                     weight) -> EngineResult:
    """One GCN layer forward pass; ``result.state`` is [V, GCN_F_OUT]."""
    return engine.run(GCN_LAYER, degrees=degrees,
                      x=jnp.asarray(x, jnp.float32),
                      weight=jnp.asarray(weight, jnp.float32))


def engine_kge_score(engine: Engine, entity, relation) -> EngineResult:
    """Per-vertex DistMult triple-score mass; ``result.state`` is [V]."""
    return engine.run(KGE_SCORE,
                      entity=jnp.asarray(entity, jnp.float32),
                      relation=jnp.asarray(relation, jnp.float32))


# ---------------------------------------------------------------------------
# Registry entries — the single declaration each program ever needs. The
# whole serving stack (request validation, batch/cache keys, dispatch,
# benchmark and test registration) derives from these; none of it names a
# program again. User programs extend the system with exactly one more
# ``registry.register`` call (see src/repro/engine/README.md).
# ---------------------------------------------------------------------------

def _non_negative(v):
    if v < 0:
        raise ValueError(f"iters must be >= 0, got {v}")


registry.register(
    "sssp", SSSP,
    params=[registry.ParamSpec("source", int, batchable=True)],
    oracle=lambda g, source: np.asarray(_alg.reference_sssp(g, source)[0]),
)

registry.register(
    "wcc", WCC,
    oracle=lambda g: np.asarray(_alg.reference_cc(g)[0]),
)

registry.register(
    "pagerank", PAGERANK,
    params=[registry.ParamSpec("iters", int, default=30, role="supersteps",
                               validate=_non_negative)],
    resources={"degrees": lambda g: g.degrees()},
    oracle=lambda g, iters: np.asarray(_alg.reference_pagerank(g,
                                                               iters=iters)),
    oracle_atol=1e-5,
)

registry.register(
    "wsssp", WEIGHTED_SSSP,
    params=[registry.ParamSpec("source", int, batchable=True)],
    oracle=_alg.reference_weighted_sssp,
)

registry.register(
    "bfs", BFS,
    params=[registry.ParamSpec("source", int, batchable=True)],
    oracle=_alg.reference_bfs,
)

registry.register(
    "labelprop", LABELPROP,
    params=[registry.ParamSpec("labels", float, role="channel",
                               channel="vertex", features=1)],
    oracle=lambda g, labels: _alg.reference_label_propagation(
        g, np.asarray(labels)),
)

registry.register(
    "ppr", PPR,
    params=[registry.ParamSpec("personalization", float, role="channel",
                               channel="vertex", features=1),
            registry.ParamSpec("iters", int, default=30, role="supersteps",
                               validate=_non_negative)],
    resources={"degrees": lambda g: g.degrees()},
    oracle=lambda g, personalization, iters: np.asarray(
        _alg.reference_personalized_pagerank(g, np.asarray(personalization),
                                             iters=iters)),
    oracle_atol=1e-5,
)


def _gcn_weight_rows(cv):
    # the dense channel's plan-free shape still has a program contract:
    # rows must match the layer's input width or the finalize matmul
    # would fail deep inside jit instead of at the server door
    if cv.shape[0] != GCN_F_IN:
        raise ValueError(
            f"gcn_layer.weight is the [F_in, F_out] = "
            f"[{GCN_F_IN}, {GCN_F_OUT}] layer matrix, got {cv.shape}")


registry.register(
    "gcn_layer", GCN_LAYER,
    params=[registry.ParamSpec("x", float, role="channel",
                               channel="vertex", features=GCN_F_IN),
            registry.ParamSpec("weight", float, role="channel",
                               channel="dense", features=GCN_F_OUT,
                               validate=_gcn_weight_rows)],
    resources={"degrees": lambda g: g.degrees()},
    oracle=lambda g, x, weight: _alg.reference_gcn_layer(
        g, np.asarray(x), np.asarray(weight)),
    oracle_atol=1e-5,
)

registry.register(
    "kge_score", KGE_SCORE,
    params=[registry.ParamSpec("entity", float, role="channel",
                               channel="vertex", features=KGE_F),
            registry.ParamSpec("relation", float, role="channel",
                               channel="edge", features=KGE_F)],
    oracle=lambda g, entity, relation: _alg.reference_kge_score(
        g, np.asarray(entity), np.asarray(relation)),
    oracle_atol=1e-5,
)
