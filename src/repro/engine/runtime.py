"""Edge-centric superstep runtime over a ``PartitionPlan``.

Execution model (paper §III, compacted):

  1. *local phase* — every partition runs Gather-Apply sweeps over its own
     CSR block (gather neighbour values along half-edges, segment-reduce per
     target, apply) — to a local fixed point for min-style programs, exactly
     one sweep for partial-aggregation programs (PageRank);
  2. *replica exchange* — only ``plan.replicated`` slots are scattered to a
     global frontier array, combined across partitions (min for replica
     state, add for partial aggregates) and gathered back.  Private
     vertices never cross the cut: an edge partition keeps every edge of a
     private vertex local, so its aggregate is already complete.

Steps 1–2 repeat until the exchanged state reaches a global fixed point
(or for a fixed number of supersteps).  ``supersteps`` is the paper's
*rounds* metric; the exchanged-slot count per superstep is its MESSAGES.

Two device mappings, same numerics:

  * **single-device fallback** — the [K, ...] partition axis is a batch
    axis; segment-reduce runs in the Pallas kernel (compiled on TPU,
    interpreted on the CPU backend);
  * **shard_map** — partitions are sharded over a 1-d device mesh axis
    (``K % n_devices == 0``, each device holds a [K/D, ...] block); the
    exchange's cross-partition combine becomes a device-local scatter
    followed by ``lax.pmin``/``psum`` over the mesh axis.  Collectives sit
    only in the exchange, so local fixed-point loops run fully
    device-local, exactly like the paper's workers between
    synchronisations.

Batched multi-source queries (the serving scenario) vmap the superstep
loop over the source axis — one compiled program answers S queries in one
superstep loop, on one device or with the batch axis vmapped inside the
shard_map body.  ``dispatch``/``dispatch_batched`` return a
``PendingResult`` without syncing so a serving scheduler can overlap batch
formation with device execution (``jax.block_until_ready`` on completion).
"""
from __future__ import annotations

import contextlib
import dataclasses
from functools import partial, wraps
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import obs as _obs
from . import kernels
from .errors import WarmStateError
from .plan import PartitionPlan
from .state import SCALAR, StateSpec

# Trace accounting: _run_loop's Python body executes only while jax traces
# (i.e. on a jit-cache miss), so this counter counts compilations, not calls.
# The streaming tests assert it stays flat across plan patches — patched
# plans keep the same treedef/avals and must reuse the warm cache; only a
# compaction epoch (new static aux) is allowed to retrace.
# The counter is folded into repro.obs: each bump also records an
# ``engine.retrace`` event on the process recorder, attributed to the plan
# epoch, padded shapes, and (via the dispatch sites' ambient tags) the
# program and bucket shape that triggered it — an unexpected retrace in a
# trace export is a visible, attributable event, not a silent bump.
TRACE_COUNTER = {"run_loop": 0}

_obs.get().register_provider(
    "jit", lambda: {"run_loop_traces": TRACE_COUNTER["run_loop"]})


class EdgeProgram(NamedTuple):
    """A "think-like-an-edge" program. All callables are pure and module
    level (the program is a static jit argument; dynamic per-query values
    travel in the traced ``ctx`` dict).

    mode "replica": state slots are replicas of one logical per-vertex value
                    (combine = min); ``apply`` runs inside the local sweep.
    mode "partial": local sweeps produce partial aggregates that sum across
                    partitions (combine = add); ``apply`` runs after the
                    exchange completes the aggregate.

    State rank is declarative (PR 10): ``state`` is the program's
    :class:`~repro.engine.state.StateSpec`.  With the default scalar spec
    every hook sees/returns [K, Vmax] blocks and the finalized result is
    [V] — bit-identical to the pre-StateSpec path.  With
    ``StateSpec(features=F)`` the same hooks carry [K, Vmax, F] planes
    and finalize to [V, F]; the engine, warm store and serving layers
    derive every shape from the spec, no per-rank branching anywhere.
    """
    name: str
    mode: str                       # "replica" | "partial"
    combine: str                    # "min" | "add" | "max"
    prepare: Callable               # (plan, kw) -> ctx dict (traced, once)
    init: Callable                  # (plan, ctx) -> [K, Vmax(, F)] state
    pre: Callable                   # (state, ctx) -> per-vertex msg values
    apply: Callable                 # (old, agg, ctx) -> new
    finalize: Callable              # (glob [V(, F)], present [V], plan, ctx)
                                    #   -> [V(, F)]
    local_fixpoint: bool = True
    default_supersteps: int | None = None   # None -> run to fixed point
    # optional hooks (None: disabled)
    edge: Callable | None = None    # (msgs [K, Emax(, F)], plan, ctx) -> msgs
                                    #   — per-half-edge transform applied
                                    #   after the neighbour gather, before
                                    #   the segment reduce (e.g. + plan.edge_w)
    warm_init: Callable | None = None
                                    # (plan, prev [V(, F)], ctx) ->
                                    #   [K, Vmax(, F)] — warm-start state from
                                    #   a previous epoch's *finalized* result.
                                    #   ``state.fill`` entries of prev mean
                                    #   "no prior information" and must reduce
                                    #   to the cold init value for that vertex.
    edge_mul: Callable | None = None
                                    # (plan, ctx) -> [K, Emax] or [K, Emax, F]
                                    #   multiplicative per-half-edge weights;
                                    #   routes the sweep through the fused
                                    #   Pallas gSpMM (gather · multiply ·
                                    #   segment-reduce in one kernel pass)
                                    #   instead of the edge hook + plain
                                    #   segment reduce
    state: StateSpec = SCALAR       # per-vertex state shape declaration


@dataclasses.dataclass(frozen=True)
class EngineResult:
    state: jax.Array                # [V(, F)] global vertex state (rank per
                                    #   the program's StateSpec)
    supersteps: jax.Array           # int32 — the paper's "rounds"
    local_iters: jax.Array          # int32 — local sweeps on the critical path
    converged: jax.Array            # bool — False iff the superstep cap was
                                    #   hit first (state is then a truncation)
    exchange_per_superstep: int     # replica slots crossing the cut per round
    total_exchanged: int            # supersteps * exchange_per_superstep

    def row(self) -> dict:
        # batched runs carry per-source vectors; report the critical path
        return {"supersteps": int(jnp.max(self.supersteps)),
                "local_iters": int(jnp.max(self.local_iters)),
                "converged": bool(jnp.all(self.converged)),
                "exchange_per_superstep": self.exchange_per_superstep,
                "total_exchanged": self.total_exchanged}


@dataclasses.dataclass(frozen=True)
class PendingResult:
    """In-flight engine computation: the superstep loop has been dispatched
    (XLA runs it asynchronously) but nothing host-side has synced on it.

    ``result()`` blocks until the device arrays are ready and materialises
    the ``EngineResult``; until then the caller is free to form and dispatch
    further batches — the serving scheduler's overlap primitive."""
    _arrays: tuple                  # (state, supersteps, local_iters, converged)
    exchange_per_superstep: int

    def block_until_ready(self) -> "PendingResult":
        jax.block_until_ready(self._arrays)
        return self

    def result(self) -> EngineResult:
        state, supersteps, local_iters, converged = \
            jax.block_until_ready(self._arrays)
        ex = self.exchange_per_superstep
        steps = int(jnp.max(supersteps))
        _obs.get().counter("engine.supersteps", steps)
        return EngineResult(state, supersteps, local_iters, converged, ex,
                            steps * ex)


def _ident(combine: str) -> float:
    return kernels._IDENTITY[combine]


def _steps(prog: EdgeProgram, max_supersteps: int | None) -> int:
    if max_supersteps is not None:    # an explicit 0 means zero supersteps
        return max_supersteps
    if prog.default_supersteps is not None:
        return prog.default_supersteps
    return 512


def _rows(arr: jax.Array) -> jax.Array:
    return jnp.arange(arr.shape[0], dtype=jnp.int32)[:, None]


def _expand(mask: jax.Array, ref: jax.Array) -> jax.Array:
    """Broadcast a [K, Vmax] mask against scalar or feature-plane state —
    the one shape-polymorphism point the superstep loop needs: everything
    else is rank-generic indexing/reshapes driven by the data."""
    return mask[:, :, None] if ref.ndim == 3 else mask


def _scoped(name: str):
    """Trace the decorated function inside ``jax.named_scope(name)``, so
    its device ops carry the name in a profiler trace; a scope object of
    its own per call (one is not re-entrant)."""
    def wrap(fn):
        @wraps(fn)
        def scoped(*args, **kw):
            with jax.named_scope(name):
                return fn(*args, **kw)
        return scoped
    return wrap


@_scoped("engine.sweep")
def _sweep(plan, prog, state, ctx, *, use_pallas: bool):
    """One Gather-Apply sweep: per-target aggregate [K, Vmax(, F)]."""
    pre = prog.pre(state, ctx)                              # [K, Vmax(, F)]
    if prog.edge_mul is not None:   # fused gSpMM path (GNN programs)
        w = prog.edge_mul(plan, ctx)
        if use_pallas:
            agg = kernels.gspmm(plan, pre, w, prog.combine)
        else:
            agg = kernels.gspmm_ref(plan, pre, w, prog.combine)
        return agg[:, :, 0] if pre.ndim == 2 else agg
    msgs = pre[_rows(plan.edge_nbr), plan.edge_nbr]         # [K, Emax(, F)]
    if prog.edge is not None:   # per-half-edge hook (weighted programs)
        msgs = prog.edge(msgs, plan, ctx)
    if use_pallas:
        return kernels.segment_reduce(plan, msgs, prog.combine)
    return kernels.segment_reduce_ref(plan, msgs, prog.combine)


@_scoped("engine.exchange")
def _exchange(plan, values, combine, axis: str | None, *,
              use_pallas: bool):
    """Combine replicated slots across partitions; private slots unchanged.

    values [K, Vmax(, F)] -> same shape. With ``axis`` set (shard_map body)
    the cross-device combine is a psum/pmin/pmax over the mesh axis.
    Feature planes ride the same scatter with a trailing feature axis.
    """
    ident = _ident(combine)
    send = jnp.where(_expand(plan.vmask & plan.replicated, values),
                     values, ident)
    tail = values.shape[2:]
    glob = jnp.full((plan.n_vertices,) + tail, ident, jnp.float32)
    flat_idx = plan.local2global.reshape(-1)
    flat_send = send.reshape((-1,) + tail)
    if combine == "min":
        glob = glob.at[flat_idx].min(flat_send)
        if axis is not None:
            glob = jax.lax.pmin(glob, axis)
    elif combine == "max":
        glob = glob.at[flat_idx].max(flat_send)
        if axis is not None:
            glob = jax.lax.pmax(glob, axis)
    else:  # add identity is 0.0, so the masked send scatters exactly
        glob = glob.at[flat_idx].add(flat_send)
        if axis is not None:
            glob = jax.lax.psum(glob, axis)
    inc = glob[plan.local2global]                           # [K, Vmax(, F)]
    if use_pallas:
        return kernels.masked_update(values, inc, plan.vmask, plan.replicated,
                                     combine)
    new = jnp.where(_expand(plan.replicated, values), inc, values)
    return jnp.where(_expand(plan.vmask, values), new, ident)


@_scoped("engine.gather")
def _gather_global(plan, state, axis: str | None):
    """Master-slot scatter of the final local states to a global [V(, F)]."""
    tail = state.shape[2:]
    out = jnp.zeros((plan.n_vertices,) + tail, jnp.float32)
    out = out.at[plan.local2global.reshape(-1)].add(
        jnp.where(_expand(plan.is_master, state),
                  state, 0.0).reshape((-1,) + tail))
    present = jnp.zeros((plan.n_vertices,), jnp.bool_)
    present = present.at[plan.local2global.reshape(-1)].max(
        plan.is_master.reshape(-1))
    if axis is not None:
        out = jax.lax.psum(out, axis)
        present = jax.lax.psum(present.astype(jnp.int32), axis) > 0
    return out, present


def _run_loop(plan: PartitionPlan, prog: EdgeProgram, kw: dict,
              prev: jax.Array | None, axis: str | None, max_supersteps: int,
              max_local_iters: int, use_pallas: bool):
    """The superstep loop (runs as-is on one device or inside shard_map).

    ``prev`` (None or a [V] previous-epoch result) selects cold vs warm
    initialisation; None is pytree *structure*, so each variant is its own
    jit cache entry and the branch below is resolved at trace time.
    """
    TRACE_COUNTER["run_loop"] += 1
    rec = _obs.get()
    if rec.enabled:   # trace-time only: never runs on a warm jit cache hit
        rec.counter("engine.retraces")
        rec.event("engine.retrace", loop="run_loop", program=prog.name,
                  epoch=plan.epoch, k=plan.k, v_max=plan.v_max,
                  e_max=plan.e_max, sharded=axis is not None)
    ctx = prog.prepare(plan, kw)
    if prev is None:
        state0 = prog.init(plan, ctx)
    else:
        state0 = prog.warm_init(plan, prev, ctx)

    if prog.mode == "replica":
        def local_phase(st):
            def body(c):
                s, it, _ = c
                # the apply and its test fuse with the sweep's last gather
                with jax.named_scope("engine.sweep"):
                    agg = _sweep(plan, prog, s, ctx, use_pallas=use_pallas)
                    ns = prog.apply(s, agg, ctx)
                    return ns, it + 1, jnp.any(ns != s)

            if not prog.local_fixpoint:
                s, it, _ = body((st, jnp.int32(0), True))
                return s, it
            st, iters, _ = jax.lax.while_loop(
                lambda c: c[2] & (c[1] < max_local_iters), body,
                (st, jnp.int32(0), jnp.bool_(True)))
            return st, iters

        def superstep(carry):
            st, steps, litot, _ = carry
            st1, li = local_phase(st)
            st2 = _exchange(plan, st1, prog.combine, axis,
                            use_pallas=use_pallas)
            changed = jnp.any(st2 != st)
            if axis is not None:
                changed = jax.lax.pmax(changed.astype(jnp.int32), axis) > 0
            return st2, steps + 1, litot + li, changed

        st, steps, litot, changed = jax.lax.while_loop(
            lambda c: c[3] & (c[1] < max_supersteps), superstep,
            (state0, jnp.int32(0), jnp.int32(0), jnp.bool_(True)))
        converged = ~changed    # still changing => the cap cut us off
    else:  # partial aggregation: lock-step, fixed superstep count
        def superstep(st, _):
            agg = _sweep(plan, prog, st, ctx, use_pallas=use_pallas)
            agg_full = _exchange(plan, agg, prog.combine, axis,
                                 use_pallas=use_pallas)
            with jax.named_scope("engine.sweep"):     # its apply half
                return prog.apply(st, agg_full, ctx), None

        st, _ = jax.lax.scan(superstep, state0, None, length=max_supersteps)
        steps = jnp.int32(max_supersteps)
        litot = steps
        converged = jnp.bool_(True)   # fixed-iteration programs by design

    if axis is not None:  # local sweep counts diverge per device: report the
        litot = jax.lax.pmax(litot, axis)  # critical path, as documented
    glob, present = _gather_global(plan, st, axis)
    return prog.finalize(glob, present, plan, ctx), steps, litot, converged


@partial(jax.jit, static_argnames=("prog", "max_supersteps",
                                   "max_local_iters", "use_pallas"))
def _run_single(plan, prog, kw, prev, max_supersteps, max_local_iters,
                use_pallas):
    return _run_loop(plan, prog, kw, prev, None, max_supersteps,
                     max_local_iters, use_pallas)


@partial(jax.jit, static_argnames=("prog", "mesh", "axis", "k_local",
                                   "max_supersteps", "max_local_iters"))
def _run_sharded(plan, kw, prev, *, prog, mesh, axis, k_local,
                 max_supersteps, max_local_iters):
    """Module-level so repeated queries hit one jit cache entry per
    (program, mesh, shape) — the serving path never retraces."""
    plan_spec = jax.tree_util.tree_map(lambda _: P(axis), plan)
    kw_spec = jax.tree_util.tree_map(lambda _: P(), kw)
    prev_spec = jax.tree_util.tree_map(lambda _: P(), prev)

    def body(plan_local, kw_local, prev_local):
        plan_local = dataclasses.replace(plan_local, k=k_local)
        return _run_loop(plan_local, prog, kw_local, prev_local, axis,
                         max_supersteps, max_local_iters,
                         use_pallas=False)

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(plan_spec, kw_spec, prev_spec),
                       out_specs=(P(), P(), P(), P()), check_vma=False)
    return fn(plan, kw, prev)


@partial(jax.jit, static_argnames=("prog", "mesh", "axis", "k_local",
                                   "max_supersteps", "max_local_iters"))
def _run_sharded_batched(plan, kw, batched_kw, prev, *, prog, mesh, axis,
                         k_local, max_supersteps, max_local_iters):
    """Batched queries on the shard_map path: partitions stay sharded over
    the mesh axis while the batch axis is vmapped *inside* the sharded body,
    so one superstep loop answers the whole micro-batch with the same
    collective schedule as the unbatched path (the XLA segment-reduce is
    used — vmapping the Pallas grid is unsupported)."""
    plan_spec = jax.tree_util.tree_map(lambda _: P(axis), plan)
    kw_spec = jax.tree_util.tree_map(lambda _: P(), kw)
    bkw_spec = jax.tree_util.tree_map(lambda _: P(), batched_kw)
    prev_spec = jax.tree_util.tree_map(lambda _: P(), prev)

    def body(plan_local, kw_local, bkw_local, prev_local):
        plan_local = dataclasses.replace(plan_local, k=k_local)

        def one(bkw, pv):
            return _run_loop(plan_local, prog, {**kw_local, **bkw}, pv,
                             axis, max_supersteps, max_local_iters,
                             use_pallas=False)

        if prev_local is None:
            return jax.vmap(lambda bkw: one(bkw, None))(bkw_local)
        return jax.vmap(one)(bkw_local, prev_local)

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(plan_spec, kw_spec, bkw_spec, prev_spec),
                       out_specs=(P(), P(), P(), P()), check_vma=False)
    return fn(plan, kw, batched_kw, prev)


@dataclasses.dataclass(frozen=True)
class Engine:
    """Partitioned execution engine bound to a plan (and optionally a mesh).

    ``mesh`` must be 1-d with axis name ``axis`` and a device count dividing
    ``plan.k``; without a mesh the single-device path runs the Pallas
    kernels, compiled for the platform the computation runs on (Mosaic on
    TPU, the interpreter on the CPU backend).
    """
    plan: PartitionPlan
    mesh: Mesh | None = None
    axis: str = "parts"
    use_pallas: bool = True

    def with_plan(self, plan: PartitionPlan) -> "Engine":
        """Rebind to a (patched or recompiled) plan. A patched plan shares
        the old plan's treedef and avals, so jitted superstep loops keep
        their compilation cache across the swap; only a plan with a bumped
        compaction ``epoch`` retraces."""
        return dataclasses.replace(self, plan=plan)

    def _check_warm(self, prog: EdgeProgram, warm_state,
                    batch: int | None) -> jax.Array | None:
        """Validate a warm-start state (typed errors, actionable messages).

        A warm state is a previous epoch's *finalized* result in the
        program's declared state shape — ``spec.shape(V)``, or the batched
        ``spec.batch_shape(S, V)`` block with one row per lane; cold rows
        (``spec.fill``) mean "no prior information" and fall back to cold
        init.  A rank mismatch (scalar block for a [V, F] program or vice
        versa) raises the same typed error as a wrong vertex count — never
        a reshape crash inside jit.
        """
        if warm_state is None:
            return None
        if prog.warm_init is None:
            raise WarmStateError(
                f"program {prog.name!r} has no warm_init hook — pass "
                "warm_init= when constructing the EdgeProgram to enable "
                "warm-started dispatch, or drop warm_state")
        spec = prog.state
        prev = jnp.asarray(warm_state, jnp.dtype(spec.dtype))
        want = spec.shape(self.plan.n_vertices) if batch is None \
            else spec.batch_shape(batch, self.plan.n_vertices)
        if prev.shape != want:
            raise WarmStateError(
                f"warm_state for program {prog.name!r} has shape "
                f"{tuple(prev.shape)} but the plan serves "
                f"{self.plan.n_vertices} vertices with per-vertex state "
                f"{spec.describe()} — expected {want} "
                "(the previous epoch's finalized result state)")
        return prev

    def _obs_dispatch(self, prog: EdgeProgram, bucket: int,
                      pallas: bool = False):
        """Per-dispatch telemetry: records the dispatch event (program,
        bucket, plan epoch, exchange volume, lane occupancy) and returns an
        ambient-tag context so any jit retrace triggered while tracing
        inside it is attributed to this program + bucket shape.  A dispatch
        on the Pallas kernels (``pallas``) whose plan holds appended
        half-edges also counts ``engine.append_scatters``: the kernels run
        their append-region scatter only then."""
        rec = _obs.get()
        if not rec.enabled:
            return contextlib.nullcontext()
        health = _obs.plan_health(self.plan)
        rec.event("engine.dispatch", program=prog.name, bucket=bucket,
                  epoch=self.plan.epoch, sharded=self.mesh is not None,
                  exchange_per_superstep=health["exchange_per_superstep"],
                  edge_lane_occupancy_max=health["edge_lane_occupancy_max"],
                  vertex_lane_occupancy_max=
                      health["vertex_lane_occupancy_max"])
        rec.counter("engine.dispatches")
        if pallas and health["append_live_half_edges"] > 0:
            rec.counter("engine.append_scatters")
        for name, value in health.items():
            rec.gauge(f"plan.{name}", value)
        return rec.tags(program=prog.name, bucket=bucket)

    def dispatch(self, prog: EdgeProgram, max_supersteps: int | None = None,
                 max_local_iters: int = 100_000, warm_state=None,
                 **kw: Any) -> PendingResult:
        """Non-blocking single-query dispatch: hands the superstep loop to
        XLA and returns immediately. ``.result()`` syncs. ``warm_state``
        (a previous [V] result) initialises via ``prog.warm_init``."""
        steps = _steps(prog, max_supersteps)
        prev = self._check_warm(prog, warm_state, None)
        kw = {k: jnp.asarray(v) for k, v in kw.items()}
        single = self.mesh is None
        with self._obs_dispatch(prog, 0, pallas=single and self.use_pallas):
            if single:
                out = _run_single(self.plan, prog, kw, prev, steps,
                                  max_local_iters, self.use_pallas)
            else:
                out = _run_sharded(self._sharded_plan(), kw, prev, prog=prog,
                                   mesh=self.mesh, axis=self.axis,
                                   k_local=self._k_local(),
                                   max_supersteps=steps,
                                   max_local_iters=max_local_iters)
        return PendingResult(out, self.plan.exchange_volume)

    def run(self, prog: EdgeProgram, max_supersteps: int | None = None,
            max_local_iters: int = 100_000, warm_state=None,
            **kw: Any) -> EngineResult:
        """``dispatch`` and sync, as one ``engine.run`` span (``program``,
        ``supersteps``)."""
        rec = _obs.get()
        sid = rec.begin("engine.run", program=prog.name)
        res = self.dispatch(prog, max_supersteps, max_local_iters,
                            warm_state=warm_state, **kw).result()
        if sid is not None:
            rec.end(sid, supersteps=int(res.supersteps))
        return res

    def dispatch_batched(self, prog: EdgeProgram, batched_kw: dict,
                         max_supersteps: int | None = None,
                         max_local_iters: int = 100_000, warm_state=None,
                         **kw: Any) -> PendingResult:
        """Non-blocking micro-batch dispatch: vmap the superstep loop over a
        batch axis of ``batched_kw`` (e.g. ``{"source": sources}`` for
        multi-source SSSP). Runs on one device or, with a mesh bound, with
        the batch axis vmapped inside the shard_map body. The XLA
        segment-reduce path is used (the Pallas kernels are not vmapped).
        The serving scheduler dispatches the next micro-batch while this
        one computes and syncs via ``.result()``.
        ``warm_state`` is a [S, V] block, one previous-result row per lane
        (+inf rows cold-start their lane)."""
        steps = _steps(prog, max_supersteps)
        kw = {k: jnp.asarray(v) for k, v in kw.items()}
        batched_kw = {k: jnp.asarray(v) for k, v in batched_kw.items()}
        n_batch = next(iter(batched_kw.values())).shape[0]
        prev = self._check_warm(prog, warm_state, n_batch)
        with self._obs_dispatch(prog, n_batch):
            if self.mesh is None:
                if prev is None:
                    def one(bkw):
                        return _run_single(self.plan, prog, {**kw, **bkw},
                                           None, steps, max_local_iters,
                                           False)

                    out = jax.vmap(one)(batched_kw)
                else:
                    def one_warm(bkw, pv):
                        return _run_single(self.plan, prog, {**kw, **bkw},
                                           pv, steps, max_local_iters,
                                           False)

                    out = jax.vmap(one_warm)(batched_kw, prev)
            else:
                out = _run_sharded_batched(self._sharded_plan(), kw,
                                           batched_kw, prev, prog=prog,
                                           mesh=self.mesh, axis=self.axis,
                                           k_local=self._k_local(),
                                           max_supersteps=steps,
                                           max_local_iters=max_local_iters)
        return PendingResult(out, self.plan.exchange_volume)

    def run_batched(self, prog: EdgeProgram, batched_kw: dict,
                    max_supersteps: int | None = None,
                    max_local_iters: int = 100_000, warm_state=None,
                    **kw: Any) -> EngineResult:
        return self.dispatch_batched(prog, batched_kw, max_supersteps,
                                     max_local_iters, warm_state=warm_state,
                                     **kw).result()

    def lower_hlo(self, prog: EdgeProgram, batched_kw: dict | None = None,
                  max_supersteps: int | None = None,
                  max_local_iters: int = 100_000, **kw: Any) -> str:
        """Post-optimization HLO text of the executable a ``dispatch``
        (``batched_kw=None``) or ``dispatch_batched`` of the same shape
        would run — the input ``repro.obs.profile`` feeds the
        ``roofline.hlo_parse`` analyzer to build per-plan cost models.

        This pays one AOT trace + XLA compile per call (the ``.lower()``
        path does not share the C++ jit executable cache), so callers must
        memoize per (program, plan shape, bucket) — ``obs.profile`` does.
        Always lowers the cold-start variant: a warm-started dispatch is
        the same superstep loop with a different init, cost-identical to
        first order."""
        steps = _steps(prog, max_supersteps)
        kw = {k: jnp.asarray(v) for k, v in kw.items()}
        if batched_kw is None:
            if self.mesh is None:
                lowered = _run_single.lower(
                    self.plan, prog, kw, None, steps, max_local_iters,
                    self.use_pallas)
            else:
                lowered = _run_sharded.lower(
                    self._sharded_plan(), kw, None, prog=prog,
                    mesh=self.mesh, axis=self.axis,
                    k_local=self._k_local(), max_supersteps=steps,
                    max_local_iters=max_local_iters)
        else:
            batched_kw = {k: jnp.asarray(v) for k, v in batched_kw.items()}
            if self.mesh is None:
                # jit(vmap(...)) compiles the same batched superstep loop
                # the eager dispatch path executes (jit under vmap fuses
                # into one XLA computation either way)
                def one(bkw):
                    return _run_single(self.plan, prog, {**kw, **bkw},
                                       None, steps, max_local_iters,
                                       False)

                lowered = jax.jit(jax.vmap(one)).lower(batched_kw)
            else:
                lowered = _run_sharded_batched.lower(
                    self._sharded_plan(), kw, batched_kw, None, prog=prog,
                    mesh=self.mesh, axis=self.axis,
                    k_local=self._k_local(), max_supersteps=steps,
                    max_local_iters=max_local_iters)
        return lowered.compile().as_text()

    # -- shard_map plumbing -------------------------------------------------
    def _k_local(self) -> int:
        ndev = self.mesh.shape[self.axis]
        assert self.plan.k % ndev == 0, \
            f"k={self.plan.k} must be divisible by mesh axis size {ndev}"
        return self.plan.k // ndev

    def _sharded_plan(self) -> PartitionPlan:
        """Plan with leaves placed along the mesh axis, transferred once per
        Engine and reused across queries (stashed on the instance; frozen
        dataclasses still allow object.__setattr__)."""
        cached = getattr(self, "_plan_placed", None)
        if cached is None:
            cached = jax.device_put(
                self.plan, jax.tree_util.tree_map(
                    lambda _: NamedSharding(self.mesh, P(self.axis)),
                    self.plan))
            object.__setattr__(self, "_plan_placed", cached)
        return cached
