"""Pallas TPU kernels for the partition-local engine layout.

Three kernels, all specialized to the ``PartitionPlan`` CSR blocks:

``segment_reduce``
    The gather/aggregate hot-spot of a superstep: reduce per-half-edge
    messages into per-target-vertex aggregates.  The CSR stream is sorted by
    target, so this is a *segmented* scan.  TPU mapping follows
    kernels/lane_cumsum.py: partitions are the 128-wide lane axis (each lane
    is one partition's independent edge stream), the edge-slot axis is
    blocked into [BLK_S, K] VMEM tiles walked sequentially, and a [1, K]
    VMEM scratch carries the running value of each lane's open segment
    across tiles.  Inside a tile the segmented combine runs as a log-step
    Hillis–Steele scan on (segment-start flag, value) pairs over the
    sublane axis (``pltpu.roll`` row shifts + masks).  The caller then
    picks each vertex's aggregate out of the scanned stream at
    ``plan.last_slot`` (a plain gather; padding slots hold the identity
    because the padding region starts a fresh identity-valued segment).
    Half-edges the streaming patch path appended after the sorted prefix
    are scatter-combined on top, in a ``lax.cond`` branch taken only when
    the plan's append region holds a live half-edge.  Under ``vmap`` (the
    batched serving path) a ``custom_vmap`` rule folds the batch onto the
    lane axis as a trailing feature axis, so a micro-batch of B queries
    is one scan over K·B lanes.

``gspmm``
    The fused GNN hot path (PR 10): gather neighbour feature rows,
    multiply by per-half-edge weights (scalar or per-feature planes),
    segment-reduce per target — DGL's ``u_mul_e_{sum,max,mean}`` gSpMM
    shape.  The multiply and the segmented combine run in ONE Pallas
    pass over the edge stream ([BLK_S, K·F] VMEM tiles, partitions
    major / features minor on the lane axis), so the weighted message
    stream is never materialised to HBM between them.  Under ``vmap`` the
    batch folds onto the lane axis as it does for ``segment_reduce``.
    ``gspmm_ref`` is the unfused XLA scatter reference.

``masked_update``
    The frontier/replica-update step of the exchange: replicated slots take
    the exchanged (cut-combined) value, private slots keep their local
    value, padding slots are pinned to the identity.  Mirrors the masked
    [K, V]-tile style of kernels/frontier_min.py.

All support combine ∈ {"min", "add", "max"} (SSSP/WCC, PageRank, GNN
max-pooling).  No caller picks how a kernel runs: ``_pallas_on_platform``
compiles it with Mosaic when the computation is lowered for a TPU and
interprets it on the CPU backend.  ``segment_reduce``,
``segment_reduce_ref`` and ``masked_update`` accept either scalar
[K, ·] streams or [K, ·, F] feature planes — the F axis is folded onto
the 128-wide lane axis, so scalar programs are literally the F=1 case
of the same kernels.

The message stream is per-half-edge, so weighted programs need no kernel
changes: the runtime applies the ``EdgeProgram.edge`` hook (e.g.
``msgs + plan.edge_w`` for weighted SSSP) after the neighbour gather, and
the weighted messages flow through the same segmented scan — masked
(deleted/padding) slots are pinned to the combine identity *after* the
hook, so they stay inert regardless of their weight.

``gather_vertex_channel`` / ``gather_edge_channel`` lay externally
supplied property planes (registry ``role="channel"`` params) out to the
partition-local padded shapes the programs consume — slack-aware (pad and
reserved slots pinned to the fill value) and fully traced, so the same
compiled gather serves every in-place plan patch.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_IDENTITY = {"min": jnp.inf, "add": 0.0, "max": -jnp.inf}
_OPS = {"min": jnp.minimum, "add": jnp.add, "max": jnp.maximum}
_TILE_BYTES = 512 * 1024    # one f32 edge-stream tile in VMEM
_BLOCK_V = 2048             # vertex slots per masked_update tile


def _scatter_combine(tgt: jax.Array, rows: jax.Array, cols: jax.Array,
                     vals: jax.Array, combine: str) -> jax.Array:
    """Scatter-combine ``vals`` into ``tgt[rows, cols]`` (identity-masked
    values are inert for every combine: inf/min, 0/add, -inf/max)."""
    at = tgt.at[rows, cols]
    if combine == "min":
        return at.min(vals)
    if combine == "max":
        return at.max(vals)
    return at.add(vals)


def _fold_append_region(plan, agg: jax.Array, messages, combine: str
                        ) -> jax.Array:
    """Combine the append region's live half-edges into ``agg``.

    The streaming patch path appends half-edges into ``[csr_fill, e_max)``
    in arbitrary order, each its own segment, so they are scatter-combined
    into the scanned aggregate ``agg`` [K, Vmax, F].  ``messages()`` builds
    the [K, Emax, F] message stream; it is called inside a ``lax.cond``
    branch taken only when some partition's append region holds a live
    half-edge, so a plan fresh from ``compile_plan`` (or one whose appended
    edges are all deleted) pays neither the stream nor the scatter.  The
    predicate reads the plan's dynamic children, so a patched plan keeps
    the same trace.  The branch covers every partition at once: slots that
    are masked or in a CSR prefix are pinned to the identity there."""
    ident = _IDENTITY[combine]
    k, e_max = plan.emask.shape
    slot = jnp.arange(e_max, dtype=jnp.int32)[None, :]
    appended = plan.emask & (slot >= plan.csr_fill[:, None])     # [K, Emax]

    def fold(agg):
        rows = jnp.arange(k, dtype=jnp.int32)[:, None]
        slack = jnp.where(appended[:, :, None], messages(), ident)
        return _scatter_combine(agg, rows, plan.edge_tgt, slack, combine)

    return jax.lax.cond(jnp.any(appended), fold, lambda agg: agg, agg)


def _pallas_on_platform(call, *args):
    """Run ``call(*args, interpret=...)`` for the platform the computation
    is lowered for: the CPU backend interprets the kernel body (how the
    tests run), ``tpu`` compiles it with Mosaic.  The choice is staged with
    ``lax.platform_dependent``, so an ahead-of-time compile for a TPU from a
    CPU-only process still gets the compiled kernel."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise NotImplementedError(
            f"the engine's Pallas kernels run compiled on TPU or "
            f"interpreted on CPU; no kernel path exists for {backend!r}")
    return jax.lax.platform_dependent(
        *args, cpu=functools.partial(call, interpret=True),
        tpu=functools.partial(call, interpret=False))


def _block_rows(s: int, lanes: int) -> int:
    """Edge-slot rows per VMEM tile: one f32 [rows, lanes] tile stays at
    ``_TILE_BYTES`` so the double-buffered operands of the widest kernel
    (gspmm: four inputs + one output) fit the default scoped VMEM at any
    lane width; never more rows than the (8-aligned) stream holds."""
    rows = max(8, _TILE_BYTES // (4 * lanes) // 8 * 8)
    return min(rows, -(-s // 8) * 8)


def _seg_scan_tile(f, v, op):
    """Inclusive segmented scan of one [rows, lanes] tile along the
    sublane axis: a log-step Hillis–Steele scan on (start flag, value)
    pairs.  Step ``d`` combines each row with the row ``d`` above it
    (``pltpu.roll`` shifts rows down; rows above ``d`` have no partner),
    unless the row already saw a segment start.  f: int32 0/1, v: f32."""
    row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
    d = 1
    while d < v.shape[0]:
        has = row >= d
        pf = pltpu.roll(f, d, 0)
        pv = pltpu.roll(v, d, 0)
        v = jnp.where(has & (f == 0), op(pv, v), v)
        f = jnp.where(has, f | pf, f)
        d *= 2
    return f, v


def _carry_out(f, v, o_ref, carry_ref, op):
    """Rows before the tile's first segment start continue the segment
    carried in from the previous tile; the last row carries forward."""
    out = jnp.where(f != 0, v, op(carry_ref[...], v))
    o_ref[...] = out
    carry_ref[...] = out[-1:, :]


def _seg_kernel(flags_ref, vals_ref, o_ref, carry_ref, *, combine: str):
    op = _OPS[combine]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        carry_ref[...] = jnp.full_like(carry_ref, _IDENTITY[combine])

    f, v = _seg_scan_tile(flags_ref[...], vals_ref[...], op)
    _carry_out(f, v, o_ref, carry_ref, op)


def _seg_call(flags, vals, *, combine: str, block_s: int, interpret: bool):
    s, k = vals.shape
    spec = pl.BlockSpec((block_s, k), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_seg_kernel, combine=combine),
        grid=(s // block_s,),
        in_specs=[spec] * 2,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((s, k), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, k), jnp.float32)],
        interpret=interpret,
        name="segment_scan",
    )(flags, vals)


@functools.partial(jax.jit, static_argnames=("combine",))
def segment_scan(flags: jax.Array, vals: jax.Array,
                 combine: str = "min") -> jax.Array:
    """Segmented inclusive scan along axis 0 of [S, K] streams.

    ``flags[s, k]`` True starts a new segment in lane k.  Returns the
    running combine of each open segment; the value at a segment's last row
    is the full segment reduction.
    """
    s, k = vals.shape
    ident = _IDENTITY[combine]
    k_pad = -(-k // 128) * 128
    block_s = _block_rows(s, k_pad)
    s_pad = -(-s // block_s) * block_s
    fp = jnp.zeros((s_pad, k_pad), jnp.int32).at[:s, :k].set(flags)
    # padding rows/lanes: identity values, no segment starts — harmless
    vp = jnp.full((s_pad, k_pad), ident, jnp.float32).at[:s, :k].set(vals)
    out = _pallas_on_platform(
        functools.partial(_seg_call, combine=combine, block_s=block_s),
        fp, vp)
    return out[:s, :k]


class _Csr(NamedTuple):
    """The plan arrays a segmented reduce reads (``edge_nbr`` only for
    gspmm's gather), in the order the kernels first read them.  The
    batching rules take these alone: a whole plan passed through
    ``custom_vmap`` keeps every leaf live in the unbatched executable."""
    edge_nbr: jax.Array | None
    csr_fill: jax.Array
    emask: jax.Array
    seg_start: jax.Array
    last_slot: jax.Array
    edge_tgt: jax.Array
    vmask: jax.Array


def _csr(plan, gather: bool = False) -> _Csr:
    return _Csr(plan.edge_nbr if gather else None, plan.csr_fill, plan.emask,
                plan.seg_start, plan.last_slot, plan.edge_tgt, plan.vmask)


def _planes4(x: jax.Array, batched: bool, size: int) -> jax.Array:
    """A rule's operand as [B, K, N, F]: unbatched operands are broadcast,
    scalar streams get F = 1."""
    if not batched:
        x = jnp.broadcast_to(x, (size,) + x.shape)
    return x[..., None] if x.ndim == 3 else x


def _to_lanes(x: jax.Array) -> jax.Array:
    """[B, K, N, F] -> [K, N, F·B]: the batch becomes the minor part of a
    trailing feature axis, so it lands on the kernels' lane axis next to
    the partitions (partition-major, feature, then batch)."""
    size, k, n, f = x.shape
    return jnp.moveaxis(x, 0, -1).reshape(k, n, f * size)


def _from_lanes(y: jax.Array, size: int) -> jax.Array:
    """[K, N, F·B] -> [B, K, N, F], the inverse of :func:`_to_lanes`."""
    k, n, fb = y.shape
    return jnp.moveaxis(y.reshape(k, n, fb // size, size), -1, 0)


def _vmapped_ref(ref, combine: str, size: int, in_batched, *args):
    """The XLA twin under ``vmap``, for a call whose plan is batched."""
    axes = jax.tree_util.tree_map(lambda b: 0 if b else None, in_batched)
    out = jax.vmap(functools.partial(ref, combine=combine),
                   in_axes=tuple(axes), axis_size=size)(*args)
    return out, True


def segment_reduce(plan, messages: jax.Array,
                   combine: str = "min") -> jax.Array:
    """Per-target aggregates over the plan's CSR stream.

    messages [K, Emax] or [K, Emax, F] (identity at masked slots) ->
    aggregates [K, Vmax] / [K, Vmax, F] (identity at padding vertices).
    Feature planes fold onto the lane axis (partition-major,
    feature-minor), so the scalar case is exactly F=1 of the same scan.
    Under ``vmap`` a batch of messages [B, K, Emax(, F)] over one plan
    is the unbatched call on [K, Emax, F·B]: one scan for the batch.  A
    batched plan falls back to the vmapped :func:`segment_reduce_ref`.

    Slack-aware bounds: the segmented scan covers only the sorted CSR prefix
    ``[0, csr_fill)`` of each lane; half-edges appended by the streaming
    patch path live in ``[csr_fill, e_max)`` in arbitrary order, so their
    contribution is combined by a masked scatter on top of the scanned
    aggregate, run only when some lane's append region holds a live
    half-edge (:func:`_fold_append_region`).  Masked (deleted/padding)
    slots are pinned to the combine identity in both regions and are
    therefore inert for every combine.
    """
    return _batched_segment_reduce(combine)(_csr(plan), messages)


@functools.cache
def _batched_segment_reduce(combine: str):
    @jax.custom_batching.custom_vmap
    def reduce(csr, messages):
        return _segment_reduce(csr, messages, combine)

    @reduce.def_vmap
    def rule(size, in_batched, csr, messages):
        csr_b, msgs_b = in_batched
        if any(jax.tree_util.tree_leaves(csr_b)):
            return _vmapped_ref(segment_reduce_ref, combine, size,
                                in_batched, csr, messages)
        scalar = messages.ndim == (3 if msgs_b else 2)
        lanes = _to_lanes(_planes4(messages, msgs_b, size))
        out = _from_lanes(_segment_reduce(csr, lanes, combine), size)
        return (out[..., 0] if scalar else out), True

    return reduce


def _segment_reduce(plan: _Csr, messages: jax.Array,
                    combine: str) -> jax.Array:
    ident = _IDENTITY[combine]
    squeeze = messages.ndim == 2
    msgs3 = messages[:, :, None] if squeeze else messages       # [K, Emax, F]
    k, e_max, f = msgs3.shape
    slot = jnp.arange(e_max, dtype=jnp.int32)[None, :]
    in_csr = slot < plan.csr_fill[:, None]                          # [K, Emax]
    msgs = jnp.where((plan.emask & in_csr)[:, :, None], msgs3, ident)
    stream = msgs.transpose(1, 0, 2).reshape(e_max, k * f)       # [Emax, K·F]
    flags = jnp.repeat(plan.seg_start.T, f, axis=1)
    scanned = segment_scan(flags, stream, combine=combine)
    scanned = scanned.reshape(e_max, k, f).transpose(1, 0, 2)    # [K, Emax, F]
    rows = jnp.arange(k, dtype=jnp.int32)[:, None]
    agg = scanned[rows, plan.last_slot]                          # [K, Vmax, F]
    agg = _fold_append_region(plan, agg, lambda: msgs3, combine)
    agg = jnp.where(plan.vmask[:, :, None], agg, ident)
    return agg[:, :, 0] if squeeze else agg


def gather_vertex_channel(plan, values: jax.Array) -> jax.Array:
    """Slack-aware layout of a global vertex property plane.

    values [V, F] (or [V]) -> [K, Vmax, F]: each live local slot takes its
    vertex's feature row via ``plan.local2global``; padding AND reserved
    slack slots (``vmask`` False) are pinned to 0.0 so a patched plan that
    populates a slack slot later picks the right row automatically — the
    gather runs traced, against the dynamic plan children, so it is valid
    for every in-place patch without retracing.  Programs call this from
    ``prepare`` (inside the shard_map body on mesh paths, where the local
    plan block gathers from the replicated [V, F] plane).
    """
    if values.ndim == 1:
        values = values[:, None]
    local = values[plan.local2global]                   # [K, Vmax, F]
    return jnp.where(plan.vmask[:, :, None], local, 0.0)


def gather_edge_channel(plan, values: jax.Array, fill: float = 0.0
                        ) -> jax.Array:
    """Slack-aware layout of an edge property plane in graph slot order.

    values [E_pad, F] (or [E_pad]) -> [K, Emax, F]: every live half-edge
    (CSR prefix *and* append/slack region — ``plan.edge_slot`` is
    maintained by both compile_plan and the streaming patch path) takes the
    feature row of its undirected edge's graph slot; pad slots and
    half-edges whose slot is unknown (patched in without slot provenance,
    edge_slot == -1) take ``fill``.  Masked slots are additionally pinned
    to the combine identity downstream of the ``edge`` hook, so garbage can
    never leak into an aggregate.
    """
    if values.ndim == 1:
        values = values[:, None]
    # slots beyond the supplied plane read ``fill``, never a clamped row —
    # a plane covering only the CSR prefix must fail soft, not alias row n-1
    ok = plan.emask & (plan.edge_slot >= 0) \
        & (plan.edge_slot < values.shape[0])
    rows = jnp.clip(plan.edge_slot, 0, values.shape[0] - 1)
    local = values[rows]                                # [K, Emax, F]
    return jnp.where(ok[:, :, None], local, jnp.float32(fill))


def segment_reduce_ref(plan, messages: jax.Array,
                       combine: str = "min") -> jax.Array:
    """XLA scatter reference for :func:`segment_reduce`.

    Accepts [K, Emax] or [K, Emax, F] messages like :func:`segment_reduce`.
    """
    ident = _IDENTITY[combine]
    squeeze = messages.ndim == 2
    msgs3 = messages[:, :, None] if squeeze else messages
    msgs = jnp.where(plan.emask[:, :, None], msgs3, ident)
    k = plan.edge_tgt.shape[0]
    rows = jnp.arange(k, dtype=jnp.int32)[:, None]
    v_max = plan.vmask.shape[1]
    out = jnp.full((k, v_max, msgs3.shape[2]), ident, jnp.float32)
    out = _scatter_combine(out, rows, plan.edge_tgt, msgs, combine)
    out = jnp.where(plan.vmask[:, :, None], out, ident)
    return out[:, :, 0] if squeeze else out


def _gspmm_kernel(flags_ref, mask_ref, w_ref, vals_ref, o_ref, carry_ref, *,
                  combine: str):
    """Fused multiply + segmented combine over one [BLK_S, K·F] tile.

    Every operand arrives in the K·F lane layout (partitions major,
    features minor).  The weighted message x = v·w is formed and
    identity-masked inside the kernel — the weighted stream never exists
    in HBM.
    """
    op = _OPS[combine]
    ident = jnp.float32(_IDENTITY[combine])

    @pl.when(pl.program_id(0) == 0)
    def _init():
        carry_ref[...] = jnp.full_like(carry_ref, ident)

    # multiply BEFORE masking: a dead slot's weight can never rescue it,
    # and the identity (±inf for min/max) is never multiplied by 0
    x = jnp.where(mask_ref[...] != 0, vals_ref[...] * w_ref[...], ident)
    f, v = _seg_scan_tile(flags_ref[...], x, op)
    _carry_out(f, v, o_ref, carry_ref, op)


def _gspmm_call(flags, mask, w, vals, *, combine: str, block_s: int,
                interpret: bool):
    s, kf = vals.shape
    spec = pl.BlockSpec((block_s, kf), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_gspmm_kernel, combine=combine),
        grid=(s // block_s,),
        in_specs=[spec] * 4,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((s, kf), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, kf), jnp.float32)],
        interpret=interpret,
        name="gspmm",
    )(flags, mask, w, vals)


@functools.partial(jax.jit, static_argnames=("combine",))
def _gspmm_scan(flags: jax.Array, mask: jax.Array, w: jax.Array,
                vals: jax.Array, combine: str) -> jax.Array:
    """Segmented scan of masked v·w streams: flags/mask [S, K] bool,
    w [S, K] or [S, K·F], vals [S, K·F] -> scanned [S, K·F].  K-wide
    operands are spread to the K·F lane layout here, outside the kernel."""
    s, kf = vals.shape
    f = kf // flags.shape[1]
    block_s = _block_rows(s, kf)
    s_pad = -(-s // block_s) * block_s

    def lanes(x, dtype):
        x = x.astype(dtype)
        if x.shape[1] != kf:
            x = jnp.repeat(x, f, axis=1)
        return jnp.zeros((s_pad, kf), dtype).at[:s].set(x)

    out = _pallas_on_platform(
        functools.partial(_gspmm_call, combine=combine, block_s=block_s),
        lanes(flags, jnp.int32), lanes(mask, jnp.int32),
        lanes(w, jnp.float32), lanes(vals, jnp.float32))
    return out[:s]


def _pad_k(x: jax.Array, k_pad: int, fill) -> jax.Array:
    """Pad the leading partition axis to ``k_pad`` lanes with ``fill``."""
    k = x.shape[0]
    if k_pad == k:
        return x
    pad = jnp.full((k_pad - k,) + x.shape[1:], fill, x.dtype)
    return jnp.concatenate([x, pad], axis=0)


def gspmm(plan, feats: jax.Array, weights: jax.Array,
          combine: str = "add") -> jax.Array:
    """Fused gSpMM: gather · multiply · segment-reduce in one kernel pass.

    DGL's ``u_mul_e_{sum,max,mean}`` shape on the partition-local layout:

    feats   [K, Vmax, F] (or [K, Vmax]) local feature rows, e.g. from
            :func:`gather_vertex_channel` or a program's ``pre``;
    weights [K, Emax] scalar per-half-edge (``plan.edge_w``) or
            [K, Emax, F] per-feature planes (a bound edge channel);
    combine "add"/"sum", "max", or "mean" (sum / clamped live-degree,
            isolated vertices aggregate to 0)
    -> [K, Vmax, F] per-target aggregates, identity at padding slots.

    The neighbour gather reuses the slack-aware ``plan.edge_nbr`` indices
    (maintained by the streaming patch path), the CSR prefix flows through
    ONE fused Pallas multiply+scan pass, and append-region half-edges are
    folded in by the same masked scatter as :func:`segment_reduce`, run
    only when the region holds a live half-edge — so the result is exact
    under in-place plan patches.  Partitions are padded so K·F stays a
    multiple of the 128-lane tile.  Under ``vmap`` the batch folds onto
    the lane axis as in :func:`segment_reduce` (weights that vary by lane
    fold with it); a batched plan falls back to the vmapped
    :func:`gspmm_ref`.
    """
    if combine == "sum":
        combine = "add"
    if combine == "mean":
        s = gspmm(plan, feats, weights, "add")
        cnt = segment_reduce(plan, jnp.ones(plan.emask.shape, jnp.float32),
                             "add")
        return s / jnp.maximum(cnt, 1.0)[:, :, None]
    return _batched_gspmm(combine)(_csr(plan, gather=True), feats, weights)


@functools.cache
def _batched_gspmm(combine: str):
    @jax.custom_batching.custom_vmap
    def fused(csr, feats, weights):
        return _gspmm(csr, feats, weights, combine)

    @fused.def_vmap
    def rule(size, in_batched, csr, feats, weights):
        csr_b, feats_b, w_b = in_batched
        if any(jax.tree_util.tree_leaves(csr_b)):
            return _vmapped_ref(gspmm_ref, combine, size, in_batched, csr,
                                feats, weights)
        f4 = _planes4(feats, feats_b, size)                 # [B, K, Vmax, F]
        if w_b or weights.ndim == 3:        # per-lane weights: fold them too
            w4 = _planes4(weights, w_b, size)
            weights = _to_lanes(jnp.broadcast_to(w4, w4.shape[:3]
                                                 + f4.shape[3:]))
        out = _gspmm(csr, _to_lanes(f4), weights, combine)
        return _from_lanes(out, size), True

    return fused


def _gspmm(plan: _Csr, feats: jax.Array, weights: jax.Array,
           combine: str) -> jax.Array:
    if feats.ndim == 2:
        feats = feats[:, :, None]
    k, e_max = plan.emask.shape
    f = feats.shape[2]
    ident = _IDENTITY[combine]
    rows = jnp.arange(k, dtype=jnp.int32)[:, None]
    msgs = feats[rows, plan.edge_nbr]                       # [K, Emax, F]
    w3 = weights[:, :, None] if weights.ndim == 2 else weights
    slot = jnp.arange(e_max, dtype=jnp.int32)[None, :]
    in_csr = slot < plan.csr_fill[:, None]
    live = plan.emask & in_csr
    # lane padding: k_pad·F a multiple of 128 so the folded lane axis tiles
    step = 128 // math.gcd(f, 128)
    k_pad = -(-k // step) * step
    flags = _pad_k(plan.seg_start, k_pad, False).T          # [Emax, k_pad]
    maskt = _pad_k(live, k_pad, False).T
    vals = _pad_k(msgs, k_pad, 0.0).transpose(1, 0, 2).reshape(
        e_max, k_pad * f)
    if weights.ndim == 2:
        wop = _pad_k(weights, k_pad, 0.0).T                 # [Emax, k_pad]
    else:
        wop = _pad_k(w3, k_pad, 0.0).transpose(1, 0, 2).reshape(
            e_max, k_pad * f)
    scanned = _gspmm_scan(flags, maskt, wop, vals, combine=combine)
    scanned = scanned.reshape(e_max, k_pad, f).transpose(1, 0, 2)[:k]
    agg = scanned[rows, plan.last_slot]                     # [K, Vmax, F]
    # append-region half-edges are weighted outside the kernel, and only
    # when the region holds one
    agg = _fold_append_region(plan, agg, lambda: msgs * w3, combine)
    return jnp.where(plan.vmask[:, :, None], agg, ident)


def gspmm_ref(plan, feats: jax.Array, weights: jax.Array,
              combine: str = "add") -> jax.Array:
    """Unfused XLA reference for :func:`gspmm`: gather, materialise the
    weighted message stream, scatter segment-reduce."""
    if combine == "sum":
        combine = "add"
    if combine == "mean":
        s = gspmm_ref(plan, feats, weights, "add")
        cnt = segment_reduce_ref(plan, jnp.ones(plan.emask.shape,
                                                jnp.float32), "add")
        return s / jnp.maximum(cnt, 1.0)[:, :, None]
    if feats.ndim == 2:
        feats = feats[:, :, None]
    rows = jnp.arange(plan.emask.shape[0], dtype=jnp.int32)[:, None]
    msgs = feats[rows, plan.edge_nbr]
    w3 = weights[:, :, None] if weights.ndim == 2 else weights
    return segment_reduce_ref(plan, msgs * w3, combine)


def _update_kernel(state_ref, inc_ref, vmask_ref, rep_ref, o_ref, *,
                   combine: str):
    ident = jnp.float32(_IDENTITY[combine])
    st = state_ref[...]
    inc = inc_ref[...]
    new = jnp.where(rep_ref[...], inc, st)
    o_ref[...] = jnp.where(vmask_ref[...], new, ident)


def _update_call(state, incoming, vmask, replicated, *, combine: str,
                 interpret: bool):
    k_pad, v_pad = state.shape
    spec = pl.BlockSpec((k_pad, _BLOCK_V), lambda i: (0, i))
    return pl.pallas_call(
        functools.partial(_update_kernel, combine=combine),
        grid=(v_pad // _BLOCK_V,),
        in_specs=[spec] * 4,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((k_pad, v_pad), jnp.float32),
        interpret=interpret,
        name="masked_update",
    )(state, incoming, vmask, replicated)


@functools.partial(jax.jit, static_argnames=("combine",))
def masked_update(state: jax.Array, incoming: jax.Array, vmask: jax.Array,
                  replicated: jax.Array, combine: str = "min") -> jax.Array:
    """Apply exchanged values to replicated slots: state/incoming [K, Vmax]
    or [K, Vmax, F] (the feature axis folds onto the slot axis — masks are
    per-vertex, so they broadcast by repetition)."""
    if state.ndim == 3:
        k, v, f = state.shape
        out = masked_update(state.reshape(k, v * f),
                            incoming.reshape(k, v * f),
                            jnp.repeat(vmask, f, axis=1),
                            jnp.repeat(replicated, f, axis=1),
                            combine=combine)
        return out.reshape(k, v, f)
    k, v = state.shape
    ident = _IDENTITY[combine]
    k_pad = -(-k // 8) * 8
    v_pad = -(-v // _BLOCK_V) * _BLOCK_V
    sp = jnp.full((k_pad, v_pad), ident, jnp.float32).at[:k, :v].set(state)
    ip = jnp.full((k_pad, v_pad), ident, jnp.float32).at[:k, :v].set(incoming)
    mp = jnp.zeros((k_pad, v_pad), jnp.bool_).at[:k, :v].set(vmask)
    rp = jnp.zeros((k_pad, v_pad), jnp.bool_).at[:k, :v].set(replicated)
    out = _pallas_on_platform(
        functools.partial(_update_call, combine=combine), sp, ip, mp, rp)
    return out[:k, :v]
