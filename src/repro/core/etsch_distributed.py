"""Distributed ETSCH: partitions → workers, frontier aggregation → collective.

This is the paper's Fig.-2 deployment: each worker holds ``K/ndev`` edge
partitions (subgraphs), runs the local phase independently, and the
aggregation phase is a single ``pmin``/``psum`` over the mesh axis — the
only communication, sized by Σ|F_i| (the paper's MESSAGES metric).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..sharding.env import auto_axes
from .etsch import Partitioning


def _pad_partitions(part: Partitioning, ndev: int) -> Partitioning:
    """Pad K to a multiple of ndev with empty partitions."""
    k = part.k
    k_pad = -(-k // ndev) * ndev
    if k_pad == k:
        return part
    pad = k_pad - k

    def padk(x, fill=0):
        return jnp.concatenate(
            [x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)], axis=0)

    return Partitioning(k_pad, part.n_vertices, part.e_max,
                        padk(part.src), padk(part.dst), padk(part.mask, False),
                        padk(part.member, False), padk(part.frontier, False))


def sssp_sharded(part: Partitioning, source: int, mesh: Mesh,
                 axis: str = "data", max_supersteps: int = 512):
    """Distributed SSSP over an edge partitioning. Returns (dist [V], supersteps).

    Local phase: masked Bellman-Ford sweeps to each worker's local fixed
    point. Aggregation: ``psum``-min over the mesh axis (frontier reconcile).
    """
    ndev = mesh.shape[axis]
    part = _pad_partitions(part, ndev)
    k_loc = part.k // ndev
    v_n = part.n_vertices
    src_v = jnp.asarray(source, jnp.int32)

    def worker(psrc, pdst, pmask, member):
        # shapes: [k_loc, E_max], member [k_loc, V]
        rows = jnp.arange(k_loc)[:, None]
        inf = jnp.float32(jnp.inf)
        is_src = (jnp.arange(v_n) == src_v)[None, :]
        dist = jnp.where(member & is_src, 0.0, inf)

        def local_sweep(d):
            du = jnp.where(pmask, d[rows, psrc] + 1.0, inf)
            dv = jnp.where(pmask, d[rows, pdst] + 1.0, inf)
            return d.at[rows, pdst].min(du).at[rows, psrc].min(dv)

        def local_fixpoint(d):
            def body(c):
                dd, _ = c
                nd = local_sweep(dd)
                return nd, jnp.any(nd != dd)
            d, _ = jax.lax.while_loop(lambda c: c[1], body, (d, jnp.bool_(True)))
            return d

        def superstep(carry):
            d, steps, _ = carry
            d1 = local_fixpoint(d)
            local_min = jnp.min(jnp.where(member, d1, inf), axis=0)   # [V]
            agg = jax.lax.pmin(local_min, axis)                       # frontier
            d2 = jnp.where(member, agg[None, :], inf)
            changed = jax.lax.psum(jnp.sum(jnp.where(d2 != d, 1, 0)), axis) > 0
            return d2, steps + 1, changed

        def cond(carry):
            _, steps, changed = carry
            return changed & (steps < max_supersteps)

        dist, steps, _ = jax.lax.while_loop(
            cond, superstep, (dist, jnp.int32(0), jnp.bool_(True)))
        out = jax.lax.pmin(jnp.min(jnp.where(member, dist, inf), axis=0), axis)
        return out, steps

    fn = jax.shard_map(worker, mesh=auto_axes(mesh),
                       in_specs=(P(axis), P(axis), P(axis), P(axis)),
                       out_specs=(P(), P()),
                       check_vma=False)
    dist, steps = jax.jit(fn)(part.src, part.dst, part.mask, part.member)
    return dist, int(steps)


def pagerank_sharded(part: Partitioning, degrees: jax.Array, mesh: Mesh,
                     axis: str = "data", iters: int = 30,
                     damping: float = 0.85) -> jax.Array:
    """Distributed PageRank: local partial in-flows, psum aggregation."""
    ndev = mesh.shape[axis]
    part = _pad_partitions(part, ndev)
    k_loc = part.k // ndev
    v_n = part.n_vertices
    deg = jnp.maximum(degrees.astype(jnp.float32), 1.0)

    def worker(psrc, pdst, pmask):
        rows = jnp.arange(k_loc)[:, None]
        rank = jnp.full((v_n,), 1.0 / v_n, jnp.float32)

        def step(rank, _):
            c = rank / deg
            cu = jnp.where(pmask, c[psrc], 0.0)
            cv = jnp.where(pmask, c[pdst], 0.0)
            part_in = jnp.zeros((k_loc, v_n), jnp.float32)
            part_in = part_in.at[rows, pdst].add(cu).at[rows, psrc].add(cv)
            local = jnp.sum(part_in, axis=0)
            inflow = jax.lax.psum(local, axis)            # aggregation phase
            return (1.0 - damping) / v_n + damping * inflow, None

        rank, _ = jax.lax.scan(step, rank, None, length=iters)
        return rank

    fn = jax.shard_map(worker, mesh=auto_axes(mesh),
                       in_specs=(P(axis), P(axis), P(axis)),
                       out_specs=P(), check_vma=False)
    return jax.jit(fn)(part.src, part.dst, part.mask)
