"""Plain NumPy references of the graph programs the benchmark checks.

Each takes the canonical edge list (``graphs.canonical``) and computes the
program's semantics directly over the whole graph, with no partitioning:

  sssp      unit-weight distances, float, +inf where unreachable
  bfs       hop levels, -1 where unreachable
  wsssp     shortest paths over ``graphs.edge_weights``, +inf unreachable
  wcc       smallest vertex id of each vertex's component
  pagerank  ``iters`` rounds of r <- (1-d)/V + d * sum_u r_u / deg_u
  gcn       D^-1/2 A_w D^-1/2 X W, A_w weighted by ``edge_weights``

``precision`` picks the arithmetic.  The min-plus programs (sssp, bfs,
wsssp) run in ``"float32"``: relaxation by ``min(d_v, f32(d_u + w))`` is a
monotone map on a finite lattice, so every order of relaxation from +inf
reaches the same fixed point and a correct float32 program equals it
exactly.  The summing programs (pagerank, gcn) run in ``"float64"``, since
a partitioned float32 sum reassociates.  ``"bfloat16"`` rounds every
stored intermediate to bfloat16: the control that a correct program must
be told apart from.
"""
from __future__ import annotations

import numpy as np

from . import graphs

DAMPING = 0.85


def bf16(x: np.ndarray) -> np.ndarray:
    """Round to the nearest bfloat16 (ties to even), returned as float32."""
    x = np.asarray(x, np.float32)
    bits = x.view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    out = bits.astype(np.uint32).view(np.float32)
    return np.where(np.isfinite(x), out, x)


def _rounder(precision: str):
    if precision in ("float64", "float32"):
        return lambda x: x
    if precision == "bfloat16":
        return bf16
    raise ValueError(f"unknown precision {precision!r}")


class Csr:
    """Half-edges of an undirected graph, grouped by target vertex."""

    def __init__(self, n: int, u: np.ndarray, v: np.ndarray):
        self.n = n
        src = np.concatenate([u, v]).astype(np.int64)
        dst = np.concatenate([v, u]).astype(np.int64)
        order = np.argsort(dst, kind="stable")
        self.src, self.dst = src[order], dst[order]
        self.w = graphs.edge_weights(self.src, self.dst)
        self.deg = np.bincount(self.dst, minlength=n)
        self.targets = np.flatnonzero(self.deg)       # vertices with edges
        self.starts = np.searchsorted(self.dst, self.targets)

    def reduce(self, ufunc, vals: np.ndarray) -> np.ndarray:
        """Per-target ``ufunc`` reduction of per-half-edge values along the
        last axis -> [..., len(targets)]."""
        return ufunc.reduceat(vals, self.starts, axis=-1)


def _relax(csr: Csr, sources: np.ndarray, weights: np.ndarray | None,
           precision: str) -> np.ndarray:
    """Min-plus relaxation to the fixed point from each source -> [S, V]."""
    rnd = _rounder(precision)
    dtype = np.float64 if precision == "float64" else np.float32
    sources = np.asarray(sources, np.int64)
    dist = np.full((len(sources), csr.n), np.inf, dtype)
    dist[np.arange(len(sources)), sources] = 0.0
    w = np.ones(len(csr.src), dtype) if weights is None \
        else rnd(weights.astype(dtype))
    while True:
        cand = rnd(dist[:, csr.src] + w)
        best = csr.reduce(np.minimum, cand)
        new = dist.copy()
        new[:, csr.targets] = np.minimum(dist[:, csr.targets], best)
        if np.array_equal(new, dist):
            return dist
        dist = new


def sssp(csr: Csr, sources, precision: str = "float32") -> np.ndarray:
    return _relax(csr, sources, None, precision)


def bfs(csr: Csr, sources, precision: str = "float32") -> np.ndarray:
    d = _relax(csr, sources, None, precision)
    return np.where(np.isinf(d), -1.0, d)


def wsssp(csr: Csr, sources, precision: str = "float32") -> np.ndarray:
    return _relax(csr, sources, csr.w, precision)


def wcc(csr: Csr) -> np.ndarray:
    label = np.arange(csr.n, dtype=np.float64)
    while True:
        best = csr.reduce(np.minimum, label[csr.src])
        new = label.copy()
        new[csr.targets] = np.minimum(label[csr.targets], best)
        if np.array_equal(new, label):
            return label
        label = new


def pagerank(csr: Csr, iters: int = 30, precision: str = "float64"
             ) -> np.ndarray:
    rnd = _rounder(precision)
    dtype = np.float64 if precision == "float64" else np.float32
    n = csr.n
    deg = np.maximum(csr.deg, 1).astype(dtype)
    r = np.full(n, 1.0 / n, dtype)
    teleport = rnd(np.asarray((1.0 - DAMPING) / n, dtype))
    for _ in range(iters):
        contrib = rnd(r / deg)
        inflow = np.zeros(n, dtype)
        inflow[csr.targets] = rnd(csr.reduce(np.add, contrib[csr.src]))
        r = rnd(teleport + rnd(DAMPING * inflow))
    return r


def gcn(csr: Csr, x: np.ndarray, weight: np.ndarray,
        precision: str = "float64") -> np.ndarray:
    rnd = _rounder(precision)
    dtype = np.float64 if precision == "float64" else np.float32
    inv = rnd(1.0 / np.sqrt(np.maximum(csr.deg, 1).astype(dtype)))
    pre = rnd(rnd(x.astype(dtype)) * inv[:, None])           # [V, F]
    msgs = rnd(pre[csr.src] * rnd(csr.w.astype(dtype))[:, None])
    agg = np.zeros_like(pre)
    agg[csr.targets] = rnd(np.add.reduceat(msgs, csr.starts, axis=0))
    h = rnd(agg * inv[:, None])
    return rnd(h @ rnd(weight.astype(dtype)))


def rel_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Largest |got - want| over the finite entries, as a share of the
    reference's largest magnitude (entries near zero carry no scale of
    their own); +inf where one side is finite and the other is not, or
    the shapes differ: a wrong answer, not a rounding."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return float("inf")
    fin_g, fin_w = np.isfinite(got), np.isfinite(want)
    if np.any(fin_g != fin_w) or np.any(got[~fin_w] != want[~fin_w]):
        return float("inf")
    if not fin_w.any():
        return 0.0
    g, w = got[fin_w], want[fin_w]
    scale = max(float(np.max(np.abs(w))), np.finfo(np.float32).tiny)
    return float(np.max(np.abs(g - w)) / scale)


def mismatches(got: np.ndarray, want: np.ndarray) -> int:
    """Entries that differ (an exact comparison)."""
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        return max(got.size, want.size, 1)
    return int(np.count_nonzero(got.astype(np.float64)
                                != want.astype(np.float64)))
