"""The benchmark's own copy of the deployment's data: graph generation,
the canonical edge layout and the edge-weight rule.

Independent of the program under test.  A configuration names its
generator (``reference/generators/<name>.py``).  The canonical layout is
the one every consumer agrees on: undirected edges with ``u < v``, no self
loops, no duplicates, sorted by ``u * n + v``; edge ``i`` of that list is
edge id ``i``.
"""
from __future__ import annotations

import importlib

import numpy as np

#: Modulus of the content-hash edge weight (the weighted programs'
#: semantics: w(u, v) = 1 + h(min, max) / MOD, in [1, 2), float32).
EDGE_WEIGHT_MOD = 1_000_003


def canonical(n: int, edges: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(u, v) int64 arrays: u < v, self loops dropped, deduplicated and
    sorted by u * n + v."""
    edges = np.asarray(edges, np.int64).reshape(-1, 2)
    u = np.minimum(edges[:, 0], edges[:, 1])
    v = np.maximum(edges[:, 0], edges[:, 1])
    keep = u != v
    keys = np.unique(u[keep] * n + v[keep])
    return keys // n, keys % n


def largest_component(n: int, u: np.ndarray, v: np.ndarray
                      ) -> tuple[int, np.ndarray, np.ndarray]:
    """Restrict to the largest connected component (ties: the one with
    the smallest label) and renumber its vertices in increasing order."""
    label = np.arange(n)
    while True:
        m = np.minimum(label[u], label[v])
        new = label.copy()
        np.minimum.at(new, u, m)
        np.minimum.at(new, v, m)
        if np.array_equal(new, label):
            break
        label = new
    roots, counts = np.unique(label, return_counts=True)
    big = roots[np.argmax(counts)]
    keep = (label[u] == big) & (label[v] == big)
    u, v = u[keep], v[keep]
    verts = np.unique(np.concatenate([u, v]))
    remap = np.full(n, -1, np.int64)
    remap[verts] = np.arange(len(verts))
    nu, nv = canonical(len(verts), np.stack([remap[u], remap[v]], 1))
    return len(verts), nu, nv


def build(cfg: dict) -> tuple[int, np.ndarray, np.ndarray]:
    """(n_vertices, u, v) of a configuration, canonical layout.  The
    generator is ``reference/generators/<cfg["generator"]>.py``, found by
    name; with ``largest_component`` the graph is restricted to it."""
    gen = importlib.import_module(
        f"{__package__}.generators.{cfg['generator']}")
    n, raw = gen.edges(cfg)
    u, v = canonical(n, raw)
    if cfg.get("largest_component", False):
        return largest_component(n, u, v)
    return n, u, v


def edge_weights(u: np.ndarray, v: np.ndarray) -> np.ndarray:
    """float32 content-hash weight of each undirected edge, in [1, 2)."""
    a = np.minimum(u, v).astype(np.int64)
    b = np.maximum(u, v).astype(np.int64)
    h = (a * 2654435761 + b * 97_571 + 12_345) % EDGE_WEIGHT_MOD
    return (1.0 + h / EDGE_WEIGHT_MOD).astype(np.float32)
