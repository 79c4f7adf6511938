"""The Graph 500 benchmark's graph generator (Kronecker, "Graph
Generation" in the Graph 500 specification, https://graph500.org).

``2**scale`` vertices and ``edgefactor * 2**scale`` undirected edge
tuples.  Each tuple picks one quadrant of the adjacency matrix per bit of
the vertex label with probabilities A, B, C and D = 1 - A - B - C; the
vertex labels are then permuted at random, and so is the order of the
tuples.  Self loops and repeated tuples are kept here; building the graph
(``graphs.canonical``) drops them, as the specification's kernel 1 may.
Written from the specification's reference code, vectorised over the
tuples.
"""
from __future__ import annotations

import numpy as np


def kronecker(scale: int, edgefactor: int, a: float, b: float, c: float,
              rng: np.random.Generator) -> np.ndarray:
    """[M, 2] int64 tuples before the permutations."""
    m = int(edgefactor) << int(scale)
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    ij = np.zeros((m, 2), np.int64)
    for bit in range(int(scale)):
        i_bit = rng.random(m) > ab
        j_bit = rng.random(m) > np.where(i_bit, c_norm, a_norm)
        ij[:, 0] += i_bit.astype(np.int64) << bit
        ij[:, 1] += j_bit.astype(np.int64) << bit
    return ij


def edges(cfg: dict) -> tuple[int, np.ndarray]:
    """(vertex count, [M, 2] edge tuples) of a configuration, fixed by its
    ``graph_seed``."""
    rng = np.random.default_rng(int(cfg["graph_seed"]))
    scale = int(cfg["scale"])
    n = 1 << scale
    ij = kronecker(scale, int(cfg["edgefactor"]), float(cfg["A"]),
                   float(cfg["B"]), float(cfg["C"]), rng)
    ij = rng.permutation(n)[ij]
    return n, ij[rng.permutation(len(ij))]
