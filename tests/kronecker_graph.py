"""The Graph 500 Kronecker graph every benchmark cell runs, at a CPU size.

Built by the benchmark's own generator and component filter
(``bench/reference/graphs.py::build``), at SCALE 7 with the
specification's edgefactor 16 and initiator A 0.57, B 0.19, C 0.19
(D 0.05): a few vertices carry most of the edges, as the hubs of the
cells' SCALE 12 and 14 graphs do.
"""
from __future__ import annotations

import functools
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

CONFIG = {"generator": "graph500", "scale": 7, "edgefactor": 16,
          "A": 0.57, "B": 0.19, "C": 0.19, "D": 0.05, "graph_seed": 0,
          "largest_component": True}


@functools.cache
def graph500():
    """The largest component of the SCALE 7 Kronecker graph, as a Graph."""
    from bench.reference.graphs import build
    from repro.core import graph

    n, u, v = build(CONFIG)
    return graph.from_edge_array(n, np.stack([u, v], 1))
