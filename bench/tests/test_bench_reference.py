"""The plain references against the program, at a small size on the
CPU; and the bfloat16 control, which the comparison must tell apart."""
from __future__ import annotations

import numpy as np
import pytest

from bench.reference import algos, dfep as ref_dfep, graphs

CFG = {"generator": "graph500", "scale": 9, "edgefactor": 16,
       "A": 0.57, "B": 0.19, "C": 0.19, "graph_seed": 0,
       "largest_component": True}
SOURCES = [0, 5, 17, 123, 300, 400]


@pytest.fixture(scope="module")
def small():
    """(n, u, v, program Graph, Engine on the XLA path, its plan)."""
    from repro import engine as E
    from repro.core import graph as G

    n, u, v = graphs.build(CFG)
    g = G.from_edge_array(n, np.stack([u, v], 1))
    owner = np.where(np.asarray(g.edge_mask), np.arange(g.e_pad) % 4, -2)
    plan = E.compile_plan(g, owner, 4)
    return n, u, v, g, E.Engine(plan, use_pallas=False)


def test_kronecker_quadrants_follow_the_specification():
    """Each bit of a tuple falls in the quadrants A, B, C, D with the
    specification's probabilities."""
    from bench.reference.generators import graph500

    ij = graph500.kronecker(10, 16, 0.57, 0.19, 0.19,
                            np.random.default_rng(1))
    assert ij.shape == (16 << 10, 2) and ij.min() >= 0 and ij.max() < 1024
    bits = np.stack([(ij >> b) & 1 for b in range(10)])   # [bit, M, 2]
    quad = bits[..., 0] * 2 + bits[..., 1]
    share = np.bincount(quad.ravel(), minlength=4) / quad.size
    assert np.allclose(share, [0.57, 0.19, 0.19, 0.05], atol=0.003)


def test_graph_is_the_canonical_largest_component():
    n, u, v = graphs.build(CFG)
    assert (u < v).all() and np.all(np.diff(u * n + v) > 0)
    assert np.array_equal(np.unique(np.concatenate([u, v])), np.arange(n))
    again = graphs.build(CFG)
    assert all(np.array_equal(a, b) for a, b in zip((n, u, v), again))


@pytest.mark.parametrize("kind", ["sssp", "bfs", "wsssp"])
def test_min_plus_reference_equals_served_answers(small, kind):
    from repro import gserve as S

    n, u, v, g, eng = small
    srv = S.GraphServer(eng, g, buckets=(8,))
    try:
        got = srv.serve([S.QueryRequest(kind, params={"source": s})
                         for s in SOURCES])
    finally:
        srv.close()
    want = getattr(algos, kind)(algos.Csr(n, u, v), SOURCES)
    for r, row in zip(got, want):
        assert algos.mismatches(r.value, row) == 0


def test_wcc_reference_equals_engine(small):
    from repro import engine as E

    n, u, v, g, eng = small
    got = np.asarray(eng.run(E.WCC).state)
    assert algos.mismatches(got, algos.wcc(algos.Csr(n, u, v))) == 0


def test_pagerank_reference_is_within_float32_rounding(small):
    from repro import engine as E

    n, u, v, g, eng = small
    got = np.asarray(eng.run(E.PAGERANK, max_supersteps=30,
                             degrees=g.degrees()).state)
    assert algos.rel_gap(got, algos.pagerank(algos.Csr(n, u, v))) < 1e-5


def test_gcn_reference_is_within_float32_rounding(small):
    from repro import engine as E

    n, u, v, g, eng = small
    rng = np.random.default_rng(3)
    x = rng.normal(size=(n, 8)).astype(np.float32)
    w = rng.normal(size=(8, 4)).astype(np.float32)
    got = np.asarray(eng.run(E.GCN_LAYER, degrees=g.degrees(), x=x,
                             weight=w).state)
    assert algos.rel_gap(got, algos.gcn(algos.Csr(n, u, v), x, w)) < 1e-5


def test_bfloat16_control_is_told_apart(small):
    n, u, v, _, _ = small
    csr = algos.Csr(n, u, v)
    wrong = algos.wsssp(csr, SOURCES, precision="bfloat16")
    right = algos.wsssp(csr, SOURCES)
    assert all(algos.mismatches(a, b) > 0 for a, b in zip(wrong, right))
    assert algos.rel_gap(algos.pagerank(csr, precision="bfloat16"),
                         algos.pagerank(csr)) > 1e-3
    x = np.random.default_rng(4).normal(size=(n, 8))
    w = np.random.default_rng(5).normal(size=(8, 4))
    assert algos.rel_gap(algos.gcn(csr, x, w, precision="bfloat16"),
                         algos.gcn(csr, x, w)) > 1e-3


def test_bf16_rounding():
    x = np.array([1.0, 1.00390625, 1.005859375, -3.0, np.inf], np.float32)
    assert algos.bf16(x).tolist() == [1.0, 1.0, 1.0078125, -3.0, np.inf]


@pytest.mark.parametrize("key", [0, 2147483647003])
def test_dfep_reference_equals_program(key):
    from repro.core import dfep, graph as G

    n, u, v = graphs.build(dict(CFG, scale=8))
    g = G.from_edge_array(n, np.stack([u, v], 1))
    owner, info = dfep.partition(g, k=8, key=key)
    want, winfo = ref_dfep.partition(n, u, v, 8, key)
    assert info["rounds"] == winfo["rounds"]
    assert np.array_equal(np.asarray(owner)[:len(u)], want)
    control, _ = ref_dfep.partition(n, u, v, 8, key, precision="bfloat16")
    assert np.count_nonzero(control != want) > 0


def test_dfep_reference_finalizes_a_stalled_auction():
    """A tiny stall limit leaves edges unsold; both sides hand them to
    the least-loaded neighbouring partition."""
    from repro.core import dfep, graph as G

    n, u, v = graphs.build(dict(CFG, scale=8))
    g = G.from_edge_array(n, np.stack([u, v], 1))
    owner, info = dfep.partition(g, k=8, key=3, max_rounds=6)
    want, winfo = ref_dfep.partition(n, u, v, 8, 3, max_rounds=6)
    assert info["finalized"] and winfo["finalized"]
    assert np.array_equal(np.asarray(owner)[:len(u)], want)
