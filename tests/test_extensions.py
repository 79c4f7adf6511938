"""Beyond-paper extensions: multi-source SSSP and k-core."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import algorithms as alg
from repro.core import dfep, graph
from repro.core.etsch import compile_partitioning


@pytest.fixture(scope="module")
def setup():
    g = graph.barabasi_albert(400, 3, seed=7)
    owner, _ = dfep.partition(g, k=4, key=0)
    part = compile_partitioning(g, owner, 4)
    return g, part


def test_multi_sssp_matches_single(setup):
    g, part = setup
    sources = jnp.array([0, 5, 17], jnp.int32)
    multi = alg.etsch_multi_sssp(part, sources)
    for i, s in enumerate([0, 5, 17]):
        ref, _ = alg.reference_sssp(g, s)
        got, want = np.asarray(multi.dist[i]), np.asarray(ref)
        finite = np.isfinite(want)
        assert (got[finite] == want[finite]).all()


@pytest.mark.parametrize("k_core", [2, 3, 5])
def test_kcore_matches_reference(setup, k_core):
    g, part = setup
    res = alg.etsch_kcore(part, k_core)
    want = alg.reference_kcore(g, k_core)
    assert np.array_equal(np.asarray(res.in_core), np.asarray(want))
    # k-core property: every member has >= k neighbours inside the core
    u, v = g.as_numpy()
    core = np.asarray(res.in_core)
    if core.any():
        deg = np.zeros(g.n_vertices, int)
        live = core[u] & core[v]
        np.add.at(deg, u[live], 1)
        np.add.at(deg, v[live], 1)
        assert (deg[core] >= k_core).all()
