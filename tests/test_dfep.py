"""DFEP behaviour tests: validity, balance, connectedness, money conservation."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import dfep, graph, metrics
from repro.core.etsch import compile_partitioning

from kronecker_graph import graph500

GRAPHS = {
    "smallworld": lambda: graph.watts_strogatz(600, 6, 0.1, seed=3),
    "graph500": graph500,
}


@pytest.fixture(scope="module", params=list(GRAPHS))
def small_graph(request):
    return GRAPHS[request.param]()


@pytest.fixture(scope="module")
def small_slots(small_graph):
    return dfep.build_slots(small_graph)


@pytest.fixture(scope="module")
def small_partition(small_graph, small_slots):
    owner, info = dfep.partition(small_graph, k=6, key=0, slots=small_slots)
    return owner, info


def test_partition_is_total_and_disjoint(small_graph, small_partition):
    owner, info = small_partition
    own = np.asarray(owner)
    em = np.asarray(small_graph.edge_mask)
    # every real edge owned by exactly one valid partition
    assert (own[em] >= 0).all() and (own[em] < 6).all()
    # padding slots are never assigned
    assert (own[~em] == -2).all()


def test_partition_covers_all_edges(small_graph, small_partition):
    owner, _ = small_partition
    own = np.asarray(owner)[np.asarray(small_graph.edge_mask)]
    assert np.bincount(own, minlength=6).sum() == small_graph.n_edges


def test_balance(small_graph, small_partition):
    owner, info = small_partition
    m = metrics.evaluate(small_graph, owner, 6, compute_gain=False)
    # paper-quality balance on a small-world graph
    assert m.largest_norm < 1.5, m.largest_norm
    assert m.nstdev < 0.35, m.nstdev


def test_connectedness(small_graph, small_partition):
    """DFEP (non-C) partitions are connected subgraphs (paper §IV)."""
    owner, info = small_partition
    if info["finalized"]:
        pytest.skip("stall fallback used; connectedness not guaranteed")
    m = metrics.evaluate(small_graph, owner, 6, compute_gain=False)
    assert m.connected_frac == 1.0


def test_money_conservation_per_round(small_graph, small_slots):
    """Units only enter via init+grants and leave 1 per purchase."""
    g, slots = small_graph, small_slots
    cfg = dfep.DfepConfig(k=4)
    st = dfep.init_state(g, cfg, jax.random.key(1))
    rnd = jax.jit(lambda s: dfep._round(g, slots, cfg, s))
    for _ in range(30):
        before_money = int(jnp.sum(st.mv))
        before_owned = int(jnp.sum(st.owner >= 0))
        st2 = rnd(st)
        after_money = int(jnp.sum(st2.mv))
        after_owned = int(jnp.sum(st2.owner >= 0))
        bought = after_owned - before_owned
        sizes = dfep._sizes(st2.owner, 4)
        grant = jnp.minimum(cfg.cap, -(-jnp.int32(g.n_edges) // jnp.maximum(sizes, 1)))
        remaining = int(jnp.sum(st2.owner == dfep.FREE))
        granted = int(jnp.sum(grant)) if remaining > 0 else 0
        assert after_money == before_money - bought + granted
        st = st2


def test_owner_never_unassigned(small_graph, small_slots):
    """Once sold, an edge stays sold (plain DFEP; DFEP-C may only transfer)."""
    g, slots = small_graph, small_slots
    cfg = dfep.DfepConfig(k=4)
    st = dfep.init_state(g, cfg, jax.random.key(2))
    rnd = jax.jit(lambda s: dfep._round(g, slots, cfg, s))
    prev = np.asarray(st.owner)
    for _ in range(40):
        st = rnd(st)
        cur = np.asarray(st.owner)
        sold_before = prev >= 0
        assert (cur[sold_before] == prev[sold_before]).all()
        prev = cur


def test_variant_c_transfers_only_to_poor(small_graph, small_slots):
    g, slots = small_graph, small_slots
    cfg = dfep.DfepConfig(k=4, variant_c=True)
    st = dfep.init_state(g, cfg, jax.random.key(3))
    rnd = jax.jit(lambda s: dfep._round(g, slots, cfg, s))
    for _ in range(60):
        prev = np.asarray(st.owner)
        st = rnd(st)
        cur = np.asarray(st.owner)
        moved = (prev >= 0) & (cur != prev)
        if moved.any():
            sizes = np.bincount(prev[prev >= 0], minlength=4)
            mean = sizes.sum() / 4
            # recipients were poor at the time of the steal
            assert (sizes[cur[moved]] < mean / cfg.poor_p + 1).all()


def test_determinism(small_graph, small_slots):
    a, _ = dfep.partition(small_graph, k=4, key=7, slots=small_slots)
    b, _ = dfep.partition(small_graph, k=4, key=7, slots=small_slots)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_road_graph_variant_c_beats_plain_on_balance():
    """Paper fig 6/7: on large-diameter graphs DFEP-C balances better."""
    g = graph.road_network(28, 28, 0.25, seed=0)
    slots = dfep.build_slots(g)
    _, info_a = dfep.partition(g, k=8, key=1, slots=slots)
    owner_a, _ = dfep.partition(g, k=8, key=1, slots=slots)
    owner_c, _ = dfep.partition(g, k=8, key=1, variant_c=True, slots=slots)
    ma = metrics.evaluate(g, owner_a, 8, compute_gain=False)
    mc = metrics.evaluate(g, owner_c, 8, compute_gain=False)
    # DFEP-C should not be (much) worse balanced on a road network
    assert mc.nstdev <= ma.nstdev * 1.25 + 0.05


def test_compile_partitioning_roundtrip(small_graph, small_partition):
    owner, _ = small_partition
    part = compile_partitioning(small_graph, owner, 6)
    sizes = np.asarray(part.sizes)
    own = np.asarray(owner)[np.asarray(small_graph.edge_mask)]
    assert (sizes == np.bincount(own, minlength=6)).all()
    # members: every edge endpoint of partition k is a member
    member = np.asarray(part.member)
    ps, pd, pm = np.asarray(part.src), np.asarray(part.dst), np.asarray(part.mask)
    for k in range(6):
        assert member[k, ps[k][pm[k]]].all()
        assert member[k, pd[k][pm[k]]].all()
