"""repro.engine correctness: plan compaction round-trips the edge set, and
engine SSSP / WCC / PageRank match the whole-graph oracles in
core/algorithms.py across graph profiles × partitioners × K."""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.core import algorithms as alg
from repro.core import baselines, dfep, graph, metrics
from repro import engine as E

PROFILES = {
    "smallworld": lambda: graph.watts_strogatz(150, 4, 0.1, seed=1),
    "powerlaw": lambda: graph.largest_component(
        graph.barabasi_albert(120, 3, seed=2)),
    "road": lambda: graph.largest_component(
        graph.road_network(10, 12, 0.25, seed=3)),
}

PARTITIONERS = {
    "dfep": lambda g, k: np.asarray(
        dfep.partition(g, k=k, key=0, max_rounds=400, stall_rounds=16)[0]),
    "greedy": lambda g, k: np.asarray(baselines.greedy_partition(g, k, seed=0)),
    "hash": lambda g, k: np.asarray(baselines.hash_partition(g, k)),
}


@pytest.fixture(scope="module")
def graphs():
    return {name: build() for name, build in PROFILES.items()}


@pytest.mark.parametrize("profile", list(PROFILES))
def test_plan_roundtrips_edge_set(graphs, profile):
    """Compacted per-partition CSR blocks contain exactly the owned edges."""
    g = graphs[profile]
    owner = baselines.hash_partition(g, 4)
    plan = E.compile_plan(g, owner, 4)
    u, v = g.as_numpy()
    want = np.unique(np.stack([np.minimum(u, v), np.maximum(u, v)], 1), axis=0)
    per_part = plan.local_edges()
    got = np.unique(np.concatenate(per_part, 0), axis=0)
    assert np.array_equal(want, got)
    # partitions are disjoint: per-partition counts sum to |E|
    assert sum(len(p) for p in per_part) == g.n_edges
    own = np.asarray(owner)[np.asarray(g.edge_mask)]
    for i in range(4):
        assert len(per_part[i]) == int((own == i).sum())


@pytest.mark.parametrize("profile", list(PROFILES))
def test_plan_masters_and_replicas(graphs, profile):
    g = graphs[profile]
    owner = baselines.greedy_partition(g, 4, seed=0)
    plan = E.compile_plan(g, owner, 4)
    l2g = np.asarray(plan.local2global)
    vmask = np.asarray(plan.vmask)
    master = np.asarray(plan.is_master)
    rep = np.asarray(plan.replicated)
    # every present vertex has exactly one master
    masters = np.bincount(l2g[master], minlength=g.n_vertices)
    present = np.zeros(g.n_vertices, bool)
    present[l2g[vmask]] = True
    assert (masters[present] == 1).all() and (masters[~present] == 0).all()
    # replicated <=> copy count >= 2
    copies = np.bincount(l2g[vmask], minlength=g.n_vertices)
    assert ((copies[l2g] >= 2) & vmask == rep).all()


@pytest.mark.parametrize("partitioner", list(PARTITIONERS))
@pytest.mark.parametrize("profile", list(PROFILES))
def test_engine_matches_oracles(graphs, profile, partitioner):
    """SSSP and WCC bit-identical, PageRank within 1e-5, for K in {2,4,8}."""
    g = graphs[profile]
    for k in (2, 4, 8):
        owner = PARTITIONERS[partitioner](g, k)
        plan = E.compile_plan(g, owner, k)
        eng = E.Engine(plan)

        r = E.engine_sssp(eng, 0)
        ref, ref_rounds = alg.reference_sssp(g, 0)
        assert np.array_equal(np.asarray(r.state), np.asarray(ref)), \
            (profile, partitioner, k, "sssp")
        # edge-partitioned execution needs no more rounds than vertex-centric
        assert int(r.supersteps) <= int(ref_rounds)

        rw = E.engine_wcc(eng)
        refc, _ = alg.reference_cc(g)
        assert np.array_equal(np.asarray(rw.state), np.asarray(refc)), \
            (profile, partitioner, k, "wcc")

        rp = E.engine_pagerank(eng, g.degrees(), iters=20)
        refp = alg.reference_pagerank(g, iters=20)
        np.testing.assert_allclose(np.asarray(rp.state), np.asarray(refp),
                                   atol=1e-5)

        # replica-exchange volume agrees with the combinatorial MESSAGES
        m = metrics.evaluate(g, owner, k, compute_gain=False)
        assert plan.exchange_per_superstep() == m.messages
        assert r.total_exchanged == int(r.supersteps) * m.messages


@pytest.mark.parametrize("partitioner", list(PARTITIONERS))
@pytest.mark.parametrize("profile", list(PROFILES))
def test_weighted_sssp_and_bfs_match_oracles(graphs, profile, partitioner):
    """The two registry-registered programs: weighted SSSP (per-half-edge
    content-hash weights via plan.edge_w + the EdgeProgram ``edge`` hook)
    and BFS hop levels — bit-identical to core/algorithms.py oracles."""
    g = graphs[profile]
    for k in (2, 4):
        owner = PARTITIONERS[partitioner](g, k)
        eng = E.Engine(E.compile_plan(g, owner, k))
        rw = E.engine_weighted_sssp(eng, 0)
        refw = alg.reference_weighted_sssp(g, 0)
        assert np.array_equal(np.asarray(rw.state), refw), \
            (profile, partitioner, k, "wsssp")
        rb = E.engine_bfs(eng, 0)
        refb = alg.reference_bfs(g, 0)
        assert np.array_equal(np.asarray(rb.state), refb), \
            (profile, partitioner, k, "bfs")


@pytest.mark.parametrize("partitioner", list(PARTITIONERS))
@pytest.mark.parametrize("profile", list(PROFILES))
def test_channel_programs_match_oracles(graphs, profile, partitioner):
    """The two property-channel programs: label propagation over an
    external [V] label plane (bit-identical — labels flow through min
    only) and personalized PageRank with an external teleport vector
    (1e-5, like plain PageRank: f32 partial sums reassociate)."""
    g = graphs[profile]
    rng = np.random.default_rng(11)
    labels = rng.integers(0, 40, size=g.n_vertices).astype(np.float32)
    pers = rng.random(g.n_vertices).astype(np.float32)
    pers /= pers.sum()
    ref_lp = alg.reference_label_propagation(g, labels)
    ref_pp = alg.reference_personalized_pagerank(g, pers, iters=15)
    for k in (2, 4):
        owner = PARTITIONERS[partitioner](g, k)
        eng = E.Engine(E.compile_plan(g, owner, k))
        rl = E.engine_label_propagation(eng, labels)
        assert np.array_equal(np.asarray(rl.state), ref_lp), \
            (profile, partitioner, k, "labelprop")
        rp = E.engine_personalized_pagerank(eng, g.degrees(), pers, iters=15)
        np.testing.assert_allclose(np.asarray(rp.state), ref_pp, atol=1e-5)


def test_labelprop_warm_init_exact():
    """Insert-only repair contract for labelprop: a previous epoch's labels
    are valid upper bounds (a larger component only lowers the min)."""
    g = graph.watts_strogatz(120, 4, 0.05, seed=4)
    owner = baselines.hash_partition(g, 3)
    eng = E.Engine(E.compile_plan(g, owner, 3))
    labels = np.arange(g.n_vertices, dtype=np.float32)
    cold = eng.run(E.LABELPROP, labels=jnp.asarray(labels))
    warm = eng.run(E.LABELPROP, labels=jnp.asarray(labels),
                   warm_state=cold.state)
    assert np.array_equal(np.asarray(warm.state), np.asarray(cold.state))
    assert int(warm.supersteps) == 1 <= int(cold.supersteps)


def test_warm_init_exact_and_fewer_supersteps(graphs):
    """warm_init: re-running from a previous exact result converges in one
    superstep with an identical answer; warm-starting from upper bounds
    (the insert-only repair scenario) also stays exact. +inf rows of a
    batched warm block cold-start their lane."""
    g = graphs["road"]          # high diameter -> many cold supersteps
    owner = baselines.greedy_partition(g, 4, seed=0)
    eng = E.Engine(E.compile_plan(g, owner, 4))
    cold = eng.run(E.SSSP, source=jnp.int32(0))
    warm = eng.run(E.SSSP, source=jnp.int32(0), warm_state=cold.state)
    assert np.array_equal(np.asarray(warm.state), np.asarray(cold.state))
    assert int(warm.supersteps) == 1 < int(cold.supersteps)
    # upper-bound init (everything shifted up, except the exact source row)
    upper = np.minimum(np.asarray(cold.state) + 2.0, np.inf)
    upper[0] = 0.0
    rep = eng.run(E.SSSP, source=jnp.int32(0), warm_state=upper)
    assert np.array_equal(np.asarray(rep.state), np.asarray(cold.state))
    # batched: lane 0 warm (exact prev), lane 1 "no information" (+inf)
    srcs = np.array([0, 5], np.int32)
    block = np.stack([np.asarray(cold.state),
                      np.full(g.n_vertices, np.inf, np.float32)])
    rb = eng.run_batched(E.SSSP, {"source": srcs}, warm_state=block)
    ref0, _ = alg.reference_sssp(g, 0)
    ref5, _ = alg.reference_sssp(g, 5)
    assert np.array_equal(np.asarray(rb.state[0]), np.asarray(ref0))
    assert np.array_equal(np.asarray(rb.state[1]), np.asarray(ref5))
    ss = np.asarray(rb.supersteps).reshape(-1)
    assert ss[0] <= ss[1], "the warm lane must not converge slower"


def test_multi_source_batched(graphs):
    """Serving path: one vmapped loop answers a batch of sources."""
    g = graphs["smallworld"]
    owner = baselines.greedy_partition(g, 4, seed=0)
    eng = E.Engine(E.compile_plan(g, owner, 4))
    sources = [0, 3, 11, 42]
    res = E.multi_source_sssp(eng, sources)
    assert res.state.shape == (len(sources), g.n_vertices)
    for i, s in enumerate(sources):
        ref, _ = alg.reference_sssp(g, s)
        assert np.array_equal(np.asarray(res.state[i]), np.asarray(ref)), s


def test_plan_cache_counters_and_lru_eviction():
    """compile_plan_cached observability: hits/misses count, and filling the
    cache past _PLAN_CACHE_MAX evicts in LRU order."""
    from repro.engine import plan as P

    E.plan_cache_clear(reset_counters=True)
    base = graph.watts_strogatz(60, 4, 0.1, seed=9)
    owner = np.where(np.asarray(base.edge_mask), 0, -2)

    p1 = E.compile_plan_cached(base, owner, 2)
    assert E.plan_cache_stats()["misses"] == 1
    assert E.compile_plan_cached(base, owner, 2) is p1
    assert E.plan_cache_stats()["hits"] == 1

    # fill with distinct (k) keys: the k=2 entry is oldest EXCEPT that we
    # re-touch it halfway, so LRU must evict the untouched k=3 entry instead
    for k in range(3, 3 + P._PLAN_CACHE_MAX - 1):
        E.compile_plan_cached(base, owner, k)
    assert E.plan_cache_stats()["size"] == P._PLAN_CACHE_MAX
    assert E.plan_cache_stats()["evictions"] == 0
    assert E.compile_plan_cached(base, owner, 2) is p1       # touch (hit)
    E.compile_plan_cached(base, owner, 3 + P._PLAN_CACHE_MAX)  # overflow
    st = E.plan_cache_stats()
    assert st["evictions"] == 1 and st["size"] == P._PLAN_CACHE_MAX
    assert E.compile_plan_cached(base, owner, 2) is p1       # survived (MRU)
    hits = E.plan_cache_stats()["hits"]
    E.compile_plan_cached(base, owner, 3)                    # evicted: miss
    st = E.plan_cache_stats()
    assert st["hits"] == hits and st["misses"] >= 2
    assert st["evictions"] == 2                              # re-add evicted
    E.plan_cache_clear(reset_counters=True)
    st = E.plan_cache_stats()
    assert st["size"] == 0 and st["hits"] == st["misses"] == 0


def test_segment_reduce_matches_reference(graphs):
    """Pallas segmented-scan reduce == XLA scatter reference, min and add."""
    from repro.engine import kernels
    import jax
    g = graphs["powerlaw"]
    plan = E.compile_plan(g, baselines.hash_partition(g, 4), 4)
    key = jax.random.key(0)
    msgs = jax.random.uniform(key, plan.emask.shape, jnp.float32, 0.0, 10.0)
    for combine in ("min", "add"):
        got = kernels.segment_reduce(plan, msgs, combine)
        want = kernels.segment_reduce_ref(plan, msgs, combine)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6)


@pytest.fixture(scope="module")
def append_plans():
    """One graph's plan fresh from ``compile_plan`` and patched with
    inserted edges: spread over every partition, and into partition 0
    alone (the append fold then runs for the whole plan while one
    partition holds appended half-edges)."""
    from repro.stream.patch import EdgeChange, patch_plan
    g = graph.watts_strogatz(90, 4, 0.2, seed=2)
    fresh = E.compile_plan(g, baselines.hash_partition(g, 3), 3,
                           edge_slack=16, vertex_slack=8)
    u, v = g.as_numpy()
    have = {(int(a), int(b)) for a, b in zip(u, v)}
    rng = np.random.default_rng(7)
    pairs = []
    while len(pairs) < 6:
        a, b = sorted(int(x) for x in rng.choice(g.n_vertices, 2,
                                                 replace=False))
        if (a, b) not in have and (a, b) not in pairs:
            pairs.append((a, b))
    spread = patch_plan(fresh, [EdgeChange(a, b, -1, i % 3)
                                for i, (a, b) in enumerate(pairs)])
    one = patch_plan(fresh, [EdgeChange(a, b, -1, 0) for a, b in pairs[:2]])
    return {"fresh": (fresh, 0), "patched": (spread, 6),
            "patched_one_partition": (one, 2)}


def _append_case(plan, kernel, combine, width):
    """(program's kernel output, XLA reference) for random messages or
    features; ``width`` None is the scalar stream."""
    import jax
    from repro.engine import kernels
    shape = () if width is None else (width,)
    if kernel == "segment_reduce":
        msgs = jax.random.uniform(jax.random.key(3),
                                  plan.emask.shape + shape, jnp.float32,
                                  0.5, 10.0)
        return (kernels.segment_reduce(plan, msgs, combine),
                kernels.segment_reduce_ref(plan, msgs, combine))
    feats = jax.random.normal(jax.random.key(4),
                              (plan.n_vertices,) + shape, jnp.float32)
    local = kernels.gather_vertex_channel(plan, feats)
    if width is None:
        local = local[:, :, 0]
    return (kernels.gspmm(plan, local, plan.edge_w, combine),
            kernels.gspmm_ref(plan, local, plan.edge_w, combine))


_ADD_TOL = {"segment_reduce": {"rtol": 1e-6},
            "gspmm": {"rtol": 1e-5, "atol": 1e-5}}


@pytest.mark.parametrize("plan_kind", ["fresh", "patched",
                                       "patched_one_partition"])
@pytest.mark.parametrize("width", [None, 4])
@pytest.mark.parametrize("combine", ["min", "add", "max"])
@pytest.mark.parametrize("kernel", ["segment_reduce", "gspmm"])
def test_append_region_fold_matches_reference(append_plans, kernel, combine,
                                              width, plan_kind):
    """The Pallas path folds the append region in only when it holds a live
    half-edge: fresh plans skip it, patched plans take it, and both match
    the XLA scatter reference (min/max exactly, add to rounding).  Where
    only partition 0 holds appended half-edges, the other partitions read
    exactly what the fresh plan gives them."""
    from repro.obs.health import plan_health
    plan, n_appended = append_plans[plan_kind]
    assert plan_health(plan)["append_live_half_edges"] == 2 * n_appended
    got, want = (np.asarray(x) for x in _append_case(plan, kernel, combine,
                                                     width))
    if combine == "add":       # the tolerances of each kernel's older tests
        np.testing.assert_allclose(got, want, **_ADD_TOL[kernel])
    else:
        np.testing.assert_array_equal(got, want)
    if plan_kind == "patched_one_partition":
        base, _ = _append_case(append_plans["fresh"][0], kernel, combine,
                               width)
        np.testing.assert_array_equal(got[1:], np.asarray(base)[1:])


@pytest.mark.parametrize("plan_kind", ["fresh", "patched"])
@pytest.mark.parametrize("weights", ["plan", "per_feature", "batched"])
@pytest.mark.parametrize("combine", ["add", "max", "mean"])
def test_vmapped_gspmm_matches_vmapped_reference(append_plans, combine,
                                                 weights, plan_kind):
    """Under ``vmap`` gspmm folds the batch onto its lane axis beside the
    features, with the plan's scalar weights, per-feature weights or
    weights that vary by lane, and matches the vmapped XLA reference
    (max exactly, add and mean to the tolerance of their older test)."""
    import jax
    from repro.engine import kernels
    plan, _ = append_plans[plan_kind]
    b, f = 3, 4
    feats = jax.random.normal(jax.random.key(5), (b, plan.n_vertices, f))
    local = jax.vmap(lambda x: kernels.gather_vertex_channel(plan, x))(feats)
    shape = {"plan": None, "per_feature": plan.emask.shape + (f,),
             "batched": (b,) + plan.emask.shape}[weights]
    w = plan.edge_w if shape is None else jax.random.uniform(
        jax.random.key(6), shape, jnp.float32, 0.5, 2.0)
    axes = (0, 0 if weights == "batched" else None)
    got, want = (np.asarray(jax.vmap(lambda x, w, fn=fn: fn(
        plan, x, w, combine), in_axes=axes)(local, w))
        for fn in (kernels.gspmm, kernels.gspmm_ref))
    assert got.shape == (b,) + plan.vmask.shape + (f,)
    if combine == "max":
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, **_ADD_TOL["gspmm"])


def test_segment_reduce_over_batched_plans_runs_the_xla_twin(append_plans):
    """A batch whose plan varies by lane cannot share one scan: the
    batching rule falls back to the vmapped XLA reference."""
    import jax
    from repro.engine import kernels
    plans = [append_plans[kind][0] for kind in ("fresh", "patched")]
    stacked = jax.tree_util.tree_map(lambda *x: jnp.stack(x), *plans)
    msgs = jax.random.uniform(jax.random.key(7), (2,) + plans[0].emask.shape,
                              jnp.float32, 0.5, 10.0)
    got = jax.vmap(lambda p, m: kernels.segment_reduce(p, m, "min"))(
        stacked, msgs)
    for i, plan in enumerate(plans):
        np.testing.assert_array_equal(
            np.asarray(got[i]),
            np.asarray(kernels.segment_reduce_ref(plan, msgs[i], "min")))


_BATCHABLE = {"sssp": E.SSSP, "bfs": E.BFS, "wsssp": E.WEIGHTED_SSSP}


@pytest.mark.parametrize("plan_kind", ["fresh", "patched"])
@pytest.mark.parametrize("bucket", [1, 8, 32])
@pytest.mark.parametrize("start", ["cold", "warm"])
@pytest.mark.parametrize("program", list(_BATCHABLE))
def test_batched_kernels_match_xla_twin_and_single_dispatch(
        append_plans, program, start, bucket, plan_kind):
    """``dispatch_batched`` on the kernels (the batch folded onto the
    segmented scan's lanes) equals the vmapped XLA twin and one
    ``dispatch`` per source, exactly, cold or warm; on the patched plan
    the append-region branch runs under the batching rule."""
    plan, _ = append_plans[plan_kind]
    prog = _BATCHABLE[program]
    sources = np.random.default_rng(bucket).choice(
        plan.n_vertices, bucket, replace=False).astype(np.int32)
    kernels, twin = E.Engine(plan), E.Engine(plan, use_pallas=False)
    warm = None
    if start == "warm":     # exact rows for even lanes, cold rows for odd
        warm = np.array(twin.run_batched(prog, {"source": sources}).state)
        warm[1::2] = prog.state.fill
    got = kernels.run_batched(prog, {"source": sources}, warm_state=warm)
    want = twin.run_batched(prog, {"source": sources}, warm_state=warm)
    np.testing.assert_array_equal(np.asarray(got.state),
                                  np.asarray(want.state))
    np.testing.assert_array_equal(np.asarray(got.supersteps),
                                  np.asarray(want.supersteps))
    for i, src in enumerate(sources):
        one = kernels.run(prog, source=jnp.int32(src),
                          warm_state=None if warm is None else warm[i])
        np.testing.assert_array_equal(np.asarray(one.state),
                                      np.asarray(got.state[i]))


def test_superstep_cap_reports_nonconvergence():
    """Hitting max_supersteps is surfaced instead of silently truncating."""
    n = 60  # path graph with alternating edge ownership: slow cut crossings
    edges = np.stack([np.arange(n - 1), np.arange(1, n)], 1)
    g = graph.from_edge_array(n, edges)
    owner = jnp.where(g.edge_mask, g.src % 2, -2)
    eng = E.Engine(E.compile_plan(g, owner, 2))
    trunc = eng.run(E.SSSP, max_supersteps=3, source=jnp.int32(0))
    assert not bool(trunc.converged)
    assert not trunc.row()["converged"]
    full = E.engine_sssp(eng, 0)
    assert bool(full.converged)
    ref, _ = alg.reference_sssp(g, 0)
    assert np.array_equal(np.asarray(full.state), np.asarray(ref))


def test_zero_supersteps_is_zero():
    """An explicit 0 is not treated as 'use the default'."""
    g = graph.watts_strogatz(64, 4, 0.1, seed=0)
    eng = E.Engine(E.compile_plan(g, baselines.hash_partition(g, 2), 2))
    r = E.engine_pagerank(eng, g.degrees(), iters=0)
    assert int(r.supersteps) == 0
    np.testing.assert_allclose(np.asarray(r.state),
                               np.full(g.n_vertices, 1.0 / g.n_vertices))


@given(seed=st.integers(0, 10_000))
@settings(max_examples=8, deadline=None)
def test_deleted_slots_are_inert(seed):
    """Padding-identity property: masking half-edge slots out of a plan (the
    streaming deletion path) must make them inert in segment_reduce — for
    both the Pallas segmented-scan path and the scatter reference, for both
    min and add — i.e. equal to a from-scratch plan without those edges.
    masked_update must likewise pin non-vmask slots to the identity."""
    import dataclasses
    import jax
    from repro.engine import kernels

    rng = np.random.default_rng(seed)
    g = graph.watts_strogatz(90, 4, 0.2, seed=seed % 7)
    owner = baselines.hash_partition(g, 3)
    plan = E.compile_plan(g, owner, 3)

    # delete a random subset of undirected edges: clear both half-edge slots
    em = np.asarray(plan.emask).copy()
    l2g = np.asarray(plan.local2global)
    tgt = np.asarray(plan.edge_tgt)
    nbr = np.asarray(plan.edge_nbr)
    u, v = g.as_numpy()
    own = np.asarray(owner)[np.asarray(g.edge_mask)]
    kill = rng.random(g.n_edges) < 0.3
    for a, b, p in zip(u[kill], v[kill], own[kill]):
        ga, gb = l2g[p, tgt[p]], l2g[p, nbr[p]]
        hit = em[p] & (((ga == a) & (gb == b)) | ((ga == b) & (gb == a)))
        assert hit.sum() == 2
        em[p, hit] = False
    deleted = dataclasses.replace(plan, emask=jnp.asarray(em))

    # reference: compile the surviving edge set from scratch
    keep = ~kill
    g2 = graph.from_edge_array(g.n_vertices,
                               np.stack([u[keep], v[keep]], 1))
    own2 = np.full(g2.e_pad, -2, np.int32)
    k2u, k2v = g2.as_numpy()
    lut = {(int(a), int(b)): int(p) for a, b, p in zip(u, v, own)}
    own2[:g2.n_edges] = [lut[(int(a), int(b))] for a, b in zip(k2u, k2v)]
    fresh = E.compile_plan(g2, own2, 3)

    key = jax.random.key(seed)
    msgs = jax.random.uniform(key, em.shape, jnp.float32, 0.5, 10.0)
    for combine in ("min", "add"):
        got_scan = np.asarray(kernels.segment_reduce(deleted, msgs, combine))
        got_ref = np.asarray(kernels.segment_reduce_ref(deleted, msgs,
                                                        combine))
        np.testing.assert_allclose(got_scan, got_ref, rtol=1e-6)
        # per-vertex aggregates equal the fresh plan's (local layouts differ;
        # compare in global-id space over surviving vertices)
        fr_msgs = jnp.zeros(np.asarray(fresh.emask).shape, jnp.float32)
        f_l2g = np.asarray(fresh.local2global)
        f_tgt = np.asarray(fresh.edge_tgt)
        f_nbr = np.asarray(fresh.edge_nbr)
        f_em = np.asarray(fresh.emask)
        # messages are a function of the (target, neighbour) global pair in
        # the original stream; replay them onto the fresh layout
        mlut = {}
        for p in range(3):
            for s in np.flatnonzero(em[p]):
                mlut[(p, int(l2g[p, tgt[p, s]]), int(l2g[p, nbr[p, s]]))] = \
                    float(np.asarray(msgs)[p, s])
        fr = np.zeros(f_em.shape, np.float32)
        for p in range(3):
            for s in np.flatnonzero(f_em[p]):
                fr[p, s] = mlut[(p, int(f_l2g[p, f_tgt[p, s]]),
                                 int(f_l2g[p, f_nbr[p, s]]))]
        want = np.asarray(kernels.segment_reduce_ref(fresh, jnp.asarray(fr),
                                                     combine))
        ident = kernels._IDENTITY[combine]
        agg_got = np.full(g.n_vertices, ident, np.float32)
        agg_want = np.full(g.n_vertices, ident, np.float32)
        vm_d = np.asarray(deleted.vmask)
        vm_f = np.asarray(fresh.vmask)
        scatter = np.minimum.at if combine == "min" else np.add.at
        for p in range(3):
            scatter(agg_got, l2g[p, vm_d[p]], got_scan[p, vm_d[p]])
            scatter(agg_want, f_l2g[p, vm_f[p]], want[p, vm_f[p]])
        np.testing.assert_allclose(agg_got, agg_want, rtol=1e-5)

    # masked_update: non-vmask slots pinned to identity, others combined
    for combine in ("min", "add"):
        state = jax.random.uniform(key, vm_d.shape, jnp.float32, 0.0, 5.0)
        inc = jax.random.uniform(jax.random.key(seed + 1), vm_d.shape,
                                 jnp.float32, 0.0, 5.0)
        outp = np.asarray(kernels.masked_update(
            state, inc, deleted.vmask, deleted.replicated, combine))
        ident = kernels._IDENTITY[combine]
        assert np.all(outp[~vm_d] == ident)


def test_isolated_vertices_finalized():
    """Vertices outside every partition (degree 0) get correct defaults."""
    edges = np.array([[0, 1], [1, 2], [3, 4]])  # vertex 5 isolated
    g = graph.from_edge_array(6, edges)
    plan = E.compile_plan(g, baselines.hash_partition(g, 2), 2)
    eng = E.Engine(plan)
    d = np.asarray(E.engine_sssp(eng, 0).state)
    assert d[5] == np.inf and d[0] == 0.0
    d5 = np.asarray(E.engine_sssp(eng, 5).state)
    assert d5[5] == 0.0 and np.isinf(d5[0])
    labels = np.asarray(E.engine_wcc(eng).state)
    assert labels[5] == 5.0
    pr = np.asarray(E.engine_pagerank(eng, g.degrees(), iters=10).state)
    ref = np.asarray(alg.reference_pagerank(g, iters=10))
    np.testing.assert_allclose(pr, ref, atol=1e-6)
