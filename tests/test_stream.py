"""repro.stream correctness: chunked ingest round-trips the edge set,
plan patches keep the engine bit-identical to the whole-graph oracles on
the mutated graph WITHOUT retracing jitted supersteps, and incremental
maintenance (online HDRF + bounded local re-auction) keeps the replication
factor within 10% of a full DFEP re-run."""
import numpy as np
import pytest

from repro.core import algorithms as alg
from repro.core import dfep, graph, metrics
from repro import engine as E
from repro import stream as S
from repro.engine import runtime
from repro.stream.patch import EdgeChange, SlackExhausted, patch_plan


def _mutation(g, frac_del=0.07, frac_ins=0.08, seed=0):
    """>= 10% of |E| worth of deletions + insertions."""
    rng = np.random.default_rng(seed)
    u, v = g.as_numpy()
    n_del = int(frac_del * g.n_edges)
    idx = rng.choice(g.n_edges, size=n_del, replace=False)
    dels = np.stack([u[idx], v[idx]], 1)
    ins = rng.integers(0, g.n_vertices, size=(int(frac_ins * g.n_edges), 2))
    return ins, dels


def _check_oracles(sess):
    g = sess.graph()
    r = E.engine_sssp(sess.engine, 0)
    ref, _ = alg.reference_sssp(g, 0)
    assert np.array_equal(np.asarray(r.state), np.asarray(ref)), "sssp"
    rw = E.engine_wcc(sess.engine)
    refc, _ = alg.reference_cc(g)
    assert np.array_equal(np.asarray(rw.state), np.asarray(refc)), "wcc"
    rp = E.engine_pagerank(sess.engine, g.degrees(), iters=15)
    refp = alg.reference_pagerank(g, iters=15)
    np.testing.assert_allclose(np.asarray(rp.state), np.asarray(refp),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# ingest
# ---------------------------------------------------------------------------

def test_streaming_graph_roundtrip():
    g = graph.watts_strogatz(120, 4, 0.2, seed=3)
    sg = S.StreamingGraph(g, chunk_size=16)
    u, v = g.as_numpy()
    # delete a few, insert a few (dupes + self-loops ignored), compare with
    # a from-scratch build of the same edge set
    sg.delete_chunk(np.stack([u[:10], v[:10]], 1))
    new = np.array([[1, 99], [99, 1], [5, 5], [2, 117], [1, 99]])
    res = sg.insert_chunk(new)
    assert len(res.slots) == 2          # dedup + self-loop drop
    want = {(int(a), int(b)) for a, b in zip(u[10:], v[10:])}
    want |= {(1, 99), (2, 117)}
    want -= {(int(a), int(b)) for a, b in zip(u[:10], v[:10])}
    gu, gv = sg.graph().as_numpy()
    assert {(int(a), int(b)) for a, b in zip(gu, gv)} == want
    # fingerprint matches an independent build of the same edge set
    ref = graph.from_edge_array(g.n_vertices, np.array(sorted(want)))
    assert sg.graph().fingerprint() == ref.fingerprint()


def test_compaction_preserves_edges_and_bumps_epoch():
    g = graph.watts_strogatz(60, 4, 0.1, seed=1)
    sg = S.StreamingGraph(g, chunk_size=8)
    fp = sg.graph().fingerprint()
    keep = sg.compact()
    assert sg.epoch == 1
    assert len(keep) == g.n_edges
    assert sg.graph().fingerprint() == fp
    assert sg.free_slots() >= sg.chunk_size


def test_graph_fingerprint_invariants():
    a = graph.from_edge_array(50, np.array([[1, 2], [2, 3], [4, 5]]))
    b = graph.from_edge_array(50, np.array([[4, 5], [2, 1], [3, 2]]),
                              pad_to=256)
    assert a.fingerprint() == b.fingerprint()     # order/padding invariant
    c = graph.from_edge_array(50, np.array([[1, 2], [2, 3], [4, 6]]))
    assert a.fingerprint() != c.fingerprint()
    # the plan cache keys on content, not identity
    oa = np.where(np.asarray(a.edge_mask), 0, -2)
    ob = np.where(np.asarray(b.edge_mask), 0, -2)
    assert E.compile_plan_cached(a, oa, 2) is E.compile_plan_cached(b, ob, 2)


# ---------------------------------------------------------------------------
# plan patching
# ---------------------------------------------------------------------------

def test_patch_matches_fresh_compile_metrics():
    """Patched replica masks/counters == a from-scratch compile of the same
    (graph, owner) state."""
    g = graph.watts_strogatz(150, 4, 0.1, seed=1)
    sess = S.StreamSession(g, S.StreamConfig(k=4, chunk_size=32,
                                             drift_threshold=1e9), key=0)
    ins, dels = _mutation(g, seed=1)
    sess.apply(inserts=ins, deletes=dels)
    assert sess.n_patches >= 1 and sess.n_recompiles == 0

    g2 = sess.graph()
    m = metrics.evaluate(g2, sess.owner, 4, compute_gain=False)
    assert sess.plan.exchange_per_superstep() == m.messages
    assert sess.plan.replication_factor() == m.replication_factor
    fresh = E.compile_plan(g2, sess.owner, 4)
    assert fresh.exchange_volume == sess.plan.exchange_volume
    assert fresh.sum_local_vertices == sess.plan.sum_local_vertices
    np.testing.assert_array_equal(np.asarray(fresh.n_edges_local),
                                  np.asarray(sess.plan.n_edges_local))
    # patched plan holds exactly the mutated edge set
    want = np.unique(np.stack(g2.as_numpy(), 1), axis=0)
    got = np.unique(np.concatenate(sess.plan.local_edges(), 0), axis=0)
    assert np.array_equal(want, got)
    # property-channel index plane: the patched edge_slot mapping per
    # (partition, global endpoints) half-edge equals a fresh compile's —
    # external [E_pad, F] planes read identically through either plan
    def slot_map(plan):
        l2g = np.asarray(plan.local2global)
        tgt = np.asarray(plan.edge_tgt)
        nbr = np.asarray(plan.edge_nbr)
        em = np.asarray(plan.emask)
        es = np.asarray(plan.edge_slot)
        return {(p, int(l2g[p, tgt[p, s]]), int(l2g[p, nbr[p, s]])):
                int(es[p, s])
                for p in range(plan.k) for s in np.flatnonzero(em[p])}
    assert slot_map(sess.plan) == slot_map(fresh)
    assert sess.plan.edge_slot_hwm == fresh.edge_slot_hwm


def test_patch_exhaustion_raises_and_leaves_plan_usable():
    g = graph.watts_strogatz(100, 4, 0.1, seed=1)
    sess = S.StreamSession(g, S.StreamConfig(k=3, chunk_size=32, edge_slack=0,
                                             vertex_slack=0,
                                             drift_threshold=1e9), key=0)
    plan = sess.plan
    # every insert below targets partition 0, so size the overflow from
    # partition 0's own free CSR slots (at least one insert, so a full
    # partition still overflows)
    free = int(plan.e_max - 1 - int(np.asarray(plan.csr_fill)[0]))
    # distinct in-range vertex pairs; 2 slots per edge overruns `free` slots
    import itertools
    pairs = itertools.combinations(range(g.n_vertices), 2)
    too_many = [EdgeChange(a, b, -1, 0)
                for a, b in itertools.islice(pairs, max(free, 1))]
    with pytest.raises(SlackExhausted):
        patch_plan(plan, too_many)
    # the input plan is untouched and still answers queries
    r = E.engine_sssp(E.Engine(plan), 0)
    ref, _ = alg.reference_sssp(sess.graph(), 0)
    assert np.array_equal(np.asarray(r.state), np.asarray(ref))


# ---------------------------------------------------------------------------
# acceptance: streamed batch >= 10% |E|
# ---------------------------------------------------------------------------

def test_streamed_batch_no_retrace_and_oracle_identical():
    """Plan patches must NOT invalidate the engine's jit cache: the
    superstep loop traces for the warm-up queries and never again."""
    g = graph.watts_strogatz(300, 6, 0.1, seed=2)
    sess = S.StreamSession(g, S.StreamConfig(k=4, chunk_size=64,
                                             drift_threshold=1e9), key=0)
    _check_oracles(sess)                       # warm every program's cache
    traced = runtime.TRACE_COUNTER["run_loop"]

    ins, dels = _mutation(g, seed=0)
    assert len(ins) + len(dels) >= 0.10 * g.n_edges
    stats = sess.apply(inserts=ins, deletes=dels)
    assert stats["recompiles"] == 0 and stats["patches"] >= 1

    _check_oracles(sess)                       # bit-identical on mutated graph
    assert runtime.TRACE_COUNTER["run_loop"] == traced, \
        "plan patch caused a jit retrace"


def test_incremental_rf_within_10pct_of_full_rerun():
    g = graph.watts_strogatz(300, 6, 0.1, seed=2)
    sess = S.StreamSession(g, S.StreamConfig(k=4, chunk_size=64,
                                             drift_threshold=0.02), key=0)
    ins, dels = _mutation(g, seed=0)
    sess.apply(inserts=ins, deletes=dels)
    _check_oracles(sess)                       # still exact after re-auction

    g2 = sess.graph()
    owner_full, _ = dfep.partition(g2, k=4, key=1)
    rf_full = E.compile_plan(g2, np.asarray(owner_full), 4).replication_factor()
    rf_inc = sess.replication_factor()
    assert rf_inc <= 1.10 * rf_full, (rf_inc, rf_full)


def test_reauction_only_moves_region_edges():
    g = graph.watts_strogatz(200, 4, 0.1, seed=5)
    owner, _ = dfep.partition(g, k=4, key=0)
    owner = np.asarray(owner)
    touched = np.zeros(g.n_vertices, bool)
    touched[:20] = True
    new_owner, info = S.local_reauction(g, owner, touched, 4, hops=1)
    u, v = np.asarray(g.src), np.asarray(g.dst)
    region = S.h_hop_vertices(u, v, np.asarray(g.edge_mask), g.n_vertices,
                              touched, 1)
    changed = (new_owner != owner) & np.asarray(g.edge_mask)
    assert not np.any(changed & ~(region[u] & region[v])), \
        "re-auction moved an edge outside the h-hop region"
    assert info["active_edges"] >= int(changed.sum())
    # every real edge still owned by a valid partition
    m = np.asarray(g.edge_mask)
    assert new_owner[m].min() >= 0 and new_owner[m].max() < 4


def test_compaction_epoch_recompiles_and_stays_correct():
    g = graph.watts_strogatz(100, 4, 0.1, seed=1)   # pad 256: 57 spare slots
    sess = S.StreamSession(g, S.StreamConfig(k=3, chunk_size=32,
                                             drift_threshold=1e9), key=0)
    rng = np.random.default_rng(1)
    stats = sess.apply(inserts=rng.integers(0, 100, size=(400, 2)))
    assert stats["epoch"] >= 1 and stats["recompiles"] >= 1
    assert sess.plan.epoch == sess.epoch
    _check_oracles(sess)


def test_batched_serving_on_patched_plan():
    g = graph.watts_strogatz(120, 4, 0.2, seed=3)
    sess = S.StreamSession(g, S.StreamConfig(k=4, chunk_size=32,
                                             drift_threshold=1e9), key=0)
    ins, dels = _mutation(g, seed=2)
    sess.apply(inserts=ins, deletes=dels)
    sources = [0, 7, 33, 64]
    res = E.multi_source_sssp(sess.engine, sources)
    for i, s in enumerate(sources):
        ref, _ = alg.reference_sssp(sess.graph(), s)
        assert np.array_equal(np.asarray(res.state[i]), np.asarray(ref)), s


def test_vertex_departure_and_return():
    """Deleting a vertex's last edge clears its slot; re-inserting later
    re-registers it (slot reuse) — engine results stay exact throughout."""
    g = graph.watts_strogatz(80, 4, 0.1, seed=4)
    sess = S.StreamSession(g, S.StreamConfig(k=3, chunk_size=32,
                                             drift_threshold=1e9), key=0)
    u, v = g.as_numpy()
    inc = (u == 0) | (v == 0)
    sess.apply(deletes=np.stack([u[inc], v[inc]], 1))
    d = np.asarray(E.engine_sssp(sess.engine, 0).state)
    ref, _ = alg.reference_sssp(sess.graph(), 0)
    assert np.array_equal(d, np.asarray(ref)) and d[0] == 0.0
    _check_oracles(sess)
    sess.apply(inserts=np.array([[0, 40], [0, 41]]))
    _check_oracles(sess)
    d2 = np.asarray(E.engine_sssp(sess.engine, 0).state)
    assert d2[40] == 1.0 and d2[41] == 1.0


# ---------------------------------------------------------------------------
# compaction policies
# ---------------------------------------------------------------------------

def _bursty_forced_recompiles(policy) -> int:
    """Forced recompiles of one policy over a seeded bursty workload:
    bursts of 8% of |E| random inserts, each followed by an idle gap, on
    the 3% email-enron stand-in with k = 8.  Two warmup bursts give the
    policy its telemetry; the count covers the eight bursts after them."""
    g = graph.load_dataset("email-enron", scale=0.03, seed=0)
    rng = np.random.default_rng(11)
    # no re-auctions: the counts isolate compaction scheduling
    sess = S.StreamSession(g, S.StreamConfig(k=8, chunk_size=64,
                                             drift_threshold=10.0),
                           key=0, policy=policy)
    burst = max(64, int(0.08 * g.n_edges))

    def burst_edges():
        e = rng.integers(0, g.n_vertices, size=(burst, 2))
        return e[e[:, 0] != e[:, 1]]

    for _ in range(2):
        sess.apply(inserts=burst_edges())
        sess.idle_tick()
    forced0 = sess.n_forced_recompiles
    for _ in range(8):
        sess.apply(inserts=burst_edges())
        sess.idle_tick()
    return sess.n_forced_recompiles - forced0


def test_adaptive_policy_absorbs_every_burst_reactive_is_forced():
    """On the same workload the adaptive policy compacts in the idle gaps
    and is never forced mid-burst, while the reactive default is forced
    at least once (so the workload does exercise the policies)."""
    adaptive = S.AdaptiveCompactionPolicy()
    try:
        assert _bursty_forced_recompiles(adaptive) == 0
    finally:
        adaptive.close()
    assert _bursty_forced_recompiles(S.ReactiveCompactionPolicy()) >= 1
