"""repro.gserve correctness: micro-batch scheduling (pad-to-bucket, FIFO
coalescing), registry-derived request validation, result-cache sharing
across tenants with exact content-keyed invalidation, fair-share admission
control, timer-based partial-bucket flush, warm-started repair across
insert-only stream patches, warm jit caches across bursts, and the
serving-under-mutation contract — every result bit-identical to the
whole-graph oracle for the snapshot (version) it was served from, with no
stale cache entry surviving a plan swap."""
import time

import numpy as np
import pytest

from repro.core import algorithms as alg
from repro.core import dfep, graph
from repro import engine as E
from repro import gserve as G
from repro import stream as S
from repro.engine import runtime
from repro.engine.registry import DEFAULT_REGISTRY


def _static_server(n=150, k=4, seed=3, **kw):
    g = graph.watts_strogatz(n, 4, 0.2, seed=seed)
    owner, _ = dfep.partition(g, k=k, key=0)
    plan = E.compile_plan(g, np.asarray(owner), k)
    return g, G.GraphServer(E.Engine(plan), g, **kw)


def _check(result, g):
    """Generic oracle check — derived from the registry entry, so it covers
    every registered program without naming one."""
    entry = result.request.entry
    ref = entry.oracle(g, **result.request.params)
    if entry.oracle_atol:
        np.testing.assert_allclose(result.value, np.asarray(ref),
                                   atol=entry.oracle_atol)
    else:
        assert np.array_equal(result.value, np.asarray(ref)), result.request


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

def test_bucket_for():
    assert G.bucket_for(1, (1, 2, 4)) == 1
    assert G.bucket_for(3, (1, 2, 4)) == 4
    assert G.bucket_for(9, (1, 2, 4)) == 4      # clamped to largest


def test_microbatcher_coalescing_and_fifo():
    b = G.MicroBatcher(buckets=(1, 2, 4))
    reqs = [G.QueryRequest("sssp", tenant="a", params={"source": 1}),
            G.QueryRequest("wcc", tenant="b"),
            G.QueryRequest("sssp", tenant="b", params={"source": 2}),
            G.QueryRequest("sssp", tenant="c", params={"source": 1}),  # dup
            G.QueryRequest("wcc", tenant="c"),
            G.QueryRequest("pagerank", tenant="a", params={"iters": 5})]
    for r in reqs:
        b.add(r)
    assert len(b) == 6
    m1 = b.next_batch()                 # sssp queue arrived first
    assert m1.key == ("sssp",) and len(m1.requests) == 3
    assert m1.params == (1, 2)          # dedup within the batch
    assert m1.lane == (0, 1, 0)
    assert m1.bucket == 2
    assert m1.padded_params == (1, 2)
    m2 = b.next_batch()                 # both wcc requests share one run
    assert m2.key == ("wcc",) and len(m2.requests) == 2 and m2.params is None
    m3 = b.next_batch()
    assert m3.key == ("pagerank", ("iters", 5))
    assert b.next_batch() is None and len(b) == 0


def test_padded_params_repeat_last():
    b = G.MicroBatcher(buckets=(4,))
    for s in (5, 9, 13):
        b.add(G.QueryRequest("sssp", params={"source": s}))
    m = b.next_batch()
    assert m.bucket == 4 and m.padded_params == (5, 9, 13, 13)


def test_request_validation():
    with pytest.raises(ValueError):
        G.QueryRequest("sssp")                   # missing source
    with pytest.raises(ValueError):
        G.QueryRequest("betweenness")            # unknown kind


def test_param_normalization_pagerank_iters_default():
    """Regression for the iters=None vs default identity bug: omitting a
    defaulted param and passing its default spell the SAME query, so they
    coalesce into one dispatch and share one cache entry."""
    a = G.QueryRequest("pagerank")
    b = G.QueryRequest("pagerank", params={"iters": 30})
    assert a.params == b.params == {"iters": 30}
    assert a.batch_key() == b.batch_key()
    assert a.cache_key() == b.cache_key()
    c = G.QueryRequest("pagerank", params={"iters": 10})
    assert c.batch_key() != a.batch_key()
    # and end-to-end: the default-spelled request hits the explicit one's
    # cache entry (one engine run total)
    _, srv = _static_server()
    r1 = srv.serve([G.QueryRequest("pagerank")])[0]
    r2 = srv.serve([G.QueryRequest("pagerank", params={"iters": 30})])[0]
    assert not r1.from_cache and r2.from_cache


# ---------------------------------------------------------------------------
# static serving
# ---------------------------------------------------------------------------

def test_serve_matches_oracles_mixed_tenants():
    g, srv = _static_server(buckets=(1, 2, 4, 8))
    reqs = [G.QueryRequest("sssp", tenant=f"t{i % 3}",
                           params={"source": (i * 7) % 150})
            for i in range(10)]
    reqs += [G.QueryRequest("wcc", tenant="t3"),
             G.QueryRequest("wcc", tenant="t4"),
             G.QueryRequest("pagerank", tenant="t5", params={"iters": 10})]
    out = srv.serve(reqs)
    assert [r.request.id for r in out] == [r.id for r in reqs]
    for r in out:
        _check(r, g)
    st = srv.stats()
    # 13 requests but far fewer dispatches: sssp coalesced, wcc shared
    assert st["completed"] == 13 and st["batches"] <= 4
    assert st["mean_batch_occupancy"] > 1.0


def test_serve_new_programs_registered_via_registry():
    """Weighted SSSP and BFS were registered through the public registry
    API only — the serving stack derives their dispatch entirely from the
    entry (zero gserve edits), and results are bit-identical to the
    core/algorithms.py oracles."""
    g, srv = _static_server(buckets=(1, 2, 4))
    reqs = [G.QueryRequest("wsssp", tenant="a", params={"source": 3}),
            G.QueryRequest("wsssp", tenant="b", params={"source": 11}),
            G.QueryRequest("bfs", tenant="a", params={"source": 3}),
            G.QueryRequest("bfs", tenant="c", params={"source": 40})]
    out = srv.serve(reqs)
    for r in out:
        _check(r, g)
    # cross-tenant cache sharing works for registered programs too
    r2 = srv.serve([G.QueryRequest("wsssp", tenant="z",
                                   params={"source": 3})])[0]
    assert r2.from_cache


@pytest.fixture(scope="module")
def catalogue_plan():
    g = graph.watts_strogatz(150, 4, 0.2, seed=3)
    owner, _ = dfep.partition(g, k=4, key=0)
    return g, E.compile_plan(g, np.asarray(owner), 4)


def _catalogue_params(entry, g, rng, source):
    """One request's parameters, built from the entry's schema alone: the
    batchable parameter takes ``source``, channel planes are random, and
    every other parameter keeps its default.  A dense plane's rows are the
    width of the program's vertex plane (its ``[F_in, F_out]`` weight)."""
    params = {}
    for spec in entry.params:
        if spec.batchable:
            params[spec.name] = source
        elif spec.role == "channel":
            rows = {"vertex": g.n_vertices, "edge": g.e_pad}.get(spec.channel)
            if rows is None:
                rows = next(s.features for s in entry.channel_params
                            if s.channel == "vertex")
            params[spec.name] = rng.random((rows, spec.features)) \
                .astype(np.float32)
    return params


@pytest.mark.parametrize("program", sorted(
    e.name for e in DEFAULT_REGISTRY.entries() if e.oracle is not None))
def test_every_registered_program_serves_its_oracle(catalogue_plan, program):
    """Each registered program with an oracle, served through a
    GraphServer from four tenants, equals its oracle at the entry's
    tolerance: batchable programs on four sources in one micro-batch,
    the rest on one shared set of parameters."""
    g, plan = catalogue_plan
    entry = DEFAULT_REGISTRY.get(program)
    rng = np.random.default_rng(1)
    shared = _catalogue_params(entry, g, rng, 0)
    reqs = [G.QueryRequest(program, tenant=f"t{i}",
                           params=_catalogue_params(entry, g, rng, s)
                           if entry.batchable else shared)
            for i, s in enumerate((0, 17, 60, 149))]
    srv = G.GraphServer(E.Engine(plan), g, buckets=(1, 2, 4),
                        cache_entries=0)
    try:
        out = srv.serve(reqs)
    finally:
        srv.close()
    assert [r.request.id for r in out] == [r.id for r in reqs]
    for r in out:
        _check(r, g)


def test_result_cache_shared_across_tenants():
    g, srv = _static_server()
    a = srv.serve([G.QueryRequest("sssp", tenant="a",
                                  params={"source": 11})])[0]
    assert not a.from_cache
    b = srv.serve([G.QueryRequest("sssp", tenant="b",
                                  params={"source": 11})])[0]
    assert b.from_cache and np.array_equal(a.value, b.value)
    w1 = srv.serve([G.QueryRequest("wcc", tenant="a")])[0]
    w2 = srv.serve([G.QueryRequest("wcc", tenant="b")])[0]
    assert not w1.from_cache and w2.from_cache
    assert srv.stats()["result_cache"]["hits"] >= 2
    # served values are shared across tenants and with the cache: mutation
    # must fail loudly instead of corrupting other tenants' answers
    for res in (a, b, w1, w2):
        with pytest.raises(ValueError):
            res.value[0] = -1.0


def test_admission_control():
    _, srv = _static_server(max_pending=2)
    srv.submit(G.QueryRequest("sssp", params={"source": 1}))
    srv.submit(G.QueryRequest("sssp", params={"source": 2}))
    with pytest.raises(G.AdmissionError):
        srv.submit(G.QueryRequest("sssp", params={"source": 3}))
    assert srv.stats()["rejected"] == 1
    out = srv.drain()                   # queue drains; door reopens
    assert len(out) == 2
    srv.submit(G.QueryRequest("sssp", params={"source": 3}))
    assert len(srv.drain()) == 1


def test_fair_share_admission_no_starvation():
    """Per-tenant fair share: one tenant saturating the queue cannot lock
    a quiet tenant out. The hog is capped at max_pending//active_tenants
    once contention exists, while the newcomer's first request is admitted
    even at a full queue — and gets served."""
    g, srv = _static_server(max_pending=8)
    admitted = 0
    with pytest.raises(G.AdmissionError):
        for i in range(20):
            srv.submit(G.QueryRequest("sssp", tenant="hog",
                                      params={"source": i}))
            admitted += 1
    assert admitted == 8                   # solo tenant may fill the queue
    # the quiet tenant still gets in at a full queue ...
    qid = srv.submit(G.QueryRequest("sssp", tenant="quiet",
                                    params={"source": 99}))
    # ... and with 2 active tenants the hog is now over its share (8 >= 4)
    with pytest.raises(G.AdmissionError, match="fair share"):
        srv.submit(G.QueryRequest("sssp", tenant="hog",
                                  params={"source": 50}))
    assert srv.stats()["rejected_fair_share"] >= 1
    out = srv.drain()
    served = {r.request.id: r for r in out}
    assert qid in served                   # the quiet tenant was served
    _check(served[qid], g)
    # queue drained: the hog's door reopens
    srv.submit(G.QueryRequest("sssp", tenant="hog", params={"source": 50}))
    assert len(srv.drain()) == 1
    # the first-request exemption is bounded: a flood of fresh tenant ids
    # hits the 2*max_pending hard wall instead of growing without bound
    n_in = 0
    with pytest.raises(G.AdmissionError, match="hard limit"):
        for i in range(1000):
            srv.submit(G.QueryRequest("sssp", tenant=f"fresh{i}",
                                      params={"source": i % 150}))
            n_in += 1
    assert n_in == 2 * 8
    srv.drain()


def test_timer_flush_bounds_partial_bucket_wait():
    """drain(max_wait_s): a partial bucket waits for the deadline (giving
    concurrent submitters time to fill it), then flushes anyway — while a
    full bucket dispatches immediately, without waiting."""
    g, srv = _static_server(buckets=(4,), max_wait_s=0.15)
    # warm the (bucket=4) jit shape outside the timing
    srv.serve([G.QueryRequest("sssp", params={"source": s})
               for s in (90, 91, 92, 93)])
    for s in (1, 2, 3):
        srv.submit(G.QueryRequest("sssp", params={"source": s}))
    t0 = time.time()
    out = srv.drain()
    waited = time.time() - t0
    assert len(out) == 3 and all(r.bucket == 4 for r in out)
    assert waited >= 0.12, "partial bucket must wait toward the deadline"
    for r in out:
        _check(r, g)
    # a full bucket never waits: with a deadline far beyond the service
    # time, drain returns as soon as the batch completes
    srv.max_wait_s = 30.0
    for s in (20, 21, 22, 23):
        srv.submit(G.QueryRequest("sssp", params={"source": s}))
    t0 = time.time()
    out = srv.drain()
    assert len(out) == 4 and time.time() - t0 < 5.0


def test_pad_to_bucket_keeps_jit_cache_warm():
    """Bursts of any size <= bucket reuse one compiled batched loop: after
    the first burst warms the (bucket=4) shape, later bursts of 2, 3 and 4
    distinct sources must not retrace."""
    g, srv = _static_server(buckets=(4,))
    srv.serve([G.QueryRequest("sssp", params={"source": s})
               for s in (1, 2, 3)])
    traced = runtime.TRACE_COUNTER["run_loop"]
    srv.serve([G.QueryRequest("sssp", params={"source": s})
               for s in (20, 21)])
    srv.serve([G.QueryRequest("sssp", params={"source": s})
               for s in (30, 31, 32, 33)])
    out = srv.serve([G.QueryRequest("sssp", params={"source": s})
                     for s in (40, 41, 42)])
    assert runtime.TRACE_COUNTER["run_loop"] == traced, \
        "padded micro-batches must hit the warm jit cache"
    for r in out:
        _check(r, g)
        assert r.bucket == 4


def test_nonblocking_dispatch_overlap():
    """dispatch_batched returns before results are materialised and several
    in-flight batches can settle out of order."""
    g, srv = _static_server()
    eng = srv.front.engine
    p1 = eng.dispatch_batched(E.SSSP, {"source": np.array([0, 5], np.int32)})
    p2 = eng.dispatch_batched(E.SSSP, {"source": np.array([9, 2], np.int32)})
    r2 = p2.result()
    r1 = p1.result()
    for res, sources in ((r1, (0, 5)), (r2, (9, 2))):
        for i, s in enumerate(sources):
            ref, _ = alg.reference_sssp(g, s)
            assert np.array_equal(np.asarray(res.state[i]), np.asarray(ref))


# ---------------------------------------------------------------------------
# serving under mutation (stream integration)
# ---------------------------------------------------------------------------

def _session_server(n=200, k=4, seed=3, **kw):
    g = graph.watts_strogatz(n, 4, 0.2, seed=seed)
    sess = S.StreamSession(g, S.StreamConfig(k=k, chunk_size=32,
                                             drift_threshold=1e9), key=0)
    srv = G.GraphServer.from_session(sess, **kw)
    return sess, srv


def test_plan_swap_on_stream_update():
    sess, srv = _session_server()
    r0 = srv.serve([G.QueryRequest("sssp", params={"source": 0})])[0]
    assert r0.version == 0 and not r0.from_cache
    sess.apply(inserts=np.array([[1, 150], [2, 160]]))
    r1 = srv.serve([G.QueryRequest("sssp", params={"source": 0})])[0]
    assert r1.version > r0.version and r1.fingerprint != r0.fingerprint
    assert not r1.from_cache, "cache must not serve across a plan swap"
    _check(r1, sess.graph())
    assert srv.stats()["plan_buffer_swaps"] >= 1


def test_warm_start_repair_after_insert_only_patch():
    """ROADMAP item: incremental SSSP result repair. After an insert-only
    patch the server warm-starts the repeated query from the previous
    epoch's distances (valid upper bounds) — the result stays bit-identical
    to the post-patch oracle while converging in no more supersteps than a
    cold recompute; a deletion breaks the lineage and forces cold."""
    sess, srv = _session_server(n=240, seed=5)
    cold = srv.serve([G.QueryRequest("sssp", params={"source": 7}),
                      G.QueryRequest("wsssp", params={"source": 7})])
    assert all(not r.warm_start for r in cold)
    # small insert-only patch (offset-3 pairs: absent from the WS(k=4)
    # lattice): old distances are upper bounds
    sess.apply(inserts=np.array([[3, 6], [10, 13]]))
    warm = srv.serve([G.QueryRequest("sssp", params={"source": 7}),
                      G.QueryRequest("wsssp", params={"source": 7})])
    for r, c in zip(warm, cold):
        assert r.warm_start and not r.from_cache
        assert r.supersteps <= c.supersteps
        _check(r, sess.graph())
    # chained insert-only patches keep the lineage alive — and in a mixed
    # batch only the lane with history is stamped warm: a never-before-seen
    # source coalesced into the same dispatch runs (and reports) cold
    sess.apply(inserts=np.array([[20, 23]]))
    warm2, fresh = srv.serve([
        G.QueryRequest("sssp", params={"source": 7}),
        G.QueryRequest("sssp", params={"source": 101})])
    assert warm2.warm_start and not fresh.warm_start
    assert fresh.bucket == warm2.bucket, "same dispatch"
    _check(warm2, sess.graph())
    _check(fresh, sess.graph())
    # a deletion breaks it: the warm store is dropped, dispatch goes cold
    gu, gv = sess.graph().as_numpy()
    sess.apply(deletes=np.array([[gu[0], gv[0]]]))
    post = srv.serve([G.QueryRequest("sssp", params={"source": 7}),
                      G.QueryRequest("bfs", params={"source": 7})])
    assert all(not r.warm_start for r in post)
    for r in post:
        _check(r, sess.graph())


def test_inflight_queries_drain_against_captured_buffer():
    """Double-buffer semantics: a batch pumped before the swap is labelled
    with (and correct for) the old snapshot; the rest of the queue drains
    against the new one."""
    sess, srv = _session_server(buckets=(2,))
    g_old = sess.graph()
    for s in (0, 3, 9, 12):
        srv.submit(G.QueryRequest("sssp", params={"source": s}))
    first = srv.pump()                         # one bucket=2 batch, old plan
    assert [r.request.params["source"] for r in first] == [0, 3]
    sess.apply(inserts=np.array([[0, 100], [3, 150], [9, 180]]))
    rest = srv.drain()                         # remaining queue, new plan
    g_new = sess.graph()
    assert g_old.fingerprint() != g_new.fingerprint()
    for r in first:
        assert r.version == 0
        _check(r, g_old)
    for r in rest:
        assert r.version > 0
        _check(r, g_new)


def test_serving_under_mutation_stress():
    """Acceptance stress: interleave stream update batches with server
    query bursts. Every returned result must be bit-identical to the oracle
    for the snapshot it was served from, and no stale result-cache entry
    may survive a version bump."""
    sess, srv = _session_server(n=200, buckets=(1, 2, 4))
    snapshots = {sess.version: sess.graph()}
    sess.subscribe(lambda s, event: snapshots.setdefault(s.version,
                                                         s.graph()))
    rng = np.random.default_rng(7)
    n_v = sess.graph().n_vertices
    results = []
    for round_ in range(4):
        # a burst of multi-tenant queries ...
        reqs = [G.QueryRequest("sssp", tenant=f"t{i % 3}",
                               params={"source": int(rng.integers(0, n_v))})
                for i in range(5)]
        reqs.append(G.QueryRequest("wcc", tenant="t0"))
        if round_ % 2:
            reqs.append(G.QueryRequest("pagerank", tenant="t1",
                                       params={"iters": 8}))
        for r in reqs:
            srv.submit(r)
        results.extend(srv.pump())             # partially drain ...
        # ... mutate mid-queue (plan swap while requests are pending) ...
        gu, gv = sess.graph().as_numpy()
        kill = rng.choice(len(gu), size=4, replace=False)
        sess.apply(inserts=rng.integers(0, n_v, size=(6, 2)),
                   deletes=np.stack([gu[kill], gv[kill]], 1))
        # ... then drain the rest against the swapped-in plan
        results.extend(srv.drain())
        # stale cache entries must not survive the bump
        fps = srv.cache.fingerprints()
        assert fps <= {sess.graph().fingerprint()}, \
            "result cache holds entries for a dead fingerprint"
    assert len(results) == 4 * 6 + 2
    served_versions = {r.version for r in results}
    assert len(served_versions) >= 3, "stress never spanned a plan swap"
    for r in results:
        g_at = snapshots[r.version]
        assert r.fingerprint == g_at.fingerprint()
        _check(r, g_at)


def test_epoch_bump_compaction_consistency():
    """Force a compaction epoch (spare slots exhausted) under serving: the
    post-compaction buffer answers correctly and carries the new epoch."""
    g = graph.watts_strogatz(100, 4, 0.1, seed=1)   # small padding
    sess = S.StreamSession(g, S.StreamConfig(k=3, chunk_size=32,
                                             drift_threshold=1e9), key=0)
    srv = G.GraphServer.from_session(sess)
    r0 = srv.serve([G.QueryRequest("sssp", params={"source": 0})])[0]
    assert r0.epoch == 0
    rng = np.random.default_rng(1)
    stats = sess.apply(inserts=rng.integers(0, 100, size=(400, 2)))
    assert stats["recompiles"] >= 1
    r1 = srv.serve([G.QueryRequest("sssp", params={"source": 0})])[0]
    assert r1.epoch == sess.epoch >= 1
    assert not r1.from_cache
    _check(r1, sess.graph())
