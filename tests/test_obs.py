"""repro.obs correctness: ring-buffer wraparound, the disabled no-op
contract, span-tree connectivity across a served micro-batch (admission ->
batch -> dispatch -> execute -> materialize), retrace events on a forced
bucket-shape change, export round-trips (JSONL + Chrome trace schema),
partition-health gauges matching the core metrics after a stream patch,
and the program's spans, counters and named scopes as a JAX profiler
trace sees them."""
import json
import pathlib
import time

import numpy as np
import pytest

from repro.core import dfep, graph, metrics
from repro import engine as E
from repro import gserve as G
from repro import obs
from repro import stream as S
from repro.engine import runtime
from repro.obs import recorder as recorder_mod
from repro.obs.recorder import Recorder


@pytest.fixture(autouse=True)
def _clean_recorder():
    """The recorder is process-global: leave it disabled and empty for
    whichever test (in any file) runs next."""
    rec = obs.get()
    rec.disable()
    rec.reset()
    yield
    rec.disable()
    rec.reset()


def _served_server(n=150, k=4, seed=3, **kw):
    g = graph.watts_strogatz(n, 4, 0.2, seed=seed)
    owner, _ = dfep.partition(g, k=k, key=0)
    plan = E.compile_plan(g, np.asarray(owner), k)
    return g, G.GraphServer(E.Engine(plan), g, **kw)


# ---------------------------------------------------------------------------
# recorder core
# ---------------------------------------------------------------------------

def test_ring_wraparound():
    r = Recorder(capacity=16)
    r.enable()
    for i in range(2 * 16 + 3):
        r.event("tick", i=i)
    evs = r.events()
    assert len(evs) == 16
    # oldest-first unwrap: the surviving events are exactly the last 16
    assert [e["args"]["i"] for e in evs] == list(range(19, 35))
    st = r.stats()
    assert st["since_reset"] == 35 and st["dropped"] == 35 - 16
    assert st["recorded"] == 35


def test_lifetime_survives_reset():
    r = Recorder(capacity=8)
    r.enable()
    for i in range(5):
        r.event("tick")
    r.reset()
    assert r.stats()["recorded"] == 5 and r.stats()["since_reset"] == 0
    r.enable()
    r.event("tock")
    assert r.stats()["recorded"] == 6
    assert [e["name"] for e in r.events()] == ["tock"]


def test_disabled_is_noop_and_cheap():
    r = Recorder(capacity=64)
    assert not r.enabled
    r.event("never", x=1)
    r.counter("never")
    r.gauge("never", 1.0)
    sid = r.begin("never")
    assert sid is None
    r.end(sid)                       # end(None) needs no caller branch
    with r.span("never") as s:
        assert s is None
    with r.tags(program="x"):
        r.event("never")
    assert r.events() == [] and r.stats()["recorded"] == 0
    assert r.stats()["open_spans"] == 0
    # near-zero overhead: one enabled-check branch per call — generously
    # bounded here (loaded CI boxes) but orders of magnitude under what
    # any allocating/recording path would cost
    n = 50_000
    t0 = time.perf_counter()
    for _ in range(n):
        r.event("never", a=1, b=2)
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 20e-6
    assert r.stats()["recorded"] == 0


def test_enable_with_new_capacity_reallocates():
    r = Recorder(capacity=4)
    r.enable()
    r.event("a")
    r.enable(capacity=8)             # capacity change drops the old ring
    assert r.stats()["capacity"] == 8 and r.events() == []
    r.event("b")
    assert [e["name"] for e in r.events()] == ["b"]


def test_span_stack_nesting_and_explicit_parent():
    r = Recorder()
    r.enable()
    with r.span("outer") as oid:
        with r.span("inner"):
            pass
        sid = r.begin("sibling", parent=oid)
        r.end(sid, extra="yes")
    by = {e["name"]: e for e in r.events()}
    assert by["inner"]["args"]["parent_id"] == oid
    assert by["sibling"]["args"]["parent_id"] == oid
    assert by["sibling"]["args"]["extra"] == "yes"
    assert "parent_id" not in by["outer"]["args"]
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in by.values())


def test_ambient_tags_merge():
    r = Recorder()
    r.enable()
    with r.tags(program="sssp", bucket=8):
        r.event("engine.retrace", epoch=3)
        r.event("engine.retrace", program="explicit-wins")
    e1, e2 = r.events()
    assert e1["args"] == {"program": "sssp", "bucket": 8, "epoch": 3}
    assert e2["args"]["program"] == "explicit-wins"


def test_provider_snapshot_and_weakref_drop():
    r = Recorder()

    class Src:
        def stats(self):
            return {"x": 1}

    s = Src()
    unreg = r.register_provider("src", s.stats)
    r.register_provider("fn", lambda: {"y": 2})
    snap = r.snapshot()
    assert snap["src"] == {"x": 1} and snap["fn"] == {"y": 2}
    del s                            # collected owner drops out silently
    assert "src" not in r.snapshot()
    unreg()
    r.register_provider("fn2", lambda: {"z": 3})
    assert "fn2" in r.snapshot()


# ---------------------------------------------------------------------------
# serve-path span tree
# ---------------------------------------------------------------------------

def test_served_batch_span_tree_connected():
    g, srv = _served_server()
    rec = obs.get()
    rec.enable()
    reqs = [G.QueryRequest("sssp", tenant="a", params={"source": 1}),
            G.QueryRequest("sssp", tenant="b", params={"source": 5}),
            G.QueryRequest("wcc", tenant="a")]
    out = srv.serve(reqs)
    assert all(r.value is not None for r in out)

    by_name = {}
    for e in rec.events():
        by_name.setdefault(e["name"], []).append(e)
    # one admission span per submitted request, tagged with its tenant
    adm = by_name["serve.admission"]
    assert len(adm) == 3
    assert {e["args"]["tenant"] for e in adm} == {"a", "b"}
    assert all(e["args"]["admitted"] for e in adm)
    # two micro-batches (sssp x2 coalesced, wcc), each a span that names
    # every rider request and tenant
    batches = by_name["serve.batch"]
    assert len(batches) == 2
    ids = {e["args"]["span_id"]: e for e in batches}
    sssp_batch = next(e for e in batches if e["args"]["program"] == "sssp")
    assert sssp_batch["args"]["n_requests"] == 2
    assert sssp_batch["args"]["tenants"] == ["a", "b"]
    assert {r.request.id for r in out[:2]} == \
        set(sssp_batch["args"]["requests"])
    # dispatch/execute/materialize all attach to a batch span explicitly
    # (the pipelined drain interleaves batches, so nesting can't carry it)
    for stage in ("serve.dispatch", "serve.execute", "serve.materialize"):
        stage_evs = by_name[stage]
        assert len(stage_evs) == 2, stage
        for e in stage_evs:
            assert e["args"]["parent_id"] in ids, stage
    # engine-level dispatch events rode along underneath
    assert len(by_name["engine.dispatch"]) == 2
    # a single-job Engine.run is one engine.run span naming its program
    # and supersteps
    res = srv.front.engine.run(E.SSSP, source=1)
    run = [e for e in rec.events() if e["name"] == "engine.run"]
    assert len(run) == 1
    assert run[0]["args"]["program"] == "sssp"
    assert run[0]["args"]["supersteps"] == int(res.supersteps) > 0
    assert rec.stats()["open_spans"] == 0
    srv.close()


def test_admission_rejection_closes_span():
    _, srv = _served_server(max_pending=2)
    rec = obs.get()
    rec.enable()
    srv.submit(G.QueryRequest("sssp", tenant="a", params={"source": 1}))
    srv.submit(G.QueryRequest("sssp", tenant="a", params={"source": 2}))
    with pytest.raises(G.AdmissionError):
        srv.submit(G.QueryRequest("sssp", tenant="a", params={"source": 3}))
    adm = [e for e in rec.events() if e["name"] == "serve.admission"]
    assert [e["args"]["admitted"] for e in adm] == [True, True, False]
    assert "reason" in adm[-1]["args"]
    assert rec.stats()["open_spans"] == 0
    srv.drain()
    srv.close()


def test_retrace_events_attributed_and_counted():
    # a graph size nothing else traces: the process-wide jit cache must be
    # cold for these avals or no retrace happens at all
    g, srv = _served_server(n=173, k=5, buckets=(1, 2))
    rec = obs.get()
    rec.enable()
    before = runtime.TRACE_COUNTER["run_loop"]
    srv.serve([G.QueryRequest("sssp", params={"source": 1})])
    srv.serve([G.QueryRequest("sssp", params={"source": 2}),
               G.QueryRequest("sssp", params={"source": 5})])
    delta = runtime.TRACE_COUNTER["run_loop"] - before
    retraces = [e for e in rec.events() if e["name"] == "engine.retrace"]
    # the accounting invariant: every TRACE_COUNTER bump is now an
    # attributable event carrying the program (explicit arg) and the
    # dispatch's bucket shape (ambient tag set at the dispatch site)
    assert len(retraces) == delta >= 1
    assert all(e["args"]["program"] == "sssp" for e in retraces)
    assert all(e["args"]["bucket"] in (1, 2) for e in retraces)
    assert all(e["args"]["epoch"] == 0 for e in retraces)
    snap = rec.snapshot()
    assert snap["counters"]["engine.retraces"] == delta
    assert snap["jit"]["run_loop_traces"] == runtime.TRACE_COUNTER["run_loop"]
    srv.close()


def test_retrace_event_on_forced_compaction_epoch():
    # zero slack: any insert forces a compaction, whose epoch bump is a new
    # static aux -> the one legitimate retrace on the streaming path, and
    # the event must carry the NEW epoch so a trace shows what triggered it
    g = graph.watts_strogatz(166, 4, 0.2, seed=2)
    sess = S.StreamSession(g, S.StreamConfig(k=3, chunk_size=32,
                                             edge_slack=0, vertex_slack=0,
                                             drift_threshold=1e9), key=0)
    srv = G.GraphServer.from_session(sess, buckets=(1,), cache_entries=0)
    srv.serve([G.QueryRequest("sssp", params={"source": 1})])  # trace cold
    rec = obs.get()
    rec.enable()
    rng = np.random.default_rng(1)
    sess.apply(inserts=rng.integers(0, g.n_vertices, size=(90, 2)))
    assert sess.epoch > 0
    before = runtime.TRACE_COUNTER["run_loop"]
    srv.serve([G.QueryRequest("sssp", params={"source": 3})])
    delta = runtime.TRACE_COUNTER["run_loop"] - before
    retraces = [e for e in rec.events() if e["name"] == "engine.retrace"]
    assert len(retraces) == delta >= 1
    assert retraces[-1]["args"]["epoch"] == sess.epoch
    assert retraces[-1]["args"]["program"] == "sssp"
    srv.close()


def test_patched_plan_keeps_warm_cache_no_retrace_events():
    g = graph.watts_strogatz(150, 4, 0.2, seed=3)
    sess = S.StreamSession(g, S.StreamConfig(k=4, chunk_size=64,
                                             drift_threshold=1e9), key=0)
    srv = G.GraphServer.from_session(sess, buckets=(2,), cache_entries=0)
    rec = obs.get()
    srv.serve([G.QueryRequest("sssp", params={"source": 1}),
               G.QueryRequest("sssp", params={"source": 5})])  # trace cold
    rec.enable()
    sess.apply(inserts=np.array([[0, 90], [3, 77]]))
    srv.serve([G.QueryRequest("sssp", params={"source": 2}),
               G.QueryRequest("sssp", params={"source": 7})])
    evs = [e["name"] for e in rec.events()]
    # patched plan: same treedef/avals -> warm jit cache, zero retraces —
    # but the swap itself and the dispatches are all on the record
    assert "engine.retrace" not in evs
    assert "stream.plan_swap" in evs and "serve.plan_swap" in evs
    assert "engine.dispatch" in evs
    srv.close()


# ---------------------------------------------------------------------------
# stream health gauges
# ---------------------------------------------------------------------------

def test_health_gauges_match_plan_metrics_after_patch():
    g = graph.watts_strogatz(150, 4, 0.2, seed=1)
    sess = S.StreamSession(g, S.StreamConfig(k=4, chunk_size=64,
                                             drift_threshold=1e9), key=0)
    rec = obs.get()
    rec.enable()
    rng = np.random.default_rng(0)
    u, v = g.as_numpy()
    sess.apply(inserts=rng.integers(0, g.n_vertices, size=(20, 2)),
               deletes=np.stack([u[:10], v[:10]], 1))

    plan = sess.plan
    snap = rec.snapshot()
    gauges = snap["gauges"]
    # the paper's axes, recomputed from the installed plan by core/metrics
    # formulas — the gauge stamped at the swap must agree exactly
    assert gauges["stream.replication_factor"] == \
        pytest.approx(plan.replication_factor())
    sizes = np.asarray(plan.n_edges_local)
    assert gauges["stream.balance_nstdev"] == \
        pytest.approx(metrics.nstdev(sizes, int(sizes.sum())))
    assert gauges["stream.exchange_per_superstep"] == plan.exchange_volume
    assert 0 < gauges["stream.edge_lane_occupancy_max"] <= 1.0
    assert gauges["stream.min_free_edge_slots"] >= 0

    swaps = [e for e in rec.events() if e["name"] == "stream.plan_swap"]
    assert swaps, "plan mutation must emit a swap event"
    last = swaps[-1]["args"]
    assert last["replication_factor"] == \
        pytest.approx(plan.replication_factor())
    assert last["inserts"] == 20 and last["deletes"] == 10
    assert last["version"] == sess.version
    # the apply itself was a span
    assert any(e["name"] == "stream.apply" for e in rec.events())


def test_append_scatters_count_dispatches_on_appended_plans():
    """``engine.append_scatters`` counts the single-device Pallas dispatches
    whose plan holds appended half-edges, and
    ``plan.append_live_half_edges`` counts those half-edges: two for each
    inserted edge still live."""
    g = graph.watts_strogatz(150, 4, 0.2, seed=1)
    sess = S.StreamSession(g, S.StreamConfig(k=4, chunk_size=64,
                                             drift_threshold=1e9), key=0)
    rec = obs.get()
    rec.enable()
    E.engine_sssp(sess.engine, 0)
    E.engine_wcc(sess.engine)
    assert rec.counters()["engine.dispatches"] == 2
    assert rec.counters().get("engine.append_scatters", 0) == 0
    assert rec.gauges()["plan.append_live_half_edges"] == 0

    u, v = g.as_numpy()
    have = {(int(a), int(b)) for a, b in zip(u, v)}
    new = [(a, b) for a, b in ((0, 75), (3, 90), (10, 120))
           if (a, b) not in have]
    assert len(new) == 3
    sess.apply(inserts=np.array(new))
    E.engine_sssp(sess.engine, 0)
    assert rec.counters()["engine.append_scatters"] == 1
    assert rec.gauges()["plan.append_live_half_edges"] == 2 * 3
    sess.apply(deletes=np.array(new[:1]))
    E.engine_sssp(sess.engine, 0)
    E.engine_wcc(sess.engine)
    E.multi_source_sssp(sess.engine, [0, 5])      # batched: no Pallas path
    c = rec.counters()
    assert c["engine.dispatches"] == 6
    assert c["engine.append_scatters"] == 3
    assert rec.gauges()["plan.append_live_half_edges"] == 2 * 2


def test_compaction_event_carries_new_epoch():
    g = graph.watts_strogatz(120, 4, 0.2, seed=3)
    sess = S.StreamSession(g, S.StreamConfig(k=3, chunk_size=32,
                                             edge_slack=0, vertex_slack=0,
                                             drift_threshold=1e9), key=0)
    rec = obs.get()
    rec.enable()
    epoch0 = sess.epoch
    rng = np.random.default_rng(1)
    sess.apply(inserts=rng.integers(0, g.n_vertices, size=(40, 2)))
    assert sess.epoch > epoch0          # zero slack forces compaction
    comps = [e for e in rec.events() if e["name"] == "stream.compaction"]
    assert comps and comps[-1]["args"]["epoch"] == sess.epoch


# ---------------------------------------------------------------------------
# export round-trip
# ---------------------------------------------------------------------------

def test_export_roundtrip(tmp_path):
    g, srv = _served_server()
    rec = obs.get()
    rec.enable()
    srv.serve([G.QueryRequest("sssp", tenant="a", params={"source": 1}),
               G.QueryRequest("wcc", tenant="b")])
    srv.close()
    evs = rec.events()

    jl = tmp_path / "trace.jsonl"
    n = obs.export_jsonl(str(jl))
    lines = [json.loads(x) for x in jl.read_text().splitlines()]
    assert n == len(lines) == len(evs)
    assert [x["name"] for x in lines] == [e["name"] for e in evs]

    ct = tmp_path / "trace_chrome.json"
    n2 = obs.export_chrome_trace(str(ct))
    doc = json.loads(ct.read_text())
    tes = doc["traceEvents"]
    assert n2 == len(tes) == len(evs)
    for te in tes:
        assert {"name", "ph", "ts", "pid", "tid"} <= set(te)
        assert te["ph"] in ("X", "i")
        if te["ph"] == "X":
            assert te["dur"] >= 0
        else:
            assert te["s"] == "t"
    # the span tree survives the export: parent ids resolve in-file
    sids = {te["args"]["span_id"] for te in tes if "span_id" in te["args"]}
    for te in tes:
        if "parent_id" in te.get("args", {}):
            assert te["args"]["parent_id"] in sids


def test_overwritten_counter_monotone_across_reset():
    r = Recorder(capacity=8)
    r.enable()
    for i in range(12):
        r.event("tick", i=i)
    assert r.stats()["overwritten"] == 4
    r.reset()                        # ring cleared, lifetime loss is not
    assert r.stats()["overwritten"] == 4
    r.enable()
    for i in range(10):
        r.event("tock", i=i)
    st = r.stats()
    assert st["overwritten"] == 6
    assert st["dropped"] == 2        # per-reset loss restarts, lifetime grows


def test_chrome_export_tolerates_overwritten_parent(tmp_path):
    r = Recorder(capacity=4)
    r.enable()
    with r.span("parent") as pid:
        pass                         # parent's X event lands first...
    sid = r.begin("orphan-child", parent=pid)
    r.end(sid)
    for i in range(3):               # ...and the flood overwrites it
        r.event("filler", i=i)
    assert all(e["name"] != "parent" for e in r.events())
    path = tmp_path / "trace.json"
    n = obs.export_chrome_trace(str(path), recorder=r)
    doc = json.loads(path.read_text())
    assert n == len(doc["traceEvents"]) == 4
    # the unresolvable reference is renamed, not emitted: Perfetto would
    # otherwise try to parent the slice onto a nonexistent span
    (child,) = [te for te in doc["traceEvents"]
                if te["name"] == "orphan-child"]
    assert "parent_id" not in child["args"]
    assert child["args"]["dangling_parent_id"] == pid
    assert doc["otherData"]["dangling_parents"] == 1


def test_raising_provider_reported_not_fatal():
    r = Recorder()
    boom_calls = []

    def boom():
        boom_calls.append(1)
        raise RuntimeError("gauge backend gone")

    r.register_provider("boom", boom)
    r.register_provider("fine", lambda: {"ok": 1})
    snap = r.snapshot()              # must not raise
    assert snap["fine"] == {"ok": 1}
    assert snap["boom"] == {"error": "RuntimeError: gauge backend gone"}
    assert boom_calls == [1]


# Clock discipline (no wall-clock time.time() in measured paths) is
# enforced repo-wide by the LP002 AST rule (repro.analysis) via
# tests/test_analysis.py::test_repo_scans_clean — alias-aware, unlike the
# grep-mirroring test that used to live here.


# ---------------------------------------------------------------------------
# the program's spans in a profiler trace; counters; named scopes
# ---------------------------------------------------------------------------

class _CountingMark:
    made = 0

    def __init__(self, name):
        type(self).made += 1

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_disabled_recorder_creates_no_annotation(monkeypatch):
    monkeypatch.setattr(recorder_mod, "TraceAnnotation", _CountingMark)
    _CountingMark.made = 0
    r = Recorder()
    r.end(r.begin("never"))
    with r.span("never"):
        pass
    assert _CountingMark.made == 0
    r.enable()
    r.end(r.begin("once"))
    with r.span("twice"):
        pass
    assert _CountingMark.made == 2


def _host_spans(trace_dir) -> dict:
    """name -> [(start_ns, end_ns, line)] of the trace's host events."""
    from jax.profiler import ProfileData

    found = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    pd = ProfileData.from_file(str(found[-1]))
    out: dict = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                out.setdefault(e.name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns,
                     (plane.name, li)))
    return out


def test_spans_reach_the_profiler_trace(tmp_path):
    import jax

    g, srv = _served_server()
    rec = obs.get()
    rec.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        dfep.partition(g, k=4, key=1)
        # two programs, so the drain pipelines two batches: the second
        # batch span opens before the first one closes
        srv.serve([G.QueryRequest("sssp", params={"source": 1}),
                   G.QueryRequest("wcc")])
    finally:
        jax.profiler.stop_trace()
    srv.close()
    got = _host_spans(tmp_path)
    for name in ("dfep.partition", "dfep.slots", "dfep.run",
                 "serve.admission", "serve.lock_wait", "serve.batch",
                 "serve.dispatch", "serve.execute", "engine.sync",
                 "serve.fetch", "serve.materialize"):
        assert name in got, name
    ring = {}
    for e in rec.events():
        if e.get("ph") == "X":
            ring.setdefault(e["name"], []).append(e)
    for name in ("dfep.partition", "serve.batch", "serve.fetch"):
        assert len(got[name]) == len(ring[name]), name
    # the batches interleave on one thread, each with its own extent
    (s0, e0, l0), (s1, e1, l1) = sorted(got["serve.batch"])
    assert l0 == l1 and s0 < s1 < e0 < e1
    durs = sorted(e["dur"] for e in ring["serve.batch"])
    assert sorted((e - s) / 1e3 for s, e, _ in got["serve.batch"]) == \
        pytest.approx(durs, rel=0.2, abs=200)
    # children lie inside their parents on the profiler's clock
    (ps, pe, _), = got["dfep.partition"]
    for child in ("dfep.slots", "dfep.run"):
        (cs, ce, _), = got[child]
        assert ps <= cs <= ce <= pe
    assert rec.stats()["open_spans"] == 0


def test_partition_and_serve_counters():
    g, srv = _served_server()
    rec = obs.get()
    rec.enable()
    _, info = dfep.partition(g, k=4, key=2)
    part = [e for e in rec.events() if e["name"] == "dfep.partition"]
    assert part[0]["args"]["rounds"] == info["rounds"]
    assert part[0]["args"]["finalized"] == info["finalized"]
    # three sssp lanes pad to a bucket of four
    srv.serve([G.QueryRequest("sssp", params={"source": s})
               for s in (1, 2, 3)])
    srv.close()
    c = rec.counters()
    assert c["dfep.rounds"] == info["rounds"]
    assert c["serve.lanes"] == 3
    assert c["serve.bucket_lanes"] == G.bucket_for(3, srv.buckets) > 3


def test_named_scopes_reach_the_hlo():
    g = graph.watts_strogatz(60, 4, 0.2, seed=1)
    import jax

    cfg = dfep.DfepConfig(k=4)
    text = dfep.run_dfep.lower(g, dfep.build_slots(g), cfg,
                               jax.random.key(0)).compile().as_text()
    for scope in ("dfep.spread", "dfep.auction", "dfep.grant"):
        assert f"/{scope}/" in text, scope
    owner, _ = dfep.partition(g, k=4, key=0)
    eng = E.Engine(E.compile_plan(g, np.asarray(owner), 4))
    text = eng.lower_hlo(E.SSSP, source=jax.numpy.int32(0))
    for scope in ("engine.sweep", "engine.exchange", "engine.gather"):
        assert f"/{scope}/" in text, scope
