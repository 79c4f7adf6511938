"""Cost attribution: the hlo_parse analyzer on real engine executables,
CostModel memoization and its never-raise contract, CostLedger accounting
invariants on a served mixed-tenant workload, cost-weighted admission and
flush ordering, and the usage renderer."""
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.core import dfep, graph
from repro import engine as E
from repro import gserve as G
from repro import obs
from repro.engine.registry import get_program
from repro.gserve.request import AdmissionError
from repro.gserve.scheduler import MicroBatcher
from repro.obs import profile, usage
from repro.obs.ledger import CostLedger, CostSample
from repro.roofline.hlo_parse import analyze_hlo


@pytest.fixture(autouse=True)
def _clean_profile_cache():
    """The model cache and recorder are process-global; leave both clean
    for whichever test runs next."""
    profile.reset_models()
    rec = obs.get()
    rec.disable()
    rec.reset()
    yield
    profile.reset_models()
    rec.disable()
    rec.reset()


def _engine(n=120, k=4, seed=3):
    g = graph.watts_strogatz(n, 4, 0.2, seed=seed)
    owner, _ = dfep.partition(g, k=k, key=0)
    return g, E.Engine(E.compile_plan(g, np.asarray(owner), k))


def _lower(g, eng, kind, params, batched=None):
    """Lower exactly the executable the serving path would dispatch."""
    entry = get_program(kind)
    params = G.QueryRequest(kind, params=params).params
    kw = {name: fn(g) for name, fn in entry.resources}
    kw.update(entry.ctx_args(params))
    return eng.lower_hlo(entry.program, batched_kw=batched,
                         max_supersteps=entry.supersteps_of(params), **kw)


# ---------------------------------------------------------------------------
# hlo_parse robustness + engine-executable coverage (ISSUE 8 satellites)
# ---------------------------------------------------------------------------

def test_unknown_opcode_degrades_to_unmodeled_count():
    hlo = """HloModule m

ENTRY %main (p0: f32[64], p1: f32[64]) -> f32[64] {
  %p0 = f32[64]{0} parameter(0)
  %p1 = f32[64]{0} parameter(1)
  %a = f32[64]{0} add(%p0, %p1)
  %b = f32[64]{0} frobnicate(%a, %p1)
  ROOT %r = f32[64]{0} multiply(%a, %b)
}
"""
    c = analyze_hlo(hlo)
    # the unknown op is counted, not raised, and does not poison the
    # modeled instructions around it (add + multiply = 2 * 64 flops);
    # its byte traffic is still charged (bytes need only shapes)
    assert c.unmodeled_ops == 1
    assert c.flops == 128.0
    assert c.bytes_traffic > 0
    assert np.isfinite(c.arithmetic_intensity)


def test_engine_hlo_costs_positive_and_monotone_in_graph_size():
    """Parse the compiled SSSP (batched) and PageRank superstep HLO at two
    graph sizes: flops/bytes positive, finite, and monotone."""
    bkw = {"source": np.zeros(4, np.int32)}
    costs = {}
    for n in (120, 240):
        g, eng = _engine(n=n)
        sssp = analyze_hlo(_lower(g, eng, "sssp", {"source": 0},
                                  batched=bkw), trip_clamp=1)
        pr = analyze_hlo(_lower(g, eng, "pagerank", {"iters": 5}),
                         trip_clamp=1)
        for c in (sssp, pr):
            assert c.flops > 0 and np.isfinite(c.flops)
            assert c.bytes_traffic > 0 and np.isfinite(c.bytes_traffic)
        costs[n] = (sssp, pr)
    s_small, p_small = costs[120]
    s_big, p_big = costs[240]
    assert s_big.flops > s_small.flops
    assert s_big.bytes_traffic > s_small.bytes_traffic
    assert p_big.flops > p_small.flops
    assert p_big.bytes_traffic > p_small.bytes_traffic


# ---------------------------------------------------------------------------
# obs.profile: memoized CostModel
# ---------------------------------------------------------------------------

def test_cost_model_memoized_per_shape():
    g, eng = _engine()
    entry = get_program("sssp")
    bkw = {"source": np.zeros(4, np.int32)}
    m1 = profile.cost_model(eng, entry.program, bucket=4, batched_kw=bkw)
    assert m1.error is None
    assert m1.flops_per_sweep > 0 and m1.hbm_bytes_per_sweep > 0
    assert m1.compile_s > 0
    m2 = profile.cost_model(eng, entry.program, bucket=4, batched_kw=bkw)
    assert m2 is m1                                  # cache hit
    st = profile.profile_stats()
    assert st["hits"] == 1 and st["misses"] == 1 and st["errors"] == 0
    # a different bucket is a different executable -> a fresh model
    bkw8 = {"source": np.zeros(8, np.int32)}
    m3 = profile.cost_model(eng, entry.program, bucket=8, batched_kw=bkw8)
    assert m3 is not m1 and profile.profile_stats()["misses"] == 2
    # cost() scales linearly in sweeps; attainable_s is a positive bound
    # on a device with published peaks and "not available" (None) on any
    # other — the CPU backend included — never an assumed peak
    fl1, by1, _ = m1.cost(1)
    fl3, by3, _ = m1.cost(3)
    assert fl3 == pytest.approx(3 * fl1) and by3 == pytest.approx(3 * by1)
    assert m1.device_kind == jax.devices()[0].device_kind
    if m1.device_kind in profile.DEVICE_PEAKS:
        assert m1.attainable_s(3) > 0
    else:
        assert m1.attainable_s(3) is None
    v5e = dataclasses.replace(m1, device_kind="TPU v5 lite")
    flops_peak, hbm_peak = profile.DEVICE_PEAKS["TPU v5 lite"]
    assert v5e.attainable_s(3) == pytest.approx(
        max(fl3 / flops_peak, by3 / hbm_peak))
    assert v5e.attainable_s(3) > 0


def test_cost_model_never_raises():
    g, eng = _engine()

    class Boom:
        plan = eng.plan
        mesh = None

        def lower_hlo(self, *a, **kw):
            raise RuntimeError("lowering exploded")

    m = profile.cost_model(Boom(), get_program("sssp").program, bucket=4)
    assert m.error is not None and "lowering exploded" in m.error
    assert m.cost(10) == (0.0, 0.0, 0.0)
    # the error model is cached too: a persistently broken lowering is
    # paid for once, not per dispatch
    m2 = profile.cost_model(Boom(), get_program("sssp").program, bucket=4)
    assert m2 is m
    st = profile.profile_stats()
    assert st["errors"] == 1 and st["hits"] == 1


# ---------------------------------------------------------------------------
# CostLedger accounting
# ---------------------------------------------------------------------------

def _sample(tenant, device_s, program="sssp", graph_fp="g1", epoch=0, **kw):
    return CostSample(tenant=tenant, program=program, graph=graph_fp,
                      epoch=epoch, device_s=device_s, **kw)


def test_ledger_totals_shares_and_snapshot():
    led = CostLedger(window_s=30.0)
    led.post(_sample("a", 0.3, flops=3e6, utilization=0.5))
    led.post(_sample("a", 0.3, program="pagerank", flops=6e6))
    led.post(_sample("b", 0.2, flops=2e6, utilization=1.0))
    led.post(_sample("b", 0.0, from_cache=True))
    tot = led.totals()
    assert tot["series"] == 3
    assert tot["device_s"] == pytest.approx(0.8)
    assert tot["flops"] == pytest.approx(11e6)
    assert tot["requests"] == 4
    assert tot["dispatched"] == 3 and tot["cached"] == 1
    # lifetime shares sum to 1 and split by device time
    shares = led.tenant_shares(None)
    assert sum(shares.values()) == pytest.approx(1.0)
    assert shares["a"] == pytest.approx(0.75)
    # windowed shares (all samples just posted) agree
    win = led.tenant_shares(30.0)
    assert win["a"] == pytest.approx(0.75, rel=1e-6)
    snap = led.snapshot()
    assert snap["kind"] == "cost_ledger"
    assert set(snap["tenants"]) == {"a", "b"}
    assert snap["tenants"]["b"]["cached"] == 1
    # utilization aggregates device-time-weighted: b's 0.2s at 1.0 plus
    # a 0s cache hit -> 1.0
    assert snap["tenants"]["b"]["utilization"] == pytest.approx(1.0)
    assert len(snap["series"]) == 3


def test_ledger_merge_is_additive():
    a, b = CostLedger(), CostLedger()
    a.post(_sample("a", 0.5, flops=1e6))
    b.post(_sample("a", 0.25, flops=2e6))
    b.post(_sample("c", 0.25))
    a.merge(b)
    tot = a.totals()
    assert tot["device_s"] == pytest.approx(1.0)
    assert tot["flops"] == pytest.approx(3e6)
    assert tot["series"] == 2                  # same-key series folded
    assert a.tenant_shares(None)["a"] == pytest.approx(0.75)


def test_served_workload_reconciles_with_execute_spans():
    """The ISSUE 8 acceptance invariant, at test scale: ledger device
    seconds == the server's measured execute-span total (±1%), and every
    completed request lands in exactly one series (cache hits included)."""
    g, eng = _engine(n=150)
    led = CostLedger(window_s=30.0)
    srv = G.GraphServer(eng, g, buckets=(1, 4), ledger=led)
    reqs = [G.QueryRequest("sssp", tenant="a", params={"source": s})
            for s in (0, 1, 2)]
    reqs += [G.QueryRequest("pagerank", tenant="b", params={"iters": 5}),
             G.QueryRequest("wcc", tenant="b")]
    srv.serve(reqs)
    # repeat query -> result-cache hit -> zero-cost sample, same series key
    rep = srv.serve([G.QueryRequest("sssp", tenant="a",
                                    params={"source": 0})])[0]
    assert rep.from_cache
    tot = led.totals()
    dev = srv.metrics.device_time_s
    assert dev > 0
    assert abs(tot["device_s"] - dev) <= 0.01 * dev
    assert tot["requests"] == srv.metrics.n_completed == 6
    assert tot["dispatched"] == 5 and tot["cached"] == 1
    snap = led.snapshot()
    has_peaks = jax.devices()[0].device_kind in profile.DEVICE_PEAKS
    for agg in snap["tenants"].values():
        if has_peaks:
            assert 0.0 <= agg["utilization"]
        else:   # no published peaks: "not available", never a guess
            assert agg["utilization"] is None
    # per-request flop attribution flowed through the models
    assert tot["flops"] > 0
    srv.close()


# ---------------------------------------------------------------------------
# cost-weighted serving behaviour
# ---------------------------------------------------------------------------

def test_cost_weighted_admission_shrinks_overdrawn_quota():
    """With the ledger showing one tenant holding ~90% of the windowed
    device time, its count-based pending quota (max_pending//n_active)
    shrinks by fair/used; the under-budget tenant keeps the full quota."""
    g, eng = _engine()

    def fill(srv):
        srv.submit(G.QueryRequest("sssp", tenant="cheap",
                                  params={"source": 0}))
        n = 0
        try:
            for it in range(20):
                srv.submit(G.QueryRequest("pagerank", tenant="heavy",
                                          params={"iters": 10 + it}))
                n += 1
        except AdmissionError:
            pass
        return n

    plain = G.GraphServer(eng, g, max_pending=8, cache_entries=0)
    count_quota = fill(plain)
    plain.close()
    assert count_quota == 4                    # 8 max_pending / 2 active

    led = CostLedger(window_s=30.0)
    led.post(_sample("heavy", 0.9, program="pagerank"))
    led.post(_sample("cheap", 0.1))
    srv = G.GraphServer(eng, g, max_pending=8, cache_entries=0, ledger=led)
    cost_quota = fill(srv)
    # fair=0.5, used=0.9 -> quota floor(4 * 0.5/0.9) = 2
    assert cost_quota == 2
    # the cheap tenant (share 0.1 < fair) keeps its count-based quota
    for s in range(1, 4):
        srv.submit(G.QueryRequest("sssp", tenant="cheap",
                                  params={"source": s}))
    srv.close()


def test_cost_weighted_flush_order_drains_cheap_tenant_first():
    b = MicroBatcher(buckets=(1, 4))
    heavy = G.QueryRequest("pagerank", tenant="heavy", params={"iters": 7})
    cheap = G.QueryRequest("sssp", tenant="cheap", params={"source": 0})
    b.add(heavy)
    b.add(cheap)
    # FIFO (no ledger): arrival order -> heavy's key first
    assert b.next_batch().requests[0].tenant == "heavy"

    b2 = MicroBatcher(buckets=(1, 4))
    b2.cost_of = {"heavy": 0.9, "cheap": 0.1}.get
    b2.add(heavy)
    b2.add(cheap)
    # cost-weighted: the cheap head tenant flushes first despite arriving
    # second; the heavy backlog drains after
    first, second = b2.next_batch(), b2.next_batch()
    assert first.requests[0].tenant == "cheap"
    assert second.requests[0].tenant == "heavy"


# ---------------------------------------------------------------------------
# renderer + snapshot plumbing
# ---------------------------------------------------------------------------

def test_usage_renderer_loads_dump_and_obs_snapshot(tmp_path):
    led = CostLedger(window_s=30.0)
    led.post(_sample("alice", 0.6, flops=5e7, utilization=0.4))
    led.post(_sample("bob", 0.2, program="pagerank"))
    p = tmp_path / "usage_ledger.json"
    led.dump(str(p))
    text = usage.render(usage.load(str(p)))
    assert "alice" in text and "bob" in text and "pagerank" in text
    assert "USAGE LEDGER" in text
    # the ledger rides inside a full obs snapshot too (provider nesting)
    unregister = __import__("repro.obs.ledger", fromlist=["register"]) \
        .register(led, name="ledger_under_test")
    try:
        snap_path = tmp_path / "snap.json"
        snap_path.write_text(json.dumps(obs.snapshot(), default=str))
        doc = json.loads(snap_path.read_text())
        # the named provider carries a full ledger snapshot the renderer
        # accepts as-is (load()'s recursive search would surface the
        # process-global "ledger" provider first, which is empty here)
        assert doc["ledger_under_test"]["kind"] == "cost_ledger"
        assert "alice" in usage.render(doc["ledger_under_test"])
    finally:
        unregister()


def test_ledger_rides_in_obs_snapshot_by_default():
    """The process-global ledger is a registered provider: posting to it
    shows up in obs.snapshot() with no extra wiring."""
    from repro.obs.ledger import get_ledger
    led = get_ledger()
    led.reset()
    led.post(_sample("snapshot-tenant", 0.1))
    try:
        found = usage._find_ledger(obs.snapshot())
        assert found is not None
        assert "snapshot-tenant" in found["tenants"]
    finally:
        led.reset()
