"""The scope reduction (``bench/scopes.py`` over ``bench/xspace.py``) on
the recorded chip trace and on a synthetic one, the metric readers that
read the program's new spans and counters, and the per-unit split of
``bench/trace_cell.py``."""
from __future__ import annotations

import gzip
import pathlib
import types

import numpy as np
import pytest

from bench import run, scopes, trace_cell, trace_reduce, xspace

FIXTURE = pathlib.Path(__file__).with_name("data") / \
    "chip_trace.xplane.pb.gz"


@pytest.fixture(scope="module")
def chip_bytes():
    return gzip.decompress(FIXTURE.read_bytes())


@pytest.fixture(scope="module")
def chip_reduced(chip_bytes):
    from jax.profiler import ProfileData

    return trace_reduce.reduce_profile(
        ProfileData.from_serialized_xspace(chip_bytes))


def test_reader_agrees_with_profile_data(chip_bytes):
    from jax.profiler import ProfileData

    pd = ProfileData.from_serialized_xspace(chip_bytes)
    mine = xspace.planes(chip_bytes)
    assert [p.name for p in mine] == [p.name for p in pd.planes]
    for p, q in zip(mine, pd.planes):
        for line, other in zip(p.lines, q.lines):
            assert line.name == other.name
            got = [(e.start_ns, e.end_ns - e.start_ns, e.name)
                   for e in line.events]
            want = [(e.start_ns, e.duration_ns, e.name) for e in other.events]
            assert got == want


def test_op_paths_cover_the_device_time(chip_bytes, chip_reduced):
    red = scopes.reduce_space(chip_bytes)
    assert red["path_share"] >= 0.95
    # the partition in the fixture ran as jit(run_dfep)/while/body/...
    assert red["scopes"]["run_dfep"] > 0
    assert red["scopes"]["_run_single"] > 0
    leaf_s = sum(s for _, s in chip_reduced["ops"])
    assert sum(red["scopes"].values()) == pytest.approx(leaf_s, rel=1e-9)
    # recorded before the kernels had names: the anonymous branch calls
    assert set(red["kernels"]) == {"branch_0_fun"}
    pallas = sum(s for n, s in chip_reduced["ops"] if n.startswith("pallas("))
    assert red["kernels"]["branch_0_fun"]["s"] == pytest.approx(pallas)
    # no program span in that trace: the gaps keep the bench.* names
    assert dict(red["idle_gaps"]) == pytest.approx(
        dict(chip_reduced["breakdown"]["idle_gaps"]))


@pytest.mark.parametrize("path,scope", [
    ("jit(run_dfep)/while/body/dfep.spread/gather", "dfep.spread"),
    ("jit(f)/engine.exchange/dfep.auction/mul", "dfep.auction"),
    ("jit(f)/while/body/vmap(engine.sweep)/add", "engine.sweep"),
    ("jit(_run_single)/jit(_where)/select_n", "_run_single"),
    (None, scopes.UNSCOPED),
])
def test_scope_of_a_path(path, scope):
    assert scopes.scope_of(path) == scope


# -- a synthetic trace -------------------------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    """Fields (number, value): an int as a varint, bytes or str as
    length-delimited."""
    out = bytearray()
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return bytes(out)


def _plane(name: str, lines: list[tuple[int, str, list]]) -> bytes:
    """lines: (id, name, [(start_us, end_us, event name, tf_op or None)])"""
    names: dict[tuple, int] = {}
    fields = [(2, name), (5, _msg((1, 1), (2, _msg((1, 1), (2, "tf_op")))))]
    for lid, lname, events in lines:
        evs = []
        for s, t, ename, tf in events:
            mid = names.setdefault((ename, tf), len(names) + 1)
            evs.append((4, _msg((1, mid), (2, s * 10 ** 6),
                                (3, (t - s) * 10 ** 6))))
        fields.append((3, _msg((1, lid), (2, lname), (3, 0), *evs)))
    for (ename, tf), mid in names.items():
        stats = [(5, _msg((1, 1), (5, tf)))] if tf else []
        fields.append((4, _msg((1, mid),
                               (2, _msg((1, mid), (2, ename), *stats)))))
    return _msg(*fields)


KERNEL = ('%masked_update.3 = f32[8,2048]{1,0} custom-call(f32[8,2048]{1,0} '
          '%a), custom_call_target="tpu_custom_call"')


@pytest.fixture(scope="module")
def synthetic() -> bytes:
    window = [(0, 100, "bench.window", None), (10, 90, "bench.drain", None),
              (20, 60, "serve.batch", None), (50, 80, "serve.batch", None),
              (25, 35, "serve.execute", None),
              (30, 33, "PjitFunction(f)", None)]
    submitter = [(0, 100, "serve.admission", None),
                 (40, 44, "serve.lock_wait", None)]
    ops = [(0, 10, "%a = f32[8] add()", "jit(f)/dfep.spread/add:"),
           (12, 20, "%b = f32[8] multiply()",
            "jit(f)/engine.exchange/dfep.auction/mul:"),
           (40, 45, "%c = f32[8] gather()", "jit(f)/while/body/gather:"),
           (45, 50, KERNEL, None),
           (85, 95, "%e = f32[8] add()", "jit(f)/vmap(engine.sweep)/add:")]
    return _msg((1, _plane("/host:CPU", [(1, "python", window),
                                         (2, "python", submitter)])),
                (1, _plane("/device:TPU:0", [(3, "XLA Ops", ops)])))


def test_gaps_are_named_by_the_window_threads_innermost_span(synthetic):
    red = scopes.reduce_space(synthetic)
    gaps = dict(red["idle_gaps"])
    # the submitter's serve.admission covers every gap and names none
    assert gaps == pytest.approx({"bench.drain": 2e-6,
                                  "serve.execute": 20e-6,
                                  "serve.batch": 35e-6,
                                  "host.other": 5e-6})


def test_device_time_by_innermost_scope_and_kernel(synthetic):
    red = scopes.reduce_space(synthetic)
    assert red["scopes"] == pytest.approx({
        "dfep.spread": 10e-6, "dfep.auction": 8e-6, "f": 5e-6,
        scopes.UNSCOPED: 5e-6, "engine.sweep": 10e-6})
    assert red["kernels"] == {"masked_update": {"s": pytest.approx(5e-6),
                                                "calls": 1}}
    assert red["device_scopes"][0][1] == pytest.approx(10e-6)
    top = {n: (scope, path) for n, scope, path, _ in red["device_ops"]}
    assert top["multiply f32[8] b"] == (
        "dfep.auction", "jit(f)/engine.exchange/dfep.auction/mul")
    assert top["pallas(f32) f32[8,2048]"] == (scopes.UNSCOPED, None)


def test_ops_without_a_scope_are_placed_by_their_neighbours():
    window = [(0, 100, "bench.window", None)]
    ops = [(0, 10, "%a = f32[8] add()", "jit(f)/while/body/engine.sweep/add:"),
           (10, 30, "%s = f32[8] fusion()", "jit(f)/while:"),
           (30, 35, "%b = f32[8] gather()",
            "jit(f)/while/body/engine.sweep/gather:"),
           (35, 38, "%w = f32[8] fusion()", None),
           (40, 50, "%c = f32[8] mul()", "jit(f)/engine.exchange/mul:"),
           (50, 60, "%x = f32[8] copy()", "jit(f)/while:")]
    trace = _msg((1, _plane("/host:CPU", [(1, "python", window)])),
                 (1, _plane("/device:TPU:0", [(3, "XLA Ops", ops)])))
    red = scopes.reduce_space(trace)
    # only the fusion between two sweep ops; not the one between the sweep
    # and the exchange, nor the copy after the last scoped op
    assert red["enclosed"] == pytest.approx({"engine.sweep": 20e-6})
    assert red["scopes"]["f"] == pytest.approx(30e-6)


def test_thread_stalls_report_the_other_thread(synthetic):
    got = scopes.thread_stalls(synthetic, stretches=[(40.0e-3, 50.0e-3)])
    (th,) = got["threads"]
    assert th["spans"] == {"serve.admission": 1, "serve.lock_wait": 1}
    (adm,) = th["longest"]["serve.admission"]
    assert adm["ms"] == pytest.approx(0.1)
    spans = {n: (ms, c) for n, ms, c in adm["main_spans"]}
    assert spans["serve.batch"] == (pytest.approx(0.07), 2)
    assert set(spans) == {"bench.drain", "serve.batch", "serve.execute"}
    assert [n for n, _, _ in adm["main_other"]] == ["PjitFunction(f)"]
    # a stretch from 40 to 50 us: the first batch on the window's thread,
    # the admission and the lock wait on the other
    (st,) = got["stretches"]
    assert st["at_ms"] == pytest.approx(0.04)
    assert {n for n, _, _ in st["main_spans"]} == {"bench.drain",
                                                    "serve.batch"}
    assert {n: ms for n, ms, _ in st["thread_spans"]} == pytest.approx(
        {"serve.admission": 0.01, "serve.lock_wait": 0.004})


def test_mirror_matches_ring_spans_to_the_trace(synthetic):
    traced = scopes.span_durations(synthetic)
    assert traced["serve.batch"] == pytest.approx([30.0, 40.0])
    ring = [{"name": "serve.batch", "ph": "X", "dur": 40.5},
            {"name": "serve.batch", "ph": "X", "dur": 30.0},
            {"name": "serve.fetch", "ph": "X", "dur": 1.0},
            {"name": "engine.retrace", "ph": "i"}]
    got = trace_cell.mirror(ring, traced)
    assert got["serve.batch"] == {"ring": 2, "trace": 2,
                                  "max_dur_diff_us": pytest.approx(0.5)}
    assert got["serve.fetch"] == {"ring": 1, "trace": 0,
                                  "max_dur_diff_us": None}
    assert "engine.retrace" not in got


def test_per_unit_split():
    red = {"scopes": {"dfep.spread": 0.3, "dfep.grant": 0.1,
                      "run_dfep": 0.05, "engine.sweep": 0.2},
           "enclosed": {"dfep.grant": 0.05, "engine.sweep": 0.1}}
    out = trace_cell.per_unit(red, 0.5, {"dfep.rounds": 100})
    assert out["per_round_ms"] == pytest.approx(
        {"dfep.spread": 3.0, "dfep.grant": 1.0, "units": 100})
    assert out["scoped_share"] == pytest.approx({"dfep.*": 0.8})
    assert out["enclosed_share"] == pytest.approx({"dfep.*": 0.9})
    assert "per_superstep_ms" not in out       # no engine.supersteps


# -- the new metric readers ------------------------------------------------

def _served_ctx(spans: bool):
    from repro import engine as E
    from repro import gserve as G
    from repro import obs
    from repro.core import dfep, graph

    g = graph.watts_strogatz(120, 4, 0.2, seed=3)
    owner, _ = dfep.partition(g, k=4, key=0)
    srv = G.GraphServer(E.Engine(E.compile_plan(g, np.asarray(owner), 4)),
                        g, buckets=(1, 4, 8))
    rec = obs.get()
    rec.reset()
    if spans:
        rec.enable()
    try:
        srv.serve([G.QueryRequest("sssp", params={"source": s})
                   for s in (1, 2, 3)])
        srv.serve([G.QueryRequest("bfs", params={"source": s})
                   for s in range(5)])
    finally:
        rec.disable()
        srv.close()
    ctx = types.SimpleNamespace(events=rec.events(), counters=rec.counters())
    rec.reset()
    return ctx


def test_serve_readers_on_a_small_run():
    ctx = _served_ctx(spans=True)
    fetch = run.reader("serve.fetch_ms")(ctx)
    assert fetch is not None and fetch > 0
    # 3 sssp lanes in a bucket of 4, 5 bfs lanes in a bucket of 8
    assert run.reader("serve.lane_fill_pct")(ctx) == pytest.approx(
        100 * 8 / 12)


def test_serve_readers_without_the_spans():
    ctx = _served_ctx(spans=False)
    assert run.reader("serve.fetch_ms")(ctx) is None
    assert run.reader("serve.lane_fill_pct")(ctx) is None
