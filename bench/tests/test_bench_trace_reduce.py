"""The trace reduction on a small trace recorded on one v5e chip: sssp,
bfs, wcc, pagerank and gcn_layer once each on the program's astroph
stand-in at scale 0.02 (8 partitions by edge id), a 20 ms sleep, a served batch of 8 sssp
queries and one DFEP partition, each inside its ``bench.*`` host span,
all inside ``bench.window``."""
from __future__ import annotations

import gzip
import pathlib
import types

import numpy as np
import pytest

from bench import run, trace_reduce

FIXTURE = pathlib.Path(__file__).with_name("data") / \
    "chip_trace.xplane.pb.gz"


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData

    pd = ProfileData.from_serialized_xspace(
        gzip.decompress(FIXTURE.read_bytes()))
    return trace_reduce.reduce_profile(pd)


def test_busy_time_lies_inside_the_window(reduced):
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    # the 20 ms sleep alone leaves the device idle
    assert reduced["window_s"] - reduced["busy_s"] > 0.02


def test_breakdown_lists_leaf_ops_and_named_gaps(reduced):
    ops = reduced["breakdown"]["device_ops"]
    gaps = reduced["breakdown"]["idle_gaps"]
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert all(isinstance(n, str) and s > 0 for n, s in ops + gaps)
    assert not any(n.startswith("while ") for n, _ in ops)
    names = dict(gaps)
    assert set(names) <= {"bench.engine_run", "bench.idle", "bench.drain",
                          "bench.dfep_partition", "host.other"}
    assert names["bench.idle"] >= 0.019
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(names.values()) == pytest.approx(idle, rel=1e-6)


def test_kernels_are_told_apart_by_their_operands(reduced):
    kinds = {n.split(" ")[0] for n, _ in reduced["ops"]
             if n.startswith("pallas(")}
    assert kinds == {"pallas(s32,f32)", "pallas(s32,s32,f32,f32)",
                     "pallas(f32,f32,s32,s32)"}


def test_short_names():
    name = ("%branch_0_fun.14 = f32[50176,128]{1,0:T(8,128)S(1)} "
            "custom-call(s32[50176,128]{1,0:T(8,128)S(1)} %a, "
            "f32[50176,128]{1,0:T(8,128)S(1)} %b), "
            'custom_call_target="tpu_custom_call"')
    assert trace_reduce.short(name) == "pallas(s32,f32) f32[50176,128]"
    assert trace_reduce.short(
        "%fusion.45 = f32[113664]{0:T(1024)S(1)} fusion(f32[8,49280]"
        "{0,1:T(8,128)S(1)} %x), kind=kLoop") == \
        "fusion f32[113664] fusion.45"


@pytest.mark.parametrize("metric", ["segment_scan_roofline",
                                    "gspmm_roofline"])
def test_roofline_shares_are_shares(reduced, metric):
    from repro import engine as E
    from repro.core import graph as G

    # the graph the fixture was recorded on
    g = G.load_dataset("astroph", scale=0.02, seed=0)
    u, _ = g.as_numpy()
    owner = np.where(np.asarray(g.edge_mask), np.arange(g.e_pad) % 8, -2)
    loop = types.SimpleNamespace(dep=types.SimpleNamespace(u=u),
                                 plan=E.compile_plan(g, owner, 8))
    ctx = types.SimpleNamespace(trace=reduced, loop=loop,
                                peaks=run.peaks_of("TPU v5 lite"))
    share = run.reader(metric)(ctx)
    assert 0 < share <= 100


def test_a_trace_without_the_kernel_reads_nothing(reduced):
    ctx = types.SimpleNamespace(trace=dict(reduced, ops=[]), loop=None,
                                peaks=None)
    assert run.reader("gspmm_roofline")(ctx) is None
