"""The four-chip analytics cell on the CPU at a small size, on four forced
host devices in a subprocess (the main test process sees one): a sound run
is correct, the control is refused, and an altered answer or chips that
skip the cross-chip all-reduces turn ``correct`` false; and the mesh
layer's two readers on made-up readings."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import types

import pytest

from bench import run

CELL = "graph500-s14-k8-mesh4.analytics"

SCRIPT = textwrap.dedent("""
    import json, os, sys, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = ["src", ".", "bench/tests"]
    import pathlib
    import jax
    import repro.compile_cache
    from bench import deploy, run
    from conftest import TINY_SCALE
    from test_bench_batch_runs import _alter_state

    assert len(jax.devices()) == 4
    repro.compile_cache.use_compile_cache = lambda: None
    deploy.OWNER_CACHE = pathlib.Path(tempfile.mkdtemp())
    cell = "%s"
    bench = run.spec()
    w = run.cell_of(bench, cell)
    cfg = deploy.load_config(w["config"])
    cfg["scale"] = TINY_SCALE

    def go(seed, hook=None):
        seen = {}

        def keep(loop):
            seen["loop"] = loop
            if hook is not None:
                hook(loop)

        out = run.run_cell(cell, seed, 1.0, False, bench=bench, config=cfg,
                           mix=deploy.load_traffic(w["traffic"]),
                           devices=jax.devices(), loop_hook=keep)
        return out, seen["loop"]

    out, loop = go(78)
    print("RESULT sound " + json.dumps({
        "correct": out["correct"], "checks": out["checks"],
        "compiles": out["notes"]["compiles_in_window"],
        "metrics": sorted(out["metrics"])}), flush=True)
    loop.plant_control()
    print("RESULT control " + json.dumps(
        {c.name: c.ok for c in loop.checks()}), flush=True)
    out, _ = go(6, _alter_state)
    print("RESULT fault " + json.dumps(
        {"correct": out["correct"], "checks": out["checks"]}), flush=True)

    # the cell's own fault: each chip keeps its own replicas' values, as
    # if the cross-chip all-reduces were left out
    from repro.engine import runtime
    runtime._across = lambda op, x, axis: x
    jax.clear_caches()
    out, _ = go(7)
    print("RESULT no_collectives " + json.dumps(
        {"correct": out["correct"], "checks": out["checks"]}), flush=True)
""" % CELL)


@pytest.fixture(scope="module")
def mesh_runs():
    """One subprocess makes the four runs; {case: what it printed}."""
    root = run.ROOT
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", SCRIPT], env=env, cwd=root,
                       capture_output=True, text=True, timeout=900)
    found = {}
    for line in p.stdout.splitlines():
        if line.startswith("RESULT "):
            _, case, body = line.split(" ", 2)
            found[case] = json.loads(body)
    assert found, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr[-3000:]}"
    return found


def test_sound_run_is_correct(mesh_runs):
    got = mesh_runs["sound"]
    assert got["correct"], got["checks"]
    assert got["compiles"] == []
    assert got["metrics"] == ["analytics_job_ms", "setup_s"]


def test_control_is_refused(mesh_runs):
    assert mesh_runs["control"]["pagerank_gap"] is False


@pytest.mark.parametrize("fault", ["fault", "no_collectives"])
def test_fault_makes_the_run_incorrect(mesh_runs, fault):
    """An altered answer, and chips that never combine their replicas
    across the mesh, both turn ``correct`` false on ``wrong_answers``."""
    got = mesh_runs[fault]
    assert not got["correct"]
    c = got["checks"]["wrong_answers"]
    assert c["value"] > c["limit"]


def _ctx(ops=None, counters=None):
    return types.SimpleNamespace(
        cell=run.cell_of(run.spec(), CELL),
        trace=None if ops is None else {"ops": ops},
        counters=counters or {})


@pytest.mark.parametrize("ops,steps,want", [
    # two chips' all-reduces of 3 and 5 ms and a split async pair, among
    # other leaf operations; 4 chips, 2 supersteps
    ([("all-reduce f32[12507] pmin.21", 3e-3),
      ("all-reduce s32[] pmax.29", 5e-3),
      ("all-reduce-start f32[12507] psum.15", 1e-3),
      ("all-reduce-done f32[12507] psum.15", 7e-3),
      ("fusion f32[436224] fusion.43", 1.0),
      ("pallas(s32,f32) f32[55296,128]", 0.5)], 2, 16e-3 / 4 / 2 * 1e3),
    ([("fusion f32[436224] fusion.43", 1.0)], 2, None),
    ([("all-reduce f32[12507] pmin.21", 3e-3)], 0, None),
    (None, 2, None)])
def test_exchange_ms_reads_the_collectives(ops, steps, want):
    value = run.reader("mesh.exchange_ms")(
        _ctx(ops, {"engine.supersteps": steps}))
    assert value == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("ops,steps,want", [
    # per chip a scalar exchange and its flag in each of 2 supersteps, and
    # a job's final gather as one combined tuple all-reduce; 4 chips
    ([("all-reduce f32[12507] pmin.21", 3e-3),
      ("all-reduce s32[] pmax.29", 5e-3)] * 2 * 4
     + [("all-reduce (f32[12507], s32[12507]) all-reduce.3", 1e-3)] * 4
     + [("fusion f32[436224] fusion.43", 1.0)],
     2, (2 * (12507 * 4 + 4) + 12507 * 8) / 2 / 1024),
    # an async pair counts once; an 8-wide bf16 plane, a pred flag
    ([("all-reduce-start bf16[12507,8] psum.15", 1e-3),
      ("all-reduce-done bf16[12507,8] psum.15", 7e-3),
      ("all-reduce pred[] pmax.2", 1e-3)] * 4,
     1, (12507 * 8 * 2 + 1) / 1024),
    ([("fusion f32[436224] fusion.43", 1.0)], 2, None),
    ([("all-reduce f32[12507] pmin.21", 3e-3)], 0, None),
    (None, 2, None)])
def test_exchange_kb_reads_the_collectives(ops, steps, want):
    value = run.reader("mesh.exchange_kb")(
        _ctx(ops, {"engine.supersteps": steps}))
    assert value == (None if want is None else pytest.approx(want))
