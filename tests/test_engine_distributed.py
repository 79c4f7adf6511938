"""Engine shard_map path: partitions sharded over an 8-device host mesh in a
subprocess (same pattern as test_distributed.py), checked against the
whole-graph oracles and the single-device fallback; the analytics
programs on the platform's kernels over 4 devices, 2 partitions each; and
the batched serving path over 4 devices against single dispatches."""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax, jax.numpy as jnp

    from repro.core import algorithms as alg
    from repro.core import baselines, dfep, graph, metrics
    from repro import engine as E

    assert len(jax.devices()) == 8
    g = graph.watts_strogatz(300, 6, 0.1, seed=2)
    owner, _ = dfep.partition(g, k=8, key=0, max_rounds=400, stall_rounds=16)
    plan = E.compile_plan(g, np.asarray(owner), 8)
    mesh = jax.make_mesh((8,), ("parts",))
    eng = E.Engine(plan, mesh=mesh)

    r = E.engine_sssp(eng, 0)
    ref, ref_rounds = alg.reference_sssp(g, 0)
    assert np.array_equal(np.asarray(r.state), np.asarray(ref)), "sssp"
    assert int(r.supersteps) <= int(ref_rounds)

    rw = E.engine_wcc(eng)
    refc, _ = alg.reference_cc(g)
    assert np.array_equal(np.asarray(rw.state), np.asarray(refc)), "wcc"

    rp = E.engine_pagerank(eng, g.degrees(), iters=20)
    refp = alg.reference_pagerank(g, iters=20)
    np.testing.assert_allclose(np.asarray(rp.state), np.asarray(refp),
                               atol=1e-5)

    # sharded == single-device fallback, superstep for superstep
    r1 = E.engine_sssp(E.Engine(plan), 0)
    assert int(r1.supersteps) == int(r.supersteps)
    assert np.array_equal(np.asarray(r1.state), np.asarray(r.state))

    # measured replica-exchange volume == combinatorial MESSAGES
    m = metrics.evaluate(g, np.asarray(owner), 8, compute_gain=False)
    assert plan.exchange_per_superstep() == m.messages, \
        (plan.exchange_per_superstep(), m.messages)
    print("exchange/superstep:", m.messages,
          "total:", r.total_exchanged, "supersteps:", int(r.supersteps))

    # K=8 partitions on a 4-device mesh (2 partition blocks per device)
    mesh4 = jax.make_mesh((4,), ("parts",))
    r4 = E.engine_sssp(E.Engine(plan, mesh=mesh4), 3)
    ref4, _ = alg.reference_sssp(g, 3)
    assert np.array_equal(np.asarray(r4.state), np.asarray(ref4)), "k8d4"
    print("ENGINE_DIST_OK")
""")


BATCHED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax, jax.numpy as jnp

    from repro.core import algorithms as alg
    from repro.core import dfep, graph
    from repro import engine as E

    assert len(jax.devices()) == 8
    g = graph.watts_strogatz(300, 6, 0.1, seed=2)
    owner, _ = dfep.partition(g, k=8, key=0, max_rounds=400, stall_rounds=16)
    plan = E.compile_plan(g, np.asarray(owner), 8)
    mesh = jax.make_mesh((8,), ("parts",))
    eng = E.Engine(plan, mesh=mesh)

    # batched multi-source SSSP through the shard_map superstep: partitions
    # stay sharded over the mesh, the batch axis is vmapped inside the body
    sources = [0, 3, 7, 11, 42, 111]
    res = E.multi_source_sssp(eng, sources)
    assert res.state.shape == (len(sources), g.n_vertices)
    for i, s in enumerate(sources):
        ref, _ = alg.reference_sssp(g, s)
        assert np.array_equal(np.asarray(res.state[i]), np.asarray(ref)), s

    # identical to the single-device batched fallback, lane for lane
    r1 = E.multi_source_sssp(E.Engine(plan), sources)
    assert np.array_equal(np.asarray(r1.state), np.asarray(res.state))
    assert np.array_equal(np.asarray(r1.supersteps), np.asarray(res.supersteps))

    # non-blocking dispatch on the mesh path settles to the same answer
    pend = eng.dispatch_batched(E.SSSP, {"source": jnp.asarray([5, 9], jnp.int32)})
    out = pend.result()
    for i, s in enumerate((5, 9)):
        ref, _ = alg.reference_sssp(g, s)
        assert np.array_equal(np.asarray(out.state[i]), np.asarray(ref)), s

    # K=8 partitions on a 4-device mesh (2 partition blocks per device)
    mesh4 = jax.make_mesh((4,), ("parts",))
    r4 = E.multi_source_sssp(E.Engine(plan, mesh=mesh4), [1, 2])
    for i, s in enumerate((1, 2)):
        ref, _ = alg.reference_sssp(g, s)
        assert np.array_equal(np.asarray(r4.state[i]), np.asarray(ref)), s
    print("ENGINE_DIST_BATCHED_OK")
""")


CHANNEL_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax, jax.numpy as jnp

    from repro.core import algorithms as alg
    from repro.core import dfep, graph
    from repro.core.graph import edge_weights
    from repro import engine as E

    assert len(jax.devices()) == 8
    g = graph.watts_strogatz(300, 6, 0.1, seed=2)
    owner, _ = dfep.partition(g, k=8, key=0, max_rounds=400, stall_rounds=16)
    plan = E.compile_plan(g, np.asarray(owner), 8)
    mesh = jax.make_mesh((8,), ("parts",))
    eng = E.Engine(plan, mesh=mesh)

    # vertex property channels on the sharded superstep: the replicated
    # [V, F] plane is gathered partition-locally INSIDE the shard_map body
    rng = np.random.default_rng(0)
    labels = rng.integers(0, 40, size=g.n_vertices).astype(np.float32)
    r = E.engine_label_propagation(eng, labels)
    assert np.array_equal(np.asarray(r.state),
                          alg.reference_label_propagation(g, labels)), "lp"

    p = rng.random(g.n_vertices).astype(np.float32); p /= p.sum()
    rp = E.engine_personalized_pagerank(eng, g.degrees(), p, iters=12)
    np.testing.assert_allclose(
        np.asarray(rp.state),
        alg.reference_personalized_pagerank(g, p, iters=12), atol=1e-5)

    # K=8 on a 4-device mesh (2 partition blocks per device)
    mesh4 = jax.make_mesh((4,), ("parts",))
    r4 = E.engine_label_propagation(E.Engine(plan, mesh=mesh4), labels)
    assert np.array_equal(np.asarray(r4.state), np.asarray(r.state)), "k8d4"

    # edge property channel on the BATCHED shard_map path: the [E_pad, F]
    # plane rides the replicated kwargs, sources ride the vmapped batch
    INF = jnp.float32(jnp.inf)
    def prepare(plan, kw):
        return {"source": kw["source"],
                "w": E.gather_edge_channel(plan, kw["weights"])[:, :, 0]}
    def init(plan, ctx):
        hit = plan.vmask & (plan.local2global == ctx["source"])
        return jnp.where(hit, 0.0, INF)
    def fin(glob, present, plan, ctx):
        iota = jnp.arange(plan.n_vertices)
        return jnp.where(present, glob,
                         jnp.where(iota == ctx["source"], 0.0, INF))
    CW = E.EdgeProgram(name="cwsssp", mode="replica", combine="min",
        prepare=prepare, init=init, pre=lambda s, c: s,
        apply=lambda o, a, c: jnp.minimum(o, a), finalize=fin,
        local_fixpoint=True, edge=lambda m, plan, ctx: m + ctx["w"])
    u, v = g.as_numpy()
    w = np.zeros(g.e_pad, np.float32)
    w[:len(u)] = edge_weights(u, v)
    rb = eng.run_batched(CW, {"source": np.array([1, 7], np.int32)},
                         weights=w)
    for i, s in enumerate((1, 7)):
        assert np.array_equal(np.asarray(rb.state[i]),
                              alg.reference_weighted_sssp(g, s)), s
    print("ENGINE_DIST_CHANNELS_OK")
""")


# The five programs of the analytics mix, k = 8 on 4 devices (the
# four-chip deployment's layout), each on the sharded path with the
# kernels the platform runs: checked against the plain references and the
# single-device engine.  Prints one line per program.
ANALYTICS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np
    import jax, jax.numpy as jnp

    from repro.core import algorithms as alg
    from repro.core import dfep, graph
    from repro import engine as E

    assert len(jax.devices()) == 4
    g = graph.watts_strogatz(300, 6, 0.1, seed=2)
    owner, _ = dfep.partition(g, k=8, key=0, max_rounds=400, stall_rounds=16)
    plan = E.compile_plan(g, np.asarray(owner), 8)
    mesh = jax.make_mesh((4,), ("parts",))
    eng, one = E.Engine(plan, mesh=mesh), E.Engine(plan)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(g.n_vertices, 8)).astype(np.float32)
    w = rng.normal(size=(8, 4)).astype(np.float32)
    deg = g.degrees()
    cases = {
        "sssp": (E.SSSP, {"source": jnp.int32(3)},
                 np.asarray(alg.reference_sssp(g, 3)[0]), 0.0),
        "bfs": (E.BFS, {"source": jnp.int32(3)},
                alg.reference_bfs(g, 3), 0.0),
        "wcc": (E.WCC, {}, np.asarray(alg.reference_cc(g)[0]), 0.0),
        "pagerank": (E.PAGERANK, {"max_supersteps": 30, "degrees": deg},
                     np.asarray(alg.reference_pagerank(g, iters=30)), 1e-5),
        "gcn_layer": (E.GCN_LAYER, {"degrees": deg, "x": x, "weight": w},
                      alg.reference_gcn_layer(g, x, w), 1e-5),
    }
    kernels = {"gcn_layer": ("gspmm", "masked_update")}
    for name, (prog, kw, want, atol) in cases.items():
        got = np.asarray(eng.run(prog, **kw).state)
        alone = np.asarray(one.run(prog, **kw).state)
        steps = kw.pop("max_supersteps", None)
        text = eng.lower_hlo(prog, max_supersteps=steps, **kw)
        ran = all(k in text for k in kernels.get(
            name, ("segment_scan", "masked_update")))
        exact = atol == 0.0
        ok = (ran and got.shape == want.shape
              and (np.array_equal(got, want) if exact
                   else np.allclose(got, want, atol=atol))
              and (np.array_equal(got, alone) if exact
                   else np.allclose(got, alone, atol=atol)))
        print("MESH_KERNELS", name, "ok" if ok else "FAILED", ran,
              float(np.max(np.abs(got - want), initial=0.0)), flush=True)
""")


# The serving path over a mesh: dispatch_batched with k = 8 partitions on
# 4 devices (the batched shard_map body), for each batchable program, cold
# and warm, on the Graph 500 Kronecker graph.  Each lane must equal one
# single-device dispatch of its source.  Prints one line per case.
BATCHED_MESH_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, "tests")
    import numpy as np
    import jax, jax.numpy as jnp

    from repro.core import dfep
    from repro import engine as E
    from kronecker_graph import graph500

    assert len(jax.devices()) == 4
    g = graph500()
    owner, _ = dfep.partition(g, k=8, key=0, max_rounds=400, stall_rounds=16)
    plan = E.compile_plan(g, np.asarray(owner), 8)
    mesh = jax.make_mesh((4,), ("parts",))
    eng, one = E.Engine(plan, mesh=mesh), E.Engine(plan)
    sources = np.random.default_rng(8).choice(
        g.n_vertices, 8, replace=False).astype(np.int32)
    progs = {"sssp": E.SSSP, "bfs": E.BFS, "wsssp": E.WEIGHTED_SSSP}
    for name, prog in progs.items():
        cold = [one.dispatch(prog, source=jnp.int32(s)).result()
                for s in sources]
        # warm: exact rows on even lanes, cold rows on odd ones
        warm = np.stack([np.asarray(r.state) for r in cold])
        warm[1::2] = prog.state.fill
        for start, prev in (("cold", None), ("warm", warm)):
            got = eng.dispatch_batched(prog, {"source": sources},
                                       warm_state=prev).result()
            want = cold if prev is None else [
                one.dispatch(prog, source=jnp.int32(s),
                             warm_state=prev[i]).result()
                for i, s in enumerate(sources)]
            ok = all(np.array_equal(np.asarray(got.state[i]),
                                    np.asarray(w.state))
                     for i, w in enumerate(want))
            print("MESH_BATCHED", name, start, "ok" if ok else "FAILED",
                  flush=True)
""")


def _run_subprocess(script: str, marker: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=1200,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert marker in res.stdout, \
        f"stdout:\n{res.stdout}\nstderr:\n{res.stderr[-3000:]}"
    return res.stdout


@pytest.mark.slow
def test_engine_shard_map():
    _run_subprocess(SCRIPT, "ENGINE_DIST_OK")


@pytest.mark.slow
def test_engine_shard_map_batched():
    """run_batched on a mesh: the lifted single-device restriction."""
    _run_subprocess(BATCHED_SCRIPT, "ENGINE_DIST_BATCHED_OK")


@pytest.mark.slow
def test_engine_shard_map_channels():
    """Property channels on both shard_map paths: vertex planes through
    dispatch, an edge plane through dispatch_batched."""
    _run_subprocess(CHANNEL_SCRIPT, "ENGINE_DIST_CHANNELS_OK")


@pytest.fixture(scope="module")
def mesh_analytics():
    """One subprocess runs all five programs; each test reads its line."""
    return _run_subprocess(ANALYTICS_SCRIPT, "MESH_KERNELS")


@pytest.mark.slow
@pytest.mark.parametrize("program", ["sssp", "bfs", "wcc", "pagerank",
                                     "gcn_layer"])
def test_engine_shard_map_runs_the_platform_kernels(mesh_analytics,
                                                    program):
    """k = 8 over 4 devices on the kernels the platform runs (interpreted
    here): exact for sssp, bfs and wcc, 1e-5 for pagerank and the GCN
    layer, against the references and the single-device engine."""
    line = next((ln for ln in mesh_analytics.splitlines()
                 if ln.startswith(f"MESH_KERNELS {program} ")), None)
    assert line is not None, mesh_analytics
    assert line.split()[2] == "ok", line


@pytest.fixture(scope="module")
def mesh_batched():
    """One subprocess runs every batched case; each test reads its line."""
    return _run_subprocess(BATCHED_MESH_SCRIPT, "MESH_BATCHED")


@pytest.mark.parametrize("start", ["cold", "warm"])
@pytest.mark.parametrize("program", ["sssp", "bfs", "wsssp"])
def test_engine_shard_map_batched_matches_single_dispatch(mesh_batched,
                                                          program, start):
    """The serving path over a mesh: k = 8 over 4 devices, a micro-batch
    of 8 sources, exactly one single-device dispatch per source."""
    line = next((ln for ln in mesh_batched.splitlines()
                 if ln.startswith(f"MESH_BATCHED {program} {start} ")), None)
    assert line is not None, mesh_batched
    assert line.split()[3] == "ok", line
