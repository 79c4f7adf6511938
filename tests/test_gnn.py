"""Vector-state supersteps + fused gSpMM: the GNN inference service.

Covers the PR 10 surface end to end:

  * ``StateSpec`` — the declarative per-vertex rank (shape/cold/key),
    typed ``StateError``/``WarmStateError`` on rank mismatches at the
    engine door instead of reshape crashes inside jit;
  * fused Pallas ``gspmm`` vs the XLA ``gspmm_ref`` across combines,
    feature widths and slack (hypothesis padding-invariance property);
  * the F=1 contract: a program lifted to [K, Vmax, 1] hooks finalizes
    BIT-identically to its legacy scalar twin (sssp replica-min path,
    pagerank partial-add path) — vector state is one code path, not a
    parallel implementation;
  * ``gcn_layer`` / ``kge_score`` served oracle-exact through
    StreamSession -> GraphServer across an insert-only stream patch,
    with zero gserve edits (the registry entry carries everything);
  * dense-channel validation at the request door, device-resident plane
    reuse, and both shard_map paths in a forced-8-device subprocess.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.core import algorithms as alg
from repro.core import baselines, dfep, graph
from repro import engine as E
from repro import gserve as G
from repro import stream as S
from repro.engine import kernels
from repro.engine.programs import GCN_F_IN, GCN_F_OUT, KGE_F
from repro.engine.registry import DEFAULT_REGISTRY


def _plan(g, k=4, **kw):
    return E.compile_plan(g, baselines.hash_partition(g, k), k, **kw)


# ---------------------------------------------------------------------------
# StateSpec — the declarative rank
# ---------------------------------------------------------------------------

def test_state_spec_shapes_and_cold():
    s = E.StateSpec()
    assert s.shape(7) == (7,) and s.batch_shape(3, 7) == (3, 7)
    assert s is not E.SCALAR and s == E.SCALAR and s.key() == E.SCALAR.key()
    cold = s.cold(5)
    assert cold.shape == (5,) and np.all(np.isinf(cold))
    v = E.StateSpec(features=4, fill=0.0)
    assert v.shape(7) == (7, 4) and v.batch_shape(3, 7) == (3, 7, 4)
    assert v.cold(5).shape == (5, 4) and not np.any(v.cold(5))
    assert v.key() != s.key()
    assert "[V, 4]" in v.describe() and "scalar" in s.describe()


def test_state_spec_rejects_nonsense():
    with pytest.raises(ValueError, match="positive int"):
        E.StateSpec(features=0)
    with pytest.raises(ValueError, match="positive int"):
        E.StateSpec(features=2.5)
    with pytest.raises(TypeError):
        E.StateSpec(dtype="not-a-dtype")


def test_error_hierarchy():
    # state violations are registry errors, so one except clause at the
    # server door catches the whole family
    assert issubclass(E.StateError, E.RegistryError)
    assert issubclass(E.WarmStateError, E.StateError)
    assert issubclass(E.ChannelError, E.StateError)


def test_warm_state_rank_mismatch_is_typed():
    g = graph.watts_strogatz(80, 4, 0.1, seed=0)
    eng = E.Engine(_plan(g, 2))
    # wrong rank (a [V, 2] block for a scalar program) — same typed error
    # as a wrong vertex count, never a reshape crash inside jit
    with pytest.raises(E.WarmStateError, match="scalar"):
        eng.run(E.WEIGHTED_SSSP, source=jnp.int32(0),
                warm_state=np.zeros((80, 2), np.float32))
    with pytest.raises(E.WarmStateError, match="80 vertices"):
        eng.run(E.WEIGHTED_SSSP, source=jnp.int32(0),
                warm_state=np.zeros(79, np.float32))


# ---------------------------------------------------------------------------
# fused gSpMM kernel vs XLA reference
# ---------------------------------------------------------------------------

def _gspmm_fixture(seed=0, f=8):
    g = graph.watts_strogatz(120, 4, 0.2, seed=seed)
    plan = _plan(g, 4, edge_slack=16, vertex_slack=8)
    rng = np.random.default_rng(seed)
    feats = jnp.asarray(rng.normal(size=(g.n_vertices, f))
                        .astype(np.float32))
    return g, plan, kernels.gather_vertex_channel(plan, feats)


@pytest.mark.parametrize("width", [8, 32, 128])
@pytest.mark.parametrize("combine", ["add", "sum", "max", "mean"])
def test_gspmm_matches_ref(combine, width):
    """The fused kernel against its reference at GNN feature widths; 128
    fills the lane tile."""
    g, plan, local = _gspmm_fixture(f=width)
    got = np.asarray(kernels.gspmm(plan, local, plan.edge_w, combine))
    ref = np.asarray(kernels.gspmm_ref(plan, local, plan.edge_w, combine))
    finite = np.isfinite(ref)
    assert np.allclose(got[finite], ref[finite], atol=1e-5)
    assert np.array_equal(finite, np.isfinite(got))


def test_gspmm_wide_edge_weights():
    """Per-feature edge weights ([K, Emax, F], the kge relation plane
    shape) flow through the same fused kernel as scalar weights."""
    g, plan, local = _gspmm_fixture(seed=3, f=4)
    rng = np.random.default_rng(9)
    w3 = jnp.asarray(rng.normal(size=plan.emask.shape + (4,))
                     .astype(np.float32))
    got = np.asarray(kernels.gspmm(plan, local, w3, "add"))
    ref = np.asarray(kernels.gspmm_ref(plan, local, w3, "add"))
    assert np.allclose(got, ref, atol=1e-5)


def test_gspmm_scalar_feats_rank():
    """Rank-2 features still come back rank-3 with F=1 — one contract."""
    g, plan, local = _gspmm_fixture(f=1)
    got = kernels.gspmm(plan, local[:, :, 0], plan.edge_w, "add")
    assert got.ndim == 3 and got.shape[2] == 1


@given(seed=st.integers(0, 10_000))
@settings(max_examples=8, deadline=None)
def test_gspmm_padding_invariance(seed):
    """Slack slots (the streaming growth region) must be inert: a plan
    compiled with reserved edge/vertex slack aggregates [V, F] planes
    identically to the slack-free plan, and both match the dense numpy
    contraction on live vertices."""
    rng = np.random.default_rng(seed)
    g = graph.watts_strogatz(90 + seed % 17, 4, 0.2, seed=seed % 5)
    owner = baselines.hash_partition(g, 3)
    tight = E.compile_plan(g, owner, 3)
    slacked = E.compile_plan(g, owner, 3, edge_slack=32, vertex_slack=16)
    f = 2 + seed % 7
    feats = rng.normal(size=(g.n_vertices, f)).astype(np.float32)

    outs = []
    for plan in (tight, slacked):
        local = kernels.gather_vertex_channel(plan, jnp.asarray(feats))
        agg = kernels.gspmm(plan, local, plan.edge_w, "add")
        glob = np.zeros((g.n_vertices, f), np.float32)
        k_idx = np.asarray(plan.vmask)
        l2g = np.asarray(plan.local2global)
        a = np.asarray(agg)
        for p in range(a.shape[0]):
            glob[l2g[p][k_idx[p]]] += a[p][k_idx[p]]
        outs.append(glob)
    assert np.allclose(outs[0], outs[1], atol=1e-5)

    u, v = g.as_numpy()
    ew = graph.edge_weights(u, v)
    dense = np.zeros((g.n_vertices, f), np.float32)
    np.add.at(dense, v, feats[u] * ew[:, None])
    np.add.at(dense, u, feats[v] * ew[:, None])
    assert np.allclose(outs[0], dense, atol=1e-4)


# ---------------------------------------------------------------------------
# F=1 lifted hooks == legacy scalar path, bit for bit
# ---------------------------------------------------------------------------

def _lift(base):
    """Clone a scalar program with hooks carrying [K, Vmax, 1] planes."""
    def init(plan, ctx):
        return base.init(plan, ctx)[:, :, None]

    def pre(state, ctx):
        return base.pre(state[:, :, 0], ctx)[:, :, None]

    def apply(old, agg, ctx):
        return base.apply(old[:, :, 0], agg[:, :, 0], ctx)[:, :, None]

    def finalize(glob, present, plan, ctx):
        return base.finalize(glob[:, 0], present, plan, ctx)

    return base._replace(name=f"vec_{base.name}", init=init, pre=pre,
                         apply=apply, finalize=finalize, warm_init=None)


def test_f1_vector_sssp_bit_identical():
    g = graph.watts_strogatz(150, 4, 0.15, seed=1)
    eng = E.Engine(_plan(g, 4))
    scalar = eng.run(E.SSSP, source=jnp.int32(0))
    vec = eng.run(_lift(E.SSSP), source=jnp.int32(0))
    assert np.array_equal(np.asarray(scalar.state), np.asarray(vec.state))
    assert int(scalar.supersteps) == int(vec.supersteps)


def test_f1_vector_pagerank_bit_identical():
    g = graph.watts_strogatz(150, 4, 0.15, seed=1)
    eng = E.Engine(_plan(g, 4))
    scalar = eng.run(E.PAGERANK, max_supersteps=15, degrees=g.degrees())
    vec = eng.run(_lift(E.PAGERANK), max_supersteps=15,
                  degrees=g.degrees())
    assert np.array_equal(np.asarray(scalar.state), np.asarray(vec.state))


# ---------------------------------------------------------------------------
# the served GNN programs
# ---------------------------------------------------------------------------

def test_gcn_layer_oracle():
    g = graph.watts_strogatz(130, 4, 0.2, seed=2)
    eng = E.Engine(_plan(g, 4))
    rng = np.random.default_rng(0)
    x = rng.normal(size=(g.n_vertices, GCN_F_IN)).astype(np.float32)
    w = rng.normal(size=(GCN_F_IN, GCN_F_OUT)).astype(np.float32)
    res = E.engine_gcn_layer(eng, g.degrees(), x, w)
    assert res.state.shape == (g.n_vertices, GCN_F_OUT)
    np.testing.assert_allclose(np.asarray(res.state),
                               alg.reference_gcn_layer(g, x, w), atol=1e-5)


def test_kge_score_oracle():
    g = graph.watts_strogatz(130, 4, 0.2, seed=2)
    eng = E.Engine(_plan(g, 4))
    rng = np.random.default_rng(1)
    ent = rng.normal(size=(g.n_vertices, KGE_F)).astype(np.float32)
    rel = rng.normal(size=(g.e_pad, KGE_F)).astype(np.float32)
    res = E.engine_kge_score(eng, ent, rel)
    assert res.state.shape == (g.n_vertices,)
    np.testing.assert_allclose(np.asarray(res.state),
                               alg.reference_kge_score(g, ent, rel),
                               atol=1e-5)


def test_dense_channel_validated_at_door():
    rng = np.random.default_rng(0)
    x = rng.random((50, GCN_F_IN)).astype(np.float32)
    # wrong rows on the dense weight matrix: rejected at request
    # construction, not deep inside the finalize matmul under jit
    with pytest.raises(ValueError, match="gcn_layer.weight"):
        G.QueryRequest("gcn_layer", params={
            "x": x, "weight": np.zeros((3, GCN_F_OUT), np.float32)})
    # wrong feature width rides the generic channel validation
    with pytest.raises(E.ChannelError, match="feature"):
        G.QueryRequest("gcn_layer", params={
            "x": np.zeros((50, 2), np.float32),
            "weight": np.zeros((GCN_F_IN, GCN_F_OUT), np.float32)})


def test_served_gnn_across_stream_patch():
    """The acceptance path: partition -> engine -> stream patch -> serve,
    oracle-exact on the exact snapshot each answer was served from, with
    the generic registry dispatch (zero gserve branching)."""
    sess = S.StreamSession(graph.watts_strogatz(140, 4, 0.1, seed=3),
                           S.StreamConfig(k=4, chunk_size=32,
                                          drift_threshold=1e9), key=0)
    srv = G.GraphServer.from_session(sess, cache_entries=0)
    rng = np.random.default_rng(5)
    try:
        for phase in range(2):
            if phase:
                n_v = sess.graph().n_vertices
                a = rng.integers(0, n_v, size=6)
                sess.apply(inserts=np.stack([a, (a + 7) % n_v], 1))
            g = sess.graph()
            for name in ("gcn_layer", "kge_score"):
                entry = DEFAULT_REGISTRY.get(name)
                params = {}
                for spec in entry.channel_params:
                    n = {"vertex": g.n_vertices, "edge": g.e_pad,
                         "dense": GCN_F_IN}[spec.channel]
                    params[spec.name] = rng.random((n, spec.features)) \
                        .astype(np.float32)
                out = srv.serve([G.QueryRequest(name, tenant=f"t{i}",
                                                params=params)
                                 for i in range(3)])
                want = entry.oracle(g, **params)
                for r in out:
                    np.testing.assert_allclose(r.value, want,
                                               atol=entry.oracle_atol)
    finally:
        srv.close()


def test_channel_planes_stay_device_resident():
    g = graph.watts_strogatz(100, 4, 0.1, seed=4)
    plan = _plan(g, 4)
    entry = DEFAULT_REGISTRY.get("gcn_layer")
    rng = np.random.default_rng(2)
    params = entry.normalize({
        "x": rng.random((g.n_vertices, GCN_F_IN)).astype(np.float32),
        "weight": rng.random((GCN_F_IN, GCN_F_OUT)).astype(np.float32)})
    before = E.resident_stats()
    first = entry.channel_args(params, plan)
    mid = E.resident_stats()
    second = entry.channel_args(params, plan)
    after = E.resident_stats()
    # same digests: the second dispatch reuses the resident buffers
    assert mid["misses"] - before["misses"] == 2
    assert after["hits"] - mid["hits"] == 2
    assert after["resident_bytes"] > 0
    for k in first:
        assert first[k] is second[k]


# ---------------------------------------------------------------------------
# shard_map paths (forced 8-device host mesh, subprocess)
# ---------------------------------------------------------------------------

MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax, jax.numpy as jnp

    from repro.core import algorithms as alg
    from repro.core import dfep, graph
    from repro import engine as E

    assert len(jax.devices()) == 8
    g = graph.watts_strogatz(300, 6, 0.1, seed=2)
    owner, _ = dfep.partition(g, k=8, key=0, max_rounds=400,
                              stall_rounds=16)
    plan = E.compile_plan(g, np.asarray(owner), 8)
    mesh = jax.make_mesh((8,), ("parts",))
    eng = E.Engine(plan, mesh=mesh)
    rng = np.random.default_rng(0)

    from repro.engine.programs import GCN_F_IN, GCN_F_OUT, KGE_F
    x = rng.normal(size=(g.n_vertices, GCN_F_IN)).astype(np.float32)
    w = rng.normal(size=(GCN_F_IN, GCN_F_OUT)).astype(np.float32)
    r = E.engine_gcn_layer(eng, g.degrees(), x, w)
    np.testing.assert_allclose(np.asarray(r.state),
                               alg.reference_gcn_layer(g, x, w), atol=1e-5)

    ent = rng.normal(size=(g.n_vertices, KGE_F)).astype(np.float32)
    rel = rng.normal(size=(g.e_pad, KGE_F)).astype(np.float32)
    rk = E.engine_kge_score(eng, ent, rel)
    np.testing.assert_allclose(np.asarray(rk.state),
                               alg.reference_kge_score(g, ent, rel),
                               atol=1e-5)

    # sharded == single-device, element for element
    r1 = E.engine_gcn_layer(E.Engine(plan), g.degrees(), x, w)
    np.testing.assert_allclose(np.asarray(r1.state), np.asarray(r.state),
                               atol=1e-6)

    # batched shard_map path with rank-3 state: an F=1 lifted SSSP must
    # match the scalar batched result lane for lane
    base = E.SSSP
    def init(plan, ctx): return base.init(plan, ctx)[:, :, None]
    def pre(state, ctx): return base.pre(state[:, :, 0], ctx)[:, :, None]
    def apply(old, agg, ctx):
        return base.apply(old[:, :, 0], agg[:, :, 0], ctx)[:, :, None]
    def fin(glob, present, plan, ctx):
        return base.finalize(glob[:, 0], present, plan, ctx)
    VEC = base._replace(name="vec_sssp", init=init, pre=pre, apply=apply,
                        finalize=fin, warm_init=None)
    sources = {"source": np.array([0, 7, 42], np.int32)}
    rv = eng.run_batched(VEC, dict(sources))
    rs = eng.run_batched(base, dict(sources))
    assert np.array_equal(np.asarray(rv.state), np.asarray(rs.state))

    # K=8 partitions on a 4-device mesh (2 partition blocks per device)
    mesh4 = jax.make_mesh((4,), ("parts",))
    r4 = E.engine_gcn_layer(E.Engine(plan, mesh=mesh4), g.degrees(), x, w)
    np.testing.assert_allclose(np.asarray(r4.state), np.asarray(r.state),
                               atol=1e-6)
    print("GNN_DIST_OK")
""")


@pytest.mark.slow
def test_gnn_shard_map():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", MESH_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1200,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert "GNN_DIST_OK" in res.stdout, \
        f"stdout:\n{res.stdout}\nstderr:\n{res.stderr[-3000:]}"
