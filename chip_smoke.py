"""Chip smoke run: partition → plan → engine → stream → serve on a TPU.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the shard_map mesh phase

The graph is the ``astroph`` profile at scale 1.0 (the published 17,903
vertices; the synthetic preferential-attachment generator, seeded, gives
its own edge count, about the published 196,972).  The one-chip run
partitions it by DFEP into k=8; the mesh run partitions it by edge hash
(k=8, two partitions per chip), so that it runs the replica exchange and
nothing else.  The larger ``dblp`` profile is out of reach for now: DFEP
at its published size did not finish within 1,190 s on one v5e chip.
Every result is checked against the whole-graph oracles of
``repro.core.algorithms`` (through the program registry for served
queries).  The script exits non-zero on any
platform other than ``tpu`` and on any mismatch; sizes and phase timings go
to earlier lines, and the last line of stdout is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

DATASET, SCALE, K, SEED = "astroph", 1.0, 8, 0
GCN_SEED = 7


def log(msg: str) -> None:
    print(msg, flush=True)


def timed(fn, *a, **kw):
    """(result, seconds) with the result synced to the host."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*a, **kw))
    return out, time.perf_counter() - t0


def exact(name: str, got, want) -> None:
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or not np.array_equal(got, want):
        bad = np.flatnonzero(got.reshape(-1) != want.reshape(-1)).size \
            if got.shape == want.shape else "shape"
        raise AssertionError(f"{name}: mismatch vs oracle ({bad} entries)")


def close(name: str, got, want, *, rtol: float = 1e-7,
          atol: float = 0.0) -> None:
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=rtol, atol=atol, err_msg=name)


# ---------------------------------------------------------------------------
# (b) graph → DFEP → plan
# ---------------------------------------------------------------------------

def build(dataset: str = DATASET, scale: float = SCALE, k: int = K, *,
          use_dfep: bool = True):
    from repro.core import baselines, dfep, graph
    from repro import engine as E

    t0 = time.perf_counter()
    g = graph.load_dataset(dataset, scale=scale, seed=SEED)
    t_graph = time.perf_counter() - t0
    if use_dfep:
        (owner, info), t_part = timed(dfep.partition, g, k=k, key=SEED)
        how = f"dfep rounds={info['rounds']} finalized={info['finalized']}"
    else:
        owner, t_part = timed(baselines.hash_partition, g, k)
        how = "hash partition"
    owner = np.asarray(owner)
    t0 = time.perf_counter()
    plan = E.compile_plan(g, owner, k)
    t_plan = time.perf_counter() - t0
    spec = graph.DATASETS[dataset]
    log(f"[build] {dataset} scale={scale}: |V|={g.n_vertices} "
        f"|E|={g.n_edges} (published |V|={spec.v_published} "
        f"|E|={spec.e_published}) k={k}")
    log(f"[build] {how} e_max={plan.e_max} v_max={plan.v_max} "
        f"replication_factor={plan.replication_factor()}")
    log(f"[build] seconds: graph={t_graph} partition={t_part} "
        f"plan={t_plan}")
    return g, owner, plan


# ---------------------------------------------------------------------------
# (c) engine programs on the Pallas path
# ---------------------------------------------------------------------------

def gcn_inputs(g):
    from repro.engine.programs import GCN_F_IN, GCN_F_OUT
    rng = np.random.default_rng(GCN_SEED)
    x = rng.normal(size=(g.n_vertices, GCN_F_IN)).astype(np.float32)
    w = rng.normal(size=(GCN_F_IN, GCN_F_OUT)).astype(np.float32)
    return x, w


def engine_cases(g):
    """(name, program, kwargs, oracle, check) for the four programs."""
    import jax.numpy as jnp
    from repro import engine as E
    from repro.core import algorithms as alg

    x, w = gcn_inputs(g)
    deg = g.degrees()
    return [
        ("sssp", E.SSSP, {"source": jnp.int32(0)},
         lambda: alg.reference_sssp(g, 0)[0], exact),
        ("wcc", E.WCC, {}, lambda: alg.reference_cc(g)[0], exact),
        ("pagerank", E.PAGERANK,
         {"max_supersteps": 30, "degrees": deg},
         lambda: alg.reference_pagerank(g, iters=30),
         lambda n, a, b: close(n, a, b, rtol=1e-5)),
        ("gcn_layer", E.GCN_LAYER,
         {"degrees": deg, "x": jnp.asarray(x), "weight": jnp.asarray(w)},
         lambda: alg.reference_gcn_layer(g, x, w),
         lambda n, a, b: close(n, a, b, atol=1e-5)),
    ]


def kernel_calls(hlo: str) -> int:
    """Pallas kernels compiled by Mosaic appear as ``tpu_custom_call``s."""
    return hlo.count("custom_call_target=\"tpu_custom_call\"")


def engine_phase(g, plan, *, require_kernels: bool = True) -> None:
    from repro import engine as E

    eng = E.Engine(plan)
    for name, prog, kw, oracle, check in engine_cases(g):
        first, t_first = timed(lambda: eng.run(prog, **kw).state)
        warm, t_warm = timed(lambda: eng.run(prog, **kw).state)
        want, t_ref = timed(oracle)
        check(name, first, want)
        exact(f"{name} (repeat)", warm, first)
        hlo = eng.lower_hlo(prog, **kw)
        n_kernels = kernel_calls(hlo)
        log(f"[engine] {name}: ok, compile+first={t_first} warm={t_warm} "
            f"oracle={t_ref} tpu_custom_calls={n_kernels}")
        if require_kernels and n_kernels == 0:
            raise AssertionError(
                f"{name}: no tpu_custom_call in the compiled executable — "
                "the Pallas kernels did not compile for the chip")


# ---------------------------------------------------------------------------
# (d) stream patch without retrace
# ---------------------------------------------------------------------------

def update_batch(g, frac: float = 0.01, seed: int = SEED):
    """About ``frac``·|E| updates: half deletions of live edges, half
    insertions of random vertex pairs (deletions free the slots the
    insertions then claim)."""
    rng = np.random.default_rng(seed)
    u, v = g.as_numpy()
    n = max(1, int(frac * g.n_edges) // 2)
    idx = rng.choice(g.n_edges, size=n, replace=False)
    deletes = np.stack([u[idx], v[idx]], 1)
    inserts = rng.integers(0, g.n_vertices, size=(n, 2))
    return inserts, deletes


def check_stream_oracles(sess, tag: str) -> None:
    from repro import engine as E
    from repro.core import algorithms as alg

    g = sess.graph()
    eng = sess.engine
    for name, run, oracle, check in [
        ("sssp", lambda: E.engine_sssp(eng, 0).state,
         lambda: alg.reference_sssp(g, 0)[0], exact),
        ("wcc", lambda: E.engine_wcc(eng).state,
         lambda: alg.reference_cc(g)[0], exact),
        ("pagerank", lambda: E.engine_pagerank(eng, g.degrees(), 30).state,
         lambda: alg.reference_pagerank(g, iters=30),
         lambda n, a, b: close(n, a, b, rtol=1e-5)),
    ]:
        got, t = timed(run)
        check(f"stream {tag} {name}", got, oracle())
        log(f"[stream] {tag} {name}: ok, seconds={t}")


def stream_phase(g, owner):
    from repro import stream as S
    from repro.engine import runtime

    t0 = time.perf_counter()
    sess = S.StreamSession(g, S.StreamConfig(k=K), owner=owner)
    log(f"[stream] session: e_max={sess.plan.e_max} v_max={sess.plan.v_max}"
        f" seconds={time.perf_counter() - t0}")
    check_stream_oracles(sess, "before")
    traces, epoch = runtime.TRACE_COUNTER["run_loop"], sess.epoch
    inserts, deletes = update_batch(g)
    t0 = time.perf_counter()
    stats = sess.apply(inserts=inserts, deletes=deletes)
    log(f"[stream] apply {len(inserts)} inserts + {len(deletes)} deletes: "
        f"seconds={time.perf_counter() - t0} rf={stats.get('rf')} "
        f"|E| now {sess.graph().n_edges}")
    check_stream_oracles(sess, "after")
    if sess.epoch != epoch:
        raise AssertionError("the update batch forced a compaction epoch")
    if runtime.TRACE_COUNTER["run_loop"] != traces:
        raise AssertionError(
            f"the plan patch retraced the superstep loop "
            f"({traces} -> {runtime.TRACE_COUNTER['run_loop']})")
    log("[stream] no retrace across the patch")
    return sess


# ---------------------------------------------------------------------------
# (e) served queries against the snapshot they were served from
# ---------------------------------------------------------------------------

def serve_requests(n_vertices: int, seed: int) -> list:
    from repro import gserve as G
    rng = np.random.default_rng(seed)
    tenants = ("alice", "bob", "carol", "dave")
    reqs = []
    for i in range(32):
        kind = ("sssp", "bfs", "wsssp", "wcc", "pagerank")[i % 5]
        params = {}
        if kind in ("sssp", "bfs", "wsssp"):
            params = {"source": int(rng.integers(0, n_vertices))}
        elif kind == "pagerank":
            params = {"iters": 20}
        reqs.append(G.QueryRequest(kind, tenant=tenants[i % 4],
                                   params=params))
    return reqs


def check_served(results, snapshots: dict, oracles: dict) -> None:
    from repro.engine.registry import get_program

    for r in results:
        if r.error is not None:
            raise AssertionError(f"request {r.request.id}: {r.error}")
        g = snapshots[r.fingerprint]
        entry = get_program(r.request.kind)
        key = (r.fingerprint, r.request.kind,
               tuple(sorted(r.request.params.items())))
        if key not in oracles:
            oracles[key] = np.asarray(entry.oracle(g, **r.request.params))
        want = oracles[key]
        name = f"served {r.request.kind} {r.request.params}"
        if entry.oracle_atol == 0.0:
            exact(name, r.value, want)
        else:   # float partial sums: relative agreement, as in (c)
            close(name, r.value, want, rtol=1e-5, atol=0.0)


def serve_phase(sess) -> None:
    from repro import gserve as G

    srv = G.GraphServer.from_session(sess, buckets=(1, 8))
    try:
        g0 = sess.graph()
        snapshots, oracles = {g0.fingerprint(): g0}, {}
        results, t = timed(lambda: srv.serve(
            serve_requests(g0.n_vertices, seed=1)))
        check_served(results, snapshots, oracles)
        log(f"[serve] {len(results)} requests: ok, seconds={t} "
            f"version={results[0].version}")
        u, v = g0.as_numpy()
        sess.apply(deletes=np.array([[u[0], v[0]]]))
        g1 = sess.graph()
        snapshots[g1.fingerprint()] = g1
        again, t = timed(lambda: srv.serve(
            serve_requests(g1.n_vertices, seed=1)))
        check_served(again, snapshots, oracles)
        if any(r.from_cache for r in again):
            raise AssertionError("a result cached before the deletion was "
                                 "served after it")
        if any(r.version <= results[0].version for r in again):
            raise AssertionError("results after the deletion were served "
                                 "from the old plan")
        st = srv.stats()
        log(f"[serve] after one deletion {len(again)} requests: ok, "
            f"seconds={t} version={again[0].version} "
            f"p50={st['latency_p50_s']} p99={st['latency_p99_s']}")
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# --chips 4: the shard_map replica exchange over a 1-D mesh
# ---------------------------------------------------------------------------

def check_spread(placed, devices) -> None:
    """Every plan leaf is split over all mesh devices, K/D rows each."""
    import jax
    want = set(devices)
    for leaf in jax.tree_util.tree_leaves(placed):
        shards = leaf.addressable_shards
        if {s.device for s in shards} != want:
            raise AssertionError(f"plan leaf {leaf.shape} sits on "
                                 f"{sorted(str(s.device) for s in shards)}")
        rows = {s.data.shape[0] for s in shards}
        if rows != {leaf.shape[0] // len(devices)}:
            raise AssertionError(f"plan leaf {leaf.shape} split into rows "
                                 f"{rows}")


def mesh_phase(g, plan, devices) -> None:
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro import engine as E
    from repro.core import algorithms as alg

    mesh = Mesh(np.array(devices), ("parts",))
    one, sharded = E.Engine(plan), E.Engine(plan, mesh=mesh)
    check_spread(sharded._sharded_plan(), devices)
    log(f"[mesh] plan leaves spread over {len(devices)} devices "
        f"({plan.k // len(devices)} partitions each)")
    sources = jnp.asarray([0, 1, 2, 3, 5, 8, 13, 21], jnp.int32)
    cases = [
        ("sssp", lambda e: e.run(E.SSSP, source=jnp.int32(0)).state,
         lambda: alg.reference_sssp(g, 0)[0], exact),
        ("pagerank", lambda e: e.run(E.PAGERANK, max_supersteps=30,
                                     degrees=g.degrees()).state,
         lambda: alg.reference_pagerank(g, iters=30),
         lambda n, a, b: close(n, a, b, rtol=1e-5)),
        ("batched sssp",
         lambda e: e.run_batched(E.SSSP, {"source": sources}).state,
         lambda: np.stack([np.asarray(alg.reference_sssp(g, s)[0])
                           for s in np.asarray(sources)]), exact),
    ]
    for name, run, oracle, check in cases:
        got_mesh, t_mesh = timed(run, sharded)
        got_one, t_one = timed(run, one)
        want = oracle()
        check(f"mesh {name} vs oracle", got_mesh, want)
        check(f"mesh {name} vs one device", got_mesh, got_one)
        log(f"[mesh] {name}: ok, mesh compile+first={t_mesh} "
            f"one-device compile+first={t_one}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the shard_map mesh phase over four "
                         "local TPU devices")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    count = len(devices)
    log(f"devices: {devices}")
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    devices = devices[:args.chips]

    from repro.compile_cache import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")
    t0 = time.perf_counter()
    g, owner, plan = build(use_dfep=args.chips == 1)
    if args.chips == 4:
        mesh_phase(g, plan, devices)
    else:
        engine_phase(g, plan)
        sess = stream_phase(g, owner)
        serve_phase(sess)
    log(f"total seconds={time.perf_counter() - t0}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
