"""Mesh-backed serving benchmark: the GraphServer over a shard_map engine.

The ROADMAP's open serving point: ``fig_serve`` measures the single-device
path, while the engine has served batched queries over shard_map since
PR 3.  This figure runs the *same* micro-batched serving flow with the
partitions sharded over a forced 8-device host mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) and reports qps +
latency for the mesh path next to the single-device path on the identical
graph/plan/queries.

On the CPU the device-count flag must be set before jax is imported, so
``main`` re-executes this module in a subprocess with the flag in the
environment (the same pattern as tests/test_engine_distributed.py).  On a
TPU host the process that already holds the chips runs the figure itself
over ``jax.devices()``: a child could not reach a chip its parent holds.
Either way the run emits ``BENCH_serve_mesh.json`` through the shared
OUT_DIR machinery.

On a host CPU the 8 "devices" are one physical core time-sliced, so
mesh qps is *not* expected to beat single-device here — the record holds
the collective-bearing serving path to a perf line (it regresses if the
shard_map dispatch stops working or slows down disproportionately) and
documents occupancy/batch shape parity between the two paths.
"""
from __future__ import annotations

import os
import subprocess
import sys
import time

_INNER_ENV = "REPRO_SERVE_MESH_INNER"


def _queries(rng, n_v: int, n: int) -> list:
    from repro import gserve as G
    return [G.QueryRequest("sssp", tenant=f"t{i % 8}",
                           params={"source": int(rng.integers(0, n_v))})
            for i in range(n)]


def _serve_point(eng, g, reqs, bucket: int, mode: str) -> dict:
    import numpy as np
    from repro import gserve as G
    srv = G.GraphServer(eng, g, buckets=(bucket,), cache_entries=0)
    t0 = time.time()
    srv.serve(_queries(np.random.default_rng(99), g.n_vertices,
                       min(bucket, len(reqs))))
    warmup_s = time.time() - t0
    srv.metrics.reset()
    t_all = time.time()
    for r in reqs:
        srv.submit(r)
    srv.drain()
    wall = time.time() - t_all
    st = srv.stats()
    srv.close()
    return {"mode": mode, "bucket": bucket, "n_queries": len(reqs),
            "qps": round(len(reqs) / wall, 2),
            "p50_s": st["latency_p50_s"], "p99_s": st["latency_p99_s"],
            "warmup_s": round(warmup_s, 3), "batches": st["batches"],
            "mean_batch_occupancy": st["mean_batch_occupancy"],
            "pad_waste_frac": st["pad_waste_frac"]}


def _inner(devices) -> None:
    import numpy as np
    from jax.sharding import Mesh

    from repro.core import dfep, graph
    from repro import engine as E

    from .common import SCALE, emit_json

    n_dev = len(devices)
    k, n_queries, bucket = 8, 32, 16
    assert k % n_dev == 0, f"k={k} partitions cannot split over {n_dev} devices"
    g = graph.load_dataset("email-enron", scale=SCALE, seed=0)
    owner, _ = dfep.partition(g, k=k, key=0)
    plan = E.compile_plan(g, np.asarray(owner), k)
    mesh = Mesh(np.array(devices), ("parts",))
    # identical query streams (same seed), fresh request ids per server
    reqs_a = _queries(np.random.default_rng(0), g.n_vertices, n_queries)
    reqs_b = _queries(np.random.default_rng(0), g.n_vertices, n_queries)

    rows = [
        _serve_point(E.Engine(plan), g, reqs_a, bucket, "single-device"),
        _serve_point(E.Engine(plan, mesh=mesh), g, reqs_b, bucket,
                     f"mesh-{n_dev}dev"),
    ]
    # the two paths must agree on everything but wall-clock
    assert rows[0]["batches"] == rows[1]["batches"]
    mesh_row = rows[1]
    emit_json("BENCH_serve_mesh", {
        "dataset": "email-enron", "scale": SCALE, "k": k,
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "n_vertices": g.n_vertices, "n_edges": g.n_edges,
        "n_devices": n_dev, "n_queries": n_queries, "bucket": bucket,
        "rows": rows,
        "mesh_qps": mesh_row["qps"],
        "mesh_mean_batch_occupancy": mesh_row["mean_batch_occupancy"],
    })


def main() -> None:
    import jax
    if os.environ.get(_INNER_ENV) == "1":
        n_dev = len(jax.devices())
        assert n_dev == 8, \
            f"expected the forced 8-device host mesh, got {n_dev}"
        _inner(jax.devices())
        return
    if jax.default_backend() == "tpu":
        _inner(jax.devices())
        return
    env = dict(os.environ)
    env[_INNER_ENV] = "1"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    res = subprocess.run([sys.executable, "-m", "benchmarks.fig_serve_mesh"],
                         env=env, cwd=root, timeout=1800)
    if res.returncode != 0:
        raise RuntimeError(
            f"mesh serving subprocess failed (exit {res.returncode})")


if __name__ == "__main__":
    main()
