"""GNN inference benchmark: fused Pallas gSpMM vs the XLA reference paths,
plus the served vector-state programs.

Two sweeps:

  1. **kernel** — for F in {8, 32, 128}, one fused-Pallas ``gspmm``
     dispatch over a ``[K, Vmax, F]`` feature block against two reference
     executions of the same contraction:

       * ``colwise_ref`` — the **XLA reference path**: F scalar-plane
         gather/scatter passes, one per feature column.  This is the only
         execution shape the pre-``StateSpec`` API could express (every
         per-vertex plane was rank-1), so it is the baseline the
         vector-state redesign replaces.  ``speedup_vs_ref`` /
         ``speedup_f128`` gate against it (floor 1.5 in tolerances.json).
       * ``batched_ref`` — rank-3 ``gspmm_ref`` (gather, materialise the
         weighted message stream, scatter segment-sum in one XLA program).
         Diagnostic only: on the CPU backend the Pallas kernel is
         interpreted, and interpret-mode wall-clock is not device performance
         (kernel_bench.py states the same caveat) — on CPU, XLA's native
         scatter wins; the fused kernel exists for the lane-tiled TPU
         lowering.

     Parity between all three is asserted at 1e-4.

  2. **served** — ``gcn_layer`` and ``kge_score`` through a live
     ``StreamSession`` + ``GraphServer``: query, apply an insert-only
     stream patch, query again; every answer validated against the dense
     numpy oracle on the exact graph snapshot it was served from.

Emits ``BENCH_gnn.json``.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dfep, graph
from repro import engine as E
from repro import gserve as G
from repro import stream as S
from repro.engine import kernels
from repro.engine.programs import GCN_F_IN
from repro.engine.registry import DEFAULT_REGISTRY

from .common import SAMPLES, SCALE, emit_json

FEATURES = (8, 32, 128)


def _gnn_graph(n: int) -> graph.Graph:
    return graph.watts_strogatz(n, 8, 0.1, seed=0)


def _best_of(fn, reps: int) -> float:
    best = float("inf")
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _kernel_sweep(g: graph.Graph, k: int) -> list[dict]:
    owner, _ = dfep.partition(g, k=k, key=0)
    plan = E.compile_plan(g, np.asarray(owner), k,
                          edge_slack=64, vertex_slack=64)
    weights = plan.edge_w
    n_edges = int(g.n_edges)
    rng = np.random.default_rng(3)
    rows = []
    for f in FEATURES:
        feats = jnp.asarray(rng.normal(size=(g.n_vertices, f))
                            .astype(np.float32))
        local = kernels.gather_vertex_channel(plan, feats)

        fused = jax.jit(lambda x: kernels.gspmm(plan, x, weights, "add"))
        batched_ref = jax.jit(
            lambda x: kernels.gspmm_ref(plan, x, weights, "add"))

        def colwise(x, f=f):
            # the pre-StateSpec shape: one rank-1 pass per feature column
            cols = [kernels.gspmm_ref(plan, x[:, :, c], weights,
                                      "add")[:, :, 0] for c in range(f)]
            return jnp.stack(cols, axis=-1)

        colwise_ref = jax.jit(colwise)

        a = np.asarray(fused(local).block_until_ready())
        b = np.asarray(batched_ref(local).block_until_ready())
        c = np.asarray(colwise_ref(local).block_until_ready())
        finite = np.isfinite(a)
        parity = bool(np.allclose(a[finite], b[finite], atol=1e-4)
                      and np.allclose(a[finite], c[finite], atol=1e-4))

        t_fused = _best_of(lambda: fused(local), SAMPLES)
        t_col = _best_of(lambda: colwise_ref(local), SAMPLES)
        t_bat = _best_of(lambda: batched_ref(local), SAMPLES)
        ef = n_edges * f  # edge-features contracted per dispatch
        rows.append({
            "features": f,
            "fused_qps": round(ef / max(t_fused, 1e-9), 1),
            "colwise_ref_qps": round(ef / max(t_col, 1e-9), 1),
            "batched_ref_qps": round(ef / max(t_bat, 1e-9), 1),
            "speedup_vs_ref": round(t_col / max(t_fused, 1e-9), 2),
            "parity": parity,
        })
    return rows


def _served_sweep(g_n: int, k: int) -> list[dict]:
    """gcn_layer + kge_score served oracle-exact across a stream patch."""
    sess = S.StreamSession(_gnn_graph(g_n),
                           S.StreamConfig(k=k, chunk_size=64,
                                          drift_threshold=1e9), key=0)
    srv = G.GraphServer.from_session(sess, cache_entries=0)
    rng = np.random.default_rng(4)
    rows = []
    for phase in ("initial", "patched"):
        if phase == "patched":
            n_v = sess.graph().n_vertices
            a = rng.integers(0, n_v, size=8)
            sess.apply(inserts=np.stack([a, (a + 5) % n_v], 1))
        g = sess.graph()
        for name in ("gcn_layer", "kge_score"):
            entry = DEFAULT_REGISTRY.get(name)
            params = {}
            for spec in entry.channel_params:
                if spec.channel == "vertex":
                    n = g.n_vertices
                elif spec.channel == "edge":
                    n = g.e_pad
                else:  # dense: the gcn weight matrix
                    n = GCN_F_IN
                params[spec.name] = rng.random((n, spec.features)) \
                    .astype(np.float32)
            t0 = time.perf_counter()
            out = srv.serve([G.QueryRequest(name, tenant=f"t{i}",
                                            params=params)
                             for i in range(4)])
            wall = time.perf_counter() - t0
            exact = all(np.allclose(r.value, entry.oracle(g, **params),
                                    atol=entry.oracle_atol)
                        for r in out)
            rows.append({"program": name, "phase": phase,
                         "n_queries": len(out),
                         "qps": round(len(out) / max(wall, 1e-9), 2),
                         "exact_vs_oracle": bool(exact)})
    srv.close()
    return rows


def run(scale: float = SCALE, k: int = 8) -> dict:
    g = _gnn_graph(max(int(64000 * scale), 2048))
    sweep = _kernel_sweep(g, k)
    served = _served_sweep(max(int(16000 * scale), 512), k)
    f128 = next(r for r in sweep if r["features"] == 128)
    return {
        "n_vertices": g.n_vertices, "n_edges": g.n_edges, "k": k,
        "sweep": sweep,
        "served": served,
        # headline acceptance: fused Pallas vs the column-at-a-time XLA
        # reference path at F=128 (floor 1.5 in tolerances.json)
        "speedup_f128": f128["speedup_vs_ref"],
        "all_parity": bool(all(r["parity"] for r in sweep)),
        "all_served_exact": bool(all(r["exact_vs_oracle"] for r in served)),
    }


def main() -> None:
    emit_json("BENCH_gnn", run())


if __name__ == "__main__":
    main()
