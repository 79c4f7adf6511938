"""Partition-health gauges derived from a compiled ``PartitionPlan``.

The paper judges a partitioning on replication factor, balance and
communication volume (§V-A); the streaming subsystem additionally lives or
dies by its remaining slack (how many more patches fit before a compaction
epoch forces a retrace).  ``plan_health`` computes all of them from the
plan's dynamic children so the stream session can stamp every installed
plan mutation with the live numbers, and ``obs.snapshot()`` always shows
the latest.

The formulas intentionally mirror ``core/metrics.py`` (``nstdev``,
``largest_norm``, replication factor = Σ|V_i| / |V|, exchange volume =
Σ|F_i| = MESSAGES) — tests/test_obs.py asserts the match — but this module
takes the *plan* as its input, not the graph + owner, so it stays a leaf
(no engine/core imports; any object with the plan's fields duck-types).

The result is memoized on the plan instance: plans are immutable pytrees
(every patch installs a new object), so health is computed at most once
per installed plan no matter how many dispatches or swap events read it.
"""
from __future__ import annotations

import numpy as np


def plan_health(plan) -> dict:
    """Health gauges for one compiled plan (memoized per plan instance)."""
    cached = plan.__dict__.get("_obs_health")
    if cached is not None:
        return cached
    sizes = np.asarray(plan.n_edges_local).astype(np.float64)   # [K]
    total = float(sizes.sum())
    k = int(plan.k)
    mean = total / k if total else 1.0
    norm = sizes / mean
    csr_fill = np.asarray(plan.csr_fill).astype(np.float64)     # [K]
    v_fill = np.asarray(plan.v_fill).astype(np.float64)         # [K]
    slot = np.arange(plan.e_max)[None, :]
    appended = np.asarray(plan.emask) & (slot >= csr_fill[:, None])
    health = {
        # the paper's axes
        "replication_factor": float(plan.replication_factor()),
        "balance_nstdev": float(np.sqrt(np.mean((norm - 1.0) ** 2)))
                          if total else 0.0,
        "largest_norm": float(norm.max()) if total else 0.0,
        "exchange_per_superstep": int(plan.exchange_volume),
        # streaming slack: how far each partition is from forcing a
        # compaction epoch (and therefore a jit retrace)
        "edge_lane_occupancy_mean": float((csr_fill / plan.e_max).mean()),
        "edge_lane_occupancy_max": float((csr_fill / plan.e_max).max()),
        "vertex_lane_occupancy_mean": float((v_fill / plan.v_max).mean()),
        "vertex_lane_occupancy_max": float((v_fill / plan.v_max).max()),
        "min_free_edge_slots": int((plan.e_max - csr_fill).min()),
        "min_free_vertex_slots": int((plan.v_max - v_fill).min()),
        # live half-edges past the sorted CSR prefix: the kernels fold the
        # append region in only while this is above 0
        "append_live_half_edges": int(appended.sum()),
    }
    object.__setattr__(plan, "_obs_health", health)
    return health
