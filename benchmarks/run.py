"""Benchmark runner: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig5,fig9 | --all] [--fast]

Emits ``name,us_per_call,derived`` CSVs and BENCH_*.json records under
experiments/bench/ and prints each table. ``--fast`` shrinks scales/samples
for a quick pass.

Two guarantees the CI bench gate leans on:

  * **no silent skips** — every ``fig*.py`` / ``kernel_bench.py`` module in
    this package must be registered below; a module on disk that the
    registry does not know is a startup error, so a new figure cannot
    quietly drop out of ``--all``;
  * **non-zero on crash** — each selected benchmark runs even if an earlier
    one crashed, the tracebacks are printed, and the process exits 1 if
    ANY of them failed (previously the first crash aborted the rest and a
    partially-written artifact dir could pass for a finished run).
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig5,fig6,fig7,fig8,fig9,fig10,"
                         "stream,serve,serve_mesh,programs,obs,cost,kernels")
    ap.add_argument("--all", action="store_true",
                    help="run every registered benchmark (the default when "
                         "--only is absent; the two flags are exclusive)")
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    if args.only and args.all:
        ap.error("--only and --all are exclusive")
    if args.fast:
        os.environ.setdefault("REPRO_BENCH_SCALE", "0.05")
        os.environ.setdefault("REPRO_BENCH_SAMPLES", "2")

    # before the first compile: JAX's persistent cache at its one place
    from repro.compile_cache import use_compile_cache
    use_compile_cache()

    # imports AFTER env so common.py picks the scales up
    from repro import obs
    from . import (fig5_k_sweep, fig6_diameter, fig7_comparison,
                   fig8_scalability, fig9_sssp, fig10_engine, fig_cost,
                   fig_gnn, fig_obs, fig_programs, fig_serve,
                   fig_serve_mesh, fig_stream, kernel_bench)

    all_benches = {
        "fig5": fig5_k_sweep.main,
        "fig6": fig6_diameter.main,
        "fig7": fig7_comparison.main,
        "fig8": fig8_scalability.main,
        "fig9": fig9_sssp.main,
        "fig10": fig10_engine.main,
        "stream": fig_stream.main,
        "serve": fig_serve.main,
        "serve_mesh": fig_serve_mesh.main,
        "programs": fig_programs.main,
        "obs": fig_obs.main,
        "cost": fig_cost.main,
        "gnn": fig_gnn.main,
        "kernels": kernel_bench.main,
    }
    # registry completeness: every benchmark module on disk must be wired
    # in, or --all silently under-reports (the CI gate assumes coverage)
    here = pathlib.Path(__file__).resolve().parent
    on_disk = {p.stem for p in here.glob("fig*.py")} | {"kernel_bench"}
    registered = {fn.__module__.rsplit(".", 1)[-1]
                  for fn in all_benches.values()}
    unwired = sorted(on_disk - registered)
    if unwired:
        ap.error(f"benchmark module(s) on disk but not registered in "
                 f"benchmarks.run: {', '.join(unwired)}")

    only = args.only.split(",") if args.only else list(all_benches)
    unknown = sorted(set(only) - set(all_benches))
    if unknown:
        ap.error(f"unknown benchmark(s) {','.join(unknown)}; "
                 f"available: {','.join(all_benches)}")
    # the recorder stays ON across the whole run so the summary table can
    # attribute events per figure; lifetime counts survive the per-figure
    # reset()s some benchmarks perform (fig_obs), so deltas stay correct
    rec = obs.get()
    rec.enable()
    # CI postmortems: with REPRO_FLIGHT_DIR set, a crashing figure dumps a
    # flight bundle (ring + snapshot + gauges) before the run moves on —
    # the workflow uploads the directory as an artifact on failure
    from repro.obs import flight as _flight
    from repro.gserve import metrics as _gmetrics
    flight_rec = _flight.from_env()
    failures: list[str] = []
    summary: list[tuple[str, str, float, int, int, float, int]] = []
    for name in only:
        t0 = time.time()
        s0 = rec.stats()
        x0 = _gmetrics.exec_totals()
        print(f"\n### running {name} ...", flush=True)
        try:
            all_benches[name]()
        except Exception:
            traceback.print_exc()
            failures.append(name)
            print(f"### {name} FAILED after {time.time()-t0:.1f}s",
                  flush=True)
            status = "FAILED"
            if flight_rec is not None:
                print(f"### flight bundle: "
                      f"{flight_rec.dump(f'bench.{name}.crash')}",
                      flush=True)
        else:
            print(f"### {name} done in {time.time()-t0:.1f}s", flush=True)
            status = "ok"
        rec.enable()       # re-arm in case the benchmark disabled it
        s1 = rec.stats()
        x1 = _gmetrics.exec_totals()
        summary.append((name, status, time.time() - t0,
                        s1["recorded"] - s0["recorded"],
                        s1["overwritten"] - s0["overwritten"],
                        x1["device_s"] - x0["device_s"],
                        x1["executes"] - x0["executes"]))

    # "overwr" = ring-buffer events silently overwritten during the figure
    # (lifetime monotone counter delta): non-zero means the exported trace
    # is missing that many events — resize the ring or trim the figure.
    # "dev_s"/"execs" = serving device-time spend (summed execute-span
    # durations / dispatch count, gserve.metrics.exec_totals deltas): the
    # attribution denominator the cost ledger reconciles against — zero for
    # figures that never touch the serving path
    print("\n### summary (obs recorder events + serving device time "
          "per figure)")
    print(f"{'figure':<12} {'status':<8} {'wall_s':>8} {'events':>8} "
          f"{'overwr':>8} {'dev_s':>8} {'execs':>6}")
    for name, status, wall, n_events, n_overwr, dev_s, execs in summary:
        print(f"{name:<12} {status:<8} {wall:>8.1f} {n_events:>8} "
              f"{n_overwr:>8} {dev_s:>8.2f} {execs:>6}")
    xt = _gmetrics.exec_totals()
    win = xt["windowed"]
    print(f"### serving device time: {xt['device_s']:.2f}s total over "
          f"{xt['executes']} executes; trailing {win['window_s']:.0f}s: "
          f"{win['n']} spans, p99 {win['p99']:.4f}s")
    from repro.obs.ledger import get_ledger
    lt = get_ledger().totals()
    if lt["requests"]:
        print(f"### global cost ledger: {lt['requests']} requests in "
              f"{lt['series']} series, {lt['device_s']:.2f} device-s, "
              f"{lt['flops']:.3g} flops")
    # static-analysis footer: the bench summary carries the same invariant
    # gate CI enforces, so a local --all run can't look green while the
    # tree has unsuppressed analyzer findings
    try:
        from repro.analysis import run_clean
        src_root = pathlib.Path(__file__).resolve().parents[1] / "src/repro"
        ok = run_clean(str(src_root))
        verdict = "PASS" if ok else \
            "FAIL — run `python -m repro.analysis src/repro` for findings"
        print(f"### static analysis (repro.analysis): {verdict}",
              flush=True)
        if not ok:
            failures.append("analysis")
    except Exception as e:  # pragma: no cover — never mask bench results
        print(f"### static analysis (repro.analysis): ERROR ({e})",
              flush=True)

    if failures:
        print(f"\n### {len(failures)} benchmark(s) crashed: "
              f"{', '.join(failures)}", flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()
