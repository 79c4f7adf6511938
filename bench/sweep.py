"""Find the knee of a serving cell once, by a sweep of offered rates.

    python3 bench/sweep.py --workload graph500-s14-k8.traverse \\
        --rates 50,100,150 --seconds 20 --seed 7

Runs the cell's window once at each rate, in one process, and prints one
JSON line per rate: the offered rate, the completed rate, the median and
95th percentile latency over the whole window and over each half, and the
refused count.  Above the knee the second half's tail is far above the
first half's: the queue grows.  The chosen rate goes into the traffic
file; the benchmark itself never searches for a rate.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import deploy, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import numpy as np

    bench = run.spec()
    cell = run.cell_of(bench, args.workload)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = deploy.load_traffic(cell["traffic"])
        mix["rate_qps"] = rate
        seen = {}
        try:
            out = run.run_cell(args.workload, args.seed, args.seconds,
                               False, bench=bench, mix=mix,
                               loop_hook=lambda loop: seen.update(loop=loop))
        except run.NoChip as e:
            print(f"bench/sweep.py: {e}", file=sys.stderr)
            return 2
        loop = seen["loop"]
        lat = loop.latencies_ms()
        half = loop.reqs.due < args.seconds / 2
        done = ~np.isnan(loop.done_t)
        print(json.dumps({
            "rate": rate, "requests": len(lat),
            "completed_per_s": float(done.sum() / (np.nanmax(loop.done_t)
                                                   - loop.t0)),
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "p95_first_half_ms": float(np.percentile(lat[half], 95)),
            "p95_second_half_ms": float(np.percentile(lat[~half], 95)),
            "correct": out["correct"], "metrics": out["metrics"],
            "notes": out["notes"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
