"""Readings that a cell's limits are set from, on the chip at the cell's
own size: for each seed, the program's checked numbers (the lower
reading) and the control's, the reference computed in bfloat16 and put in
the program's place (the upper reading).

    python3 bench/control.py --workload <cell> --seeds 11,12,13 \\
        --seconds 10

One process: the deployment is built once, and each seed runs the cell's
set-up and a window of ``--seconds`` at the cell's own load.  Prints one
JSON line per seed.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

from bench import deploy, run  # noqa: E402
from bench.loop import find  # noqa: E402


def readings(loop) -> dict:
    return {c.name: c.value for c in loop.checks()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10)
    args = ap.parse_args(argv)
    bench = run.spec()
    cell = run.cell_of(bench, args.workload)
    try:
        run.check_device(int(cell["chips"]))
    except run.NoChip as e:
        print(f"bench/control.py: {e}", file=sys.stderr)
        return 2
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    cfg = deploy.load_config(cell["config"])
    mix = deploy.load_traffic(cell["traffic"])
    driver = find(mix["loop"])
    dep = deploy.build_graph(cfg)
    if driver.needs_owner:
        deploy.partition_owner(dep)
    for seed in (int(s) for s in args.seeds.split(",")):
        loop = driver(dep, mix, seed, args.seconds)
        loop.setup()
        loop.window()
        loop.free()
        program = readings(loop)
        loop.plant_control()
        control = readings(loop)
        print(json.dumps({"cell": args.workload, "seed": seed,
                          "program": program, "control": control,
                          "limits": {k: mix["limits"][k] for k in program}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
