"""Paper Fig. 8: DFEP scalability with worker count.

The paper measures Hadoop wall-clock on EC2 with 2..16 nodes. Here the
distributed (shard_map) DFEP runs with 1/2/4/8 devices and we report
wall-clock per round + the collective schedule. On the CPU each point runs
in a subprocess with that many forced host devices (XLA's device count is
fixed at process init); on one physical core the *speedup* is structural
(per-worker work shrinks; the psum schedule is real), so we report
per-round work bytes alongside time. On a TPU host the process that holds
the chips runs every point itself over prefixes of ``jax.devices()`` — a
child could not reach a chip its parent holds."""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import textwrap
import time

from .common import SCALE, emit, emit_json

WORKER = textwrap.dedent("""
    import os, sys, json
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={sys.argv[1]}"
    import jax
    from benchmarks.fig8_scalability import point
    print(json.dumps(point(jax.devices(), float(sys.argv[2]))))
""")


def point(devices, scale: float) -> dict:
    """Distributed DFEP over a 1-D mesh of ``devices`` (one worker each)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import dfep, dfep_distributed, graph

    ndev = len(devices)
    g = graph.load_dataset("dblp", scale=scale, seed=0)
    mesh = Mesh(np.array(devices), ("data",))
    cfg = dfep.DfepConfig(k=16, max_rounds=60, stall_rounds=60)  # fixed rounds
    t0 = time.perf_counter()
    _, info = dfep_distributed.run_dfep_sharded(g, cfg, jax.random.key(0),
                                                mesh)
    dt = time.perf_counter() - t0
    return {"ndev": ndev, "V": g.n_vertices, "E": g.n_edges,
            "rounds": info["rounds"], "wall_s": round(dt, 2),
            "edges_per_worker": g.e_pad // ndev}


def _subprocess_point(nd: int, scale: float) -> dict:
    root = pathlib.Path(__file__).resolve().parents[1]
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join([str(root / "src"), str(root)])
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", WORKER, str(nd), str(scale)],
                         env=env, cwd=root, capture_output=True, text=True,
                         timeout=1800)
    line = res.stdout.strip().splitlines()[-1] if res.stdout.strip() else "{}"
    try:
        return json.loads(line)
    except json.JSONDecodeError:
        return {"ndev": nd, "error": res.stderr[-300:]}


def run(devs=(1, 2, 4, 8), scale=SCALE) -> list[dict]:
    import jax
    if jax.default_backend() == "tpu":
        chips = jax.devices()
        rows = [point(chips[:nd], scale) for nd in devs if nd <= len(chips)]
    else:
        rows = [_subprocess_point(nd, scale) for nd in devs]
    if rows and "wall_s" in rows[0]:
        base = rows[0]["wall_s"]
        for r in rows:
            if "wall_s" in r:
                r["speedup_vs_1"] = round(base / r["wall_s"], 2)
    return rows


def main() -> None:
    rows = run()
    emit("fig8_scalability", rows)
    # persist the structured record like fig10/stream/serve do
    emit_json("BENCH_scalability", {"scale": SCALE, "rows": rows})


if __name__ == "__main__":
    main()
