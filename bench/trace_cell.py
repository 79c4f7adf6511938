"""Run one benchmark cell once under the profiler, as
``bench/run.py --trace 1`` does, and add what ``bench/scopes.py`` reads
from the same trace.

    python3 bench/trace_cell.py --workload <cell> --seed <n> --seconds <s>

The last line of standard output is ``run.py``'s result object with one
more key, ``scopes``:

* ``scopes``, ``kernels``, ``path_share``, ``device_scopes``,
  ``device_ops``, ``idle_gaps``, ``enclosed``: ``scopes.reduce_space`` of
  the window (idle gaps named by the program's spans on the window's
  thread);
* ``per_round_ms``: the device time of each ``dfep.*`` scope over the
  window's ``dfep.rounds`` counter, ``per_superstep_ms``: each
  ``engine.*`` scope over ``engine.supersteps``, and ``scoped_share`` of
  each family: its scopes' device time over the window's busy time
  (``enclosed_share``: with its ``enclosed`` time added);
* ``stalls``: ``scopes.thread_stalls``, with the serving cell's five
  latest hand-overs of a request (due instant to hand-over) as stretches;
* ``traced_end_to_end``: the cell's end-to-end metrics other than
  ``setup_s``, read from this traced window, to set against an untraced
  run's for the cost of tracing;
* ``mirror``: for each span name, how many the recorder's ring and the
  trace hold, and the largest difference of their durations in us.

Like ``run.py``, it exits with code 2 on anything but a TPU.

It wraps ``trace_reduce.reduce_dir`` for the length of one run because
``run.py`` deletes the trace before its metric readers run, and they see
only what ``reduce_dir`` returns.  Once ``reduce_dir`` returns
``scopes.reduce_space`` itself, that wrap, this script and the gap naming
that ``scopes`` repeats from ``trace_reduce`` go.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import types

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
for p in (str(BENCH.parent / "src"), str(BENCH.parent)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import run  # noqa: E402  (starts the set-up clock)
from bench import scopes, trace_reduce, xspace  # noqa: E402

#: scope families, each over the counter of its units
PER_UNIT = {"dfep.": ("dfep.rounds", "per_round_ms"),
            "engine.": ("engine.supersteps", "per_superstep_ms")}


def per_unit(red: dict, busy_s: float, counters: dict) -> dict:
    out = {}
    for prefix, (counter, key) in PER_UNIT.items():
        mine = {n: s for n, s in red["scopes"].items()
                if n.startswith(prefix)}
        units = counters.get(counter, 0)
        if not mine or not units:
            continue
        out[key] = {n: s / units * 1e3 for n, s in mine.items()}
        out[key]["units"] = units
        near = sum(t for n, t in red.get("enclosed", {}).items()
                   if n.startswith(prefix))
        for share, secs in (("scoped_share", sum(mine.values())),
                            ("enclosed_share", sum(mine.values()) + near)):
            out.setdefault(share, {})[prefix + "*"] = \
                secs / busy_s if busy_s else None
    return out


def mirror(ring: list[dict], traced: dict) -> dict:
    """Ring spans against their annotations in the trace, by name."""
    by_name: dict[str, list] = {}
    for e in ring:
        if e.get("ph") == "X":
            by_name.setdefault(e["name"], []).append(e["dur"])
    out = {}
    for name, durs in by_name.items():
        got = traced.get(name, [])
        diff = (max(abs(a - b) for a, b in zip(sorted(durs), got))
                if len(got) == len(durs) else None)
        out[name] = {"ring": len(durs), "trace": len(got),
                     "max_dur_diff_us": diff}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    extra: dict = {}
    reduce_dir = trace_reduce.reduce_dir

    def reduce_and_read_scopes(tdir):
        out = reduce_dir(tdir)
        data = xspace.read_dir(tdir)
        extra.update(scopes.reduce_space(data))
        extra["stalls"] = scopes.thread_stalls(data, late_submits())
        extra["traced_spans_us"] = scopes.span_durations(data)
        return out

    loops = []

    def late_submits(top: int = 5) -> list:
        """The serving loop's latest hand-overs, as (due, handed over) in
        ms from the window's start."""
        loop = loops[0]
        if not hasattr(loop, "submit_t"):
            return []
        late = loop.submit_t - (loop.t0 + loop.reqs.due)
        done = ~np.isnan(late)
        worst = np.flatnonzero(done)[np.argsort(-late[done])[:top]]
        return [(loop.reqs.due[i] * 1e3,
                 (loop.submit_t[i] - loop.t0) * 1e3) for i in worst]

    trace_reduce.reduce_dir = reduce_and_read_scopes
    try:
        out = run.run_cell(args.workload, args.seed, args.seconds, True,
                           loop_hook=loops.append)
    except run.NoChip as e:
        print(f"bench/trace_cell.py: {e}", file=sys.stderr)
        return 2
    finally:
        trace_reduce.reduce_dir = reduce_dir
    from repro import obs

    extra.update(per_unit(extra, out["device"]["busy_s"],
                          obs.get().counters()))
    extra["mirror"] = mirror(obs.get().events(),
                             extra.pop("traced_spans_us"))
    bench = run.spec()
    ctx = types.SimpleNamespace(loop=loops[0])
    extra["traced_end_to_end"] = {
        m["name"]: run.reader(m["name"])(ctx)
        for m in run.metrics_for(bench, args.workload, False)
        if m["name"] != "setup_s"}
    out["scopes"] = extra
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
