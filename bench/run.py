"""Run one benchmark cell once on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell, its configuration and its traffic mix are found by name:
``BENCHMARK.json`` names the configuration (``bench/configs/<name>.json``)
and the mix (``bench/traffic/<name>.json``), the mix names its window
driver (``bench/loops/<name>.py``) and holds the limits of its checks;
each metric is read by ``bench/metrics/<name>.py``.  One process runs the cell once: set-up
(graph, owner array, program objects, warm-up of every shape the window
uses), the measured window, then the check of what the window produced
against the plain references.  With ``--trace 1`` the window runs under
the JAX profiler and the program's recorder, and the per-layer metrics are
reported instead of the end-to-end ones.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` when
traced, and ``checks`` last: each compared number with its limit).  The
last lines of standard error repeat the checks.  On any platform other
than ``tpu``, or with fewer chips than the cell asks for, it exits with
code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

TRACE_DIR = BENCH / ".trace"


class NoChip(RuntimeError):
    """The platform is not a TPU, or has fewer chips than the cell asks."""


def spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_of(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def metrics_for(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics this cell reports in this kind of run."""
    e2e = [m for m in bench["end_to_end"]
           if m["name"] == "setup_s" or cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moves = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moves)]


def reader(name: str):
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def check_device(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, found platform {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, found "
                     f"{len(devices)}")
    return devices


class CompileCount:
    """Names of the executables JAX compiles or loads from its cache."""

    def __init__(self):
        import jax
        from jax._src import dispatch

        self.names: list[str] = []

        def listen(event, duration, fun_name=None, **_):
            if event == dispatch.BACKEND_COMPILE_EVENT:
                self.names.append(str(fun_name))

        jax.monitoring.register_event_duration_secs_listener(listen)


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             bench: dict | None = None, config: dict | None = None,
             mix: dict | None = None, devices=None, loop_hook=None) -> dict:
    """One run of one cell; returns the result object.  ``devices``
    stands for the chip check (tests pass the CPU's); ``config``/``mix``
    replace the cell's files (tests shrink them); ``loop_hook(loop)`` runs
    after set-up (tests plant faults there)."""
    from bench import deploy, trace_reduce
    from bench.loop import clock, find, note

    bench = bench if bench is not None else spec()
    cell = cell_of(bench, cell_name)
    if devices is None:
        devices = check_device(int(cell["chips"]))
    import jax
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    # every executable goes to the persistent cache, small ones too, so
    # that only the first run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileCount()

    cfg = config if config is not None else deploy.load_config(cell["config"])
    mix = mix if mix is not None else deploy.load_traffic(cell["traffic"])
    driver = find(mix["loop"])
    dep = deploy.build_graph(cfg)
    if driver.needs_owner:
        deploy.partition_owner(dep)
    loop = driver(dep, mix, seed, seconds)
    loop.setup()
    if loop_hook is not None:
        loop_hook(loop)
    setup_s = clock() - T_START

    from repro import obs

    tdir = TRACE_DIR / f"{cell_name}-{seed}"
    if trace:
        obs.enable(capacity=1 << 20)
        obs.reset()
        t_obs0 = clock()
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
    n_compiles = len(compiles.names)
    with note("window"):
        loop.window()
    compiled_in_window = compiles.names[n_compiles:]
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        obs.disable()
        reduced = trace_reduce.reduce_dir(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
    stats = devices[0].memory_stats() or {}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)

    ctx = types.SimpleNamespace(
        cell=cell, cfg=cfg, mix=mix, loop=loop, setup_s=setup_s,
        trace=reduced, peaks=peaks_of(devices[0].device_kind)
        if trace else None,
        events=obs.get().events() if trace else [],
        counters=obs.get().counters() if trace else {},
        t_obs0=t_obs0 if trace else None)
    metrics = {}
    for m in metrics_for(bench, cell_name, trace):
        value = reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    loop.free()
    checks = loop.checks()
    out = {"correct": all(c.ok for c in checks),
           "attempted": int(loop.attempted), "failed": int(loop.failed),
           "metrics": metrics,
           "device": {"platform": devices[0].platform,
                      "kind": devices[0].device_kind,
                      "count": len(devices),
                      "memory_peak_bytes": int(peak)}}
    if trace:
        out["device"]["busy_s"] = reduced["busy_s"]
        out["device"]["window_s"] = reduced["window_s"]
        out["breakdown"] = reduced["breakdown"]
    out["notes"] = {"compiles_in_window": compiled_in_window,
                    "memory_bytes_limit": int(stats.get("bytes_limit", 0)),
                    **loop.notes()}
    # JSON has no infinity: a gap that is infinite (an answer finite on
    # one side only) is written as the largest double
    out["checks"] = {c.name: {"value": min(c.value, sys.float_info.max),
                              "limit": c.limit} for c in checks}
    return out


def peaks_of(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       "bench/peaks.json")
    return table[kind]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    print(f"notes {json.dumps(out['notes'])}", file=sys.stderr, flush=True)
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
