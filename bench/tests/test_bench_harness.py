"""The benchmark's files, found by name, and its generators."""
from __future__ import annotations

import os
import re
import subprocess
import sys

import numpy as np
import pytest

from bench import deploy, gen, loop, run

BENCH = run.spec()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_json_has_the_contracts_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += [c["name"] for c in BENCH["configs"]] + CELLS
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(CELLS)
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert run.metrics_for(BENCH, w["name"], True), w["name"]
        assert len(run.metrics_for(BENCH, w["name"], False)) >= 2


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["end_to_end"]
                                    + BENCH["per_layer"]])
def test_every_metric_has_a_reader(metric):
    assert callable(run.reader(metric))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_load_by_name(cell):
    w = run.cell_of(BENCH, cell)
    cfg = deploy.load_config(w["config"])
    mix = deploy.load_traffic(w["traffic"])
    assert cfg["name"] == w["config"]
    driver = loop.find(mix["loop"])
    assert issubclass(driver, loop.Loop)
    assert mix["limits"] and min(mix["limits"].values()) >= 0
    entry = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    assert entry["file"] == f"bench/configs/{w['config']}.json"


def test_generators_are_deterministic_in_the_seed():
    mix = deploy.load_traffic("traverse")
    n = 400
    a = gen.requests(mix, n, 20.0, 2147483653)
    b = gen.requests(mix, n, 20.0, 2147483653)
    c = gen.requests(mix, n, 20.0, 2147483654)
    assert np.array_equal(a.due, b.due) and a.kind == b.kind
    assert np.array_equal(a.source, b.source) and a.tenant == b.tenant
    assert not np.array_equal(a.source, c.source)
    # every seed gets the same amount of each kind of work
    assert sorted(a.kind) == sorted(c.kind)
    assert sorted(a.tenant) == sorted(c.tenant)


@pytest.mark.parametrize("spec", [{"dist": "uniform"},
                                  {"dist": "zipf", "s": 1.0}])
def test_source_distributions_are_found_by_name(spec):
    draw = lambda seed: gen.sources(spec, 1000, 5000,  # noqa: E731
                                    gen.rng(seed, "sources"))
    a, b = draw(7), draw(7)
    assert np.array_equal(a, b) and not np.array_equal(a, draw(8))
    assert a.min() >= 0 and a.max() < 1000
    top = np.bincount(a, minlength=1000).max() / len(a)
    # Zipf(1.0) over 1,000 vertices puts 1 / H(1000) = 13% on its top
    # vertex; uniform sources put about 0.2% on any one
    assert (top > 0.1) == (spec["dist"] == "zipf")


def test_command_refuses_the_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(run.BENCH / "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_command_fails_with_only_the_benchmark(tmp_path):
    """A checkout that holds only BENCHMARK.json and ``bench/`` has no
    program to run: the command fails and prints no result."""
    import shutil

    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_peaks_are_known_only_for_listed_devices():
    assert run.peaks_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        run.peaks_of("cpu")
