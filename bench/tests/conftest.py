"""Shared by the benchmark's tests: the repository root and ``src`` on the
path, and small versions of the cells that run on the CPU.

The cells proper refuse any platform but a TPU; these tests stand the
CPU's devices in for the chip check, shrink each configuration's graph to
a few hundred vertices, and keep every file the runs write out of the
checkout (no persistent compile cache, owner arrays in a temporary
directory)."""
from __future__ import annotations

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

#: the Graph 500 SCALE the CPU runs use (512 vertices before the
#: largest component is taken)
TINY_SCALE = 9


@pytest.fixture
def tiny_run(monkeypatch, tmp_path):
    """``tiny_run(cell, seed, seconds, hook=None)`` -> (result, loop)."""
    import jax
    import repro.compile_cache
    from bench import deploy, run

    monkeypatch.setattr(repro.compile_cache, "use_compile_cache",
                        lambda: None)
    monkeypatch.setattr(deploy, "OWNER_CACHE", tmp_path / "owners")

    def go(cell_name, seed=20261018, seconds=2.0, hook=None, mix_edit=None):
        bench = run.spec()
        cell = run.cell_of(bench, cell_name)
        cfg = deploy.load_config(cell["config"])
        cfg["scale"] = TINY_SCALE
        mix = deploy.load_traffic(cell["traffic"])
        if "rate_qps" in mix:
            mix["rate_qps"] = 12
            mix["sample"] = 12
        if mix_edit is not None:
            mix_edit(mix)
        seen = {}

        def keep(loop):
            seen["loop"] = loop
            if hook is not None:
                hook(loop)

        out = run.run_cell(cell_name, seed, seconds, False, bench=bench,
                           config=cfg, mix=mix, devices=jax.devices(),
                           loop_hook=keep)
        return out, seen["loop"]

    return go
