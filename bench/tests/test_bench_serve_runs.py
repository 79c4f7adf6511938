"""Whole runs of the serving cell on the CPU at a small size: a sound
run is correct, the control is refused, and each fault the cell can have
turns ``correct`` false.  (The cell runs on one chip: there is no
exchange between chips to leave out.)"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

SERVE = ["graph500-s14-k8.traverse"]


@pytest.mark.parametrize("cell", SERVE)
def test_sound_run_is_correct(tiny_run, cell):
    out, loop = tiny_run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert len(loop.values) > 0
    assert out["notes"]["compiles_in_window"] == []
    assert out["metrics"]["query_p95_ms"]["value"] > 0
    assert out["metrics"]["setup_s"]["value"] > 0


@pytest.mark.parametrize("cell", SERVE)
def test_control_is_refused(tiny_run, cell):
    _, loop = tiny_run(cell, seed=77)
    loop.plant_control()
    checks = {c.name: c for c in loop.checks()}
    assert not checks["wrong_answers"].ok


def _alter_answers(loop):
    srv = loop.srv
    complete = srv._complete

    def altered(fl):
        out = []
        for r in complete(fl):
            v = np.array(r.value, copy=True)
            v[np.argmax(np.isfinite(v) & (v > 0))] += 1.0
            out.append(dataclasses.replace(r, value=v))
        return out

    srv._complete = altered


def _drop_half(loop):
    """Every second answer the server produces never reaches the client."""
    srv = loop.srv
    drain = srv.drain
    seen = [0]

    def half(*a, **k):
        out = []
        for r in drain(*a, **k):
            seen[0] += 1
            if seen[0] % 2:
                out.append(r)
        return out

    srv.drain = half


@pytest.mark.parametrize("cell,fault,check", [
    ("graph500-s14-k8.traverse", _alter_answers, "wrong_answers"),
    ("graph500-s14-k8.traverse", _drop_half, "unanswered"),
])
def test_fault_makes_the_run_incorrect(tiny_run, cell, fault, check):
    out, _ = tiny_run(cell, seed=5, hook=fault)
    assert not out["correct"]
    c = out["checks"][check]
    assert c["value"] > c["limit"]
