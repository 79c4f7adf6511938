"""Whole runs of the partition and analytics cells on the CPU at a small
size: a sound run is correct, the control is refused, and an answer
altered where it is produced turns ``correct`` false."""
from __future__ import annotations

import dataclasses

import pytest


@pytest.mark.parametrize("cell,e2e", [
    ("graph500-s12-k8.partition", "partition_s"),
    ("graph500-s14-k8.analytics", "analytics_job_ms")])
def test_sound_run_is_correct(tiny_run, cell, e2e):
    out, _ = tiny_run(cell, seconds=1.0)
    assert out["correct"], out["checks"]
    assert out["notes"]["compiles_in_window"] == []
    assert out["metrics"][e2e]["value"] > 0


@pytest.mark.parametrize("cell,check", [
    ("graph500-s12-k8.partition", "owner_mismatch"),
    ("graph500-s14-k8.analytics", "pagerank_gap")])
def test_control_is_refused(tiny_run, cell, check):
    _, loop = tiny_run(cell, seed=78, seconds=1.0)
    loop.plant_control()
    checks = {c.name: c for c in loop.checks()}
    assert not checks[check].ok


def _alter_owner(loop):
    """The program's partitions come back with one edge moved (the test
    restores the module's function)."""
    from repro.core import dfep

    partition = dfep.partition

    def altered(g, k, key=0, **kw):
        owner, info = partition(g, k, key=key, **kw)
        return owner.at[0].set((owner[0] + 1) % k), info

    dfep.partition = altered


def _alter_state(loop):
    job = loop.job

    def altered(kind, slot):
        res = job(kind, slot)
        return dataclasses.replace(res, state=res.state * 1.5 + 1.0)

    loop.job = altered


@pytest.mark.parametrize("cell,fault,check", [
    ("graph500-s12-k8.partition", _alter_owner, "owner_mismatch"),
    ("graph500-s14-k8.analytics", _alter_state, "wrong_answers"),
    ("graph500-s14-k8.analytics", _alter_state, "gcn_gap"),
])
def test_fault_makes_the_run_incorrect(tiny_run, monkeypatch, cell, fault,
                                       check):
    from repro.core import dfep

    monkeypatch.setattr(dfep, "partition", dfep.partition)
    out, _ = tiny_run(cell, seed=6, seconds=1.0, hook=fault)
    assert not out["correct"]
    c = out["checks"][check]
    assert c["value"] > c["limit"]
