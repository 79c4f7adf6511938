"""The owner-array cache of the serving configurations."""
from __future__ import annotations

import shutil

import numpy as np

from bench import deploy

CFG = dict(deploy.load_config("graph500-s14-k8"), scale=8)


def _root(tmp_path):
    root = tmp_path / "root"
    for rel in deploy.PARTITIONER_SOURCES:
        (root / rel).parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(deploy.ROOT / rel, root / rel)
    return root


def test_second_run_loads_the_owner_array(tmp_path):
    root = _root(tmp_path)
    cache = tmp_path / "cache"
    first = deploy.build_graph(CFG)
    deploy.partition_owner(first, cache, root)
    assert not first.owner_cached
    second = deploy.build_graph(CFG)
    deploy.partition_owner(second, cache, root)
    assert second.owner_cached
    assert np.array_equal(first.owner, second.owner)
    assert second.owner_info == first.owner_info


def test_a_change_to_the_partitioner_misses_the_cache(tmp_path):
    root = _root(tmp_path)
    cache = tmp_path / "cache"
    dep = deploy.build_graph(CFG)
    deploy.partition_owner(dep, cache, root)
    before = deploy.owner_key(CFG, root)
    with open(root / "src/repro/core/dfep.py", "a") as f:
        f.write("\n# changed\n")
    assert deploy.owner_key(CFG, root) != before
    again = deploy.build_graph(CFG)
    deploy.partition_owner(again, cache, root)
    assert not again.owner_cached


def test_the_key_follows_the_configuration():
    other = dict(CFG, dfep_key=1)
    assert deploy.owner_key(CFG) != deploy.owner_key(other)
