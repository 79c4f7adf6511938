"""Build a configuration's deployment: the graph, its DFEP owner array,
and the program objects a traffic mix drives.

The graph comes from the benchmark's own generator (``reference.graphs``),
fixed by the configuration's ``graph_seed``: a deployment is one dataset.
The program receives it through its public ``from_edge_array`` entry.

The owner array of a serving configuration is computed by the program's
DFEP on the first run in a checkout and kept in ``bench/.owner_cache``,
keyed by the configuration file and the partitioner's sources, so later
runs do not pay a whole partition in their set-up.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import pathlib

import numpy as np

from .reference import graphs

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
OWNER_CACHE = BENCH / ".owner_cache"
#: program sources whose change invalidates a cached owner array
PARTITIONER_SOURCES = ("src/repro/core/dfep.py", "src/repro/core/graph.py")


def load_config(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def load_traffic(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@dataclasses.dataclass
class Deployment:
    cfg: dict
    n: int                      # vertices
    u: np.ndarray               # canonical edges (reference layout)
    v: np.ndarray
    graph: object               # the program's Graph
    owner: np.ndarray | None = None
    owner_info: dict | None = None
    owner_cached: bool = False


def owner_key(cfg: dict, root: pathlib.Path = ROOT) -> str:
    """Cache key: the configuration and the partitioner's sources."""
    h = hashlib.sha256(json.dumps(cfg, sort_keys=True).encode())
    for rel in PARTITIONER_SOURCES:
        h.update((root / rel).read_bytes())
    return f"{cfg['name']}-{h.hexdigest()[:20]}"


def build_graph(cfg: dict) -> Deployment:
    from repro.core import graph as G

    n, u, v = graphs.build(cfg)
    g = G.from_edge_array(n, np.stack([u, v], 1))
    return Deployment(cfg, n, u, v, g)


def partition_owner(dep: Deployment, cache_dir: pathlib.Path | None = None,
                    root: pathlib.Path = ROOT) -> None:
    """Fill ``dep.owner`` from the cache, or by the program's DFEP."""
    from repro.core import dfep

    cfg = dep.cfg
    cache_dir = OWNER_CACHE if cache_dir is None else cache_dir
    path = cache_dir / f"{owner_key(cfg, root)}.npz"
    if path.exists():
        with np.load(path) as z:
            dep.owner = z["owner"]
            dep.owner_info = json.loads(str(z["info"]))
        dep.owner_cached = True
        return
    owner, info = dfep.partition(dep.graph, k=int(cfg["k"]),
                                 key=int(cfg["dfep_key"]))
    dep.owner = np.asarray(owner)
    dep.owner_info = info
    cache_dir.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".tmp.npz")
    np.savez(tmp, owner=dep.owner, info=json.dumps(info))
    tmp.replace(path)
