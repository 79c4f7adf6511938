"""One traffic generator for every mix: reads a mix's data file and draws
the window's work from ``--seed``.

Every seed gets the same amount of work (the same request count, kind
counts and tenant shares); the seed decides order, arrival instants and
sources.  Arrivals are a Poisson process conditioned on its count: the
request instants are sorted uniform draws over the window.  Sources come
from the distribution the mix names (``bench/dists/<name>.py``, found by
name).
"""
from __future__ import annotations

import dataclasses
import importlib
import zlib

import numpy as np


def rng(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per purpose, all fixed by ``seed``."""
    return np.random.default_rng([int(seed) % 2 ** 63,
                                  zlib.crc32(stream.encode())])


def _exact_counts(total: int, shares: list[float]) -> np.ndarray:
    """Integer counts summing to ``total`` in proportion to ``shares``
    (largest remainder)."""
    w = np.asarray(shares, np.float64)
    raw = total * w / w.sum()
    cnt = np.floor(raw).astype(np.int64)
    short = total - int(cnt.sum())
    cnt[np.argsort(-(raw - cnt), kind="stable")[:short]] += 1
    return cnt


def sources(spec: dict, n_vertices: int, size: int,
            r: np.random.Generator) -> np.ndarray:
    """``size`` source vertices from the distribution ``spec["dist"]``,
    with the spec's other keys as its parameters."""
    dist = importlib.import_module(f"{__package__}.dists.{spec['dist']}")
    params = {k: v for k, v in spec.items() if k != "dist"}
    return dist.draw(r, n_vertices, size, **params)


@dataclasses.dataclass
class Requests:
    due: np.ndarray          # [N] seconds after the window opens
    kind: list[str]          # [N] program names
    tenant: list[str]        # [N]
    source: np.ndarray       # [N] int


def requests(mix: dict, n_vertices: int, seconds: float, seed: int
             ) -> Requests:
    r = rng(seed, "requests")
    n = int(round(float(mix["rate_qps"]) * seconds))
    due = np.sort(r.uniform(0.0, seconds, size=n))
    kinds = list(mix["mix"])
    per_kind = _exact_counts(n, [mix["mix"][k] for k in kinds])
    kind = np.repeat(np.arange(len(kinds)), per_kind)
    r.shuffle(kind)
    t = int(mix["tenants"])
    shares = 1.0 / np.arange(1, t + 1) ** float(mix["tenant_zipf"])
    tenant = np.repeat(np.arange(t), _exact_counts(n, list(shares)))
    r.shuffle(tenant)
    source = sources(mix["sources"], n_vertices, n, rng(seed, "sources"))
    return Requests(due, [kinds[i] for i in kind],
                    [f"tenant{i}" for i in tenant], source)


def jobs(n_vertices: int, count: int, seed: int) -> np.ndarray:
    """Sources of the analytics jobs, one per job slot."""
    return rng(seed, "jobs").integers(0, n_vertices, size=count)


def sample(seed: int, population: int, size: int, stream: str
           ) -> np.ndarray:
    """``size`` distinct indices of ``range(population)`` drawn from the
    seed, in increasing order."""
    size = min(size, population)
    return np.sort(rng(seed, stream).choice(population, size=size,
                                            replace=False))
