"""Sources with Zipf(``s``) popularity: the vertex of rank r is drawn with
probability proportional to 1 / r**s; the ranking is a permutation of the
vertices drawn from the same generator."""
import numpy as np


def draw(rng: np.random.Generator, n_vertices: int, size: int,
         s: float = 1.0) -> np.ndarray:
    p = 1.0 / np.arange(1, n_vertices + 1) ** float(s)
    rank = rng.choice(n_vertices, size=size, p=p / p.sum())
    return rng.permutation(n_vertices)[rank]
