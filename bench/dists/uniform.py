"""Sources uniform over the vertices."""
import numpy as np


def draw(rng: np.random.Generator, n_vertices: int, size: int
         ) -> np.ndarray:
    return rng.integers(0, n_vertices, size=size)
