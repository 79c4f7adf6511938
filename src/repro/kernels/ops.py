"""Jit'd public wrappers for the Pallas kernels.

``interpret=True`` (default here) executes the kernel bodies in Python on
CPU — bit-correct validation of the TPU kernels in this container. On a
real TPU runtime set ``interpret=False`` (the wrappers are jit'd either
way and the BlockSpecs are the TPU tiling).
"""
from __future__ import annotations

from .frontier_min import frontier_min
from .lane_cumsum import lane_cumsum
from .minplus_sweep import minplus_sweep

__all__ = ["lane_cumsum", "frontier_min", "minplus_sweep"]
