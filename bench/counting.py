"""The least work of one kernel call, counted from the algorithm and not
from what an implementation emits: no padded lanes, no padding slots, no
scan passes.  A roofline share is the least time over the measured time.

One sweep of the engine reduces every live half-edge of every partition
once into its target vertex row:

  segment_scan  reads one value per live half-edge and state column
                (``width`` float32), writes one row per local vertex;
                one combine per value read.
  gspmm         reads the neighbour's feature row (``width`` float32) and
                the half-edge's weight (one float32) per live half-edge,
                writes one row per local vertex; a multiply and an add per
                feature.
"""
from __future__ import annotations

F32 = 4


def segment_scan(half_edges: int, local_rows: int, width: int
                 ) -> tuple[float, float]:
    """(flops, bytes) of one segment_scan call over the plan."""
    flops = half_edges * width
    moved = (half_edges * width + local_rows * width) * F32
    return float(flops), float(moved)


def gspmm(half_edges: int, local_rows: int, width: int
          ) -> tuple[float, float]:
    """(flops, bytes) of one gspmm call over the plan."""
    flops = 2 * half_edges * width
    moved = (half_edges * (width + 1) + local_rows * width) * F32
    return float(flops), float(moved)


def least_s(flops: float, moved: float, peaks: dict) -> float:
    """Least time on the chip: the larger of the two roofs."""
    return max(flops / peaks["flops_per_s"],
               moved / peaks["hbm_bytes_per_s"])
