"""Roofline share of the ``gspmm`` Pallas kernel on the gcn_layer sweep
(feature width 8, the program's registered input width).

The least time of the algorithm's work in each call (``bench/counting.py``
over the chip's published peaks) over the summed device time of the
kernel's events in the trace.  No such event: no value."""
from bench import counting

#: the kernel's device operations, as ``trace_reduce.short`` names them:
#: a Pallas call whose operands are the flags, mask, weights and features
PREFIX = "pallas(s32,s32,f32,f32) "
WIDTH = 8


def read(ctx):
    if not ctx.trace:
        return None
    times = [s for n, s in ctx.trace["ops"] if n.startswith(PREFIX)]
    if not times or sum(times) <= 0:
        return None
    loop = ctx.loop
    fl, moved = counting.gspmm(
        2 * len(loop.dep.u), int(loop.plan.sum_local_vertices), WIDTH)
    least = counting.least_s(fl, moved, ctx.peaks)
    return len(times) * least / sum(times) * 100.0
