"""Device time of the cross-chip collectives per superstep: the leaf
operations of the trace whose opcode is ``all-reduce`` (or its async
``all-reduce-start`` / ``all-reduce-done`` halves), summed over every
chip's ``XLA Ops`` line, over the cell's chips and the program's
``engine.supersteps``.  The engine's collectives are all all-reduces: the
replica exchange's pmin/psum/pmax, the convergence flag's pmax and the
final gather's psums.  A traced run on a four-chip v5e host shows them
as synchronous ``all-reduce`` operations only; the time of each includes
its wait for the slowest chip.  No such operation: no value."""
import re

#: ``trace_reduce.short`` names an operation ``<opcode> <type> <name>``
OPCODE = re.compile(r"^all-reduce(-start|-done)? ")


def read(ctx):
    if not ctx.trace:
        return None
    times = [s for n, s in ctx.trace["ops"] if OPCODE.match(n)]
    steps = ctx.counters.get("engine.supersteps", 0)
    if not times or not steps:
        return None
    return sum(times) / int(ctx.cell["chips"]) / steps * 1e3
