"""Bytes each chip puts into the cross-chip collectives per superstep, in
KiB: the result types of the trace's ``all-reduce`` operations (or of
``all-reduce-start``, the first half of an async pair; ``-done`` repeats
its type), summed over every chip's ``XLA Ops`` line, over the cell's
chips and the program's ``engine.supersteps``.  So it reads what the
compiled all-reduces carried, whatever the program sends.  A tuple type
counts each of its arrays.  No such operation: no value."""
import re

#: ``trace_reduce.short`` names an operation ``<opcode> <type> <name>``
OPCODE = re.compile(r"^all-reduce(-start)? ")
#: one array of a result type: ``f32[12507]``, ``s32[]``, ``pred[8,4]``
ARRAY = re.compile(r"\b(pred|[a-z]+(\d+))\[([\d,]*)\]")


def type_bytes(name: str) -> int:
    """Bytes of the result type in an operation's short name."""
    total = 0
    for _, bits, dims in ARRAY.findall(name.split(" ", 1)[1]):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * (int(bits) // 8 if bits else 1)
    return total


def read(ctx):
    if not ctx.trace:
        return None
    sent = sum(type_bytes(n) for n, _ in ctx.trace["ops"] if OPCODE.match(n))
    steps = ctx.counters.get("engine.supersteps", 0)
    if not sent or not steps:
        return None
    return sent / int(ctx.cell["chips"]) / steps / 1024
