"""Job time over supersteps on the single-job path: the summed host time
of the window's ``Engine.run`` calls over the ``engine.supersteps``
counter the program records."""


def read(ctx):
    steps = ctx.counters.get("engine.supersteps", 0)
    if not steps:
        return None
    return sum(ctx.loop.job_s) / steps * 1e3
