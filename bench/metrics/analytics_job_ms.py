"""Window time over whole analytics jobs; the window ends when the cycle
of jobs in flight finishes."""


def read(ctx):
    return ctx.loop.window_s / ctx.loop.attempted * 1e3
