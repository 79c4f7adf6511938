"""Window time over whole DFEP partitions; the window ends when the
partition in flight finishes."""


def read(ctx):
    return ctx.loop.window_s / len(ctx.loop.owners)
