"""Set-up time: process start to the first timed operation (graph,
owner array, program objects, warm-up of every shape the window uses)."""


def read(ctx):
    return ctx.setup_s
