"""Partition time over DFEP rounds (the program's ``info["rounds"]``)."""


def read(ctx):
    rounds = sum(i["rounds"] for i in ctx.loop.infos)
    return ctx.loop.window_s / rounds * 1e3 if rounds else None
