"""Mean DFEP rounds per partition (a count)."""


def read(ctx):
    infos = ctx.loop.infos
    return sum(i["rounds"] for i in infos) / len(infos) if infos else None
