"""Mean ``serve.execute`` span of one batch: the device sync of the
batched superstep loop and the copy of its state to the host."""
from bench import spans


def read(ctx):
    return spans.mean_ms(ctx, "serve.execute")
