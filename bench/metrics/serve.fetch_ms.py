"""Mean host time of one batch's ``serve.fetch`` span: the copy of the
synced batch state (and its superstep counts) to the host, inside
``serve.execute``."""
from bench import spans


def read(ctx):
    return spans.mean_ms(ctx, "serve.fetch")
