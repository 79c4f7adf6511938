"""Mean host time of one batch's ``serve.materialize`` span (fanning the
lanes out to per-request results, filling the cache)."""
from bench import spans


def read(ctx):
    return spans.mean_ms(ctx, "serve.materialize")
