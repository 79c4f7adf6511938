"""Share of the dispatched bucket lanes that carried a request: the
program's ``serve.lanes`` counter (real lanes) over ``serve.bucket_lanes``
(lanes after padding to the bucket), summed over the window's
dispatches.  No counter: no value."""


def read(ctx):
    lanes = ctx.counters.get("serve.lanes", 0)
    bucket = ctx.counters.get("serve.bucket_lanes", 0)
    if not bucket:
        return None
    return 100.0 * lanes / bucket
