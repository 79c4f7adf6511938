"""95th percentile of query latency over every request due in the window,
from its due instant to its result (open loop: a stall is charged to the
requests queued behind it)."""
import numpy as np


def read(ctx):
    lat = ctx.loop.latencies_ms()
    return float(np.percentile(lat, 95)) if len(lat) else None
