"""Mean wait of a request from its due instant to the start of the
``serve.dispatch`` span of the batch that carried it (joined through the
``serve.batch`` span's request ids; batches answered from the cache have
no dispatch and are left out)."""
import numpy as np

from bench import spans as sp


def read(ctx):
    batches = {e["args"]["span_id"]: e["args"].get("requests", [])
               for e in sp.spans(ctx, "serve.batch")}
    loop = ctx.loop
    waits = []
    for d in sp.spans(ctx, "serve.dispatch"):
        start = ctx.t_obs0 + d["ts"] * 1e-6
        for rid in batches.get(d["args"].get("parent_id"), []):
            i = loop.index_of.get(rid)
            if i is not None:
                waits.append(start - (loop.t0 + loop.reqs.due[i]))
    return float(np.mean(waits) * 1e3) if waits else None
