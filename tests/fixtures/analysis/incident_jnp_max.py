# analysis-virtual-path: engine/runtime.py
"""Incident fixture — PR 6 observability-overhead regression.

The first cut of the engine instrumentation computed the convergence
gauge with ``jnp.max`` while building the recorder event.  Every recorded
superstep dispatched a fresh single-op XLA computation and
the served benchmark blew its 3% overhead budget.  The fix reduced
with numpy on the already-synced host copy; TS001 must flag the original
forever."""
import jax.numpy as jnp

from repro import obs as _obs


def materialize(result):
    host = result.block_until_ready()
    _obs.get().event(
        "engine.superstep",
        residual=float(jnp.max(jnp.abs(result.delta))),  # FLAG: TS001
    )
    return host
