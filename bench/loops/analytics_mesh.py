"""Closed loop, one client, over a mesh: the analytics driver with the
configuration's partitions spread over every device the run is given
(the cell's ``chips``, which ``run.py`` checks are there) on a 1-D axis,
contiguous blocks of k / chips partitions each.  Jobs, sources, window,
checks and references are ``analytics.py``'s, so the one-chip and the
mesh cells differ only in the ``Engine``'s mesh."""
from __future__ import annotations

import jax
from jax.sharding import Mesh

from .analytics import Analytics


class AnalyticsMesh(Analytics):
    @property
    def eng(self):
        return self._eng

    @eng.setter
    def eng(self, engine):
        """The one-chip driver builds ``Engine(plan)`` and drops it with
        ``None``: every engine it builds gets the mesh."""
        if engine is not None:
            from repro import engine as E

            mesh = Mesh(jax.devices(), ("parts",))
            engine = E.Engine(engine.plan, mesh=mesh)
        self._eng = engine


LOOP = AnalyticsMesh
