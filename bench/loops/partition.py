"""Closed loop: the program's DFEP back to back.  The window runs whole
cycles of a fixed pool of keys, in an order drawn from the seed, and ends
when the cycle in flight ends: every run partitions with the same keys
(whose round counts differ), the same number of times each."""
from __future__ import annotations

import jax
import numpy as np

from .. import gen
from ..loop import Check, Loop, clock, note
from ..reference import dfep as ref_dfep


class Partition(Loop):
    needs_owner = False

    def setup(self) -> None:
        from repro.core import dfep

        self.k = int(self.dep.cfg["k"])
        pool = int(self.mix["key_pool"])
        self.order = gen.rng(self.seed, "keys").permutation(pool).tolist()
        with note("dfep_partition"):
            # the warm-up key lies outside the pool
            owner, _ = dfep.partition(self.dep.graph, k=self.k, key=pool)
            # the leftover sweep runs only when the auction stalls: warm it
            jax.block_until_ready(dfep.finalize(self.dep.graph, owner,
                                                self.k))

    def key_of(self, i: int) -> int:
        """The key of the window's ``i``-th partition."""
        return int(self.order[i % len(self.order)])

    def window(self) -> None:
        from repro.core import dfep

        self.owners, self.infos = [], []
        t0 = clock()
        while True:
            for _ in self.order:
                with note("dfep_partition"):
                    owner, info = dfep.partition(
                        self.dep.graph, k=self.k,
                        key=self.key_of(len(self.owners)))
                    owner = np.asarray(owner)
                self.owners.append(owner[:len(self.dep.u)])
                self.infos.append(info)
            if clock() - t0 >= self.seconds:
                break
        self.t0 = t0
        self.window_s = clock() - t0
        self.attempted = len(self.owners)

    def _sampled(self) -> np.ndarray:
        return gen.sample(self.seed, len(self.owners),
                          int(self.mix["sample"]), "check")

    def _reference(self, i: int, precision: str) -> np.ndarray:
        owner, _ = ref_dfep.partition(self.dep.n, self.dep.u, self.dep.v,
                                      self.k, self.key_of(int(i)),
                                      precision=precision)
        return owner

    def plant_control(self) -> None:
        for i in self._sampled():
            self.owners[i] = self._reference(i, "bfloat16")

    def checks(self) -> list[Check]:
        """Sampled partitions against the reference DFEP, edge by edge."""
        bad = sum(int(np.count_nonzero(self.owners[i]
                                       != self._reference(i, "float32")))
                  for i in self._sampled())
        return [self.check("owner_mismatch", bad)]


LOOP = Partition
