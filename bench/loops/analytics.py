"""Closed loop, one client: ``Engine.run`` cycles through the mix's jobs;
the window ends when the cycle in flight finishes."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import gen
from ..loop import Check, Loop, clock, note
from ..reference import algos

GCN_F_IN, GCN_F_OUT = 8, 4   # the program's registered gcn_layer widths


class Analytics(Loop):
    def setup(self) -> None:
        from repro import engine as E

        dep = self.dep
        self.plan = E.compile_plan(dep.graph, dep.owner, int(dep.cfg["k"]))
        self.eng = E.Engine(self.plan)
        self.deg = dep.graph.degrees()
        key = jax.random.key(self.seed % 2 ** 32)
        kx, kw = jax.random.split(key)
        make = jax.jit(lambda kx, kw: (
            jax.random.normal(kx, (dep.n, GCN_F_IN), jnp.float32),
            jax.random.normal(kw, (GCN_F_IN, GCN_F_OUT), jnp.float32)))
        self.x, self.w = make(kx, kw)
        self.kinds = list(self.mix["jobs"])
        self.sources = gen.jobs(dep.n, 1 << 16, self.seed)
        progs = {"sssp": E.SSSP, "bfs": E.BFS, "wcc": E.WCC,
                 "pagerank": E.PAGERANK, "gcn_layer": E.GCN_LAYER}
        steps = int(self.mix["pagerank_supersteps"])

        def job(kind: str, slot: int):
            kw = {}
            if kind in ("sssp", "bfs"):
                kw["source"] = jnp.int32(self.sources[slot])
            elif kind == "pagerank":
                kw = {"max_supersteps": steps, "degrees": self.deg}
            elif kind == "gcn_layer":
                kw = {"degrees": self.deg, "x": self.x, "weight": self.w}
            return self.eng.run(progs[kind], **kw)

        self.job = job
        for kind in self.kinds:
            with note("engine_run"):
                job(kind, 0)

    def window(self) -> None:
        kinds = self.kinds
        self.kept: dict[int, object] = {}
        self.job_s, self.steps = [], []
        t0 = clock()
        slot = 0
        while True:
            for kind in kinds:
                ts = clock()
                with note("engine_run"):
                    res = self.job(kind, slot)
                self.job_s.append(clock() - ts)
                self.steps.append(int(np.max(np.asarray(res.supersteps))))
                self.kept[slot] = res.state
                slot += 1
            if clock() - t0 >= self.seconds:
                break
        self.t0 = t0
        self.window_s = clock() - t0
        self.attempted = slot
        # keep only the sampled answers (the rest leave device memory)
        per = int(self.mix["sample_per_kind"])
        cycles = slot // len(kinds)
        keep = set()
        for j, kind in enumerate(kinds):
            for c in gen.sample(self.seed, cycles, per, f"check{kind}"):
                keep.add(int(c) * len(kinds) + j)
        self.kept = {s: np.asarray(self.kept[s]) for s in sorted(keep)}

    def free(self) -> None:
        self.x, self.w = np.asarray(self.x), np.asarray(self.w)
        self.eng = self.plan = self.deg = None

    def _reference(self, slot: int, precision: str | None):
        kind = self.kinds[slot % len(self.kinds)]
        p = {} if precision is None else {"precision": precision}
        csr = self._csr
        if kind in ("sssp", "bfs"):
            return getattr(algos, kind)(csr, [self.sources[slot]], **p)[0]
        if kind == "wcc":
            return algos.wcc(csr)
        if kind == "pagerank":
            return algos.pagerank(csr, int(self.mix["pagerank_supersteps"]),
                                  **p)
        return algos.gcn(csr, self.x, self.w, **p)

    def plant_control(self) -> None:
        self._csr = algos.Csr(self.dep.n, self.dep.u, self.dep.v)
        for slot in self.kept:
            self.kept[slot] = self._reference(slot, "bfloat16")

    def checks(self) -> list[Check]:
        """Sampled jobs: sssp, bfs and wcc exactly, pagerank and gcn by
        their largest gap to the float64 reference."""
        self._csr = algos.Csr(self.dep.n, self.dep.u, self.dep.v)
        wrong = 0
        gaps = {"pagerank": 0.0, "gcn_layer": 0.0}
        for slot, got in self.kept.items():
            kind = self.kinds[slot % len(self.kinds)]
            want = self._reference(slot, None)
            if kind in gaps:
                gaps[kind] = max(gaps[kind], algos.rel_gap(got, want))
            else:
                wrong += int(algos.mismatches(got, want) > 0)
        return [self.check("wrong_answers", wrong),
                self.check("pagerank_gap", gaps["pagerank"]),
                self.check("gcn_gap", gaps["gcn_layer"])]


LOOP = Analytics
