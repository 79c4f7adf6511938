"""Open-loop serving: requests fall due on a schedule drawn from the
seed.  A submitter thread hands each request to ``GraphServer.submit`` at
its due instant; the calling thread drains the server whenever work is
pending, so requests are admitted while batches execute.  Latency runs
from the due instant to the result, so a late submit is charged to the
request."""
from __future__ import annotations

import gc
import threading
import time

import numpy as np

from .. import gen
from ..loop import Check, Loop, clock, note
from ..reference import algos


class Serve(Loop):
    def setup(self) -> None:
        from repro import gserve as G
        from repro import stream as S

        dep, mix, cfg = self.dep, self.mix, self.dep.cfg
        self.reqs = gen.requests(mix, dep.n, self.seconds, self.seed)
        self.sess = S.StreamSession(
            dep.graph, S.StreamConfig(k=int(cfg["k"]), **cfg["stream"]),
            owner=dep.owner)
        self.buckets = tuple(cfg["serve"]["buckets"])
        # warm-up on a server of its own: every (program, bucket) shape,
        # each batch of distinct sources never asked before (a cache hit
        # would shrink the batch to a smaller bucket)
        warm = G.GraphServer.from_session(self.sess, buckets=self.buckets)
        fresh = iter(gen.rng(self.seed, "warm").permutation(dep.n).tolist())
        try:
            for kind in mix["mix"]:
                for b in self.buckets:
                    warm.serve([G.QueryRequest(kind, params={
                        "source": next(fresh) % dep.n}) for _ in range(b)])
        finally:
            warm.close()
        self.srv = G.GraphServer.from_session(self.sess,
                                              buckets=self.buckets)
        self.sample_idx = set(gen.sample(self.seed, len(self.reqs.due),
                                         int(mix["sample"]), "check").tolist())

    def _submit_all(self, t0: float, wake: threading.Event,
                    stop: threading.Event) -> None:
        """The submitter thread: each request at its due instant."""
        from repro import gserve as G

        reqs, srv = self.reqs, self.srv
        for i in range(len(reqs.due)):
            wait = t0 + reqs.due[i] - clock()
            if wait > 0:
                time.sleep(wait)
            if stop.is_set():
                return
            req = G.QueryRequest(reqs.kind[i], tenant=reqs.tenant[i],
                                 params={"source": int(reqs.source[i])})
            self.index_of[req.id] = i
            self.submit_t[i] = clock()
            try:
                with note("submit"):
                    srv.submit(req)
            except G.AdmissionError:
                self.refused[i] = True
            wake.set()

    def window(self) -> None:
        reqs, srv = self.reqs, self.srv
        n_req = len(reqs.due)
        self.submit_t = np.full(n_req, np.nan)
        self.done_t = np.full(n_req, np.nan)
        self.refused = np.zeros(n_req, bool)
        self.errors = np.zeros(n_req, bool)
        self.values: dict[int, np.ndarray] = {}
        self.index_of = index_of = {}   # request id -> request index
        wake, stop = threading.Event(), threading.Event()
        self.gc_pauses: list[float] = []
        gc_start = [0.0]

        def gc_watch(phase, info):
            if phase == "start":
                gc_start[0] = clock()
            else:
                self.gc_pauses.append(clock() - gc_start[0])

        gc.callbacks.append(gc_watch)
        t0 = clock()
        submitter = threading.Thread(target=self._submit_all,
                                     args=(t0, wake, stop),
                                     name="bench-submit")
        submitter.start()
        try:
            while True:
                last = not submitter.is_alive()
                wake.clear()
                if srv.pending():
                    with note("drain"):
                        results = srv.drain()
                    for r in results:
                        i = index_of[r.request.id]
                        self.done_t[i] = self.submit_t[i] + r.latency_s
                        if r.error is not None:
                            self.errors[i] = True
                        elif i in self.sample_idx:
                            self.values[i] = r.value
                    continue
                if last:
                    break
                with note("idle"):
                    wake.wait(0.01)
        finally:
            stop.set()
            submitter.join()
            gc.callbacks.remove(gc_watch)
        self.t0 = t0
        self.t_end = clock()
        self.window_s = self.seconds
        self.attempted = n_req
        self.failed = int(self.refused.sum() + self.errors.sum())
        self.unanswered = int((np.isnan(self.done_t) & ~self.refused).sum())

    def notes(self) -> dict:
        """How late the submitter handed requests over (a starved client
        is not a fast server), the process's garbage-collector pauses in
        the window, and refusals."""
        late = (self.submit_t - (self.t0 + self.reqs.due)) * 1e3
        late = late[~np.isnan(late)]
        return {"submit_late_ms_p95": float(np.percentile(late, 95))
                if len(late) else 0.0,
                "submit_late_ms_max": float(late.max()) if len(late) else 0.0,
                "gc_pause_ms_max": max(self.gc_pauses, default=0.0) * 1e3,
                "gc_pause_ms_sum": sum(self.gc_pauses) * 1e3,
                "refused": int(self.refused.sum())}

    def latencies_ms(self) -> np.ndarray:
        """Due instant to result, every request due in the window; a
        refused or unanswered request counts as waiting to the end."""
        due = self.t0 + self.reqs.due
        done = np.where(np.isnan(self.done_t), self.t_end, self.done_t)
        return (done - due) * 1e3

    def free(self) -> None:
        self.srv.close()
        self.srv = self.sess = None

    def _reference(self, precision: str) -> dict[int, np.ndarray]:
        """Reference answer of every sampled request."""
        csr = algos.Csr(self.dep.n, self.dep.u, self.dep.v)
        groups: dict[str, list[int]] = {}
        for i in sorted(self.values):
            groups.setdefault(self.reqs.kind[i], []).append(i)
        out = {}
        for kind, idx in sorted(groups.items()):
            for lo in range(0, len(idx), 64):
                part = idx[lo:lo + 64]
                want = getattr(algos, kind)(csr, self.reqs.source[part],
                                            precision=precision)
                out.update(zip(part, want))
        return out

    def plant_control(self) -> None:
        """Put the reference, computed in bfloat16, in the program's
        place: what ``checks`` must then refuse."""
        self.values.update(self._reference("bfloat16"))

    def checks(self) -> list[Check]:
        """Sampled answers against the float32 reference, and every
        request due in the window answered."""
        want = self._reference("float32")
        wrong = sum(int(algos.mismatches(got, want[i]) > 0)
                    for i, got in self.values.items())
        return [self.check("unanswered", self.unanswered),
                self.check("wrong_answers", wrong)]


LOOP = Serve
