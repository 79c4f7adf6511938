"""Split a traced window's device time by named scope and by kernel, and
name its idle gaps by the program's own spans.

This reads what ``trace_reduce`` leaves out of a profiler trace:

* the ``op_name`` path of each leaf operation (``xspace``), so device time
  falls under the ``jax.named_scope`` it was traced in: the innermost named
  scope on the path (a dotted name such as ``dfep.spread`` or
  ``engine.exchange``) wins; an operation under none falls under its
  executable, the outermost ``jit(<name>)`` on the path; one with no path
  under ``unscoped``;
* the kernels by name: a Pallas call (``tpu_custom_call``) is named by its
  HLO instruction less the ``.N`` suffix, which is the ``name=`` its
  ``pallas_call`` was given;
* the program's spans, which its recorder mirrors into the trace as host
  annotations: an idle gap is named by the innermost span (the program's
  or a ``bench.*`` one) that covers its midpoint on the thread that holds
  ``bench.window``; spans of other threads name no gap.

Leaf operations, the window and the averaging over devices are as in
``trace_reduce``, so the scopes of a window sum to the leaf time there;
``device_ops`` lists the costliest leaf operations with their scope and
path, which shows where the compiler left an operation without one.
``enclosed`` places such operations by the order the device ran them in:
the time of each leaf operation with no named scope whose nearest leaf
operations with one, before and after it on the device's line, carry the
same named scope, under that scope.
"""
from __future__ import annotations

import collections
import heapq
import re

import numpy as np

from . import trace_reduce, xspace

UNSCOPED = "unscoped"
TOP = 10

#: a program span or named scope: ``<layer>.<what>`` in lower case; a
#: transform may wrap it on a path (``vmap(engine.sweep)``)
_SPAN = re.compile(r"[a-z][a-z0-9_]*(?:\.[a-z0-9_]+)+")
_SCOPE = re.compile(r"(?:[a-z_]+\()*([a-z][a-z0-9_]*(?:\.[a-z0-9_]+)+)\)*")
_JIT = re.compile(r"jit\((.+)\)")
_INSTR = re.compile(r"%?([A-Za-z_][\w\-]*?)(?:\.\d+)? = ")


def op_path(stats: dict) -> str | None:
    """The ``op_name`` path of an ``XLA Ops`` event (its ``tf_op`` stat,
    ``<path>:<type>``), or None."""
    tf = stats.get("tf_op")
    if not tf:
        return None
    return tf.rsplit(":", 1)[0] if ":" in tf else tf


def scope_of(path: str | None) -> str:
    if not path:
        return UNSCOPED
    parts = path.split("/")
    for part in reversed(parts):
        m = _SCOPE.fullmatch(part)
        if m:
            return m.group(1)
    for part in parts:
        m = _JIT.fullmatch(part)
        if m:
            return m.group(1)
    return UNSCOPED


def kernel_of(name: str) -> str | None:
    """The name of a Pallas kernel's event, or None for any other op."""
    if "tpu_custom_call" not in name:
        return None
    m = _INSTR.match(name)
    return m.group(1) if m else None


def _window(planes) -> tuple[float, float, list]:
    """(start, end, the spans of its thread) of ``bench.window``."""
    for p in planes:
        if p.name.startswith("/device:"):
            continue
        for line in p.lines:
            for e in line.events:
                if e.name == trace_reduce.WINDOW:
                    spans = [s for s in line.events if s is not e
                             and _SPAN.fullmatch(s.name)]
                    return e.start_ns, e.end_ns, spans
    raise ValueError(f"no {trace_reduce.WINDOW!r} host span in the trace")


def _innermost(spans: list, times) -> list:
    """For each of the ascending ``times``, the name of the span covering
    it that started last (the one that ends first on a tie), or None: a
    sweep over the spans sorted by start, with a heap of the open ones."""
    heap: list = []
    out = []
    i = 0
    for t in times:
        while i < len(spans) and spans[i].start_ns <= t:
            s = spans[i]
            heapq.heappush(heap, (-s.start_ns, s.end_ns, i))
            i += 1
        while heap and heap[0][1] < t:     # ended: covers no later time
            heapq.heappop(heap)
        out.append(spans[heap[0][2]].name if heap else None)
    return out


def _named(scope: str) -> bool:
    return bool(_SPAN.fullmatch(scope))


def _enclosed(leaves: list) -> collections.Counter:
    """Of ``leaves``, (seconds, scope) in the order a device ran them, the
    time of those with no named scope that sit between two leaves of one
    named scope, by that scope."""
    before, last = [], None
    for _, scope in leaves:
        last = scope if _named(scope) else last
        before.append(last)
    out: collections.Counter = collections.Counter()
    after = None
    for (sec, scope), prev in zip(reversed(leaves), reversed(before)):
        if _named(scope):
            after = scope
        elif after is not None and after == prev:
            out[after] += sec
    return out


def reduce_space(data: bytes) -> dict:
    """Reduce a serialized ``XSpace`` of one traced window."""
    planes = xspace.planes(data, lambda plane, line: (
        not plane.startswith("/device:") or line == trace_reduce.OPS_LINE))
    w0, w1, spans = _window(planes)
    spans.sort(key=lambda s: s.start_ns)
    devices = [p for p in planes if p.name.startswith("/device:")
               and any(line.name == trace_reduce.OPS_LINE
                       for line in p.lines)]
    if not devices:
        raise ValueError("no device plane with an 'XLA Ops' line")
    scopes: collections.Counter = collections.Counter()
    by_op: collections.Counter = collections.Counter()
    kernels: dict[str, list] = {}
    gaps: collections.Counter = collections.Counter()
    enclosed: collections.Counter = collections.Counter()
    with_path = leaf_s = 0.0
    for plane in devices:
        evs = sorted(((max(e.start_ns, w0), min(e.end_ns, w1), e)
                      for line in plane.lines
                      if line.name == trace_reduce.OPS_LINE
                      for e in line.events
                      if e.end_ns > w0 and e.start_ns < w1),
                     key=lambda x: (x[0], -x[1]))
        leaves = []
        for s, t, e in trace_reduce._leaves(evs):
            sec = (t - s) * 1e-9
            path = op_path(e.stats)
            leaves.append((sec / len(devices), scope_of(path)))
            scopes[leaves[-1][1]] += sec / len(devices)
            by_op[trace_reduce.short(e.name), path] += sec / len(devices)
            leaf_s += sec
            with_path += sec if path else 0.0
            k = kernel_of(e.name)
            if k is not None:
                got = kernels.setdefault(k, [0.0, 0])
                got[0] += sec / len(devices)
                got[1] += 1
        enclosed.update(_enclosed(leaves))
        iv = trace_reduce._union(np.asarray(
            [(s, t) for s, t, _ in evs], np.float64).reshape(-1, 2))
        edges = np.concatenate([[w0], iv.reshape(-1), [w1]]).reshape(-1, 2)
        edges = edges[edges[:, 1] > edges[:, 0]]
        names = _innermost(spans, 0.5 * (edges[:, 0] + edges[:, 1]))
        for (s, t), name in zip(edges, names):
            gaps[name or "host.other"] += (t - s) * 1e-9 / len(devices)
    return {
        "scopes": dict(scopes.most_common()),
        "kernels": {k: {"s": s, "calls": n // len(devices)}
                    for k, (s, n) in kernels.items()},
        "path_share": with_path / leaf_s if leaf_s else 0.0,
        "device_scopes": [[n, s] for n, s in scopes.most_common(TOP)],
        "device_ops": [[n, scope_of(p), p, s]
                       for (n, p), s in by_op.most_common(TOP)],
        "idle_gaps": [[n, s] for n, s in gaps.most_common(TOP)],
        "enclosed": dict(enclosed.most_common()),
    }


def thread_stalls(data: bytes, stretches=(), top: int = 5) -> dict:
    """What the window's thread did while another thread stalled.

    ``threads``: for each thread other than the window's that records
    program spans (the serving cell's submitter), its count of spans by
    name and its ``top`` longest of each name.  ``stretches``: for each
    given ``(start, end)`` stretch, e.g. a late hand-over.  Each entry
    carries what overlapped it on the window's thread: its program spans
    (``main_spans``) and the runtime's own host events (``main_other``),
    each as ``[name, ms of overlap, count]``, the largest first; a
    stretch also carries the other threads' spans (``thread_spans``).
    Times in ms from the window's start."""
    planes = xspace.planes(
        data, lambda plane, line: not plane.startswith("/device:"))
    w0, w1, _ = _window(planes)
    main, threads = None, []
    for p in planes:
        for line in p.lines:
            names = {e.name for e in line.events}
            if trace_reduce.WINDOW in names:
                main = line
            elif any(_SPAN.fullmatch(n) for n in names):
                threads.append(line)
    if main is None:
        return {"threads": [], "stretches": []}

    def index(events):
        evs = [e for e in events if e.name != trace_reduce.WINDOW]
        return (evs, np.asarray([e.start_ns for e in evs], np.float64),
                np.asarray([e.end_ns for e in evs], np.float64))

    def overlap(idx, s: float, t: float, program: bool, n: int) -> list:
        evs, starts, ends = idx
        by: dict[str, list] = {}
        for j in np.flatnonzero((starts < t) & (ends > s)):
            name = evs[j].name
            if bool(_SPAN.fullmatch(name)) != program:
                continue
            got = by.setdefault(name, [name, 0.0, 0])
            got[1] += (min(t, ends[j]) - max(s, starts[j])) * 1e-6
            got[2] += 1
        return sorted(by.values(), key=lambda g: -g[1])[:n]

    m_idx = index(main.events)

    def around(s: float, t: float) -> dict:
        return {"at_ms": (s - w0) * 1e-6, "ms": (t - s) * 1e-6,
                "main_spans": overlap(m_idx, s, t, True, 2 * top),
                "main_other": overlap(m_idx, s, t, False, top)}

    out = {"threads": [], "stretches": []}
    for line in threads:
        evs = [e for e in line.events if _SPAN.fullmatch(e.name)
               and e.end_ns > w0 and e.start_ns < w1]
        by_name = collections.defaultdict(list)
        for e in evs:
            by_name[e.name].append(e)
        out["threads"].append({
            "line": f"{line.name}/{line.id}",
            "spans": {n: len(es) for n, es in by_name.items()},
            "longest": {n: [around(e.start_ns, e.end_ns) for e in sorted(
                es, key=lambda e: e.start_ns - e.end_ns)[:top]]
                for n, es in by_name.items()}})
    t_idx = index([e for line in threads for e in line.events])
    for s_ms, t_ms in stretches:
        s, t = w0 + s_ms * 1e6, w0 + t_ms * 1e6
        out["stretches"].append(
            {**around(s, t),
             "thread_spans": overlap(t_idx, s, t, True, 2 * top)})
    return out


def span_durations(data: bytes) -> dict[str, list[float]]:
    """The sorted durations (us) of the host events with a span's name."""
    out: dict[str, list[float]] = {}
    for p in xspace.planes(
            data, lambda plane, line: not plane.startswith("/device:")):
        for line in p.lines:
            for e in line.events:
                if _SPAN.fullmatch(e.name):
                    out.setdefault(e.name, []).append(
                        (e.end_ns - e.start_ns) / 1e3)
    return {n: sorted(d) for n, d in out.items()}
