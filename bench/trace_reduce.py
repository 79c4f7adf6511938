"""Reduce a JAX profiler trace of one window to the numbers the benchmark
reports: device busy time, per-operation device time, kernel events, and
the idle gaps named by what the host was doing.

The window is the host span ``bench.window`` that ``run.py`` opens around
it.  Busy time is the union of the intervals in which an operation ran on
a device's ``XLA Ops`` line, clipped to the window and averaged over the
devices.  Operations nest on that line (a ``while`` spans its body), so
per-operation time counts leaf operations only: those that contain no
other.  Their names are the HLO instruction text; ``short`` keeps the
opcode, the result type without layouts and the instruction name, and
marks Pallas kernels (``tpu_custom_call``) with the dtypes of their
operands, which is what tells the engine's kernels apart.

An idle gap is a stretch of the window with no operation on the device;
it is named by the innermost ``bench.*`` host span (other than the window
itself) that covers its midpoint, or ``host.other`` if none does.
"""
from __future__ import annotations

import collections
import pathlib
import re

import numpy as np

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
TOP = 10


_LAYOUT = re.compile(r"\{[^{}]*\}")
_INSTR = re.compile(r"^%?(\S+) = (.*?) ([a-z][a-z0-9\-]*)\((.*)$")


def short(name: str) -> str:
    """``<opcode> <result type> <instruction>``, layouts dropped; a Pallas
    kernel is ``pallas(<operand dtypes>) <result type>``."""
    text = name
    while True:
        stripped = _LAYOUT.sub("", text)
        if stripped == text:
            break
        text = stripped
    m = _INSTR.match(text)
    if not m:
        return text[:80]
    instr, rtype, opcode, rest = m.groups()
    if 'tpu_custom_call' in name:
        args = rest.split(")", 1)[0]
        dts = ",".join(re.findall(r"\b([a-z]+\d+|pred)\[", args))
        return f"pallas({dts}) {rtype}"
    return f"{opcode} {rtype[:60]} {instr}"


def _leaves(events: list[tuple[float, float, str]]
            ) -> list[tuple[float, float, str]]:
    """Events (sorted by start) that contain no later event."""
    out = []
    for i, (s, t, n) in enumerate(events):
        if i + 1 < len(events) and events[i + 1][0] < t:
            continue
        out.append((s, t, n))
    return out


def _device_planes(pd):
    return [p for p in pd.planes
            if p.name.startswith("/device:") and any(
                line.name == OPS_LINE for line in p.lines)]


def _host_spans(pd) -> list[tuple[float, float, str]]:
    """(start_ns, end_ns, name) of every ``bench.*`` host span."""
    out = []
    for p in pd.planes:
        if p.name.startswith("/device:"):
            continue
        for line in p.lines:
            for e in line.events:
                if e.name.startswith("bench."):
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name))
    return out


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge [N, 2] intervals (sorted by start) into disjoint ones."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    merged = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return np.asarray(merged, np.float64)


def reduce_profile(pd) -> dict:
    """Reduce a loaded ``jax.profiler.ProfileData``."""
    spans = _host_spans(pd)
    wins = [(s, e) for s, e, n in spans if n == WINDOW]
    if not wins:
        raise ValueError(f"no {WINDOW!r} host span in the trace")
    w0, w1 = wins[0]
    inner = sorted((s, e, n) for s, e, n in spans if n != WINDOW)
    in_s = np.asarray([s for s, _, _ in inner], np.float64)
    in_e = np.asarray([e for _, e, _ in inner], np.float64)
    planes = _device_planes(pd)
    if not planes:
        raise ValueError("no device plane with an 'XLA Ops' line")
    busy = []
    by_op: collections.Counter = collections.Counter()
    ops: list[tuple[str, float]] = []          # leaf (short name, seconds)
    gaps: collections.Counter = collections.Counter()
    for plane in planes:
        evs = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                s, t = e.start_ns, e.start_ns + e.duration_ns
                if t <= w0 or s >= w1:
                    continue
                evs.append((max(s, w0), min(t, w1), e.name))
        evs.sort(key=lambda x: (x[0], -x[1]))
        for s, t, n in _leaves(evs):
            name = short(n)
            by_op[name] += (t - s) * 1e-9
            ops.append((name, (t - s) * 1e-9))
        iv = _union(np.asarray([(s, t) for s, t, _ in evs],
                               np.float64).reshape(-1, 2))
        busy.append(float(np.sum(iv[:, 1] - iv[:, 0])) * 1e-9)
        edges = np.concatenate([[w0], iv.reshape(-1), [w1]]).reshape(-1, 2)
        for s, t in edges:
            if t <= s:
                continue
            mid = 0.5 * (s + t)
            cover = np.flatnonzero((in_s <= mid) & (in_e >= mid))
            # spans are sorted by start: the last cover is the innermost
            name = inner[cover[-1]][2] if len(cover) else "host.other"
            gaps[name] += (t - s) * 1e-9 / len(planes)
    return {
        "busy_s": float(np.mean(busy)),
        "window_s": (w1 - w0) * 1e-9,
        "ops": ops,
        "breakdown": {
            "device_ops": [[n, s] for n, s in by_op.most_common(TOP)],
            "idle_gaps": [[n, s] for n, s in gaps.most_common(TOP)]},
    }


def reduce_dir(trace_dir) -> dict:
    """Reduce the one ``*.xplane.pb`` file under ``trace_dir``."""
    from jax.profiler import ProfileData

    found = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_profile(ProfileData.from_file(str(found[-1])))
