"""Read a JAX profiler trace (an ``XSpace`` protobuf) with each event's
metadata stats, which ``jax.profiler.ProfileData`` does not expose.

What the benchmark needs from them is the ``tf_op`` stat of an ``XLA Ops``
event: the operation's ``op_name`` path, e.g.
``jit(run_dfep)/while/body/dfep.spread/gather``, which names the
``jax.named_scope``s the operation was traced under.  This is a plain
reader of the protobuf wire format for the few fields it uses, so it
needs no generated classes and no package beyond the standard library.

Times are as ``ProfileData`` gives them: whole nanoseconds, an event
starting at its line's ``timestamp_ns`` plus its offset.
"""
from __future__ import annotations

import dataclasses
import gzip
import pathlib
import struct

# field numbers of tsl/profiler/protobuf/xplane.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 3, 4, 5
_LINE_ID, _LINE_NAME, _LINE_TS, _LINE_EVENTS = 1, 2, 3, 4
_EVENT_META_ID, _EVENT_OFFSET, _EVENT_DURATION = 1, 2, 3
_META_NAME, _META_STATS = 2, 5
_STAT_META_ID, _STAT_DOUBLE, _STAT_STR, _STAT_REF = 1, 2, 5, 7


@dataclasses.dataclass
class Event:
    start_ns: int
    end_ns: int
    name: str
    stats: dict          # the event metadata's stats, by stat name


@dataclasses.dataclass
class Line:
    id: int
    name: str
    events: list[Event]


@dataclasses.dataclass
class Plane:
    name: str
    lines: list[Line]


def _varint(b: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(b: bytes):
    """(field number, value) of each field of one message: an int for a
    varint or fixed-width field, bytes for a length-delimited one."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 2:
            size, i = _varint(b, i)
            v = b[i:i + size]
            i += size
        elif kind == 1:
            v = struct.unpack_from("<q", b, i)[0]
            i += 8
        elif kind == 5:
            v = struct.unpack_from("<i", b, i)[0]
            i += 4
        else:
            raise ValueError(f"unsupported wire type {kind}")
        yield key >> 3, v


def _map_entry(b: bytes) -> tuple[int, bytes]:
    key, value = 0, b""
    for f, v in _fields(b):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _stat(b: bytes, stat_names: dict) -> tuple[str, object]:
    meta, value = 0, None
    for f, v in _fields(b):
        if f == _STAT_META_ID:
            meta = v
        elif f == _STAT_DOUBLE:
            value = struct.unpack("<d", struct.pack("<q", v))[0]
        elif f == _STAT_STR:
            value = v.decode("utf-8", "replace")
        elif f == _STAT_REF:
            value = stat_names.get(v, "")
        elif value is None:
            value = v
    return stat_names.get(meta, str(meta)), value


def _plane(b: bytes, want_lines) -> Plane:
    name, raw_lines, raw_meta, stat_names = "", [], {}, {}
    for f, v in _fields(b):
        if f == _PLANE_NAME:
            name = v.decode("utf-8", "replace")
        elif f == _PLANE_LINES:
            raw_lines.append(v)
        elif f == _PLANE_EVENT_META:
            k, m = _map_entry(v)
            raw_meta[k] = m
        elif f == _PLANE_STAT_META:
            k, m = _map_entry(v)
            stat_names[k] = next((s.decode("utf-8", "replace")
                                  for g, s in _fields(m) if g == _META_NAME),
                                 "")
    meta: dict[int, tuple[str, dict]] = {}

    def meta_of(mid: int) -> tuple[str, dict]:
        got = meta.get(mid)
        if got is None:
            ename, stats = "", {}
            for g, s in _fields(raw_meta.get(mid, b"")):
                if g == _META_NAME:
                    ename = s.decode("utf-8", "replace")
                elif g == _META_STATS:
                    k, val = _stat(s, stat_names)
                    stats[k] = val
            got = meta[mid] = (ename, stats)
        return got

    lines = []
    for raw in raw_lines:
        lid, lname, ts, evs = 0, "", 0, []
        for f, v in _fields(raw):
            if f == _LINE_ID:
                lid = v
            elif f == _LINE_NAME:
                lname = v.decode("utf-8", "replace")
            elif f == _LINE_TS:
                ts = v
            elif f == _LINE_EVENTS:
                evs.append(v)
        if not want_lines(name, lname):
            continue
        events = []
        for raw_ev in evs:
            mid = off = dur = 0
            for f, v in _fields(raw_ev):
                if f == _EVENT_META_ID:
                    mid = v
                elif f == _EVENT_OFFSET:
                    off = v
                elif f == _EVENT_DURATION:
                    dur = v
            ename, stats = meta_of(mid)
            start = ts + off // 1000
            events.append(Event(start, start + dur // 1000, ename, stats))
        lines.append(Line(lid, lname, events))
    return Plane(name, lines)


def planes(data: bytes, want_lines=lambda plane, line: True) -> list[Plane]:
    """The planes of a serialized ``XSpace``; only the lines for which
    ``want_lines(plane name, line name)`` holds are decoded."""
    return [_plane(v, want_lines) for f, v in _fields(data)
            if f == _SPACE_PLANES]


def read_dir(trace_dir) -> bytes:
    """The bytes of the one ``*.xplane.pb`` (or ``*.xplane.pb.gz``) file
    under ``trace_dir``; the newest if there are several."""
    root = pathlib.Path(trace_dir)
    found = sorted(root.rglob("*.xplane.pb")) or \
        sorted(root.rglob("*.xplane.pb.gz"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = found[-1].read_bytes()
    return gzip.decompress(data) if found[-1].suffix == ".gz" else data
