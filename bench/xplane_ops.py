"""A profiler trace (`.xplane.pb`) read with the standard library alone.

`jax.profiler.ProfileData` gives each event's name and times but not the
stats its metadata carries, and the `tf_op` stat of a device operation is
where its jax name scope lives (`jit(bfs_step)/.../push/expand/...`). This
module decodes the protobuf wire format of the file directly, so it needs
neither tensorflow nor the protobuf package:

    XSpace.planes (1) -> XPlane: name (2), lines (3), event_metadata (4),
                                 stat_metadata (5)
    XLine: name (2), timestamp_ns (3), events (4)
    XEvent: metadata_id (1), offset_ps (2), duration_ps (3), stats (4)
    XEventMetadata: id (1), name (2), stats (5)
    XStatMetadata: id (1), name (2)
    XStat: metadata_id (1), double (2), uint64 (3), int64 (4), str (5),
           bytes (6), ref (7: the id of a stat metadata whose name is the
           value)

Times come out in whole nanoseconds as `ProfileData` gives them: the
line's `timestamp_ns` plus the event's offset, each cut to the nanosecond.
"""

from __future__ import annotations

import struct
from typing import Dict, Iterator, List, NamedTuple, Tuple


class Event(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    stats: Dict[str, object]    # the metadata's stats, then the event's


class Plane(NamedTuple):
    name: str
    lines: Dict[str, List[Event]]


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message: an int for the
    varint and fixed types, a bytes object for a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 1:
            v = buf[i:i + 8]
            i += 8
        elif wt == 2:
            size, i = _varint(buf, i)
            v = buf[i:i + size]
            i += size
        elif wt == 5:
            v = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield num, wt, v


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _map_entry(buf: bytes) -> Tuple[int, bytes]:
    key, val = 0, b""
    for num, _wt, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def _stat(buf: bytes, stat_names: Dict[int, str]) -> Tuple[str, object]:
    mid, value = 0, None
    for num, _wt, v in _fields(buf):
        if num == 1:
            mid = v
        elif num == 2:
            value = struct.unpack("<d", v)[0]
        elif num == 3:
            value = v
        elif num == 4:
            value = _signed(v)
        elif num == 5:
            value = bytes(v).decode("utf-8", "replace")
        elif num == 6:
            value = bytes(v)
        elif num == 7:
            value = stat_names.get(v, "")
    return stat_names.get(mid, str(mid)), value


def _plane(buf: bytes) -> Plane:
    name = ""
    lines_raw, ev_meta_raw, stat_names = [], [], {}
    for num, _wt, v in _fields(buf):
        if num == 2:
            name = bytes(v).decode()
        elif num == 3:
            lines_raw.append(v)
        elif num == 4:
            ev_meta_raw.append(v)
        elif num == 5:
            sid, meta = _map_entry(v)
            for mnum, _mwt, mv in _fields(meta):
                if mnum == 2:
                    stat_names[sid] = bytes(mv).decode()
    ev_meta: Dict[int, Tuple[str, Dict[str, object]]] = {}
    for raw in ev_meta_raw:
        eid, meta = _map_entry(raw)
        ename, estats = "", {}
        for num, _wt, v in _fields(meta):
            if num == 2:
                ename = bytes(v).decode("utf-8", "replace")
            elif num == 5:
                k, val = _stat(v, stat_names)
                estats[k] = val
        ev_meta[eid] = (ename, estats)
    lines: Dict[str, List[Event]] = {}
    for raw in lines_raw:
        lname, ts, events = "", 0, []
        for num, _wt, v in _fields(raw):
            if num == 2:
                lname = bytes(v).decode()
            elif num == 3:
                ts = _signed(v)
            elif num == 4:
                events.append(v)
        out = lines.setdefault(lname, [])
        for ev in events:
            mid = off = dur = 0
            stats: Dict[str, object] = {}
            for num, _wt, v in _fields(ev):
                if num == 1:
                    mid = v
                elif num == 2:
                    off = _signed(v)
                elif num == 3:
                    dur = _signed(v)
                elif num == 4:
                    k, val = _stat(v, stat_names)
                    stats[k] = val
            ename, estats = ev_meta.get(mid, ("", {}))
            start = ts + off // 1000
            out.append(Event(ename, start, start + dur // 1000,
                             {**estats, **stats}))
    return Plane(name, lines)


def read(path: str) -> List[Plane]:
    """Every plane of the trace, each line's events in file order."""
    with open(path, "rb") as f:
        buf = f.read()
    return [_plane(v) for num, _wt, v in _fields(buf) if num == 1]
