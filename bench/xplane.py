"""Read a profiler `.xplane.pb` without TensorFlow: a protobuf wire decoder
for the few messages of tsl/profiler/protobuf/xplane.proto that the trace
reduction needs.

    XSpace.planes = 1
    XPlane: name = 2, lines = 3, event_metadata = 4 (map), stat_metadata = 5 (map)
    XLine: name = 2, timestamp_ns = 3, events = 4, display_name = 11
    XEvent: metadata_id = 1, offset_ps = 2, duration_ps = 3
    XEventMetadata: id = 1, name = 2, display_name = 4, stats = 5
    XStatMetadata: id = 1, name = 2
    XStat: metadata_id = 1, double = 2, uint64 = 3, int64 = 4, str = 5,
           bytes = 6, ref = 7

Times come out in picoseconds on the line's clock: timestamp_ns·1000 +
offset_ps.
"""
from __future__ import annotations

import dataclasses
import struct
from typing import Dict, List


def _varint(buf: bytes, i: int):
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _fields(buf: bytes):
    """(field number, value) pairs; value is an int or a bytes slice."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            val = buf[i:i + ln]
            i += ln
        elif wire == 1:
            val = struct.unpack_from("<q", buf, i)[0]
            i += 8
        elif wire == 5:
            val = struct.unpack_from("<i", buf, i)[0]
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, val


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


@dataclasses.dataclass
class Event:
    name: str
    start_ps: int
    dur_ps: int
    stats: Dict[str, object]


@dataclasses.dataclass
class Line:
    name: str
    events: List[Event]


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]


def _stat(buf: bytes, stat_names: Dict[int, str]):
    mid, val = 0, None
    for f, v in _fields(buf):
        if f == 1:
            mid = v
        elif f == 2:
            val = struct.unpack("<d", struct.pack("<q", v))[0]
        elif f in (3, 4):
            val = _signed(v) if f == 4 else v
        elif f == 5:
            val = bytes(v).decode("utf-8", "replace")
        elif f == 7:
            val = stat_names.get(v, v)
    return stat_names.get(mid, str(mid)), val


def _plane(buf: bytes) -> Plane:
    name, raw_lines, ev_meta_raw, stat_names = "", [], [], {}
    for f, v in _fields(buf):
        if f == 2:
            name = bytes(v).decode()
        elif f == 3:
            raw_lines.append(v)
        elif f == 4:
            ev_meta_raw.append(v)
        elif f == 5:
            for ef, ev in _fields(v):
                if ef == 2:
                    sid, sname = 0, ""
                    for sf, sv in _fields(ev):
                        if sf == 1:
                            sid = sv
                        elif sf == 2:
                            sname = bytes(sv).decode()
                    stat_names[sid] = sname
    meta: Dict[int, tuple] = {}
    for raw in ev_meta_raw:
        for ef, ev in _fields(raw):
            if ef != 2:
                continue
            mid, mname, disp, mstats = 0, "", "", {}
            for mf, mv in _fields(ev):
                if mf == 1:
                    mid = mv
                elif mf == 2:
                    mname = bytes(mv).decode("utf-8", "replace")
                elif mf == 4:
                    disp = bytes(mv).decode("utf-8", "replace")
                elif mf == 5:
                    k, val = _stat(mv, stat_names)
                    mstats[k] = val
            meta[mid] = (disp or mname, mstats)
    lines = []
    for raw in raw_lines:
        lname, ts_ns, events = "", 0, []
        raw_events = []
        for lf, lv in _fields(raw):
            if lf == 2:
                lname = bytes(lv).decode()
            elif lf == 11 and not lname:
                lname = bytes(lv).decode()
            elif lf == 3:
                ts_ns = lv
            elif lf == 4:
                raw_events.append(lv)
        for rev in raw_events:
            mid = off = dur = 0
            for ef, evv in _fields(rev):
                if ef == 1:
                    mid = evv
                elif ef == 2:
                    off = evv
                elif ef == 3:
                    dur = evv
            mname, mstats = meta.get(mid, (str(mid), {}))
            events.append(Event(mname, ts_ns * 1000 + off, dur, mstats))
        lines.append(Line(lname, events))
    return Plane(name, lines)


def read_planes(path: str) -> List[Plane]:
    """Every plane of the trace. An event's `stats` are its metadata's (for
    device ops: `tf_op`, the scope path; `hlo_category`); the events' own
    stats are not read."""
    with open(path, "rb") as fh:
        buf = fh.read()
    return [_plane(v) for f, v in _fields(buf) if f == 1]
