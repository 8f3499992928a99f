"""What an ``.xplane.pb`` holds about each device op beyond its name and time.

``jax.profiler.ProfileData`` surfaces an event's own stats only. The rest is
in the plane's ``event_metadata`` map, which every ``XLA Ops`` event points
into by ``metadata_id``: ``tf_op`` (the jax name stack of the op,
``jit(f)/layers/block/attention/...``), ``hlo_category`` (``convolution
fusion``, ``all-reduce``, ``custom-call`` ...), ``flops`` and
``bytes_accessed`` of one execution, ``program_id`` (the fingerprint in the
``XLA Modules`` event's name). This file reads exactly those, from the
protobuf wire format, with the standard library only (no ``xplane_pb2`` can be
counted on, and ``protobuf`` is not a dependency).

Field numbers (tsl/profiler/protobuf/xplane.proto; checked against the traces
under ``tests/benchmark/data``)::

    XSpace         planes=1
    XPlane         id=1 name=2 lines=3 event_metadata=4 stat_metadata=5   (maps: key=1 value=2)
    XLine          name=2 timestamp_ns=3 events=4
    XEvent         metadata_id=1 offset_ps=2 duration_ps=3 stats=4
    XStat          metadata_id=1 double=2 uint64=3 int64=4 str=5 bytes=6 ref=7
    XEventMetadata id=1 name=2 display_name=4 stats=5
    XStatMetadata  id=1 name=2

A ``ref`` stat's value is the NAME of the stat metadata it points to. Events
are joined to metadata by id, never by name: two programs can hold the same
instruction text. Times are seconds on the trace's clock, as ``ProfileData``
gives them (``line.timestamp_ns`` + ``offset_ps``).

On the v5e's traces (looked at, PR 24): ``flops`` of a fusion is what XLA's
cost analysis says it executes (a 2048^3 bf16 matmul with its tanh:
17 188 257 792); a Mosaic ``custom-call`` has no ``flops`` stat (XLA cannot
see into the kernel); ``bytes_accessed`` counts every operand and result at
every memory space, so on-chip traffic is in it (``memory_access_breakdown``
would split it, and is not read).
"""

from __future__ import annotations

import re
import struct
from typing import Dict, Iterator, List, NamedTuple, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
KEPT = ("tf_op", "hlo_category", "flops", "bytes_accessed", "program_id")


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message: an int for a varint,
    the raw bytes for the fixed and length-delimited types."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an xplane file?")
        yield key >> 3, wire, value


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf: bytes, stat_names: Dict[int, str]):
    """(name, value) of one XStat."""
    name, value = None, None
    for f, _, v in fields(buf):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = _signed(v)
        elif f == 5:
            value = v.decode("utf-8", "replace")
        elif f == 6:
            value = v
        elif f == 7:
            value = stat_names.get(v, "")
    return name, value


def _map_entry(buf: bytes) -> Tuple[int, bytes]:
    key, value = 0, b""
    for f, _, v in fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


class Op(NamedTuple):
    metadata_id: int
    start_s: float
    duration_s: float


class Plane(NamedTuple):
    name: str                       # "/device:TPU:0", "/host:CPU"
    metadata: Dict[int, dict]       # metadata_id -> {"name", "tf_op", "hlo_category", ...}
    lines: Dict[str, List[Op]]      # line name -> its events ("XLA Ops", "XLA Modules", "python3")


def _events(line: bytes) -> Tuple[str, List[Op]]:
    name, t0_ns, raw = "", 0, []
    for f, _, v in fields(line):
        if f == 2:
            name = v.decode("utf-8", "replace")
        elif f == 3:
            t0_ns = v
        elif f == 4:
            raw.append(v)
    t0, events = t0_ns * 1e-9, []
    for ev in raw:
        mid = offset_ps = duration_ps = 0
        for f, wire, v in fields(ev):
            if wire == 0:
                if f == 1:
                    mid = v
                elif f == 2:
                    offset_ps = v
                elif f == 3:
                    duration_ps = v
        events.append(Op(mid, t0 + offset_ps * 1e-12, duration_ps * 1e-12))
    return name, events


def _plane(name: str, buf: bytes, all_stats: bool) -> Plane:
    lines, event_md, stat_names = {}, [], {}
    for f, _, v in fields(buf):
        if f == 3:
            line_name, events = _events(v)
            lines.setdefault(line_name, []).extend(events)
        elif f == 4:
            event_md.append(_map_entry(v)[1])
        elif f == 5:
            sid, sname = 0, ""
            for g, _, w in fields(_map_entry(v)[1]):
                if g == 1:
                    sid = w
                elif g == 2:
                    sname = w.decode("utf-8", "replace")
            stat_names[sid] = sname
    metadata: Dict[int, dict] = {}
    for md in event_md:
        mid, entry = 0, {"name": ""}
        for f, _, v in fields(md):
            if f == 1:
                mid = v
            elif f == 2:
                entry["name"] = v.decode("utf-8", "replace")
            elif f == 5:
                stat, value = _stat(v, stat_names)
                if all_stats or stat in KEPT:
                    entry[stat] = value
        metadata[mid] = entry
    return Plane(name, metadata, lines)


def planes(data: bytes, wanted=lambda name: True, all_stats: bool = False) -> List[Plane]:
    """The planes of an XSpace whose name ``wanted`` accepts. ``all_stats``
    keeps every stat of the event metadata (for a dump), not only ``KEPT``."""
    found = []
    for f, _, plane in fields(data):
        if f != 1:
            continue
        name = next((v.decode("utf-8", "replace") for g, wire, v in fields(plane)
                     if g == 2 and wire == 2), "")
        if wanted(name):
            found.append(_plane(name, plane, all_stats))
    return found


def device_planes(data: bytes, all_stats: bool = False) -> List[Plane]:
    """The ``/device:TPU:<n>`` planes, by n."""
    found = planes(data, DEVICE_PLANE.match, all_stats)
    return sorted(found, key=lambda p: int(DEVICE_PLANE.match(p.name).group(1)))


def host_spans(data: bytes, prefix: str) -> List[Tuple[str, float, float]]:
    """(name, start, end) of the host planes' events whose name starts with
    ``prefix`` (the benchmark's ``bm:*`` ``TraceAnnotation``s)."""
    spans = []
    for plane in planes(data, lambda name: name.startswith("/host:")):
        for events in plane.lines.values():
            for ev in events:
                name = plane.metadata.get(ev.metadata_id, {}).get("name", "")
                if name.startswith(prefix):
                    spans.append((name, ev.start_s, ev.start_s + ev.duration_s))
    return spans
