"""The metadata table of a ``jax.profiler`` trace, which ``ProfileData`` hides.

Every ``XLA Ops`` event of a device plane points at an event-metadata record
of its plane whose stats say what the operation IS: ``tf_op`` (the
operation's JAX name stack, with the program's ``jax.named_scope``s in it:
``jit(step_fn)/while/body/llmd.attn.proj/dot_general``), ``hlo_category``,
``flops``, ``bytes_accessed``, ``program_id`` and ``source`` (file:line).
``jax.profiler.ProfileData`` (jax 0.9.0) shows an event's name, start and
duration and nothing of this, not even which record an event points at.  An
event's ``name`` there is its record's ``name`` (the whole HLO line), and
two programs may hold the same line, so ``read`` returns the events of the
lines asked for with their record's id, and ``op_table`` the table by name
for a reader that has only ``ProfileData``'s events.

This is a reader of the XSpace wire format (protobuf, proto3) far enough
for that and nothing else: the standard library only, no TensorFlow, no
``xprof``, no generated ``_pb2``.  Field numbers are those of
``tsl/profiler/protobuf/xplane.proto``:

  XSpace          1 planes
  XPlane          2 name, 3 lines, 4 event_metadata (map), 5 stat_metadata
                  (map)
  map entry       1 key, 2 value
  XLine           2 name, 3 timestamp_ns, 4 events
  XEvent          1 metadata_id, 2 offset_ps, 3 duration_ps
  XEventMetadata  1 id, 2 name, 5 stats
  XStatMetadata   1 id, 2 name
  XStat           1 metadata_id, 2 double, 3 uint64, 4 int64, 5 str,
                  6 bytes, 7 ref (an id of the plane's stat_metadata, whose
                  name is the value)

A line that is not asked for is stepped over unparsed, and so are an
event's own stats.
"""

from __future__ import annotations

import struct
import sys
from typing import Any, Dict, Iterator, List, Sequence, Tuple

# What ``op_table`` keeps of an operation's stats.
OP_STATS = ("tf_op", "hlo_category", "flops", "bytes_accessed",
            "program_id", "source")
_VARINT, _FIXED64, _BYTES, _FIXED32 = 0, 1, 2, 5


def _varint(buf, at: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def fields(buf) -> Iterator[Tuple[int, int, Any]]:
    """(field number, wire type, value) of one message (bytes or a
    memoryview of them): an int for a varint, the raw bytes for a fixed or
    length-delimited field."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == _VARINT:
            value, at = _varint(buf, at)
        elif wire == _BYTES:
            size, at = _varint(buf, at)
            value = buf[at:at + size]
            at += size
        elif wire == _FIXED64:
            value = buf[at:at + 8]
            at += 8
        elif wire == _FIXED32:
            value = buf[at:at + 4]
            at += 4
        else:
            raise ValueError(f"wire type {wire} at byte {at}: not an XSpace")
        yield number, wire, value


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _stat(buf, stat_names: Dict[int, str]) -> Tuple[int, Any]:
    """(the stat's metadata id, its value): a string, a number, or the name
    a reference into the plane's stat-metadata table stands for."""
    key, value = 0, None
    for number, _, v in fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = struct.unpack("<d", v)[0]
        elif number == 3:
            value = v
        elif number == 4:
            value = _signed(v)
        elif number == 5:
            value = bytes(v).decode("utf-8", "replace")
        elif number == 6:
            value = bytes(v)
        elif number == 7:
            value = stat_names.get(v, "")
    return key, value


def _map_values(entries) -> Iterator[bytes]:
    for entry in entries:
        for number, _, v in fields(entry):
            if number == 2:
                yield v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _line(buf) -> Tuple[str, int, List[Any]]:
    name, t0, events = "", 0, []
    for number, _, v in fields(buf):
        if number == 2:
            name = _text(v)
        elif number == 3:
            t0 = _signed(v)
        elif number == 4:
            events.append(v)
    return name, t0, events


def _events(t0_ns: int, raw) -> List[Tuple[int, int, int]]:
    out = []
    for buf in raw:
        key = offset = duration = 0
        for number, wire, v in fields(buf):
            if wire != _VARINT:
                continue
            if number == 1:
                key = v
            elif number == 2:
                offset = v
            elif number == 3:
                duration = v
        start = t0_ns * 1000 + offset
        out.append((start, start + duration, key))
    return out


def _plane(buf, lines: Sequence[str]) -> Dict[str, Any]:
    name, line_bufs, event_entries, stat_entries = "", [], [], []
    for number, _, v in fields(buf):
        if number == 2:
            name = _text(v)
        elif number == 3:
            line_bufs.append(v)
        elif number == 4:
            event_entries.append(v)
        elif number == 5:
            stat_entries.append(v)
    stat_names: Dict[int, str] = {}
    for meta in _map_values(stat_entries):
        key, text = 0, ""
        for number, _, v in fields(meta):
            if number == 1:
                key = v
            elif number == 2:
                text = _text(v)
        stat_names[key] = text
    ops: Dict[int, Dict[str, Any]] = {}
    for meta in _map_values(event_entries):
        key, stats = 0, {"name": ""}
        for number, _, v in fields(meta):
            if number == 1:
                key = v
            elif number == 2:
                stats["name"] = _text(v)
            elif number == 5:
                stat, value = _stat(v, stat_names)
                if stat_names.get(stat) in OP_STATS:
                    stats[stat_names[stat]] = value
        ops[key] = stats
    found: Dict[str, List[Tuple[int, int, int]]] = {}
    for line_buf in line_bufs if lines else ():
        line, t0, raw = _line(line_buf)
        if line in lines:
            found.setdefault(line, []).extend(_events(t0, raw))
    return {"name": name, "ops": ops, "lines": found}


def read(path: str, lines: Sequence[str] = ()) -> List[Dict[str, Any]]:
    """The planes of the trace at ``path``: ``name``; ``ops``, {record id:
    {"name": the event name, and those of ``OP_STATS`` the record holds}};
    ``lines``, {line name: [(start, end in ps, record id)]} of the lines
    named in ``lines`` that the plane has."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    return [_plane(v, lines) for number, wire, v in fields(buf)
            if number == 1 and wire == _BYTES]


def _varint_bytes(value: int) -> bytes:
    out = bytearray()
    while True:
        out.append((value & 0x7F) | (0x80 if value > 0x7F else 0))
        value >>= 7
        if not value:
            return bytes(out)


def without_planes(buf, names: Sequence[str]) -> bytes:
    """The XSpace ``buf`` without the planes named in ``names``, every other
    byte as it was.  ``/host:metadata`` holds the traced programs' whole HLO
    protos (a megabyte a program with its Mosaic kernels), which no reader
    here reads: a recorder drops it to keep a test trace small."""
    out = bytearray()
    for number, wire, v in fields(memoryview(buf)):
        if wire != _BYTES:
            raise ValueError("an XSpace holds length-delimited fields only")
        if number == 1 and any(
                n == 2 and _text(x) in names for n, _, x in fields(v)):
            continue
        out += _varint_bytes(number << 3 | _BYTES) + _varint_bytes(len(v))
        out += v
    return bytes(out)


def op_table(path: str) -> Dict[str, Dict[str, Dict[str, Any]]]:
    """{plane name: {event name: {stat: value}}}.  Of two records of one
    name (the same HLO line in two programs) the first that says where it
    came from is kept."""
    out = {}
    for plane in read(path):
        table = out.setdefault(plane["name"], {})
        for stats in plane["ops"].values():
            stats = dict(stats)
            name = stats.pop("name")
            if name not in table or (stats.get("tf_op")
                                     and not table[name].get("tf_op")):
                table[name] = stats
    return out


if __name__ == "__main__":
    for plane, ops in op_table(sys.argv[1]).items():
        named = sum(1 for s in ops.values() if s.get("tf_op"))
        print(f"{plane}: {len(ops)} event names, {named} with tf_op")
