"""What `jax.profiler.ProfileData` leaves out of a `*.xplane.pb`: the stats
of an event's METADATA. The profiler keeps what is the same for every run of
one operation — on a TPU the framework-op path it was lowered from
(`jit(qw_solo_k10)/jit(main)/aggs.terms/scatter-add`), its category, its
source line — once, on the event's metadata record, and `ProfileEvent.stats`
yields only the stats of the event itself. This reads them with the standard
library alone, straight from the protobuf wire format of the few messages
involved (tsl/profiler/protobuf/xplane.proto):

    XSpace         planes = 1
    XPlane         name = 2, lines = 3, event_metadata = 4 (map),
                   stat_metadata = 5 (map)
    XLine          name = 2, events = 4
    XEvent         metadata_id = 1
    XEventMetadata id = 1, name = 2, display_name = 4, stats = 5
    XStatMetadata  id = 1, name = 2
    XStat          metadata_id = 1, double = 2, uint64 = 3, int64 = 4,
                   str = 5, bytes = 6, ref = 7 (the id of a stat metadata
                   whose name is the value)

Only planes whose name starts with the given prefix are opened; everything
else is skipped by its length.
"""

from __future__ import annotations

import struct

VARINT, FIXED64, BYTES, FIXED32 = 0, 1, 2, 5


def fields(buf: memoryview):
    """(field number, wire type, value) of one message: an int for a varint,
    the raw bytes for fixed widths, a view for a length-delimited field."""
    at, end = 0, len(buf)
    while at < end:
        key, at = varint(buf, at)
        number, kind = key >> 3, key & 7
        if kind == VARINT:
            value, at = varint(buf, at)
        elif kind == BYTES:
            size, at = varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif kind == FIXED64:
            value, at = buf[at:at + 8], at + 8
        elif kind == FIXED32:
            value, at = buf[at:at + 4], at + 4
        else:
            raise ValueError(f"wire type {kind} at byte {at}")
        yield number, kind, value


def varint(buf: memoryview, at: int) -> tuple:
    result, shift = 0, 0
    while True:
        byte = buf[at]
        at += 1
        result |= (byte & 0x7F) << shift
        if byte < 0x80:
            return result, at
        shift += 7


def signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def map_entry(buf: memoryview) -> tuple:
    key, value = 0, memoryview(b"")
    for number, _, field in fields(buf):
        if number == 1:
            key = field
        elif number == 2:
            value = field
    return key, value


def stat(buf: memoryview, stat_names: dict) -> tuple:
    """(name, value) of one XStat."""
    name, value = None, None
    for number, kind, field in fields(buf):
        if number == 1:
            name = stat_names.get(field, str(field))
        elif number == 2 and kind == FIXED64:
            value = struct.unpack("<d", field)[0]
        elif number == 3:
            value = field
        elif number == 4:
            value = signed(field)
        elif number in (5, 6):
            value = bytes(field).decode(errors="replace")
        elif number == 7:
            value = stat_names.get(field, str(field))
    return name, value


def plane_metadata(plane: memoryview) -> tuple:
    """(name, {line: [metadata id of each event, in file order]},
    {metadata id: {"name", "display_name", stat: value, ...}})."""
    name, lines, events, stats = "", [], [], []
    for number, _, field in fields(plane):
        if number == 2:
            name = bytes(field).decode(errors="replace")
        elif number == 3:
            lines.append(field)
        elif number == 4:
            events.append(field)
        elif number == 5:
            stats.append(field)
    stat_names = {}
    for entry in stats:
        key, value = map_entry(entry)
        for number, _, field in fields(value):
            if number == 2:
                stat_names[key] = bytes(field).decode(errors="replace")
    metadata = {}
    for entry in events:
        key, value = map_entry(entry)
        record: dict = {}
        for number, _, field in fields(value):
            if number == 2:
                record["name"] = bytes(field).decode(errors="replace")
            elif number == 4:
                record["display_name"] = bytes(field).decode(errors="replace")
            elif number == 5:
                stat_name, stat_value = stat(field, stat_names)
                if stat_name is not None and stat_value is not None:
                    record[stat_name] = stat_value
        metadata[key] = record
    by_line: dict = {}
    for line in lines:
        line_name, ids = "", []
        for number, _, field in fields(line):
            if number == 2:
                line_name = bytes(field).decode(errors="replace")
            elif number == 4:
                ids.append(next((value for n, _, value in fields(field)
                                 if n == 1), 0))
        by_line.setdefault(line_name, []).extend(ids)
    return name, by_line, metadata


def event_metadata(path: str, plane_prefix: str) -> dict:
    """{plane: {line: [the metadata record of each event, in file order]}}
    for the planes whose name starts with `plane_prefix`."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out = {}
    for number, kind, plane in fields(space):
        if number != 1 or kind != BYTES:
            continue
        name = next((bytes(f).decode(errors="replace")
                     for n, _, f in fields(plane) if n == 2), "")
        if not name.startswith(plane_prefix):
            continue
        _, by_line, metadata = plane_metadata(plane)
        out[name] = {line: [metadata.get(i, {}) for i in ids]
                     for line, ids in by_line.items()}
    return out
