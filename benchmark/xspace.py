"""The part of an .xplane.pb that jax's own reader does not show: an
event's metadata.

``jax.profiler.ProfileData`` (what benchmark/xplane.py reads through)
gives an event its name, its times and its own stats. The stats the
compiler wrote for the *op* — ``tf_op`` (the JAX name stack, so the
``jax.named_scope`` an op was traced under), ``flops``,
``bytes_accessed``, ``hlo_category``, ``source`` — hang on the event's
``XEventMetadata``, which it does not reach. This module walks the
protobuf wire format itself, with the standard library alone (the one
ready-made ``xplane_pb2`` here is TensorFlow's: a 15 s import, and not
promised on the machine with the chip), and only as far as the readers
need: the device planes, their "XLA Ops" line, and those ops' metadata.

The schema (tsl/profiler/protobuf/xplane.proto), field numbers:

  XSpace          planes 1
  XPlane          name 2, lines 3, event_metadata 4 (map: key 1, value
                  2), stat_metadata 5 (map), stats 6
  XLine           name 2, timestamp_ns 3, events 4
  XEvent          metadata_id 1, offset_ps 2, duration_ps 3, stats 4
  XEventMetadata  id 1, name 2, stats 5
  XStatMetadata   id 1, name 2
  XStat           metadata_id 1, double 2, uint64 3, int64 4, str 5,
                  bytes 6, ref 7 (the id of a stat metadata whose NAME is
                  the value)
"""

from __future__ import annotations

import re

# On a device plane the ops are on this line; the other lines ("Steps",
# "XLA Modules", "XLA TraceMe", ...) repeat the same time at a coarser
# grain and would count it twice.
OP_LINE = re.compile(r"^XLA Ops")
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


def fields(buf):
    """(field number, wire type, value) of one message's top level: an
    int for a varint (type 0), a memoryview for a length-delimited field
    (type 2, not copied), raw 8 or 4 bytes for the fixed types."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        wire = key & 7
        if wire == 0:
            val = shift = 0
            while True:
                b = buf[i]
                i += 1
                val |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
        elif wire == 2:
            size = shift = 0
            while True:
                b = buf[i]
                i += 1
                size |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            val = buf[i:i + size]
            i += size
        elif wire == 1:
            val = buf[i:i + 8]
            i += 8
        elif wire == 5:
            val = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, wire, val


def text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def first(buf, number: int, default=None):
    for num, _wire, val in fields(buf):
        if num == number:
            return val
    return default


def map_entries(plane, number: int):
    """(key, value message) of a map field of a plane."""
    for num, _wire, entry in fields(plane):
        if num == number:
            key = value = None
            for n2, _w2, v2 in fields(entry):
                if n2 == 1:
                    key = v2
                elif n2 == 2:
                    value = v2
            if value is not None:
                yield key, value


def planes(buf):
    """(name, plane message) of an XSpace."""
    for num, _wire, plane in fields(buf):
        if num == 1:
            name = first(plane, 2)
            yield ("" if name is None else text(name)), plane


def op_stat(plane, stat_name: str) -> dict:
    """event metadata id -> the value of the op's ``stat_name`` stat, for
    the metadata that has one (a string for a str or ref stat)."""
    stat_names = {}
    for key, meta in map_entries(plane, 5):
        name = first(meta, 2)
        stat_names[key] = "" if name is None else text(name)
    wanted = {k for k, n in stat_names.items() if n == stat_name}
    out = {}
    for key, meta in map_entries(plane, 4):
        for num, _wire, stat in fields(meta):
            if num != 5:
                continue
            got = {n: v for n, _w, v in fields(stat)}
            if got.get(1) not in wanted:
                continue
            if 5 in got:
                out[key] = text(got[5])
            elif 7 in got:
                out[key] = stat_names.get(got[7], "")
            else:
                out[key] = next(
                    (got[n] for n in (2, 3, 4, 6) if n in got), None)
    return out


def op_events(plane):
    """[(start_ns, end_ns, event metadata id)] of a device plane's "XLA
    Ops" lines, by start; nested ops included, as in
    ``xplane.Trace.devices``."""
    out = []
    for num, _wire, line in fields(plane):
        if num != 3:
            continue
        name = first(line, 2)
        if name is None or not OP_LINE.match(text(name)):
            continue
        t0_ns = first(line, 3, 0)
        for n2, _w2, event in fields(line):
            if n2 != 4:
                continue
            meta = offset = dur = 0
            for n3, _w3, v3 in fields(event):
                if n3 == 1:
                    meta = v3
                elif n3 == 2:
                    offset = v3
                elif n3 == 3:
                    dur = v3
            # whole nanoseconds as jax's reader hands them out (start
            # and duration each cut down), so that these intervals are
            # xplane.load's to the digit
            start = t0_ns + offset // 1000
            out.append((start, start + dur // 1000, meta))
    out.sort()
    return out


def op_names(plane) -> dict:
    """event metadata id -> the op's name as the profiler wrote it (its
    whole HLO line on a TPU)."""
    out = {}
    for key, meta in map_entries(plane, 4):
        name = first(meta, 2)
        out[key] = "" if name is None else text(name)
    return out


def device_ops(path: str) -> dict:
    """plane name -> ([(start_ns, end_ns, metadata id)], {metadata id:
    name}, {metadata id: tf_op}) for each device plane of the file."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    return {
        name: (op_events(plane), op_names(plane), op_stat(plane, "tf_op"))
        for name, plane in planes(buf) if DEVICE_PLANE.match(name)
    }
