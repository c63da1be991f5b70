"""Reduction of a jax.profiler trace (.xplane.pb) to the numbers the
benchmark reports: device busy time, idle gaps with what the host was
doing in them, device time by op name, and the time of ops matching a
pattern. Only the "XLA Ops" line of a device plane is read: what part of a
collective runs beside other work ("Async XLA Ops" is a line of its own)
is not reduced yet, for want of a trace of more than one chip to pin it
on.

All times are seconds, all timestamps nanoseconds on the trace's clock.
Pinned on a small recorded trace by benchmark/tests.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

# gaps shorter than this are the device's own pauses between two ops of
# one program, not the host's doing
SHORT_GAP_NS = 50_000

# On a device plane the ops are on this line; the other lines ("Steps",
# "XLA Modules", "XLA TraceMe", ...) repeat the same time at a coarser
# grain and would count it twice.
OP_LINE = re.compile(r"^XLA Ops")
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
HOST_PLANE = re.compile(r"^/host:CPU$")


@dataclasses.dataclass
class Trace:
    """devices: plane name -> [(start_ns, end_ns, op name)], sorted by
    start, nested ops included (a `while` holds its body's ops).
    host: [(start_ns, end_ns, name)] of every host span."""

    devices: dict
    host: list


def op_name(text: str) -> str:
    """The TPU profiler names an op by its whole HLO line,
    `%fusion.7 = u32[...] fusion(...), kind=kCustom, calls=...`: keep
    the instruction's name and, of a fusion, its kind."""
    name, _, rest = text.partition(" = ")
    kind = re.search(r"kind=k(\w+)", rest)
    return name.lstrip("%") + (f"[{kind.group(1)}]" if kind else "")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Trace:
    """Read an .xplane.pb with jax's own reader. On the CPU backend there
    is no device plane: the ops are host events that carry an `hlo_op`
    stat, and they stand in for one device (rehearsal only)."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    on_cpu = not any(DEVICE_PLANE.match(p.name) for p in planes)
    devices: dict = {}
    host: list = []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if OP_LINE.match(line.name):
                    ops.extend(
                        (int(e.start_ns), int(e.start_ns + e.duration_ns),
                         op_name(e.name)) for e in line.events)
        elif HOST_PLANE.match(plane.name):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("$"):  # python tracer frames
                        continue
                    span = (int(e.start_ns),
                            int(e.start_ns + e.duration_ns), e.name)
                    # the stats are read only where they decide anything:
                    # a chip's trace has hundreds of thousands of host events
                    if on_cpu and any(k == "hlo_op" for k, _ in e.stats):
                        devices.setdefault(plane.name, []).append(span)
                    else:
                        host.append(span)
    for ops in devices.values():
        ops.sort()
    return Trace(devices=devices, host=host)


def union(intervals) -> list:
    """Sorted, merged copy of [(start, end), ...]."""
    merged: list = []
    for s, e in sorted((i[0], i[1]) for i in intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def total_s(intervals) -> float:
    return sum(e - s for s, e in intervals) / 1e9


def subtract(a, b) -> list:
    """Parts of the merged intervals ``a`` that no interval of the merged
    ``b`` covers."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def self_pieces(ops) -> list:
    """[(start, end, name)]: each op cut down to the time its nested ops
    do not cover, so that the pieces of one line never overlap. ``ops``
    sorted by start; nesting is by containment, as the profiler writes a
    `while` and its body."""
    out = []
    stack: list = []  # [end, name, start of the open piece]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            e, name, cur = stack.pop()
            if e > cur:
                out.append((cur, e, name))
            if stack:
                stack[-1][2] = max(stack[-1][2], e)

    for s, e, name in ops:
        close(s)
        if stack:
            if s > stack[-1][2]:
                out.append((stack[-1][2], s, stack[-1][1]))
            stack[-1][2] = max(stack[-1][2], s)
        stack.append([e, name, s])
    close(float("inf"))
    out.sort()
    return out


def busy_s(trace: Trace) -> float | None:
    """Seconds in which an op ran, averaged over the devices traced."""
    if not trace.devices:
        return None
    per = [total_s(union(ops)) for ops in trace.devices.values()]
    return sum(per) / len(per)


def op_time_by_name(trace: Trace, top: int = 10) -> list:
    """[[name, seconds]] by self time, summed over devices, largest
    first."""
    acc: dict = {}
    for ops in trace.devices.values():
        for s, e, name in self_pieces(ops):
            acc[name] = acc.get(name, 0) + (e - s)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / 1e9] for name, ns in ranked]


def regex_s(trace: Trace, pattern: str) -> float | None:
    """Seconds of ops whose name matches ``pattern``, by self time,
    averaged over devices."""
    if not trace.devices:
        return None
    rx = re.compile(pattern)
    per = [total_s(union(p for p in self_pieces(ops) if rx.search(p[2])))
           for ops in trace.devices.values()]
    return sum(per) / len(per)


def _covering(spans, starts, ends, at):
    """Name of the shortest of ``spans`` that covers ``at``, or None."""
    cover = np.flatnonzero((starts <= at) & (ends > at))
    if not len(cover):
        return None
    return spans[cover[np.argmin((ends - starts)[cover])]][2]


def idle_gaps(trace: Trace, start_ns: int, end_ns: int, phases=(),
              top: int = 10) -> list:
    """[["phase/host span", seconds]]: the time inside [start_ns, end_ns]
    in which no op ran on the first device, summed by what the host was
    doing in the middle of each gap: the phase (one of the caller's
    ``phases`` spans, "-" outside them) and the shortest, so innermost,
    host span of the trace. Largest first."""
    if not trace.devices:
        return []
    ops = trace.devices[sorted(trace.devices)[0]]
    gaps = subtract([[start_ns, end_ns]], union(ops))
    arrays = [
        (spans, np.array([sp[0] for sp in spans], dtype=np.int64),
         np.array([sp[1] for sp in spans], dtype=np.int64))
        for spans in (list(phases), trace.host)
    ]
    acc: dict = {}
    for s, e in gaps:
        if e - s < SHORT_GAP_NS:
            name = "(between ops)"
        else:
            mid = (s + e) // 2
            name = "/".join(_covering(*a, mid) or "-" for a in arrays)
        acc[name] = acc.get(name, 0) + (e - s)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / 1e9] for name, ns in ranked]


def span_named(trace: Trace, name: str):
    """The first host span with this name, or None."""
    hits = [sp for sp in trace.host if sp[2] == name]
    return min(hits) if hits else None
