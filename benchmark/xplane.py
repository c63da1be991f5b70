"""Reduction of a jax.profiler trace (.xplane.pb) to the numbers the
benchmark reports: device busy time, idle gaps with what the host was
doing in them, device time by op name, and the time of ops matching a
pattern. Only the "XLA Ops" line of a device plane is read: what part of a
collective runs beside other work ("Async XLA Ops" is a line of its own)
is not reduced yet, for want of a trace of more than one chip to pin it
on.

A device op is named by the program's stage scope it was traced under
and its instruction, ``dedup/merge/sort.279`` (``scope_path``), so that
time by op name is time by stage too. The host planes are read with
jax's own reader; the device planes, whose ops' metadata it does not
reach, through benchmark/xspace.py: one walk of the file a trace.

All times are seconds, all timestamps nanoseconds on the trace's clock.
Pinned on small recorded traces by benchmark/tests.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

from benchmark import xspace
from benchmark.xspace import DEVICE_PLANE

# gaps shorter than this are the device's own pauses between two ops of
# one program, not the host's doing
SHORT_GAP_NS = 50_000

HOST_PLANE = re.compile(r"^/host:CPU$")

# the program's stage scopes (``obs.stage``, a ``jax.named_scope``): the
# device members of raft_tpu.obs.events.TIMELINE_STAGES (the tests hold
# the two lists together)
STAGES = ("expand", "canon", "dedup", "emit", "exchange", "seen_merge")
# what an op under no stage is booked to
UNSCOPED = "-"
# elements of a name stack that are control flow, not a scope
_CONTROL = ("while", "body", "cond", "closed_call")


@dataclasses.dataclass
class Trace:
    """devices: plane name -> [(start_ns, end_ns, op name)], sorted by
    start, nested ops included (a `while` holds its body's ops).
    host: [(start_ns, end_ns, name)] of every host span.
    scoped: some op carries a stage scope, and so every op's name starts
    with its scope path (``-`` under no stage)."""

    devices: dict
    host: list
    scoped: bool = False


def scope_path(tf_op, levels: int = 2) -> tuple:
    """The stage scope an op was traced under, ``levels`` deep: ("dedup",
    "merge"), ("expand",), () under no stage. ``tf_op`` is the op's JAX
    name stack (``jit(_wave_step)/while/body/canon/inchunk/sort``; a
    fusion carries that of its root op). The stage is the outermost
    element that is one of ``STAGES``; below it count the named scopes
    alone: not a transform (``vmap()``, ``jit(f)``), a function's name
    (``DeviceBFS._st_expand``), control flow, a closed call's repeat of
    the prefix, nor the last element, which is the op itself."""
    parts = tf_op.split("/") if isinstance(tf_op, str) else []
    for i, part in enumerate(parts):
        if part in STAGES:
            inner = [p for p in parts[i + 1:-1]
                     if "(" not in p and "." not in p
                     and p not in _CONTROL + STAGES
                     and not p.startswith("branch_")]
            return (part, *inner[:levels - 1])
    return ()


def op_name(text: str) -> str:
    """The TPU profiler names an op by its whole HLO line,
    `%fusion.7 = u32[...] fusion(...), kind=kCustom, calls=...`: keep
    the instruction's name and, of a fusion, its kind."""
    name, _, rest = text.partition(" = ")
    kind = re.search(r"kind=k(\w+)", rest)
    return name.lstrip("%") + (f"[{kind.group(1)}]" if kind else "")


def scoped_name(tf_op, text: str) -> str:
    """``canon/inchunk/fusion.1240[Custom]``, ``-/copy.12``: an op's
    scope path, then its name."""
    return "/".join((*(scope_path(tf_op) or (UNSCOPED,)), op_name(text)))


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Trace:
    """Read an .xplane.pb. On the CPU backend there is no device plane:
    the ops are host events that carry an `hlo_op` stat, and they stand
    in, bare-named, for one device (rehearsal only)."""
    from jax.profiler import ProfileData

    walked = xspace.device_ops(path)
    # a trace of a program without scopes keeps its bare names
    scoped = any(scope_path(tf_op) for _events, _names, tf_ops
                 in walked.values() for tf_op in tf_ops.values())
    devices: dict = {}
    for plane, (events, names, tf_ops) in walked.items():
        label = {meta: scoped_name(tf_ops.get(meta), name) if scoped
                 else op_name(name) for meta, name in names.items()}
        devices[plane] = [(s, e, label[meta]) for s, e, meta in events]
    host: list = []
    for plane in ProfileData.from_file(path).planes:
        if not HOST_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("$"):  # python tracer frames
                    continue
                span = (int(e.start_ns),
                        int(e.start_ns + e.duration_ns), e.name)
                # the stats are read only where they decide anything:
                # a chip's trace has hundreds of thousands of host events
                if not walked and any(k == "hlo_op" for k, _ in e.stats):
                    devices.setdefault(plane.name, []).append(span)
                else:
                    host.append(span)
    for ops in devices.values():
        ops.sort()
    return Trace(devices=devices, host=host, scoped=scoped)


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
