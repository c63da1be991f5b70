"""{"kind": "scope_time", "scope": <stage or null>, "per": <number or
name>}: device seconds of the ops traced under one stage scope of the
program (``jax.named_scope``: expand, canon, dedup, emit, seen_merge,
exchange), over ``per``.

An op's scope is in its metadata's ``tf_op``, the JAX name stack
(``jit(_wave_step)/while/body/canon/...``); a fusion carries that of its
root op. The time is self time, cut as ``xplane.self_pieces`` cuts it (a
``while`` does not count its body again), of the "XLA Ops" line, averaged
over the device planes. An op is booked to the outermost stage in its
name stack; ``"scope": null`` reads the rest: compiler-made ops with no
name stack (copies, the ``while`` and its condition) and ops traced under
no stage. The buckets therefore add up to ``xplane.busy_s``.

Reads the file (``ctx["trace_path"]``) through benchmark/xspace.py, once
for all the metrics of a run. Nothing where there is no file or no
device plane, and nothing where not one op of the trace carries a stage
scope: a program that has none (a commit before the scopes, or
executables from a compile cache that one filled) must not read as
"0 s of canon".
"""

from benchmark import xplane, xspace
from benchmark.readers import number

# the device members of raft_tpu.obs.events.TIMELINE_STAGES (the tests
# hold the two lists together)
STAGES = ("expand", "canon", "dedup", "emit", "exchange", "seen_merge")


def stage_of(tf_op):
    """The outermost stage scope of a name stack, or None."""
    if isinstance(tf_op, str):
        for part in tf_op.split("/"):
            if part in STAGES:
                return part
    return None


def seconds_by_scope(path: str):
    """{stage or None: seconds}, averaged over the device planes; None
    where the file has no device plane or no scoped op."""
    per_device = []
    scoped = False
    for events, tf_ops in xspace.device_ops(path).values():
        stage = {meta: stage_of(name) for meta, name in tf_ops.items()}
        scoped = scoped or any(stage.values())
        acc: dict = {}
        for start, end, meta in xplane.self_pieces(events):
            key = stage.get(meta)
            acc[key] = acc.get(key, 0) + (end - start)
        per_device.append(acc)
    if not per_device or not scoped:
        return None
    return {key: sum(acc.get(key, 0) for acc in per_device)
            / len(per_device) / 1e9
            for key in (*STAGES, None)}


def read(spec, ctx):
    if not ctx.get("trace_path"):
        return None
    if "scope_seconds" not in ctx:  # one walk of the file a run
        ctx["scope_seconds"] = seconds_by_scope(ctx["trace_path"])
    per = number(spec["per"], ctx)
    if ctx["scope_seconds"] is None or not per:
        return None
    return ctx["scope_seconds"][spec["scope"]] / per
