"""{"kind": "scope_time", "scope": <stage or null>, "per": <number or
name>}: device seconds of the ops traced under one stage scope of the
program (``jax.named_scope``: expand, canon, dedup, emit, seen_merge,
exchange), over ``per``.

An op's scope is the head of its name in the trace, where
``xplane.load`` put it from the op's metadata (``xplane.scope_path``:
the outermost stage in its JAX name stack). The time is self time, cut as
``xplane.self_pieces`` cuts it (a ``while`` does not count its body
again), of the "XLA Ops" line, averaged over the device planes.
``"scope": null`` reads the rest: compiler-made ops with no name stack
(copies, the ``while`` and its condition) and ops traced under no stage.
The buckets therefore add up to ``xplane.busy_s``.

Nothing where there is no trace or no device plane, and nothing where
not one op of the trace carries a stage scope: a program that has none
(a commit before the scopes, or executables from a compile cache that
one filled) must not read as "0 s of canon".
"""

from benchmark import xplane
from benchmark.readers import number

# scripts/stage_split.py reads these two here
STAGES = xplane.STAGES


def stage_of(tf_op):
    """The outermost stage scope of a name stack, or None."""
    return next(iter(xplane.scope_path(tf_op, 1)), None)


def seconds_by_scope(trace):
    """{stage or None: seconds}, averaged over the device planes; None
    where the trace has no device plane or no scoped op."""
    if trace is None or not trace.devices or not trace.scoped:
        return None
    total: dict = {}
    for ops in trace.devices.values():
        for start, end, name in xplane.self_pieces(ops):
            key = name.partition("/")[0]
            total[key] = total.get(key, 0) + (end - start)
    per = len(trace.devices) * 1e9
    return {None if key == xplane.UNSCOPED else key: total.get(key, 0) / per
            for key in (*STAGES, xplane.UNSCOPED)}


def read(spec, ctx):
    if "scope_seconds" not in ctx:  # one pass over the ops a run
        ctx["scope_seconds"] = seconds_by_scope(ctx["trace"])
    per = number(spec["per"], ctx)
    if ctx["scope_seconds"] is None or not per:
        return None
    return ctx["scope_seconds"][spec["scope"]] / per
