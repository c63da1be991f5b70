"""{"kind": "op_share", "regex": ...}: device time of the ops whose name
matches, by self time and averaged over chips, over the traced window."""

from benchmark import xplane


def read(spec, ctx):
    if ctx["trace"] is None:
        return None
    s = xplane.regex_s(ctx["trace"], spec["regex"])
    window = ctx["scalars"].get("trace_window_s")
    return None if s is None or not window else s / window
