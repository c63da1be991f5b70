"""{"kind": "scalar_ratio", "num": ..., "den": ...}: one measured number
over another."""

from benchmark.readers import number


def read(spec, ctx):
    num, den = number(spec["num"], ctx), number(spec["den"], ctx)
    return None if num is None or not den else num / den
