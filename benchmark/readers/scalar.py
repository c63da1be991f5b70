"""{"kind": "scalar", "name": ...}: a number the mode measured."""

from benchmark.readers import number


def read(spec, ctx):
    return number(spec["name"], ctx)
