"""{"kind": "stat", "name": ...}: one of the traced verdict's result
statistics, as the program reports it (``CheckResult.stats``,
``ShardedResult.stats``)."""


def read(spec, ctx):
    return ctx["stats"].get(spec["name"])
