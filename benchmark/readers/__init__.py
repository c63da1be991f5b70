"""Per-layer metrics as data. benchmark/layer_metrics/<name>.json names a
reader (`"reduce": {"kind": ...}`) and its parameters; each reader is one
module here, benchmark/readers/<kind>.py, with one function
`read(spec, ctx)`. A later PR adds a metric as a JSON file, and a kind of
reading that is not here yet as a module of its own.

A reader returns a number, or None where it finds nothing to read; the
harness then leaves the metric out. Context:

  scalars     named numbers the mode measured (build_s, device_busy_s, ...)
  waves       the traced verdict's wave rows, as the program reports them
  stats       the traced verdict's result statistics, as the program
              reports them (per-shard counts on the sharded engine)
  params      the cell's engine parameters (chunk, ...)
  trace       benchmark.xplane.Trace of the traced verdict, or None
  trace_path  that trace's .xplane.pb, there until every metric is read
  peaks       the device's row of peaks.json
"""

from __future__ import annotations

import importlib


def number(spec, ctx):
    """A literal, or the name of a scalar or of an engine parameter."""
    if isinstance(spec, (int, float)):
        return spec
    if spec in ctx["scalars"]:
        return ctx["scalars"][spec]
    return ctx["params"].get(spec)


def rows(where, ctx) -> list:
    """Wave rows under an optional filter {"field", "le": number or
    name}: the rows whose field is at most that."""
    if not where:
        return ctx["waves"]
    bound = number(where["le"], ctx)
    return [r for r in ctx["waves"] if r[where["field"]] <= bound]


def read(metric: dict, ctx: dict):
    """The value of one benchmark/layer_metrics file, scaled, or None."""
    spec = metric["reduce"]
    kind = importlib.import_module(f"benchmark.readers.{spec['kind']}")
    value = kind.read(spec, ctx)
    return None if value is None else value * spec.get("scale", 1)
