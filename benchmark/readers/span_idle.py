"""{"kind": "span_idle", "span": ..., "where": {...}}: how long the
first device ran no op inside a host span of the program, in seconds:
the median over the trace's spans of that name.

The span's duration less the union of the device-op intervals inside it.
With ``where`` (the filter of ``readers.rows``: {"field", "le"}) the
spans are paired with the traced verdict's wave rows by order — the
program opens one ``wave`` span per row — and only those whose row
passes count; spans and rows that do not pair up read as nothing.
"""

import statistics

from benchmark import xplane
from benchmark.readers import number


def read(spec, ctx):
    trace = ctx["trace"]
    if trace is None or not trace.devices:
        return None
    spans = sorted(sp for sp in trace.host if sp[2] == spec["span"])
    where = spec.get("where")
    if where:
        waves = ctx["waves"]
        bound = number(where["le"], ctx)
        if len(spans) != len(waves) or bound is None:
            return None
        spans = [sp for sp, row in zip(spans, waves)
                 if row[where["field"]] <= bound]
    if not spans:
        return None
    busy = xplane.union(trace.devices[sorted(trace.devices)[0]])
    idle = [xplane.total_s(xplane.subtract([[s, e]], busy))
            for s, e, _name in spans]
    return statistics.median(idle)
