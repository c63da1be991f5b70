"""{"kind": "wave_median", "field": ..., "where": {...}}: the median of
one field over the traced verdict's wave rows, under an optional
filter."""

import statistics

from benchmark.readers import rows


def read(spec, ctx):
    vals = [r[spec["field"]] for r in rows(spec.get("where"), ctx)]
    return statistics.median(vals) if vals else None
