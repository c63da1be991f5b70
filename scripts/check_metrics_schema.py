#!/usr/bin/env python
"""Validate --metrics-out JSONL files against the declared event schema.

    python scripts/check_metrics_schema.py m.jsonl [more.jsonl ...]

Checks every line against raft_tpu.obs.events.DECLARED_EVENTS (the same
tuple the tier-1 smoke test pins): valid JSON per line, known event
type, every declared key present, wave indices strictly increasing
within a run, no wave after a run's summary, and a legal exit_cause on
each summary. A `stall` event (a wave exceeding the rolling-median
wave-time factor, obs/collector.py) and a `preempt` event (SIGTERM/
SIGINT observed, checkpoint path recorded) carry the generic known-
type + declared-keys checks. Coverage events get the structural checks on top: the
actions block must be [enabled, fired, new] non-negative int triples
matching actions_total, coverage must come before the run's summary
with non-decreasing wave indices, and the cumulative per-action
counters must be monotone non-decreasing cell by cell across the
stream. The resilience events (retry / resume / ckpt_generation /
preempt, from the self-healing runtime) are validated too: retry
attempts must be ints >= 1 strictly increasing across a supervised
session (a summary resets the counter), backoff_s non-negative,
resume/ckpt_generation generations ints >= 0, and ckpt_generation
skipped-diagnostics a list of strings. The elastic-mesh events ride the
same rules: a reshard (load-time fp-mod-D re-routing of a checkpoint
written on a different mesh size) must appear after the manifest but
before any wave and carry distinct from_d/to_d >= 1, while shard_lost /
shard_stall must name a shard index inside the mesh (0 <= shard <
device_count), carry a wave no older than the run's last completed
wave, and come before the summary. A `memwatch` event (a wave's
reading of the device's memory, emitted when the wave set a new plan
peak or the allocator's peak rose in it: obs/memwatch.py) must keep
both peak_bytes (the allocator's; null on a device that reports none)
and plan_peak_bytes (the geometry's) monotone non-decreasing across the
run, with bytes <= peak_bytes and plan_bytes <= plan_peak_bytes,
non-negative int byte counts throughout (the measured three or null),
and a breakdown mapping buffer families to non-negative byte counts.
A `wave` event's hbm_bytes and hbm_peak_rise (the allocator's bytes in
use at the wave's end and its peak's rise since the read before) must
be non-negative ints or null and its hbm_frac a non-negative number or
null; a `summary` event's hbm_peak_bytes, hbm_live_bytes,
hbm_init_bytes and hbm_init_rise likewise, its hbm_budget_bytes and
hbm_plan_bytes non-negative ints, and hbm_live_bytes <= hbm_peak_bytes
where both are there (the allocator's peak covers every reading of the
run).
A `wave` event's canon_tier3_local and
canon_tier3_full (lanes its canon routed to tier 3's buckets) must be
non-negative ints that together do not exceed generated -
canon_dup_lanes (the representatives its in-chunk dedup let through),
and its dedup_sort_lanes, where the engine counts them (the lanes its
dedup stage's merged sort sorted), a non-negative int, as are its
expand_rows_built and expand_rows_budget (the successor rows its apply
passes built and the rows their plan budgets). A `manifest` or
`summary` event's dedup_plan, where it has one, must list merge, search
and wave_prefix (and rungs, where it says them) as non-negative ints,
wave_prefix strictly increasing from 0 where it is not empty, rungs
strictly increasing, and sort_lanes the merged runs, the largest prefix
and the queries together at least, and the last rung.
A `summary` event's frontier_peak_rows (the most rows a wave wrote) and
restart_fired (the successors the model's crash actions generated, from
the run's coverage block) must be non-negative ints where it has them.
A `summary` event's set-up keys (programs_loaded, programs_traced,
setup_*_s, load_*_s: obs/compiles.py) must be non-negative numbers or
null, and its `programs`, where it has them, a list of records with a
kind of trace / lower / load, a fun_name, non-negative seconds, a
nesting, a cause naming run, top, depth and bracket, and on a load
cache_hit true or false.
Job-tagged streams (the one
multiplexed file a `raft_tpu sweep --metrics-out` run writes) get the
fleet rules: a `job` tag must be a non-empty string, each job's wave
indices must be strictly increasing within its run, and every job
manifest must be matched by exactly one summary with the same tag.
Exit status 0 iff every file is clean.

Dependency-free on purpose (no jax/numpy import happens): schema
validation must work on a machine with nothing but the repo checked
out, e.g. when auditing a metrics file copied off a TPU host.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from raft_tpu.obs.events import validate_lines  # noqa: E402


def validate_file(path: str) -> tuple[dict, list[str]]:
    """(event-type counts, problems) for one JSONL file."""
    with open(path) as fh:
        counts, problems = validate_lines(fh)
    if not counts:
        problems = [*problems, "no events at all (empty stream)"]
    elif "manifest" not in counts:
        problems = [*problems, "stream has no manifest event"]
    return counts, problems


def main(argv: list[str]) -> int:
    if not argv:
        print(__doc__.strip(), file=sys.stderr)
        return 64
    rc = 0
    for path in argv:
        try:
            counts, problems = validate_file(path)
        except OSError as e:
            print(f"{path}: cannot read ({e})", file=sys.stderr)
            rc = 1
            continue
        summary = " ".join(f"{k}={v}" for k, v in sorted(counts.items()))
        if problems:
            rc = 1
            print(f"{path}: INVALID ({summary})", file=sys.stderr)
            for p in problems:
                print(f"  {p}", file=sys.stderr)
        else:
            print(f"{path}: ok ({summary})")
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
