"""The per-layer metrics that read the program's tracing spine (PR 24:
stage scopes, host spans, compile counters), and a bench dir that lists
them.

They are not in benchmark/layer_metrics/ nor in BENCHMARK.json: the
harness takes a cell's metrics from the cell's own file
(benchmark/run.py, ``cell["per_layer"]``), so an accepted cell reports a
new metric only after an edit to that file, which is a ``benchmark`` PR's
to make (PERF.md section 7 names the edit). Until then the readers run
where a bench dir elsewhere lists the metrics — ``--bench-dir``, as the
benchmark's tests add their throw-away cells:

    python3 benchmark/tests/tracing_overlay.py benchmark/out/overlay
    python3 benchmark/run.py --bench-dir benchmark/out/overlay \\
        --workload raft3-wide --seed 1 --seconds 51 --trace 1

``build`` copies the repository's cells, configurations, goldens, traffic
mixes and per-layer metrics there, adds the files below and appends their
names to the cells that report them. benchmark/tests/
test_tracing_metrics.py reads the same table.
"""

from __future__ import annotations

import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _stage(scope, about):
    name = f"{scope or 'unscoped'}_s_per_mstate"
    return name, {
        "name": name, "layer": "Stages in a chunk", "unit": "s/Mstate",
        "better": "lower", "moves": "states_per_s",
        "source": "device_trace", "about": about,
        "reduce": {"kind": "scope_time", "scope": scope, "per": "mstates"},
    }


METRICS = dict([
    _stage("expand", "device self time of the ops under the `expand` "
           "scope (guard pass, compaction, sparse apply) over the golden's "
           "distinct states in millions"),
    _stage("canon", "the same under `canon`: canonical fingerprints "
           "through the memo"),
    _stage("dedup", "the same under `dedup`: probes of the seen run and "
           "the wave's ladder, first occurrence in the chunk"),
    _stage("emit", "the same under `emit`: coverage, the cursor-append "
           "emit, the chunk's sorted run, invariants, stats"),
    _stage("seen_merge", "the same under `seen_merge`: the in-wave "
           "cascade and the end-of-wave merge program"),
    _stage(None, "device self time of the ops under no stage scope (the "
           "`while` and its condition, copies, the stats reset); the six "
           "add up to device_busy_s_per_mstate"),
    ("wave_idle_ms", {
        "name": "wave_idle_ms", "layer": "Host wave loop", "unit": "ms",
        "better": "lower", "moves": "verdict_s", "source": "device_trace",
        "about": "median, over the program's `wave` spans whose frontier "
                 "fits one chunk, of the time the chip ran no op inside "
                 "the span: how long it sat idle a wave while the host "
                 "held the loop",
        "reduce": {"kind": "span_idle", "span": "wave",
                   "where": {"field": "frontier", "le": "chunk"},
                   "scale": 1000},
    }),
    ("host_share", {
        "name": "host_share", "layer": "Host wave loop", "unit": "%",
        "better": "lower", "moves": "verdict_s", "source": "program_span",
        "about": "sum of the wave rows' host_s over the sum of their "
                 "wave_s: what the dispatch, fetch, seen-merge and "
                 "checkpoint brackets leave of the waves",
        "reduce": {"kind": "wave_sum_ratio", "num": "host_s",
                   "den": "wave_s", "scale": 100},
    }),
    ("programs_loaded", {
        "name": "programs_loaded", "layer": "Compile + cache",
        "unit": "count", "better": "lower", "moves": "setup_s",
        "source": "program_counter",
        "about": "programs the process asked the compiler or its cache "
                 "for up to the end of the traced verdict, by the "
                 "program's own count",
        "reduce": {"kind": "stat", "name": "programs_loaded"},
    }),
])

STAGE_METRICS = [n for n in METRICS if n.endswith("_s_per_mstate")]

# which accepted cell reports which (each moves an end-to-end metric the
# cell reports)
CELLS = {
    "raft3-wide": [*STAGE_METRICS, "programs_loaded"],
    "raft3-small": ["wave_idle_ms", "host_share", "programs_loaded"],
}


def build(dst: str) -> str:
    """A bench dir at ``dst`` (replaced if there) like the repository's,
    with the metrics above as files and in their cells' lists."""
    shutil.rmtree(dst, ignore_errors=True)
    for d in ("configs", "goldens", "traffic", "layer_metrics", "workloads"):
        shutil.copytree(os.path.join(BENCH, d), os.path.join(dst, d))
    for name, spec in METRICS.items():
        with open(os.path.join(dst, "layer_metrics", f"{name}.json"), "w") as f:
            json.dump(spec, f, indent=1)
    for cell, names in CELLS.items():
        path = os.path.join(dst, "workloads", f"{cell}.json")
        with open(path) as f:
            spec = json.load(f)
        spec["per_layer"] += names
        with open(path, "w") as f:
            json.dump(spec, f, indent=1)
    return dst


if __name__ == "__main__":
    print(build(sys.argv[1]))
