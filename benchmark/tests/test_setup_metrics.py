"""The six per-layer metrics that split `setup_s` (PR 35): what the
program's own records say of the seconds before the window. Each is one
file of benchmark/layer_metrics/ read by `stat`, and every cell reports
each, by whichever of the two ways. On the CPU, with --allow-cpu; nothing
here is a timing.

    python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import files  # noqa: E402
from files import BENCH, ROOT, SETUP, layer_metric_files, load  # noqa: E402
from test_benchmark import run_cell, spare_bench  # noqa: E402, F401
from benchmark import readers  # noqa: E402


def test_the_files_benchmark_json_and_the_cells_name_each_other():
    files.check_setup_metrics(ROOT)


def test_a_program_without_the_records_reports_none_of_them():
    """The parent's side of this PR's traced runs: `stat` finds nothing
    in the statistics of a program that does not keep them, and the line
    leaves the metric out."""
    ctx = {"scalars": {}, "waves": [], "stats": {"programs_loaded": 19},
           "params": {}, "trace": None, "trace_path": None, "peaks": {}}
    files = layer_metric_files()
    assert all(readers.read(files[name], ctx) is None for name in SETUP)
    assert readers.read(files["programs_loaded"], ctx) == 19


def test_a_traced_run_of_a_named_cell_reports_all_six_as_numbers(spare_bench):
    """A rehearsal of `raft3-small` itself (its name is what the files
    list) at depth 6: the six beside what the cell reports already, and
    the identities the records promise."""
    bench_dir = spare_bench("raft3-small", "raft3-small", 6, 6)
    proc, res = run_cell("--bench-dir", bench_dir, "--workload", "raft3-small",
                         "--seed", "2147483999", "--seconds", "1", "--trace", "1")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert res["correct"] is True
    cell = load(BENCH, "workloads", "raft3-small.json")
    naming = [n for n, m in layer_metric_files().items() if "raft3-small" in m.get("workloads", ())]
    assert set(SETUP) <= set(naming)
    assert set(res["metrics"]) == {*cell["per_layer"], *naming}
    got = {name: res["metrics"][name] for name in SETUP}
    assert all(m["unit"] == "s" and isinstance(m["value"], float) and m["value"] >= 0
               for m in got.values())
    value = {name: m["value"] for name, m in got.items()}
    # process start to the package's first line: there is a /proc here
    assert value["setup_pre_s"] > 0
    # a CPU run has no persistent cache: everything was compiled
    assert value["load_cache_read_s"] == 0 and value["load_compile_s"] > 0
    parts = sum(value[n] for n in SETUP if n not in ("setup_pre_s", "load_union_s"))
    assert value["load_union_s"] <= parts + 1e-9
    with open(os.path.join(BENCH, "out", "raft3-small-2147483999-trace.jsonl")) as f:
        (setup,) = [r for r in map(json.loads, f) if r["event"] == "setup"]
    # nothing loads after the warm-up, so the traced verdict's cumulative
    # reading is the set-up's: inside the two clocks round it
    assert value["load_union_s"] <= setup["build_s"] + setup["warmup_s"]
    # and the three readings tile the set-up: the package's first line is
    # where build_s starts, bar the interpreter's own start before run.py's
    assert abs(value["setup_pre_s"] + setup["build_s"] + setup["warmup_s"] - setup["setup_s"]) < 0.5
