"""`dedup_search_steps` (PR 57): the chunk-steps of the traced verdict
whose `first_new` searched the seen run, by the program's own count, in
the one cell that searches, `raft3-deep-cross` of the configuration
`raft3-deep`. One file of benchmark/layer_metrics/ read by `stat`, which
names the cell; the configuration, the cell and the metric appended to
BENCHMARK.json last. And a rehearsal of the cell, cut to depth 8 at
capacities a test can hold: the search itself needs 4.2 M states before
it, and is held at a small chunk by tests/test_raft3_deep.py. On the
CPU, with --allow-cpu; nothing here is a device number.

    python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import copy
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import files  # noqa: E402
from files import BENCH, ROOT, layer_metric_files, load  # noqa: E402
from test_benchmark import run_cell, spare_bench  # noqa: E402, F401
from benchmark import readers  # noqa: E402

NAME, CELL, CONFIG = "dedup_search_steps", "raft3-deep-cross", "raft3-deep"
SEARCH = ("dedup_search_share", "dedup_search_queries", NAME)


def ctx(stats):
    return {"scalars": {}, "waves": [], "stats": stats, "params": {},
            "trace": None, "trace_path": None, "peaks": {}}


def test_the_file_benchmark_json_and_the_deep_cell_name_each_other():
    files.check_metric(ROOT, NAME)
    files.check_cell(ROOT, CELL)
    files.check_listing(ROOT)
    spec = layer_metric_files()[NAME]
    assert spec["reduce"] == {"kind": "stat", "name": NAME}
    assert (spec["layer"], spec["unit"], spec["source"], spec["moves"]) == (
        "Stages in a chunk", "count", "program_counter", "states_per_s")
    # held to the new cell: no older cell's verdict searches (a later
    # cell that does lists the metric in its own file)
    assert spec["workloads"] == [CELL]
    assert files.cells_reporting(ROOT, NAME)[0] == CELL == files.cells(ROOT)[10]
    # the three of the search are read in it, the older two by its own list
    cell = load(BENCH, "workloads", f"{CELL}.json")
    assert set(SEARCH) <= set(cell["per_layer"])
    for name in SEARCH[:2]:
        assert CELL in files.cells_reporting(ROOT, name)
        assert CELL not in layer_metric_files()[name]["workloads"]


def test_benchmark_json_gained_its_entries_by_appending_alone():
    """The tenth configuration, the eleventh cell and the thirty-fifth
    metric, wherever later PRs' entries come to stand behind them, the
    cell's name in the `workloads` of every metric that lists its cells
    and that the cell reports; cut back to before them the file only
    appends to itself, which is what PR 57 started from as far as a
    checkout without its history can say."""
    new = load(ROOT, "BENCHMARK.json")
    assert new["configs"][9]["name"] == CONFIG
    assert new["workloads"][10] == {
        k: load(BENCH, "workloads", f"{CELL}.json")[k]
        for k in ("name", "config", "traffic", "chips", "why")}
    assert new["per_layer"][34]["name"] == NAME
    before = copy.deepcopy(new)
    for key, n in (("configs", 9), ("workloads", 10), ("per_layer", 34)):
        del before[key][n:]
    older = {w["name"] for w in before["workloads"]}
    for metric in before["end_to_end"] + before["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = [c for c in metric["workloads"] if c in older]
    assert files.only_appended(before, new)
    assert not files.only_appended(new, before)
    assert all(w["chips"] == 1 for w in new["workloads"][:11])


def test_the_reader_takes_the_count_and_leaves_out_a_parent_without_it():
    spec = layer_metric_files()[NAME]
    assert readers.read(spec, ctx({NAME: 180, "dedup_search_queries": 180 * 65536})) == 180
    assert readers.read(spec, ctx({NAME: 0})) == 0
    # a program that lacks the counter, as this PR's parent: nothing, no raise
    assert readers.read(spec, ctx({"dedup_search_queries": 11796480})) is None


def test_the_deep_cell_cut_to_depth_8_is_correct_on_the_cpu(spare_bench):
    """The new cell through the command, from the files as they are but
    for the depth and the two capacities (three frontier buffers of 2^22
    rows are 7.4 GB): the golden's prefix decides `correct`, and the
    traced line carries the counter, 0 where nothing is searched."""
    cell = load(BENCH, "workloads", f"{CELL}.json")
    params = {**cell["engine_params"], "frontier_cap": 1 << 16, "journal_cap": 1 << 18}
    bench_dir = spare_bench(CELL, CELL, 8, 8, engine_params=params)
    proc, res = run_cell("--bench-dir", bench_dir, "--workload", CELL,
                         "--seed", str(2**31 + 57), "--seconds", "1", "--trace", "1")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert res["correct"] is True and res["failed"] == 0
    assert res["compared"] == {
        "verdicts_off_golden": [0, 0], "verdicts_short_of_2": [0, 0],
        "window_compiles": [0, 0], "window_cache_entries": [0, 0]}
    specs = layer_metric_files()
    # what a CPU's line cannot carry: the scopes' times (its ops carry no scope)
    unread = {m for m in cell["per_layer"] if specs[m]["reduce"]["kind"] == "scope_time"}
    assert set(res["metrics"]) == set(cell["per_layer"]) - unread
    assert res["metrics"][NAME] == {"value": 0, "unit": "count"}
    assert res["metrics"]["dedup_search_queries"]["value"] == 0
    assert res["metrics"]["frontier_peak_rows"]["value"] == max(
        load(BENCH, "goldens", f"{CONFIG}.json")["depth_counts"][:9])
