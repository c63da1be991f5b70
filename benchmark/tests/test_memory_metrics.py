"""The three per-layer metrics of the device's memory (PR 53): what the
program's own read of the allocator (`raft_tpu/obs/memwatch.py`) says of
the peak, of what a run holds between programs, and of how far the
geometry's plan falls short of the peak. Each is one file of
benchmark/layer_metrics/ read by `stat` with a scale, named by the wide
cells but `kraft3-wide`, appended to BENCHMARK.json. On the CPU, with
--allow-cpu, whose allocator reports nothing: nothing here is a device
number.

Why not `kraft3-wide`: test_additions.py rehearses, on the CPU, a cell
that reports what `kraft3-wide` reports, and holds that every metric of
it but those read by `scope_time` is on the CPU's line. These three read
nothing there, by design. Listing that cell too takes an edit of that
test (leave out what a reader finds nothing for), which is a `benchmark`
PR's; the program's `stats` carry the keys in every cell meanwhile.

    python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import copy
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import files  # noqa: E402
from files import BENCH, ROOT, layer_metric_files, load  # noqa: E402
from test_benchmark import run_cell, spare_bench  # noqa: E402, F401
from benchmark import readers  # noqa: E402

MEMORY = {"hbm_peak_share": "hbm_peak_frac", "hbm_live_share": "hbm_live_frac",
          "hbm_plan_gap_share": "hbm_plan_gap_frac"}


def ctx(stats):
    return {"scalars": {}, "waves": [], "stats": stats, "params": {},
            "trace": None, "trace_path": None, "peaks": {}}


@pytest.mark.parametrize("name", MEMORY)
def test_the_file_benchmark_json_and_the_wide_cells_name_each_other(name):
    files.check_metric(ROOT, name)
    files.check_listing(ROOT)
    spec = layer_metric_files()[name]
    assert spec["reduce"] == {"kind": "stat", "name": MEMORY[name], "scale": 100}
    assert (spec["layer"], spec["unit"], spec["source"], spec["moves"]) == (
        "Seen set, device memory", "%", "program_counter", "states_per_s")
    # the rule, whatever cells later PRs add or list: the cells the file
    # names are those that report the metric, in BENCHMARK.json's order,
    # and each reports `states_per_s`, which `raft3-small` does not
    wide = [c for c in files.cells(ROOT)
            if "states_per_s" in load(BENCH, "workloads", f"{c}.json")["end_to_end"]]
    assert files.cells_reporting(ROOT, name) == spec["workloads"]
    assert set(spec["workloads"]) <= set(wide) and "raft3-small" not in wide
    # the other two list the same cells
    assert all(layer_metric_files()[n]["workloads"] == spec["workloads"] for n in MEMORY)


def test_benchmark_json_names_them_in_order_and_only_appended_to_what_came_before():
    """The three entries stand in `per_layer` in this order, one after
    the other, wherever later PRs' entries come to stand behind them;
    and the file as far as the third only appends to the file before the
    first, which is what PR 53 started from as far as a checkout without
    its history can say."""
    new = load(ROOT, "BENCHMARK.json")
    listed = [m["name"] for m in new["per_layer"]]
    first = listed.index("hbm_peak_share")
    assert listed[first:first + 3] == list(MEMORY)
    before, upto = copy.deepcopy(new), copy.deepcopy(new)
    del before["per_layer"][first:]
    del upto["per_layer"][first + 3:]
    assert files.only_appended(before, upto)
    assert not files.only_appended(upto, before)


def test_the_readers_scale_a_fraction_and_leave_out_a_null():
    """A chip's `stats` against a CPU's, whose measured keys are null,
    and a parent's, which has none of the keys: a reader that finds
    nothing returns nothing, and the line leaves the metric out."""
    specs = layer_metric_files()
    chip = {"hbm_peak_frac": 0.57, "hbm_live_frac": 0.39, "hbm_plan_gap_frac": -0.02}
    assert {n: readers.read(specs[n], ctx(chip)) for n in MEMORY} == {
        "hbm_peak_share": pytest.approx(57.0), "hbm_live_share": pytest.approx(39.0),
        "hbm_plan_gap_share": pytest.approx(-2.0)}
    for stats in (dict.fromkeys(chip), {"programs_loaded": 19}):
        assert all(readers.read(specs[n], ctx(stats)) is None for n in MEMORY)


def test_a_wide_cell_on_the_cpu_is_correct_and_reports_none_of_the_three(spare_bench):
    """A rehearsal of `raft3-wide` itself (its name is what the files
    list) at depth 6: a `correct` traced line without the three, and the
    program's `stats` with the plan standing and the measured keys null."""
    import json

    bench_dir = spare_bench("raft3-wide", "raft3-wide", 6, 6)
    proc, res = run_cell("--bench-dir", bench_dir, "--workload", "raft3-wide",
                         "--seed", "2147484053", "--seconds", "1", "--trace", "1")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert res["correct"] is True
    naming = [n for n, m in layer_metric_files().items() if "raft3-wide" in m.get("workloads", ())]
    assert set(MEMORY) <= set(naming)
    assert not set(MEMORY) & set(res["metrics"])
    assert "programs_loaded" in res["metrics"]  # `stat` does read this line's stats
    with open(os.path.join(BENCH, "out", "raft3-wide-2147484053-trace.jsonl")) as f:
        rows = [r for r in map(json.loads, f) if r["event"] == "wave" and r["n"] == "traced"]
    assert [r["depth"] for r in rows] == [1, 2, 3, 4, 5, 6]
    assert all(r["hbm_bytes"] is None and r["hbm_peak_rise"] is None
               and 0 < r["hbm_frac"] < 1 for r in rows)
