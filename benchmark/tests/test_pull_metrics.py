"""`expand_handlemessage_share` (PR 55): the share of the traced
verdict's wall that the ops under `expand/HandleMessage` take, in the
two cells whose model file is `models/pull_raft.py`. One file of
benchmark/layer_metrics/ read by `op_share`, which names both cells, so
`pull3-full.json` is not touched; appended to BENCHMARK.json last. And a
rehearsal of the cell PR 55 brings, `pullv2-full`, cut to depth 6. On
the CPU, with --allow-cpu; nothing here is a device number.

    python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import files  # noqa: E402
from files import BENCH, ROOT, layer_metric_files, load  # noqa: E402
from test_benchmark import run_cell, spare_bench  # noqa: E402, F401
from test_tracing_metrics import unpacked  # noqa: E402
from benchmark import readers, xplane  # noqa: E402

NAME = "expand_handlemessage_share"
PULL_CELLS = ["pull3-full", "pullv2-full"]


def ctx(trace, window_s):
    return {"scalars": {"trace_window_s": window_s}, "waves": [], "stats": {},
            "params": {}, "trace": trace, "trace_path": None, "peaks": {}}


def test_the_file_benchmark_json_and_the_two_pull_cells_name_each_other():
    files.check_metric(ROOT, NAME)
    files.check_listing(ROOT)
    spec = layer_metric_files()[NAME]
    assert spec["reduce"] == {
        "kind": "op_share", "regex": "^expand/HandleMessage/", "scale": 100}
    assert (spec["layer"], spec["unit"], spec["source"], spec["moves"]) == (
        "Stages in a chunk", "%", "device_trace", "states_per_s")
    # the cells the file names are those that report the metric, and they
    # are the cells of the configurations lowered by the pull model file
    assert files.cells_reporting(ROOT, NAME) == spec["workloads"] == PULL_CELLS
    for cell in PULL_CELLS:
        files.check_cell(ROOT, cell)
        config = load(BENCH, "configs", load(
            BENCH, "workloads", f"{cell}.json")["config"], "config.json")
        assert "raft_tpu/models/pull_raft.py" in config["spec"]
    # it came as a file: the older cell's own list does not know it
    assert NAME not in load(BENCH, "workloads", "pull3-full.json")["per_layer"]
    assert NAME in load(BENCH, "workloads", "pullv2-full.json")["per_layer"]


def test_the_reader_takes_the_groups_own_ops_over_the_window():
    """A made-up trace: the share is the self time of the ops whose
    scoped name starts with the group's path, over the traced wall, in
    per cent; another group's ops, another stage's and an op that only
    ends in the name are not read."""
    spec = layer_metric_files()[NAME]
    ops = [(0, 300, "expand/HandleMessage/fusion.3[Loop]"),
           (300, 400, "expand/HandleMessage/gather.1"),
           (400, 500, "expand/BecomeLeader/fusion.9[Loop]"),
           (500, 900, "dedup/merge/sort.2"),
           (900, 1000, "expand/fusion.1"),
           (1000, 1100, "-/expand/HandleMessage/copy.4")]
    trace = xplane.Trace(devices={"/device:TPU:0": ops}, host=[])
    assert readers.read(spec, ctx(trace, 2000 / 1e9)) == pytest.approx(20.0)
    assert readers.read(spec, ctx(None, 1.0)) is None
    assert readers.read(spec, ctx(trace, None)) is None


def test_a_program_without_the_scope_reads_zero(tmp_path):
    """PR 24's recorded trace has the stage scopes and not yet the
    groups' (PR 51), as a parent of this PR's that lacks them would:
    `expand` is read, `expand/HandleMessage` finds nothing, and the
    share is 0, not an error."""
    pinned = load(BENCH, "testdata", "scoped_v5e.pinned.json")
    trace = xplane.load(unpacked(tmp_path, "scoped_v5e"))
    assert trace.scoped and pinned["scope_s"]["expand"] > 0
    spec = layer_metric_files()[NAME]
    assert readers.read(spec, ctx(trace, pinned["verdict_span_s"])) == 0.0


def test_pullv2_full_cut_to_depth_6_is_correct_on_the_cpu(spare_bench):
    """The new cell through the command, from the files as they are but
    for the depth: the adapter finds the variant's lowering by the cfg's
    file name, the golden's prefix decides `correct`, and the traced line
    carries the new metric (0 on a CPU trace, whose ops carry no scope)."""
    bench_dir = spare_bench("pullv2-full", "pullv2-full", 6, 6)
    proc, res = run_cell("--bench-dir", bench_dir, "--workload", "pullv2-full",
                         "--seed", str(2**31 + 55), "--seconds", "1", "--trace", "1")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert res["correct"] is True and res["failed"] == 0
    with open(os.path.join(BENCH, "out", f"pullv2-full-{2**31 + 55}-trace.jsonl")) as f:
        (built,) = [r for r in map(json.loads, f) if r["event"] == "setup"]
    assert built["ident"].startswith("PullRaftVariant2/") and "/W=289/" in built["ident"]
    assert res["compared"] == {
        "verdicts_off_golden": [0, 0], "verdicts_short_of_2": [0, 0],
        "window_compiles": [0, 0], "window_cache_entries": [0, 0]}
    cell = load(BENCH, "workloads", "pullv2-full.json")
    specs = layer_metric_files()
    unread = {m for m in cell["per_layer"]
              if specs[m]["reduce"]["kind"] == "scope_time"}
    assert set(res["metrics"]) == set(cell["per_layer"]) - unread
    assert res["metrics"][NAME] == {"value": 0.0, "unit": "%"}
    assert res["metrics"]["dedup_search_queries"]["value"] == 0
    assert res["metrics"]["frontier_peak_rows"]["value"] == max(
        load(BENCH, "goldens", "pullv2.json")["depth_counts"][:7])
