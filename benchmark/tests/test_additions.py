"""A rehearsal of what a later PR does when it brings a cell or a metric.

A configuration, its golden, a traffic mix, a cell and two per-layer
metrics (one read by `op_share`, one by `scope_time` under the
`exchange` scope) are added to a copy of the repository's benchmark as
new files, and BENCHMARK.json gains appended entries; no file that was
there changes. Every file check of this directory must hold on the
result, and the new cell must run from it. A check that pins a list of
the day (the cells there are, the number of metric files, a metric's
place in ``per_layer``) fails here first. The controls plant one defect
each in the rehearsed copy: the checks still refuse what they are for.
On the CPU, with --allow-cpu; nothing here is a timing.

    python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import contextlib
import copy
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import files  # noqa: E402
from files import BENCH, ROOT, load  # noqa: E402
from test_benchmark import run_cell  # noqa: E402

# what a root holds of benchmark/: the data, and the two directories in
# which a mix's mode and a metric's reader are looked up by name
DIRS = ("configs", "goldens", "traffic", "workloads", "layer_metrics", "modes", "readers")
# names no later PR will want for a file of its own
CONFIG, MIX, CELL = "rehearsal3", "rehearsal-d6", "rehearsal3-wide"
SHARE, EXCHANGE = "rehearsal_sort_share", "rehearsal_exchange_s_per_mstate"
LIKE = "kraft3-wide"  # the new cell reports what this one does
NEW_METRICS = {
    SHARE: {
        "layer": "Wave program", "unit": "%", "better": "lower",
        "moves": "states_per_s", "source": "device_trace",
        "about": "rehearsal: device self time of the sorts over the traced verdict's wall",
        "reduce": {"kind": "op_share", "regex": "sort", "scale": 100}},
    EXCHANGE: {
        "layer": "Stages in a chunk", "unit": "s/Mstate", "better": "lower",
        "moves": "states_per_s", "source": "device_trace",
        "about": "rehearsal: device self time of the ops under the `exchange` scope",
        "reduce": {"kind": "scope_time", "scope": "exchange", "per": "mstates"}},
}


def dump(spec, *parts):
    with open(os.path.join(*parts), "w") as f:
        json.dump(spec, f, indent=1)


def contents(root):
    return {os.path.relpath(os.path.join(base, f), root): open(os.path.join(base, f), "rb").read()
            for base, _dirs, names in os.walk(root) for f in names}


def copy_of_the_repository(root):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for d in DIRS:
        shutil.copytree(os.path.join(BENCH, d), os.path.join(root, "benchmark", d),
                        ignore=shutil.ignore_patterns("__pycache__"))


def add_a_cell_and_two_metrics(root):
    """The addition: new files, and entries appended to BENCHMARK.json."""
    bench_dir = os.path.join(root, "benchmark")
    bench = load(root, "BENCHMARK.json")
    reported = [m for m in files.names("layer_metrics", root)
                if LIKE in files.cells_reporting(root, m)]

    # a configuration and its golden: raft3's under another name
    shutil.copytree(os.path.join(bench_dir, "configs", "raft3"),
                    os.path.join(bench_dir, "configs", CONFIG))
    config = dict(load(bench_dir, "configs", CONFIG, "config.json"), name=CONFIG)
    dump(config, bench_dir, "configs", CONFIG, "config.json")
    dump(dict(load(bench_dir, "goldens", "raft3.json"), config=CONFIG),
         bench_dir, "goldens", f"{CONFIG}.json")
    (entry,) = [c for c in bench["configs"] if c["name"] == "raft3"]
    bench["configs"].append(dict(
        entry, name=CONFIG, file=f"benchmark/configs/{CONFIG}/config.json"))

    # a traffic mix, and a cell that lists all it reports in its own file
    mix = dict(load(bench_dir, "traffic", "init-d20.json"),
               name=MIX, max_depth=6, warmup_depth=6)
    dump(mix, bench_dir, "traffic", f"{MIX}.json")
    cell = dict(load(bench_dir, "workloads", "raft3-wide.json"),
                name=CELL, config=CONFIG, traffic=MIX, per_layer=reported,
                why="rehearsal: Init to depth 6 of raft3's copy, one chunk a wave")
    dump(cell, bench_dir, "workloads", f"{CELL}.json")
    bench["workloads"].append({k: cell[k] for k in ("name", "config", "traffic", "chips", "why")})
    for metric in (*bench["end_to_end"], *bench["per_layer"]):
        if "workloads" in metric and metric["name"] in (*cell["end_to_end"], *reported):
            metric["workloads"].append(CELL)

    # two metrics that come the other way: their files name the cell
    for name, spec in NEW_METRICS.items():
        dump({"name": name, **spec, "workloads": [CELL]}, bench_dir, "layer_metrics", f"{name}.json")
        bench["per_layer"].append({"name": name, "workloads": [CELL],
                                   **{k: spec[k] for k in files.SHARED}})
    dump(bench, root, "BENCHMARK.json")


@pytest.fixture()
def rehearsed(tmp_path):
    root = str(tmp_path)
    copy_of_the_repository(root)
    add_a_cell_and_two_metrics(root)
    return root


def check_all(root):
    files.check_listing(root)
    for cell in files.cells(root):
        files.check_cell(root, cell)
    for metric in files.names("layer_metrics", root):
        files.check_metric(root, metric)
    files.check_setup_metrics(root)
    files.check_tracing_metrics(root)
    files.check_stage_metrics(root)


def test_a_cell_a_configuration_and_two_metrics_arrive_as_new_files_and_appended_entries(tmp_path):
    root = str(tmp_path)
    copy_of_the_repository(root)
    before = contents(root)
    check_all(root)  # the copy is a root like the repository
    add_a_cell_and_two_metrics(root)
    after = contents(root)

    # new files, appended entries, nothing else
    assert {k: v for k, v in after.items() if k in before and k != "BENCHMARK.json"} == {
        k: v for k, v in before.items() if k != "BENCHMARK.json"}
    added = sorted(set(after) - set(before))
    assert added == sorted([
        f"benchmark/configs/{CONFIG}/Raft.cfg", f"benchmark/configs/{CONFIG}/config.json",
        f"benchmark/goldens/{CONFIG}.json", f"benchmark/traffic/{MIX}.json",
        f"benchmark/workloads/{CELL}.json",
        *(f"benchmark/layer_metrics/{name}.json" for name in NEW_METRICS)])
    old, new = (json.loads(c["BENCHMARK.json"]) for c in (before, after))
    assert files.only_appended(old, new) and old != new
    assert len(new["workloads"]) == len(old["workloads"]) + 1
    assert len(new["per_layer"]) == len(old["per_layer"]) + 2

    # every file check holds on the result
    check_all(root)
    cell = load(root, "benchmark", "workloads", f"{CELL}.json")
    reports = [m for m in files.names("layer_metrics", root) if CELL in files.cells_reporting(root, m)]
    assert set(reports) == {*cell["per_layer"], *NEW_METRICS}

    # and the new cell runs from it: what the files say, less what a CPU
    # trace cannot give (no op of it carries a stage scope), left out and
    # not zero
    proc, res = run_cell("--bench-dir", os.path.join(root, "benchmark"), "--workload", CELL,
                         "--seed", str(2**31 + 38), "--seconds", "1", "--trace", "1")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert res["correct"] is True and res["failed"] == 0
    specs = files.layer_metric_files(root)
    scoped = {m for m in reports if specs[m]["reduce"]["kind"] == "scope_time"}
    assert EXCHANGE in scoped
    assert set(res["metrics"]) == set(reports) - scoped
    assert res["metrics"][SHARE]["unit"] == "%"
    assert 0 < res["metrics"][SHARE]["value"] < 100


def test_only_appended_takes_an_appended_entry():
    old = load(ROOT, "BENCHMARK.json")
    new = copy.deepcopy(old)
    assert files.only_appended(old, new)
    new["workloads"].append({"name": "another"})
    new["end_to_end"][1]["workloads"].append("another")
    assert files.only_appended(old, new) and not files.only_appended(new, old)


def a_changed_bound(bench):
    bench["end_to_end"][1]["bound"] = 0.02


def a_removed_cell(bench):
    del bench["workloads"][1]


def a_reordered_list(bench):
    bench["per_layer"].append(bench["per_layer"].pop(0))


@pytest.mark.parametrize("edit", [a_changed_bound, a_removed_cell, a_reordered_list],
                         ids=lambda f: f.__name__)
def test_only_appended_refuses(edit):
    old = load(ROOT, "BENCHMARK.json")
    new = copy.deepcopy(old)
    edit(new)
    assert not files.only_appended(old, new)


# ---------------- the controls ----------------
# One defect each, planted in the rehearsed root; the check named must
# fail on it, with the words that say why.

@contextlib.contextmanager
def edited(root, *parts):
    """A JSON file of the root, to change in place."""
    spec = load(root, *parts)
    yield spec
    dump(spec, root, *parts)


def entry_of(bench, metric):
    (entry,) = [m for m in bench["per_layer"] if m["name"] == metric]
    return entry


def listed_for_a_metric_neither_way_reports(root):
    with edited(root, "BENCHMARK.json") as bench:
        entry_of(bench, "narrow_wave_ms")["workloads"].append(CELL)
    return [(files.check_metric, "narrow_wave_ms", "not those that report it")]


def own_file_lists_what_benchmark_json_lacks(root):
    with edited(root, "BENCHMARK.json") as bench:
        entry_of(bench, "device_idle_share")["workloads"].remove(CELL)
    return [(files.check_metric, "device_idle_share", "not those that report it"),
            (files.check_cell, CELL, "differs from BENCHMARK.json's lists")]


def a_metric_file_benchmark_json_does_not_list(root):
    dump({"name": "unlisted", **NEW_METRICS[SHARE], "workloads": [CELL]},
         root, "benchmark", "layer_metrics", "unlisted.json")
    return [(files.check_listing, None, "per_layer differ")]


def a_metric_file_names_a_cell_that_is_not_there(root):
    with edited(root, "benchmark", "layer_metrics", f"{SHARE}.json") as spec:
        spec["workloads"].append("no-such-cell")
    return [(files.check_metric, SHARE, "names a cell BENCHMARK.json does not have")]


def a_files_unit_differs(root):
    with edited(root, "benchmark", "layer_metrics", f"{SHARE}.json") as spec:
        spec["unit"] = "share"
    return [(files.check_metric, SHARE, "unit differs from BENCHMARK.json's")]


def a_files_moves_differs(root):
    with edited(root, "benchmark", "layer_metrics", f"{SHARE}.json") as spec:
        spec["moves"] = "setup_s"
    return [(files.check_metric, SHARE, "moves differs from BENCHMARK.json's")]


def a_cell_missing_from_a_setup_metric(root):
    # by both ways and from the list, so the rule itself holds
    with edited(root, "BENCHMARK.json") as bench:
        entry_of(bench, "load_union_s")["workloads"].remove(CELL)
    with edited(root, "benchmark", "workloads", f"{CELL}.json") as cell:
        cell["per_layer"].remove("load_union_s")
    files.check_metric(root, "load_union_s")
    return [(files.check_setup_metrics, None, "not reported by every cell")]


def a_stage_with_no_metric_file(root):
    os.remove(os.path.join(root, "benchmark", "layer_metrics", "canon_s_per_mstate.json"))
    return [(files.check_stage_metrics, None, "has no metric file")]


@pytest.mark.parametrize("plant", [
    listed_for_a_metric_neither_way_reports, own_file_lists_what_benchmark_json_lacks,
    a_metric_file_benchmark_json_does_not_list, a_metric_file_names_a_cell_that_is_not_there,
    a_files_unit_differs, a_files_moves_differs, a_cell_missing_from_a_setup_metric,
    a_stage_with_no_metric_file], ids=lambda f: f.__name__)
def test_a_planted_defect_fails_the_check_that_is_for_it(rehearsed, plant):
    for check, name, why in plant(rehearsed):
        with pytest.raises(AssertionError) as failure:
            check(rehearsed, *([name] if name else []))
        assert why in str(failure.value), (check.__name__, str(failure.value))
