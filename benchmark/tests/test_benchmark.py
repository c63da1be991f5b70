"""The benchmark's own tests: on the CPU, with --allow-cpu, at tiny depths.

    python -m pytest benchmark/tests -q

They pin the harness (cells resolve, the last line's keys, correct/failed
against a right and a wrong golden, no chip no number, new cells as files
only) and the yardstick (trace reduction, readers). Nothing here is a
timing.
"""

from __future__ import annotations

import gzip
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import files  # noqa: E402
from files import BENCH, ROOT, load, names  # noqa: E402
from benchmark import readers, xplane  # noqa: E402
from benchmark.modes import bfs  # noqa: E402

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def run_cell(*argv, devices=1, allow_cpu=True):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), *argv,
         *(["--allow-cpu"] if allow_cpu else [])],
        env=env, capture_output=True, text=True, timeout=900)
    lines = proc.stdout.strip().splitlines()
    return proc, (json.loads(lines[-1]) if lines else None)


@pytest.fixture()
def spare_bench(tmp_path):
    """A bench dir elsewhere whose cells are added as files only: the
    repository's configs, goldens, traffic mixes and per-layer metrics,
    and an empty workloads/ for throw-away cells."""
    for d in ("configs", "goldens", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(BENCH, d), tmp_path / d)
    (tmp_path / "workloads").mkdir()

    def add_cell(name, base, max_depth, warmup_depth, **changes):
        """A cell like ``base`` under a traffic mix of its own."""
        cell = load(BENCH, "workloads", f"{base}.json")
        traffic = load(BENCH, "traffic", f"{cell['traffic']}.json")
        traffic.update(name=name, max_depth=max_depth, warmup_depth=warmup_depth)
        cell.update(name=name, traffic=name, **changes)
        for d, spec in (("traffic", traffic), ("workloads", cell)):
            with open(tmp_path / d / f"{name}.json", "w") as f:
                json.dump(spec, f)
        return str(tmp_path)

    return add_cell


# ---------------- the files ----------------
# The checks are functions of a root, in files.py; test_additions.py makes
# them again on a copy of the repository that has gained a cell.

@pytest.mark.parametrize("cell", names("workloads"))
def test_cell_resolves_and_agrees_with_benchmark_json(cell):
    files.check_cell(ROOT, cell)


@pytest.mark.parametrize("metric", names("layer_metrics"))
def test_metric_file_agrees_with_benchmark_json_and_names_its_cells(metric):
    files.check_metric(ROOT, metric)


def test_benchmark_json_and_the_files_name_each_other():
    files.check_listing(ROOT)


# ---------------- the command ----------------

def test_right_golden_is_correct_and_the_line_has_the_contracts_keys(spare_bench):
    bench_dir = spare_bench("small-d6", "raft3-small", 6, 6)
    proc, res = run_cell("--bench-dir", bench_dir, "--workload", "small-d6",
                         "--seed", "3", "--seconds", "1", "--trace", "0")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert set(res) == RESULT_KEYS and set(res["device"]) == DEVICE_KEYS
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 2
    assert set(res["metrics"]) == {"setup_s", "verdict_s"}
    assert all(set(m) == {"value", "unit"} for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    # every verdict of the window is timed in depth + 1 pieces that make it up
    with open(os.path.join(BENCH, "out", "small-d6-3.jsonl")) as f:
        rows = [json.loads(line) for line in f]
    timed = [r for r in rows if r["event"] == "verdict" and r["n"] != "warmup"]
    assert len(timed) == res["attempted"] - 1
    for r in timed:
        assert len(r["pieces"]) == 7 and min(r["pieces"]) > 0
        assert sum(r["pieces"]) == pytest.approx(r["seconds"], rel=1e-9)
    (window,) = [r for r in rows if r["event"] == "window"]
    assert res["metrics"]["verdict_s"]["value"] == window["steady_s"] == pytest.approx(
        bfs.steady_seconds([r["pieces"] for r in timed]))


def test_traced_run_reports_the_cells_layer_metrics(spare_bench):
    bench_dir = spare_bench("small-d6", "raft3-small", 6, 6)
    proc, res = run_cell("--bench-dir", bench_dir, "--workload", "small-d6",
                         "--seed", "3", "--seconds", "1", "--trace", "1")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert set(res) == RESULT_KEYS | {"breakdown"} and list(res)[-1] == "compared"
    assert set(res["device"]) == DEVICE_KEYS | {"busy_s", "window_s"}
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    cell = load(BENCH, "workloads", "raft3-small.json")
    assert set(res["metrics"]) == set(cell["per_layer"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < len(res["breakdown"]["device_ops"]) <= 10
    assert res["correct"] is True


def test_wrong_golden_is_not_correct(spare_bench, tmp_path):
    bench_dir = spare_bench("small-d6", "raft3-small", 6, 4)
    golden = load(bench_dir, "goldens", "raft3.json")
    golden["totals"]["4"] = {"total": 47, "terminal": 0}
    golden["depth_counts"][6] += 1
    with open(os.path.join(bench_dir, "goldens", "raft3.json"), "w") as f:
        json.dump(golden, f)
    proc, res = run_cell("--bench-dir", bench_dir, "--workload", "small-d6",
                         "--seed", "3", "--seconds", "1", "--trace", "0")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert res["correct"] is False and res["failed"] >= 1
    # the numbers `correct` rests on, beside their limits: on the line,
    # and the last lines of standard error
    assert res["compared"]["verdicts_off_golden"] == [res["failed"], 0]
    assert all(limit == 0 for _value, limit in res["compared"].values())
    assert proc.stderr.strip().splitlines()[-len(res["compared"])].startswith(
        f"benchmark: verdicts_off_golden {res['failed']} (limit 0)")
    assert "benchmark: verdict warmup: total" in proc.stderr


def test_a_metric_arrives_as_one_file_that_names_its_cells(spare_bench):
    """The other way in: a per-layer metric whose own file lists the
    cells that report it. One file is added to the bench dir and nothing
    in it is edited; the named cell reports the metric beside those its
    own file lists. (A cell that a file does not name does not report
    it: the traced run above, beside the nine files that name the
    repository's cells.)"""
    bench_dir = spare_bench("small-d6", "raft3-small", 6, 6)

    def contents():
        return {os.path.join(base, f): open(os.path.join(base, f)).read()
                for base, _dirs, files in os.walk(bench_dir) for f in files}

    before = contents()
    added = os.path.join(bench_dir, "layer_metrics", "programs_again.json")
    with open(added, "w") as f:
        json.dump({"name": "programs_again", "unit": "count", "workloads": ["small-d6"],
                   "reduce": {"kind": "stat", "name": "programs_loaded"}}, f)
    assert {k: v for k, v in contents().items() if k != added} == before
    proc, res = run_cell("--bench-dir", bench_dir, "--workload", "small-d6",
                         "--seed", "7", "--seconds", "1", "--trace", "1")
    assert proc.returncode == 0, proc.stderr[-2000:]
    cell = load(BENCH, "workloads", "raft3-small.json")
    assert res["correct"] is True
    assert set(res["metrics"]) == {*cell["per_layer"], "programs_again"}
    assert res["metrics"]["programs_again"]["unit"] == "count"
    assert res["metrics"]["programs_again"]["value"] >= 1


def test_a_sharded_cell_is_files_only_and_needs_its_chips(spare_bench):
    """A cell of another engine and chip count arrives as two data files
    (the four-chip cell PERF.md section 7 keeps for later): it rehearses
    on four virtual devices, and is refused on two."""
    bench_dir = spare_bench(
        "x4-d6", "raft3-wide", 6, 6, engine="sharded", chips=4,
        engine_params={"chunk": 512, "msg_slots": 32})
    argv = ("--bench-dir", bench_dir, "--workload", "x4-d6",
            "--seed", "5", "--seconds", "1", "--trace", "0")
    proc, res = run_cell(*argv, devices=4)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert res["correct"] is True and res["device"]["count"] == 4
    assert set(res["metrics"]) == {"setup_s", "states_per_s"}
    proc, res = run_cell(*argv, devices=2)
    assert proc.returncode != 0 and res is None


def test_no_chip_no_number():
    proc, res = run_cell("--workload", "raft3-small", "--seed", "1",
                         "--seconds", "1", "--trace", "0", allow_cpu=False)
    assert proc.returncode != 0 and res is None


def test_unknown_cell_is_refused():
    proc, res = run_cell("--workload", "no-such-cell")
    assert proc.returncode == 64 and res is None


# ---------------- the yardstick ----------------

def test_compare_names_every_departure():
    golden = {"depth_counts": [1, 1, 3], "totals": {"2": {"total": 15, "terminal": 0}}}
    good = {"depth_counts": [1, 1, 3], "distinct": 5, "total": 15, "terminal": 0,
            "violation": None, "exit_cause": "max_depth",
            "waves": [{"depth": 1, "overflow_bits": 0}, {"depth": 2, "overflow_bits": 0}]}
    assert bfs.compare(good, golden, 2) == []
    for key, bad in (("depth_counts", [1, 1, 4]), ("distinct", 6), ("total", 14),
                     ("terminal", 1), ("violation", "NoLogDivergence"),
                     ("exit_cause", "exhausted"),
                     ("waves", [{"depth": 1, "overflow_bits": 1}, {"depth": 2, "overflow_bits": 0}])):
        assert bfs.compare(dict(good, **{key: bad}), golden, 2), key
    assert bfs.compare(good, golden, 3)  # deeper than the golden goes
    assert bfs.compare(good, dict(golden, totals={}), 2)


def test_steady_seconds_drops_what_the_host_adds_and_keeps_a_slowdown():
    quiet = [[1.0, 0.5, 2.0]] * 5
    assert bfs.steady_seconds(quiet) == pytest.approx(3.5)
    # a host that adds time everywhere but once to each piece
    noisy = [[1.0, 0.55, 2.05], [1.9, 0.5, 2.1], [1.05, 0.6, 2.0], [1.1, 0.55, 3.1]]
    assert bfs.steady_seconds(noisy) == pytest.approx(3.5)
    # a program 2 % slower in one piece, in every verdict
    assert bfs.steady_seconds([[1.0, 0.5, 2.04]] * 5) == pytest.approx(3.54)
    assert bfs.steady_seconds([[1.0, 0.5, 2.0]]) == pytest.approx(3.5)
    # verdicts that do not divide alike: the fastest whole one
    assert bfs.steady_seconds([[1.0, 2.5], [1.0, 0.5, 2.0], [4.0]]) == pytest.approx(3.5)


def test_interval_arithmetic():
    assert xplane.union([(5, 7), (0, 2), (1, 3)]) == [[0, 3], [5, 7]]
    assert xplane.subtract([[0, 10]], [[2, 3], [5, 12]]) == [[0, 2], [3, 5]]
    ops = [(0, 100, "while"), (10, 30, "a"), (30, 50, "all-to-all.1"), (60, 90, "a"), (120, 130, "b")]
    assert xplane.self_pieces(ops) == [
        (0, 10, "while"), (10, 30, "a"), (30, 50, "all-to-all.1"), (50, 60, "while"),
        (60, 90, "a"), (90, 100, "while"), (120, 130, "b")]
    trace = xplane.Trace(devices={"/device:TPU:0": ops, "/device:TPU:1": [(0, 50, "a")]},
                         host=[(0, 200, "verdict"), (100, 125, "device_get")])
    assert xplane.busy_s(trace) == pytest.approx((110 + 50) / 2 / 1e9)
    assert xplane.op_time_by_name(trace, top=2) == [["a", 100 / 1e9], ["while", 30 / 1e9]]
    assert xplane.regex_s(trace, "all-to-all") == pytest.approx(10 / 1e9)
    gaps = xplane.idle_gaps(trace, 0, 200_000, phases=[(0, 150_000, "wide_wave")])
    assert gaps[0][0] == "wide_wave/-" and gaps[0][1] == pytest.approx((200_000 - 130) / 1e9)


def test_readers_read_what_the_files_say():
    trace = xplane.Trace(devices={"d": [(0, 10**9, "all-to-all.1")]}, host=[])
    ctx = {
        "scalars": {"build_s": 2.0, "warmup_s": 8.0, "cache_new_entries": 0,
                    "device_busy_s": 3.0, "mstates": 0.5, "device_idle": 0.25,
                    "trace_window_s": 4.0},
        "waves": [{"frontier": 10, "wave_s": 0.1}, {"frontier": 9000, "wave_s": 0.9},
                  {"frontier": 20, "wave_s": 0.3}],
        "stats": {}, "params": {"chunk": 4096}, "trace": trace, "trace_path": None,
        "peaks": {},
    }
    want = {"build_s": 2.0, "warmup_s": 8.0, "cache_new_entries": 0,
            "narrow_wave_ms": 200.0, "device_busy_s_per_mstate": 6.0,
            "device_idle_share": 25.0}
    for name, value in want.items():
        got = readers.read(load(BENCH, "layer_metrics", f"{name}.json"), ctx)
        assert got == pytest.approx(value), name
    # a reader that finds nothing to read returns nothing
    empty = dict(ctx, scalars={}, waves=[], trace=None)
    for name in want:
        assert readers.read(load(BENCH, "layer_metrics", f"{name}.json"), empty) is None, name
    share = {"reduce": {"kind": "op_share", "regex": "all-to-all", "scale": 100}}
    assert readers.read(share, ctx) == pytest.approx(25.0)
    assert readers.read(share, empty) is None


def test_recorded_trace_reduces_to_the_pinned_numbers(tmp_path):
    """A traced depth-6 verdict of raft3 recorded on the v5e (PR 23): the
    reduction every later PR's numbers go through, pinned."""
    pinned = load(BENCH, "testdata", "tiny_v5e.pinned.json")
    path = tmp_path / "tiny.xplane.pb"
    with gzip.open(os.path.join(BENCH, "testdata", "tiny_v5e.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    trace = xplane.load(str(path))
    span = xplane.span_named(trace, "verdict")
    assert sorted(trace.devices) == pinned["devices"]
    assert xplane.busy_s(trace) == pytest.approx(pinned["busy_s"], rel=1e-9)
    assert (span[1] - span[0]) / 1e9 == pytest.approx(pinned["verdict_span_s"], rel=1e-9)
    assert xplane.op_time_by_name(trace, top=3) == [
        [n, pytest.approx(s, rel=1e-9)] for n, s in pinned["top_ops"]]
    assert xplane.idle_gaps(trace, span[0], span[1], top=3) == [
        [n, pytest.approx(s, rel=1e-9)] for n, s in pinned["top_gaps"]]
    assert xplane.regex_s(trace, pinned["regex"]) == pytest.approx(pinned["regex_s"], rel=1e-9)
