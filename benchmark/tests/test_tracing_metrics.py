"""The readers of the program's tracing spine (PR 24): stage scopes on the
device ops, host spans of the wave loop, unrounded row clocks, compile
counters. On the CPU, with --allow-cpu; nothing here is a timing.

    python -m pytest benchmark/tests -q

The metrics they serve are in tracing_overlay.py, beside this file, and
not yet in BENCHMARK.json (why: that module's docstring).
"""

from __future__ import annotations

import gzip
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

import tracing_overlay  # noqa: E402
from test_benchmark import load, run_cell  # noqa: E402
from benchmark import adapter, readers, xplane, xspace  # noqa: E402
from benchmark.readers import scope_time  # noqa: E402


def unpacked(tmp_path, name):
    """A recorded trace of benchmark/testdata, unzipped."""
    path = tmp_path / f"{name}.xplane.pb"
    with gzip.open(os.path.join(BENCH, "testdata", f"{name}.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    return str(path)


def ctx_of(path, pinned):
    """The context run.py hands the readers, from a recorded trace and
    the rows and statistics recorded with it."""
    return {"scalars": {"mstates": pinned["mstates"]}, "waves": pinned["waves"],
            "stats": pinned["stats"], "params": {"chunk": pinned["chunk"]},
            "trace": xplane.load(path), "trace_path": path, "peaks": {}}


# ---------------- the files ----------------

def test_the_metrics_name_readers_cells_and_layers_that_exist():
    bench = load(ROOT, "BENCHMARK.json")
    layers = {m["layer"] for m in bench["per_layer"]} | {
        "Stages in a chunk", "Host wave loop"}  # PERF.md section 3's rows
    listed = {m["name"] for m in bench["per_layer"]}
    for name, spec in tracing_overlay.METRICS.items():
        assert spec["name"] == name and name not in listed
        assert spec["layer"] in layers
        assert os.path.exists(os.path.join(
            BENCH, "readers", f"{spec['reduce']['kind']}.py"))
    for cell, names in tracing_overlay.CELLS.items():
        end_to_end = load(BENCH, "workloads", f"{cell}.json")["end_to_end"]
        for name in names:
            assert tracing_overlay.METRICS[name]["moves"] in end_to_end, (cell, name)


def test_the_readers_stage_list_is_the_programs():
    from raft_tpu.obs.events import TIMELINE_STAGES  # jax-free

    assert set(scope_time.STAGES) == set(TIMELINE_STAGES) - {"checkpoint", "host"}
    want = {f"{s}_s_per_mstate" for s in scope_time.STAGES if s != "exchange"}
    assert want | {"unscoped_s_per_mstate"} == set(tracing_overlay.STAGE_METRICS)


# ---------------- the wire format ----------------

def test_xspace_reads_the_ops_that_jax_reads(tmp_path):
    """The standard-library walk of the file and jax's own reader agree
    on every op's interval, to the nanosecond."""
    path = unpacked(tmp_path, "tiny_v5e")
    trace = xplane.load(path)
    got = xspace.device_ops(path)
    assert sorted(got) == sorted(trace.devices)
    for name, (events, tf_ops) in got.items():
        assert [(s, e) for s, e, _ in events] == [
            (s, e) for s, e, _ in trace.devices[name]]
        assert tf_ops and all(isinstance(v, str) for v in tf_ops.values())


def test_a_trace_without_scopes_reads_as_nothing(tmp_path):
    """PR 23's recorded trace: the program had no stage scope then, as
    executables from a stale compile cache have none."""
    path = unpacked(tmp_path, "tiny_v5e")
    assert scope_time.seconds_by_scope(path) is None
    ctx = {"scalars": {"mstates": 1.0}, "params": {}, "trace_path": path}
    for name in tracing_overlay.STAGE_METRICS:
        assert readers.read(tracing_overlay.METRICS[name], ctx) is None, name


# ---------------- the readers, on made-up input ----------------

def test_stage_of_takes_the_outermost_stage():
    assert scope_time.stage_of("jit(_wave_step)/while/body/canon/eq:") == "canon"
    assert scope_time.stage_of("jit(_chunk_step)/shard_map/exchange/route/sort:") == "exchange"
    assert scope_time.stage_of("jit(merge)/seen_merge/sort:") == "seen_merge"
    assert scope_time.stage_of("jit(_wave_step)/while/body/emit/invariants/dedup_rows:") == "emit"
    assert scope_time.stage_of("jit(_wave_step)/while/cond/lt:") is None
    assert scope_time.stage_of("jit(canonical)/mul:") is None
    assert scope_time.stage_of(None) is None


def test_span_idle_wave_sum_ratio_and_stat():
    ops = [(0, 40, "a"), (50, 90, "b"), (100, 180, "c"), (400, 500, "d")]
    host = [(0, 100, "wave"), (100, 200, "wave"), (200, 520, "wave"),
            (10, 30, "dispatch"), (0, 600, "run")]
    ctx = {"scalars": {}, "params": {"chunk": 4096},
           "trace": xplane.Trace(devices={"/device:TPU:0": ops}, host=host),
           "trace_path": None,
           "waves": [{"frontier": 7, "host_s": 0.25, "wave_s": 1.0},
                     {"frontier": 4096, "host_s": 0.25, "wave_s": 2.0},
                     {"frontier": 9000, "host_s": 0.5, "wave_s": 5.0}],
           "stats": {"programs_loaded": 57}}
    idle = tracing_overlay.METRICS["wave_idle_ms"]
    # narrow waves: 20 ns and 20 ns idle; the wide one (220 ns) is left out
    assert readers.read(idle, ctx) == pytest.approx(20 / 1e9 * 1000)
    every = {"reduce": {"kind": "span_idle", "span": "wave"}}
    assert readers.read(every, ctx) == pytest.approx(20 / 1e9)
    assert readers.read({"reduce": {"kind": "span_idle", "span": "fetch"}}, ctx) is None
    # spans that do not pair with the rows read as nothing
    assert readers.read(idle, dict(ctx, waves=ctx["waves"][:2])) is None
    assert readers.read(idle, dict(ctx, trace=None)) is None
    assert readers.read(tracing_overlay.METRICS["host_share"], ctx) == pytest.approx(12.5)
    rounded = [{"frontier": 7, "wave_s": 1.0}]  # a program without host_s
    assert readers.read(tracing_overlay.METRICS["host_share"], dict(ctx, waves=rounded)) is None
    assert readers.read(tracing_overlay.METRICS["host_share"], dict(ctx, waves=[])) is None
    assert readers.read(tracing_overlay.METRICS["programs_loaded"], ctx) == 57
    assert readers.read(tracing_overlay.METRICS["programs_loaded"], dict(ctx, stats={})) is None


# ---------------- the recorded trace, with scopes ----------------

def test_scoped_trace_reduces_to_the_pinned_numbers(tmp_path):
    """A traced depth-6 verdict of raft3 recorded on the v5e by PR 24's
    chip run, with the stage scopes and the program's spans in it."""
    pinned = load(BENCH, "testdata", "scoped_v5e.pinned.json")
    path = unpacked(tmp_path, "scoped_v5e")
    ctx = ctx_of(path, pinned)
    busy = xplane.busy_s(ctx["trace"])
    assert busy == pytest.approx(pinned["busy_s"], rel=1e-9)
    seconds = scope_time.seconds_by_scope(path)
    assert seconds == {
        (None if k == "unscoped" else k): pytest.approx(v, rel=1e-9)
        for k, v in pinned["scope_s"].items()}
    # the buckets are a partition of the busy time
    assert sum(seconds.values()) == pytest.approx(busy, rel=5e-3)
    assert sum(seconds.values()) == pytest.approx(busy, rel=1e-9)
    assert seconds["exchange"] == 0  # one chip
    for name, want in pinned["metrics"].items():
        got = readers.read(tracing_overlay.METRICS[name], ctx)
        assert got == pytest.approx(want, rel=1e-9), name
    assert set(pinned["metrics"]) == set(tracing_overlay.METRICS)


def test_scoped_trace_holds_the_programs_spans_beside_the_verdict(tmp_path):
    """A benchmark verdict (telemetry = adapter.WaveClock, ``active``
    false, no --trace-dir): the program's run, init, wave, dispatch,
    fetch and seen_merge spans are on the plane `verdict` is on."""
    pinned = load(BENCH, "testdata", "scoped_v5e.pinned.json")
    trace = xplane.load(unpacked(tmp_path, "scoped_v5e"))
    verdict = xplane.span_named(trace, "verdict")
    assert (verdict[1] - verdict[0]) / 1e9 == pytest.approx(pinned["verdict_span_s"], rel=1e-9)
    inside = [sp for sp in trace.host if verdict[0] <= sp[0] and sp[1] <= verdict[1]]
    count = {n: sum(sp[2] == n for sp in inside)
             for n in ("run", "init", "wave", "dispatch", "fetch", "seen_merge", "finish")}
    depth = len(pinned["waves"])
    assert count == {"run": 1, "init": 1, "finish": 1, "wave": depth,
                     "dispatch": depth, "fetch": depth, "seen_merge": depth}
    (init,) = [sp for sp in inside if sp[2] == "init"]
    assert (init[1] - init[0]) / 1e9 == pytest.approx(pinned["init_span_s"], rel=1e-9)
    # idle time by PROGRAM span: the verdict's one large gap, the canon
    # memo's upload, is issued in `init` but leaves the chip waiting
    # inside the first wave's `fetch`
    phases = [sp for sp in inside if sp[2] in (
        "init", "dispatch", "fetch", "seen_merge", "telemetry", "finish")]
    gaps = xplane.idle_gaps(trace, verdict[0], verdict[1], phases=phases, top=3)
    assert gaps == [[n, pytest.approx(s, rel=1e-9)] for n, s in pinned["top_gaps_by_span"]]
    assert gaps[0][0].startswith("fetch/")


# ---------------- the command ----------------

def test_a_cell_that_lists_the_new_metrics_runs_to_a_result_line(tmp_path):
    """A throw-away cell (raft3-small cut to depth 6) in a bench dir that
    lists every new metric: the rehearsal runs to a result line, the
    metrics that need no device plane are on it beside PR 23's, and those
    that need one are left out, not zero."""
    bench_dir = tracing_overlay.build(str(tmp_path / "bench"))
    cell = load(bench_dir, "workloads", "raft3-small.json")
    traffic = load(bench_dir, "traffic", f"{cell['traffic']}.json")
    traffic.update(name="tracing-d6", max_depth=6, warmup_depth=6)
    cell.update(name="tracing-d6", traffic="tracing-d6",
                per_layer=[*load(BENCH, "workloads", "raft3-small.json")["per_layer"],
                           *tracing_overlay.METRICS])
    for d, spec in (("traffic", traffic), ("workloads", cell)):
        with open(os.path.join(bench_dir, d, "tracing-d6.json"), "w") as f:
            json.dump(spec, f)
    proc, res = run_cell("--bench-dir", bench_dir, "--workload", "tracing-d6",
                         "--seed", str(2**31 + 11), "--seconds", "1", "--trace", "1")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert res["correct"] is True
    got = res["metrics"]
    assert set(got) == {"build_s", "warmup_s", "cache_new_entries", "narrow_wave_ms",
                        "wave_idle_ms", "host_share", "programs_loaded"}
    assert got["programs_loaded"]["value"] >= 1
    assert 0 < got["host_share"]["value"] < 100
    assert got["wave_idle_ms"]["value"] >= 0
    assert got["host_share"]["unit"] == "%"


def test_a_benchmark_verdict_writes_the_programs_spans(tmp_path):
    """adapter.verdict under a profiler session, as modes/bfs.py makes
    its traced verdict: the program's own spans land beside `verdict`,
    one `wave` a depth, and the wave rows and statistics carry what the
    readers read."""
    import time

    import jax

    cell = load(BENCH, "workloads", "raft3-small.json")
    config = load(BENCH, "configs", cell["config"], "config.json")
    engine = adapter.build_engine(
        os.path.join(BENCH, "configs", cell["config"], config["cfg"]),
        "device", dict(cell["engine_params"], chunk=512), jax.devices()[:1])
    adapter.verdict(engine, 4, time.perf_counter)  # compile outside the trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("verdict"):
            got = adapter.verdict(engine, 4, time.perf_counter)
    finally:
        jax.profiler.stop_trace()
    assert len(got["stamps"]) == 4 + 2
    trace = xplane.load(xplane.find_xplane(str(tmp_path)))
    verdict = xplane.span_named(trace, "verdict")
    inside = [sp[2] for sp in trace.host if verdict[0] <= sp[0] and sp[1] <= verdict[1]]
    for name, n in (("run", 1), ("init", 1), ("finish", 1), ("wave", 4),
                    ("dispatch", 4), ("fetch", 4), ("seen_merge", 4)):
        assert inside.count(name) == n, (name, inside.count(name))
    for row in got["waves"]:
        assert row["dispatch_s"] + row["fetch_s"] + row["merge_s"] == pytest.approx(
            row["device_s"], abs=1e-9)
        assert row["compiles"] == 0
    assert got["stats"]["run_compiles"] == 0
    assert got["stats"]["programs_loaded"] >= 1
