"""The readers of the program's tracing spine (PR 24): stage scopes on the
device ops, host spans of the wave loop, unrounded row clocks, compile
counters. On the CPU, with --allow-cpu; nothing here is a timing.

    python -m pytest benchmark/tests -q

The nine metrics they serve (files.TRACING) are files of
benchmark/layer_metrics/, each listing the cells that report it
(BENCHMARK.json since PR 34).
"""

from __future__ import annotations

import gzip
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import files  # noqa: E402
from files import BENCH, ROOT, STAGE_METRICS, TRACING, layer_metric_files, load  # noqa: E402
from test_benchmark import run_cell, spare_bench  # noqa: E402, F401
from benchmark import adapter, readers, xplane, xspace  # noqa: E402
from benchmark.readers import scope_time  # noqa: E402

METRICS = layer_metric_files()


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
    files.check_tracing_metrics(ROOT)


def test_the_readers_stage_list_is_the_programs():
    from raft_tpu.obs.events import TIMELINE_STAGES  # jax-free

    assert scope_time.STAGES is xplane.STAGES
    assert set(xplane.STAGES) == set(TIMELINE_STAGES) - {"checkpoint", "host"}
    files.check_stage_metrics(ROOT)


# ---------------- the wire format ----------------

def test_xspace_reads_the_ops_that_jax_reads(tmp_path):
    """The standard-library walk of the file, which ``xplane.load`` takes
    the device ops from, and jax's own reader agree on every op's
    interval and name, to the nanosecond."""
    from jax.profiler import ProfileData

    path = unpacked(tmp_path, "tiny_v5e")
    got = xspace.device_ops(path)
    jaxs = {p.name: sorted(
        (int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
        for line in p.lines if xspace.OP_LINE.match(line.name) for e in line.events)
        for p in ProfileData.from_file(path).planes if xspace.DEVICE_PLANE.match(p.name)}
    assert sorted(got) == sorted(jaxs) and got
    for name, (events, names, tf_ops) in got.items():
        assert sorted((s, e, names[meta]) for s, e, meta in events) == jaxs[name]
        assert tf_ops and all(isinstance(v, str) for v in tf_ops.values())
    trace = xplane.load(path)
    assert {k: [(s, e) for s, e, _ in v] for k, v in trace.devices.items()} == {
        k: [(s, e) for s, e, _ in v] for k, v in jaxs.items()}


def test_a_trace_without_scopes_reads_as_nothing(tmp_path):
    """PR 23's recorded trace: the program had no stage scope then, as
    executables from a stale compile cache have none. Its ops keep their
    bare names, and no stage metric reads it."""
    trace = xplane.load(unpacked(tmp_path, "tiny_v5e"))
    assert not trace.scoped
    assert not any("/" in name for ops in trace.devices.values() for _s, _e, name in ops)
    assert scope_time.seconds_by_scope(trace) is None
    ctx = {"scalars": {"mstates": 1.0}, "params": {}, "trace": trace}
    for name in STAGE_METRICS:
        assert readers.read(METRICS[name], ctx) is None, name
    assert readers.read(METRICS["expand_s_per_mstate"], dict(ctx, trace=None)) is None


# ---------------- the readers, on made-up input ----------------

def test_scope_path_takes_the_outermost_stage_and_the_named_scopes_under_it():
    path = xplane.scope_path
    assert path("jit(_wave_step)/while/body/dedup/merge/sort:") == ("dedup", "merge")
    assert path("jit(_wave_step)/while/body/canon/while/body/closed_call/inchunk/jit(argsort)/sort:") == ("canon", "inchunk")
    assert path("jit(_wave_step)/while/body/expand/vmap(vmap())/gather:") == ("expand",)
    assert path("jit(_wave_step)/while/body/expand/jit(cumsum)/DeviceBFS._st_expand/add:") == ("expand",)
    assert path("jit(_wave_step)/while/body/dedup/cond/branch_1_fun/search/jit(searchsorted)/lt:") == ("dedup", "search")
    assert path("jit(_wave_step)/while/body/emit/invariants/dedup/eq:") == ("emit", "invariants")
    assert path("jit(_wave_step)/while/body/emit/coverage/inner/add:", levels=3) == ("emit", "coverage", "inner")
    assert path("jit(_wave_step)/while/body/emit/coverage/add:", levels=1) == ("emit",)
    assert path("jit(_wave_step)/while/cond/lt:") == () and path(None) == ()
    # a name in the trace is the path, then the instruction and a fusion's kind
    assert xplane.scoped_name(
        "jit(_wave_step)/while/body/canon/inchunk/sort:",
        "%fusion.7 = u32[8]{0} fusion(u32[8]{0} %p), kind=kCustom, calls=%c") == "canon/inchunk/fusion.7[Custom]"
    assert xplane.scoped_name(None, "%copy.12 = u32[8]{0} copy(u32[8]{0} %p)") == "-/copy.12"


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
    idle = METRICS["wave_idle_ms"]
    # narrow waves: 20 ns and 20 ns idle; the wide one (220 ns) is left out
    assert readers.read(idle, ctx) == pytest.approx(20 / 1e9 * 1000)
    every = {"reduce": {"kind": "span_idle", "span": "wave"}}
    assert readers.read(every, ctx) == pytest.approx(20 / 1e9)
    assert readers.read({"reduce": {"kind": "span_idle", "span": "fetch"}}, ctx) is None
    # spans that do not pair with the rows read as nothing
    assert readers.read(idle, dict(ctx, waves=ctx["waves"][:2])) is None
    assert readers.read(idle, dict(ctx, trace=None)) is None
    assert readers.read(METRICS["host_share"], ctx) == pytest.approx(12.5)
    rounded = [{"frontier": 7, "wave_s": 1.0}]  # a program without host_s
    assert readers.read(METRICS["host_share"], dict(ctx, waves=rounded)) is None
    assert readers.read(METRICS["host_share"], dict(ctx, waves=[])) is None
    assert readers.read(METRICS["programs_loaded"], ctx) == 57
    assert readers.read(METRICS["programs_loaded"], dict(ctx, stats={})) is None


# ---------------- the recorded trace, with scopes ----------------

def test_scoped_trace_reduces_to_the_pinned_numbers(tmp_path):
    """A traced depth-6 verdict of raft3 recorded on the v5e by PR 24's
    chip run, with the stage scopes and the program's spans in it."""
    pinned = load(BENCH, "testdata", "scoped_v5e.pinned.json")
    path = unpacked(tmp_path, "scoped_v5e")
    ctx = ctx_of(path, pinned)
    busy = xplane.busy_s(ctx["trace"])
    assert busy == pytest.approx(pinned["busy_s"], rel=1e-9)
    seconds = scope_time.seconds_by_scope(ctx["trace"])
    assert seconds == {
        (None if k == "unscoped" else k): pytest.approx(v, rel=1e-9)
        for k, v in pinned["scope_s"].items()}
    # the buckets are a partition of the busy time
    assert sum(seconds.values()) == pytest.approx(busy, rel=5e-3)
    assert sum(seconds.values()) == pytest.approx(busy, rel=1e-9)
    assert seconds["exchange"] == 0  # one chip
    for name, want in pinned["metrics"].items():
        got = readers.read(METRICS[name], ctx)
        assert got == pytest.approx(want, rel=1e-9), name
    assert set(pinned["metrics"]) == set(TRACING)
    # time by op name is time by stage too: every name starts with its
    # scope path, `-` under no stage, and the names of a stage add up to
    # its bucket
    assert ctx["trace"].scoped
    by_name = dict(xplane.op_time_by_name(ctx["trace"], top=10**6))
    heads = {name.split("/")[0] for name in by_name}
    assert heads <= {*xplane.STAGES, xplane.UNSCOPED} and xplane.UNSCOPED in heads
    for stage, want in seconds.items():
        head = stage or xplane.UNSCOPED
        assert sum(s for name, s in by_name.items() if name.split("/")[0] == head) == pytest.approx(want, rel=1e-9)
    assert [n for n, _s in xplane.op_time_by_name(ctx["trace"], top=3)] == pinned["top_op_names"]


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

def test_a_cell_that_lists_the_new_metrics_runs_to_a_result_line(spare_bench):
    """A throw-away cell (raft3-small cut to depth 6) whose own file lists
    the nine metrics under ``per_layer``, the first way in: the rehearsal
    runs to a result line, the metrics that need no device plane are on
    it beside PR 23's, and those that need one are left out, not zero."""
    mine = load(BENCH, "workloads", "raft3-small.json")["per_layer"]
    bench_dir = spare_bench("tracing-d6", "raft3-small", 6, 6,
                            per_layer=[*mine, *TRACING])
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
    assert "from stop_trace to the result line" in proc.stderr


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
