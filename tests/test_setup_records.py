"""Set-up from inside (raft_tpu/obs/compiles.py, obs/trace.py): a record
for every program the process traces, lowers and loads, with the span
that caused it; the set-up phases; the run's own wall.

The contracts pinned here:

  * a run that compiles leaves records of all three kinds, each with the
    ``fun_name`` JAX gave it and a cause inside ``init`` or a ``wave``;
    a second run of the same engine adds none (the retrace counter);
  * seconds of a kind are the union of its records' intervals, never
    their sum (traces nest);
  * ``init_s + waves_s + finish_s`` is the run's wall;
  * the ``setup/*`` spans are in any profiler session, whichever
    telemetry facade the run was handed.
"""

import json
import time

import pytest

from raft_tpu import SETUP_S
from raft_tpu.models.raft import RaftParams, cached_model
from raft_tpu.obs import (
    COMPILES,
    HERE,
    ProgressRenderer,
    Telemetry,
    setup_phase,
    validate_event,
)
from raft_tpu.obs import compiles
from raft_tpu.obs.events import SETUP_KEYS, SUMMARY_KEYS

SMALL = RaftParams(
    n_servers=2, n_values=1, max_elections=1, max_restarts=0, msg_slots=16
)
INVS = ("LeaderHasAllAckedValues", "NoLogDivergence")
RAFT_CFG = "configs/standard-raft/Raft.cfg"


def _device(**kw):
    from raft_tpu.checker.device_bfs import DeviceBFS

    kw.setdefault("chunk", 256)
    kw.setdefault("frontier_cap", 1 << 12)
    kw.setdefault("seen_cap", 1 << 15)
    kw.setdefault("journal_cap", 1 << 15)
    return DeviceBFS(cached_model(SMALL), invariants=INVS, symmetry=True, **kw)


def _host():
    from raft_tpu.checker.bfs import BFSChecker

    return BFSChecker(
        cached_model(SMALL), invariants=INVS, symmetry=True, chunk=256)


def _sharded():
    import jax

    from raft_tpu.parallel.sharded import ShardedBFS

    return ShardedBFS(
        cached_model(SMALL), invariants=INVS, symmetry=True,
        devices=jax.devices()[:2], chunk=512, frontier_cap=2048,
        seen_cap=1 << 13)


# ------------------------------------------------ records, from events


def _bracket(rec, event, start, end, name, inside=()):
    """Feed a ``CompileRecords`` one bracket as JAX reports it: a scalar
    at its start, what happens inside, the time span at its end."""
    rec._enter(event, start, fun_name=name)
    for step in inside:
        step()
    rec._leave(event, start, end, fun_name=name)


def test_records_keep_nesting_the_cache_hit_and_the_union():
    rec = compiles.CompileRecords()
    # tracing f (0..3) traces g (1..2) inside it; then f lowers (3..4)
    # and loads (4..6), the persistent cache answering in 0.5 s
    _bracket(rec, compiles.TRACED, 0.0, 3.0, "f", inside=[
        lambda: _bracket(rec, compiles.TRACED, 1.0, 2.0, "g")])
    _bracket(rec, compiles.LOWERED, 3.0, 4.0, "jit(f)")
    _bracket(rec, compiles.LOADED, 4.0, 6.0, "jit(f)", inside=[
        lambda: rec._event(compiles.CACHE_HIT),
        lambda: rec._duration(compiles.CACHE_READ, 0.5)])
    # a second program, compiled: the hit is not carried over
    _bracket(rec, compiles.LOADED, 7.0, 8.0, "jit(h)")
    rec._leave("/jax/some/other_duration", 0.0, 100.0, fun_name="x")

    assert [(r["kind"], r["fun_name"], r["nesting"]) for r in rec.records] == [
        ("trace", "g", 1), ("trace", "f", 0), ("lower", "jit(f)", 0),
        ("load", "jit(f)", 0), ("load", "jit(h)", 0)]
    hit, miss = rec.records[3], rec.records[4]
    assert (hit["cache_hit"], hit["cache_read_s"]) == (True, 0.5)
    assert (miss["cache_hit"], miss["cache_read_s"]) == (False, 0.0)
    assert "cache_hit" not in rec.records[0]
    stats = rec.run_stats(compiles.CompileRecords().snapshot())
    assert stats["programs_traced"] == 1 and stats["programs_loaded"] == 2
    assert stats["run_compiles"] == 2 and stats["run_cache_hits"] == 1
    # the union, not the sum: g's second is inside f's three
    assert stats["load_trace_s"] == 3.0
    assert sum(r["seconds"] for r in rec.records if r["kind"] == "trace") == 4.0
    assert stats["load_lower_s"] == 1.0
    assert stats["load_cache_read_s"] == 0.5
    assert stats["load_compile_s"] == 2.5
    assert stats["load_union_s"] == 7.0
    # what a summary carries: the top-level records, without the clocks
    programs = rec.programs(compiles.CompileRecords().snapshot())
    assert [p["fun_name"] for p in programs] == [
        "f", "jit(f)", "jit(f)", "jit(h)"]
    assert all("start" not in p and p["cause"] == HERE.cause()
               for p in programs)


@pytest.mark.parametrize("intervals,total", [
    ([(0, 1), (2, 3)], 2),                    # apart
    ([(0, 2), (1, 3)], 3),                    # overlapping
    ([(1, 2), (0, 3)], 3),                    # the inner one ends first
    ([(1, 2), (3, 4), (0, 5)], 5),            # one bracket round two
    ([(0, 1), (1, 2)], 2),                    # touching
    ([(5, 6), (0, 1), (0.5, 5.5)], 6),        # ends out of order
    ([(5, 6), (8, 9), (0, 1), (7, 8.5)], 4),  # out of order, between two
])
def test_interval_union(intervals, total):
    union = compiles.IntervalUnion()
    for start, end in intervals:
        union.add(float(start), float(end))
    assert union.total == pytest.approx(total)


def test_slow_load_is_told_to_the_progress_line():
    import io

    rec = compiles.CompileRecords()
    out = io.StringIO()
    rec.watchers.append(ProgressRenderer(stream=out).loaded)
    HERE.run, HERE.top, HERE.depth, HERE.bracket = 7, "wave", 1, "dispatch"
    try:
        _bracket(rec, compiles.LOADED, 0.0, 42.13, "jit(_wave_step)")
        _bracket(rec, compiles.LOADED, 50.0, 50.5, "jit(zeros)")  # fast
        HERE.top, HERE.depth, HERE.bracket = "setup/engine", None, None
        _bracket(rec, compiles.LOADED, 60.0, 62.0, "jit(iota)", inside=[
            lambda: rec._event(compiles.CACHE_HIT)])
    finally:
        HERE.run = HERE.top = HERE.depth = HERE.bracket = None
    assert out.getvalue().splitlines() == [
        "loading jit(_wave_step): compiled in 42.1 s (wave 1, dispatch)",
        "loading jit(iota): read from the cache in 2.0 s (setup/engine)"]


def test_telemetry_with_progress_watches_loads_until_it_closes():
    before = list(COMPILES.watchers)
    with Telemetry(progress_every=0.0) as tel:
        assert COMPILES.watchers == [*before, tel.progress.loaded]
    assert COMPILES.watchers == before
    with Telemetry():
        assert COMPILES.watchers == before


# ------------------------------------------------ records, from a run


def test_first_run_leaves_records_of_all_three_kinds_with_their_cause():
    eng = _device()
    n0 = len(COMPILES.records)
    res = eng.run(max_depth=4, collect_metrics=True)
    new = COMPILES.records[n0:]
    assert {r["kind"] for r in new} == {"trace", "lower", "load"}
    for r in new:
        assert isinstance(r["fun_name"], str) and r["fun_name"], r
        assert r["end"] >= r["start"] and r["seconds"] == r["end"] - r["start"]
        cause = r["cause"]
        assert cause["run"] == eng._run_id, r
        assert cause["top"] in ("init", "wave"), r
        assert (cause["depth"] is None) == (cause["top"] == "init"), r
    # the wave program: traced, lowered and loaded by wave 1's dispatch
    wave = [r for r in new if r["nesting"] == 0
            and r["fun_name"] in ("_wave_step", "jit(_wave_step)")]
    assert [r["kind"] for r in wave] == ["trace", "lower", "load"]
    for r in wave:
        assert r["cause"] == {"run": eng._run_id, "top": "wave", "depth": 1,
                              "bracket": "dispatch"}
    assert wave[2]["cache_hit"] is False  # the CPU has no cache here
    # and the traces it is made of are inside it
    assert any(r["nesting"] > 0 for r in new if r["kind"] == "trace")
    assert res.stats["run_compiles"] == sum(r["kind"] == "load" for r in new)
    assert HERE.cause() == {
        "run": None, "top": None, "depth": None, "bracket": None}


def test_second_run_adds_no_record():
    """The retrace counter's contract: what ran once is in the process."""
    eng = _device()
    with Telemetry() as tel:
        first = eng.run(max_depth=4, telemetry=tel)
        n = len(COMPILES.records)
        assert tel.last_summary["programs"]
        again = eng.run(max_depth=4, telemetry=tel)
    assert len(COMPILES.records) == n
    assert tel.last_summary["programs"] == []
    for key in ("programs_traced", "programs_loaded", "load_union_s",
                "load_trace_s", "load_lower_s", "load_compile_s"):
        assert again.stats[key] == first.stats[key], key
    assert again.stats["run_compiles"] == 0


def test_growth_records_are_booked_to_their_wave_and_bracket():
    """Capacities so tiny that the journal outgrows them mid-run (as
    test_obs.test_growth_compile_is_booked_to_its_wave): the programs
    that re-shape the buffers say `grow` and the wave that grew. The
    journal starts at 40 rows, not that test's 32: the re-shaping
    programs are eager ops a process compiles once a shape, so with its
    shapes the later of the two in a worker would find no `grow`
    record."""
    eng = _device(chunk=32, frontier_cap=32, journal_cap=40)
    n0 = len(COMPILES.records)
    rows = eng.run(collect_metrics=True).metrics
    grew = [r["depth"] for r in rows if r["grow_s"] > 0]
    assert grew
    new = COMPILES.records[n0:]
    by_grow = [r for r in new if r["cause"]["bracket"] == "grow"]
    assert {r["kind"] for r in by_grow} == {"trace", "lower", "load"}
    assert {r["cause"]["depth"] for r in by_grow} <= set(grew)
    assert all(r["cause"]["top"] == "wave" for r in by_grow)
    # the wave after it loads the wave program at the new shapes
    regrown = [r for r in new if r["kind"] == "load"
               and r["fun_name"] == "jit(_wave_step)"]
    assert [r["cause"]["depth"] for r in regrown][:2] == [1, grew[0] + 1]
    assert all(r["cause"]["bracket"] == "dispatch" for r in regrown)


def test_nested_traces_count_once_and_the_parts_make_up_the_union():
    eng = _device()
    res = eng.run(max_depth=4)
    st = res.stats
    by_kind = {kind: [r for r in COMPILES.records if r["kind"] == kind]
               for kind in ("trace", "lower", "load")}
    assert any(r["nesting"] > 0 for r in by_kind["trace"])
    assert st["load_trace_s"] < sum(r["seconds"] for r in by_kind["trace"])
    assert st["programs_traced"] == sum(
        r["nesting"] == 0 for r in by_kind["trace"])
    assert st["programs_loaded"] == len(by_kind["load"])
    parts = (st["load_trace_s"] + st["load_lower_s"] + st["load_compile_s"]
             + st["load_cache_read_s"])
    # the kinds may overlap (a lowering that traces), never leave a gap
    assert st["load_union_s"] <= parts + 1e-9
    assert parts <= sum(r["seconds"] for r in COMPILES.records) + 1e-9
    assert st["load_compile_s"] == pytest.approx(
        sum(r["seconds"] - r["cache_read_s"] for r in by_kind["load"]))


# ------------------------------------------------ the run's own wall


@pytest.mark.parametrize("engine", [
    "device", "host", pytest.param("sharded", marks=pytest.mark.slow)])
def test_init_waves_and_finish_are_the_runs_wall(engine):
    eng = {"device": _device, "host": _host, "sharded": _sharded}[engine]()
    t = time.perf_counter()
    res = eng.run(max_depth=4, collect_metrics=True)
    wall = time.perf_counter() - t
    st = res.stats
    assert min(st["init_s"], st["waves_s"], st["finish_s"]) > 0
    tiled = st["init_s"] + st["waves_s"] + st["finish_s"]
    # read beside res.seconds: a few statements apart
    assert tiled == pytest.approx(res.seconds, abs=2e-3)
    assert res.seconds <= wall
    # the rows' wave_s leave out each wave's telemetry bracket and the
    # loop's head, and nothing else
    rows = sum(r["wave_s"] for r in res.metrics)
    assert rows <= st["waves_s"]
    assert st["waves_s"] - rows < 0.05 * len(res.metrics)
    assert res.metrics[0]["elapsed_s"] - res.metrics[0]["wave_s"] == (
        pytest.approx(st["init_s"], abs=2e-3))


# ------------------------------------------------ the set-up phases


def test_setup_phases_keep_self_seconds():
    before = dict(SETUP_S)
    t = time.perf_counter()
    with setup_phase("engine"):
        assert HERE.top == "setup/engine"
        time.sleep(0.02)
        with setup_phase("engine/canon"):  # a child span, engine's seconds
            assert HERE.top == "setup/engine/canon"
            time.sleep(0.02)
            with setup_phase("backend"):   # its own, wherever it starts
                time.sleep(0.03)
        assert HERE.top == "setup/engine"
    wall = time.perf_counter() - t
    assert HERE.top is None
    took = {k: SETUP_S[k] - before[k] for k in SETUP_S if k != "pre"}
    assert took["backend"] >= 0.03 and took["engine"] >= 0.04
    # no second counted twice: the backend's are not the engine's too
    assert took["engine"] + took["backend"] == pytest.approx(wall, abs=2e-3)
    assert took["cfg"] == took["model"] == took["import"] == 0
    assert set(SETUP_S) == {k[len("setup_"):-2] for k in SETUP_KEYS
                            if k.startswith("setup_")}


def test_process_record_is_on_every_result():
    pre = SETUP_S["pre"]
    assert pre is not None and pre >= 0, (
        "this platform has /proc: the process's age at the package's "
        "first line is known")
    assert SETUP_S["import"] > 0
    res = _device().run(max_depth=2)
    assert res.stats["setup_pre_s"] == pre
    assert res.stats["setup_engine_s"] > 0
    for key in ("programs_loaded", "programs_traced", *SETUP_KEYS):
        assert key in SUMMARY_KEYS and key in res.stats, key
    assert _host().run(max_depth=2).stats["setup_engine_s"] >= (
        res.stats["setup_engine_s"])


def _host_spans(trace_dir):
    """[(start_ns, end_ns, name)] of the host plane, by start."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            spans.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in line.events)
    return sorted(spans, key=lambda s: (s[0], -s[1]))


@pytest.mark.parametrize("facade", ["null", "wave_clock"])
def test_setup_spans_in_any_profiler_session(tmp_path, facade):
    """cfg, model, engine, run: what a caller passes through without
    knowing, as the benchmark's adapter does."""
    import jax

    from benchmark.adapter import WaveClock
    from raft_tpu.checker.device_bfs import DeviceBFS
    from raft_tpu.models.registry import build_from_cfg
    from raft_tpu.utils.cfg import parse_cfg

    tel = None if facade == "null" else WaveClock(time.perf_counter)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        setup = build_from_cfg(parse_cfg(RAFT_CFG), msg_slots=16)
        eng = DeviceBFS(
            setup.model, invariants=setup.invariants, symmetry=setup.symmetry,
            chunk=64, frontier_cap=1 << 10, seen_cap=1 << 12,
            journal_cap=1 << 12)
        eng.run(max_depth=2, telemetry=tel)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    names = [s[2] for s in spans]
    order = ["setup/cfg", "setup/model", "setup/engine", "run"]
    (cfg, model, engine, run) = (
        spans[names.index(name)] for name in order)
    assert [names.count(name) for name in order] == [1, 1, 1, 1]
    tops = [cfg, model, engine, run]
    assert all(a[1] <= b[0] for a, b in zip(tops, tops[1:]))
    canon = spans[names.index("setup/engine/canon")]
    assert engine[0] <= canon[0] and canon[1] <= engine[1]
    init = spans[names.index("init")]
    assert run[0] <= init[0] and init[1] <= run[1]


# ------------------------------------------------ the stream, the CLI


def _summary(**over):
    ev = dict.fromkeys(SUMMARY_KEYS, 0)
    ev.update(event="summary", exit_cause="exhausted", **over)
    return ev


GOOD_PROGRAM = {
    "kind": "load", "fun_name": "jit(_merge)", "seconds": 31.0, "nesting": 0,
    "cache_hit": False, "cache_read_s": 0.0,
    "cause": {"run": 1, "top": "wave", "depth": 19, "bracket": "seen_merge"},
}


def test_summary_with_programs_validates():
    assert validate_event(_summary(programs=[GOOD_PROGRAM])) == []
    assert validate_event(_summary(setup_pre_s=None)) == []
    assert validate_event(_summary()) == []  # no programs: an older stream


@pytest.mark.parametrize("programs,said", [
    ([dict(GOOD_PROGRAM, kind="link")], "kind 'link'"),
    ([dict(GOOD_PROGRAM, seconds=-1.0)], "seconds -1.0"),
    ([dict(GOOD_PROGRAM, cause={"run": 1})], "cause"),
    ([{k: v for k, v in GOOD_PROGRAM.items() if k != "cache_hit"}],
     "cache_hit"),
    ([{"kind": "trace"}], "missing"),
    (["_merge"], "not an object"),
    ({"_merge": 31.0}, "not a list"),
])
def test_summary_with_a_broken_program_record_does_not_validate(
        programs, said):
    problems = validate_event(_summary(programs=programs))
    assert len(problems) == 1 and said in problems[0], problems


@pytest.mark.parametrize("key,value", [
    ("load_union_s", -0.5), ("setup_cfg_s", "fast"),
    ("programs_traced", True)])
def test_summary_setup_keys_are_non_negative_numbers(key, value):
    problems = validate_event(_summary(**{key: value}))
    assert len(problems) == 1 and key in problems[0], problems


def test_summary_without_the_setup_keys_is_missing_them():
    ev = _summary()
    del ev["load_union_s"]
    (problem,) = validate_event(ev)
    assert "missing declared keys: ['load_union_s']" in problem


def test_cli_json_carries_the_setup_keys_and_the_programs(tmp_path, capsys):
    from raft_tpu.__main__ import main
    from scripts.check_metrics_schema import validate_file

    mpath = tmp_path / "m.jsonl"
    rc = main([RAFT_CFG, "--platform", "cpu", "--msg-slots", "16",
               "--max-depth", "2", "--chunk", "64", "--frontier-cap", "1024",
               "--seen-cap", "4096", "--journal-cap", "4096", "--json",
               "--metrics-out", str(mpath)])
    cap = capsys.readouterr()
    assert rc == 0, cap.err
    summ = json.loads(cap.out.strip().splitlines()[-1])
    assert summ["event"] == "summary"
    for key in ("programs_loaded", "programs_traced", "init_s", "waves_s",
                "finish_s", *SETUP_KEYS):
        assert isinstance(summ[key], float | int), key
    # the phases the CLI passed through on its way to the run
    for key in ("setup_import_s", "setup_cfg_s", "setup_model_s",
                "setup_engine_s", "load_trace_s", "load_lower_s",
                "load_compile_s", "load_union_s"):
        assert summ[key] > 0, key
    kinds = {(p["kind"], p["fun_name"]) for p in summ["programs"]}
    assert {("trace", "_wave_step"), ("lower", "jit(_wave_step)"),
            ("load", "jit(_wave_step)")} <= kinds
    assert all(p["nesting"] == 0 and p["cause"]["run"] is not None
               for p in summ["programs"])
    counts, problems = validate_file(str(mpath))
    assert not problems and counts["summary"] == 1
