"""Live telemetry (raft_tpu/obs): JSONL stream validity, zero extra
device syncs, watchdog, schema/renderer lock-step, fleet stats, CLI.

The headline guarantees pinned here:

  * the metrics stream is count-accurate — the final cumulative
    ``distinct`` in the wave stream equals the checker's reported
    distinct, at any cadence;
  * attaching a collector adds ZERO extra ``jax.device_get`` calls and
    leaves the result bit-identical (telemetry reuses the once-per-wave
    host snapshot the loop already fetches);
  * the progress renderer only consumes declared WAVE_KEYS, so the
    stderr line and the JSONL schema cannot drift apart.
"""

import io
import json
import re
import time

import pytest

from raft_tpu.obs import (
    DECLARED_EVENTS,
    MANIFEST_KEYS,
    STALL_KEYS,
    SUMMARY_KEYS,
    TIMELINE_STAGES,
    WAVE_KEYS,
    MetricsCollector,
    ProgressRenderer,
    Telemetry,
    format_count,
    hashv_of,
    validate_lines,
)
from raft_tpu.models.raft import RaftParams, cached_model
from raft_tpu.obs.events import HBM_KEYS, HBM_MEASURED_KEYS
from raft_tpu.obs.memwatch import ROW_KEYS as HBM_ROW_KEYS

SMALL = RaftParams(
    n_servers=2, n_values=1, max_elections=1, max_restarts=0, msg_slots=16
)
INVS = ("LeaderHasAllAckedValues", "NoLogDivergence")


def _device(**kw):
    from raft_tpu.checker.device_bfs import DeviceBFS

    kw.setdefault("chunk", 256)
    kw.setdefault("frontier_cap", 1 << 12)
    kw.setdefault("seen_cap", 1 << 15)
    kw.setdefault("journal_cap", 1 << 15)
    return DeviceBFS(cached_model(SMALL), invariants=INVS, symmetry=True, **kw)


# ---------------------------------------------------------------- stream


def test_device_metrics_stream_valid_and_count_accurate(tmp_path):
    path = tmp_path / "m.jsonl"
    with Telemetry(metrics_path=str(path)) as tel:
        res = _device().run(max_depth=4, telemetry=tel)
    with open(path) as fh:
        lines = fh.readlines()
    counts, problems = validate_lines(lines)
    assert not problems, problems
    assert counts["manifest"] == 1 and counts["summary"] == 1
    assert counts["wave"] >= 4  # >= depth-many wave events
    # coverage pairs with each wave plus one final snapshot
    assert counts["coverage"] == counts["wave"] + 1

    events = [json.loads(ln) for ln in lines]
    assert events[0]["event"] == "manifest"
    assert events[-1]["event"] == "summary"
    man, summ = events[0], events[-1]
    waves = [e for e in events if e["event"] == "wave"]

    # every declared key present on every event
    for ev, keys in zip((man, waves[0], summ), (MANIFEST_KEYS, WAVE_KEYS, SUMMARY_KEYS)):
        assert all(k in ev for k in keys), (ev["event"], keys)

    # count-accuracy: cumulative distinct in the stream == result
    assert waves[-1]["distinct"] == res.distinct
    assert summ["distinct"] == res.distinct
    assert summ["total"] == res.total
    assert summ["depth"] == res.depth
    assert summ["exit_cause"] == "max_depth"
    assert summ["waves"] == len(waves)
    # wave index strictly increasing from 1
    assert [w["wave"] for w in waves] == list(range(1, len(waves) + 1))

    # the dedup stage's counter: on every row, the run's total on the
    # summary, and both ends say what the stage can choose from
    assert all(w["dedup_sort_lanes"] > 0 for w in waves)
    assert summ["dedup_sort_lanes"] == sum(
        w["dedup_sort_lanes"] for w in waves) == res.stats["dedup_sort_lanes"]
    # the run is merged, so nothing was searched, and every row says
    # which run its wave met
    assert summ["dedup_search_queries"] == 0 == sum(
        w["dedup_search_queries"] for w in waves)
    assert summ["dedup_search_steps"] == 0 == sum(
        w["dedup_search_steps"] for w in waves)
    assert {w["seen_lanes"] for w in waves} == {summ["seen_lanes"]}
    # the apply pass's counters: the rows its tiles built follow what a
    # wave's chunks keep, under the rows its plan budgets
    assert all(0 < w["expand_rows_built"] <= w["expand_rows_budget"]
               for w in waves)
    for key in ("expand_rows_built", "expand_rows_budget"):
        assert summ[key] == sum(w[key] for w in waves) == res.stats[key]
    assert man["dedup_plan"]["wave_prefix"] == [0, 4096]
    assert summ["dedup_plan"] == res.stats["dedup_plan"]

    # manifest provenance: ident carries the fingerprint revision
    assert man["engine"] == "device"
    assert man["hashv"] == hashv_of(man["ident"]) > 0
    assert man["symmetry"] is True


def test_cadence_keeps_stream_count_accurate(tmp_path):
    path = tmp_path / "m2.jsonl"
    with Telemetry(metrics_path=str(path), every=3) as tel:
        res = _device().run(max_depth=5, telemetry=tel)
    with open(path) as fh:
        lines = fh.readlines()
    _, problems = validate_lines(lines)
    assert not problems, problems
    waves = [json.loads(ln) for ln in lines if '"wave"' in ln]
    waves = [w for w in waves if w["event"] == "wave"]
    assert 0 < len(waves) < 5  # thinned by cadence...
    # ...but the LAST wave is always flushed so the tail stays accurate
    assert waves[-1]["distinct"] == res.distinct


def test_telemetry_adds_zero_device_syncs_and_is_bit_identical(monkeypatch):
    import jax

    eng = _device()
    eng.run(max_depth=4)  # warm the compile cache outside the count

    real = jax.device_get
    calls = {"n": 0}

    def counting(x):
        calls["n"] += 1
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting)
    bare = eng.run(max_depth=4)
    n_bare = calls["n"]

    calls["n"] = 0
    tel = Telemetry()
    instrumented = eng.run(max_depth=4, telemetry=tel)
    n_tel = calls["n"]
    monkeypatch.undo()

    assert n_tel == n_bare, (
        f"telemetry added {n_tel - n_bare} device_get syncs per run"
    )
    assert instrumented.distinct == bare.distinct
    assert instrumented.depth_counts == bare.depth_counts
    assert instrumented.total == bare.total
    assert instrumented.terminal == bare.terminal
    assert len(tel.wave_events()) >= 4
    # the per-action coverage block rides the same snapshot: present,
    # bit-identical with telemetry on/off, final event mirrors it
    assert bare.coverage is not None
    assert instrumented.coverage == bare.coverage
    covs = tel.coverage_events()
    assert covs and covs[-1]["final"] is True
    assert covs[-1]["actions"] == instrumented.coverage


# -------------------------------------------------------------- watchdog


def _fields(keys, **kw):
    """All declared keys zeroed except event/wave (the collector owns
    those), overridden by kw."""
    ev = dict.fromkeys(keys, 0)
    ev.pop("event", None)
    ev.pop("wave", None)
    ev.update(kw)
    return ev


def _wave(depth, wave_s):
    return _fields(WAVE_KEYS, depth=depth, wave_s=wave_s)


def test_watchdog_flags_stall_against_prior_median():
    c = MetricsCollector(stall_factor=4.0, stall_min_waves=5)
    c.manifest(_fields(MANIFEST_KEYS))
    for d in range(5):
        c.wave(_wave(d, 1.0))
    assert c.stalls == 0
    c.wave(_wave(5, 10.0))  # 10x the rolling median of 1.0
    assert c.stalls == 1
    stall = c.events_of("stall")[0]
    assert all(k in stall for k in STALL_KEYS)
    assert stall["factor"] == pytest.approx(10.0)
    assert stall["median_wave_s"] == pytest.approx(1.0)
    # judged BEFORE joining the window: an immediate second slow wave
    # still compares against the healthy median
    c.wave(_wave(6, 10.0))
    assert c.stalls == 2
    c.summary(_fields(SUMMARY_KEYS))
    assert c.last_summary["stalls"] == 2

    # too few samples -> never fires (no median to trust yet)
    c2 = MetricsCollector(stall_min_waves=5)
    c2.manifest(_fields(MANIFEST_KEYS))
    for d in range(4):
        c2.wave(_wave(d, 1.0))
    c2.wave(_wave(4, 50.0))
    assert c2.stalls == 0


# -------------------------------------------- schema/renderer lock-step


def test_schema_and_renderer_stay_in_sync():
    # the contract check_metrics_schema.py and the engines share
    assert tuple(n for n, _ in DECLARED_EVENTS) == (
        "manifest", "wave", "stall", "coverage", "summary",
        "retry", "resume", "ckpt_generation", "preempt",
        "shard_lost", "reshard", "shard_stall",
        "memwatch",
    )
    for _, keys in DECLARED_EVENTS:
        assert keys[0] == "event"
        assert len(set(keys)) == len(keys)
    # the renderer may only read declared wave keys
    assert set(ProgressRenderer.CONSUMES) <= set(WAVE_KEYS)

    ev = dict.fromkeys(WAVE_KEYS, 0)
    ev.update(event="wave", depth=7, generated_total=1_200_000,
              distinct=310_000, distinct_per_s=2648.0,
              canon_dup_rate=0.53)
    line = ProgressRenderer().render_wave(ev)
    assert line == (
        "Progress (depth 7): 1.2M generated, 310k distinct, 2,648/s, "
        "dup 53%"
    )

    out = io.StringIO()
    r = ProgressRenderer(every_s=0.0, stream=out)
    r(ev)
    r({"event": "stall", "wave": 9, "depth": 7, "wave_s": 8.0,
       "median_wave_s": 1.0, "factor": 8.0})
    summ = dict.fromkeys(SUMMARY_KEYS, 0)
    summ.update(event="summary", exit_cause="exhausted", seconds=1.0)
    r(summ)
    text = out.getvalue()
    assert "Progress (depth 7)" in text
    assert "Warning: wave 9" in text
    assert "Finished" in text and "(exhausted)" in text


# What a stream carries beyond the schema's keys, an engine: the key
# sets of a depth-4 run at PR 45's tree (8ace3e6, read from a `git
# archive` copy). `checker/engine.py` makes the declared keys of every
# row and summary (`wave_row`, `summary_fields`); an engine adds its own
# where it calls them, after the declared ones.
_PHASES = ("dispatch_s", "fetch_s", "merge_s", "grow_s", "compiles",
           "compile_s")
_RUN_LOADED = ("run_compiles", "run_compile_s", "run_cache_hits",
               "run_cache_read_s")
# the run's device memory (obs/memwatch.py), on ``stats`` and so on the
# summary, whoever listens
_TRACED_RUN = (*_RUN_LOADED, "init_s", "waves_s", "finish_s", "programs",
               *HBM_KEYS)
ROW_OWN = {
    "device": (*_PHASES, "dedup_sort_lanes", "dedup_search_queries",
               "dedup_search_steps", "seen_lanes", "expand_rows_built",
               "expand_rows_budget"),
    "host": (),
    "sharded": (*_PHASES, "a2a_lanes", "a2a_bytes", "shard_new",
                "shard_new_min", "shard_new_max"),
    "host_fleet": ("jobs_active",),
}
SUMMARY_OWN = {
    "device": (*_TRACED_RUN, "dedup_plan", "dedup_sort_lanes",
               "dedup_search_queries", "dedup_search_steps",
               "expand_rows_built", "expand_rows_budget"),
    "host": _TRACED_RUN,
    "sharded": (*_TRACED_RUN, "dedup_plan", "shard_dup_lanes",
                "shard_skew"),
    # the group's summary, then a synthesized one a job
    "host_fleet": (*_RUN_LOADED, "fleet_jobs"),
}
STATS_OWN = {  # beyond obs/compiles.py run_stats and the top spans
    "device": {"dedup_plan", "canon_tier3_local", "canon_tier3_full",
               "dedup_sort_lanes", "dedup_search_queries",
               "dedup_search_steps", "expand_rows_built",
               "expand_rows_budget"},
    "host": set(),
    "sharded": {"dedup_plan", "canon_tier3_local", "canon_tier3_full",
                "canon_dup_lanes", "canon_dup_rate", "shard_dup_lanes",
                "shard_distinct", "shard_skew", "coverage"},
}


def _packed_fleet():
    """A BFSChecker over two packed Raft jobs (tests/test_fleet.py's
    small grid), and the jobs' names."""
    from raft_tpu.checker.bfs import BFSChecker
    from raft_tpu.fleet.grouping import group_jobs
    from raft_tpu.fleet.manifest import parse_manifest_obj
    from raft_tpu.fleet.packer import build_packed

    mf = parse_manifest_obj({
        "spec": "Raft",
        "defaults": {
            "constants": {"Server": ["s1", "s2"], "Value": ["v1"],
                          "MaxElections": 1, "MaxRestarts": 0},
            "invariants": list(INVS), "msg_slots": 16},
        "grid": {"MaxElections": [1, 2]},
    }, path="<test>")
    (group,) = group_jobs(mf)
    setup = group.setups[0]
    return BFSChecker(
        build_packed(group), invariants=setup.invariants,
        symmetry=setup.symmetry, chunk=512,
    ), [j.name for j in group.jobs]


def _run_of(engine, **kw):
    """A depth-4 run of one of the three engines, as the test above
    builds them."""
    from raft_tpu.checker.bfs import BFSChecker

    if engine == "device":
        return _device().run(max_depth=4, **kw)
    if engine == "host":
        return BFSChecker(
            cached_model(SMALL), invariants=INVS, symmetry=True, chunk=256,
        ).run(max_depth=4, **kw)
    return _sharded(2, frontier_cap=2048, seen_cap=1 << 13).run(
        max_depth=4, **kw)


@pytest.mark.parametrize("engine", ["device", "host", "sharded", "host_fleet"])
def test_rows_and_summary_have_one_author(engine):
    """Every engine's wave rows are the schema's keys in the schema's
    order, then that engine's declared own keys; its summary and its
    result's ``stats`` likewise: the key sets of the parent tree."""
    from raft_tpu.obs.events import PROCESS_KEYS

    tel = Telemetry()
    stats = None
    if engine == "host_fleet":
        eng, names = _packed_fleet()
        eng.run_fleet(job_names=names, max_depth=4, telemetry=tel)
    else:
        stats = _run_of(engine, telemetry=tel).stats
    tel.close()

    rows = tel.wave_events()
    assert [r["depth"] for r in rows] == [1, 2, 3, 4]
    for row in rows:
        assert tuple(row) == (*WAVE_KEYS, *ROW_OWN[engine])
    summaries = [e for e in tel.events if e["event"] == "summary"]
    # "waves" and "stalls" are the collector's, after the engine's
    declared = set(SUMMARY_KEYS)
    assert set(summaries[0]) == declared | set(SUMMARY_OWN[engine])
    # how full the frontier got: the widest wave's `new`, said alike
    assert summaries[0]["frontier_peak_rows"] == max(r["new"] for r in rows)
    if engine == "host_fleet":
        assert [s["job"] for s in summaries[1:]] == names
        for s in summaries[1:]:
            assert set(s) == declared | {*_RUN_LOADED, "job"}
    else:
        assert len(summaries) == 1
        base = {*PROCESS_KEYS, *_RUN_LOADED, "init_s", "waves_s", "finish_s",
                "frontier_peak_rows", "restart_fired", *HBM_KEYS}
        assert set(stats) == base | STATS_OWN[engine]
        # stats is the summary's own dict, but for the sharded engine's
        # fleet aggregates
        assert all(summaries[0][k] == v for k, v in stats.items()
                   if k in summaries[0])


@pytest.mark.parametrize("engine", ["device", "host", "sharded"])
def test_the_engines_say_device_memory_alike_telemetry_or_not(engine):
    """Rows, ``stats`` and the summary of the three engines carry the
    same ``hbm_*`` keys (``checker/engine.py``'s builders, from the
    run's ``MemWatch``), and a bare run carries what a telemetry run
    carries. On the CPU the allocator reports nothing: every measured
    key is null and the plan stands."""
    tel = Telemetry()
    stats = _run_of(engine, telemetry=tel).stats
    tel.close()
    bare = _run_of(engine, collect_metrics=True)  # NULL_TELEMETRY

    (summary,) = [e for e in tel.events if e["event"] == "summary"]
    for got in (stats, bare.stats, summary):
        assert [k for k in got if k.startswith("hbm_")] == list(HBM_KEYS)
    assert all(summary[k] == stats[k] for k in HBM_KEYS)
    for row in (*tel.wave_events(), *bare.metrics):
        assert [k for k in row if k.startswith("hbm_")] == list(HBM_ROW_KEYS)
        assert row["hbm_bytes"] is None and row["hbm_peak_rise"] is None
        # the plan's, on the CPU (the host engine's few KB round to 0)
        assert (engine == "host" or row["hbm_frac"] > 0) and (
            row["hbm_frac"] == round(row["hbm_frac"], 6) < 1)
    assert [r["hbm_frac"] for r in bare.metrics] == [
        r["hbm_frac"] for r in tel.wave_events()]
    for got in (stats, bare.stats):
        assert all(got[k] is None for k in (
            *HBM_MEASURED_KEYS, "hbm_peak_frac", "hbm_live_frac",
            "hbm_plan_gap_frac"))
        assert got["hbm_plan_bytes"] == bare.stats["hbm_plan_bytes"] > 0
        assert got["hbm_plan_frac"] == (
            got["hbm_plan_bytes"] / got["hbm_budget_bytes"])
    # the widest row's plan is the run's
    assert max(r["hbm_frac"] for r in bare.metrics) == pytest.approx(
        bare.stats["hbm_plan_frac"], abs=1e-6)


def test_format_count():
    assert format_count(1234) == "1,234"
    assert format_count(310_000) == "310k"
    assert format_count(1_200_000) == "1.2M"
    assert format_count(3_400_000_000) == "3.4B"


# --------------------------------------------------- schema validation


def test_check_metrics_schema_script(tmp_path):
    from scripts.check_metrics_schema import main, validate_file

    good = tmp_path / "good.jsonl"
    c = MetricsCollector(path=str(good))
    c.manifest(_fields(MANIFEST_KEYS, ident="x/hashv=5"))
    for d in range(3):
        c.wave(_wave(d, 0.5))
    c.summary(_fields(SUMMARY_KEYS, exit_cause="exhausted"))
    c.close()
    counts, problems = validate_file(str(good))
    assert not problems, problems
    assert counts == {"manifest": 1, "wave": 3, "summary": 1}
    assert main([str(good)]) == 0

    bad = tmp_path / "bad.jsonl"
    lines = good.read_text().splitlines()
    w1 = json.loads(lines[1])
    del w1["distinct"]  # missing declared key
    w1["wave"] = 7  # breaks strict increase for the next wave
    lines[1] = json.dumps(w1)
    bad.write_text("\n".join(lines) + "\n{not json\n")
    _, problems = validate_file(str(bad))
    text = "\n".join(problems)
    assert "missing declared keys" in text
    assert "strictly" in text
    assert "not valid JSON" in text
    assert main([str(bad)]) == 1

    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    _, problems = validate_file(str(empty))
    assert any("empty stream" in p for p in problems)
    assert main([]) == 64


def test_double_buffered_write_lags_by_one(tmp_path):
    path = tmp_path / "buf.jsonl"
    c = MetricsCollector(path=str(path))
    c.manifest(_fields(MANIFEST_KEYS))
    c.wave(_wave(0, 0.1))
    c._fh.flush()
    on_disk = path.read_text().splitlines()
    assert len(on_disk) == 1  # wave 1 still pending; manifest flushed
    c.close()
    assert len(path.read_text().splitlines()) == 2


# ----------------------------------------------------- engines: others


@pytest.mark.slow
def test_host_checker_stream(tmp_path):
    from raft_tpu.checker.bfs import BFSChecker

    path = tmp_path / "host.jsonl"
    with Telemetry(metrics_path=str(path)) as tel:
        res = BFSChecker(
            cached_model(SMALL), invariants=INVS, symmetry=True, chunk=256
        ).run(telemetry=tel)
    with open(path) as fh:
        counts, problems = validate_lines(fh)
    assert not problems, problems
    waves = tel.wave_events()
    assert waves[-1]["distinct"] == res.distinct
    assert tel.last_summary["engine"] == "host"
    assert tel.last_summary["exit_cause"] == "exhausted"


@pytest.mark.slow
def test_sharded_stream_and_fleet_stats(tmp_path):
    import jax

    from raft_tpu.parallel.sharded import ShardedBFS

    path = tmp_path / "shard.jsonl"
    engine = ShardedBFS(
        cached_model(SMALL), invariants=INVS, symmetry=True,
        devices=jax.devices()[:4], chunk=512, frontier_cap=1024,
        seen_cap=1 << 12,
    )
    with Telemetry(metrics_path=str(path)) as tel:
        res = engine.run(telemetry=tel)
    with open(path) as fh:
        counts, problems = validate_lines(fh)
    assert not problems, problems
    assert counts["manifest"] == 1 and counts["summary"] == 1
    man = tel.events[0]
    assert man["engine"] == "sharded" and man["device_count"] == 4
    assert tel.wave_events()[-1]["distinct"] == res.distinct

    # satellite: aggregated fleet canon stats + per-shard skew on the
    # returned result
    assert res.stats is not None
    for k in ("canon_dup_lanes", "canon_dup_rate", "shard_dup_lanes",
              "shard_distinct", "shard_skew", "coverage",
              "canon_tier3_local", "canon_tier3_full"):
        assert k in res.stats, k
    # two servers have no tiers: every representative of the in-chunk
    # dedup takes the S!-table min, and the rows' lanes add up to the
    # run's
    waves = tel.wave_events()
    assert res.stats["canon_tier3_local"] == 0
    assert res.stats["canon_tier3_full"] == sum(
        w["canon_tier3_full"] for w in waves) > 0
    assert all(w["canon_tier3_full"] == w["generated"] - w["canon_dup_lanes"]
               for w in waves)
    assert res.stats["canon_dup_lanes"] == sum(
        w["canon_dup_lanes"] for w in waves) == sum(
        res.stats["shard_dup_lanes"]) > 0
    assert tel.last_summary["canon_tier3_full"] == res.stats[
        "canon_tier3_full"]
    assert len(res.stats["shard_dup_lanes"]) == 4
    assert sum(res.stats["shard_distinct"]) == res.distinct
    # fleet-summed coverage: one row per action, new sums to distinct
    # beyond the inits
    assert res.coverage == res.stats["coverage"]
    assert len(res.coverage) == len(cached_model(SMALL).ACTION_NAMES)
    assert sum(r[2] for r in res.coverage) == res.distinct - res.depth_counts[0]
    assert res.stats["shard_skew"] >= 1.0
    assert tel.last_summary["canon_dup_rate"] == res.stats[
        "canon_dup_rate"
    ]

    # the offline digest: per-shard balance from the rows' shard_new,
    # the analytic watermarks from the memwatch events
    from scripts.obs_report import render_run, split_runs

    with open(path) as fh:
        text = render_run(split_runs(fh)[-1])
    assert "Shard balance" in text
    assert "shard skew" in text
    assert "Memory watermarks" in text


# ------------------------------ phase split and memory watermarks


def test_telemetry_run_carries_phase_split_and_watermarks(tmp_path):
    """A telemetry run of the device engine counts what a bare one
    counts, every wave row carries the host-side phase split, and the
    stream and summary carry the analytic memory watermarks."""
    eng = _device()
    bare = eng.run(max_depth=5)

    path = tmp_path / "tl.jsonl"
    with Telemetry(metrics_path=str(path)) as tel:
        res = eng.run(max_depth=5, telemetry=tel)

    assert res.distinct == bare.distinct
    assert res.total == bare.total
    assert res.terminal == bare.terminal
    assert res.depth_counts == bare.depth_counts

    with open(path) as fh:
        counts, problems = validate_lines(fh)
    assert not problems, problems
    assert counts["memwatch"] >= 1

    for w in tel.wave_events():
        for k in ("device_s", "host_s", "ckpt_s", "tel_s"):
            assert isinstance(w[k], (int, float)), k
            assert w[k] >= 0, k

    # the CPU's allocator reports nothing: the plan stands, the
    # measured keys are null
    s = tel.last_summary
    assert s["hbm_plan_bytes"] > 0
    assert 0 < s["hbm_plan_frac"] < 1
    assert s["hbm_peak_bytes"] is None and s["hbm_peak_frac"] is None


def test_progress_renderer_observatory_gauges():
    ev = dict.fromkeys(WAVE_KEYS, 0)
    ev.update(event="wave", depth=7, generated_total=100, distinct=50,
              distinct_per_s=10.0, canon_dup_rate=0.5,
              hbm_frac=0.5)
    line = ProgressRenderer().render_wave(ev)
    assert line.endswith("dup 50%, hbm 50%")
    # no reading of the allocator (the CPU): the fraction is the plan's
    ev.update(hbm_bytes=None)
    assert ProgressRenderer().render_wave(ev).endswith("dup 50%, plan 50%")
    # a null/zero gauge leaves the pinned base line untouched
    ev.update(hbm_frac=0)
    assert ProgressRenderer().render_wave(ev).endswith("dup 50%")
    # lanes the canon routed to tier 3, as a share of the wave's lanes
    ev.update(generated=200, canon_tier3_local=30, canon_tier3_full=20)
    assert ProgressRenderer().render_wave(ev).endswith("dup 50%, tier3 25%")


def test_wave_tier_counters_schema_rule():
    from raft_tpu.obs.events import validate_event

    ev = dict.fromkeys(WAVE_KEYS, 0)
    ev.update(event="wave", generated=100, canon_dup_lanes=40,
              canon_tier3_local=35, canon_tier3_full=25)
    assert validate_event(ev) == []
    # more tier-3 lanes than representatives of the in-chunk dedup
    (problem,) = validate_event(dict(ev, canon_tier3_full=26))
    assert "exceed" in problem
    (problem,) = validate_event(dict(ev, canon_tier3_local=-1))
    assert "non-negative" in problem


@pytest.mark.parametrize("key,value,says", [
    ("dedup_sort_lanes", -1, "non-negative int"),
    ("dedup_sort_lanes", 1.5, "non-negative int"),
    ("dedup_sort_lanes", True, "non-negative int"),
    ("dedup_search_queries", -1, "non-negative int"),
    ("dedup_search_queries", 1.5, "non-negative int"),
    ("dedup_search_steps", -1, "non-negative int"),
    ("dedup_search_steps", True, "non-negative int"),
    ("seen_lanes", -1, "non-negative int"),
    ("seen_lanes", True, "non-negative int"),
    ("expand_rows_built", -1, "non-negative int"),
    ("expand_rows_built", 0.5, "non-negative int"),
    ("expand_rows_budget", -1, "non-negative int"),
    ("expand_rows_budget", True, "non-negative int"),
])
def test_wave_dedup_sort_lanes_schema_rule(key, value, says):
    from raft_tpu.obs.events import validate_event

    ev = dict.fromkeys(WAVE_KEYS, 0)
    ev.update(event="wave", dedup_sort_lanes=327680,
              dedup_search_queries=65536, dedup_search_steps=1,
              seen_lanes=1 << 22,
              expand_rows_built=28672, expand_rows_budget=151552)
    assert validate_event(ev) == []
    (problem,) = validate_event({**ev, key: value})
    assert says in problem


@pytest.mark.parametrize("etype,keys", [
    ("manifest", MANIFEST_KEYS), ("summary", SUMMARY_KEYS)])
def test_dedup_plan_schema_rule(etype, keys):
    """`dedup_plan` on a manifest and a summary: the sizes merged and
    searched, the wave buffer's prefix sizes from 0 up (none on the
    sharded engine) and the most lanes a chunk-step sorts."""
    from raft_tpu.checker.util import dedup_plan, merge_rungs
    from raft_tpu.obs.events import validate_event

    ev = _fields(keys, ident="x/hashv=5", exit_cause="exhausted")
    ev["event"] = etype
    plan = dedup_plan([1 << 18], 1 << 16, (0, 1 << 16, 1 << 18))
    assert plan["sort_lanes"] == (1 << 18) + (1 << 18) + (1 << 16)
    assert validate_event({**ev, "dedup_plan": plan}) == []
    sharded = dedup_plan([1 << 16, 1 << 17, 1 << 23], 1 << 16)
    assert sharded["wave_prefix"] == []
    assert validate_event({**ev, "dedup_plan": sharded}) == []
    # a run past the sort's floor says its rungs; a stream written
    # before PR 49 has no such key and stays clean
    prefix = (0, 1 << 15, 1 << 17, 1 << 19)
    for size in (1 << 20, 1 << 22):
        cut = dedup_plan([size], 1 << 15, prefix,
                         merge_rungs(size, 1 << 15, prefix))
        assert cut["rungs"][0] == 1 << 18 and cut["sort_lanes"] == max(
            cut["rungs"][-1], sum(cut["merge"]) + prefix[-1]) + (1 << 15)
        assert validate_event({**ev, "dedup_plan": cut}) == []
    assert plan["rungs"] == [] and validate_event({**ev, "dedup_plan": {
        k: v for k, v in plan.items() if k != "rungs"}}) == []
    for bad, says in (
        ({**cut, "rungs": cut["rungs"][::-1]}, "rungs"),
        ({**cut, "rungs": [1 << 18, 1 << 18]}, "rise strictly"),
        ({**cut, "sort_lanes": cut["rungs"][-1] - 1}, "under the last rung"),
        ({**cut, "rungs": [1.5]}, "non-negative ints"),
        ({**plan, "wave_prefix": [65536, 262144]}, "strictly from 0"),
        ({**plan, "wave_prefix": [0, 262144, 65536]}, "strictly from 0"),
        ({**plan, "sort_lanes": 1 << 18}, "under the merged runs"),
        ({**plan, "merge": [-1]}, "non-negative ints"),
        ({k: v for k, v in plan.items() if k != "wave_prefix"},
         "must carry"),
    ):
        (problem,) = validate_event({**ev, "dedup_plan": bad})
        assert "dedup_plan" in problem and says in problem


# ---------------------------------------- observatory schema fixtures


def _observatory_stream(tmp_path, name="obs.jsonl"):
    """One schema-clean stream with two memwatch events."""
    path = tmp_path / name
    c = MetricsCollector(path=str(path))
    c.manifest(_fields(MANIFEST_KEYS, ident="x/hashv=5"))
    c.wave(_wave(0, 0.5))
    c.event("memwatch", wave=1, depth=0, bytes=120, peak_bytes=300,
            peak_rise=300, budget_bytes=1000, frac=0.12, plan_bytes=100,
            plan_peak_bytes=100, plan_frac=0.1,
            breakdown={"frontier": 60, "seen": 40})
    c.wave(_wave(1, 0.4))
    # a CPU's: no reading of the allocator, the plan alone
    c.event("memwatch", wave=2, depth=1, bytes=None, peak_bytes=None,
            peak_rise=None, budget_bytes=1000, frac=0.15, plan_bytes=150,
            plan_peak_bytes=200, plan_frac=0.15,
            breakdown={"frontier": 150})
    c.summary(_fields(SUMMARY_KEYS, exit_cause="exhausted"))
    c.close()
    return path


def _perturb(path, tmp_path, match, repl, name):
    lines = path.read_text().splitlines()
    hits = [i for i, ln in enumerate(lines) if match in ln]
    assert hits, match
    lines[hits[0]] = lines[hits[0]].replace(match, repl)
    bad = tmp_path / name
    bad.write_text("\n".join(lines) + "\n")
    return bad


def test_observatory_fixture_positive(tmp_path):
    from scripts.check_metrics_schema import validate_file

    good = _observatory_stream(tmp_path)
    counts, problems = validate_file(str(good))
    assert not problems, problems
    assert counts == {"manifest": 1, "wave": 2, "memwatch": 2, "summary": 1}


def test_observatory_fixture_nonmonotone_peak(tmp_path):
    from scripts.check_metrics_schema import validate_file

    good = _observatory_stream(tmp_path)
    # second memwatch plan peak drops below the first: 200 -> 50
    bad = _perturb(good, tmp_path, '"plan_peak_bytes": 200',
                   '"plan_peak_bytes": 50', "bad_peak.jsonl")
    # keep plan_bytes <= its peak so ONLY the monotonicity rule fires
    bad.write_text(bad.read_text().replace('"plan_bytes": 150',
                                           '"plan_bytes": 50'))
    _, problems = validate_file(str(bad))
    assert any("monotone" in p for p in problems), problems


@pytest.mark.parametrize("etype, change, says", [
    ("wave", {"hbm_bytes": -1}, "hbm_bytes"),
    ("wave", {"hbm_peak_rise": 1.5}, "hbm_peak_rise"),
    ("wave", {"hbm_frac": -0.1}, "hbm_frac"),
    ("memwatch", {"bytes": 400, "peak_bytes": 300}, "exceeds peak_bytes"),
    ("memwatch", {"plan_bytes": 101}, "exceeds plan_peak_bytes"),
    ("memwatch", {"peak_rise": True}, "peak_rise"),
    ("memwatch", {"plan_peak_bytes": None}, "plan_peak_bytes"),
    ("summary", {"hbm_live_bytes": 9, "hbm_peak_bytes": 8},
     "hbm_live_bytes 9 exceeds hbm_peak_bytes 8"),
    ("summary", {"hbm_init_rise": -4}, "hbm_init_rise"),
    ("summary", {"hbm_plan_bytes": "many"}, "hbm_plan_bytes"),
])
def test_device_memory_schema_rules(etype, change, says):
    """The ``hbm_*`` keys of a row and a summary and the keys of a
    ``memwatch`` event: byte counts are non-negative ints, the measured
    ones or null; what a run held is under the allocator's peak."""
    from raft_tpu.obs.events import MEMWATCH_KEYS, validate_event

    good = {
        "wave": {**dict.fromkeys(WAVE_KEYS, 0), "hbm_bytes": None,
                 "hbm_peak_rise": None},
        "memwatch": {**dict.fromkeys(MEMWATCH_KEYS, 0), "bytes": 120,
                     "peak_bytes": 300, "plan_bytes": 100,
                     "plan_peak_bytes": 100, "breakdown": {"seen": 100}},
        "summary": {**dict.fromkeys(SUMMARY_KEYS, 0),
                    "exit_cause": "exhausted",
                    **dict.fromkeys(HBM_MEASURED_KEYS),
                    "hbm_plan_bytes": 7, "hbm_budget_bytes": 100},
    }[etype]
    good["event"] = etype
    assert validate_event(good) == []
    (problem,) = validate_event({**good, **change})
    assert says in problem


# ------------------------------------------------------------ bench gate


def test_bench_gate_evaluate():
    from scripts.bench_gate import evaluate

    summ = {"event": "summary", "distinct": 31, "total": 40, "depth": 4,
            "terminal": 0, "seconds": 10.0}
    base = {"metrics": {
        "distinct": {"value": 31, "direction": "eq"},
        "seconds": {"value": 8.0, "rel_tol": 0.5, "direction": "max"},
    }}
    v = evaluate(summ, base)
    assert v["pass"] and v["checked"] == 2 and not v["failures"]

    tight = {"metrics": {"distinct": {"value": 25, "direction": "eq"}}}
    v2 = evaluate(summ, tight)
    assert not v2["pass"]
    assert "distinct" in v2["failures"][0]

    # a gated metric missing from the summary fails, never skips
    v3 = evaluate(summ, {"metrics": {"nope": {"value": 1}}})
    assert not v3["pass"] and "missing" in v3["failures"][0]

    # min direction: smaller is worse
    v4 = evaluate(summ, {"metrics": {
        "seconds": {"value": 20.0, "rel_tol": 0.1, "direction": "min"}}})
    assert not v4["pass"]

    # malformed baselines raise (exit 64 at the CLI), distinct from fail
    for bad in (
        {"metrics": {}},
        {"metrics": {"x": {"value": 1, "tol": 1, "rel_tol": 1}}},
        {"metrics": {"x": {"value": 1, "direction": "sideways"}}},
        {"metrics": {"x": {}}},
    ):
        with pytest.raises(ValueError):
            evaluate(summ, bad)


def test_bench_gate_script_exit_codes(tmp_path, capsys):
    from scripts.bench_gate import main as gate_main

    summ = {"event": "summary", "distinct": 31, "depth": 4}
    m = tmp_path / "m.jsonl"
    m.write_text(json.dumps(summ) + "\n")
    base = tmp_path / "base.json"
    base.write_text(json.dumps({"metrics": {
        "distinct": {"value": 31, "direction": "eq"}}}))
    assert gate_main([str(m), str(base)]) == 0
    verdict = json.loads(capsys.readouterr().out)
    assert verdict["pass"] is True and verdict["checked"] == 1

    tight = tmp_path / "tight.json"
    tight.write_text(json.dumps({"metrics": {
        "distinct": {"value": 25, "direction": "eq"}}}))
    assert gate_main([str(m), str(tight)]) == 3
    cap = capsys.readouterr()
    assert json.loads(cap.out)["pass"] is False
    assert "GATE FAIL" in cap.err

    assert gate_main([str(tmp_path / "nope.jsonl"), str(base)]) == 66
    capsys.readouterr()
    broken = tmp_path / "broken.json"
    broken.write_text("{not json")
    assert gate_main([str(m), str(broken)]) == 64
    capsys.readouterr()


# ----------------------------------------------------------------- CLI


CFG = """\
CONSTANTS
    n1 = n1
    n2 = n2
    v1 = v1
    Server = { n1, n2 }
    Value = { v1 }
    Follower = Follower
    Candidate = Candidate
    Leader = Leader
    Nil = Nil
    RequestVoteRequest = RequestVoteRequest
    RequestVoteResponse = RequestVoteResponse
    AppendEntriesRequest = AppendEntriesRequest
    AppendEntriesResponse = AppendEntriesResponse
    EqualTerm = EqualTerm
    LessOrEqualTerm = LessOrEqualTerm
    MaxElections = 1
    MaxRestarts = 0

INIT Init
NEXT Next

INVARIANT
NoLogDivergence
"""

CLI_BASE = [
    "--platform", "cpu", "--msg-slots", "16", "--max-depth", "4",
    "--chunk", "256", "--frontier-cap", "4096", "--seen-cap", "16384",
    "--journal-cap", "16384",
]


@pytest.mark.slow
def test_cli_json_progress_and_bit_identical_result(tmp_path, capsys):
    from raft_tpu.__main__ import main

    cfg = tmp_path / "Raft.cfg"
    cfg.write_text(CFG)
    mpath = tmp_path / "cli.jsonl"

    rc = main([str(cfg), *CLI_BASE, "--progress=0",
               "--metrics-out", str(mpath), "--json"])
    cap = capsys.readouterr()
    assert rc == 0, cap.err

    # stdout: result lines only, summary event as the LAST line
    out_lines = cap.out.strip().splitlines()
    summ = json.loads(out_lines[-1])
    assert summ["event"] == "summary"
    assert summ["exit_cause"] == "max_depth"
    result_line = next(ln for ln in out_lines if ln.startswith("distinct="))
    assert f"distinct={summ['distinct']}" in result_line

    # stderr: banner + live progress, never stdout
    assert "spec=" in cap.err
    assert "Progress (depth" in cap.err
    assert "Progress (depth" not in cap.out

    # the file on disk is schema-clean and count-accurate
    with open(mpath) as fh:
        counts, problems = validate_lines(fh)
    assert not problems, problems
    assert counts["wave"] >= 4

    # telemetry must not perturb the result: identical result line
    # without any telemetry flag
    rc = main([str(cfg), *CLI_BASE])
    cap = capsys.readouterr()
    assert rc == 0, cap.err
    bare_line = next(
        ln for ln in cap.out.strip().splitlines()
        if ln.startswith("distinct=")
    )
    # wall-clock fields differ run to run; the counts must not
    strip = lambda s: s.split(" time=")[0]  # noqa: E731
    assert strip(bare_line) == strip(result_line)


CFG3 = CFG.replace("    v1 = v1", "    n3 = n3\n    v1 = v1").replace(
    "Server = { n1, n2 }", "Server = { n1, n2, n3 }")


def test_cli_metrics_smoke_and_bench_gate(tmp_path, capsys):
    """Tier-1 smoke of the whole telemetry loop: a depth-4 3-server
    Raft CLI check with --metrics-out produces a schema-clean stream
    that PASSES the committed bench_gate baseline, while a 20%-tighter
    baseline fails with the strict-gate exit code 3."""
    from pathlib import Path

    from raft_tpu.__main__ import main
    from scripts.bench_gate import main as gate_main
    from scripts.check_metrics_schema import validate_file

    cfg = tmp_path / "Raft.cfg"
    cfg.write_text(CFG3)
    mpath = tmp_path / "tl.jsonl"

    rc = main([str(cfg), *CLI_BASE, "--metrics-out", str(mpath)])
    cap = capsys.readouterr()
    assert rc == 0, cap.err

    counts, problems = validate_file(str(mpath))
    assert not problems, problems
    assert counts["wave"] == 4
    assert counts["memwatch"] >= 1

    with open(mpath) as fh:
        summ = json.loads(fh.read().strip().splitlines()[-1])
    assert summ["event"] == "summary"
    assert summ["hbm_plan_bytes"] > 0

    golden = Path(__file__).parent / "golden" / "raft3_depth4_gate.json"
    assert gate_main([str(mpath), str(golden)]) == 0
    verdict = json.loads(capsys.readouterr().out)
    assert verdict["pass"] is True and verdict["checked"] >= 4

    # tighten every eq count by 20%: a regression gate that cannot
    # fail is no gate — pin the exit-3 path on the same stream
    base = json.loads(golden.read_text())
    base["metrics"]["distinct"]["value"] = round(
        base["metrics"]["distinct"]["value"] * 0.8)
    tight = tmp_path / "tight.json"
    tight.write_text(json.dumps(base))
    assert gate_main([str(mpath), str(tight)]) == 3
    cap = capsys.readouterr()
    assert "GATE FAIL distinct" in cap.err


# ------------------------------------------------- the tracing spine
#
# Device side: every stage of the chunk pipeline is a jax.named_scope
# (obs.stage) inside the programs the engines dispatch, so a profile
# names its ops by stage. Host side: the wave loop's spans are written
# by the engines themselves into whatever jax.profiler session is open,
# whichever telemetry facade they were handed; the same brackets feed
# the wave rows. Compile counters come from the program (obs.COMPILES).


def _sharded(n_dev=4, **kw):
    import jax

    from raft_tpu.parallel.sharded import ShardedBFS

    kw.setdefault("chunk", 512)
    kw.setdefault("frontier_cap", 1024)
    kw.setdefault("seen_cap", 1 << 12)
    return ShardedBFS(
        cached_model(SMALL), invariants=INVS, symmetry=True,
        devices=jax.devices()[:n_dev], **kw)


_LOWERED: dict = {}


def _lowered_text(engine: str, program: str) -> str:
    """Lowered text, with debug info, of one production program (the
    audit surface hands out the jit objects and abstract arguments);
    lowered once per test process, nothing compiled or run."""
    key = (engine, program)
    if key not in _LOWERED:
        eng = _device() if engine == "device" else _sharded()
        (entry,) = [e for e in eng.audit_programs() if e["name"] == program]
        _LOWERED[key] = entry["fn"].lower(*entry["args"]).as_text(
            debug_info=True)
    return _LOWERED[key]


@pytest.mark.parametrize("engine,program,stage", [
    ("device", "wave", "expand"),
    ("device", "wave", "canon"),
    ("device", "wave", "dedup"),
    ("device", "wave", "emit"),
    # the prefix switch keeps the merged sort's scope (PR 36)
    ("device", "wave", "dedup/merge"),
    ("device", "seen_merge", "seen_merge"),
    ("sharded", "chunk", "expand"),
    ("sharded", "chunk", "canon"),
    ("sharded", "chunk", "exchange"),
    ("sharded", "chunk", "dedup"),
    ("sharded", "chunk", "emit"),
    # canon's nested scopes (a layout without tiers has these two; the
    # five-server ones are in test_flexraft5.py)
    ("device", "wave", "canon/inchunk"),
    ("device", "wave", "canon/tier3_full"),
    ("sharded", "chunk", "canon/inchunk"),
    ("sharded", "chunk", "canon/tier3_full"),
])
def test_stage_scope_in_lowered_program(engine, program, stage):
    assert stage.split("/")[0] in TIMELINE_STAGES
    # a location reads "jit(_wave_step)/while/body/canon/...", or, inside
    # a shard_map, starts at the scope: "canon/..."
    # (a nested scope may sit below control flow: "canon/while/body/inchunk/")
    path = '/(?:[^"]*/)?'.join(stage.split("/"))
    assert re.search(rf'["/]{path}/', _lowered_text(engine, program)), (
        f"no op of {engine}:{program} carries the {stage!r} scope")


def test_the_wave_program_merges_nothing_into_the_seen_set():
    """The wave's new fingerprints are appended to one buffer, not
    merged up a ladder of sorted runs (PR 36): no op of the wave program
    is under `seen_merge`, which is the end-of-wave program alone, and
    every 2-key sort inside the loop is the dedup stage's or canon's."""
    text = _lowered_text("device", "wave")
    assert not re.search(r'["/]seen_merge/', text)
    assert re.search(r'["/]emit/(?:[^"]*/)?dynamic_update_slice', text)


@pytest.mark.parametrize("engine,program", [
    ("device", "wave"), ("sharded", "chunk")])
def test_inchunk_dedup_of_the_engines_programs_has_no_scatter_and_no_table(
        engine, program):
    """The program an engine dispatches, not the canon alone: nothing
    under `canon/inchunk` writes by a scatter, and no two-column u64
    table rides the program's arguments (the cross-chunk memo went in
    PR 33; what is left of u64 there are the seen runs and, sharded,
    the journal's fingerprints)."""
    text = _lowered_text(engine, program)
    under = [ln for ln in text.splitlines()
             if re.search(r'["/]canon/(?:[^"]*/)?inchunk/', ln)]
    assert any("/sort" in ln for ln in under)  # the walk sees in
    assert not [ln for ln in under if "scatter" in ln]
    (main,) = [ln for ln in text.splitlines()
               if "func.func public @main" in ln]
    assert not re.search(r"x2xui64>", main.split(") -> ")[0]), main


def test_stage_scope_in_lsm_merge_program():
    """The sharded engine's seen merge is RunLSM's, a program of its
    own per (na, nb, out) signature."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.checker.lsm import RunLSM

    body, _ = RunLSM.merge_spec(8, 8)
    run = jax.ShapeDtypeStruct((8,), jnp.uint64)
    text = jax.jit(body).lower(run, run).as_text(debug_info=True)
    assert "/seen_merge/" in text


def _host_spans(trace_dir):
    """[(start_ns, end_ns, name, stats)] of the host plane, by start."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            spans.extend(
                (e.start_ns, e.start_ns + e.duration_ns, e.name,
                 dict(e.stats))
                for e in line.events)
    return sorted(spans, key=lambda s: (s[0], -s[1]))


def test_stage_split_reduces_a_recorded_trace(tmp_path, capsys):
    """What an operator runs after ``python -m raft_tpu CFG --trace-dir
    DIR``: ``scripts/stage_split.py --trace-dir DIR`` reduces the newest
    trace under DIR, here one recorded on a v5e, to seconds by stage
    scope. Every bucket is a device stage of TIMELINE_STAGES (one scope
    deeper where there is one) or ``unscoped``, and they add up to the
    seconds the device was busy."""
    import gzip
    import pathlib

    from scripts import stage_split

    recorded = (pathlib.Path(__file__).parents[1] / "benchmark" / "testdata"
                / "scoped_v5e.xplane.pb.gz")
    run_dir = tmp_path / "plugins" / "profile" / "2026_09_27_00_00_00"
    run_dir.mkdir(parents=True)
    (run_dir / "host.xplane.pb").write_bytes(
        gzip.decompress(recorded.read_bytes()))

    stage_split.main(["--trace-dir", str(tmp_path)])

    table = capsys.readouterr().out
    res = json.loads(table.strip().splitlines()[-1])
    buckets = res["by_scope_s"]
    # recorded before sparse_apply named its groups (PR 51): all of
    # expand is its own
    assert res["expand_by_group_s"] == {"-": buckets["expand"]}
    device_stages = set(TIMELINE_STAGES) - {"checkpoint", "host"}
    assert {b.split("/")[0] for b in buckets} <= device_stages | {"unscoped"}
    assert {"expand", "canon", "dedup", "emit", "seen_merge"} <= set(buckets)
    assert res["busy_s"] > 0
    assert sum(buckets.values()) == pytest.approx(res["busy_s"], rel=1e-9)
    assert all(f"\n{b} " in "\n" + table for b in [*buckets, "busy"])


@pytest.mark.parametrize("facade", ["null", "wave_clock"])
def test_program_spans_in_any_profiler_session(tmp_path, facade):
    import jax

    eng = _device()
    eng.run(max_depth=4)  # compile outside the trace
    # the benchmark's own facade: the do-nothing one for everything but
    # wave_annotation, where it reads its clock
    from benchmark.adapter import WaveClock

    tel = None if facade == "null" else WaveClock(time.perf_counter)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        eng.run(max_depth=4, telemetry=tel)
    finally:
        jax.profiler.stop_trace()
    if tel is not None:
        assert len(tel.stamps) == 4
    spans = _host_spans(str(tmp_path))
    by_name = {}
    for sp in spans:
        by_name.setdefault(sp[2], []).append(sp)
    assert "verdict" not in by_name  # the benchmark's own span name
    (run,) = by_name["run"]
    (init,) = by_name["init"]
    (finish,) = by_name["finish"]
    assert run[3]["engine"] == "device" and run[3]["run"] >= 2
    waves = by_name["wave"]
    assert [w[3]["depth"] for w in waves] == [1, 2, 3, 4]
    assert all(w[3]["run"] == run[3]["run"] for w in waves)
    # init, the waves and finish tile the run, in that order
    tops = [init, *waves, finish]
    assert all(run[0] <= sp[0] and sp[1] <= run[1] for sp in tops)
    assert all(a[1] <= b[0] for a, b in zip(tops, tops[1:]))
    # each wave holds one dispatch, one fetch and one seen_merge
    for w in waves:
        inside = [sp[2] for sp in spans
                  if w[0] <= sp[0] and sp[1] <= w[1] and sp is not w]
        for name in ("dispatch", "fetch", "seen_merge"):
            assert inside.count(name) == 1, (w[3], name, inside)


def _rows_of(engine):
    if engine == "device":
        return _device().run(max_depth=4, collect_metrics=True).metrics
    if engine == "host":
        from raft_tpu.checker.bfs import BFSChecker

        return BFSChecker(
            cached_model(SMALL), invariants=INVS, symmetry=True, chunk=256,
        ).run(max_depth=4, collect_metrics=True).metrics
    return _sharded(2, frontier_cap=2048, seen_cap=1 << 13).run(
        max_depth=4, collect_metrics=True).metrics


@pytest.mark.parametrize("engine", [
    "device", "host", pytest.param("sharded", marks=pytest.mark.slow)])
def test_wave_row_clocks_unrounded_and_add_up(engine):
    rows = _rows_of(engine)
    assert [r["depth"] for r in rows] == [1, 2, 3, 4]
    for r in rows:
        assert r["device_s"] + r["host_s"] + r["ckpt_s"] == pytest.approx(
            r["wave_s"], abs=1e-9)
        if engine != "host":
            assert r["dispatch_s"] + r["fetch_s"] + r["merge_s"] == (
                pytest.approx(r["device_s"], abs=1e-9))
            assert r["grow_s"] == 0.0
    # perf_counter differences, not values rounded to a millisecond
    for key in ("wave_s", "elapsed_s", "device_s", "host_s"):
        assert any(r[key] != round(r[key], 4) for r in rows), key
    assert all(a["elapsed_s"] < b["elapsed_s"] for a, b in zip(rows, rows[1:]))


def test_unrounded_stream_still_validates(tmp_path):
    from scripts.check_metrics_schema import main

    path = tmp_path / "m.jsonl"
    with Telemetry(metrics_path=str(path)) as tel:
        _device().run(max_depth=4, telemetry=tel)
    assert main([str(path)]) == 0
    wave = tel.wave_events()[-1]
    for key in ("dispatch_s", "fetch_s", "merge_s", "grow_s", "compiles",
                "compile_s"):
        assert key in wave, key
    for key in ("programs_loaded", "run_compiles", "run_compile_s",
                "run_cache_hits"):
        assert key in tel.last_summary, key


def test_compile_counters_by_run_and_by_wave():
    from raft_tpu.obs import COMPILES

    eng = _device()
    first = eng.run(max_depth=4, collect_metrics=True)
    again = eng.run(max_depth=4, collect_metrics=True)
    assert first.stats["run_compiles"] >= 1
    assert first.stats["run_compile_s"] > 0
    assert again.stats["run_compiles"] == 0
    assert again.stats["run_compile_s"] == 0
    assert all(r["compiles"] == 0 for r in again.metrics)
    # cumulative in the process, never falling
    assert first.stats["programs_loaded"] <= again.stats["programs_loaded"]
    assert again.stats["programs_loaded"] == COMPILES.loaded


def test_growth_compile_is_booked_to_its_wave():
    """Capacities so tiny that the journal outgrows them mid-run."""
    eng = _device(chunk=32, frontier_cap=32, journal_cap=32)
    rows = eng.run(collect_metrics=True).metrics
    grew = [i for i, r in enumerate(rows) if r["grow_s"] > 0]
    assert grew and grew[0] > 1, (
        "nothing grew mid-run: the capacities are not tiny enough")
    first = grew[0]
    # wave 1 compiles the wave program; then nothing is loaded until the
    # wave that grows loads the programs that re-shape its buffers, and
    # the wave after it the wave program at the new shapes
    assert rows[0]["compiles"] >= 1
    assert all(r["compiles"] == 0 and r["grow_s"] == 0
               for r in rows[1:first])
    assert rows[first]["compiles"] >= 1 and rows[first]["compile_s"] > 0
    assert rows[first + 1]["compiles"] >= 1
    assert rows[first + 1]["compile_s"] <= rows[first + 1]["dispatch_s"]
