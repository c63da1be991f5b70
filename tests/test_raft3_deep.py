"""raft3-deep: upstream's Raft.cfg at the size its users run it to, where
the seen run passes the merge-or-search crossover and is searched.

The benchmark's cell `raft3-deep-cross` takes the cfg from Init to the
first wave that runs entirely against a seen run of more than 2^22
fingerprints (64 lanes a query at the cell's 65,536 query lanes a
chunk-step): four sizes of the seen run, 2^18, 2^20, 2^22 and 2^24
lanes, the last one searched. Tier-1 affords none of that at the cell's
chunk, so the engine is held on a small one with the same shape: 128
query lanes a chunk-step (16 rows x 8), a floor of 2^9 lanes and a
ladder of 2^9, 2^11, 2^13 and 2^15 lanes by hand. 128 queries merge up
to 2^13 lanes, so, as on the chip, the third size is the last that is
merged whole and the fourth is searched: by every chunk-step of wave 14,
the first whose run holds more than 2^13 fingerprints. What the cell
adds to the engine's records, `dedup_search_steps`, is held there, and
the same verdict with the search forced off is held equal in every
count. The rest holds the cell's, the configuration's, the traffic's and
the golden's files to one another by the rules the files state.
"""

import json
import os

import pytest

from raft_tpu.checker import util
from raft_tpu.checker.lsm import pow2_at_least
from raft_tpu.models.registry import build_from_cfg
from raft_tpu.utils.cfg import parse_cfg

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = os.path.join(ROOT, "configs", "standard-raft", "Raft.cfg")
BENCH = os.path.join(ROOT, "benchmark")
SMOKE_GOLDEN = os.path.join(
    ROOT, "tests", "golden", "raft_cfg_depth_counts.json")
CELL = "raft3-deep-cross"
DEPTH = 14  # of the small engine's verdict
CHUNK, VPS = 16, 8
LADDER = [1 << 9, 1 << 11, 1 << 13, 1 << 15]


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def setup():
    return build_from_cfg(parse_cfg(CFG), msg_slots=32)


@pytest.fixture(scope="module")
def golden():
    return _load(BENCH, "goldens", "raft3-deep.json")


@pytest.fixture(scope="module")
def cell():
    return _load(BENCH, "workloads", f"{CELL}.json")


def _small_engine(setup):
    from raft_tpu.checker.device_bfs import DeviceBFS

    eng = DeviceBFS(setup.model, invariants=setup.invariants, symmetry=True,
                    chunk=CHUNK, valid_per_state=VPS, frontier_cap=1 << 13,
                    max_frontier_cap=1 << 13, journal_cap=1 << 15)
    eng._seen_sizes = list(LADDER)
    eng.SORT_FLOOR = LADDER[0]
    return eng


@pytest.fixture(scope="module")
def searched(setup):
    eng = _small_engine(setup)
    return eng, eng.run(max_depth=DEPTH, collect_metrics=True)


@pytest.fixture(scope="module")
def merged(setup):
    """The same verdict with the crossover out of reach, so that every
    run is merged whatever its size, after the last rung of its largest
    run; the constant is back before any other test reads it."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(util, "MERGE_LANES_PER_QUERY", 1 << 20)
        eng = _small_engine(setup)
        res = eng.run(max_depth=DEPTH, collect_metrics=True)
        return eng._rungs(LADDER[3])[-1], res


def _crossing_depth(counts, lanes):
    """The rule of the cell's depth: the smallest D with more than
    ``lanes`` fingerprints in the seen run before wave D."""
    return next(d for d in range(1, len(counts) + 1)
                if sum(counts[:d]) > lanes)


# ---------------- the engine, small ----------------


def test_small_engine_has_the_cells_shape(searched):
    """Third size merged whole, fourth searched past rungs that end at
    the crossover: 2^22 and 2^24 lanes at 65,536 queries on the chip."""
    eng, _res = searched
    assert eng.VC == CHUNK * VPS == 128
    top = util.MERGE_LANES_PER_QUERY * eng.VC
    assert top == LADDER[2]
    assert [util.merges(s, eng.VC) for s in LADDER] == [True] * 3 + [False]
    assert eng._rungs(LADDER[0]) == ()
    assert eng._rungs(LADDER[2])[-1] == LADDER[2] + eng.FCAP
    assert eng._rungs(LADDER[3])[-1] == top
    # the cell's own geometry, from shapes alone
    assert util.merges(1 << 22, 65536) and not util.merges(1 << 24, 65536)
    assert util.merge_rungs(
        1 << 24, 65536, util.wave_prefix_sizes(65536, 1 << 22))[-1] == 1 << 22


@pytest.mark.parametrize("which", ["searched", "merged"])
def test_counts_equal_the_oracles(which, request):
    """Every per-depth count, the total and the terminal count of the
    verdict, searched or merged, are the pure-Python oracle's."""
    _, res = request.getfixturevalue(which)
    smoke = _load(SMOKE_GOLDEN)["depth_limited"]
    counts = smoke["depth_counts"][: DEPTH + 1]
    assert [int(x) for x in res.depth_counts] == counts
    assert res.distinct == sum(counts) == 15962
    totals = _load(BENCH, "goldens", "raft3.json")["totals"][str(DEPTH)]
    assert (res.total, res.terminal) == (totals["total"], totals["terminal"])
    assert res.violation is None and res.exit_cause == "max_depth"
    assert not any(w["overflow_bits"] for w in res.metrics)


def test_the_seen_run_steps_three_times_and_is_searched_in_the_last_wave(
        searched):
    _eng, res = searched
    rows = res.metrics
    counts = [int(x) for x in res.depth_counts]
    met = [next(s for s in LADDER if sum(counts[:d]) <= s)
           for d in range(1, DEPTH + 1)]
    assert [w["seen_lanes"] for w in rows] == met
    assert met == [LADDER[0]] * 9 + [LADDER[1]] * 2 + [LADDER[2]] * 2 + [
        LADDER[3]]
    assert _crossing_depth(counts, LADDER[2]) == DEPTH


def test_search_steps_are_the_chunk_steps_the_rule_predicts(searched):
    """`dedup_search_steps`: the chunk-steps of the waves that meet a run
    past the crossover, ceil(frontier / chunk) each, and the searched
    query lanes are VC times that; 0 on every other wave."""
    eng, res = searched
    rows = res.metrics
    want = [-(-w["frontier"] // CHUNK)
            if not util.merges(w["seen_lanes"], eng.VC) else 0 for w in rows]
    assert want[:-1] == [0] * (DEPTH - 1) and want[-1] == 253
    assert [w["dedup_search_steps"] for w in rows] == want
    assert [w["dedup_search_queries"] for w in rows] == [
        eng.VC * s for s in want]
    assert res.stats["dedup_search_steps"] == 253
    assert res.stats["dedup_search_queries"] == 253 * 128 > 0
    # a searched step sorts the wave's prefix and its queries, never the run
    assert rows[-1]["dedup_sort_lanes"] <= 253 * (eng.FCAP + eng.VC)


def test_the_search_forced_off_changes_no_count(searched, merged):
    eng, a = searched
    last_rung, b = merged
    assert last_rung == LADDER[3] + eng.FCAP
    assert b.stats["dedup_search_steps"] == 0 == b.stats[
        "dedup_search_queries"]
    assert [w["dedup_search_steps"] for w in b.metrics] == [0] * DEPTH
    for key in ("depth_counts", "distinct", "total", "terminal", "coverage"):
        assert getattr(a, key) == getattr(b, key), key
    for wa, wb in zip(a.metrics, b.metrics):
        for key in ("frontier", "new", "generated", "terminal", "seen_lanes",
                    "canon_dup_lanes", "expand_rows_built"):
            assert wa[key] == wb[key], (wa["depth"], key)
    # merged, the last wave sorts the run's real lanes with each step
    assert b.metrics[-1]["dedup_sort_lanes"] > a.metrics[-1][
        "dedup_sort_lanes"]


def test_wave_rows_and_summary_carry_the_counter(searched):
    from raft_tpu.obs.events import validate_event

    _eng, res = searched
    for n, w in enumerate(res.metrics, 1):
        assert "dedup_search_steps" in w
        assert validate_event({"event": "wave", "wave": n, **w}) == []


# ---------------- the files ----------------


def test_the_two_benchmark_copies_of_the_cfg_are_byte_equal():
    with open(os.path.join(BENCH, "configs", "raft3", "Raft.cfg"), "rb") as f:
        raft3 = f.read()
    with open(os.path.join(
            BENCH, "configs", "raft3-deep", "Raft.cfg"), "rb") as f:
        assert f.read() == raft3
    with open(CFG, "rb") as f:
        assert f.read() == raft3


def test_golden_agrees_with_raft3s_and_with_the_smoke_golden(golden):
    raft3 = _load(BENCH, "goldens", "raft3.json")
    n = len(raft3["depth_counts"])
    assert golden["depth_counts"][:n] == raft3["depth_counts"]
    for depth, totals in raft3["totals"].items():
        assert golden["totals"][depth] == totals, depth
    assert golden["msg_slots"] == raft3["msg_slots"] == 32
    assert golden["config"] == "raft3-deep"
    assert "oracle_golden.py" in golden["command"]
    reach = golden["independent_to_depth"]
    assert len(golden["depth_counts"]) >= min(reach, 48) + 1
    smoke = _load(SMOKE_GOLDEN)
    if "exhausted" in golden:
        # the oracle reached the end of the space: the engines' own
        # whole-space counts are confirmed by it, or one of them is wrong
        ex = golden["exhausted"]
        assert sum(golden["depth_counts"]) == ex["distinct"]
        assert {k: ex[k] for k in ("distinct", "total", "terminal")} == {
            k: smoke["exhaustive"][k]
            for k in ("distinct", "total", "terminal")}
        assert len(golden["depth_counts"]) - 1 == smoke["exhaustive"]["depth"]


def test_depth_follows_the_rule_from_the_goldens_own_counts(golden, cell):
    """D is the smallest depth with more than 2^22 fingerprints in the
    seen run before wave D: 64 lanes a query at the chunk's 65,536."""
    params = cell["engine_params"]
    vc = params["chunk"] * 16  # DeviceBFS's valid_per_state
    lanes = util.MERGE_LANES_PER_QUERY * vc
    assert lanes == 1 << 22
    counts = golden["depth_counts"]
    depth = _crossing_depth(counts, lanes)
    traffic = _load(BENCH, "traffic", f"{cell['traffic']}.json")
    assert cell["traffic"] == f"init-d{depth}-warm{depth}"
    assert traffic["max_depth"] == traffic["warmup_depth"] == depth
    assert (traffic["mode"], traffic["from"]) == ("bfs", "Init")
    assert sum(counts[: depth - 1]) <= lanes < sum(counts[:depth])
    assert golden["independent_to_depth"] >= depth + 1
    assert str(depth) in golden["totals"]


def test_capacities_follow_their_rules_from_the_goldens_counts(golden, cell):
    """frontier_cap: the smallest power of two `_maybe_grow` leaves
    alone (3 x the most rows a wave before the bound writes);
    journal_cap: the smallest that holds the verdict's states and 3 x
    its widest wave."""
    params = cell["engine_params"]
    traffic = _load(BENCH, "traffic", f"{cell['traffic']}.json")
    depth = traffic["max_depth"]
    counts = golden["depth_counts"]
    widest = max(counts[1:depth])  # the last wave's rows grow nothing
    assert params["frontier_cap"] == pow2_at_least(util.HEADROOM * widest)
    assert max(counts[: depth + 1]) <= params["frontier_cap"]
    states = sum(counts[: depth + 1]) - 1
    assert params["journal_cap"] == pow2_at_least(
        states + util.HEADROOM * max(counts[1: depth + 1]))
    assert set(params) == {"chunk", "msg_slots", "frontier_cap",
                           "journal_cap"}
    assert (params["chunk"], params["msg_slots"]) == (4096, 32)
    # the seen run's four sizes, the CLI's default ladder
    sizes = [1 << 18, 1 << 20, 1 << 22, 1 << 24]
    met = [next(s for s in sizes if sum(counts[:d]) <= s)
           for d in range(1, depth + 1)]
    assert sorted(set(met)) == sizes and met[-1] == 1 << 24
    assert met[-2] == 1 << 22


def test_job_prose_states_the_goldens_numbers(golden, cell):
    traffic = _load(BENCH, "traffic", f"{cell['traffic']}.json")
    depth = traffic["max_depth"]
    counts = golden["depth_counts"]
    distinct = sum(counts[: depth + 1])
    totals = golden["totals"][str(depth)]
    steps = [-(-n // 4096) for n in counts[:depth]]
    searched = steps[-1]
    job = cell["job"]
    for number in (distinct, totals["total"], totals["terminal"], sum(steps),
                   searched, searched * 65536):
        assert f"{number:,}" in job, number
    assert f"depth {depth}" in job
    config = _load(BENCH, "configs", "raft3-deep", "config.json")
    assert f"{distinct:,}" in config["reduced_note"]
    assert f"depth {depth}" in config["reduced_note"]


def test_config_states_source_constants_cut_and_guarantees(cell):
    config = _load(BENCH, "configs", "raft3-deep", "config.json")
    raft3 = _load(BENCH, "configs", "raft3", "config.json")
    assert config["name"] == cell["config"] == "raft3-deep"
    assert "specifications/standard-raft/Raft.cfg:10-36" in config["source"]
    # two deployments of one public file: sources that differ
    assert config["source"] != raft3["source"]
    assert config["architecture"] is None
    assert config["constants"] == raft3["constants"]
    assert config["reduced"] == cell["reduced"] == ["max_depth"]
    for key in ("msg_slots", "chunk", "row_lanes"):
        assert config["assumed"][key] == raft3["assumed"][key]
    assert len(config["guarantees"]) == 6
    assert any("merged or searched" in g for g in config["guarantees"])
    bench = _load(ROOT, "BENCHMARK.json")
    (entry,) = [c for c in bench["configs"] if c["name"] == "raft3-deep"]
    assert bench["configs"][9] is entry  # the tenth, the eleventh cell
    assert entry["file"] == "benchmark/configs/raft3-deep/config.json"
    assert bench["workloads"][10]["name"] == CELL


def test_cell_reports_pull3s_twenty_and_four_more(cell):
    """The three hbm_* shares are not among them: each names its cells
    in its own file, and benchmark/tests/test_memory_metrics.py holds
    that list equal to the cells that report it, so a cell that comes as
    a new file cannot list them (pullv2-full could not either); the
    traced verdict's stats and the line's memory_peak_bytes carry the
    chip's reading."""
    pull3 = _load(BENCH, "workloads", "pull3-full.json")["per_layer"]
    assert len(pull3) == 20
    assert cell["per_layer"] == [
        *pull3, "dedup_sort_lanes", "frontier_peak_rows", "emit_append_share",
        "dedup_search_steps"]
    assert cell["end_to_end"] == ["setup_s", "states_per_s"]
    assert (cell["chips"], cell["engine"]) == (1, "device")
