"""pull3: upstream's PullRaft.cfg (follower-pull replication: 3 servers,
2 values, MaxElections 2, MaxRestarts 0, 6 permutations, two
invariants), at the published constants and the registry's own bag
width, against the pure-Python oracle: 259-lane rows, 85 candidate
actions a state, 64 of them HandleMessage over the bag's slots.

The cfg in the tree is reconstructed (its header says from what) and
keeps upstream's latent bug, `v2` undeclared; the benchmark's copy has
the one repair made in the file. The whole space is 1,113,796 states to
depth 37 (benchmark/goldens/pull3.json); tier-1 affords its first 14
depths, 20,091 states, at the cell's chunk. One DeviceBFS verdict to
that depth serves the tests of the engine; what the cell adds to the
engine's records, `dedup_search_queries` and a wave row's `seen_lanes`,
is held on a small engine whose seen run is given a short ladder by
hand, so that it passes the merge-or-search crossover on the CPU.
"""

import itertools
import json
import os
import random
import re

import jax
import numpy as np
import pytest

from raft_tpu.checker import util
from raft_tpu.models.pull_raft import PullRaftModel, PullRaftParams
from raft_tpu.models.registry import build_from_cfg, oracle_for_setup
from raft_tpu.utils.cfg import CfgError, parse_cfg

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = os.path.join(ROOT, "configs", "pull-raft", "PullRaft.cfg")
BENCH = os.path.join(ROOT, "benchmark")
BENCH_CFG = os.path.join(BENCH, "configs", "pull3", "PullRaft.cfg")
SMOKE_GOLDEN = os.path.join(
    ROOT, "tests", "golden", "pull3_cfg_depth_counts.json")
DEPTH = 14
INVARIANTS = ("LeaderHasAllAckedValues", "NoLogDivergence")
RECEIPTS = {
    "UpdateTerm", "HandleRequestVoteRequest", "HandleRequestVoteResponse",
    "RejectPullEntriesRequest", "AcceptPullEntriesRequest", "LearnOfLeader",
    "HandleSuccessPullEntriesResponse", "HandleFailPullEntriesResponse"}


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def setup():
    # the CLI's path under --lenient, the registry's own bag width
    return build_from_cfg(parse_cfg(CFG, lenient=True))


@pytest.fixture(scope="module")
def golden():
    return _load(BENCH, "goldens", "pull3.json")


def _action(label):
    """`RequestVote(0)`, `UpdateTerm[1]`, `LearnOfLeader`: the action."""
    return re.split(r"[(\[]", label)[0]


def _walked(oracle, seed):
    """{action: states it was taken from} on seeded random walks of 80
    steps, at most 10 states an action: a rejected pull and the
    truncation that answers it sit some thirty steps from Init, behind a
    second election, where BFS order would not reach in a test's time."""
    rng = random.Random(seed)
    taken = {}
    for _ in range(400):
        st = oracle.init_state()
        for _step in range(80):
            succs = oracle.successors(st)
            if not succs:
                break
            label, nxt = rng.choice(succs)
            taken.setdefault(_action(label), []).append(st)
            st = nxt
    return {name: sts[:: max(1, len(sts) // 10)][:10]
            for name, sts in taken.items()}


def test_in_tree_cfg_keeps_upstreams_bug_and_builds_the_published_constants(
        setup):
    from raft_tpu.ops.symmetry import Canonicalizer

    with pytest.raises(CfgError, match="undeclared model value 'v2'"):
        parse_cfg(CFG)
    p = setup.model.p
    assert (p.n_servers, p.n_values) == (3, 2)
    assert (p.max_elections, p.max_restarts) == (2, 0)
    assert (p.msg_slots, p.max_log, p.variant2) == (64, 6, False)
    assert setup.model.name == "PullRaft"
    assert setup.symmetry and setup.invariants == INVARIANTS
    # the row the cell is named for: 85 candidates, 64 of them bag slots
    assert (setup.model.layout.W, setup.model.A) == (259, 85)
    groups = {g.name: g.n for g in setup.model.sparse_groups()}
    assert groups["HandleMessage"] == 64
    assert Canonicalizer.for_model(setup.model, symmetry=True).P == 6


def test_the_two_cfg_copies_differ_in_one_line_and_build_one_model():
    """The benchmark's copy is the in-tree cfg with `v2` declared, which
    is all --lenient does to it; the adapter, which parses strictly,
    builds from it the engine the CLI builds from the in-tree file."""
    from benchmark import adapter
    from raft_tpu.checker.device_bfs import DeviceBFS

    with open(CFG) as f:
        tree = f.read().splitlines()
    with open(BENCH_CFG) as f:
        bench = f.read().splitlines()
    added = [line for line in bench if line not in tree]
    assert [line.split() for line in added] == [["v2", "=", "v2"]]
    assert [line for line in bench if line not in added] == tree
    lenient, strict = parse_cfg(CFG, lenient=True), parse_cfg(BENCH_CFG)
    assert lenient.constants == strict.constants
    assert lenient.invariants == strict.invariants
    assert lenient.symmetry == strict.symmetry
    cell = _load(BENCH, "workloads", "pull3-full.json")
    assert cell["engine_params"] == {
        "chunk": 2048, "msg_slots": 64, "frontier_cap": 524288}
    params = dict(cell["engine_params"], chunk=64, frontier_cap=1 << 12)
    bench_eng = adapter.build_engine(BENCH_CFG, cell["engine"], params, None)
    cli = build_from_cfg(lenient, msg_slots=params["msg_slots"])
    cli_eng = DeviceBFS(cli.model, invariants=cli.invariants,
                        symmetry=cli.symmetry, chunk=64, frontier_cap=1 << 12)
    assert adapter.ident(bench_eng) == cli_eng._ckpt_ident()
    assert "PullRaft/" in adapter.ident(bench_eng)
    config = _load(BENCH, "configs", "pull3", "config.json")
    assert config["assumed"]["row_lanes"] == cli.model.layout.W
    assert config["assumed"]["max_log"] == cli.model.p.max_log
    assert config["constants"]["INVARIANT"] == list(INVARIANTS)


@pytest.mark.parametrize("variant2", [False, True])
def test_successor_sets_match_oracle_on_walked_states(setup, variant2):
    """After the conversion to one-hot reads and writes: every action the
    constants allow was taken from some sampled state (all eight
    receipts, a rejected pull and the truncation it causes among them),
    and per state the (action, successor) pairs equal the oracle's, under
    both variants of the one lowering file."""
    if variant2:
        model = PullRaftModel(PullRaftParams(
            3, 2, 2, 0, msg_slots=64, variant2=True))
        from raft_tpu.oracle.pull_oracle import PullRaftOracle

        oracle = PullRaftOracle(3, 2, 2, 0, variant2=True)
    else:
        model, oracle = setup.model, oracle_for_setup(setup)
    walked = _walked(oracle, 43)
    assert RECEIPTS | {"RequestVote", "BecomeLeader", "ClientRequest",
                       "SendPullEntriesRequest"} == set(walked)
    sample = [st for name in sorted(walked) for st in walked[name]]
    assert any(len(log) >= 2 for st in sample for log in st["log"])
    vecs = np.stack([model.encode(st) for st in sample]).astype(np.int32)
    succs, valid, rank, ovf = jax.device_get(model.expand(vecs))
    assert not np.any(valid & ovf)
    for b, st in enumerate(sample):
        got = sorted(
            (model.ACTION_NAMES[rank[b, a]],
             oracle.serialize_full(model.decode(succs[b, a])))
            for a in np.nonzero(valid[b])[0])
        want = sorted((_action(label), oracle.serialize_full(s2))
                      for label, s2 in oracle.successors(st))
        assert got == want, f"successor mismatch at state {b}"


def test_canon_is_brute_force_over_6_permutations_of_the_oracle(setup):
    """The engine's canonical fingerprint of a state is the least, over
    all 6 server permutations, of the plain view hash of the oracle's
    permuted state as the model encodes it (`acked` in the view)."""
    from raft_tpu.ops.symmetry import Canonicalizer

    model, oracle = setup.model, oracle_for_setup(setup)
    canon = Canonicalizer.for_model(model, symmetry=True)
    walked = _walked(oracle, 7)
    states = [st for name in sorted(walked) for st in walked[name]][::4]
    perms = list(itertools.permutations(range(3)))
    rows = np.stack([
        model.encode(oracle.permute(st, list(sigma)))
        for st in states for sigma in perms
    ]).astype(np.int32)
    raw = np.asarray(canon.raw_fingerprints(rows)).reshape(
        len(states), len(perms))
    got = np.asarray(canon.fingerprints(rows)).reshape(raw.shape)
    assert np.array_equal(got[:, 0], raw.min(axis=1))
    assert np.array_equal(got, np.broadcast_to(got[:, :1], got.shape))
    keys = [oracle.canon(st, True) for st in states]
    assert len(set(keys)) == len(set(got[:, 0].tolist()))


@pytest.fixture(scope="module")
def device_run():
    """The benchmark's copy of the cfg, built as the adapter builds it,
    at the cell's chunk and bag width, to the depth tier-1 affords (a
    frontier of 2^15 rows holds it: 3 x 7,803 new rows < 2^15)."""
    from benchmark import adapter

    cell = _load(BENCH, "workloads", "pull3-full.json")
    eng = adapter.build_engine(
        BENCH_CFG, cell["engine"],
        dict(cell["engine_params"], frontier_cap=1 << 15), None)
    return eng, eng.run(max_depth=DEPTH, collect_metrics=True)


def test_device_bfs_at_the_cells_chunk_counts_the_golden_to_depth_14(
        device_run, golden):
    eng, res = device_run
    smoke = _load(SMOKE_GOLDEN)["depth_limited"]
    assert (eng.chunk, eng.VC, eng.A, eng.W) == (2048, 32768, 85, 259)
    assert res.violation is None and res.exit_cause == "max_depth"
    assert [int(x) for x in res.depth_counts] == smoke["depth_counts"]
    assert (res.distinct, res.total, res.terminal) == (
        smoke["distinct"], smoke["total"], smoke["terminal"])
    assert res.distinct == 20091
    rows = res.metrics
    assert [w["depth"] for w in rows] == list(range(1, DEPTH + 1))
    assert not any(w["overflow_bits"] for w in rows)
    # a merged run is never searched: the counter the cell reports reads
    # 0 on every row and on the run, and every row says which run it met
    plan = eng._dedup_plan()
    assert (plan["merge"], plan["search"]) == ([1 << 18], [])
    assert [w["seen_lanes"] for w in rows] == [1 << 18] * DEPTH
    assert [w["dedup_search_queries"] for w in rows] == [0] * DEPTH
    assert res.stats["dedup_search_queries"] == 0
    assert res.stats["dedup_sort_lanes"] == sum(
        w["dedup_sort_lanes"] for w in rows) > 0


def test_golden_is_the_whole_space_and_the_smoke_file_its_prefix(golden):
    """benchmark/goldens/pull3.json is the pooled oracle run's record to
    depth 38: the last new state is at depth 37, wave 38 finds none, and
    the totals are pinned at the cell's depth, the smoke's and the
    exhausted end. tests/golden/pull3_cfg_depth_counts.json, what
    chip_smoke.py's leg G and this file's engine are held to, is its
    prefix."""
    assert golden["msg_slots"] == 64
    assert golden["independent_to_depth"] == 38
    counts = golden["depth_counts"]
    assert len(counts) == 38 and counts[-1] == 152
    assert sum(counts) == golden["exhausted"]["distinct"] == 1113796
    assert golden["totals"]["37"] == {"total": 3233465, "terminal": 9942}
    assert golden["totals"]["38"]["terminal"] == golden["exhausted"][
        "terminal"] == 10094
    traffic = _load(BENCH, "traffic", "init-d37-warm37.json")
    assert traffic["warmup_depth"] == traffic["max_depth"] == 37
    # the cell's chunk-steps, from the counts: a wave expands the
    # previous depth's new states in chunks of 2,048 rows
    steps = [-(-n // 2048) for n in counts[:37]]
    assert sum(steps) == 565 and sum(s for s in steps if s > 1) == 552
    size = lambda n: next(s for s in (1 << 18, 1 << 20, 1 << 22) if n <= s)
    met = [size(sum(counts[:k])) for k in range(1, 38)]
    assert met == [1 << 18] * 22 + [1 << 20] * 10 + [1 << 22] * 5
    searched = sum(s for s, lanes in zip(steps, met)
                   if not util.merges(lanes, 32768))
    assert searched == 53 and f"{searched * 32768:,}" in _load(
        BENCH, "workloads", "pull3-full.json")["job"]
    smoke = _load(SMOKE_GOLDEN)["depth_limited"]
    depth = smoke["max_depth"]
    assert depth == DEPTH and smoke["msg_slots"] == 64
    assert smoke["depth_counts"] == counts[: depth + 1]
    assert smoke["distinct"] == sum(smoke["depth_counts"])
    assert {k: smoke[k] for k in ("total", "terminal")} == golden[
        "totals"][str(depth)]


def test_search_queries_count_the_chunk_steps_whose_content_no_rung_holds(
        setup):
    """A small engine whose seen run is past the crossover for part of
    the verdict: 128 query lanes a chunk-step (16 rows x 8), a floor of
    1,024 lanes and a ladder of 4,096, 16,384 and 65,536 lanes by hand.
    128 queries merge up to 8,192 lanes, so the 16,384-lane run is
    merged by its rungs while it and the wave hold no more than that:
    all of wave 12 (4,150 fingerprints before it, 7,258 after) and the
    chunk-steps of wave 13 that start with 934 new states or fewer;
    the rest of wave 13 and all 315 steps of wave 14, whose run starts
    with 12,288 fingerprints, search it. The counter is those steps'
    query lanes and nothing else, each row says which run it met, and
    the counts stay the golden's."""
    from raft_tpu.checker.device_bfs import DeviceBFS

    eng = DeviceBFS(setup.model, invariants=setup.invariants, symmetry=True,
                    chunk=16, valid_per_state=8, frontier_cap=1 << 13,
                    max_frontier_cap=1 << 13, journal_cap=1 << 15)
    eng._seen_sizes = [1 << 12, 1 << 14, 1 << 16]
    eng.SORT_FLOOR = 1 << 10
    assert eng.VC == 128
    assert util.merges(1 << 13, 128) and not util.merges(1 << 14, 128)
    assert eng._rungs(1 << 14) == (
        1024, 1536, 2048, 3072, 4096, 6144, 8192)
    res = eng.run(max_depth=14, collect_metrics=True)
    counts = _load(SMOKE_GOLDEN)["depth_limited"]["depth_counts"][:15]
    assert [int(x) for x in res.depth_counts] == counts
    assert res.violation is None and res.distinct == 20091
    assert (sum(counts[:12]), sum(counts[:13]), sum(counts[:14])) == (
        4150, 7258, 12288)
    rows = res.metrics
    assert not any(w["overflow_bits"] for w in rows)
    assert [w["seen_lanes"] for w in rows] == [1 << 12] * 11 + [1 << 14] * 3
    # the row's own size is the run the wave met; lsm_lanes is the run
    # it left behind, a wave earlier at each step
    assert [w["lsm_lanes"] for w in rows] == (
        [1 << 12] * 10 + [1 << 14] * 3 + [1 << 16])
    steps = [-(-w["frontier"] // 16) for w in rows]
    assert steps[11:] == [116, 195, 315]
    queries = [w["dedup_search_queries"] for w in rows]
    assert queries[:12] == [0] * 12 and queries[13] == 315 * 128
    # wave 13 searches from the step at which its count passes 934
    assert 0 < queries[12] < 195 * 128 and queries[12] % 128 == 0
    assert res.stats["dedup_search_queries"] == sum(queries)
    plan = eng._dedup_plan()
    assert plan["search"] == [1 << 16] and plan["rungs"][-1] == 8192
    # a step sorts the rung that holds its content or, searching, the
    # wave's prefix and its queries: never the run's capacity
    assert rows[11]["dedup_sort_lanes"] <= 116 * (8192 + 128)
    searched13 = queries[12] // 128
    assert rows[12]["dedup_sort_lanes"] <= (
        (195 - searched13) * (8192 + 128) + searched13 * ((1 << 13) + 128))
    assert rows[13]["dedup_sort_lanes"] <= 315 * ((1 << 13) + 128)


def test_wave_row_schema_knows_the_two_new_keys(device_run):
    """Every row of a real verdict carries both keys and passes the
    schema (tests/test_obs.py holds what the schema refuses of them)."""
    from raft_tpu.obs.events import validate_event

    _eng, res = device_run
    for n, w in enumerate(res.metrics, 1):
        assert {"dedup_search_queries", "seen_lanes"} <= set(w)
        # the collector adds the two keys a stream's row starts with
        assert validate_event({"event": "wave", "wave": n, **w}) == []
