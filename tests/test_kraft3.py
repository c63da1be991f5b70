"""kraft3: upstream's KRaft.cfg (Kafka's KIP-595 fetch-based quorum: 3
servers, 1 value, MaxElections 2, MaxRestarts 0, 6 permutations, four
invariants), at the published constants and the registry's own bag
width, against the pure-Python oracle: 291-lane rows, 98 candidate
actions a state, 80 of them HandleMessage over the bag's slots.

The cfg in the tree is reconstructed (its header says from what). The
space is small by nature at these depths (2,361 distinct states to depth
10), so everything here runs at the cfg's own constants. One DeviceBFS
verdict to depth 10 serves the tests of the engine; the oracle's is its
twin.
"""

import filecmp
import itertools
import json
import os
import random
import subprocess
import sys

import jax
import numpy as np
import pytest

from raft_tpu.models import kraft
from raft_tpu.models.registry import build_from_cfg, oracle_for_setup
from raft_tpu.utils.cfg import parse_cfg

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = os.path.join(ROOT, "configs", "pull-raft", "KRaft.cfg")
BENCH = os.path.join(ROOT, "benchmark")
DEPTH = 10
INVARIANTS = (
    "LeaderHasAllAckedValues",
    "NoLogDivergence",
    "NeverTwoLeadersInSameEpoch",
    "NoIllegalState",
)


@pytest.fixture(scope="module")
def setup():
    # strict parsing, the registry's own bag width: the CLI's path
    return build_from_cfg(parse_cfg(CFG))


@pytest.fixture(scope="module")
def oracle(setup):
    return oracle_for_setup(setup)


@pytest.fixture(scope="module")
def walked(oracle):
    """{action: states it was taken from} on seeded random walks of 60
    steps, at most 12 states an action. BFS order would stop short of a
    pending fetch answered by a new leader, some twenty steps from Init;
    a walk reaches it in a second."""
    rng = random.Random(32)
    taken = {}
    for _ in range(300):
        st = oracle.init_state()
        for _step in range(60):
            succs = oracle.successors(st)
            if not succs:
                break
            label, nxt = rng.choice(succs)
            taken.setdefault(label.split("(")[0], []).append(st)
            st = nxt
    return {name: sts[:: max(1, len(sts) // 12)][:12]
            for name, sts in taken.items()}


@pytest.fixture(scope="module")
def sample(walked):
    return [st for name in sorted(walked) for st in walked[name]]


@pytest.fixture(scope="module")
def oracle_run(setup, oracle):
    return oracle.bfs(invariants=setup.invariants, symmetry=True,
                      max_depth=DEPTH)


@pytest.fixture(scope="module")
def device_run(setup):
    from raft_tpu.checker.device_bfs import DeviceBFS

    eng = DeviceBFS(setup.model, invariants=setup.invariants, symmetry=True,
                    chunk=256, frontier_cap=1 << 12)
    return eng, eng.run(max_depth=DEPTH, collect_metrics=True)


def test_in_tree_kraft_cfg_builds_the_published_constants(setup):
    from raft_tpu.ops.symmetry import Canonicalizer

    p = setup.model.p
    assert (p.n_servers, p.n_values) == (3, 1)
    assert (p.max_elections, p.max_restarts) == (2, 0)
    assert p.msg_slots == 80  # the registry's own
    assert setup.model.name == "KRaft"
    assert setup.symmetry and setup.invariants == INVARIANTS
    # the row the cell is named for: 98 candidates, 80 of them bag slots
    assert (setup.model.layout.W, setup.model.A) == (291, 98)
    groups = {g.name: g.n for g in setup.model.sparse_groups()}
    assert groups["HandleMessage"] == 80
    canon = Canonicalizer.for_model(setup.model, symmetry=True)
    assert canon.P == 6
    # the benchmark's configuration runs a copy of this very file
    assert filecmp.cmp(CFG, os.path.join(
        BENCH, "configs", "kraft3", "KRaft.cfg"), shallow=False)


def _diverging(st) -> bool:
    """A Diverging FetchResponse is in flight: a follower's log holds
    what its new leader's does not."""
    return any(dict(m).get("mresult") == "Diverging"
               for m, _count in st["messages"])


def test_successor_sets_match_oracle_on_walked_states(
        setup, oracle, walked, sample):
    """Every receipt action of the spec was taken from some sampled
    state, a pending fetch and a diverging log among them, and per state
    the (action, successor) pairs equal the oracle's."""
    model = setup.model
    assert {"SendFetchRequest", "AcceptFetchRequest", "RejectFetchRequest",
            "DivergingFetchRequest", "HandleSuccessFetchResponse",
            "HandleDivergingFetchResponse", "HandleBeginQuorumRequest",
            "HandleRequestVoteRequest", "HandleRequestVoteResponse",
            "BecomeLeader", "ClientRequest", "RequestVote"} <= set(walked)
    assert any(any(pf is not None for pf in st["pendingFetch"])
               for st in sample)
    assert any(_diverging(st) for st in sample)
    vecs = np.stack([model.encode(st) for st in sample]).astype(np.int32)
    succs, valid, rank, ovf = jax.device_get(model.expand(vecs))
    assert not np.any(valid & ovf)
    for b, st in enumerate(sample):
        got = sorted(
            (model.ACTION_NAMES[rank[b, a]],
             oracle.serialize_full(model.decode(succs[b, a])))
            for a in np.nonzero(valid[b])[0])
        want = sorted((label.split("(")[0], oracle.serialize_full(s2))
                      for label, s2 in oracle.successors(st))
        assert got == want, f"successor mismatch at state {b}"


def test_canon_is_brute_force_over_6_permutations_of_the_oracle(
        setup, oracle, sample):
    """The engine's canonical fingerprint of a state is the least, over
    all 6 server permutations, of the plain view hash of the oracle's
    permuted state as the model encodes it (the nil-valued `mleader`
    inside the packed records and the decomposed pendingFetch among the
    remapped fields)."""
    from raft_tpu.ops.symmetry import Canonicalizer

    model = setup.model
    canon = Canonicalizer.for_model(model, symmetry=True)
    states = sample[::2]
    perms = list(itertools.permutations(range(3)))
    assert len(perms) == canon.P
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


def test_device_bfs_counts_match_oracle_to_depth_10(device_run, oracle_run):
    _eng, res = device_run
    want = oracle_run
    assert res.violation is None and want["violation"] is None
    assert res.exit_cause == "max_depth"
    assert [int(x) for x in res.depth_counts] == want["depth_counts"]
    assert (res.distinct, res.total, res.terminal) == (
        want["distinct"], want["total"], want["terminal"])
    assert res.distinct == 2361
    rows = res.metrics
    assert [w["depth"] for w in rows] == list(range(1, DEPTH + 1))
    assert not any(w["overflow_bits"] for w in rows)


@pytest.fixture(scope="module")
def sharded_run(setup):
    """A one-device ShardedBFS verdict to depth 8: the other caller of
    canon's in-chunk dedup."""
    from raft_tpu.parallel.sharded import ShardedBFS

    eng = ShardedBFS(setup.model, invariants=setup.invariants, symmetry=True,
                     devices=jax.devices()[:1], chunk=256,
                     frontier_cap=1 << 12, seen_cap=1 << 14)
    return eng, eng.run(max_depth=8, collect_metrics=True)


@pytest.mark.parametrize("engine", ["device", "sharded"])
def test_every_representative_takes_the_table_and_the_rest_are_duplicates(
        engine, request):
    """Three servers have no tiers: a wave's lanes are its in-chunk
    duplicates, which skip the permutations, and their representatives,
    each of which takes the 6-table min. The counts are the golden's."""
    _eng, res = request.getfixturevalue(f"{engine}_run")
    with open(os.path.join(BENCH, "goldens", "kraft3.json")) as f:
        golden = json.load(f)
    depth = res.depth
    assert [int(x) for x in res.depth_counts] == golden["depth_counts"][
        : depth + 1]
    if str(depth) in golden["totals"]:
        assert {"total": res.total, "terminal": res.terminal} == golden[
            "totals"][str(depth)]
    rows = res.metrics
    assert len(rows) == depth
    for w in rows:
        assert w["canon_tier3_local"] == 0
        assert w["generated"] - w["canon_dup_lanes"] == w[
            "canon_tier3_full"] > 0, w
        assert w["canon_dup_rate"] == round(
            w["canon_dup_lanes"] / w["generated"], 4)
    # Init's three timeouts are three raw views; later waves repeat
    assert rows[0]["canon_dup_lanes"] == 0
    assert sum(w["canon_dup_lanes"] for w in rows) > res.total // 4
    assert res.stats["canon_tier3_full"] == sum(
        w["canon_tier3_full"] for w in rows)


def test_golden_prefix_is_what_the_oracle_and_the_engine_count(
        device_run, oracle_run):
    """benchmark/goldens/kraft3.json, the pooled oracle run's record,
    starts with this process's one-process oracle counts; its totals
    cover the cell's depth, and what chip_smoke.py's leg E holds the CLI
    to is its prefix."""
    with open(os.path.join(BENCH, "goldens", "kraft3.json")) as f:
        golden = json.load(f)
    assert golden["msg_slots"] == 80
    assert golden["independent_to_depth"] >= 20
    assert golden["depth_counts"][: DEPTH + 1] == oracle_run["depth_counts"]
    _eng, res = device_run
    assert golden["depth_counts"][: DEPTH + 1] == [
        int(x) for x in res.depth_counts]
    with open(os.path.join(BENCH, "traffic", "init-d20-warm20.json")) as f:
        traffic = json.load(f)
    assert traffic["warmup_depth"] == traffic["max_depth"] == 20
    for depth in (8, 14, 18, 20):
        assert str(depth) in golden["totals"]
    with open(os.path.join(
            ROOT, "tests", "golden", "kraft_cfg_depth_counts.json")) as f:
        smoke = json.load(f)["depth_limited"]
    depth = smoke["max_depth"]
    assert depth == 14 and smoke["msg_slots"] == 80
    assert smoke["depth_counts"] == golden["depth_counts"][: depth + 1]
    assert smoke["distinct"] == sum(smoke["depth_counts"])
    assert {k: smoke[k] for k in ("total", "terminal")} == golden[
        "totals"][str(depth)]


def _two_leaders(st):
    """Servers 0 and 1 believe in different leaders of one epoch."""
    ep = max(st["currentEpoch"])
    return dict(st, currentEpoch=(ep, ep) + st["currentEpoch"][2:],
                leader=(0, 1) + st["leader"][2:])


def _illegal(st):
    return dict(st, state=(kraft.ILLEGAL,) + st["state"][1:])


@pytest.mark.parametrize("name,break_it", [
    ("NeverTwoLeadersInSameEpoch", _two_leaders),
    ("NoIllegalState", _illegal),
])
def test_the_two_invariants_no_other_cell_evaluates_equal_the_oracles(
        setup, oracle, sample, name, break_it):
    """The kernel and the oracle's predicate agree on reachable states
    (where both hold) and on the same states broken by hand (where
    neither does)."""
    model = setup.model
    states = sample[::3]
    states = states + [break_it(st) for st in states]
    want = np.array([oracle.INVARIANTS[name](oracle, st) for st in states])
    vecs = np.stack([model.encode(st) for st in states]).astype(np.int32)
    got = np.asarray(model.invariants[name](vecs))
    assert np.array_equal(got, want)
    half = len(states) // 2
    assert want[:half].all() and not want[half:].any()


def test_a_second_verdict_across_the_seen_runs_ladder_compiles_nothing(setup):
    """The cell `kraft3-wide` takes the seen run past its first size
    inside every verdict: the merge that steps it up, the steady merge
    at the larger size and the wave program against the larger run are
    each a program of their own, and `_merge_cache` and the jit cache
    have to hand all of them back to the next `run()`. The ladder's
    first size is 2^18 lanes at any capacity a CPU test can fill, so
    this engine is given a short ladder by hand: 512 lanes, stepped at
    depth 8 (515 distinct) and again at depth 10 (2,361)."""
    from raft_tpu.checker.device_bfs import DeviceBFS

    eng = DeviceBFS(setup.model, invariants=setup.invariants, symmetry=True,
                    chunk=64, frontier_cap=1 << 12)
    eng._seen_sizes = [1 << 9, 1 << 11, 1 << 13]
    first = eng.run(max_depth=DEPTH, collect_metrics=True)
    lanes = [w["lsm_lanes"] for w in first.metrics]
    assert lanes == [512] * 7 + [2048] * 2 + [8192]
    assert first.stats["run_compiles"] >= 3
    again = eng.run(max_depth=DEPTH, collect_metrics=True)
    assert [w["lsm_lanes"] for w in again.metrics] == lanes
    assert again.stats["run_compiles"] == 0
    assert not any(w["compiles"] for w in again.metrics)
    assert again.stats["programs_loaded"] == first.stats["programs_loaded"]
    assert again.depth_counts == first.depth_counts
    assert (again.distinct, again.total) == (first.distinct, first.total)
    assert again.distinct == 2361
    # the merge that stepped the run up is one of the cached programs,
    # and its ops carry the stage scope a trace is split by
    step_up = [k for k in eng._merge_cache if k[0] < k[2]]
    assert {(k[0], k[2]) for k in step_up} == {(512, 2048), (2048, 8192)}
    merge, donate = DeviceBFS._seen_merge_spec(step_up[0])
    assert donate == ()  # a larger output cannot alias the old run
    lowered = jax.jit(merge).lower(*[
        jax.ShapeDtypeStruct((n,), np.uint64)
        for n in (step_up[0][0], *step_up[0][1])]).as_text(debug_info=True)
    assert "seen_merge" in lowered


def test_oracle_golden_script_is_byte_equal_with_and_without_workers():
    """KRaftOracle.bfs returns `terminal` as the other oracles' do, so
    the script's one-process path runs, and the pool's output is its
    byte for byte."""
    def run(*extra):
        r = subprocess.run(
            [sys.executable, "scripts/oracle_golden.py", CFG,
             "--max-depth", "6", *extra],
            cwd=ROOT, capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert r.returncode == 0, r.stderr[-2000:]
        return r.stdout

    one = run()
    assert one == run("--workers", "2")
    got = json.loads(one)
    assert got["depth_counts"] == [1, 1, 3, 6, 15, 29, 60]
    assert (got["distinct"], got["total"], got["terminal"]) == (115, 214, 0)


@pytest.mark.parametrize("module,cls,args", [
    ("pull_oracle", "PullRaftOracle", (3, 1, 1, 0)),
    ("kraft_reconfig_oracle", "KRaftReconfigOracle",
     (3, 1, 2, 2, 3, 1, 0, 1, 1, 1, 4)),
])
def test_the_other_pull_oracles_return_terminal_too(module, cls, args):
    import importlib

    oracle = getattr(
        importlib.import_module(f"raft_tpu.oracle.{module}"), cls)(*args)
    res = oracle.bfs(max_depth=3)
    assert res["terminal"] == 0 and res["distinct"] == sum(
        res["depth_counts"])
