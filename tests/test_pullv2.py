"""pullv2: upstream's PullRaftVariant2.cfg (PullRaft's second variant: a
follower pulls only after a LeaderNotifyRequest reached it, a voter's
last entry rides on its vote, the new leader notifies every peer with
the last common entry and LearnOfLeader truncates to it; 3 servers, 2
values, MaxElections 2, MaxRestarts 0, 6 permutations, two invariants),
at the published constants and the registry's own bag width, against
the pure-Python oracle: 289-lane rows (a view of 285: `acked` is aux),
the same 85 candidate actions a state as PullRaft, 64 of them
HandleMessage over the bag's slots.

The cfg in the tree is reconstructed (its header says from what) and
keeps upstream's latent bug, `v2` undeclared; the benchmark's copy has
the one repair made in the file. The whole space is 1,454,442 states to
depth 39 (benchmark/goldens/pullv2.json); tier-1 affords its first 14
depths, 16,221 states, at the cell's chunk: one DeviceBFS verdict to
that depth, built as the benchmark's adapter builds it.
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
from raft_tpu.models.registry import build_from_cfg, oracle_for_setup
from raft_tpu.utils.cfg import CfgError, parse_cfg

from conftest import eqns

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = os.path.join(ROOT, "configs", "pull-raft", "PullRaftVariant2.cfg")
BENCH = os.path.join(ROOT, "benchmark")
BENCH_CFG = os.path.join(BENCH, "configs", "pullv2", "PullRaftVariant2.cfg")
SMOKE_GOLDEN = os.path.join(
    ROOT, "tests", "golden", "pullv2_cfg_depth_counts.json")
DEPTH = 14
BOUND = 39
INVARIANTS = ("LeaderHasAllAckedValues", "NoLogDivergence")
RECEIPTS = {
    "UpdateTerm", "HandleRequestVoteRequest", "HandleRequestVoteResponse",
    "RejectPullEntriesRequest", "AcceptPullEntriesRequest", "LearnOfLeader",
    "HandleSuccessPullEntriesResponse", "HandleFailPullEntriesResponse"}
# the six kernel groups, PullRaft's own: (name, candidates a state)
GROUPS = [("Restart", 3), ("RequestVote", 3), ("BecomeLeader", 3),
          ("ClientRequest", 6), ("SendPullEntriesRequest", 6),
          ("HandleMessage", 64)]


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def setup():
    # the CLI's path under --lenient, the registry's own bag width
    return build_from_cfg(parse_cfg(CFG, lenient=True))


@pytest.fixture(scope="module")
def golden():
    return _load(BENCH, "goldens", "pullv2.json")


@pytest.fixture(scope="module")
def cell():
    return _load(BENCH, "workloads", "pullv2-full.json")


def _action(label):
    """`RequestVote(0)`, `UpdateTerm[1]`, `LearnOfLeader`: the action."""
    return re.split(r"[(\[]", label)[0]


@pytest.fixture(scope="module")
def walked(setup):
    """{action: (state, successor) pairs it was taken by} on seeded
    random walks of 80 steps: a rejected pull and the second election's
    notifies sit some thirty steps from Init, where BFS order would not
    reach in a test's time."""
    oracle = oracle_for_setup(setup)
    rng = random.Random(55)
    taken = {}
    for _ in range(400):
        st = oracle.init_state()
        for _step in range(80):
            succs = oracle.successors(st)
            if not succs:
                break
            label, nxt = rng.choice(succs)
            taken.setdefault(_action(label), []).append((st, nxt))
            st = nxt
    return taken


def _truncates(pair):
    st, nxt = pair
    return any(len(b) < len(a) for a, b in zip(st["log"], nxt["log"]))


def _notifies_a_peer_that_did_not_vote(pair):
    st, nxt = pair
    (i,) = [i for i, (a, b) in enumerate(zip(st["state"], nxt["state"]))
            if a != b]
    return len(st["votesGranted"][i]) < len(st["state"])


def _sample(walked):
    """At most 10 states an action, evenly through the walks, and with
    them a BecomeLeader whose notify goes to a peer that did not vote
    (PullRaft notifies only those; the variant notifies all, with no
    last common entry for that one)."""
    sample = []
    for name in sorted(walked):
        pairs = walked[name]
        sample += [st for st, _ in pairs[:: max(1, len(pairs) // 10)][:10]]
    unvoted = [p for p in walked["BecomeLeader"]
               if _notifies_a_peer_that_did_not_vote(p)]
    assert unvoted
    return sample + [unvoted[0][0]]


def _assert_successors_equal(model, oracle, sample):
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


def test_in_tree_cfg_keeps_upstreams_bug_and_builds_the_published_constants(
        setup):
    from raft_tpu.ops.symmetry import Canonicalizer

    with pytest.raises(CfgError, match="undeclared model value 'v2'"):
        parse_cfg(CFG)
    cfg = parse_cfg(CFG, lenient=True)
    assert len(cfg.diagnostics) == 1 and "'v2'" in cfg.diagnostics[0]
    p = setup.model.p
    assert (p.n_servers, p.n_values) == (3, 2)
    assert (p.max_elections, p.max_restarts) == (2, 0)
    assert (p.msg_slots, p.max_log, p.variant2) == (64, 6, True)
    assert setup.model.name == "PullRaftVariant2"
    assert setup.symmetry and setup.invariants == INVARIANTS
    # the row the cell is named for: PullRaft's 259 lanes and votedFor's
    # 3, votesLastEntry's 27; the same 85 candidates in the same groups
    assert (setup.model.layout.W, setup.model.A) == (289, 85)
    assert [(g.name, g.n) for g in setup.model.sparse_groups()] == GROUPS
    plain = build_from_cfg(parse_cfg(
        os.path.join(os.path.dirname(CFG), "PullRaft.cfg"), lenient=True))
    assert plain.model.layout.W == 259 and not plain.model.p.variant2
    assert [(g.name, g.n) for g in plain.model.sparse_groups()] == GROUPS
    # `acked` leaves the view (PullRaftVariant2.tla:114): the canon
    # hashes 285 of the row's lanes, PullRaft's 257 of 259
    canon = Canonicalizer.for_model(setup.model, symmetry=True)
    assert canon.P == 6
    assert setup.model.layout.view_len == 285
    assert plain.model.layout.view_len == 257


def test_the_two_cfg_copies_differ_in_one_line_and_build_one_model(cell):
    """The benchmark's copy is the in-tree cfg with `v2` declared, which
    is all --lenient does to it, under upstream's own file name (the
    registry takes the spec from it); the adapter, which parses
    strictly, builds from it the engine the CLI builds from the in-tree
    file."""
    from benchmark import adapter
    from raft_tpu.checker.device_bfs import DeviceBFS

    with open(CFG) as f:
        tree = f.read().splitlines()
    with open(BENCH_CFG) as f:
        bench = f.read().splitlines()
    added = [line for line in bench if line not in tree]
    assert [line.split() for line in added] == [["v2", "=", "v2"]]
    assert [line for line in bench if line not in added] == tree
    assert os.path.basename(BENCH_CFG) == os.path.basename(CFG)
    lenient, strict = parse_cfg(CFG, lenient=True), parse_cfg(BENCH_CFG)
    assert lenient.constants == strict.constants
    assert lenient.invariants == strict.invariants
    assert lenient.symmetry == strict.symmetry
    assert cell["engine_params"] == {
        "chunk": 2048, "msg_slots": 64, "frontier_cap": 524288}
    params = dict(cell["engine_params"], chunk=64, frontier_cap=1 << 12)
    bench_eng = adapter.build_engine(BENCH_CFG, cell["engine"], params, None)
    cli = build_from_cfg(lenient, msg_slots=params["msg_slots"])
    cli_eng = DeviceBFS(cli.model, invariants=cli.invariants,
                        symmetry=cli.symmetry, chunk=64, frontier_cap=1 << 12)
    assert adapter.ident(bench_eng) == cli_eng._ckpt_ident()
    assert adapter.ident(bench_eng).startswith("PullRaftVariant2/")
    assert "variant2=True" in adapter.ident(bench_eng)
    config = _load(BENCH, "configs", "pullv2", "config.json")
    assert config["cfg"] == os.path.basename(BENCH_CFG)
    assert config["assumed"]["row_lanes"] == cli.model.layout.W
    assert config["assumed"]["max_log"] == cli.model.p.max_log
    assert config["assumed"]["msg_slots"] == cli.model.p.msg_slots
    assert config["constants"]["INVARIANT"] == list(INVARIANTS)


def test_config_file_states_the_source_the_cuts_and_pull3s_guarantees():
    config = _load(BENCH, "configs", "pullv2", "config.json")
    (entry,) = [c for c in _load(ROOT, "BENCHMARK.json")["configs"]
                if c["name"] == "pullv2"]
    assert config["source"] == entry["source"] == (
        "https://github.com/Vanlightly/raft-tlaplus "
        "specifications/pull-raft/PullRaftVariant2.cfg")
    assert config["architecture"] is None
    assert config["reduced"] == entry["reduced"] == ["max_depth"]
    assert "cuts nothing" in config["reduced_note"]
    assert {"MaxElections", "MaxRestarts", "v2", "msg_slots", "max_log",
            "chunk", "row_lanes"} <= set(config["assumed"])
    assert (config["assumed"]["MaxElections"],
            config["assumed"]["MaxRestarts"]) == (2, 0)
    # what is recorded of upstream's file is not assumed
    assert not {"Server", "Value", "VIEW", "SYMMETRY", "INVARIANT"} & set(
        config["assumed"])
    assert (config["constants"]["Server"], config["constants"]["Value"]) == (
        3, 2)
    pull3 = _load(BENCH, "configs", "pull3", "config.json")
    assert config["guarantees"] == pull3["guarantees"]
    assert len(config["guarantees"]) == 6


def test_successor_sets_match_oracle_on_walked_states(setup, walked):
    """Every action the constants allow but Restart (MaxRestarts is 0)
    was taken from some sampled state, all eight receipts among them,
    and a notify to a peer that did not vote; per state the (action,
    successor) pairs equal the oracle's."""
    model, oracle = setup.model, oracle_for_setup(setup)
    assert oracle.variant2
    assert RECEIPTS | {"RequestVote", "BecomeLeader", "ClientRequest",
                       "SendPullEntriesRequest"} == set(walked)
    sample = _sample(walked)
    assert any(len(log) >= 2 for st in sample for log in st["log"])
    assert any(vle is not None for st in sample
               for row in st["votesLastEntry"] for vle in row)
    _assert_successors_equal(model, oracle, sample)


def test_learn_of_leader_truncates_after_a_third_election_only(walked):
    """The variant's truncation (NeedsTruncation, TruncateLog:
    PullRaftVariant2.tla:171-179, :398-410) needs a voter whose log has
    an entry the new leader lacks, under a last entry of a higher term on
    the leader's side: the leader-to-be was leader or follower of a term
    between the voter's entry and this election, which is a third
    election. Under the cfg's MaxElections = 2 every voter's log is a
    prefix of the winner's, the notify's last common entry is the
    voter's last entry and LearnOfLeader cuts nothing: none of the walks'
    LearnOfLeader steps shortens a log. So the branch is held here at
    MaxElections = 3, the cfg's other constants, on a directed path of 23
    steps whose last one truncates, every state of it against the
    oracle."""
    from raft_tpu.models.pull_raft import PullRaftModel, PullRaftParams
    from raft_tpu.oracle.pull_oracle import PullRaftOracle

    assert len(walked["LearnOfLeader"]) > 500
    assert not any(_truncates(p) for p in walked["LearnOfLeader"])
    oracle = PullRaftOracle(3, 2, 3, 0, variant2=True)
    st, path = oracle.init_state(), []

    def step(prefix, holds=lambda s: True):
        nonlocal st
        for label, nxt in oracle.successors(st):
            if label.startswith(prefix) and holds(nxt):
                path.append(st)
                st = nxt
                return
        raise AssertionError(f"no successor matching {prefix!r}")

    def election(i, voter, term):
        step(f"RequestVote({i})")
        step(f"UpdateTerm[{voter}]", lambda s: s["currentTerm"][voter] == term)
        step("HandleRequestVoteRequest", lambda s: s["votedFor"][voter] == i)
        step("HandleRequestVoteResponse",
             lambda s: voter in s["votesGranted"][i])
        step(f"BecomeLeader({i})")

    election(0, 1, 2)
    step("ClientRequest(0,0)")  # an entry of term 2 only server 0 holds
    step("UpdateTerm[2]")
    election(2, 1, 3)
    step("ClientRequest(2,1)")
    step("LearnOfLeader", lambda s: s["leader"][1] == 2)
    step("SendPullEntriesRequest(1,2)")
    step("AcceptPullEntriesRequest")  # server 1 holds the entry of term 3
    step("HandleSuccessPullEntriesResponse")
    election(1, 0, 4)  # server 0 votes: (1, 3) beats its (1, 2)
    assert st["votesLastEntry"][1][0] == (1, 2)
    before = st
    step("LearnOfLeader", lambda s: s["leader"][0] == 1)
    assert len(path) == 23
    assert (before["log"][0], st["log"][0]) == (((2, 0),), ())
    model = PullRaftModel(PullRaftParams(
        3, 2, 3, 0, msg_slots=64, variant2=True))
    assert model.layout.W == 289
    _assert_successors_equal(model, oracle, [*path, st])


def test_canon_is_brute_force_over_6_permutations_of_the_oracle(
        setup, walked):
    """The engine's canonical fingerprint of a state is the least, over
    all 6 server permutations, of the plain view hash of the oracle's
    permuted state as the model encodes it; two states that differ in
    `acked` alone, which the variant's view drops, are one."""
    from raft_tpu.ops.symmetry import Canonicalizer

    model, oracle = setup.model, oracle_for_setup(setup)
    canon = Canonicalizer.for_model(model, symmetry=True)
    states = [st for name in sorted(walked)
              for st, _ in walked[name][:: max(1, len(walked[name]) // 3)][:3]]
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
    st = next(s for s in states if s["acked"][0] is False)
    other = dict(st, acked=(True,) + tuple(st["acked"][1:]))
    assert oracle.canon(st, True) == oracle.canon(other, True)
    pair = np.stack([model.encode(st), model.encode(other)]).astype(np.int32)
    assert not np.array_equal(pair[0], pair[1])
    fps = np.asarray(canon.fingerprints(pair))
    assert fps[0] == fps[1]


@pytest.fixture(scope="module")
def device_run(cell):
    """The benchmark's copy of the cfg, built as the adapter builds it,
    at the cell's chunk and bag width, to the depth tier-1 affords (a
    frontier of 2^15 rows holds it: 3 x 6,301 new rows < 2^15)."""
    from benchmark import adapter

    eng = adapter.build_engine(
        BENCH_CFG, cell["engine"],
        dict(cell["engine_params"], frontier_cap=1 << 15), None)
    return eng, eng.run(max_depth=DEPTH, collect_metrics=True)


def test_device_bfs_at_the_cells_chunk_counts_the_golden_to_depth_14(
        device_run):
    eng, res = device_run
    smoke = _load(SMOKE_GOLDEN)["depth_limited"]
    assert (eng.chunk, eng.VC, eng.A, eng.W) == (2048, 32768, 85, 289)
    assert res.violation is None and res.exit_cause == "max_depth"
    assert [int(x) for x in res.depth_counts] == smoke["depth_counts"]
    assert (res.distinct, res.total, res.terminal) == (
        smoke["distinct"], smoke["total"], smoke["terminal"])
    assert res.distinct == 16221
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
    assert res.stats["frontier_peak_rows"] == max(smoke["depth_counts"])
    assert res.stats["restart_fired"] == 0


def test_golden_is_the_whole_space_and_the_smoke_file_its_prefix(golden):
    """benchmark/goldens/pullv2.json is the pooled oracle run's record to
    depth 40: the last new state is at depth 39, wave 40 finds none, and
    the totals are pinned at the cell's depth, the smoke's and the
    exhausted end. tests/golden/pullv2_cfg_depth_counts.json, what
    chip_smoke.py's leg J and this file's engine are held to, is its
    prefix."""
    assert golden["config"] == "pullv2" and golden["msg_slots"] == 64
    assert golden["independent_to_depth"] == BOUND + 1
    assert "scripts/oracle_golden.py" in golden["command"]
    assert "benchmark/configs/pullv2/PullRaftVariant2.cfg" in golden["command"]
    counts = golden["depth_counts"]
    assert len(counts) == BOUND + 1 and counts[-1] == 504
    assert sum(counts) == golden["exhausted"]["distinct"] == 1454442
    assert golden["exhausted"]["depth"] == BOUND
    assert golden["totals"][str(BOUND)] == {
        "total": 3993435, "terminal": 14140}
    assert golden["totals"][str(BOUND + 1)] == {
        "total": 3993435, "terminal": 14644}
    assert golden["exhausted"]["total"] == 3993435
    assert golden["exhausted"]["terminal"] == 14644 == 14140 + counts[-1]
    smoke = _load(SMOKE_GOLDEN)["depth_limited"]
    depth = smoke["max_depth"]
    assert depth == DEPTH and smoke["msg_slots"] == 64
    assert smoke["depth_counts"] == counts[: depth + 1]
    assert smoke["distinct"] == sum(smoke["depth_counts"])
    assert {k: smoke[k] for k in ("total", "terminal")} == golden[
        "totals"][str(depth)]


def test_cell_files_numbers_follow_from_the_golden(golden, cell):
    """What benchmark/workloads/pullv2-full.json says of its job: the
    bound is the depth of the last new state, the chunk-steps by wave and
    by the size of the seen run they met, that no step searches, the
    capacity the growth rule leaves alone, the metrics it reports."""
    from raft_tpu.checker.device_bfs import DeviceBFS

    traffic = _load(BENCH, "traffic", f"{cell['traffic']}.json")
    assert (cell["config"], cell["traffic"], cell["chips"], cell["engine"]) == (
        "pullv2", "init-d39-warm39", 1, "device")
    assert traffic["warmup_depth"] == traffic["max_depth"] == BOUND
    assert (traffic["mode"], traffic["from"]) == ("bfs", "Init")
    counts = golden["depth_counts"]
    assert len(counts) - 1 == BOUND == golden["exhausted"]["depth"]
    chunk = cell["engine_params"]["chunk"]
    # wave d expands depth d - 1's new states in chunks of 2,048 rows
    steps = [-(-n // chunk) for n in counts[:BOUND]]
    wide = sum(s for s in steps if s > 1)
    assert (sum(steps), wide, sum(s > 1 for s in steps)) == (735, 723, 27)
    distinct = list(itertools.accumulate(counts))
    sizes = (1 << 18, 1 << 20, 1 << 22)
    # wave k meets the run that holds the states of depths 0 to k - 1
    met = [next(s for s in sizes if n <= s) for n in distinct[:BOUND]]
    assert met == [1 << 18] * 23 + [1 << 20] * 8 + [1 << 22] * 8
    by_size = {s: sum(n for n, m in zip(steps, met) if m == s)
               for s in sizes}
    assert by_size == {1 << 18: 143, 1 << 20: 380, 1 << 22: 212}
    assert (distinct[22], distinct[23]) == (257069, 324793)
    assert (distinct[30], distinct[31]) == (1026194, 1127846)
    assert steps[31:] == [50, 46, 40, 32, 23, 13, 6, 2]
    # the 2^22-lane run is merged at a rung that holds its content and
    # the widest wave it meets: nothing is searched
    vc = chunk * 16
    assert distinct[-1] + max(counts[32:]) <= 3 << 19
    assert util.merges(3 << 19, vc) and not util.merges(1 << 22, vc)
    job = cell["job"]
    for n in (sum(counts), golden["totals"][str(BOUND)]["total"],
              golden["totals"][str(BOUND)]["terminal"], 735, 723, 143, 380,
              212):
        assert f"{n:,}" in job, n
    # the growth rule runs after every wave but the last; the journal
    # holds the verdict and the widest wave's headroom
    fcap = cell["engine_params"]["frontier_cap"]
    widest = max(counts)
    assert widest == 110640 == counts[29]
    assert fcap // 2 < DeviceBFS.HEADROOM * widest <= fcap == 1 << 19
    assert sum(counts) + DeviceBFS.HEADROOM * widest < 1 << 22
    assert cell["end_to_end"] == ["setup_s", "states_per_s"]
    pull3 = _load(BENCH, "workloads", "pull3-full.json")["per_layer"]
    assert len(pull3) == 20
    # the three hbm_* metrics name their cells in their own files, and
    # benchmark/tests/test_memory_metrics.py holds those lists equal to
    # the cells that report them: a cell that lists one breaks that
    # test, so this one carries the keys in its `stats` only
    assert cell["per_layer"] == [
        *pull3, "dedup_sort_lanes", "frontier_peak_rows", "emit_append_share",
        "expand_handlemessage_share"]
    spec = _load(BENCH, "layer_metrics", "expand_handlemessage_share.json")
    assert spec["workloads"] == ["pull3-full", "pullv2-full"]
    bench = _load(ROOT, "BENCHMARK.json")
    # the tenth cell and the thirty-fourth metric, wherever later PRs'
    # entries come to stand behind them
    assert bench["workloads"][9]["name"] == "pullv2-full"
    assert bench["per_layer"][33]["name"] == "expand_handlemessage_share"
    upto = [w["name"] for w in bench["workloads"][:10]]
    for entry in bench["per_layer"]:
        if entry["name"] in cell["per_layer"] and "workloads" in entry:
            assert [c for c in entry["workloads"] if c in upto][-1] == (
                "pullv2-full"), entry["name"]


def test_handlemessage_has_a_scope_of_its_own_that_the_new_metric_reads(
        device_run):
    """`expand/HandleMessage`: the fused receipt kernel's segment slice,
    row gather and kernel are under the group's name in the wave program
    this cell runs, and `expand_handlemessage_share`'s pattern reads an
    op named from that stack and none of another group's."""
    from benchmark import xplane

    eng, _res = device_run
    (prog,) = [p for p in eng.audit_programs() if p["name"] == "wave"]
    stacks = {str(e.source_info.name_stack)
              for e in eqns(jax.make_jaxpr(prog["fn"])(*prog["args"]).jaxpr)}
    for name, _n in GROUPS:
        assert any(s.startswith(f"expand/{name}/while/body/vmap()")
                   for s in stacks), name
    rx = re.compile(_load(
        BENCH, "layer_metrics", "expand_handlemessage_share.json")[
            "reduce"]["regex"])
    stack = ("jit(_wave_step)/while/body/expand/{}/while/body/vmap()/"
             "select_n:")
    named = lambda g: xplane.scoped_name(
        stack.format(g), "%fusion.7 = fusion()")
    assert rx.search(named("HandleMessage"))
    assert not any(rx.search(named(g)) for g, _n in GROUPS[:-1])
