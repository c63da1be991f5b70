"""fsync3: upstream's raft-and-fsync/RaftFsync.cfg (Raft with an explicit
fsyncIndex: a crash cuts a server's log to it; three fsync policy
constants, as upstream publishes them: FALSE, TRUE, TRUE; 3 servers, 1
value, MaxElections 2, MaxRestarts 0, 6 permutations), at the registry's
own bag width, against the pure-Python oracle: 192-lane rows, 78
candidate actions a state in 9 kernel groups.

The cfg parses strictly as it stands, so the benchmark's copy is the
tree's, byte for byte. Both are reconstructed (the header says from what,
and which constant is assumed). One DeviceBFS verdict of the benchmark's
copy to depth 12 (as the adapter builds it, at the cell's chunk and bag
width) serves every test of the engine here; its counts are the pooled
oracle run's.

At upstream's MaxRestarts = 0 no server crashes, so the Restart kernel
(its fsync arm cuts the log to fsyncIndex) runs at its budget and keeps
nothing. It is held here on the same file with MaxRestarts raised to 1,
which is no published deployment and has no cell: the walked states, a
scripted crash that loses an entry, and a DeviceBFS verdict to depth 10
against the oracle's own counts of that variant.
"""

import filecmp
import itertools
import json
import os
import random
import re
import subprocess
import sys

import jax
import numpy as np
import pytest

from raft_tpu.models.registry import build_from_cfg, oracle_for_setup
from raft_tpu.utils.cfg import parse_cfg

from conftest import collect_states, eqns, scatter_kernels

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = os.path.join(ROOT, "configs", "raft-and-fsync", "RaftFsync.cfg")
RAFT_CFG = os.path.join(ROOT, "configs", "standard-raft", "Raft.cfg")
BENCH = os.path.join(ROOT, "benchmark")
BENCH_CFG = os.path.join(BENCH, "configs", "fsync3", "RaftFsync.cfg")
# twelve one-chunk waves at the cell's chunk (20 s on the CPU, compile
# included)
DEPTH = 12
# the variant with one crash: the oracle's per-depth counts, total and
# terminal at depth 10 (scripts/oracle_golden.py on the file with
# MaxRestarts = 1, PR 51's first run, to depth 21)
CRASH_DEPTH = 10
CRASH_COUNTS = [1, 1, 4, 9, 23, 48, 95, 168, 283, 480, 876]
CRASH_TOTALS = {"total": 5432, "terminal": 7}
INVARIANTS = ("LeaderHasAllAckedValues", "NoLogDivergence")
GROUPS = [
    ("Restart", 3), ("Timeout", 3), ("RequestVotePair", 6),
    ("BecomeLeader", 3), ("ClientRequest", 3), ("AdvanceCommitIndex", 3),
    ("AppendEntries", 6), ("AdvanceFsyncIndex", 3), ("HandleMessage", 48),
]
# a leader that appended a value it has not fsynced crashes: the log is
# longer than fsyncIndex, so Restart loses the entry (RaftFsync.tla:211-216)
LOSSY_CRASH = (
    "Timeout(0)", "RequestVote(0,1)", "UpdateTerm", "HandleRequestVoteRequest",
    "HandleRequestVoteResponse", "BecomeLeader(0)", "ClientRequest(0,0)",
    "Restart(0)",
)
# a voter that timed out after granting its vote is a term ahead of the
# leader it elected, and rejects that leader's AppendEntries
REJECTED = (
    "Timeout(0)", "RequestVote(0,1)", "UpdateTerm", "HandleRequestVoteRequest",
    "Timeout(1)", "HandleRequestVoteResponse", "BecomeLeader(0)",
    "AppendEntries(0,1)", "RejectAppendEntriesRequest",
)


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def action(label: str) -> str:
    """``Restart(0)``, ``UpdateTerm[1]``: the action's name."""
    return re.split(r"[(\[]", label)[0]


@pytest.fixture(scope="module")
def setup():
    # strict parsing, the registry's own bag width: the adapter's path
    return build_from_cfg(parse_cfg(BENCH_CFG))


@pytest.fixture(scope="module")
def crash_setup(tmp_path_factory):
    """The same file with MaxRestarts = 1 (the spec is named by the
    file, so the copy keeps the name)."""
    with open(CFG) as f:
        text = f.read()
    assert text.count("MaxRestarts = 0\n") == 1
    path = tmp_path_factory.mktemp("crash") / "RaftFsync.cfg"
    path.write_text(text.replace("MaxRestarts = 0\n", "MaxRestarts = 1\n"))
    return build_from_cfg(parse_cfg(str(path)))


@pytest.fixture(scope="module")
def oracle(crash_setup):
    return oracle_for_setup(crash_setup)


@pytest.fixture(scope="module")
def golden():
    return load(BENCH, "goldens", "fsync3.json")


@pytest.fixture(scope="module")
def walked(oracle):
    """(full states, the action names enabled on the way, the state the
    lossy crash starts from): the first 120 states in BFS order, 24
    seeded random walks of 30 steps, and the two scripted walks."""
    states = {oracle.serialize_full(st): st
              for st in collect_states(oracle, max_depth=6, cap=120)}
    rng = random.Random(51)
    names = set()

    def walk(choose, steps):
        st = prev = oracle.init_state()
        for step in range(steps):
            succs = oracle.successors(st)
            if not succs:
                break
            names.update(action(label) for label, _s2 in succs)
            prev, st = st, choose(step, succs)
            states[oracle.serialize_full(st)] = st
        return prev

    for _walk in range(24):
        walk(lambda _step, succs: rng.choice(succs)[1], 30)
    before_crash = None
    for script in (LOSSY_CRASH, REJECTED):
        last = walk(lambda step, succs, _s=script: next(
            s2 for label, s2 in succs if label.startswith(_s[step])),
            len(script))
        before_crash = before_crash or last
    return list(states.values()), names, before_crash


@pytest.fixture(scope="module")
def device_run(tmp_path_factory):
    """(engine, result, the events as the telemetry wrote them, the
    metrics file): one verdict to depth 12."""
    from benchmark import adapter
    from raft_tpu.obs import Telemetry

    path = str(tmp_path_factory.mktemp("fsync3") / "metrics.jsonl")
    cell = load(BENCH, "workloads", "fsync3-wide.json")
    assert cell["engine_params"] == {
        "chunk": 2048, "msg_slots": 48, "frontier_cap": 1 << 19}
    eng = adapter.build_engine(
        BENCH_CFG, cell["engine"],
        dict(cell["engine_params"], frontier_cap=1 << 15), None)
    with Telemetry(metrics_path=path) as tel:
        res = eng.run(max_depth=DEPTH, collect_metrics=True, telemetry=tel)
    with open(path) as f:
        events = [json.loads(line) for line in f]
    return eng, res, events, path


def test_tree_cfg_parses_strictly_and_builds_the_published_policy():
    """No --lenient, no --msg-slots: three boolean constants through
    ``_require_bool``, the row and the kernel groups the cell is named
    for, and the CLI takes the file as it stands."""
    from raft_tpu.__main__ import main

    cfg = parse_cfg(CFG)
    assert not cfg.diagnostics
    setup = build_from_cfg(cfg)
    p = setup.model.p
    assert setup.model.name == "RaftFsync"
    assert (p.n_servers, p.n_values) == (3, 1)
    assert p.has_fsync and p.strict_send_once and p.trunc_term_mismatch
    assert not p.has_pending_response
    # RaftFsync.cfg:24-26, as SURVEY.md records them
    assert (p.fsync_leader_before_ae, p.fsync_leader_quorum,
            p.fsync_follower_reply) == (False, True, True)
    # upstream's, as tests/test_raft_fsync.py recorded them of its file
    assert (p.max_elections, p.max_restarts) == (2, 0)
    assert p.msg_slots == 48  # the registry's own
    assert setup.symmetry and setup.invariants == INVARIANTS
    model = setup.model
    assert (model.layout.W, model.A) == (192, 78)
    assert [(g.name, g.n) for g in model.sparse_groups()] == GROUPS
    assert len(model.ACTION_NAMES) == 14
    assert main([CFG, "--checker", "tpu", "--chunk", "256",
                 "--max-depth", "2"]) == 0


def test_the_benchmarks_cfg_is_the_trees_byte_for_byte(setup, device_run):
    """The cfg needs no repair, so the copy the adapter parses is the
    file a CLI user runs, and the adapter's engine has the identity
    string of the CLI's."""
    from benchmark import adapter
    from raft_tpu.checker.device_bfs import DeviceBFS
    from raft_tpu.ops.symmetry import Canonicalizer

    assert filecmp.cmp(CFG, BENCH_CFG, shallow=False)
    config = load(BENCH, "configs", "fsync3", "config.json")
    assert config["cfg"] == "RaftFsync.cfg" and "byte-equal" in config[
        "cfg_note"]
    assumed = config["assumed"]
    assert {k: assumed[k] for k in (
        "Value", "msg_slots", "chunk", "row_lanes")} == {
        "Value": 1, "msg_slots": 48, "chunk": 2048, "row_lanes": 192}
    # recorded of upstream's file, so not assumed
    assert not {"MaxElections", "MaxRestarts"} & set(assumed)
    assert config["reduced"] == ["max_depth"]
    p = setup.model.p
    consts = config["constants"]
    assert (consts["LeaderFsyncBeforeAppendEntries"],
            consts["LeaderFsyncBeforeIncludeInQuorum"],
            consts["FollowerFsyncBeforeReply"]) == (
        p.fsync_leader_before_ae, p.fsync_leader_quorum,
        p.fsync_follower_reply)
    assert (consts["MaxElections"], consts["MaxRestarts"]) == (
        p.max_elections, p.max_restarts) == (2, 0)
    canon = Canonicalizer.for_model(setup.model, symmetry=True)
    assert canon.P == 6
    cli = DeviceBFS(setup.model, invariants=setup.invariants,
                    symmetry=setup.symmetry, chunk=2048,
                    frontier_cap=1 << 15)
    assert adapter.ident(device_run[0]) == cli._ckpt_ident()
    assert "RaftFsync/" in cli._ckpt_ident()


def test_successor_sets_match_oracle_on_walked_states(
        setup, crash_setup, oracle, walked):
    """With one crash allowed, the lowering's successors of every walked
    state are the oracle's, and the walks enable every one of the spec's
    14 actions: a Restart that loses an entry its server had not fsynced
    among them. At the cfg's own MaxRestarts = 0 the same states have the
    same successors but the crashes."""
    from raft_tpu.models.raft import ACTION_NAMES

    states, names, before_crash = walked
    assert names == set(ACTION_NAMES) and len(names) == 14
    assert len(before_crash["log"][0]) == 1 > before_crash["fsyncIndex"][0]
    crashed = dict(oracle.successors(before_crash))["Restart(0)"]
    assert crashed["log"][0] == () and crashed["restartCtr"] == 1

    model = crash_setup.model
    assert (model.p.max_restarts, model.layout.W, model.A) == (1, 192, 78)
    vecs = np.stack([model.encode(st) for st in states])
    succs, valid, rank, ovf = jax.device_get(model.expand(vecs))
    assert not np.any(valid & ovf)
    # the published cfg on the same rows (restartCtr 0 of 0 enables no
    # Restart; 1 of 0 is unreachable there and left out)
    fresh = np.array([st["restartCtr"] == 0 for st in states])
    _s0, valid0, _r0, _o0 = jax.device_get(setup.model.expand(vecs[fresh]))
    crash = rank[fresh] == model.ACTION_NAMES.index("Restart")
    assert np.any(valid[fresh] & crash)
    assert np.array_equal(valid0, valid[fresh] & ~crash)
    for b, st in enumerate(states):
        got = sorted(
            oracle.serialize_full(model.decode(succs[b, a]))
            for a in range(model.A)
            if valid[b, a]
        )
        want = sorted(
            oracle.serialize_full(s2) for _l, s2 in oracle.successors(st))
        assert got == want, f"successor mismatch at state {b}"
    # the crash itself, through the lowering: binding 0 is Restart(0)
    row = jax.device_get(model.expand(model.encode(before_crash)[None]))
    assert row[1][0, 0]
    assert oracle.serialize_full(model.decode(row[0][0, 0])) == (
        oracle.serialize_full(crashed))


def test_no_kernel_writes_through_a_dynamic_index_scatter(setup):
    """Under has_fsync too (tests/test_expand_sparse.py walks the family
    at small sizes): every write is a one-hot select, the form the v5e's
    compiler keeps (PR 30). Nothing is compiled."""
    assert scatter_kernels(setup.model) == {}


def test_device_bfs_equals_the_goldens_prefix_to_depth_12(device_run, golden):
    eng, res, _events, _path = device_run
    assert (eng.chunk, eng.VC, eng.A, eng.W) == (2048, 32768, 78, 192)
    want = golden["depth_counts"][: DEPTH + 1]
    assert res.violation is None and res.exit_cause == "max_depth"
    assert [int(x) for x in res.depth_counts] == want
    assert res.distinct == sum(want) == 3936
    assert {"total": res.total, "terminal": res.terminal} == golden[
        "totals"][str(DEPTH)] == {"total": 8724, "terminal": 0}
    rows = res.metrics
    assert [w["depth"] for w in rows] == list(range(1, DEPTH + 1))
    assert not any(w["overflow_bits"] for w in rows)
    # the groups' budgets hold what the cfg keeps: nothing starved
    assert sum(eng.model.sparse_plan(eng.chunk, eng.VC, None)) == 94208


def test_golden_is_the_oracles_record_and_the_smokes_prefix(golden):
    """benchmark/goldens/fsync3.json, the pooled oracle run's record at
    upstream's constants: at depth 18 it is the count ISSUE 51 gives for
    MaxRestarts = 0 (152,790 distinct, 0 terminal), it pins the depths
    the traffic mix names, and what chip_smoke.py's leg I holds the CLI
    to is its prefix."""
    assert golden["msg_slots"] == 48
    assert golden["independent_to_depth"] >= 20
    assert golden["depth_counts"][:21] == [
        1, 1, 3, 6, 15, 28, 52, 85, 143, 246, 469, 944, 1943, 3884, 7398,
        13369, 23094, 38518, 62591, 99622, 155672]
    assert sum(golden["depth_counts"][:19]) == 152790
    assert sum(golden["depth_counts"][:21]) == 408084
    for depth, total, terminal in (
            (12, 8724, 0), (14, 37966, 0), (18, 459445, 0),
            (20, 1274344, 14)):
        assert golden["totals"][str(depth)] == {
            "total": total, "terminal": terminal}
    traffic = load(BENCH, "traffic", "init-d20-warm20.json")
    for depth in (traffic["warmup_depth"], traffic["max_depth"]):
        assert str(depth) in golden["totals"]
        assert len(golden["depth_counts"]) > depth
    smoke = load(ROOT, "tests", "golden",
                 "fsync3_cfg_depth_counts.json")["depth_limited"]
    depth = smoke["max_depth"]
    assert smoke["depth_counts"] == golden["depth_counts"][: depth + 1]
    assert smoke["distinct"] == sum(smoke["depth_counts"])
    assert {k: smoke[k] for k in ("total", "terminal")} == golden[
        "totals"][str(depth)]


def test_cell_files_numbers_follow_from_the_golden(golden):
    """What benchmark/workloads/fsync3-wide.json says of its job: the
    chunk-steps by wave, where the seen run leaves its first size, the
    capacity the growth rule leaves alone, the metrics it reports."""
    from raft_tpu.checker.device_bfs import DeviceBFS

    cell = load(BENCH, "workloads", "fsync3-wide.json")
    traffic = load(BENCH, "traffic", f"{cell['traffic']}.json")
    depth, chunk = traffic["max_depth"], cell["engine_params"]["chunk"]
    assert (depth, traffic["warmup_depth"], chunk) == (20, 20, 2048)
    counts = golden["depth_counts"][: depth + 1]
    # wave d expands depth d - 1's rows
    steps = [-(-n // chunk) for n in counts[:-1]]
    assert steps == [1] * 13 + [2, 4, 7, 12, 19, 31, 49]
    assert sum(steps) == 137 and sum(s for s in steps if s > 1) == 124
    # the seen run's first size holds the distinct states of every wave
    # but the last, whose merge steps it up
    first, second = 1 << 18, 1 << 20
    distinct = list(itertools.accumulate(counts))
    assert distinct[19] <= first < distinct[20] <= second
    job = cell["job"]
    for n in (sum(counts), golden["totals"]["20"]["total"],
              golden["totals"]["20"]["terminal"], 137, 124):
        assert f"{n:,}" in job, n
    # the growth rule runs after every wave but the last
    fcap = cell["engine_params"]["frontier_cap"]
    widest_seen = max(counts[1:-1])
    assert fcap // 2 < DeviceBFS.HEADROOM * widest_seen <= fcap == 1 << 19
    assert DeviceBFS.HEADROOM * max(counts[1:-2]) <= fcap // 2
    wide = load(BENCH, "workloads", "kraftrc3-wide.json")["per_layer"]
    assert cell["per_layer"] == [
        *wide, "dedup_sort_lanes", "emit_append_share", "frontier_peak_rows"]
    for name in ("expand_restart_share", "restart_fired"):
        assert load(BENCH, "layer_metrics", f"{name}.json")[
            "workloads"] == ["fsync3-wide"]


def test_restart_fired_is_the_coverage_blocks_and_is_declared(device_run):
    """How many of the run's successors are a crash: the `fired` column
    of the coverage block the run fetched, summed over the actions the
    model declares as crashes, on the result's stats and on the summary,
    in the schema, and refused by it when it is no count. No server
    crashes under the published cfg, and the Restart group is built all
    the same."""
    from raft_tpu.obs.events import SUMMARY_KEYS

    eng, res, events, path = device_run
    assert eng.model.CRASH_ACTIONS == ("Restart",)
    restart = res.coverage[eng.model.ACTION_NAMES.index("Restart")]
    assert [int(x) for x in restart] == [0, 0, 0]
    assert res.stats["restart_fired"] == 0
    assert eng.model.sparse_plan(eng.chunk, eng.VC, None)[0] == 6144
    (summary,) = [ev for ev in events if ev["event"] == "summary"]
    assert summary["restart_fired"] == 0
    assert "restart_fired" in SUMMARY_KEYS
    script = os.path.join(ROOT, "scripts", "check_metrics_schema.py")
    ok = subprocess.run([sys.executable, script, path],
                        capture_output=True, text=True)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    bad_path = f"{path}.bad"
    with open(bad_path, "w") as f:
        for ev in events:
            if ev["event"] == "summary":
                ev = dict(ev, restart_fired=-1)
            f.write(json.dumps(ev) + "\n")
    bad = subprocess.run([sys.executable, script, bad_path],
                         capture_output=True, text=True)
    assert bad.returncode != 0
    assert "restart_fired" in bad.stdout + bad.stderr


def test_one_crash_through_the_wave_program_equals_the_oracle(
        crash_setup, oracle):
    """The variant with MaxRestarts = 1 through DeviceBFS at the cell's
    chunk: per-depth counts, total and terminal are the oracle's, and
    `restart_fired` is the number of Restart successors the oracle
    generates from the same states."""
    from raft_tpu.checker.device_bfs import DeviceBFS

    eng = DeviceBFS(crash_setup.model, invariants=crash_setup.invariants,
                    symmetry=crash_setup.symmetry, chunk=2048,
                    frontier_cap=1 << 13)
    res = eng.run(max_depth=CRASH_DEPTH, collect_metrics=True)
    assert res.violation is None and res.exit_cause == "max_depth"
    assert [int(x) for x in res.depth_counts] == CRASH_COUNTS
    assert {"total": res.total, "terminal": res.terminal} == CRASH_TOTALS
    assert not any(w["overflow_bits"] for w in res.metrics)
    # the oracle's own walk of the same levels, one state a class
    seen = {oracle.canon(oracle.init_state(), crash_setup.symmetry)}
    level, crashes = [oracle.init_state()], 0
    for _depth in range(CRASH_DEPTH):
        nxt = []
        for st in level:
            for label, s2 in oracle.successors(st):
                crashes += action(label) == "Restart"
                key = oracle.canon(s2, crash_setup.symmetry)
                if key not in seen:
                    seen.add(key)
                    nxt.append(s2)
        level = nxt
    assert len(seen) == sum(CRASH_COUNTS)
    assert res.stats["restart_fired"] == crashes > 0


@pytest.mark.parametrize("checker", [
    ("tpu",), ("tpu-host",), ("sharded", "--devices", "2")],
    ids=lambda c: c[0])
def test_no_restart_fires_on_raft_cfg(checker, capsys):
    """Raft.cfg sets MaxRestarts = 0 too: the counter reads 0, and the
    three engines say it alike."""
    from raft_tpu.__main__ import main

    assert main([RAFT_CFG, "--checker", *checker, "--chunk", "256",
                 "--msg-slots", "24", "--max-depth", "5", "--json"]) == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert (summary["distinct"], summary["total"]) == (63, 120)
    assert summary["restart_fired"] == 0


def test_each_group_has_a_scope_of_its_own_under_expand(device_run):
    """`expand/Restart`, `expand/HandleMessage`, ...: a group's segment
    slice, row gather, parameter selects and kernels are under the
    group's name, opened outside the kernels' vmap and, since PR 56,
    the loop over the tiles of what the group keeps, and the benchmark's
    rule reads it as the stage's second level, which is what
    `expand_restart_share` and `stage_split.py`'s `expand_by_group_s`
    sum."""
    from benchmark import xplane

    eng, _res, _events, _path = device_run
    (prog,) = [p for p in eng.audit_programs() if p["name"] == "wave"]
    found = {}
    for e in eqns(jax.make_jaxpr(prog["fn"])(*prog["args"]).jaxpr):
        found.setdefault(str(e.source_info.name_stack), set()).add(
            e.primitive.name)
    for name, _n in GROUPS:
        under = set().union(*(
            prims for stack, prims in found.items()
            if stack.startswith(f"expand/{name}")))
        assert {"dynamic_slice", "gather"} <= under, name
        # the kernels run inside the scope, not beside it
        assert any(stack.startswith(f"expand/{name}/while/body/vmap()")
                   for stack in found), name
    assert not any(stack.startswith("expand/vmap()/Restart")
                   for stack in found)
    stack = ("jit(_wave_step)/while/body/expand/Restart/while/body/vmap()/"
             "select_n:")
    assert xplane.scope_path(stack) == ("expand", "Restart")
    spec = load(BENCH, "layer_metrics", "expand_restart_share.json")
    assert re.match(spec["reduce"]["regex"],
                    xplane.scoped_name(stack, "%fusion.7 = fusion()"))
