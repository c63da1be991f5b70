"""addremove4: upstream's four-server RaftWithReconfigAddRemove.cfg (the
thesis's one-at-a-time membership change: 4 servers, 1 value,
InitClusterSize 3, MinClusterSize 2, one add and one remove, the thesis
bug off, 24 permutations), at the published constants and the registry's
own bag width, against the pure-Python oracle: 735-lane rows, 192
candidate actions a state.

Upstream's file omits MaxClusterSize, which the spec requires. The cfg in
the tree keeps the omission (strict mode refuses it by name, --lenient
sets it to |Server| and says so); the benchmark's copy carries that one
line, because its adapter parses strictly. Both are reconstructed (the
header says from what). One DeviceBFS verdict of the benchmark's copy to
depth 9 (as the adapter builds it, at the cell's chunk) serves every test
of the engine here; its counts are the pooled oracle run's.
"""

import difflib
import itertools
import json
import os
import random
import subprocess
import sys

import jax
import numpy as np
import pytest

from raft_tpu.models.registry import build_from_cfg, oracle_for_setup
from raft_tpu.utils.cfg import CfgError, parse_cfg

from conftest import collect_states, eqns, scatter_kernels

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = os.path.join(
    ROOT, "configs", "standard-raft", "RaftWithReconfigAddRemove.cfg")
BENCH = os.path.join(ROOT, "benchmark")
BENCH_CFG = os.path.join(
    BENCH, "configs", "addremove4", "RaftWithReconfigAddRemove.cfg")
DEPTH = 9
# the cell's chunk, a 16,384-lane worklist: depth 9 has six one-chunk
# waves, then waves of 2, 3 and 5 chunks (25 s on the CPU, compile
# included)
CHUNK = 1024
INVARIANTS = (
    "LeaderHasAllAckedValues",
    "NoLogDivergence",
    "MaxOneReconfigurationAtATime",
)


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def setup():
    # strict parsing, the registry's own bag width: the adapter's path
    return build_from_cfg(parse_cfg(BENCH_CFG))


@pytest.fixture(scope="module")
def oracle(setup):
    return oracle_for_setup(setup)


@pytest.fixture(scope="module")
def golden():
    return load(BENCH, "goldens", "addremove4.json")


# the leader removes itself: the command, its replication to both other
# members (the new configuration's quorum), and the commit that makes the
# leader leave its own cluster (_commit_removed)
SELF_REMOVAL = (
    "AppendRemoveServerCommandToLog(0,0)",
    "AppendEntries(0,1)", "AcceptAppendEntriesRequest",
    "HandleAppendEntriesResponse",
    "AppendEntries(0,2)", "AcceptAppendEntriesRequest",
    "HandleAppendEntriesResponse",
    "AdvanceCommitIndex(0)",
)


@pytest.fixture(scope="module")
def walked(oracle):
    """(full states, the action names enabled on the way): the first 120
    states in BFS order, 24 seeded random walks of 30 steps (which reach
    both reconfiguration commands, a snapshot to the joiner and a reset
    of the server that was never a member), and the eight steps of a
    leader's own removal."""
    states = {oracle.serialize_full(st): st
              for st in collect_states(oracle, max_depth=5, cap=120)}
    rng = random.Random(47)
    names = set()

    def walk(choose, steps):
        st = oracle.init_state()
        for step in range(steps):
            succs = oracle.successors(st)
            if not succs:
                break
            names.update(label.split("(")[0] for label, _s2 in succs)
            st = choose(step, succs)
            states[oracle.serialize_full(st)] = st

    for _walk in range(24):
        walk(lambda _step, succs: rng.choice(succs)[1], 30)
    walk(lambda step, succs: next(
        s2 for label, s2 in succs if label.startswith(SELF_REMOVAL[step])),
        len(SELF_REMOVAL))
    return list(states.values()), names


@pytest.fixture(scope="module")
def device_run(tmp_path_factory):
    """(engine, result, the events as the telemetry wrote them, the
    metrics file): one verdict to depth 9."""
    from benchmark import adapter
    from raft_tpu.obs import Telemetry

    path = str(tmp_path_factory.mktemp("addremove4") / "metrics.jsonl")
    cell = load(BENCH, "workloads", "addremove4-wide.json")
    assert cell["engine_params"] == {
        "chunk": 1024, "msg_slots": 112, "frontier_cap": 1 << 20}
    eng = adapter.build_engine(
        BENCH_CFG, cell["engine"],
        dict(cell["engine_params"], chunk=CHUNK, frontier_cap=1 << 15), None)
    with Telemetry(metrics_path=path) as tel:
        res = eng.run(max_depth=DEPTH, collect_metrics=True, telemetry=tel)
    with open(path) as f:
        events = [json.loads(line) for line in f]
    return eng, res, events, path


def test_in_tree_cfg_is_refused_by_name_and_repaired_under_lenient(capsys):
    """SURVEY.md 2.2: "the build's cfg reader must diagnose this". Strict
    mode raises naming the constant and the spec line that requires it;
    --lenient builds the published constants with MaxClusterSize = 4 and
    keeps the diagnostic; the CLI maps the first to exit code 64."""
    from raft_tpu.__main__ import main

    cfg = parse_cfg(CFG)  # parses cleanly: the fault is the builder's to find
    with pytest.raises(CfgError, match=r"MaxClusterSize.*\.tla:88"):
        build_from_cfg(cfg)
    cfg = parse_cfg(CFG, lenient=True)
    setup = build_from_cfg(cfg)
    assert any("MaxClusterSize" in d and "|Server| = 4" in d
               for d in cfg.diagnostics)
    p = setup.model.p
    assert (p.n_servers, p.n_values, p.init_cluster_size) == (4, 1, 3)
    assert (p.min_cluster_size, p.max_cluster_size) == (2, 4)
    assert (p.max_add_reconfigs, p.max_remove_reconfigs) == (1, 1)
    assert not p.include_thesis_bug
    # assumed: tests/test_reconfig_add_remove.py's four-server case
    assert (p.max_elections, p.max_restarts, p.max_values_per_term) == (
        1, 0, 1)
    assert p.msg_slots == 112  # the registry's own
    assert setup.model.name == "RaftWithReconfigAddRemove"
    assert setup.symmetry and setup.invariants == INVARIANTS
    assert main([CFG, "--checker", "tpu", "--max-depth", "1"]) == 64
    assert "MaxClusterSize" in capsys.readouterr().err


def test_the_two_cfg_copies_differ_in_one_line_and_build_one_engine(
        setup, device_run):
    """The benchmark's copy is the tree's with --lenient's repair made
    in the file: one added line, one model, and the adapter's engine has
    the identity string of the CLI's."""
    from benchmark import adapter
    from raft_tpu.checker.device_bfs import DeviceBFS
    from raft_tpu.ops.symmetry import Canonicalizer

    with open(CFG) as f, open(BENCH_CFG) as g:
        tree, bench = f.read().splitlines(), g.read().splitlines()
    changed = [line for line in difflib.ndiff(tree, bench)
               if line[:1] in "+-?"]
    assert changed == ["+     MaxClusterSize = 4"]
    config = load(BENCH, "configs", "addremove4", "config.json")
    assert "MaxClusterSize = 4" in config["cfg_note"]
    assert config["assumed"]["MaxClusterSize"] == 4

    lenient = build_from_cfg(parse_cfg(CFG, lenient=True))
    assert lenient.model.p == setup.model.p
    assert (lenient.invariants, lenient.symmetry) == (
        setup.invariants, setup.symmetry)
    # the row the cell is named for
    assert (setup.model.layout.W, setup.model.A) == (735, 192)
    assert config["assumed"]["row_lanes"] == 735
    canon = Canonicalizer.for_model(setup.model, symmetry=True)
    assert canon.P == 24 and not canon.prune
    cli = DeviceBFS(lenient.model, invariants=lenient.invariants,
                    symmetry=lenient.symmetry, chunk=CHUNK,
                    frontier_cap=1 << 15)
    assert adapter.ident(device_run[0]) == cli._ckpt_ident()
    assert "RaftWithReconfigAddRemove/" in cli._ckpt_ident()


def test_successor_sets_match_oracle_on_walked_states_at_four_servers(
        setup, oracle, walked):
    """The lowering's successors of every walked state are the oracle's,
    and the walk enables every action of the spec but Restart, which
    MaxRestarts = 0 never enables: the two reconfiguration commands,
    the snapshot trio and the reset among them."""
    from raft_tpu.models.reconfig_raft import ACTION_NAMES
    from raft_tpu.oracle.reconfig_oracle import LEADER, NOTMEMBER

    states, names = walked
    assert names == set(ACTION_NAMES) - {"Restart"}
    roles = {tuple(st["state"]) for st in states}
    # the leader that committed its own removal left its cluster
    assert (NOTMEMBER, 0, 0, NOTMEMBER) in roles
    # and the joiner became a member beside a leader
    assert any(LEADER in r and r[3] != NOTMEMBER for r in roles)

    model = setup.model
    vecs = np.stack([model.encode(st) for st in states])
    succs, valid, _rank, ovf = jax.device_get(model.expand(vecs))
    assert not np.any(valid & ovf)
    for b, st in enumerate(states):
        got = sorted(
            oracle.serialize_full(model.decode(succs[b, a]))
            for a in range(model.A)
            if valid[b, a]
        )
        want = sorted(
            oracle.serialize_full(s2) for _l, s2 in oracle.successors(st))
        assert got == want, f"successor mismatch at state {b}"


def test_no_kernel_writes_through_a_dynamic_index_scatter(setup):
    """At the published constants too (tests/test_expand_sparse.py walks
    the family at three servers): every write is a one-hot select, the
    form the v5e's compiler keeps (PR 30). Nothing is compiled."""
    assert scatter_kernels(setup.model) == {}


def test_canon_is_brute_force_over_24_permutations_of_the_oracle(
        setup, oracle, walked):
    """The engine's canonical fingerprint of a state is the least, over
    all 24 server permutations, of the plain view hash of the oracle's
    permuted state as the model encodes it."""
    from raft_tpu.ops.symmetry import Canonicalizer

    model = setup.model
    canon = Canonicalizer.for_model(model, symmetry=True)
    states = walked[0][::8]
    perms = list(itertools.permutations(range(4)))
    assert len(perms) == canon.P
    rows = np.stack([
        model.encode(oracle.permute(st, list(sigma)))
        for st in states for sigma in perms
    ]).astype(np.int32)
    raw = np.asarray(canon.raw_fingerprints(rows)).reshape(
        len(states), len(perms))
    got = np.asarray(canon.fingerprints(rows)).reshape(raw.shape)
    assert np.array_equal(got[:, 0], raw.min(axis=1))
    # the same for every member of the orbit
    assert np.array_equal(got, np.broadcast_to(got[:, :1], got.shape))
    # and two states share a fingerprint only where the oracle's own
    # canonical views are equal
    keys = [oracle.canon(st, True) for st in states]
    assert len(set(keys)) == len(set(got[:, 0].tolist()))


def test_device_bfs_equals_the_goldens_prefix_to_depth_9(device_run, golden):
    eng, res, _events, _path = device_run
    assert (eng.chunk, eng.VC, eng.A, eng.W) == (1024, 16384, 192, 735)
    want = golden["depth_counts"][: DEPTH + 1]
    assert res.violation is None and res.exit_cause == "max_depth"
    assert [int(x) for x in res.depth_counts] == want
    assert res.distinct == sum(want) == 18253
    assert {"total": res.total, "terminal": res.terminal} == golden[
        "totals"][str(DEPTH)]
    rows = res.metrics
    assert [w["depth"] for w in rows] == list(range(1, DEPTH + 1))
    assert not any(w["overflow_bits"] for w in rows)
    # no tiers at four servers: every representative takes the 24 tables
    assert all(w["canon_tier3_local"] == 0 < w["canon_tier3_full"]
               for w in rows)


def test_golden_is_the_issues_counts_and_the_smokes_prefix(golden):
    """benchmark/goldens/addremove4.json, the pooled oracle run's record,
    pins the depths the traffic mix names, and what chip_smoke.py's leg H
    holds the CLI to is its prefix."""
    assert golden["msg_slots"] == 112
    assert golden["independent_to_depth"] >= 16
    assert golden["depth_counts"] == [
        1, 6, 27, 91, 251, 602, 1294, 2558, 4790, 8633, 15210, 26257,
        44664, 76025, 131746, 234259, 424063]
    assert golden["totals"]["16"] == {"total": 2473432, "terminal": 745}
    traffic = load(BENCH, "traffic", "init-d16-warm16.json")
    for depth in (traffic["warmup_depth"], traffic["max_depth"]):
        assert str(depth) in golden["totals"]
        assert len(golden["depth_counts"]) > depth
    smoke = load(ROOT, "tests", "golden",
                 "addremove4_cfg_depth_counts.json")["depth_limited"]
    depth = smoke["max_depth"]
    assert depth == DEPTH
    assert smoke["depth_counts"] == golden["depth_counts"][: depth + 1]
    assert smoke["distinct"] == sum(smoke["depth_counts"])
    assert {k: smoke[k] for k in ("total", "terminal")} == golden[
        "totals"][str(depth)]


def test_cell_files_numbers_follow_from_the_golden(golden):
    """What benchmark/workloads/addremove4-wide.json says of its job:
    the chunk-steps by wave and by the seen run's size, the capacity
    the growth rule leaves alone, the peak the new counter reads."""
    from raft_tpu.checker.device_bfs import DeviceBFS

    cell = load(BENCH, "workloads", "addremove4-wide.json")
    traffic = load(BENCH, "traffic", f"{cell['traffic']}.json")
    depth, chunk = traffic["max_depth"], cell["engine_params"]["chunk"]
    counts = golden["depth_counts"][: depth + 1]
    # wave d expands depth d - 1's rows
    steps = [-(-n // chunk) for n in counts[:-1]]
    assert steps == [1] * 6 + [2, 3, 5, 9, 15, 26, 44, 75, 129, 229]
    assert sum(steps) == 543 and sum(s for s in steps if s > 1) == 537
    # the seen run's first size holds the distinct states until wave 14's
    # merge; waves 15 and 16 run against the second
    first, second = 1 << 18, 1 << 20
    distinct = list(itertools.accumulate(counts))
    assert distinct[13] <= first < distinct[14] and distinct[16] <= second
    assert (sum(steps[:14]), sum(steps[14:])) == (185, 358)
    job = cell["job"]
    for n in (sum(counts), golden["totals"]["16"]["total"],
              golden["totals"]["16"]["terminal"], 543, 537, 185, 358,
              max(counts)):
        assert f"{n:,}" in job, n
    # the growth rule runs after every wave but the last
    fcap = cell["engine_params"]["frontier_cap"]
    widest_seen = max(counts[1:-1])
    assert fcap // 2 < DeviceBFS.HEADROOM * widest_seen <= fcap
    assert DeviceBFS.HEADROOM * max(counts[1:-2]) <= fcap // 2
    assert cell["per_layer"][-2:] == ["emit_append_share",
                                     "frontier_peak_rows"]


def test_frontier_peak_rows_is_the_widest_waves_new_and_is_declared(
        device_run):
    """How full the frontier got, beside how large it was: on the
    result's stats and on the summary, in the schema, and refused by it
    when it is no count."""
    from raft_tpu.obs.events import SUMMARY_KEYS

    _eng, res, events, path = device_run
    peak = max(w["new"] for w in res.metrics)
    assert peak == max(res.depth_counts[1:]) == 8633
    assert res.stats["frontier_peak_rows"] == peak
    (summary,) = [ev for ev in events if ev["event"] == "summary"]
    assert summary["frontier_peak_rows"] == peak
    assert peak <= summary["peak_frontier_cap"] == 1 << 15
    assert "frontier_peak_rows" in SUMMARY_KEYS
    script = os.path.join(ROOT, "scripts", "check_metrics_schema.py")
    ok = subprocess.run([sys.executable, script, path],
                        capture_output=True, text=True)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    bad_path = path + ".bad"
    with open(bad_path, "w") as f:
        for ev in events:
            if ev["event"] == "summary":
                ev = dict(ev, frontier_peak_rows=-1)
            f.write(json.dumps(ev) + "\n")
    bad = subprocess.run([sys.executable, script, bad_path],
                         capture_output=True, text=True)
    assert bad.returncode != 0
    assert "frontier_peak_rows" in bad.stdout + bad.stderr


def test_the_append_has_a_scope_of_its_own_in_the_wave_program(device_run):
    """`emit/append` beside `emit/coverage` and `emit/invariants`: the
    compaction's gather and the dynamic_update_slice appends are under
    it, and the benchmark's rule reads it as the stage's second level."""
    from benchmark import xplane

    eng, _res, _events, _path = device_run
    (prog,) = [p for p in eng.audit_programs() if p["name"] == "wave"]
    found = {}
    for e in eqns(jax.make_jaxpr(prog["fn"])(*prog["args"]).jaxpr):
        found.setdefault(str(e.source_info.name_stack), set()).add(
            e.primitive.name)
    under = set().union(*(prims for stack, prims in found.items()
                          if stack.startswith("emit/append")))
    assert {"dynamic_update_slice", "gather"} <= under
    # every append of the stage is under it: rows, journal, fingerprints
    assert not any(
        "dynamic_update_slice" in prims for stack, prims in found.items()
        if stack.startswith("emit") and not stack.startswith("emit/append"))
    assert {s.split("/")[1] for s in found if s.startswith("emit/")} >= {
        "append", "coverage", "invariants"}
    assert xplane.scope_path(
        "jit(_wave_step)/while/body/emit/append/dynamic_update_slice:"
    ) == ("emit", "append")
