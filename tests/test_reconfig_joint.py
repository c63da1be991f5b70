"""RaftWithReconfigJointConsensus differential tests: TPU kernels vs the
independent oracle (standard-raft/RaftWithReconfigJointConsensus.tla,
1,145 lines), dual-quorum flow, adjacency invariant, and reference-cfg
loading."""

import numpy as np
import pytest

from pathlib import Path

import jax

from raft_tpu.checker.bfs import BFSChecker
from raft_tpu.models.joint_raft import (
    JointRaftModel,
    JointRaftParams,
    cached_model,
    reconfig_shapes,
)
from raft_tpu.oracle.joint_oracle import LEADER, JointRaftOracle

from conftest import collect_states as _collect_states


def oracle_for(p: JointRaftParams) -> JointRaftOracle:
    return JointRaftOracle(
        p.n_servers, p.n_values, p.init_cluster_size, p.max_elections,
        p.max_restarts, p.max_reconfigs, p.max_values_per_term, p.reconfig_type,
    )


PARAMS = [
    # one-for-one swap (the reference cfg's ReconfigType=2), 3 servers
    JointRaftParams(
        n_servers=3, n_values=1, init_cluster_size=2, max_elections=1,
        max_restarts=0, max_reconfigs=1, max_values_per_term=1,
        reconfig_type=2, msg_slots=64,
    ),
    # add-only on 3 servers
    JointRaftParams(
        n_servers=3, n_values=1, init_cluster_size=2, max_elections=1,
        max_restarts=0, max_reconfigs=1, max_values_per_term=1,
        reconfig_type=3, msg_slots=64,
    ),
]


def test_reconfig_shapes_match_reconfig_type():
    """IsValidReconfiguration (:813-825) per type."""
    # type 2: exactly one added and one removed
    shapes2 = reconfig_shapes(3, 2)
    assert all(bin(a).count("1") == 1 and bin(r).count("1") == 1 for a, r in shapes2)
    assert len(shapes2) == 9
    # type 3: nonempty add, empty remove
    shapes3 = reconfig_shapes(3, 3)
    assert all(a != 0 and r == 0 for a, r in shapes3)
    assert len(shapes3) == 7
    # type 4: empty add, nonempty remove
    shapes4 = reconfig_shapes(3, 4)
    assert all(a == 0 and r != 0 for a, r in shapes4)
    # type 1: anything with at least one nonempty side
    shapes1 = reconfig_shapes(3, 1)
    assert len(shapes1) == 8 * 8 - 1


@pytest.mark.parametrize("params", PARAMS)
def test_successor_sets_match_oracle(params):
    model = cached_model(params)
    oracle = oracle_for(params)
    states = _collect_states(oracle, max_depth=8, cap=100)
    vecs = np.stack([model.encode(st) for st in states])
    succs, valid, rank, ovf = jax.device_get(model.expand(vecs))
    assert not np.any(valid & ovf)
    for b, st in enumerate(states):
        got = sorted(
            oracle.serialize_full(model.decode(succs[b, a]))
            for a in range(model.A)
            if valid[b, a]
        )
        want = sorted(oracle.serialize_full(s2) for _l, s2 in oracle.successors(st))
        assert got == want, f"successor mismatch at state {b}"


def test_encode_decode_roundtrip():
    params = PARAMS[0]
    model = cached_model(params)
    oracle = oracle_for(params)
    for st in _collect_states(oracle, max_depth=7, cap=90):
        assert model.decode(model.encode(st)) == st


@pytest.mark.slow
def test_bfs_counts_match_oracle():
    params = PARAMS[0]
    model = cached_model(params)
    oracle = oracle_for(params)
    invs = (
        "LeaderHasAllAckedValues",
        "NoLogDivergence",
        "MaxOneReconfigurationAtATime",
    )
    checker = BFSChecker(model, invariants=invs, symmetry=True, chunk=256)
    res = checker.run(max_depth=7)
    ores = oracle.bfs(invariants=invs, symmetry=True, max_depth=7)
    assert res.violation is None and ores["violation"] is None
    assert res.distinct == ores["distinct"]
    assert res.depth_counts == ores["depth_counts"]
    assert res.total == ores["total"]


def test_joint_consensus_two_phase_flow():
    """Protocol sanity: OldNew (joint, dual quorum) -> commit -> New ->
    commit completes the reconfiguration (:827-876)."""
    params = PARAMS[0]  # swap: members {0,1}, swap 1 out for 2
    oracle = oracle_for(params)
    st = oracle.init_state()

    def step(prefix):
        nonlocal st
        for label, s2 in oracle.successors(st):
            if label.startswith(prefix):
                st = s2
                return
        raise AssertionError(f"no successor matching {prefix!r}")

    assert st["state"][0] == LEADER
    step("AppendOldNewConfigToLog(0,+[2],-[1])")
    cfg = st["config"][0]
    assert cfg[1] is True  # jointConsensus
    assert cfg[2] == frozenset({0, 1, 2})  # joint members = old + added
    assert cfg[3] == frozenset({0, 1})  # old
    assert cfg[4] == frozenset({0, 2})  # new
    assert st["nextIndex"][0][2] == -1  # fresh member needs a snapshot
    # catch up the fresh member via snapshot
    step("SendSnapshot(0,2)")
    step("UpdateTerm")
    step("HandleSnapshotRequest")
    step("HandleSnapshotResponse")
    # replicate the OldNew entry to member 1 and commit (dual quorum:
    # old={0,1} needs {0,1}-majority, new={0,2} needs {0,2}-majority)
    step("AppendEntries(0,1)")
    step("AcceptAppendEntriesRequest")
    step("HandleAppendEntriesResponse")
    step("AdvanceCommitIndex(0)")
    assert st["commitIndex"][0] == 2
    assert st["config"][0][5] is True  # committed, still joint
    assert st["config"][0][1] is True
    # phase 2: NewConfigCommand
    step("AppendNewConfigToLog(0)")
    assert st["config"][0][1] is False
    assert st["config"][0][2] == frozenset({0, 2})
    assert st["log"][0][-1][0] == "NewConfigCommand"
    assert oracle.max_one_reconfiguration_at_a_time(st)


def test_adjacency_invariant_detects_bad_log():
    """MaxOneReconfigurationAtATime (:1080-1101) rejects adjacent same-type
    config commands and accepts properly interleaved ones."""
    params = PARAMS[0]
    oracle = oracle_for(params)
    model = cached_model(params)
    st = oracle.init_state()
    members = frozenset({0, 1})
    # seed New at 1, then OldNew at 2, New at 3 (legal interleave)
    oldnew = ("OldNewConfigCommand", 1, (1, members, frozenset({0, 2}), frozenset({0, 1, 2})))
    new2 = ("NewConfigCommand", 1, (1, frozenset({0, 2})))
    good = oracle._with(
        st, log=oracle._set(st["log"], 0, st["log"][0] + (oldnew, new2))
    )
    assert oracle.max_one_reconfiguration_at_a_time(good)
    # two adjacent New commands (indices 1 and... seed New + another New)
    bad = oracle._with(
        st, log=oracle._set(st["log"], 0, st["log"][0] + (new2,))
    )
    assert not oracle.max_one_reconfiguration_at_a_time(bad)
    # the device invariant agrees on both
    vecs = np.stack([model.encode(good), model.encode(bad)])
    ok = np.asarray(
        jax.device_get(model.invariants["MaxOneReconfigurationAtATime"](vecs))
    )
    assert ok.tolist() == [True, False]


def test_reference_joint_cfg_loads():
    """The in-tree cfg (reconstructed from SURVEY.md section 2.2; its
    header says so), parsed strictly."""
    from raft_tpu.utils.cfg import parse_cfg
    from raft_tpu.models.registry import build_from_cfg

    path = (
        Path(__file__).resolve().parent.parent / "configs" / "standard-raft"
        / "RaftWithReconfigJointConsensus.cfg"
    )
    cfg = parse_cfg(str(path))
    setup = build_from_cfg(cfg, msg_slots=16)
    assert setup.model.name == "RaftWithReconfigJointConsensus"
    assert setup.model.p.n_servers == 4
    assert setup.model.p.init_cluster_size == 3
    assert setup.model.p.max_elections == 1
    assert setup.model.p.max_reconfigs == 2
    assert setup.model.p.max_values_per_term == 1
    assert setup.model.p.reconfig_type == 2
    assert setup.invariants == (
        "LeaderHasAllAckedValues",
        "NoLogDivergence",
        "MaxOneReconfigurationAtATime",
    )
    assert setup.symmetry
