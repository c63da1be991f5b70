"""RaftWithReconfigAddRemove differential tests: TPU kernels vs the
independent oracle (standard-raft/RaftWithReconfigAddRemove.tla, 1,083
lines), wide-message bag round-trips, BFS count parity, and the
documented missing-MaxClusterSize cfg diagnosis."""

import numpy as np
import pytest

from pathlib import Path

import jax

from raft_tpu.checker.bfs import BFSChecker
from raft_tpu.models.reconfig_raft import (
    ReconfigRaftModel,
    ReconfigRaftParams,
    cached_model,
)
from raft_tpu.oracle.reconfig_oracle import (
    LEADER,
    NOTMEMBER,
    ReconfigRaftOracle,
    most_recent_reconfig_entry,
)

from conftest import collect_states as _collect_states


def oracle_for(p: ReconfigRaftParams) -> ReconfigRaftOracle:
    return ReconfigRaftOracle(
        p.n_servers, p.n_values, p.init_cluster_size, p.max_elections,
        p.max_restarts, p.max_values_per_term, p.max_add_reconfigs,
        p.max_remove_reconfigs, p.min_cluster_size, p.max_cluster_size,
        include_thesis_bug=p.include_thesis_bug,
    )


# smaller than the reference cfg (3 servers not 4) to keep kernels quick;
# a 4-server case mirrors the reference constants
PARAMS = [
    ReconfigRaftParams(
        n_servers=3, n_values=1, init_cluster_size=2, max_elections=1,
        max_restarts=0, max_values_per_term=1, max_add_reconfigs=1,
        max_remove_reconfigs=1, min_cluster_size=2, max_cluster_size=3,
        msg_slots=64,
    ),
    ReconfigRaftParams(
        n_servers=4, n_values=1, init_cluster_size=3, max_elections=1,
        max_restarts=0, max_values_per_term=1, max_add_reconfigs=1,
        max_remove_reconfigs=1, min_cluster_size=2, max_cluster_size=4,
        msg_slots=72,
    ),
]


@pytest.mark.slow
@pytest.mark.parametrize("params", PARAMS)
def test_successor_sets_match_oracle(params):
    model = cached_model(params)
    oracle = oracle_for(params)
    states = _collect_states(oracle, max_depth=8, cap=110)
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
    for st in _collect_states(oracle, max_depth=7, cap=100):
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


def test_reconfig_flow_add_then_snapshot():
    """Protocol sanity: the initial leader adds a server, which triggers a
    snapshot catch-up (nextIndex sentinel path, :795-824,:862-921)."""
    params = PARAMS[0]
    oracle = oracle_for(params)
    st = oracle.init_state()

    def step(prefix):
        nonlocal st
        for label, s2 in oracle.successors(st):
            if label.startswith(prefix):
                st = s2
                return
        raise AssertionError(f"no successor matching {prefix!r}")

    # leader 0, members {0,1}; add server 2
    assert st["state"][0] == LEADER
    step("AppendAddServerCommandToLog(0,2)")
    assert st["config"][0][1] == frozenset({0, 1, 2})
    assert st["config"][0][2] is False  # uncommitted reconfig
    assert st["nextIndex"][0][2] == -1  # PendingSnapshotRequest
    step("SendSnapshot(0,2)")
    assert st["nextIndex"][0][2] == -2
    # the new server must fence its term (0 -> 1) before accepting
    step("UpdateTerm")
    step("HandleSnapshotRequest")
    assert len(st["log"][2]) == 2  # InitCluster + AddServer
    assert st["config"][2][1] == frozenset({0, 1, 2})
    step("HandleSnapshotResponse")
    assert st["nextIndex"][0][2] == 3
    assert st["matchIndex"][0][2] == 2
    # replication to member 1, then commit of the config entry
    step("AppendEntries(0,1)")
    step("AcceptAppendEntriesRequest")
    step("HandleAppendEntriesResponse")
    step("AdvanceCommitIndex(0)")
    assert st["commitIndex"][0] == 2
    assert st["config"][0][2] is True  # reconfig committed
    assert oracle.max_one_reconfiguration_at_a_time(st)
    assert oracle.no_log_divergence(st)


def test_remove_leader_leaves_cluster():
    """A leader that commits its own removal becomes NotMember
    (:633-640); its commitIndex resets."""
    params = ReconfigRaftParams(
        n_servers=3, n_values=1, init_cluster_size=3, max_elections=1,
        max_restarts=0, max_values_per_term=1, max_add_reconfigs=0,
        max_remove_reconfigs=1, min_cluster_size=2, max_cluster_size=3,
        msg_slots=64,
    )
    oracle = oracle_for(params)
    st = oracle.init_state()

    def step(prefix):
        nonlocal st
        for label, s2 in oracle.successors(st):
            if label.startswith(prefix):
                st = s2
                return
        raise AssertionError(f"no successor matching {prefix!r}")

    step("AppendRemoveServerCommandToLog(0,0)")  # leader removes itself
    assert st["config"][0][1] == frozenset({1, 2})
    for peer in (1, 2):
        step(f"AppendEntries(0,{peer})")
        step("AcceptAppendEntriesRequest")
        step("HandleAppendEntriesResponse")
    step("AdvanceCommitIndex(0)")
    assert st["state"][0] == NOTMEMBER
    assert st["commitIndex"][0] == 0


def test_most_recent_reconfig_entry():
    log = (
        ("InitClusterCommand", 1, (1, frozenset({0, 1}))),
        ("AppendCommand", 1, 0),
        ("AddServerCommand", 1, (2, 2, frozenset({0, 1, 2}))),
    )
    idx, entry = most_recent_reconfig_entry(log)
    assert idx == 3 and entry[0] == "AddServerCommand"


def test_reference_cfg_diagnoses_missing_max_cluster_size():
    """On the tree's reconstruction of upstream's file, which keeps the
    omission (tests/test_addremove4.py holds the rest of it)."""
    from raft_tpu.utils.cfg import CfgError, parse_cfg
    from raft_tpu.models.registry import build_from_cfg

    path = str(Path(__file__).parent.parent / "configs" / "standard-raft"
               / "RaftWithReconfigAddRemove.cfg")
    cfg = parse_cfg(path)  # parses cleanly; the bug is builder-level
    with pytest.raises(CfgError, match="MaxClusterSize"):
        build_from_cfg(cfg, msg_slots=16)
    cfg = parse_cfg(path, lenient=True)
    setup = build_from_cfg(cfg, msg_slots=16)
    assert any("MaxClusterSize" in d for d in cfg.diagnostics)
    assert setup.model.name == "RaftWithReconfigAddRemove"
    assert setup.model.p.n_servers == 4
    assert setup.model.p.max_cluster_size == 4  # repaired to |Server|
    assert setup.model.p.init_cluster_size == 3
    assert not setup.model.p.include_thesis_bug
    assert setup.invariants == (
        "LeaderHasAllAckedValues",
        "NoLogDivergence",
        "MaxOneReconfigurationAtATime",
    )
    assert setup.symmetry
