"""End-to-end checker tests: BFS parity with the oracle, cfg loading, traces."""

import numpy as np
import pytest

from pathlib import Path

from raft_tpu.checker.bfs import BFSChecker
from raft_tpu.models.raft import RaftModel, RaftParams, cached_model
from raft_tpu.oracle.raft_oracle import RaftOracle

REF_CFG = str(
    Path(__file__).resolve().parent.parent
    / "configs" / "standard-raft" / "Raft.cfg"
)


def _bfs_pair(params, invariants, symmetry=True, max_depth=None, chunk=256):
    model = cached_model(params)
    oracle = RaftOracle(
        params.n_servers, params.n_values, params.max_elections, params.max_restarts
    )
    checker = BFSChecker(model, invariants=invariants, symmetry=symmetry, chunk=chunk)
    res = checker.run(max_depth=max_depth)
    ores = oracle.bfs(invariants=invariants, symmetry=symmetry, max_depth=max_depth)
    return res, ores, checker


@pytest.mark.slow
@pytest.mark.parametrize("symmetry", [True, False])
def test_bfs_counts_match_oracle_small(symmetry):
    params = RaftParams(n_servers=3, n_values=1, max_elections=1, max_restarts=0, msg_slots=16)
    res, ores, _ = _bfs_pair(
        params, ("LeaderHasAllAckedValues", "NoLogDivergence"), symmetry=symmetry
    )
    assert res.violation is None and ores["violation"] is None
    assert res.distinct == ores["distinct"]
    assert res.depth_counts == ores["depth_counts"]
    assert res.total == ores["total"]


def test_bfs_counts_match_oracle_with_restarts():
    params = RaftParams(n_servers=2, n_values=2, max_elections=2, max_restarts=1, msg_slots=24)
    res, ores, _ = _bfs_pair(
        params,
        ("LeaderHasAllAckedValues", "NoLogDivergence"),
        symmetry=True,
        max_depth=8,
        chunk=512,
    )
    assert res.violation is None and ores["violation"] is None
    assert res.distinct == ores["distinct"]
    assert res.depth_counts == ores["depth_counts"]
    assert res.total == ores["total"]


def test_cfg_parse_reference_raft():
    from raft_tpu.utils.cfg import parse_cfg
    from raft_tpu.models.registry import build_from_cfg

    cfg = parse_cfg(REF_CFG)
    assert cfg.init == "Init" and cfg.next == "Next"
    assert cfg.view == "view" and cfg.symmetry == "symmServers"
    assert cfg.invariants == ["LeaderHasAllAckedValues", "NoLogDivergence"]
    setup = build_from_cfg(cfg, msg_slots=16)
    assert setup.model.p.n_servers == 3
    assert setup.model.p.n_values == 1
    assert setup.model.p.max_elections == 2
    assert setup.model.p.max_restarts == 0
    assert setup.server_names == ["n1", "n2", "n3"]


def test_cfg_diagnoses_undeclared_model_value():
    from raft_tpu.utils.cfg import CfgError, parse_cfg

    text = "CONSTANTS\n    v1 = v1\n    Value = { v1, v2 }\n"
    with pytest.raises(CfgError, match="undeclared model value 'v2'"):
        parse_cfg("inline.cfg", text=text)


def test_violation_trace_on_injected_invariant():
    # A predicate that forbids any committed entry -> must be violated, and
    # the reconstructed trace must be a valid action chain from Init.
    import jax.numpy as jnp

    params = RaftParams(n_servers=3, n_values=1, max_elections=1, max_restarts=0, msg_slots=16)
    model = cached_model(params)
    lay = model.layout

    def no_commit(states):
        ci = lay.get(states, "commitIndex")
        return jnp.all(ci == 0, axis=1)

    model.invariants["NoCommit"] = no_commit
    try:
        checker = BFSChecker(model, invariants=("NoCommit",), symmetry=True, chunk=256)
        res = checker.run()
    finally:
        del model.invariants["NoCommit"]
    assert res.violation is not None
    assert res.trace is not None
    assert res.violation.depth == len(res.trace) - 1
    # the violating final state indeed commits something
    final = res.trace[-1][1]
    assert any(ci > 0 for ci in final["commitIndex"])
    # and the trace starts at Init
    oracle = RaftOracle(3, 1, 1, 0)
    assert res.trace[0][1] == oracle.init_state()
    # shortest counterexample: BFS depth of first commit
    ores = RaftOracle(3, 1, 1, 0).bfs(invariants=(), symmetry=True)
    assert res.violation.depth <= len(ores["depth_counts"])
