"""State-space cartography (per-action coverage): registry lock-step,
device accumulation invariants, cross-engine agreement, schema
round-trip, CLI table + strict dead-action gate.

The coverage block is ``actions[rank] = [enabled, fired, new_distinct]``
with ``rank`` indexing the model's ACTION_NAMES (the Next-disjunct
order). The invariants pinned here:

  * the rank-constant table each spec lowering declares and its
    ACTION_NAMES list cannot drift apart (the AST smoke test reads the
    constants straight from the module source);
  * per action: enabled <= fired (an enabled state contributes at least
    one valid lane) and new <= fired (dedup only shrinks);
  * sum(new) == distinct states beyond the inits — every distinct state
    is attributed to exactly one action;
  * host and device engines agree on enabled/fired exactly (new
    attribution may differ per action across engines when one state is
    reachable by several actions in the same wave — the SUM still
    matches);
  * --coverage prints a table naming every action; --coverage=strict
    exits 3 when an action never fired.
"""

import dataclasses
import json

import numpy as np
import pytest

from raft_tpu.models.raft import RaftParams, cached_model

# all 12 plain-raft disjuncts fire by depth 10 at these params (restarts
# exercise Restart/UpdateTerm, a second election forces AE rejections)
COV_PARAMS = RaftParams(
    n_servers=2, n_values=1, max_elections=2, max_restarts=1, msg_slots=16
)
INVS = ("NoLogDivergence",)

MODEL_MODULES = (
    "raft", "kraft", "pull_raft", "kraft_reconfig", "joint_raft",
    "reconfig_raft",
)


def _device(model, invariants=INVS, **kw):
    from raft_tpu.checker.device_bfs import DeviceBFS

    kw.setdefault("chunk", 512)
    kw.setdefault("frontier_cap", 1 << 12)
    kw.setdefault("seen_cap", 1 << 15)
    kw.setdefault("journal_cap", 1 << 15)
    return DeviceBFS(model, invariants=invariants, symmetry=True, **kw)


# ------------------------------------------------- rank/name registry


def test_every_lowering_names_every_rank():
    """len(ACTION_NAMES) == max declared rank + 1, for every spec
    lowering — a new disjunct without a name (or a stale name list)
    breaks coverage attribution silently otherwise.

    The AST rank-table reader lives in the lane-discipline lint pass
    now (raft_tpu.analysis.lanes.module_max_rank, the migrated
    ``_module_max_rank``); this wrapper pins each module's table
    against its ACTION_NAMES the way the original did."""
    import importlib

    from raft_tpu.analysis.lanes import module_max_rank

    for name in MODEL_MODULES:
        mod = importlib.import_module(f"raft_tpu.models.{name}")
        with open(mod.__file__) as fh:
            max_rank = module_max_rank(fh.read())
        assert max_rank is not None, f"{name}: no rank table found"
        assert len(mod.ACTION_NAMES) == max_rank + 1, (
            f"{name}: {len(mod.ACTION_NAMES)} names for ranks "
            f"0..{max_rank}"
        )


def test_lane_discipline_pass_clean():
    """The full lane-discipline pass (ACTION_NAMES lock-step across the
    registry PLUS ``_cv`` routing of fleet-dynamic constants) reports
    nothing on the shipped tree — the superset contract of the wrapper
    above, run exactly as ``raft_tpu lint --pass lane-discipline``."""
    from raft_tpu.analysis import lanes

    res = lanes.run()
    assert res.checked >= len(MODEL_MODULES)
    assert not res.findings, [f.render() for f in res.findings]


def test_raft_instance_trims_fsync_ranks():
    from raft_tpu.models import raft as raft_mod

    plain = cached_model(COV_PARAMS)
    assert plain.ACTION_NAMES == list(raft_mod.ACTION_NAMES[:12])
    fsync = cached_model(dataclasses.replace(COV_PARAMS, has_fsync=True))
    assert fsync.ACTION_NAMES == list(raft_mod.ACTION_NAMES)
    # the shared mixin resolves labels through the instance table
    assert plain.action_label(raft_mod.R_RESTART, 0).startswith("Restart")


# ------------------------------------------------- device accumulation


def test_device_coverage_accumulation_invariants():
    from raft_tpu.obs import Telemetry

    model = cached_model(COV_PARAMS)
    with Telemetry() as tel:
        res = _device(model).run(max_depth=10, telemetry=tel)
    K = len(model.ACTION_NAMES)
    cov = np.asarray(res.coverage)
    assert cov.shape == (K, 3)
    enabled, fired, new = cov[:, 0], cov[:, 1], cov[:, 2]
    assert (enabled <= fired).all()
    assert (new <= fired).all()
    assert int(new.sum()) == res.distinct - res.depth_counts[0]
    # acceptance: on this config every plain-raft action fires
    assert (fired > 0).all(), (
        f"dead actions: "
        f"{[model.ACTION_NAMES[r] for r in np.nonzero(fired == 0)[0]]}"
    )
    covs = tel.coverage_events()
    assert covs[-1]["final"] is True
    assert covs[-1]["actions"] == res.coverage
    assert covs[-1]["actions_fired"] == K
    assert covs[-1]["frontier_hist"] == res.depth_counts
    assert not any(e["final"] for e in covs[:-1])


def _raft2_setup():
    return cached_model(COV_PARAMS), INVS, 6, {}


def _flexraft5_setup():
    """The benchmark's five-server lowering (A = 82 actions a state, 200
    lanes a row), as tests/test_flexraft5.py builds it."""
    import os

    from raft_tpu.models.registry import build_from_cfg
    from raft_tpu.utils.cfg import parse_cfg

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    setup = build_from_cfg(parse_cfg(os.path.join(
        root, "configs", "flexible-raft", "FlexibleRaft.cfg")), msg_slots=32)
    return setup.model, setup.invariants, 5, {"chunk": 256}


@pytest.mark.parametrize(
    "make", [_raft2_setup, _flexraft5_setup], ids=["raft2", "flexraft5"])
def test_host_and_device_engines_agree(make):
    from raft_tpu.checker.bfs import BFSChecker

    model, invs, depth, kw = make()
    host = BFSChecker(model, invariants=invs, symmetry=True, chunk=512).run(
        max_depth=depth
    )
    dev = _device(model, invariants=invs, **kw).run(max_depth=depth)
    h, d = np.asarray(host.coverage), np.asarray(dev.coverage)
    assert d.shape == (len(model.ACTION_NAMES), 3) and d[:, 1].sum() > 0
    assert h[:, :2].tolist() == d[:, :2].tolist()  # enabled/fired exact
    assert int(h[:, 2].sum()) == int(d[:, 2].sum())
    assert int(d[:, 2].sum()) == dev.distinct - dev.depth_counts[0]


# ------------------------------------------------- the counting helper


COUNT_CASES = {  # name -> (K, shape of the lanes)
    "k12": (12, (64, 53)),
    "k21": (21, (32, 82)),
    "odd-lanes": (12, (37, 53)),  # 1,961 lanes, not a multiple of 128
    "flat-lanes": (12, (1000,)),  # the compacted [VC] lanes of new_k
    "all-masked": (12, (16, 53)),
    "minus-one-under-false-mask": (12, (16, 53)),
    "one-bucket": (12, (16, 53)),
}


def _count_case(name):
    """(rank, mask, K) for one case of the helper's test; ranks are drawn
    from -1..K-1, -1 being what an invalid candidate lane carries."""
    rng = np.random.default_rng(27)
    K, shape = COUNT_CASES[name]
    rank = rng.integers(-1, K, size=shape).astype(np.int32)
    mask = rng.random(shape) < 0.5
    if name == "all-masked":
        mask[...] = False
    elif name == "minus-one-under-false-mask":
        # every -1 lane is under a false mask, as _expand1 leaves them
        mask = rank >= 0
        assert (rank == -1).any()
    elif name == "one-bucket":
        rank[...] = 7
        mask[...] = True
    return rank, mask, K


@pytest.mark.parametrize("name", COUNT_CASES)
def test_rank_counts_is_bincount(name):
    """util.rank_counts (what both device engines count fired and
    new-distinct with) against np.bincount, the host engine's count."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.checker.util import rank_counts, rank_onehot

    rank, mask, K = _count_case(name)
    keep = mask & (rank >= 0)
    want = np.bincount(rank[keep], minlength=K)
    got = jax.jit(rank_counts, static_argnums=2)(rank, mask, K)
    assert got.dtype == jnp.int32 and got.shape == (K,)
    assert np.asarray(got).tolist() == want.tolist()
    hot = np.asarray(rank_onehot(jnp.asarray(rank), jnp.asarray(mask), K))
    assert hot.shape == (*rank.shape, K)
    assert (hot.sum(axis=-1) == keep).all()  # one slot a counted lane
    if name == "all-masked":
        assert not want.any()
    if name == "one-bucket":
        assert want[7] == rank.size and want.sum() == rank.size


# ------------------------------------------------- schema round-trip


def _cov_event(wave, actions, final=False):
    return {
        "event": "coverage", "wave": wave, "depth": wave,
        "actions": actions, "actions_total": len(actions),
        "actions_fired": sum(1 for r in actions if r[1]),
        "seen_lanes": [8], "seen_real": 4, "probe_runs": 1,
        "frontier_hist": [1] * (wave + 1), "final": final,
    }


def _stream(events):
    return [json.dumps(e) for e in events]


def test_coverage_schema_roundtrip_and_monotonicity():
    from raft_tpu.obs import MANIFEST_KEYS, SUMMARY_KEYS, WAVE_KEYS
    from raft_tpu.obs.events import validate_lines

    def fields(keys, **kw):
        ev = dict.fromkeys(keys, 0)
        ev.update(kw)
        return ev

    man = fields(MANIFEST_KEYS, event="manifest", action_names=["A", "B"])
    w1 = fields(WAVE_KEYS, event="wave", wave=1)
    w2 = fields(WAVE_KEYS, event="wave", wave=2)
    summ = fields(SUMMARY_KEYS, event="summary", exit_cause="exhausted")

    good = _stream([
        man, w1, _cov_event(1, [[1, 1, 1], [0, 0, 0]]),
        w2, _cov_event(2, [[2, 3, 1], [1, 1, 1]], final=True), summ,
    ])
    counts, problems = validate_lines(good)
    assert not problems, problems
    assert counts["coverage"] == 2

    # cumulative counters must never decrease cell-by-cell
    bad = _stream([
        man, w1, _cov_event(1, [[2, 2, 1], [0, 0, 0]]),
        w2, _cov_event(2, [[1, 3, 1], [1, 1, 1]], final=True), summ,
    ])
    _, problems = validate_lines(bad)
    assert any("not monotone" in p for p in problems), problems

    # coverage after the run's summary is a stream bug
    bad2 = _stream([man, w1, summ, _cov_event(1, [[1, 1, 1], [0, 0, 0]])])
    _, problems = validate_lines(bad2)
    assert any("after the run's summary" in p for p in problems), problems

    # malformed actions block (negative count / wrong arity)
    bad3 = _stream([man, w1, _cov_event(1, [[1, -1, 1], [0, 0]]), summ])
    _, problems = validate_lines(bad3)
    assert any("non-negative int triples" in p for p in problems), problems


# ----------------------------------------------------------------- CLI


CFG_TEMPLATE = """\
CONSTANTS
    n1 = n1
    n2 = n2
    v1 = v1
    Server = {{ n1, n2 }}
    Value = {{ v1 }}
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
    MaxElections = {elections}
    MaxRestarts = {restarts}

INIT Init
NEXT Next

INVARIANT
NoLogDivergence
"""

CLI_BASE = [
    "--platform", "cpu", "--msg-slots", "16", "--chunk", "256",
    "--frontier-cap", "4096", "--seen-cap", "16384",
    "--journal-cap", "16384",
]


@pytest.mark.slow
def test_cli_coverage_table_names_every_action(tmp_path, capsys):
    from raft_tpu.__main__ import main
    from raft_tpu.models import raft as raft_mod

    cfg = tmp_path / "Raft.cfg"
    cfg.write_text(CFG_TEMPLATE.format(elections=2, restarts=1))
    rc = main([str(cfg), *CLI_BASE, "--max-depth", "10", "--coverage"])
    cap = capsys.readouterr()
    assert rc == 0, cap.err
    assert "Action coverage" in cap.out
    for name in raft_mod.ACTION_NAMES[:12]:
        assert name in cap.out, f"table missing action {name}"
    assert "never fired" not in cap.out


@pytest.mark.slow
def test_cli_coverage_strict_gates_on_dead_action(tmp_path, capsys):
    from raft_tpu.__main__ import main

    # MaxRestarts=0 makes the Restart disjunct unreachable
    cfg = tmp_path / "Raft.cfg"
    cfg.write_text(CFG_TEMPLATE.format(elections=1, restarts=0))
    rc = main([str(cfg), *CLI_BASE, "--max-depth", "4",
               "--coverage=strict"])
    cap = capsys.readouterr()
    assert "WARNING: action Restart never fired" in cap.out
    assert rc == 3, cap.err
