"""Guard-first sparse expansion (models/base.py SparseExpandMixin).

The contract under test, for every model family:

  1. the guard pass (``guards1``) is bit-identical to the dense
     ``_expand1`` on valid/rank/ovf — it is DCE-derived, so any drift
     means the derivation broke;
  2. the guard jaxpr materializes NO batched successor blocks (no
     [*, W]-shaped equation outputs) — the whole point of the split;
  3. ``sparse_apply`` reconstructs the compacted [VC, W] successor
     block bit-identically to the dense gather for in-budget lanes,
     with exact budget-threshold semantics (exactly-full fits, one-
     past-full sets the overflow flag and zero-fills the spilled
     lanes);
  4. all three engines produce identical runs (distinct/total/depth
     counts/coverage triples, and counterexample traces) with the
     sparse path as with the dense path, pinned via a shim that hides
     the mixin methods.

Params mirror tests/test_device_smoke.py so cached_model reuses the
already-built lowerings.
"""

import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import collect_states, gather_kernels, scatter_kernels
from raft_tpu.checker.bfs import BFSChecker
from raft_tpu.checker.device_bfs import DeviceBFS
from raft_tpu.parallel.sharded import ShardedBFS


def _raft():
    from raft_tpu.models.raft import RaftParams, cached_model

    return cached_model(RaftParams(
        n_servers=2, n_values=2, max_elections=2, max_restarts=0,
        msg_slots=16,
    ))


def _raft_fsync():
    """RaftFsync (the policy of test_device_smoke.py's fsync model):
    Timeout, RequestVotePair, AdvanceFsyncIndex and Restart's fsync arm."""
    from raft_tpu.models.raft import RaftParams, cached_model

    return cached_model(RaftParams(
        n_servers=3, n_values=1, max_elections=1, max_restarts=1,
        msg_slots=24, strict_send_once=True, has_pending_response=False,
        trunc_term_mismatch=True, has_fsync=True,
        fsync_leader_before_ae=False, fsync_leader_quorum=True,
        fsync_follower_reply=True,
    ))


def _raft_net_faults():
    """Raft with DuplicateMessage and DropMessage over every slot."""
    from raft_tpu.models.raft import RaftParams, cached_model

    return cached_model(RaftParams(
        n_servers=2, n_values=1, max_elections=1, max_restarts=0,
        msg_slots=12, net_faults=True,
    ))


def _pull_raft():
    from raft_tpu.models.pull_raft import PullRaftParams, cached_model

    return cached_model(PullRaftParams(
        n_servers=3, n_values=1, max_elections=2, max_restarts=0,
        msg_slots=24,
    ))


def _kraft():
    from raft_tpu.models.kraft import KRaftParams, cached_model

    return cached_model(KRaftParams(
        n_servers=3, n_values=1, max_elections=2, max_restarts=0,
        msg_slots=24,
    ))


def _joint_raft():
    from raft_tpu.models.joint_raft import JointRaftParams, cached_model

    return cached_model(JointRaftParams(
        n_servers=3, n_values=1, init_cluster_size=2, max_elections=1,
        max_restarts=0, max_reconfigs=1, max_values_per_term=1,
        reconfig_type=2, msg_slots=64,
    ))


def _reconfig_raft():
    from raft_tpu.models.reconfig_raft import (
        ReconfigRaftParams, cached_model,
    )

    return cached_model(ReconfigRaftParams(
        n_servers=3, n_values=1, init_cluster_size=2, max_elections=1,
        max_restarts=0, max_values_per_term=1, max_add_reconfigs=1,
        max_remove_reconfigs=1, min_cluster_size=2, max_cluster_size=3,
        msg_slots=64,
    ))


def _kraft_reconfig():
    from raft_tpu.models.kraft_reconfig import (
        KRaftReconfigParams, cached_model,
    )

    return cached_model(KRaftReconfigParams(
        n_hosts=3, n_values=1, init_cluster_size=2, min_cluster_size=2,
        max_cluster_size=3, max_elections=1, max_restarts=1,
        max_values_per_epoch=1, max_add_reconfigs=1,
        max_remove_reconfigs=1, max_spawned_servers=4, msg_slots=24,
    ))


FAMILIES = {
    "raft": _raft,
    "raft_fsync": _raft_fsync,
    "raft_net_faults": _raft_net_faults,
    "pull_raft": _pull_raft,
    "kraft": _kraft,
    "joint_raft": _joint_raft,
    "reconfig_raft": _reconfig_raft,
    "kraft_reconfig": _kraft_reconfig,
}


class DenseShim:
    """Model proxy that hides the sparse expand contract, forcing every
    engine down the legacy dense path (the parity reference)."""

    def __init__(self, inner):
        self.__dict__["_inner"] = inner

    def __getattr__(self, name):
        if name in ("sparse_apply", "host_apply"):
            raise AttributeError(name)
        return getattr(self.__dict__["_inner"], name)


def _frontier(model, depth=3, cap=512):
    """A real reachable frontier: a few dense waves from init with
    exact-bytes dedup (guard behaviour on reachable states is what the
    parity must hold on; random bit patterns may be unreachable)."""
    W = model.layout.W
    frontier = model.init_states()
    seen = set(s.tobytes() for s in np.asarray(frontier))
    for _ in range(depth):
        B = 256
        nxt = []
        for off in range(0, len(frontier), B):
            cs = frontier[off:off + B]
            nb = len(cs)
            if nb < B:
                cs = np.concatenate(
                    [cs, np.repeat(cs[-1:], B - nb, axis=0)])
            succs, valid, _, _ = jax.device_get(model.expand(cs))
            valid = np.array(valid)
            valid[nb:] = False
            flat = np.array(succs).reshape(-1, W)
            for i in np.nonzero(valid.reshape(-1))[0]:
                t = flat[i].tobytes()
                if t not in seen:
                    seen.add(t)
                    nxt.append(flat[i])
            if len(seen) > 4 * cap:
                break
        if not nxt:
            break
        frontier = np.array(nxt, dtype=np.int32)
        if len(frontier) >= cap:
            break
    return np.asarray(frontier)[:cap]


def _chunk_of(model, C=64):
    fr = _frontier(model)
    reps = -(-C // len(fr))
    return np.tile(fr, (reps, 1))[:C]


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_guards_bit_identical_to_dense(family):
    model = FAMILIES[family]()
    batch = jnp.asarray(_chunk_of(model))
    _, valid, rank, ovf = jax.device_get(
        jax.jit(lambda b: jax.vmap(model._expand1)(b))(batch))
    gv, gr, go = jax.device_get(
        jax.jit(lambda b: jax.vmap(model.guards1)(b))(batch))
    np.testing.assert_array_equal(np.asarray(valid), np.asarray(gv))
    np.testing.assert_array_equal(np.asarray(rank), np.asarray(gr))
    np.testing.assert_array_equal(np.asarray(ovf), np.asarray(go))


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_guard_jaxpr_writes_no_successor_blocks(family):
    """The guard jaxpr must not materialize any [*, W] successor block:
    that is the work the split exists to avoid. (Single [W]-vectors are
    fine — the input state itself is one.)

    The jaxpr inspection migrated to the guard-purity lint pass
    (raft_tpu.analysis.guard_purity.check_model), which generalizes it
    with the declared-lane read audit; this wrapper runs the pass on
    each family and pins a clean report."""
    from raft_tpu.analysis import guard_purity

    findings = []
    guard_purity.check_model(family, FAMILIES[family](), findings)
    assert not findings, [f.render() for f in findings]


# A kernel that writes `.at[i].set` with a binding or a decoded server as
# the index is a batched scatter under the worklist's vmap. On the v5e
# such writes were DROPPED in the joint-consensus lowering's sparse apply
# at a batch size no golden covered (PR 30; scripts/stage_diff.py
# --scatter reproduces it), so every lowering writes by models/base.py's
# one-hot selects, and a small run on the chip is no guard: this is.
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_no_kernel_writes_through_a_dynamic_index_scatter(family):
    """No per-action kernel of the family holds a scatter (the jaxpr
    of each kernel as the sparse apply calls it; nothing compiled)."""
    assert scatter_kernels(FAMILIES[family]()) == {}


# A kernel that reads `d[f][i]` with a binding, a decoded server, a log
# position or a bag slot as the index is a per-lane gather under the
# worklist's vmap, and in the guard pass under the chunk's (a read by the
# inner vmap's own iota included). On the v5e those cost 6.5-12 ns an
# index and were most of `expand` (PRs 31-44), so every lowering reads by
# `models/base.py::onehot_row` / `onehot_get2`.
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_no_kernel_reads_through_a_dynamic_index_gather(family):
    """No per-action kernel of the family, traced under the worklist's
    vmap, and no guard pass over a chunk holds a gather (`sparse_apply`'s
    own row and binding gathers are outside the kernels; nothing
    compiled)."""
    assert gather_kernels(FAMILIES[family]()) == {}


@pytest.mark.parametrize("family,bag_word", [
    ("joint_raft", "msg_w0"), ("reconfig_raft", "msg_w0"),
    ("kraft", "msg_hi"), ("kraft_reconfig", "msg_w0"),
    ("pull_raft", "msg_hi"), ("raft", "msg_hi")])
def test_one_hot_reads_match_the_oracle_on_empty_slots_and_logs(
        family, bag_word):
    """A one-hot read of an index outside its axis yields 0 where the
    gather it replaced clamped. The first levels from Init are where
    such indices would come from: most bag slots EMPTY (every field of
    the word decodes to 0), a server outside the cluster with an empty
    log (`ll - 1` is -1). Per state, the enabled candidates' (action,
    successor) pairs equal the oracle's, none overflows, and no
    candidate on an EMPTY slot is enabled."""
    from types import SimpleNamespace

    from raft_tpu.models.registry import oracle_for_setup
    from raft_tpu.ops.packing import EMPTY

    model = FAMILIES[family]()
    oracle = oracle_for_setup(SimpleNamespace(model=model))
    states = collect_states(oracle, max_depth=3, cap=40)
    vecs = np.stack([model.encode(st) for st in states]).astype(np.int32)
    lay = model.layout
    empty = lay.get(vecs, bag_word) == int(EMPTY)
    assert empty.any(axis=1).all() and (~empty).any()
    assert (lay.get(vecs, "log_len") == 0).any()
    succs, valid, rank, ovf = jax.device_get(
        jax.jit(jax.vmap(model._expand1))(vecs))
    assert not np.any(valid & ovf)
    slot0 = model.A - model.p.msg_slots  # HandleMessage(m) comes last
    assert model.bindings[slot0] == ("HandleMessage", (0,))
    assert not np.any(valid[:, slot0:] & empty)
    assert np.all(rank[:, slot0:][empty] == -1)
    for b, st in enumerate(states):
        got = sorted(
            (model.ACTION_NAMES[rank[b, a]],
             oracle.serialize_full(model.decode(succs[b, a])))
            for a in np.nonzero(valid[b])[0])
        # an oracle's label is the action, then its binding in brackets
        want = sorted((re.split(r"[(\[]", label)[0], oracle.serialize_full(s2))
                      for label, s2 in oracle.successors(st))
        assert got == want, f"state {b}"


def _stage_diff(*argv):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return subprocess.run(
        [sys.executable, *argv], cwd=root, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": os.pathsep.join(
                 [root, os.path.join(root, "scripts"),
                  os.path.join(root, "tests")])})


def test_stage_diff_rehearses_on_the_cpu(tmp_path):
    """scripts/stage_diff.py, the stage-by-stage differential that found
    PR 30's dropped writes, with the CPU on both sides: it reaches every
    stage through the engine's own functions (so it breaks here when one
    of them moves) and two runs of one backend are equal."""
    r = _stage_diff(
        "scripts/stage_diff.py", "configs/standard-raft/Raft.cfg",
        "--msg-slots", "16", "--chunk", "256", "--depth", "2",
        "--platform", "cpu", "--out", str(tmp_path))
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.splitlines()
    assert lines[-1] == "ALL STAGES EQUAL"
    stages = {ln.split()[0] for ln in lines if " differ" in ln}
    assert {"dense_succs", "guards_valid", "sparse_rows", "canon_fp_dedup",
            "canon_dedup_off_rule", "invariant_NoLogDivergence",
            "wave_rows", "wave_new", "wave_jparent"} <= stages
    # and without --platform cpu it refuses to call the CPU a chip
    r = _stage_diff(
        "scripts/stage_diff.py", "configs/standard-raft/Raft.cfg",
        "--msg-slots", "16", "--chunk", "256", "--depth", "1",
        "--out", str(tmp_path))
    assert r.returncode == 3 and "no accelerator" in r.stderr


def test_stage_diff_scatter_puts_the_scatters_back():
    """--scatter is the reproducer's switch: the one-hot write helpers
    become the `.at[]` writes they replaced, so the joint-consensus
    kernels hold scatters again (in a child: it patches modules)."""
    r = _stage_diff("-c", (
        "import stage_diff, conftest, test_expand_sparse as t\n"
        "stage_diff.scatter_writes()\n"
        "found = conftest.scatter_kernels(t.FAMILIES['joint_raft']())\n"
        "print(sorted(found), sum(found.values()))"))
    assert r.returncode == 0, r.stderr[-2000:]
    names, total = r.stdout.strip().rsplit(" ", 1)
    assert "HandleMessage" in names and int(total) > 40, r.stdout


def test_stage_diff_gather_puts_the_gathers_back():
    """--gather is the same switch for the reads: the one-hot read
    helpers become `arr[i]` and `arr[i, j]` again, so old reads can be
    timed and diffed against new ones on the chip from one tree."""
    r = _stage_diff("-c", (
        "import stage_diff, conftest, test_expand_sparse as t\n"
        "stage_diff.gather_reads()\n"
        "found = conftest.gather_kernels(t.FAMILIES['joint_raft']())\n"
        "print(sorted(found), sum(found.values()))"))
    assert r.returncode == 0, r.stderr[-2000:]
    names, total = r.stdout.strip().rsplit(" ", 1)
    assert "HandleMessage" in names and "guards" in names, r.stdout
    assert int(total) > 100, r.stdout


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_sparse_apply_parity_loose_plan(family):
    model = FAMILIES[family]()
    C = 64
    A, W = model.A, model.layout.W
    VC = min(C * A, C * 16)
    batch = jnp.asarray(_chunk_of(model, C))
    succs, valid, _, _ = jax.jit(
        lambda b: jax.vmap(model._expand1)(b))(batch)
    vflat = valid.reshape(-1)
    vpos = jnp.cumsum(vflat) - 1
    sdst = jnp.where(vflat, jnp.minimum(vpos, VC), VC)
    sel = (
        jnp.full((VC + 1,), C * A, jnp.int32)
        .at[sdst]
        .set(jnp.arange(C * A, dtype=jnp.int32))[:VC]
    )
    selv = sel < C * A
    dense = np.asarray(jnp.concatenate(
        [succs.reshape(C * A, W), jnp.zeros((1, W), jnp.int32)], axis=0,
    )[sel])
    plan = model.sparse_plan(C, VC)  # loose: overflow-impossible
    flatc, ovf, _ = jax.device_get(jax.jit(
        lambda b, s, sv: model.sparse_apply(b, s, sv, plan)
    )(batch, sel, selv))
    assert not bool(ovf)
    np.testing.assert_array_equal(dense, np.asarray(flatc))


def test_apply_budget_exact_thresholds():
    """Exactly-full budgets fit without overflow and stay bit-identical;
    one-past-full sets the overflow flag, zero-fills the spilled lanes
    of the squeezed group, and leaves every other lane bit-identical."""
    model = _raft()
    C = 64
    A, W = model.A, model.layout.W
    VC = C * A  # full worklist: every enabled lane compacts in
    batch = jnp.asarray(_chunk_of(model, C))
    succs, valid, _, _ = jax.jit(
        lambda b: jax.vmap(model._expand1)(b))(batch)
    vflat = valid.reshape(-1)
    vpos = jnp.cumsum(vflat) - 1
    sdst = jnp.where(vflat, jnp.minimum(vpos, VC), VC)
    sel = (
        jnp.full((VC + 1,), C * A, jnp.int32)
        .at[sdst]
        .set(jnp.arange(C * A, dtype=jnp.int32))[:VC]
    )
    selv = sel < C * A
    dense = np.asarray(jnp.concatenate(
        [succs.reshape(C * A, W), jnp.zeros((1, W), jnp.int32)], axis=0,
    )[sel])

    groups = model.sparse_groups()
    valid_h = np.asarray(valid)
    counts = [int(valid_h[:, g.off:g.off + g.n].sum()) for g in groups]
    gi = int(np.argmax(counts))  # squeeze the busiest group
    assert counts[gi] >= 2, "frontier too shallow to exercise budgets"

    # exactly-full: per-group budgets == enabled counts
    plan_exact = tuple(counts)
    flatc, ovf, _ = jax.device_get(jax.jit(
        lambda b, s, sv: model.sparse_apply(b, s, sv, plan_exact)
    )(batch, sel, selv))
    assert not bool(ovf)
    np.testing.assert_array_equal(dense, np.asarray(flatc))

    # one-past-full: the squeezed group's LAST worklist lane spills
    plan_tight = tuple(
        c - 1 if i == gi else c for i, c in enumerate(counts))
    flatc_t, ovf_t, _ = jax.device_get(jax.jit(
        lambda b, s, sv: model.sparse_apply(b, s, sv, plan_tight)
    )(batch, sel, selv))
    assert bool(ovf_t)
    flatc_t = np.asarray(flatc_t)
    g = groups[gi]
    sel_h = np.asarray(sel)
    cand = np.where(sel_h < C * A, sel_h % A, -1)
    in_group = (cand >= g.off) & (cand < g.off + g.n)
    spilled = np.zeros(VC, dtype=bool)
    spilled[np.nonzero(in_group)[0][-1]] = True  # lane past the budget
    np.testing.assert_array_equal(dense[~spilled], flatc_t[~spilled])
    assert (flatc_t[spilled] == 0).all()


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_host_engine_parity(family):
    model = FAMILIES[family]()
    inv = tuple(list(model.invariants)[:1])
    sparse = BFSChecker(model, invariants=inv, symmetry=True, chunk=256)
    dense = BFSChecker(
        DenseShim(model), invariants=inv, symmetry=True, chunk=256)
    assert sparse._sparse and not dense._sparse
    rs, rd = sparse.run(max_depth=3), dense.run(max_depth=3)
    assert (rs.distinct, rs.total, rs.depth_counts, rs.terminal) == (
        rd.distinct, rd.total, rd.depth_counts, rd.terminal)
    assert rs.coverage == rd.coverage


_HEAVY = ("joint_raft", "kraft_reconfig", "reconfig_raft")


@pytest.mark.parametrize(
    "family",
    [f for f in sorted(FAMILIES) if f not in _HEAVY]
    + [pytest.param(f, marks=pytest.mark.slow) for f in _HEAVY],
)
def test_device_engine_parity(family):
    model = FAMILIES[family]()
    inv = tuple(list(model.invariants)[:1])
    kw = dict(invariants=inv, symmetry=True, chunk=128,
              frontier_cap=1 << 12, seen_cap=1 << 15)
    sparse = DeviceBFS(model, **kw)
    dense = DeviceBFS(DenseShim(model), **kw)
    assert sparse._sparse and not dense._sparse
    rs, rd = sparse.run(max_depth=3), dense.run(max_depth=3)
    assert (rs.distinct, rs.total, rs.depth_counts, rs.terminal) == (
        rd.distinct, rd.total, rd.depth_counts, rd.terminal)
    assert rs.coverage == rd.coverage


def test_sharded_engine_parity():
    model = _raft()
    inv = tuple(list(model.invariants)[:1])
    kw = dict(invariants=inv, symmetry=True, chunk=128,
              frontier_cap=1 << 12, seen_cap=1 << 15)
    sparse = ShardedBFS(model, **kw)
    dense = ShardedBFS(DenseShim(model), **kw)
    assert sparse._sparse and not dense._sparse
    rs, rd = sparse.run(max_depth=3), dense.run(max_depth=3)
    assert (rs.distinct, rs.total, rs.depth_counts, rs.terminal) == (
        rd.distinct, rd.total, rd.depth_counts, rd.terminal)
    assert rs.coverage == rd.coverage


@pytest.mark.slow
@pytest.mark.parametrize("family", sorted(set(FAMILIES) - {"raft"}))
def test_sharded_engine_parity_all_families(family):
    model = FAMILIES[family]()
    inv = tuple(list(model.invariants)[:1])
    kw = dict(invariants=inv, symmetry=True, chunk=128,
              frontier_cap=1 << 12, seen_cap=1 << 15)
    rs = ShardedBFS(model, **kw).run(max_depth=3)
    rd = ShardedBFS(DenseShim(model), **kw).run(max_depth=3)
    assert (rs.distinct, rs.total, rs.depth_counts, rs.coverage) == (
        rd.distinct, rd.total, rd.depth_counts, rd.coverage)


def test_violation_trace_parity():
    """A violating run must produce the identical counterexample trace
    down both paths (trace reconstruction replays the dense expand, but
    the journal it replays was written by the sparse wave loop)."""
    import jax.numpy as jnp

    from raft_tpu.models.raft import RaftParams, cached_model

    model = cached_model(RaftParams(
        n_servers=3, n_values=1, max_elections=1, max_restarts=0,
        msg_slots=16,
    ))
    lay = model.layout

    def no_commit(states):  # forbids any commit -> guaranteed to trip
        return jnp.all(lay.get(states, "commitIndex") == 0, axis=1)

    model.invariants["NoCommit"] = no_commit
    try:
        rs = BFSChecker(
            model, invariants=("NoCommit",), symmetry=True, chunk=256,
        ).run()
        rd = BFSChecker(
            DenseShim(model), invariants=("NoCommit",), symmetry=True,
            chunk=256,
        ).run()
    finally:
        del model.invariants["NoCommit"]
    assert rs.violation is not None and rd.violation is not None
    assert rs.violation.depth == rd.violation.depth
    assert rs.violation.global_id == rd.violation.global_id
    assert rs.trace is not None and rd.trace is not None
    assert [a for a, _ in rs.trace] == [a for a, _ in rd.trace]
    assert rs.trace[-1][1] == rd.trace[-1][1]


def test_e2e_sparse_run_with_telemetry(tmp_path):
    """End-to-end: a real run() down the sparse path with telemetry and
    coverage attached — the metrics stream must validate against the
    declared schema and the new wave gauges must be live (density in
    (0, 1], budget overflow 0 on a surviving run)."""
    from raft_tpu.obs import Telemetry
    from raft_tpu.obs.events import validate_lines

    model = _raft()
    inv = tuple(list(model.invariants)[:1])
    dev = DeviceBFS(
        model, invariants=inv, symmetry=True, chunk=256,
        frontier_cap=1 << 12, seen_cap=1 << 15, journal_cap=1 << 15,
    )
    assert dev._sparse  # the production path under test
    path = tmp_path / "m.jsonl"
    with Telemetry(metrics_path=str(path)) as tel:
        res = dev.run(max_depth=3, telemetry=tel, collect_metrics=True)
    with open(path) as fh:
        lines = fh.readlines()
    counts, problems = validate_lines(lines)
    assert not problems, problems
    assert counts["manifest"] == 1 and counts["summary"] == 1
    assert counts["wave"] >= 3

    import json

    waves = [json.loads(ln) for ln in lines]
    waves = [e for e in waves if e["event"] == "wave"]
    for w in waves:
        assert 0.0 <= w["enabled_density"] <= 1.0
        assert w["expand_budget_ovf"] == 0  # abort fires before this
    assert any(w["enabled_density"] > 0.0 for w in waves)
    assert res.coverage is not None and res.metrics is not None
    for wm in res.metrics:
        assert "enabled_density" in wm and "expand_budget_ovf" in wm
