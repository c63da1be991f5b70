"""Differential tests: the JAX Raft kernels vs the pure-Python oracle.

The oracle (raft_tpu/oracle/raft_oracle.py) is written directly against the
TLA+ text; the kernels are an independent lowering. Agreement on successor
sets over every reachable state of a small model is the core correctness
evidence (SURVEY.md §4: differential testing strategy).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raft_tpu.models.raft import RaftModel, RaftParams
from raft_tpu.oracle.raft_oracle import RaftOracle
from raft_tpu.ops.symmetry import Canonicalizer

from conftest import collect_states as _collect_states
from conftest import gather_kernels, scatter_kernels


def make(params: RaftParams):
    model = RaftModel(params)
    oracle = RaftOracle(
        params.n_servers, params.n_values, params.max_elections, params.max_restarts
    )
    return model, oracle


SMALL = RaftParams(n_servers=3, n_values=1, max_elections=1, max_restarts=1, msg_slots=24)


def test_init_roundtrip():
    model, oracle = make(SMALL)
    vec = model.init_states()[0]
    assert model.decode(vec) == oracle.init_state()
    assert np.array_equal(model.encode(oracle.init_state()), vec)


def test_encode_decode_roundtrip_reachable():
    model, oracle = make(SMALL)
    for st in _collect_states(oracle, max_depth=4, cap=120):
        vec = model.encode(st)
        assert model.decode(vec) == st


def test_successor_sets_match_oracle():
    model, oracle = make(SMALL)
    states = _collect_states(oracle, max_depth=5, cap=150)
    vecs = np.stack([model.encode(st) for st in states])
    succs, valid, rank, ovf = jax.device_get(model.expand(vecs))
    assert not np.any(valid & ovf), "bag overflow on valid successor"
    for b, st in enumerate(states):
        got = sorted(
            oracle.serialize_full(model.decode(succs[b, a]))
            for a in range(model.A)
            if valid[b, a]
        )
        want = sorted(oracle.serialize_full(s2) for _l, s2 in oracle.successors(st))
        assert got == want, f"successor mismatch at state {b}: {st}"


def test_successor_counts_match_exactly():
    # valid-candidate multiplicity must equal the oracle's enabled-action count
    model, oracle = make(SMALL)
    states = _collect_states(oracle, max_depth=4, cap=80)
    vecs = np.stack([model.encode(st) for st in states])
    _, valid, _, _ = jax.device_get(model.expand(vecs))
    for b, st in enumerate(states):
        assert int(valid[b].sum()) == len(oracle.successors(st))


def test_fingerprint_permutation_invariance():
    model, oracle = make(SMALL)
    canon = Canonicalizer(model.layout, model.packer, symmetry=True)
    states = _collect_states(oracle, max_depth=4, cap=60)
    vecs = np.stack([model.encode(st) for st in states])
    fps = np.asarray(canon.fingerprints(vecs))
    perms = [[1, 0, 2], [2, 1, 0], [1, 2, 0]]
    for sigma in perms:
        pvecs = np.stack([model.encode(oracle.permute(st, sigma)) for st in states])
        pfps = np.asarray(canon.fingerprints(pvecs))
        assert np.array_equal(fps, pfps)


def test_fingerprint_matches_oracle_equivalence():
    # fp equality <=> oracle canonical-view equality, over a reachable sample
    model, oracle = make(SMALL)
    canon = Canonicalizer(model.layout, model.packer, symmetry=True)
    states = _collect_states(oracle, max_depth=4, cap=120)
    vecs = np.stack([model.encode(st) for st in states])
    fps = np.asarray(canon.fingerprints(vecs)).tolist()
    keys = [oracle.canon(st) for st in states]
    by_key = {}
    by_fp = {}
    for fp, key in zip(fps, keys):
        assert by_key.setdefault(key, fp) == fp, "same view, different fp"
        assert by_fp.setdefault(fp, key) == key, "fp collision between views"


def test_invariants_match_oracle():
    model, oracle = make(SMALL)
    states = _collect_states(oracle, max_depth=5, cap=150)
    vecs = np.stack([model.encode(st) for st in states])
    for name in ("NoLogDivergence", "LeaderHasAllAckedValues", "CommittedEntriesReachMajority"):
        ok = np.asarray(model.invariants[name](vecs))
        for b, st in enumerate(states):
            assert bool(ok[b]) == oracle.INVARIANTS[name](oracle, st), (name, b)


# ---- one-hot kernels against the gather / scatter idiom they replaced ----

# Raft.cfg's constants, flexraft5's, a RaftFsync policy, a net_faults set
# and the 2-server set of tests/test_expand_sparse.py: every kernel of
# models/raft.py, each under the flags that change its body.
ONEHOT_SETS = {
    "raft_cfg": RaftParams(
        n_servers=3, n_values=1, max_elections=2, max_restarts=0,
        msg_slots=32),
    "flexraft5": RaftParams(
        n_servers=5, n_values=2, max_elections=2, max_restarts=0,
        msg_slots=32, election_quorum=3, replication_quorum=4,
        strict_send_once=True, has_pending_response=False,
        trunc_term_mismatch=True),
    "raft_fsync": RaftParams(
        n_servers=3, n_values=1, max_elections=1, max_restarts=1,
        msg_slots=24, strict_send_once=True, has_pending_response=False,
        trunc_term_mismatch=True, has_fsync=True,
        fsync_leader_before_ae=True, fsync_leader_quorum=True,
        fsync_follower_reply=True),
    "net_faults": RaftParams(
        n_servers=3, n_values=1, max_elections=1, max_restarts=1,
        msg_slots=16, net_faults=True),
    "two_servers": RaftParams(
        n_servers=2, n_values=2, max_elections=2, max_restarts=0,
        msg_slots=16),
}
# the actions the walk below does not reach (MaxRestarts 0 disables
# Restart; five servers at quorum 4 commit nothing in a thinned walk):
# every other rank of the set's table has to fire, or the comparison
# means little
ONEHOT_SILENT = {
    "raft_cfg": {"Restart"},
    "flexraft5": {"Restart", "AdvanceCommitIndex"},
    "raft_fsync": {"RejectAppendEntriesRequest"},
    "net_faults": {"RejectAppendEntriesRequest"},
    "two_servers": {"Restart"},
}
WALK_ROWS = 64


def _walk(model, depth):
    """Reachable full states by the model's own expand: up to ``depth``
    levels from Init, each thinned to WALK_ROWS states spread over the
    level's new ones (exact-bytes dedup), every level kept."""
    W, rows = model.layout.W, WALK_ROWS
    level = np.asarray(model.init_states(), np.int32)
    seen = {s.tobytes() for s in level}
    kept = [level]
    for _ in range(depth):
        batch = np.concatenate(
            [level, np.repeat(level[-1:], rows - len(level), axis=0)])
        succs, valid, _, _ = jax.device_get(model.expand(batch))
        valid = np.array(valid)
        valid[len(level):] = False
        new = []
        for row in np.asarray(succs).reshape(-1, W)[valid.reshape(-1)]:
            key = row.tobytes()
            if key not in seen:
                seen.add(key)
                new.append(row)
        if not new:
            break
        step = -(-len(new) // rows)
        level = np.stack(new[::step])
        kept.append(level)
    return np.concatenate(kept)


def _expand_and_apply(model, states):
    """{name: array}: ``vmap(_expand1)`` of ``states`` in chunks of
    WALK_ROWS rows, and ``sparse_apply``'s rows of each chunk's enabled
    worklist under the loose plan (what the engines compile: guard pass,
    compaction, apply)."""
    C, A = WALK_ROWS, model.A
    plan = model.sparse_plan(C, C * A)

    @jax.jit
    def both(batch):
        succs, valid, rank, ovf = jax.vmap(model._expand1)(batch)
        gv, _, _ = jax.vmap(model.guards1)(batch)
        vflat = gv.reshape(-1)
        dst = jnp.where(vflat, jnp.cumsum(vflat) - 1, C * A)
        sel = (jnp.full((C * A + 1,), C * A, jnp.int32)
               .at[dst].set(jnp.arange(C * A, dtype=jnp.int32))[:C * A])
        rows, apply_ovf, _ = model.sparse_apply(
            batch, sel, sel < C * A, plan)
        return dict(succs=succs, valid=valid, rank=rank, ovf=ovf, sel=sel,
                    sparse_rows=rows, apply_ovf=apply_ovf[None])

    outs = []
    for off in range(0, len(states), C):
        batch = states[off:off + C]
        batch = np.concatenate(
            [batch, np.repeat(batch[-1:], C - len(batch), axis=0)])
        outs.append(jax.device_get(both(batch)))
    return {k: np.concatenate([o[k] for o in outs]) for k in outs[0]}


@pytest.mark.parametrize("name", sorted(ONEHOT_SETS))
def test_onehot_kernels_equal_the_gather_and_scatter_forms(name, monkeypatch):
    """models/raft.py reads and writes by models/base.py's one-hot
    helpers. With the five helpers swapped for the `arr[i]` reads and
    `.at[i]` writes they replaced (scripts/stage_diff.py's two tables:
    the parent's idiom from this tree), every successor row, valid, rank
    and ovf of the dense expand and every row of the sparse apply is the
    same, bit for bit, on walked states: no read leaned on a gather's
    clamping, no write on a scatter's dropping."""
    from raft_tpu.models import raft as raft_mod
    from scripts import stage_diff

    params = ONEHOT_SETS[name]
    model = RaftModel(params)
    states = _walk(model, depth=22)
    assert len(states) > 12 * WALK_ROWS
    got = _expand_and_apply(model, states)
    fired = {model.ACTION_NAMES[r]
             for r in np.unique(got["rank"][got["valid"]])}
    assert set(model.ACTION_NAMES) - fired == ONEHOT_SILENT[name]
    assert not got["apply_ovf"].any()

    for forms in (stage_diff.SCATTER_FORMS, stage_diff.GATHER_FORMS):
        for helper, form in forms.values():
            monkeypatch.setattr(raft_mod, helper, form)
    old = RaftModel(params)  # traced from here on, under the swap
    assert scatter_kernels(old) and gather_kernels(old)
    want = _expand_and_apply(old, states)
    for what in got:
        np.testing.assert_array_equal(got[what], want[what], err_msg=what)
