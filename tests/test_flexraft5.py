"""flexraft5: upstream's five-server FlexibleRaft.cfg (5 servers, 2 values,
election quorum 3, replication quorum 4, 120 permutations), at the
published constants and small depth, against the pure-Python oracle.

The cfg in the tree is reconstructed (its header says from what); the
twin that reads the upstream file itself is
test_flexible_raft.py::test_reference_flexible_cfg_loads, which needs a
checkout with the reference beside it.
"""

import collections
import filecmp
import os

import jax
import numpy as np
import pytest

from raft_tpu.models.registry import build_from_cfg, oracle_for_setup
from raft_tpu.utils.cfg import parse_cfg

from raft_tpu.ops.hashing import U64_MAX

from conftest import collect_states, eqns, indexed_ops, lower_dedup_canon

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = os.path.join(ROOT, "configs", "flexible-raft", "FlexibleRaft.cfg")
MSG_SLOTS = 32  # the benchmark cell's bag width


@pytest.fixture(scope="module")
def setup():
    return build_from_cfg(parse_cfg(CFG), msg_slots=MSG_SLOTS)


@pytest.fixture(scope="module")
def sample(setup):
    """The oracle, and a deterministic sample of full states reached by
    depth 6 (the first 150 in BFS order)."""
    oracle = oracle_for_setup(setup)
    return oracle, collect_states(oracle, max_depth=6, cap=150)


def test_in_tree_flexible_cfg_builds_the_published_constants(setup):
    from raft_tpu.ops.symmetry import Canonicalizer

    p = setup.model.p
    assert (p.n_servers, p.n_values) == (5, 2)
    assert (p.election_quorum, p.replication_quorum) == (3, 4)
    assert (p.max_elections, p.max_restarts) == (2, 0)  # assumed: Raft.cfg's
    assert p.strict_send_once and not p.has_pending_response
    assert p.trunc_term_mismatch
    assert setup.symmetry
    assert setup.invariants == ("LeaderHasAllAckedValues", "NoLogDivergence")
    canon = Canonicalizer.for_model(setup.model, symmetry=True)
    assert canon.P == 120 and canon.prune
    # the benchmark's configuration runs a copy of this very file
    assert filecmp.cmp(CFG, os.path.join(
        ROOT, "benchmark", "configs", "flexraft5", "FlexibleRaft.cfg"),
        shallow=False)


def test_successor_sets_match_oracle_at_five_servers(setup, sample):
    model = setup.model
    oracle, states = sample
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


def test_device_bfs_counts_match_oracle_to_depth_7(setup):
    from raft_tpu.checker.device_bfs import DeviceBFS

    depth = 7
    want = oracle_for_setup(setup).bfs(
        invariants=setup.invariants, symmetry=True, max_depth=depth)
    eng = DeviceBFS(setup.model, invariants=setup.invariants, symmetry=True,
                    chunk=512, frontier_cap=1 << 12)
    res = eng.run(max_depth=depth, collect_metrics=True)
    assert res.violation is None and want["violation"] is None
    assert [int(x) for x in res.depth_counts] == want["depth_counts"]
    assert (res.distinct, res.total, res.terminal) == (
        want["distinct"], want["total"], want["terminal"])
    rows = res.metrics
    assert [w["depth"] for w in rows] == list(range(1, depth + 1))
    assert not any(w["overflow_bits"] for w in rows)
    # the tier counters ride the wave row: lanes that took tier 3 are
    # representatives of the in-chunk dedup, and this cfg has them from
    # wave 1 (a timed-out server beside four tied ones)
    for w in rows:
        t3 = w["canon_tier3_local"] + w["canon_tier3_full"]
        assert 0 <= t3 <= w["generated"] - w["canon_dup_lanes"], w
    assert rows[0]["canon_tier3_local"] == rows[0]["generated"]
    assert res.stats["canon_tier3_local"] == sum(
        w["canon_tier3_local"] for w in rows) > 0
    assert res.stats["canon_tier3_full"] == sum(
        w["canon_tier3_full"] for w in rows)


def _tie_groups(sig_row):
    """Sizes of the groups of equal per-server signatures."""
    _vals, counts = np.unique(np.asarray(sig_row), return_counts=True)
    return sorted(counts.tolist())


def test_tiered_canon_is_brute_force_over_120_permutations(setup, sample):
    """Seeded rows with tie groups of 3, 4 and 5: the tiers equal the
    S!-table masked min lane for lane, permuted copies canonicalise
    alike, and the tier counters account for every lane once."""
    from test_symmetry_v3 import canon_pair

    model = setup.model
    oracle, states = sample
    rng = np.random.default_rng(26)
    rows = [model.encode(st) for st in states[:60]]
    rows += [np.asarray(model.init_states()[0])] * 4  # all five tied
    for st in states[:60:3]:  # another member of the same orbit
        sigma = [int(x) for x in rng.permutation(5)]
        rows.append(model.encode(oracle.permute(st, sigma)))
    batch = np.stack(rows).astype(np.int32)
    auto, full = canon_pair(model)
    assert full.P == 120

    fa = np.asarray(auto.fingerprints(batch))
    assert np.array_equal(fa, np.asarray(full.fingerprints(batch)))
    n0 = 64
    assert np.array_equal(fa[n0:], fa[:60:3])  # orbit-invariant

    # an independent classification, from the signatures alone
    sig = np.asarray(auto._signatures(batch[:, : auto.VL]))
    groups = [_tie_groups(r) for r in sig]
    largest = [g[-1] for g in groups]
    assert {3, 4, 5} <= set(largest), sorted(set(largest))
    valid = np.ones(len(batch), bool)
    valid[-3:] = False  # lanes that are not to be counted
    sel = np.flatnonzero(valid)
    fps, tiers = auto._canon_view(batch[:, : auto.VL], valid)
    assert np.array_equal(np.asarray(fps)[sel], fa[sel])
    tiers = [int(x) for x in np.asarray(tiers)]
    assert tiers == [sum(3 <= largest[i] < 5 for i in sel),
                     sum(largest[i] == 5 for i in sel)]
    assert min(tiers) > 0
    tier12_only = sum(largest[i] <= 2 for i in sel)
    assert tier12_only + sum(tiers) == len(sel)

    # through the in-chunk dedup: one canon per distinct raw view, on
    # its first valid lane, every other lane masked
    fps_d, n_dup, tiers_d = auto.fingerprints_dedup(batch, valid)
    raw = np.asarray(auto.raw_fingerprints(batch))
    _u, first = np.unique(raw[sel], return_index=True)
    reps = sel[first]
    assert np.array_equal(np.asarray(fps_d)[reps], fa[reps])
    rest = np.setdiff1d(np.arange(len(batch)), reps)
    assert np.all(np.asarray(fps_d)[rest] == U64_MAX)
    assert int(n_dup) == len(sel) - len(reps) >= 3
    assert [int(x) for x in np.asarray(tiers_d)] == [
        sum(3 <= largest[i] < 5 for i in reps),
        sum(largest[i] == 5 for i in reps)]


@pytest.fixture(scope="module")
def dedup_canon_lowered(setup):
    """Lowered text of the engines' canon at five servers."""
    return lower_dedup_canon(setup.model)


@pytest.mark.parametrize(
    "scope", ["inchunk", "tier12", "tier3_local", "tier3_full"])
def test_canon_scopes_nest_as_siblings_at_five_servers(
        dedup_canon_lowered, scope):
    """What scripts/stage_split.py splits `canon` by: each scope is on
    some op of the engines' canon, and the tiers, which run in the body
    of the in-chunk dedup's loop, are not booked under `inchunk`."""
    assert f"/{scope}/" in dedup_canon_lowered
    assert "inchunk/tier" not in dedup_canon_lowered
    assert "/inchunk/while/" not in dedup_canon_lowered


def test_inchunk_dedup_indexes_rows_alone_at_five_servers(dedup_canon_lowered):
    """Under `canon/inchunk` nothing is read or written a lane at a time
    (PR 54): its two gathers read rows, the raw hash's view columns and
    a block's representatives, and it scatters nothing. The tiers, which
    run on a block of representatives in the loop's body, keep their own
    (`tier3_local`'s pattern reads, the tier-3 drains' writes)."""
    ops = indexed_ops(dedup_canon_lowered)
    inchunk = [op for op in ops if "/inchunk/" in op[2]]
    assert [(kind, len(dims)) for kind, dims, _ in inchunk] == [
        ("gather", 2)] * 2, inchunk
    assert {stack.split("/")[-4] for kind, _dims, stack in ops
            if kind == "scatter"} == {"tier3_local", "tier3_full"}


def test_tier12_looks_servers_up_without_a_gather(setup):
    """A five-entry table is read by compares and selects (PR 29): a
    gather costs the chip nanoseconds a lane however small its table,
    and thirteen of them were 57 % of the device's time in
    flexraft5-wide. The parent's jaxpr of what `canon/tier12` scopes had
    31 gathers from a [B, S] operand: in `_signatures` 24 to [B, 32] and
    6 to [B, 5], in `_tier_pre` the sorted signatures. Nothing is
    compiled."""
    from raft_tpu.ops.symmetry import Canonicalizer

    canon = Canonicalizer.for_model(setup.model, symmetry=True)
    B, S = 64, canon.S
    assert S == 5
    closed = jax.make_jaxpr(
        lambda view: canon._tier_pre(view, canon._signatures(view)))(
        jax.ShapeDtypeStruct((B, canon.VL), np.int32))
    names = collections.Counter(e.primitive.name for e in eqns(closed.jaxpr))
    assert names["select_n"] > 0 and names["reduce_sum"] > 0  # the walk sees in
    from_table = [e for e in eqns(closed.jaxpr)
                  if e.primitive.name == "gather"
                  and e.invars[0].aval.shape == (B, S)]
    assert not from_table, [str(e.outvars[0].aval) for e in from_table]


def test_no_growth_after_the_wave_that_max_depth_ends():
    """Raft.cfg's constants, depth 7: the last wave writes 113 rows, and
    3 x 113 > 256 would grow the frontier for a wave that never runs,
    leave FCAP grown, and make the next run() compile a new wave
    program."""
    from raft_tpu.checker.device_bfs import DeviceBFS
    from raft_tpu.models.raft import RaftParams, cached_model

    p = RaftParams(n_servers=3, n_values=1, max_elections=2, max_restarts=0,
                   msg_slots=24)
    eng = DeviceBFS(
        cached_model(p), invariants=("NoLogDivergence",), symmetry=True,
        chunk=64, frontier_cap=256)
    first = eng.run(max_depth=7)
    assert [int(x) for x in first.depth_counts][-2:] == [65, 113]
    assert first.depth_counts[-1] * eng.HEADROOM > 256  # would have grown
    assert eng.FCAP == 256
    again = eng.run(max_depth=7)
    assert eng.FCAP == 256
    # the process's count of programs loaded stands where it was
    assert again.stats["programs_loaded"] == first.stats["programs_loaded"]
    assert again.stats["run_compiles"] == 0
    assert again.depth_counts == first.depth_counts
    # a run that goes on does grow, between the same two waves
    deeper = eng.run(max_depth=8)
    assert eng.FCAP > 256 and int(deeper.depth_counts[-1]) == 205
