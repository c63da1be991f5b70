"""Property tests for the canonical fingerprint (ops/symmetry.py):
sort-free multiset bag hashing + signature-pruned permutation min, plus
the v5 additions — k-round 1-WL signature refinement, the tie-group-
local tier 3, and the raw-keyed canon memo.

The correctness contract (module docstring there):
  - the per-server signature is permutation-EQUIVARIANT at every
    refinement depth,
  - the fast tiered path is bit-identical to the brute-force masked min
    over the full S! table (mode="full") at the SAME refinement depth —
    for every tier route (argsort-only, swap products, tie-group-local
    blocks, full-table drain),
  - memoization is value-preserving: a memo hit returns exactly the
    cold-canon fingerprint, under any table size (including constant
    eviction at tiny capacities),
  - fingerprints are orbit-invariant and separate orbits exactly like
    the oracle's canonical view (TLC's SYMMETRY semantics,
    ``Raft.tla:116``).
"""

import itertools

import jax
import numpy as np
import pytest

from raft_tpu.models.pull_raft import PullRaftModel, PullRaftParams
from raft_tpu.models.raft import RaftModel, RaftParams
from raft_tpu.oracle.pull_oracle import PullRaftOracle
from raft_tpu.oracle.raft_oracle import RaftOracle
from raft_tpu.ops.hashing import U64_MAX
from raft_tpu.ops.symmetry import Canonicalizer

from conftest import (
    collect_states, first_lane_masked, indexed_ops, jaxpr_digest,
    lower_dedup_canon, scope_paths)


def raft3():
    p = RaftParams(n_servers=3, n_values=1, max_elections=1, max_restarts=1,
                   msg_slots=24)
    return RaftModel(p), RaftOracle(p.n_servers, p.n_values, p.max_elections,
                                    p.max_restarts)


def raft5():
    p = RaftParams(n_servers=5, n_values=2, max_elections=2, max_restarts=0,
                   msg_slots=48)
    return RaftModel(p), RaftOracle(p.n_servers, p.n_values, p.max_elections,
                                    p.max_restarts)


def pull3():
    p = PullRaftParams(n_servers=3, n_values=1, max_elections=2,
                       max_restarts=0, msg_slots=24)
    return (PullRaftModel(p),
            PullRaftOracle(p.n_servers, p.n_values, p.max_elections,
                           p.max_restarts))


CASES = {"raft3": raft3, "raft5": raft5, "pull3": pull3}


def canon_pair(model, refine_rounds: int = 3):
    auto = Canonicalizer.for_model(model, symmetry=True,
                                   refine_rounds=refine_rounds)
    full = Canonicalizer(
        model.layout, model.packer,
        msg_server_fields=getattr(model, "msg_server_fields",
                                  ("msource", "mdest")),
        msg_server_nil_fields=getattr(model, "msg_server_nil_fields", ()),
        msg_perm_spec=getattr(model, "msg_perm_spec", None),
        symmetry=True, mode="full", refine_rounds=refine_rounds,
    )
    return auto, full


def states_of(name, depth=4, cap=150):
    model, oracle = CASES[name]()
    states = collect_states(oracle, max_depth=depth, cap=cap)
    vecs = np.stack([model.encode(st) for st in states])
    return model, oracle, states, vecs


@pytest.mark.parametrize("name", list(CASES))
def test_auto_equals_bruteforce(name):
    model, _oracle, _states, vecs = states_of(name)
    auto, full = canon_pair(model)
    fa = np.asarray(auto.fingerprints(vecs))
    fb = np.asarray(full.fingerprints(vecs))
    assert np.array_equal(fa, fb)
    assert not np.any(fa == U64_MAX)


@pytest.mark.parametrize("name", ["raft3", "raft5"])
def test_auto_equals_bruteforce_tie_heavy(name):
    # a batch of replicated Init states is 100% signature-tied with
    # S-sized (all-tied) groups — the full-S!-table drain — while the
    # reachable states mix in argsort-only, swap-product and tie-group-
    # local lanes; the adaptive blocked tier 3 must stay bit-identical
    # no matter how many heavy lanes a chunk carries (the retired
    # static-budget design fell off a whole-batch lax.cond cliff here)
    model, _oracle, _states, vecs = states_of(name, depth=3, cap=40)
    reps = np.repeat(model.init_states(), 200, axis=0)
    batch = np.concatenate([reps, vecs, reps], axis=0)
    auto, full = canon_pair(model)
    fa = np.asarray(auto.fingerprints(batch))
    fb = np.asarray(full.fingerprints(batch))
    assert np.array_equal(fa, fb)


@pytest.mark.parametrize("rounds", [1, 2, 3])
def test_refinement_rounds_bit_identical_to_bruteforce(rounds):
    # the k-round 1-WL refinement changes WHICH permutations are
    # admissible (and so the fingerprint VALUES of tied states), but at
    # every depth the pruned tiered path must equal the full-table
    # masked min computed at the SAME depth
    model, _oracle, _states, vecs = states_of("raft5", depth=3, cap=60)
    reps = np.repeat(model.init_states(), 50, axis=0)
    batch = np.concatenate([reps, vecs], axis=0)
    auto, full = canon_pair(model, refine_rounds=rounds)
    fa = np.asarray(auto.fingerprints(batch))
    fb = np.asarray(full.fingerprints(batch))
    assert np.array_equal(fa, fb)
    assert not np.any(fa == U64_MAX)


def test_refinement_depth_preserves_partition():
    # deeper refinement only shrinks tie groups WITHIN an orbit: the
    # induced equality partition over a reachable sample must not move
    # (values may — the admissible-set minimum changes representative)
    model, _oracle, _states, vecs = states_of("raft5", depth=3, cap=120)
    parts = []
    for rounds in (1, 2, 3):
        auto, _ = canon_pair(model, refine_rounds=rounds)
        fps = np.asarray(auto.fingerprints(vecs)).tolist()
        first = {}
        parts.append([first.setdefault(fp, i) for i, fp in enumerate(fps)])
    assert parts[0] == parts[1] == parts[2]


def test_tie_group_local_lanes_exercised_and_bit_identical():
    # the tie-group-local tier must actually fire (lanes whose largest
    # tie group is >= 3 but not all-tied) alongside full-table lanes,
    # and both routes must match brute force lane-for-lane
    model, _oracle, _states, vecs = states_of("raft5", depth=2, cap=120)
    reps = np.repeat(model.init_states(), 30, axis=0)
    batch = np.concatenate([vecs, reps], axis=0).astype(np.int32)
    auto, full = canon_pair(model)
    view = batch[:, : auto.VL]
    sig = auto._signatures(view)
    _fp, _sigma, _pat, is_local, is_full = auto._tier_pre(view, sig)
    is_local = np.asarray(is_local)
    is_full = np.asarray(is_full)
    assert is_local.sum() > 0, "no tie-group-local lanes in the sample"
    assert is_full.sum() > 0, "no full-table lanes in the sample"
    fa = np.asarray(auto.fingerprints(batch))
    fb = np.asarray(full.fingerprints(batch))
    assert np.array_equal(fa, fb)
    # the local route in particular (the new code path) is bit-identical
    assert np.array_equal(fa[is_local], fb[is_local])


@pytest.mark.parametrize("name", list(CASES))
def test_orbit_invariance(name):
    model, oracle, states, vecs = states_of(name)
    auto, _ = canon_pair(model)
    fps = np.asarray(auto.fingerprints(vecs))
    S = model.layout.n_servers
    rng = np.random.default_rng(7)
    sigmas = [list(rng.permutation(S)) for _ in range(4)]
    for sigma in sigmas:
        pvecs = np.stack(
            [model.encode(oracle.permute(st, sigma)) for st in states]
        )
        pfps = np.asarray(auto.fingerprints(pvecs))
        assert np.array_equal(fps, pfps), f"sigma={sigma}"


@pytest.mark.parametrize("name", ["raft3", "pull3"])
def test_signature_equivariance(name):
    # sig(perm(x))[sigma[i]] == sig(x)[i] for every reachable sample state
    model, oracle, states, vecs = states_of(name)
    auto, _ = canon_pair(model)
    S = model.layout.n_servers
    sig = np.asarray(auto._signatures(vecs[:, : auto.VL]))
    for sigma in itertools.permutations(range(S)):
        pvecs = np.stack(
            [model.encode(oracle.permute(st, list(sigma))) for st in states]
        )
        psig = np.asarray(auto._signatures(pvecs[:, : auto.VL]))
        assert np.array_equal(psig[:, list(sigma)], sig), f"sigma={sigma}"


def kraft3():
    from raft_tpu.models.kraft import KRaftParams, cached_model
    from raft_tpu.oracle.kraft_oracle import KRaftOracle

    p = KRaftParams(n_servers=3, n_values=1, max_elections=2, max_restarts=0,
                    msg_slots=56)
    return cached_model(p), KRaftOracle(p.n_servers, p.n_values,
                                        p.max_elections, p.max_restarts)


def flexraft5():
    from raft_tpu.models.registry import build_from_cfg, oracle_for_setup
    from raft_tpu.utils.cfg import parse_cfg

    from test_flexraft5 import CFG, MSG_SLOTS

    setup = build_from_cfg(parse_cfg(CFG), msg_slots=MSG_SLOTS)
    return setup.model, oracle_for_setup(setup)


# name -> (builder, depth and cap of the sample); kraft3's mleader is the
# tree's one server_nil message field (first not Nil at depth 6, state 382),
# flexraft5 is tests/test_flexraft5.py's sample
LOOKUP_CASES = {"raft3": (raft3, 4, 150), "pull3": (pull3, 4, 150),
                "kraft3": (kraft3, 6, 700), "raft5": (raft5, 4, 150),
                "flexraft5": (flexraft5, 6, 150)}


def _lookup_by_gather(t, idx):
    """The plain reference of ``symmetry._lookup``: the
    ``take_along_axis`` gather it replaced (PR 29)."""
    import jax.numpy as jnp

    return jnp.take_along_axis(t, idx, axis=1)


@pytest.fixture(scope="module", params=list(LOOKUP_CASES))
def lookup_batch(request):
    """A model and a view batch with Init (all tied, every votedFor Nil),
    reachable rows (empty message slots: the index is out of range before
    the clip) and full bags."""
    build, depth, cap = LOOKUP_CASES[request.param]
    model, oracle = build()
    states = collect_states(oracle, max_depth=depth, cap=cap)
    vecs = np.stack([model.encode(st) for st in states])
    batch = np.concatenate([model.init_states(), vecs]).astype(np.int32)
    # every looked-up field names servers and, where it can, Nil
    auto = Canonicalizer.for_model(model, symmetry=True)
    words = [batch[:, sl] for sl in auto._msg_word_sls]
    for fname, kind in auto.msg_perm_spec:
        val = np.asarray(auto._unpack_key(words, fname))
        want = auto.S + (kind == "server_nil")
        assert len(np.unique(val)) == want, (fname, kind)
    return model, batch


@pytest.mark.parametrize("rounds", [1, 2, 3])
def test_signatures_bit_equal_to_gather_reference(lookup_batch, rounds,
                                                  monkeypatch):
    from raft_tpu.ops import symmetry

    model, batch = lookup_batch
    auto = Canonicalizer.for_model(model, symmetry=True, refine_rounds=rounds)
    view = batch[:, : auto.VL]

    def tier12():
        """The signatures and, where the layout has tiers, what tier 1
        makes of them: it sorts them through the same lookup."""
        sig = auto._signatures(view)
        return [np.asarray(x) for x in (
            sig, *(auto._tier_pre(view, sig) if auto.prune else ()))]

    got = tier12()
    monkeypatch.setattr(symmetry, "_lookup", _lookup_by_gather)
    want = tier12()
    sig = got[0]
    assert sig.dtype == np.uint64 and sig.shape == (len(batch), auto.S)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert np.array_equal(a, b)
    # the sample is not degenerate: Init is all tied, later rows are not
    assert len(set(sig[0].tolist())) == 1
    assert any(len(set(row.tolist())) > 1 for row in sig)


@pytest.mark.parametrize("name", ["raft3", "raft5"])
def test_fp_equality_matches_oracle_canon(name):
    # fp equality <=> oracle canonical-view equality on a reachable sample
    model, oracle, states, vecs = states_of(name, depth=4, cap=200)
    auto, _ = canon_pair(model)
    fps = np.asarray(auto.fingerprints(vecs)).tolist()
    keys = [oracle.canon(st) for st in states]
    by_key, by_fp = {}, {}
    for fp, key in zip(fps, keys):
        assert by_key.setdefault(key, fp) == fp, "same view, different fp"
        assert by_fp.setdefault(fp, key) == key, "fp collision between views"


def test_bag_multiset_hash_slot_order_free():
    # two encodings of the same bag in different slot order must hash
    # identically (the v3 bag hash is a multiset hash, no slot sort)
    model, _oracle = raft3()
    auto, _ = canon_pair(model)
    vec = np.asarray(model.init_states()[0:1]).copy()
    # synthesize: swap two occupied message slots if present; Init has an
    # empty bag, so craft one state with two sends via the oracle
    _model, oracle2 = raft3()
    st = oracle2.init_state()
    for _lab, s2 in oracle2.successors(st):
        if len(s2["messages"]) >= 2:
            st = s2
            break
    else:  # walk two steps to get >=2 distinct records
        for _lab, s2 in oracle2.successors(st):
            for _lab2, s3 in oracle2.successors(s2):
                if len(s3["messages"]) >= 2:
                    st = s3
                    break
            if len(st["messages"]) >= 2:
                break
    assert len(st["messages"]) >= 2
    vec = model.encode(st)[None, :]
    # swap the first two occupied slots across all bag words + cnt
    lay = model.layout
    sls = [lay.sl(f.name) for f in lay.fields.values()
           if f.kind in ("msg_hi", "msg_lo", "msg_word", "msg_cnt")]
    swapped = vec.copy()
    for sl in sls:
        seg = swapped[:, sl].copy()
        seg[:, [0, 1]] = seg[:, [1, 0]]
        swapped[:, sl] = seg
    f1 = np.asarray(auto.fingerprints(vec))
    f2 = np.asarray(auto.fingerprints(swapped))
    assert np.array_equal(f1, f2)


def _dedup(auto, batch, valid):
    """``fingerprints_dedup`` as numpy: (fps, n_dup, [local, full])."""
    fps, n_dup, tiers = auto.fingerprints_dedup(batch, valid)
    return np.asarray(fps), int(n_dup), [int(x) for x in np.asarray(tiers)]


@pytest.mark.parametrize("name", ["raft3", "raft5"])
def test_dedup_equals_plain(name):
    # one canon per distinct raw view: the first lane of a view,
    # duplicated and Init-repeated rows among them, carries what the
    # plain entry gives it, and every later lane of the view is masked
    model, _oracle, _states, vecs = states_of(name, depth=3, cap=80)
    reps = np.repeat(model.init_states(), 40, axis=0)
    batch = np.concatenate([vecs, reps, vecs], axis=0).astype(np.int32)
    auto, _ = canon_pair(model)
    valid = np.ones(len(batch), dtype=bool)
    fps, n_dup, tiers = _dedup(auto, batch, valid)
    want, want_dup = first_lane_masked(auto, batch, valid)
    assert np.array_equal(fps, want)
    assert fps[0] == np.asarray(auto.fingerprints(batch[:1]))[0] != U64_MAX
    assert np.all(fps[-len(vecs):] == U64_MAX)
    assert n_dup == want_dup >= len(vecs) + 39
    assert sum(tiers) <= len(batch) - n_dup


def test_dedup_invalid_lanes_masked():
    model, _oracle, _states, vecs = states_of("raft3", depth=3, cap=60)
    auto, _ = canon_pair(model)
    _u, first = np.unique(
        np.asarray(auto.raw_fingerprints(vecs)), return_index=True)
    vecs = vecs[np.sort(first)]  # states that differ past the view go
    assert len(vecs) > 20
    batch = np.concatenate([vecs, vecs[:20]]).astype(np.int32)
    valid = np.arange(len(batch)) % 3 != 0
    fps, n_dup, _tiers = _dedup(auto, batch, valid)
    assert np.all(fps[~valid] == U64_MAX)
    want, want_dup = first_lane_masked(auto, batch, valid)
    assert np.array_equal(fps, want)
    # an invalid lane is nobody's duplicate and nobody's representative:
    # lane 0 is invalid, so its view's fingerprint is on its copy, and a
    # valid lane's copy is masked
    plain = np.asarray(auto.fingerprints(batch))
    assert not valid[0] and valid[len(vecs)]
    assert fps[len(vecs)] == plain[0] != U64_MAX
    assert valid[1] and valid[len(vecs) + 1] and fps[len(vecs) + 1] == U64_MAX
    assert n_dup == want_dup > 0
    none = np.zeros(len(batch), bool)
    fps, n_dup, tiers = _dedup(auto, batch, none)
    assert np.all(fps == U64_MAX) and n_dup == 0 and tiers == [0, 0]


@pytest.mark.parametrize("name", ["raft3", "raft5"])
def test_dedup_one_view_many_times(name):
    # a chunk that is one raw view B times over is one representative,
    # lane 0: Init is all-tied, so that one lane takes the S!-table min,
    # and the loop makes one trip
    model, _oracle = CASES[name]()
    B = 200
    batch = np.repeat(model.init_states()[:1], B, axis=0).astype(np.int32)
    auto, _ = canon_pair(model)
    fps, n_dup, tiers = _dedup(auto, batch, np.ones(B, bool))
    assert n_dup == B - 1 and tiers == [0, 1]
    assert fps[0] == np.asarray(auto.fingerprints(batch[:1]))[0]
    assert np.all(fps[1:] == U64_MAX)


def test_dedup_more_representatives_than_a_block():
    # 150 lanes drain in blocks of 64: three trips of the loop, the last
    # block written at the end of a 192-slot buffer and part filled;
    # shuffled, so the return sort has a permutation to undo
    model, _oracle, _states, vecs = states_of("raft5", depth=4, cap=150)
    rng = np.random.default_rng(33)
    batch = vecs[rng.permutation(len(vecs))[:150]].astype(np.int32)
    batch[100:110] = batch[:10]
    auto, _ = canon_pair(model)
    valid = np.ones(len(batch), bool)
    assert len(batch) == 150
    fps, n_dup, _tiers = _dedup(auto, batch, valid)
    want, want_dup = first_lane_masked(auto, batch, valid)
    assert n_dup == want_dup == 10
    assert len(batch) - n_dup > 2 * 64
    assert np.array_equal(fps, want)
    assert np.all(fps[100:110] == U64_MAX) and not np.any(fps[:100] == U64_MAX)


B_NEW = 64  # one shape a canon: one program of each for the 24 cases


def _canon_and_orbits(name):
    """(canon, rows [n, W] of distinct canonical classes, their images
    under a permutation that moves the raw view), for the in-chunk
    dedup's two callers."""
    if name == "raft3":
        model, oracle, states, _vecs = states_of("raft3", depth=8, cap=1200)
        canon = Canonicalizer.for_model(model, symmetry=True)
        image = [oracle.permute(st, [1, 2, 0]) for st in states]
    else:
        from test_kraft_reconfig import small_oracle

        (model,) = _kraftrc_small()
        oracle = small_oracle()
        states = collect_states(oracle, max_depth=4, cap=150)
        canon = model.make_canonicalizer(True)
        # hosts 0 and 1 hold the initial cluster's two servers
        image = [oracle.permute(st, [1, 0, 2], [0]) for st in states]
    rows = np.stack([model.encode(st) for st in states]).astype(np.int32)
    images = np.stack([model.encode(st) for st in image]).astype(np.int32)
    fps = np.asarray(canon.fingerprints(rows))
    assert np.array_equal(fps, np.asarray(canon.fingerprints(images)))
    _u, first = np.unique(fps, return_index=True)
    keep = np.sort(first)
    moved = keep[np.asarray(canon.raw_fingerprints(rows))[keep]
                 != np.asarray(canon.raw_fingerprints(images))[keep]]
    assert len(moved) >= B_NEW // 2 and len(keep) >= B_NEW
    return canon, rows[keep], rows[moved], images[moved]


@pytest.fixture(scope="module", params=["raft3", "kraftrc_slot_canon"])
def new_lane_case(request):
    canon, rows, moved, images = _canon_and_orbits(request.param)
    B = B_NEW
    batches = {
        "one_view_b_times": np.repeat(rows[:1], B, axis=0),
        "distinct_views": rows[:B],
    }
    # lanes 2k and 2k + 1: two raw views of one canonical class, the
    # higher lane's raw key the smaller, so the raw sort puts the higher
    # lane's segment first and the lower lane must still be the new one
    pair = np.stack([moved[: B // 2], images[: B // 2]], axis=1)
    raw = np.asarray(canon.raw_fingerprints(pair.reshape(B, -1))).reshape(
        B // 2, 2)
    swap = raw[:, 0] < raw[:, 1]
    pair[swap] = pair[swap][:, ::-1]
    batches["one_class_two_raw_views_higher_lane_smaller_key"] = (
        pair.reshape(B, -1))
    raw = np.asarray(canon.raw_fingerprints(pair.reshape(B, -1)))
    assert np.all(raw[0::2] > raw[1::2])
    return canon, jax.jit(canon.fingerprints_dedup), batches


@pytest.mark.parametrize("runs", [False, True], ids=["no_runs", "runs"])
@pytest.mark.parametrize("invalid", [False, True], ids=["valid", "invalid"])
@pytest.mark.parametrize("batch", [
    "one_view_b_times", "distinct_views",
    "one_class_two_raw_views_higher_lane_smaller_key"])
def test_first_new_of_the_masked_lanes_is_first_new_of_every_lanes_canon(
        new_lane_case, batch, invalid, runs):
    """What lets a duplicate come back masked: `util.first_new`, the one
    consumer, marks the same lanes new, lane for lane, on the in-chunk
    dedup's output as on the plain canon of every lane, against nothing
    and against a seen run that holds some of the chunk's fingerprints;
    and a lane that is new carries the canon of its own row."""
    from raft_tpu.checker.util import first_new

    canon, dedup, batches = new_lane_case
    rows = batches[batch]
    B = len(rows)
    valid = np.arange(B) % 3 != 0 if invalid else np.ones(B, bool)
    masked, n_dup, _tiers = jax.device_get(dedup(rows, valid))
    want, want_dup = first_lane_masked(canon, rows, valid)
    assert np.array_equal(masked, want) and int(n_dup) == want_dup
    plain = np.where(valid, np.asarray(canon.fingerprints(rows)),
                     np.uint64(U64_MAX))
    seen = ()
    if runs:
        run = np.full(2 * B, np.uint64(U64_MAX))
        held = np.unique(plain[valid])[::3]
        run[: len(held)] = held
        seen = (jax.numpy.asarray(run),)
    occ = np.ones(len(seen), bool)
    new_masked = np.asarray(first_new(jax.numpy.asarray(masked), occ, seen))
    new_plain = np.asarray(first_new(jax.numpy.asarray(plain), occ, seen))
    assert np.array_equal(new_masked, new_plain)
    assert np.array_equal(masked[new_masked], plain[new_plain])
    assert not np.any(new_masked & ~valid)
    # the host's word for it: the lowest valid lane of each fingerprint
    # the run does not hold
    lowest = {}
    for lane in np.flatnonzero(valid)[::-1]:
        lowest[plain[lane]] = lane
    held = set(seen[0].tolist()) if runs else set()
    assert sorted(np.flatnonzero(new_plain)) == sorted(
        lane for fp, lane in lowest.items() if fp not in held)
    if batch.startswith("one_class") and not runs:
        assert new_plain.sum() == len({plain[i] for i in np.flatnonzero(valid)})
        assert not np.any(new_plain[1::2] & valid[0::2])


def _joint4():
    from raft_tpu.models.registry import build_from_cfg
    from raft_tpu.utils.cfg import parse_cfg

    from test_joint4 import CFG

    return (build_from_cfg(parse_cfg(CFG)).model,)


@pytest.mark.parametrize(
    "build", [raft3, flexraft5, _joint4, kraft3],
    ids=["raft3", "flexraft5", "joint4", "kraft3"])
def test_inchunk_dedup_lowers_to_sorts_alone(build):
    """Strict: a per-lane write costs the chip by the chunk's capacity
    (the un-sort, the table's write and the loop's ``.at[pos].set`` were
    28 % of raft3-wide's device time, PERF.md section 6, PR 33). Nothing
    under ``canon/inchunk`` is a scatter, the program takes the rows and
    their mask and no table, and the three sorts are there. Nor is a
    per-lane read: the two gathers under the scope read rows (the raw
    hash's view columns, by a constant; a block's representatives), and
    the fill that read a fingerprint a lane went in PR 54."""
    text = lower_dedup_canon(build()[0])
    under = [ln for ln in text.splitlines() if "/inchunk/" in ln]
    assert any("/inchunk/sort" in ln for ln in under)  # the walk sees in
    assert not [ln for ln in under if "scatter" in ln]
    inchunk = [op for op in indexed_ops(text) if "/inchunk/" in op[2]]
    assert [(kind, len(dims)) for kind, dims, _ in inchunk] == [
        ("gather", 2)] * 2, inchunk
    (main,) = [ln for ln in text.splitlines()
               if "func.func public @main" in ln]
    assert main.count("%arg") == 2 and "ui64" not in main.split("->")[0], main
    assert text.count("stablehlo.sort") >= 3


def test_canon_stage_at_raft3s_chunk_shape_indexes_rows_alone():
    """The engines' canon stage as `raft3-wide` runs it, 65,536 compacted
    lanes a chunk-step (nothing compiled): no scatter, and no gather
    from a one-dimensional array, each a serial pass of 4.6 and 7.1 ns a
    lane on the chip (the fill `canon_rep[rank]` and the loop's
    `orderp[pos]` were two such until PR 54, 7.4 % of the cell's device
    time). Three servers have no tiers, so this is the whole stage."""
    from raft_tpu.obs import stage

    model = raft3()[0]
    canon = Canonicalizer.for_model(model, symmetry=True)
    text = jax.jit(stage("canon")(canon.fingerprints_dedup)).lower(
        jax.ShapeDtypeStruct((65536, model.layout.W), np.int32),
        jax.ShapeDtypeStruct((65536,), bool)).as_text(debug_info=True)
    ops = indexed_ops(text)
    assert [op for op in ops if "/inchunk/" in op[2]]  # the walk sees in
    assert not [op for op in ops if op[0] == "scatter" or len(op[1]) < 2]
    # the one gather of the stage by a traced index: a block's rows
    assert [dims for _kind, dims, stack in ops
            if "/while/body/inchunk/" in stack] == [["65537", "117"]]


def _kraftrc_small():
    from raft_tpu.models import kraft_reconfig

    from test_kraft_reconfig import SMALLP

    return (kraft_reconfig.cached_model(SMALLP),)


# (equations, digest) of `Canonicalizer.fingerprints_dedup`'s jaxpr over a
# 256-lane batch (conftest.jaxpr_digest), pinned at PR 54, whose in-chunk
# dedup traces 11 equations fewer than PR 40's tree (ad5b664: 718 and
# 5536), where it was that method's own lines: the fill's `cumsum` and
# gather, `argsort`'s iota and the loop's index gather went, one sort of
# one key came
PARENT_JAXPR = {"raft3": (707, "0ddcda304be4b4f4"),
                "flexraft5": (5525, "a46fe6185be9a9bc")}


@pytest.mark.parametrize("name, build", [
    ("raft3", raft3), ("flexraft5", flexraft5),
    ("kraftrc_slot_canon", _kraftrc_small)])
def test_both_canons_run_their_permutations_under_the_one_inchunk_dedup(
        name, build):
    """`ops.symmetry.fingerprints_by_raw_view` is the tree's one in-chunk
    dedup: `Canonicalizer` and the slot canon of `KRaftWithReconfig` both
    lower to it (`canon/inchunk` beside the canon's own scopes, which run
    in its loop's body and stay its siblings), with no per-lane write of
    its own; and what `Canonicalizer` traces is what it traced at the
    pin, equation for equation."""
    from raft_tpu.obs import stage

    model = build()[0]
    canon = Canonicalizer.for_model(model, symmetry=True)
    args = (jax.ShapeDtypeStruct((256, model.layout.W), np.int32),
            jax.ShapeDtypeStruct((256,), bool))
    text = jax.jit(stage("canon")(canon.fingerprints_dedup)).lower(
        *args).as_text(debug_info=True)
    paths = scope_paths(text)
    assert ("canon", "inchunk") in paths
    assert "/inchunk/sort" in text and "/inchunk/while/" not in text
    assert not [ln for ln in text.splitlines()
                if "/inchunk/" in ln and "scatter" in ln]
    if name in PARENT_JAXPR:
        assert type(canon) is Canonicalizer
        # three servers have no tiers: the full table on every lane
        own = {"tier3_full"} | (
            {"tier12", "tier3_local"} if name == "flexraft5" else set())
        assert jaxpr_digest(jax.make_jaxpr(canon.fingerprints_dedup)(
            *args)) == PARENT_JAXPR[name]
    else:
        assert type(canon).__name__ == "SlotCanonicalizer"
        own = {"slot_sort", "slot_remap", "slot_bag", "slot_hash"}
        assert "scatter" not in text
        assert "(slot_" not in text  # no scope opened under a vmap
    assert {p[1] for p in paths if len(p) > 1} == own | {"inchunk"}


def test_seeded_family_differs():
    # the audit relies on seeded families failing independently: same
    # states, different seed => (near-certainly) different fingerprints
    model, _oracle, _states, vecs = states_of("raft3")
    a0 = Canonicalizer.for_model(model, symmetry=True, seed=0)
    a1 = Canonicalizer.for_model(model, symmetry=True, seed=0x5EED)
    f0 = np.asarray(a0.fingerprints(vecs))
    f1 = np.asarray(a1.fingerprints(vecs))
    assert not np.array_equal(f0, f1)
    # but both must induce the SAME partition (orbit separation)
    assert (len(set(f0.tolist())) == len(set(f1.tolist())))
