"""The merged-sort lookup of the dedup stage (checker/util.py first_new).

  1. lane for lane equal to the reference it replaced: ``probe_sorted``
     over each run, and-ed with first occurrence in the chunk by lowest
     lane index — on planted hits, duplicates inside the chunk,
     duplicates that also hit, U64_MAX lanes, all-padding runs, a key in
     two runs, and run sizes on both sides of the crossover;
  2. with the wave's append buffer (PR 36): lane for lane equal to the
     sort of every lane the buffer has and to that reference, at counts
     on both sides of every prefix size, whatever order the buffer is
     in, and the lanes it says it sorted are those of the prefix chosen;
  3. the choice between merging and searching is a function of shapes
     alone: ``DeviceBFS._st_dedup`` traced at the benchmark cells' shapes
     is one switch over the buffer's prefix sizes, two sorts a branch,
     with no gather and no loop from ``searchsorted`` while the seen run
     is under the crossover, and keeps today's probe, under its cond,
     for a seen run above it;
  4. a small engine whose waves cross two prefix sizes is the oracle's
     equal, depth by depth, and its seen run after every wave is the
     sorted set of the oracle's fingerprints;
  5. with the seen run's real-lane count (PR 49): the rungs are closer
     than four apart, hold the run with every prefix and are none at
     the sort's floor; lane for lane equal to that reference and to the
     prefix switch at contents at, just under and just over every rung;
     the lanes sorted are the rung's and never more than the prefix
     switch sorted; a run too long to merge whole is searched only by
     the steps whose content no rung holds; and a small engine whose
     seen run steps mid-verdict sorts, wave for wave, the rungs its
     journal says its chunk-steps met, resumed or not.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.checker import util
from raft_tpu.checker.device_bfs import DeviceBFS
from raft_tpu.models.raft import RaftParams, cached_model
from raft_tpu.ops.hashing import U64_MAX
from raft_tpu.oracle.raft_oracle import RaftOracle

N = 64  # query lanes; runs of up to N * MERGE_LANES_PER_QUERY merge
EDGE = N * util.MERGE_LANES_PER_QUERY
PAD = np.uint64(U64_MAX)


def _run(rng, size, real, lo=0, hi=1 << 63):
    r = np.full((size,), PAD)
    r[:real] = np.sort(rng.integers(lo, hi, size=real, dtype=np.uint64))
    return r


def _reference(vals, occ, runs):
    """The lookup as it was: a binary search of every occupied run, and
    first occurrence by lowest lane index."""
    fresh = vals != PAD
    for o, r in zip(occ, runs):
        if o:
            fresh &= ~np.asarray(util.probe_sorted(
                jnp.asarray(r), jnp.asarray(vals)))
    first = np.zeros(vals.shape, bool)
    first[np.unique(vals, return_index=True)[1]] = True
    return fresh & first


def _case(name):
    """(vals, occ, runs) of one named case; the seed is the name's."""
    rng = np.random.default_rng(sum(map(ord, name)))
    vals = rng.integers(0, 1 << 63, size=N, dtype=np.uint64)
    sizes = (256, 512)
    occ = None
    if name == "crossover":
        sizes = (EDGE // 2, EDGE, 2 * EDGE, 4 * EDGE)
    elif name == "all-searched":
        sizes = (2 * EDGE, 4 * EDGE)
    runs = [_run(rng, s, s // 2) for s in sizes]
    if name == "no-hits":
        pass
    elif name in ("planted-hits", "crossover", "all-searched"):
        for j, r in enumerate(runs):
            vals[j::8] = r[rng.integers(0, r.shape[0] // 2, size=N // 8)]
    elif name == "dups-in-chunk":
        vals[1::2] = vals[0::2][::-1]
    elif name == "dups-that-hit":
        vals[0::4] = runs[0][:N // 4]
        vals[3::4] = vals[0::4]
        vals[2::4] = vals[1::4]
    elif name == "u64max-lanes":
        vals[::3] = PAD
        vals[1::6] = runs[1][:len(vals[1::6])]
    elif name == "all-u64max":
        vals[:] = PAD
    elif name == "all-padding-runs":
        runs = [np.full((s,), PAD) for s in sizes]
    elif name == "key-in-two-runs":
        shared = rng.integers(0, 1 << 63, size=32, dtype=np.uint64)
        runs = [np.sort(np.concatenate([r[:-32], shared])) for r in runs]
        vals[::2] = shared
    elif name == "first-and-last-lane":
        vals[0], vals[-1] = runs[0][0], runs[1][runs[1].shape[0] // 2 - 1]
    elif name == "unoccupied-searched-run":
        # an unoccupied level is all padding (RunLSM._empty_of); above
        # the crossover its search is skipped, below it is sorted anyway
        sizes = (EDGE, 2 * EDGE)
        runs = [_run(rng, EDGE, EDGE // 2), np.full((2 * EDGE,), PAD)]
        occ = np.array([True, False])
        vals[::4] = runs[0][:N // 4]
    elif name == "small-values":
        # dense keys: every kind of collision at once
        runs = [_run(rng, s, s // 2, 0, 200) for s in sizes]
        vals = rng.integers(0, 300, size=N, dtype=np.uint64)
        vals[::7] = PAD
    else:
        raise AssertionError(name)
    if occ is None:
        occ = np.ones((len(runs),), bool)
    return vals, occ, runs


CASES = (
    "no-hits", "planted-hits", "dups-in-chunk", "dups-that-hit",
    "u64max-lanes", "all-u64max", "all-padding-runs", "key-in-two-runs",
    "first-and-last-lane", "crossover", "all-searched",
    "unoccupied-searched-run", "small-values",
)


@pytest.mark.parametrize("name", CASES)
def test_first_new_equals_probe_and_first_occurrence(name):
    vals, occ, runs = _case(name)
    got = jax.jit(lambda v, o, *r: util.first_new(v, o, r))(
        jnp.asarray(vals), jnp.asarray(occ), *map(jnp.asarray, runs))
    want = _reference(vals, occ, runs)
    np.testing.assert_array_equal(np.asarray(got), want)
    if name in ("planted-hits", "dups-that-hit", "crossover", "small-values"):
        assert 0 < want.sum() < (vals != PAD).sum()  # the case bites


# the wave's append buffer: CAP lanes that can be real and N of drop
# region, sorted by prefixes of 0, 32, 128 and all 512 lanes
R0, CAP = 32, 512
PREFIX = util.wave_prefix_sizes(R0, CAP)
COUNTS = sorted({0, 1, CAP, *(p + d for p in PREFIX[1:-1] for d in (-1, 0, 1)),
                 CAP - 1})


def test_wave_prefix_sizes_are_four_apart_and_end_at_the_capacity():
    assert PREFIX == (0, 32, 128, 512)
    assert util.wave_prefix_sizes(1 << 16, 1 << 18) == (0, 1 << 16, 1 << 18)
    assert util.wave_prefix_sizes(1 << 15, 1 << 19) == (
        0, 1 << 15, 1 << 17, 1 << 19)
    # a capacity that is no power of two, and one under a chunk's lanes
    assert util.wave_prefix_sizes(1 << 14, 3 << 16) == (
        0, 1 << 14, 1 << 16, 3 << 16)
    assert util.wave_prefix_sizes(4096, 1024) == (0, 1024)
    assert COUNTS == [0, 1, 31, 32, 33, 127, 128, 129, 511, 512]


@pytest.mark.parametrize("count", COUNTS)
def test_first_new_sorts_the_prefix_the_wave_has_written(count):
    """The buffer holds ``count`` fingerprints in the order chunk-steps
    left them (each block sorted, the buffer not), U64_MAX after. The
    queries repeat lanes of the buffer (its first and its last real lane
    among them), lanes of the seen run and each other."""
    rng = np.random.default_rng(count)
    seen = _run(rng, 256, 200)
    buf = np.full((CAP + N,), PAD)
    buf[:count] = rng.integers(0, 1 << 63, size=count, dtype=np.uint64)
    assert count < 2 or (np.diff(buf[:count].astype(np.float64)) < 0).any()
    vals = rng.integers(0, 1 << 63, size=N, dtype=np.uint64)
    if count:
        vals[0::8] = buf[rng.integers(0, count, size=N // 8)]
        vals[8], vals[16] = buf[0], buf[count - 1]
    vals[1::8] = seen[rng.integers(0, 200, size=N // 8)]
    vals[3::8] = vals[2::8]  # duplicates within the chunk
    vals[5::16] = vals[0::16]  # a duplicate of a buffer lane, twice
    vals[7::16] = PAD
    occ = np.ones((1,), bool)
    got, lanes, queries = jax.jit(
        lambda v, o, s, b, c: util.first_new(v, o, (s,), wave=(b, c, PREFIX))
    )(jnp.asarray(vals), jnp.asarray(occ), jnp.asarray(seen),
      jnp.asarray(buf), np.int32(count))
    # every lane the buffer can hold, sorted with the rest: the program
    # before the prefix, which took the wave's fingerprints as runs
    whole = jax.jit(lambda v, o, *r: util.first_new(v, o, r))(
        jnp.asarray(vals), jnp.asarray([True, True]), jnp.asarray(seen),
        jnp.asarray(buf[:CAP]))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(whole))
    want = _reference(vals, [True, True], [seen, np.sort(buf[:CAP])])
    np.testing.assert_array_equal(np.asarray(got), want)
    assert 0 < want.sum() < (vals != PAD).sum()
    prefix = min(p for p in PREFIX if p >= count)
    assert int(lanes) == 256 + prefix + N
    assert int(queries) == 0  # a merged run is never searched


def test_first_new_never_searches_the_wave_buffer():
    """A buffer longer than the crossover is merged all the same (it is
    not sorted, so it cannot be searched): no gather whatever its size,
    where a sorted run of that size is searched."""
    sds = jax.ShapeDtypeStruct
    cap = 4 * EDGE
    assert not util.merges(cap, N)
    sizes = util.wave_prefix_sizes(N, cap)
    with_buf = _primitives(jax.make_jaxpr(
        lambda v, b, c: util.first_new(v, None, (), wave=(b, c, sizes)))(
        sds((N,), jnp.uint64), sds((cap + N,), jnp.uint64),
        sds((), jnp.int32)).jaxpr, set())
    assert "sort" in with_buf and not with_buf & {"gather", "scan", "while"}
    as_run = _primitives(jax.make_jaxpr(
        lambda v, o, r: util.first_new(v, o, (r,)))(
        sds((N,), jnp.uint64), sds((1,), jnp.bool_),
        sds((cap,), jnp.uint64)).jaxpr, set())
    assert {"gather", "cond"} <= as_run


def _subjaxprs(eqn):
    for p in eqn.params.values():
        for sub in p if isinstance(p, (list, tuple)) else (p,):
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield sub


def _primitives(jaxpr, out):
    for eqn in jaxpr.eqns:
        out.add(eqn.primitive.name)
        for sub in _subjaxprs(eqn):
            _primitives(sub, out)
    return out


@pytest.mark.parametrize("seen_lanes,searches", [
    (1 << 18, False),   # the cells' first seen run: the prefix switch
    (1 << 20, False),   # three cells' second: rungs, all merged
    (1 << 22, False),   # the crossover itself still merges whole
    (1 << 24, True),    # the exhaustive Raft.cfg run's seen run
    (1 << 25, True),    # the top of _seen_sizes
])
def test_dedup_lowering_follows_run_shapes(seen_lanes, searches):
    """Traced (nothing compiles or runs) at the cells' capacities:
    --chunk 4096, so 65,536 query lanes, and a wave buffer of 2^18 lanes
    sorted by prefixes of 0, 2^16 and 2^18 lanes beside a seen run at
    the sort's floor; a longer run by its rungs, and past the last of
    them by those prefixes and the search."""
    eng = DeviceBFS(
        cached_model(RaftParams(
            n_servers=2, n_values=1, max_elections=1, max_restarts=0,
            msg_slots=16)),
        chunk=4096)
    prefix = (0, 1 << 16, 1 << 18)
    assert (eng.VC, eng.R0, eng._wave_prefix()) == (1 << 16, 1 << 16, prefix)
    assert seen_lanes in eng._seen_sizes
    rungs = eng._rungs(seen_lanes)
    assert rungs == util.merge_rungs(seen_lanes, eng.VC, prefix)
    assert bool(rungs) == (seen_lanes > 1 << 18)
    sds = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(eng._st_dedup)(
        sds((eng.VC,), jnp.uint64), sds((1,), jnp.bool_),
        sds((eng.FCAP + eng.VC,), jnp.uint64), sds((), jnp.int32),
        sds((), jnp.int32), sds((seen_lanes,), jnp.uint64)).jaxpr
    # one switch, and every sort of the stage is in it: the merged sort
    # and the sort back to lane order, a branch
    top = [eqn.primitive.name for eqn in jaxpr.eqns]
    assert "sort" not in top
    switch, *probe = [eqn for eqn in jaxpr.eqns
                      if eqn.primitive.name == "cond"]
    branches = list(_subjaxprs(switch))
    merged = 0 if searches else seen_lanes
    lanes = [*(r for r in rungs),
             *(merged + p for p in prefix if searches or not rungs)]
    assert len(branches) == len(lanes)
    for run_part, branch in zip(lanes, branches):
        sorts = [eqn for eqn in branch.eqns if eqn.primitive.name == "sort"]
        assert [eqn.invars[0].aval.shape[0] for eqn in sorts] == [
            run_part + eng.VC] * 2
        assert not _primitives(branch, set()) & {
            "gather", "scatter", "scan", "while", "cond"}
    prims = _primitives(jaxpr, set())
    assert "scatter" not in prims
    plan = util.dedup_plan([seen_lanes], eng.VC, prefix, rungs)
    if searches:
        assert len(probe) == 1 and {"gather", "scan"} <= prims
        assert rungs[-1] == 1 << 22  # the crossover, asked of the rung
        assert plan == {"merge": [], "search": [seen_lanes],
                        "wave_prefix": list(prefix), "rungs": list(rungs),
                        "sort_lanes": rungs[-1] + eng.VC}
    else:
        assert not probe and not prims & {"gather", "scan", "while"}
        assert not rungs or rungs[-1] == seen_lanes + prefix[-1]
        assert plan == {"merge": [seen_lanes], "search": [],
                        "wave_prefix": list(prefix), "rungs": list(rungs),
                        "sort_lanes": seen_lanes + prefix[-1] + eng.VC}


def test_dedup_plan_without_a_wave_buffer_is_the_runs_alone():
    """The sharded engine's: every level of its LSM a sorted run, no
    append buffer, so no prefix and the lanes of the merged levels."""
    sizes = [1 << 16, 1 << 17, 1 << 18, 1 << 23]
    assert util.dedup_plan(sizes, 1 << 16) == {
        "merge": sizes[:3], "search": [1 << 23], "wave_prefix": [],
        "rungs": [], "sort_lanes": sum(sizes[:3]) + (1 << 16)}


SMALL = RaftParams(
    n_servers=3, n_values=1, max_elections=1, max_restarts=0, msg_slots=16)
INVS = ("LeaderHasAllAckedValues", "NoLogDivergence")
DEPTH = 20


@pytest.fixture(scope="module")
def oracle_fps():
    """The oracle's states level by level to DEPTH, as the engine's own
    fingerprints of them."""
    model = cached_model(SMALL)
    canon = DeviceBFS(model, invariants=INVS, symmetry=True, chunk=16).canon
    oracle = RaftOracle(3, 1, 1, 0)
    init = oracle.init_state()
    keys = {oracle.canon(init, True)}
    levels = [[init]]
    for _ in range(DEPTH):
        nxt = []
        for st in levels[-1]:
            for _label, s2 in oracle.successors(st):
                key = oracle.canon(s2, True)
                if key not in keys:
                    keys.add(key)
                    nxt.append(s2)
        levels.append(nxt)
    return [np.asarray(canon.fingerprints(np.stack(
        [model.encode(s) for s in lv]).astype(np.int32)), dtype=np.uint64)
        for lv in levels]


def _small_engine(seen_sizes, floor=None):
    """128 query lanes a chunk-step (16 rows x 8) and a buffer of 1,024
    lanes; the seen run's sizes by hand (the first size an engine picks
    is 2^18), and the sort's floor where the case wants rungs at this
    scale."""
    eng = DeviceBFS(cached_model(SMALL), invariants=INVS, symmetry=True,
                    chunk=16, valid_per_state=8, frontier_cap=1024,
                    max_frontier_cap=1024, journal_cap=1 << 12)
    eng._seen_sizes = list(seen_sizes)
    if floor is not None:
        eng.SORT_FLOOR = floor
    assert (eng.VC, eng._wave_prefix()) == (128, (0, 128, 512, 1024))
    return eng


def _keep_seen(eng):
    """The seen run's real lanes after every merge of ``eng``."""
    seen_after = []
    merge_seen = eng._merge_seen

    def merge_and_keep(wave_new, new_real):
        merge_seen(wave_new, new_real)
        seen_after.append(eng._lsm_export())

    eng._merge_seen = merge_and_keep
    return seen_after


def _equals_oracle(res, seen_after, oracle_fps):
    assert res.depth_counts == [len(f) for f in oracle_fps]
    assert res.depth_counts[19:] == [530, 562] and res.violation is None
    assert len(seen_after) == DEPTH
    for d, seen in enumerate(seen_after, start=1):
        np.testing.assert_array_equal(
            seen, np.unique(np.concatenate(oracle_fps[:d + 1])))


def test_small_engine_whose_waves_cross_two_prefix_sizes_equals_the_oracle(
        oracle_fps):
    """A buffer of 1,024 lanes sorted by prefixes of 0, 128, 512 and
    1,024: the waves of depths 19 and 20 find 530 and 562 new states in
    30 and 34 chunk-steps, so their counts pass 128 and 512. The seen
    run is 8,192 lanes, which 128 queries still merge, and at the
    sort's floor, so it is sorted whole."""
    eng = _small_engine([1 << 13])
    seen_after = _keep_seen(eng)
    prefix = eng._wave_prefix()
    res = eng.run(max_depth=DEPTH, collect_metrics=True)
    assert eng._dedup_plan() == {
        "merge": [1 << 13], "search": [], "wave_prefix": list(prefix),
        "rungs": [], "sort_lanes": (1 << 13) + 1024 + 128}
    _equals_oracle(res, seen_after, oracle_fps)

    # each chunk-step sorted the seen run, its 128 queries and the
    # smallest prefix that held the wave's count before it: replayed
    # from the counts alone, a wave's new states can land anywhere in its
    # chunk-steps, so the replay bounds the row from both sides
    for row in res.metrics:
        steps = -(-row["frontier"] // 16)
        fixed = steps * ((1 << 13) + 128)
        top = min(p for p in prefix if p >= row["new"])
        assert fixed <= row["dedup_sort_lanes"] <= fixed + (steps - 1) * top
        if steps == 1:
            assert row["dedup_sort_lanes"] == fixed
    wide = res.metrics[-1]["dedup_sort_lanes"] - 34 * ((1 << 13) + 128)
    assert wide > 33 * 128  # some step of the widest wave sorted 512 or more
    assert res.stats["dedup_sort_lanes"] == sum(
        row["dedup_sort_lanes"] for row in res.metrics)


# ---- the seen run's real-lane count (PR 49) ----

# a run of 2,048 lanes above a floor of 256, which 64 queries merge
# whole, beside the buffer above; and one of 8,192 lanes, which they do
# not
SEEN, LONG, FLOOR = 2048, 8192, 256
RUNGS = util.merge_rungs(SEEN, N, PREFIX, FLOOR)
LONG_RUNGS = util.merge_rungs(LONG, N, PREFIX, FLOOR)


def test_rungs_are_closer_than_four_apart_and_hold_the_run_with_every_prefix():
    assert RUNGS == (256, 384, 512, 768, 1024, 1536, 2048,
                     2048 + 32, 2048 + 128, 2048 + 512)
    # a run too long to merge whole: the rungs that merges() allows
    assert LONG_RUNGS == (256, 384, 512, 768, 1024, 1536, 2048, 3072, 4096)
    assert util.merges(4096, N) and not util.merges(LONG, N)
    # nothing to cut at the floor or under it: the prefix switch alone
    assert util.merge_rungs(FLOOR, N, PREFIX, FLOOR) == ()
    assert util.merge_rungs(1 << 18, 1 << 14, (0, 1 << 14, 1 << 20)) == ()
    assert util.SORT_FLOOR_LANES == 1 << 18
    # the three cells whose seen run leaves its first size: VC, FCAP
    for vc, cap in ((1 << 14, 1 << 20), (1 << 15, 1 << 19)):
        prefix = util.wave_prefix_sizes(vc, cap)
        for run in (1 << 20, 1 << 22):
            rungs = util.merge_rungs(run, vc, prefix)
            assert rungs[0] == 1 << 18 and list(rungs) == sorted(set(rungs))
            assert all(2 * b <= 3 * a for a, b in zip(rungs, rungs[1:]))
            if util.merges(run, vc):
                assert {run + p for p in prefix} <= set(rungs)
                assert rungs[-1] == run + cap
            else:
                assert rungs[-1] == util.MERGE_LANES_PER_QUERY * vc < run
    # addremove4-wide against its 2^20-lane run: twice the branches
    assert util.merge_rungs(1 << 20, 1 << 14, util.wave_prefix_sizes(
        1 << 14, 1 << 20)) == (
        262144, 393216, 524288, 786432, 1048576, 1048576 + 16384,
        1048576 + 65536, 1048576 + 262144, 1572864, 2097152)


def _contents(run, rungs):
    """(real, count) pairs: contents at, just under and just over every
    rung, all of it the run's, or the run's padding starting mid-rung
    and the wave's lanes laid in from there; and the wave's count at
    the prefix switch's jump from 128 lanes to all 512."""
    found = {(0, 0), (run, CAP), (run // 3, 300), (run - 300, 300)}
    for r in rungs:
        for total in (r - 1, r, r + 1):
            if total <= run:
                found.add((total, 0))
            for real in (r // 2 + 7, r - 1 - (r // 7) % CAP):
                if 0 <= total - real <= CAP and real <= run:
                    found.add((real, total - real))
    return sorted(found)


@functools.lru_cache(maxsize=None)
def _jitted(rungs):
    """``first_new`` with the run's count and ``rungs``, and without."""
    return (
        jax.jit(lambda v, o, s, b, c, r: util.first_new(
            v, o, (s,), wave=(b, c, PREFIX), real=(r, rungs))),
        jax.jit(lambda v, o, s, b, c: util.first_new(
            v, o, (s,), wave=(b, c, PREFIX))))


def _first_new_with_count(rungs, vals, occ, seen, buf, count, real):
    new, old = _jitted(rungs)
    args = (jnp.asarray(vals), jnp.asarray(occ), jnp.asarray(seen),
            jnp.asarray(buf), np.int32(count))
    return new(*args, np.int32(real)), old(*args)


def _content_case(run, real, count):
    """A run holding ``real`` fingerprints, a buffer holding ``count``
    in the order chunk-steps left them, and queries that repeat lanes
    of both (each one's first and last real lane among them), each
    other and padding."""
    rng = np.random.default_rng(run + 31 * real + count)
    seen = _run(rng, run, real)
    buf = np.full((CAP + N,), PAD)
    buf[:count] = rng.integers(0, 1 << 63, size=count, dtype=np.uint64)
    vals = rng.integers(0, 1 << 63, size=N, dtype=np.uint64)
    if count:
        vals[0::8] = buf[rng.integers(0, count, size=N // 8)]
        vals[8], vals[16] = buf[0], buf[count - 1]
    if real:
        vals[1::8] = seen[rng.integers(0, real, size=N // 8)]
        vals[9], vals[17] = seen[0], seen[real - 1]
    vals[3::8] = vals[2::8]  # duplicates within the chunk
    vals[5::16] = vals[0::16]
    vals[7::16] = PAD
    return vals, seen, buf


@pytest.mark.parametrize("real,count", _contents(SEEN, RUNGS))
def test_first_new_sorts_the_rung_that_holds_the_run_and_the_wave(real, count):
    vals, seen, buf = _content_case(SEEN, real, count)
    occ = np.ones((1,), bool)
    (got, lanes, queries), (old, old_lanes, _q) = _first_new_with_count(
        RUNGS, vals, occ, seen, buf, count, real)
    want = _reference(vals, [True, True], [seen, np.sort(buf[:CAP])])
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(old))
    assert want.sum() < (vals != PAD).sum() or not (real or count)
    rung = min(r for r in RUNGS if r >= real + count)
    assert int(lanes) == rung + N and int(queries) == 0
    prefix = min(p for p in PREFIX if p >= count)
    assert int(old_lanes) == SEEN + prefix + N >= int(lanes)


@pytest.mark.parametrize("real,count", [
    *_contents(LONG, LONG_RUNGS[-2:]), (4096, 1), (4000, 97), (LONG, 0),
    (LONG - 5, CAP), (6000, 128), (6000, 129)])
def test_first_new_searches_a_long_run_only_past_its_last_rung(real, count):
    """A run of 8,192 lanes is past the crossover of 64 queries: while
    it and the wave hold 4,096 lanes or fewer it is merged at the rung
    that holds them and not searched; past that the step sorts the
    buffer's prefix and searches the run, as every step did."""
    vals, seen, buf = _content_case(LONG, real, count)
    occ = np.ones((1,), bool)
    (got, lanes, queries), (old, old_lanes, old_queries) = (
        _first_new_with_count(
            LONG_RUNGS, vals, occ, seen, buf, count, real))
    want = _reference(vals, [True, True], [seen, np.sort(buf[:CAP])])
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(old))
    prefix = min(p for p in PREFIX if p >= count)
    assert (int(old_lanes), int(old_queries)) == (prefix + N, N)
    if real + count <= LONG_RUNGS[-1]:
        rung = min(r for r in LONG_RUNGS if r >= real + count)
        assert (int(lanes), int(queries)) == (rung + N, 0)
    else:
        assert (int(lanes), int(queries)) == (prefix + N, N)


def test_first_new_skips_the_search_of_an_unoccupied_long_run():
    vals, seen, buf = _content_case(LONG, 0, CAP)
    (got, lanes, queries), (old, *_r) = _first_new_with_count(
        LONG_RUNGS, vals, np.zeros((1,), bool), seen, buf, CAP,
        LONG_RUNGS[-1])  # a count past the last rung: the prefix cases
    np.testing.assert_array_equal(np.asarray(got), np.asarray(old))
    assert (int(lanes), int(queries)) == (CAP + N, 0)


@pytest.mark.parametrize("run,rungs", [(SEEN, RUNGS), (LONG, LONG_RUNGS)])
def test_rung_branches_lower_without_gather_or_scatter(run, rungs):
    sds = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(lambda v, o, s, b, c, r: util.first_new(
        v, o, (s,), wave=(b, c, PREFIX), real=(r, rungs)))(
        sds((N,), jnp.uint64), sds((1,), jnp.bool_), sds((run,), jnp.uint64),
        sds((CAP + N,), jnp.uint64), sds((), jnp.int32),
        sds((), jnp.int32)).jaxpr
    switch, *probe = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    branches = list(_subjaxprs(switch))
    searched = not util.merges(run, N)
    assert len(probe) == searched
    assert len(branches) == len(rungs) + searched * len(PREFIX)
    for r, branch in zip(rungs, branches):
        prims = _primitives(branch, set())
        assert "dynamic_slice" in prims and not prims & {
            "gather", "scatter", "scan", "while", "cond"}
        sorts = [e for e in branch.eqns if e.primitive.name == "sort"]
        assert [e.invars[0].aval.shape[0] for e in sorts] == [r + N] * 2


def _parent_first_new(vals, occ, runs):
    """``first_new`` without a wave buffer as it stood before PR 49."""
    n = vals.shape[0]
    merged = [r for r in runs if util.merges(r.shape[0], n)]
    with jax.named_scope("merge"):
        new = util._merged_new(vals, merged)
    with jax.named_scope("search"):
        for i, r in enumerate(runs):
            if util.merges(r.shape[0], n):
                continue
            hit = jax.lax.cond(
                occ[i], lambda rr: util.probe_sorted(rr, vals),
                lambda rr: util.ne_u64(vals, vals), r)
            new = new & ~hit
    return new


@pytest.mark.parametrize("sizes", [(256, 512), (EDGE, 2 * EDGE, 4 * EDGE)])
def test_first_new_without_a_wave_buffer_lowers_as_it_did(sizes):
    """The sharded engine's call and ``RunLSM``'s: no buffer, no count,
    and the program text of the parent's."""
    sds = jax.ShapeDtypeStruct
    args = (sds((N,), jnp.uint64), sds((len(sizes),), jnp.bool_),
            *(sds((s,), jnp.uint64) for s in sizes))
    texts = [jax.jit(lambda v, o, *r, f=f: f(v, o, r)).lower(*args).as_text()
             for f in (util.first_new, _parent_first_new)]
    assert texts[0] == texts[1] and "sort" in texts[0]


def test_a_run_at_the_floor_lowers_to_the_prefix_switch_whatever_the_count():
    """No rungs: the count is not read, and the program is the prefix
    switch's but for the argument."""
    sds = jax.ShapeDtypeStruct
    args = (sds((N,), jnp.uint64), sds((1,), jnp.bool_),
            sds((FLOOR,), jnp.uint64), sds((CAP + N,), jnp.uint64),
            sds((), jnp.int32), sds((), jnp.int32))
    assert util.merge_rungs(FLOOR, N, PREFIX, FLOOR) == ()
    texts = [jax.jit(lambda v, o, s, b, c, r, real=real: util.first_new(
        v, o, (s,), wave=(b, c, PREFIX),
        real=real and (r, ()))).lower(*args).as_text()
        for real in (True, None)]
    assert texts[0] == texts[1]


def _rungs_of_a_run(eng, res):
    """The lanes each wave's chunk-steps sorted, replayed from the
    journal: a new state's parent says which chunk-step found it, so
    the wave's count before every step is known, and with the seen
    run's count before the wave the rung each step took."""
    n0 = res.depth_counts[0]
    jparent = np.asarray(jax.device_get(eng._jparent))[:eng._jcount]
    lanes, first_gid = [], 0
    for d, new in enumerate(res.depth_counts[1:], start=1):
        real = sum(res.depth_counts[:d])
        frontier = res.depth_counts[d - 1]
        born = sum(res.depth_counts[1:d])  # journal rows before this wave
        step_of = (jparent[born:born + new] - first_gid) // eng.chunk
        steps = -(-frontier // eng.chunk)
        found = np.bincount(step_of, minlength=steps)
        before = np.concatenate([[0], np.cumsum(found)[:-1]])
        size = eng._seen_size_for(real)
        rungs = eng._rungs(size)
        prefix = eng._wave_prefix()
        lanes.append(sum(
            min(r for r in rungs if r >= real + c) + eng.VC if rungs
            else size + min(p for p in prefix if p >= c) + eng.VC
            for c in before.tolist()))
        first_gid += frontier
    assert first_gid + res.depth_counts[-1] - n0 == eng._jcount
    return lanes


def test_small_engine_whose_seen_run_steps_up_sorts_the_rungs_it_met(
        oracle_fps, tmp_path):
    """The same verdict with a floor of 256 lanes and a seen run of
    1,024 lanes that steps to 4,096 in wave 16's merge (1,109 distinct
    after it): every wave from the 12th on crosses rungs, waves 17-20
    against the longer run, whose real lanes go from 1,109 to 3,073.
    The oracle's equal wave for wave; each row's ``dedup_sort_lanes``
    is the sum of the rungs its steps took; and a run resumed from a
    checkpoint taken after the step takes the same rungs."""
    eng = _small_engine([1 << 10, 1 << 12], floor=256)
    seen_after = _keep_seen(eng)
    ckpt = str(tmp_path / "after18.npz")
    first = eng.run(max_depth=18, checkpoint_path=ckpt, collect_metrics=True)
    assert [w["seen_lanes"] for w in first.metrics] == (
        [1 << 10] * 16 + [1 << 12] * 2)
    del seen_after[:]
    res = eng.run(max_depth=DEPTH, collect_metrics=True)
    _equals_oracle(res, seen_after, oracle_fps)
    plan = eng._dedup_plan()
    assert plan["rungs"] == [
        256, 384, 512, 768, 1024, 1536, 2048, 3072, 4096,
        4096 + 128, 4096 + 512, 4096 + 1024]
    assert plan["sort_lanes"] == 4096 + 1024 + 128 and plan["search"] == []
    rows = res.metrics
    assert [w["dedup_sort_lanes"] for w in rows] == _rungs_of_a_run(eng, res)
    assert [w["dedup_sort_lanes"] for w in rows[:18]] == [
        w["dedup_sort_lanes"] for w in first.metrics]
    # fewer lanes than the prefix switch sorts against the same runs,
    # wave for wave from the first that is wider than a chunk
    for w in rows:
        steps = -(-w["frontier"] // 16)
        assert w["dedup_sort_lanes"] <= steps * (
            w["seen_lanes"] + 128 + min(
                p for p in eng._wave_prefix() if p >= w["new"]))
        assert w["dedup_search_queries"] == 0
    assert rows[-1]["dedup_sort_lanes"] < 34 * (4096 + 128)

    resumed = _small_engine([1 << 10, 1 << 12], floor=256)
    again = resumed.run(max_depth=DEPTH, resume=ckpt, collect_metrics=True)
    assert resumed._seen_real == eng._seen_real == 3073
    assert again.depth_counts == res.depth_counts
    assert [(w["depth"], w["seen_lanes"], w["dedup_sort_lanes"])
            for w in again.metrics] == [
        (w["depth"], w["seen_lanes"], w["dedup_sort_lanes"])
        for w in rows[18:]]
