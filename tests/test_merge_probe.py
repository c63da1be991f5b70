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
     sorted set of the oracle's fingerprints.
"""

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
    (1 << 18, False),   # the cells' seen run: everything merges
    (1 << 22, False),   # the crossover itself still merges
    (1 << 24, True),    # the exhaustive Raft.cfg run's seen run
    (1 << 25, True),    # the top of _seen_sizes
])
def test_dedup_lowering_follows_run_shapes(seen_lanes, searches):
    """Traced (nothing compiles or runs) at the cells' capacities:
    --chunk 4096, so 65,536 query lanes, and a wave buffer of 2^18 lanes
    sorted by prefixes of 0, 2^16 and 2^18 lanes."""
    eng = DeviceBFS(
        cached_model(RaftParams(
            n_servers=2, n_values=1, max_elections=1, max_restarts=0,
            msg_slots=16)),
        chunk=4096)
    prefix = (0, 1 << 16, 1 << 18)
    assert (eng.VC, eng.R0, eng._wave_prefix()) == (1 << 16, 1 << 16, prefix)
    assert seen_lanes in eng._seen_sizes
    sds = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(eng._st_dedup)(
        sds((eng.VC,), jnp.uint64), sds((1,), jnp.bool_),
        sds((eng.FCAP + eng.VC,), jnp.uint64), sds((), jnp.int32),
        sds((seen_lanes,), jnp.uint64)).jaxpr
    # one switch over the prefix sizes, and every sort of the stage is
    # in it: the merged sort and the sort back to lane order, a branch
    top = [eqn.primitive.name for eqn in jaxpr.eqns]
    assert "sort" not in top
    switch, *probe = [eqn for eqn in jaxpr.eqns
                      if eqn.primitive.name == "cond"]
    branches = list(_subjaxprs(switch))
    assert len(branches) == len(prefix)
    merged = 0 if searches else seen_lanes
    for p, branch in zip(prefix, branches):
        sorts = [eqn for eqn in branch.eqns if eqn.primitive.name == "sort"]
        assert [eqn.invars[0].aval.shape[0] for eqn in sorts] == [
            merged + p + eng.VC] * 2
        assert not _primitives(branch, set()) & {
            "gather", "scatter", "scan", "while", "cond"}
    prims = _primitives(jaxpr, set())
    assert "scatter" not in prims
    plan = util.dedup_plan([seen_lanes], eng.VC, prefix)
    if searches:
        assert len(probe) == 1 and {"gather", "scan"} <= prims
        assert plan == {"merge": [], "search": [seen_lanes],
                        "wave_prefix": list(prefix),
                        "sort_lanes": prefix[-1] + eng.VC}
    else:
        assert not probe and not prims & {"gather", "scan", "while"}
        assert plan == {"merge": [seen_lanes], "search": [],
                        "wave_prefix": list(prefix),
                        "sort_lanes": seen_lanes + prefix[-1] + eng.VC}


def test_dedup_plan_without_a_wave_buffer_is_the_runs_alone():
    """The sharded engine's: every level of its LSM a sorted run, no
    append buffer, so no prefix and the lanes of the merged levels."""
    sizes = [1 << 16, 1 << 17, 1 << 18, 1 << 23]
    assert util.dedup_plan(sizes, 1 << 16) == {
        "merge": sizes[:3], "search": [1 << 23], "wave_prefix": [],
        "sort_lanes": sum(sizes[:3]) + (1 << 16)}


SMALL = RaftParams(
    n_servers=3, n_values=1, max_elections=1, max_restarts=0, msg_slots=16)
INVS = ("LeaderHasAllAckedValues", "NoLogDivergence")
DEPTH = 20


def test_small_engine_whose_waves_cross_two_prefix_sizes_equals_the_oracle():
    """128 query lanes a chunk-step and a buffer of 1,024 lanes sorted by
    prefixes of 0, 128, 512 and 1,024: the waves of depths 19 and 20
    find 530 and 562 new states in 30 and 34 chunk-steps, so their
    counts pass 128 and 512. The seen run is 8,192 lanes, by hand (the
    first size an engine picks is 2^18), which 128 queries still merge."""
    model = cached_model(SMALL)
    eng = DeviceBFS(model, invariants=INVS, symmetry=True, chunk=16,
                    valid_per_state=8, frontier_cap=1024,
                    max_frontier_cap=1024, journal_cap=1 << 12)
    eng._seen_sizes = [1 << 13]
    prefix = (0, 128, 512, 1024)
    assert (eng.VC, eng._wave_prefix()) == (128, prefix)
    seen_after = []
    merge_seen = eng._merge_seen

    def merge_and_keep(wave_new, new_real):
        merge_seen(wave_new, new_real)
        seen_after.append(eng._lsm_export())

    eng._merge_seen = merge_and_keep
    res = eng.run(max_depth=DEPTH, collect_metrics=True)
    assert eng._dedup_plan() == {
        "merge": [1 << 13], "search": [], "wave_prefix": list(prefix),
        "sort_lanes": (1 << 13) + 1024 + 128}

    # the oracle, level by level, and its states' fingerprints
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
    assert res.depth_counts == [len(lv) for lv in levels]
    assert res.depth_counts[19:] == [530, 562] and res.violation is None
    fps = [np.asarray(eng.canon.fingerprints(np.stack(
        [model.encode(s) for s in lv]).astype(np.int32)), dtype=np.uint64)
        for lv in levels]
    assert len(seen_after) == DEPTH
    for d, seen in enumerate(seen_after, start=1):
        np.testing.assert_array_equal(
            seen, np.unique(np.concatenate(fps[:d + 1])))

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
