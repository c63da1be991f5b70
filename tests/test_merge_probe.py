"""The merged-sort lookup of the dedup stage (checker/util.py first_new).

  1. lane for lane equal to the reference it replaced: ``probe_sorted``
     over each run, and-ed with first occurrence in the chunk by lowest
     lane index — on planted hits, duplicates inside the chunk,
     duplicates that also hit, U64_MAX lanes, all-padding runs, a key in
     two runs, and run sizes on both sides of the crossover;
  2. the choice between merging and searching is a function of shapes
     alone: ``DeviceBFS._st_dedup`` traced at the benchmark cells' shapes
     has no gather and no loop from ``searchsorted`` while every run is
     under the crossover, and keeps today's probe, under its cond, for a
     seen run above it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.checker import util
from raft_tpu.checker.device_bfs import DeviceBFS
from raft_tpu.models.raft import RaftParams, cached_model
from raft_tpu.ops.hashing import U64_MAX

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


def _primitives(jaxpr, out):
    for eqn in jaxpr.eqns:
        out.add(eqn.primitive.name)
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else (p,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
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
    --chunk 4096, so 65,536 query lanes, a ladder of 2^16..2^18 lanes."""
    eng = DeviceBFS(
        cached_model(RaftParams(
            n_servers=2, n_values=1, max_elections=1, max_restarts=0,
            msg_slots=16)),
        chunk=4096)
    assert (eng.VC, eng.R0, eng._wave_geom()) == (1 << 16, 1 << 16, 2)
    assert seen_lanes in eng._seen_sizes
    sds = jax.ShapeDtypeStruct
    sizes = [seen_lanes, 1 << 16, 1 << 17, 1 << 18]
    prims = _primitives(jax.make_jaxpr(eng._st_dedup)(
        sds((eng.VC,), jnp.uint64), sds((len(sizes),), jnp.bool_),
        *(sds((s,), jnp.uint64) for s in sizes)).jaxpr, set())
    assert "sort" in prims
    assert "scatter" not in prims
    plan = util.dedup_plan(sizes, eng.VC)
    if searches:
        assert {"gather", "scan", "cond"} <= prims
        assert plan == {"merge": sizes[1:], "search": [seen_lanes],
                        "sort_lanes": sum(sizes[1:]) + eng.VC}
    else:
        assert not prims & {"gather", "scan", "while", "cond"}
        assert plan == {"merge": sizes, "search": [],
                        "sort_lanes": sum(sizes) + eng.VC}
