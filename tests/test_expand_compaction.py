"""``expand``'s two stream compactions against the scatters they replaced.

Until PR 50 both stream compactions of int32 indices in ``expand`` were
``.at[dst].set`` scatters: the valid lanes of a chunk
(``engine.compact_chunk``) and, a group, the group's lanes of the
worklist (``SparseExpandMixin.sparse_apply``). Each is one ``lax.sort``
of one int32 key since (a scatter is a serial pass on the TPU, 4.6 ns a
lane; PERF.md section 6, PR 50). The retired forms are kept here as
``_reference_*``, as tests/test_emit_append.py keeps
``_reference_scatter``, and the code under ``raft_tpu/`` is held
bit-equal to them for every family of tests/test_expand_sparse.py.

A file of its own so that the test runner's workers can take it beside
tests/test_expand_sparse.py, whose cases are the longest file of the
suite: each family compiles its apply pass twice here.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.models.base import apply_tile
from test_expand_sparse import FAMILIES, _chunk_of, _raft


def _reference_compact(valid, VC):
    """The retired valid-lane compaction of ``engine.compact_chunk``:
    (sel, selv) by a cumsum and a scatter into a [VC + 1] index buffer."""
    C, A = valid.shape
    vflat = valid.reshape(-1)
    vpos = jnp.cumsum(vflat) - 1
    sdst = jnp.where(vflat, jnp.minimum(vpos, VC), VC)
    sel = (
        jnp.full((VC + 1,), C * A, jnp.int32)
        .at[sdst]
        .set(jnp.arange(C * A, dtype=jnp.int32))[:VC]
    )
    return sel, sel < C * A


def _reference_sparse_apply(model, batch, sel, selv, plan):
    """The retired ``SparseExpandMixin.sparse_apply``: a table gather
    for each lane's group, then a group its own compaction scatter over
    all VC worklist lanes and a gather of ``sel`` through it."""
    C, W = batch.shape
    A = model.A
    groups = model.sparse_groups()
    VC = sel.shape[0]
    total = sum(plan)
    group_of = np.zeros((A,), np.int32)
    for gi, g in enumerate(groups):
        group_of[g.off : g.off + g.n] = gi
    wg = jnp.where(
        selv,
        jnp.asarray(group_of)[jnp.clip(sel, 0, C * A - 1) % A],
        len(groups),
    )
    selp = jnp.concatenate([sel, jnp.full((1,), C * A, jnp.int32)])
    row = jnp.full((VC,), total, jnp.int32)
    apply_ovf = jnp.zeros((), bool)
    blocks = []
    base = 0
    for gi, (g, eb) in enumerate(zip(groups, plan)):
        mask = wg == gi
        pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
        apply_ovf = apply_ovf | (jnp.sum(mask.astype(jnp.int32)) > eb)
        edst = jnp.where(mask, jnp.minimum(pos, eb), eb)
        idx = (
            jnp.full((eb + 1,), VC, jnp.int32)
            .at[edst]
            .set(jnp.arange(VC, dtype=jnp.int32))[:eb]
        )
        flat = selp[idx]
        lane = jnp.clip(flat // A, 0, C - 1)
        k = jnp.clip(flat % A - g.off, 0, g.n - 1)
        srows = batch[lane]
        tbl = jnp.asarray(g.params)
        kern = model.kernel_for(g.name)
        args = [tbl[:, c][k] for c in range(tbl.shape[1])]
        blocks.append(
            jax.vmap(lambda s, *a, _k=kern: _k(s, *a)[1])(srows, *args))
        row = jnp.where(
            mask & (pos < eb), base + jnp.minimum(pos, eb - 1), row)
        base += eb
    allb = jnp.concatenate(blocks + [jnp.zeros((1, W), jnp.int32)], axis=0)
    return allb[row], apply_ovf


# what the busiest group of a worklist holds, by name: against its
# budget ``eb`` and its tile ``T = apply_tile(eb, C)`` (PR 56: a group's
# rows are built a tile a trip under the group's own count)
GROUP_COUNTS = {
    "count_0": lambda eb, T: 0,
    "count_1": lambda eb, T: 1,
    "count_tile_less_1": lambda eb, T: T - 1,
    "count_tile": lambda eb, T: T,
    "count_tile_plus_1": lambda eb, T: T + 1,
    "at_budget": lambda eb, T: eb,
    "one_past_budget": lambda eb, T: eb + 1,
}
CASES = (*GROUP_COUNTS, "empty_group", "random", "all_drop")


def _worklists(model, valid, VC, seed=0):
    """(plan, {case: (sel, overflows, lanes a group)}) over the enabled
    lanes of a real chunk: one static plan, and ascending worklists
    (subsets of the chunk's valid flat lanes, as ``compact_chunk`` would
    hand them) that give the busiest group each count of
    ``GROUP_COUNTS`` (none, one, a lane either side of a whole tile, its
    budget and one lane past it), empty a group that had lanes, keep a
    random subset, and drop every lane."""
    rng = np.random.default_rng(seed)
    C, A = valid.shape
    groups = model.sparse_groups()
    flat = np.nonzero(valid.reshape(-1))[0].astype(np.int32)
    cand = flat % A
    member = [flat[(cand >= g.off) & (cand < g.off + g.n)] for g in groups]
    counts = [len(m) for m in member]
    gi = int(np.argmax(counts))
    assert counts[gi] >= 3, "frontier too shallow to exercise budgets"
    # the busiest group's budget is one lane under what the chunk
    # enables; the others hold all of theirs (a group with nothing
    # enabled keeps one row: a budget is never 0)
    plan = tuple(
        counts[gi] - 1 if i == gi else max(1, c)
        for i, c in enumerate(counts))
    T = apply_tile(plan[gi], C)

    def pick(sizes):
        lanes = np.sort(np.concatenate([
            rng.choice(m, size=n, replace=False)
            for m, n in zip(member, sizes)]))
        assert len(lanes) <= VC
        return (np.concatenate([
            lanes, np.full(VC - len(lanes), C * A)]).astype(np.int32),
            any(n > eb for n, eb in zip(sizes, plan)), tuple(sizes))

    others = [int(rng.integers(0, c + 1)) for c in counts]
    cases = {}
    for case, count in GROUP_COUNTS.items():
        n = min(max(count(plan[gi], T), 0), counts[gi])
        cases[case] = pick(
            [n if i == gi else m for i, m in enumerate(others)])
    # empty the second busiest group; the busiest stays in budget
    gj = int(np.argsort(counts)[-2])
    assert counts[gj] >= 1
    cases["empty_group"] = pick(
        [0 if i == gj else min(c, plan[i]) for i, c in enumerate(counts)])
    cases["random"] = pick([min(n, plan[i]) for i, n in enumerate(others)])
    cases["all_drop"] = pick([0] * len(counts))
    assert set(cases) == set(CASES)
    return plan, cases


@functools.lru_cache(maxsize=None)
def _both_forms(family):
    """One plan a family, so each form compiles once for all the cases
    of the family (the test runner hands this file to one worker)."""
    model = FAMILIES[family]()
    C = 64
    A = model.A
    VC = min(C * A, C * 16)
    batch = jnp.asarray(_chunk_of(model, C))
    valid = np.asarray(jax.jit(jax.vmap(model.guards1))(batch)[0])
    plan, cases = _worklists(model, valid, VC)
    new = jax.jit(lambda b, s: model.sparse_apply(b, s, s < C * A, plan))
    old = jax.jit(
        lambda b, s: _reference_sparse_apply(model, b, s, s < C * A, plan))
    return C, batch, plan, cases, new, old


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_sparse_apply_equals_the_retired_scatter_segmentation(family, case):
    """One sort of (group, flat lane) segments the worklist exactly as
    the per-group scatters did, and a group's rows built a tile a trip
    under the group's count are the rows the one-shot block held: every
    row of the [VC, W] block and the budget bit, on worklists whose
    busiest group holds nothing, one lane, a lane either side of a whole
    tile, its budget, and one lane past it (``apply_ovf``, the lane past
    the budget a zeros row), with an emptied group, a random subset and
    no lane at all. The rows built are whole tiles of what each group
    keeps."""
    C, batch, plan, cases, new, old = _both_forms(family)
    sel, ovf, sizes = cases[case]
    got, got_ovf, built = jax.device_get(new(batch, jnp.asarray(sel)))
    want, want_ovf = jax.device_get(old(batch, jnp.asarray(sel)))
    assert bool(got_ovf) == bool(want_ovf) == ovf
    np.testing.assert_array_equal(got, want)
    tiles = [apply_tile(eb, C) for eb in plan]
    assert int(built) == sum(
        -(-min(n, eb) // T) * T for n, eb, T in zip(sizes, plan, tiles))
    assert int(built) <= sum(-(-eb // T) * T for eb, T in zip(plan, tiles))
    if case == "all_drop":
        assert not np.asarray(got).any() and int(built) == 0


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_compact_chunk_equals_the_retired_scatter(family):
    """The sorted keys' lane part (the key carries the lane's rank under
    its index since PR 52; tests/test_emit_sorts.py holds the rank) is
    the retired scatter's ``sel``: with room to spare, with ``n_gen`` exactly VC (fits) and
    VC + 1 (``compact_ovf``, the last valid lane cut), and on a chunk
    with no valid lane. The dense arm (``succs`` given) needs no apply
    pass, so each VC is a cheap program; the rows gathered through
    ``sel`` are compared too."""
    from raft_tpu.checker.engine import compact_chunk

    model = FAMILIES[family]()
    C = 16
    A, W = model.A, model.layout.W
    batch = jnp.asarray(_chunk_of(model, C))
    succs, valid, rank, _ = jax.jit(jax.vmap(model._expand1))(batch)
    K = len(model.ACTION_NAMES)
    n = int(np.asarray(valid).sum())
    assert n >= 2
    flatp = np.concatenate(
        [np.asarray(succs).reshape(C * A, W), np.zeros((1, W), np.int32)])
    for v, VC, ovf in [(valid, min(C * A, n + 7), False), (valid, n, False),
                       (valid, n - 1, True),
                       (jnp.zeros_like(valid), 8, False)]:
        n_gen = jnp.sum(v)
        flatc, sel, selv, _, got_ovf, _ = jax.device_get(jax.jit(
            lambda b, s, vv, ng, VC=VC: compact_chunk(
                None, None, b, s, vv, rank, K, ng, VC))(
                    batch, succs, v, n_gen))
        want_sel, want_selv = jax.device_get(_reference_compact(v, VC))
        assert sel.shape == (VC,) and bool(got_ovf) == ovf, VC
        np.testing.assert_array_equal(sel, want_sel)
        np.testing.assert_array_equal(selv, want_selv)
        np.testing.assert_array_equal(flatc, flatp[want_sel])


def test_sparse_plan_refuses_a_key_past_int32():
    """The one sort's key is ``group * (chunk * A + 1) + flat lane`` and
    the drop key lies past the last group's: a chunk whose
    ``(G + 1) * (chunk * A + 1)`` passes 2^31 is refused when the plan
    is made (an engine's constructor), naming chunk and A, and the
    largest chunk under it is taken."""
    model = _raft()
    A, G = model.A, len(model.sparse_groups())
    fits = ((1 << 31) - 1) // (G + 1)  # the largest stride that fits
    chunk = (fits - 1) // A
    assert (G + 1) * (chunk * A + 1) < 1 << 31
    assert len(model.sparse_plan(chunk, 1 << 16)) == G
    with pytest.raises(ValueError, match=rf"chunk={chunk + 1} x A={A} "):
        model.sparse_plan(chunk + 1, 1 << 16)
