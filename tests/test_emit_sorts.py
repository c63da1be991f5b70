"""``emit``'s index work against the gathers and the scatter it replaced.

Until PR 52 the emit stage read three 1-D arrays through a traced index
and wrote one: the action rank of each compacted lane through the
compaction's ``sel`` (``emit/coverage``), the survivors' lanes by an
``.at[dst].set`` into an index buffer (``util.dense_prefix_sel``), and
the journal's parent and candidate through those lanes. Each is a serial
pass on the TPU (7.1 ns a lane a gather, 4.6 the scatter; PERF.md
section 6, PR 52). Since, the rank rides in the low bits of
``engine.compact_chunk``'s sort key, and the survivors' lanes and their
``sel`` come out of one ``lax.sort`` of one int32 key, ``sel`` its
payload (``util.dense_prefix_sel`` is that sort without a payload, as
the sharded engine calls it). The retired
forms are kept here as ``_reference_*``, as tests/test_emit_append.py
keeps ``_reference_scatter``, and the code under ``raft_tpu/`` is held
bit-equal to them for every family of tests/test_expand_sparse.py, pad
lanes included.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.checker.device_bfs import DeviceBFS
from raft_tpu.checker.engine import compact_chunk, rank_key_bits
from raft_tpu.checker.util import dense_prefix_sel, emit_append, rank_counts
from raft_tpu.parallel.sharded import ShardedBFS
from test_expand_sparse import FAMILIES, _chunk_of, _raft


def _reference_rank(rank, sel):
    """The retired ``flat_rk`` of ``_st_finish`` (and ``lane_rank`` of
    ``_cs_pre``): the guard grid's rank gathered through ``sel``, a drop
    lane reading the -1 appended past the grid."""
    return jnp.concatenate(
        [rank.reshape(-1), jnp.full((1,), -1, rank.dtype)])[sel]


def _reference_dense_prefix_sel(new, n_lanes):
    """The retired ``util.dense_prefix_sel``: a cumsum and a scatter
    into an (n_lanes + 1) index buffer."""
    edst = jnp.where(new, jnp.cumsum(new) - 1, n_lanes)
    return (
        jnp.full((n_lanes + 1,), n_lanes, jnp.int32)
        .at[edst]
        .set(jnp.arange(n_lanes, dtype=jnp.int32))[:n_lanes]
    )


def _reference_blocks(flatc, sel, new, A, first_gid):
    """The retired step 5 of ``_st_finish``: the survivors' rows and the
    journal's two blocks, each a gather through the scattered ``esel``
    of an array with a zero appended for the pad lanes."""
    VC, W = flatc.shape
    esel = _reference_dense_prefix_sel(new, VC)
    z = jnp.zeros((1,), jnp.int32)
    return (
        esel,
        jnp.concatenate([flatc, jnp.zeros((1, W), jnp.int32)])[esel],
        jnp.concatenate([first_gid + sel // A, z])[esel],
        jnp.concatenate([sel % A, z])[esel],
    )


def _finish(model, C, VC, K, FCAP, JCAP):
    """``DeviceBFS._st_finish`` itself, jitted over a chunk's lanes with
    the carries at fixed cursors: (next_buf, jparent, jcand, cov)."""
    A, W = model.A, model.layout.W
    eng = types.SimpleNamespace(
        model=model, chunk=C, A=A, W=W, VC=VC, FCAP=FCAP, JCAP=JCAP,
        n_actions=K, invariants=())

    def run(flatc, sel, sel_rank, valid, rank, new, ncount, cursor,
            base_gid):
        stats = jnp.zeros((DeviceBFS.N_STATS,), jnp.int64).at[:2].set(ncount)
        fps = jnp.arange(VC, dtype=jnp.uint64)
        out = DeviceBFS._st_finish(
            eng, jnp.full((FCAP + VC, W), -7, jnp.int32),
            jnp.full((JCAP + VC,), -7, jnp.int32),
            jnp.full((JCAP + VC,), -7, jnp.int32),
            jnp.zeros((0,), jnp.int32), stats,
            jnp.zeros((K, 3), jnp.int64),
            jnp.zeros((FCAP + VC,), jnp.uint64), flatc, fps, sel,
            sel_rank, valid, rank, new, jnp.sum(valid), jnp.int32(0),
            jnp.bool_(False), jnp.bool_(False), jnp.zeros((3,), jnp.int32),
            jnp.zeros((2,), jnp.int32), jnp.int32(0), cursor, base_gid)
        return out[0], out[1], out[2], out[5]

    return jax.jit(run)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_emit_equals_the_retired_gathers_and_scatter(family):
    """On a real chunk of every family: ``compact_chunk``'s
    ``sel_rank`` is the rank gathered through ``sel``;
    ``dense_prefix_sel`` is the scattered ``esel``; and what
    ``_st_finish`` appends to the frontier, the journal's parent and
    candidate (the whole buffers, so the pad lanes a later append
    overwrites too) and counts new-distinct by rank is what the retired
    gathers through ``esel`` gave. Chunks with no valid lane, one, all
    VC lanes valid (none, some and all of them new) and one lane too
    many (``compact_ovf``: the first VC stay)."""
    model = FAMILIES[family]()
    C = 16
    A, W = model.A, model.layout.W
    K = len(model.ACTION_NAMES)
    assert K and rank_key_bits(C, A, K) == (K + 1).bit_length()
    batch = jnp.asarray(_chunk_of(model, C))
    succs, valid, rank, _ = jax.jit(jax.vmap(model._expand1))(batch)
    vh = np.asarray(valid)
    n = int(vh.sum())
    assert n >= 4
    one = np.zeros_like(vh)
    one[tuple(np.argwhere(vh)[n // 2])] = True
    rng = np.random.default_rng(52)
    FCAP = JCAP = 2 * C * A
    for case, v, VC in [("none", np.zeros_like(vh), 8), ("one", one, 8),
                        ("room", vh, n + 5), ("all", vh, n),
                        ("overflow", vh, n - 1)]:
        v = jnp.asarray(v)
        flatc, sel, selv, sel_rank, ovf, _ = jax.jit(
            lambda b, s, vv, r, VC=VC: compact_chunk(
                None, None, b, s, vv, r, K, jnp.sum(vv), VC)
        )(batch, succs, v, rank)
        assert bool(ovf) == (case == "overflow"), case
        assert sel_rank.dtype == jnp.int32
        np.testing.assert_array_equal(
            sel_rank, _reference_rank(rank, sel), err_msg=case)
        finish = _finish(model, C, VC, K, FCAP, JCAP)
        sv = np.asarray(selv)
        for which, new in [("no", np.zeros_like(sv)),
                           ("some", sv & (rng.random(VC) < 0.5)),
                           ("every", sv)]:
            new = jnp.asarray(new)
            esel, blk, jp_blk, jc_blk = _reference_blocks(
                flatc, sel, new, A, jnp.int32(1000 + 3 * C))
            np.testing.assert_array_equal(
                dense_prefix_sel(new, VC), esel, err_msg=(case, which))
            got = finish(flatc, sel, sel_rank, v, rank, new,
                         jnp.int32(5), jnp.int32(3 * C), jnp.int32(1000))
            n_new = jnp.sum(new)
            want = [
                emit_append(jnp.full((cap + VC, *b.shape[1:]), -7,
                                     jnp.int32), b, jnp.int32(5), n_new,
                            cap)[0]
                for b, cap in ((blk, FCAP), (jp_blk, JCAP),
                               (jc_blk, JCAP))]
            for g, w, what in zip(got, want, ("rows", "jparent", "jcand")):
                np.testing.assert_array_equal(
                    g, w, err_msg=(case, which, what))
            np.testing.assert_array_equal(
                got[3][:, 2],
                rank_counts(_reference_rank(rank, sel), new, K),
                err_msg=(case, which))
            assert int(got[3][:, 2].sum()) == int(n_new)


def test_compact_chunk_without_ranks_sorts_the_lanes_alone():
    """With no action ranks to count (``n_actions`` 0) the key is the
    lane's index, the rank is not read (None does) and every lane's
    comes back -1: ``sel`` is the same as with them."""
    model = _raft()
    C, A = 16, model.A
    K = len(model.ACTION_NAMES)
    batch = jnp.asarray(_chunk_of(model, C))
    succs, valid, rank, _ = jax.jit(jax.vmap(model._expand1))(batch)
    assert rank_key_bits(C, A, 0) == 0
    VC = int(np.asarray(valid).sum()) + 3
    _, sel0, selv0, no_rank, _, _ = compact_chunk(
        None, None, batch, succs, valid, None, 0, jnp.sum(valid), VC)
    _, sel, selv, _, _, _ = compact_chunk(
        None, None, batch, succs, valid, rank, K, jnp.sum(valid), VC)
    assert (np.asarray(no_rank) == -1).all()
    np.testing.assert_array_equal(sel0, sel)
    np.testing.assert_array_equal(selv0, selv)


@pytest.mark.parametrize("engine", [DeviceBFS, ShardedBFS],
                         ids=["device", "sharded"])
def test_engines_refuse_a_compaction_key_past_int32(engine):
    """The compaction's key is ``flat * R + rank + 1`` with the drop key
    ``chunk * A * R``: a chunk whose ``(chunk * A + 1) * R`` passes 2^31
    is refused by the engine's constructor with the sizes in the
    message, and the largest chunk under it has a key."""
    model = _raft()
    A, K = model.A, len(model.ACTION_NAMES)
    R = 1 << (K + 1).bit_length()
    assert K + 2 <= R < 2 * (K + 2)
    chunk = (((1 << 31) - 1) // R - 1) // A
    assert rank_key_bits(chunk, A, K) == R.bit_length() - 1
    assert rank_key_bits(chunk + 1, A, 0) == 0
    with pytest.raises(
            ValueError,
            match=rf"chunk={chunk + 1} x A={A} candidate lanes with {K} "
                  rf"action ranks .*\({chunk + 1} \* {A} \+ 1\) \* {R} "):
        engine(model, chunk=chunk + 1, frontier_cap=chunk + 1)
