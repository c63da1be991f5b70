"""Emit parity: the round-6 contiguous cursor-append emit must be
bit-identical to the retired full-capacity scatter emit it replaced.

Three layers of evidence:
  1. unit parity of the emit helpers (checker/util.py dense_prefix_sel +
     emit_append) against a reference scatter, sweeping the cursor across
     the exactly-full and one-past-full capacity boundaries — the
     drop-lane overflow semantics the rewrite promised to preserve;
  2. engine parity on >= 2 models and both chunk geometries, host and
     device engines (identical counts, depth profile, terminal states,
     coverage table);
  3. engine-level overflow behavior: a journal/frontier capacity sized
     exactly to the run completes, one lane short raises OverflowError —
     the buffer-geometry change (pad rows past cap instead of one drop
     row at cap) must not shift the overflow threshold by a single row.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.checker.bfs import BFSChecker
from raft_tpu.checker.device_bfs import DeviceBFS
from raft_tpu.checker.util import dense_prefix_sel, emit_append
from raft_tpu.models.raft import RaftParams, cached_model

TINY = RaftParams(n_servers=2, n_values=1, max_elections=2, max_restarts=0, msg_slots=16)
SMALL = RaftParams(n_servers=3, n_values=1, max_elections=1, max_restarts=0, msg_slots=16)
INVS = ("LeaderHasAllAckedValues", "NoLogDivergence")


# ---------------- 1. unit parity of the emit helpers ----------------


def _reference_scatter(buf_rows, block_vals, new, count, cap):
    """The retired emit: arbitrary-index scatter with row `cap` as the
    drop lane (numpy mirror of the pre-round-6 _chunk_step step 5)."""
    npos = np.cumsum(new) - 1
    out = buf_rows.copy()
    for lane in range(len(new)):
        if new[lane]:
            dst = min(count + npos[lane], cap)
            out[dst] = block_vals[lane]
    ovf = count + int(new.sum()) > cap
    return out, ovf


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("cap,n_lanes", [(16, 8), (32, 8), (17, 8)])
def test_emit_append_matches_scatter_rows(seed, cap, n_lanes):
    """Sweep the cursor from empty through exactly-full to past-full:
    rows [0, cap) and the overflow flag must match the scatter path
    bit-for-bit at every cursor (the drop REGION [cap, cap+B) replaces
    the scatter's drop ROW cap; rows past cap are don't-care)."""
    rng = np.random.default_rng(seed)
    W = 3
    for count in range(0, cap + 2):
        new = rng.random(n_lanes) < 0.6
        n_new = int(new.sum())
        vals = rng.integers(1, 100, size=(n_lanes, W)).astype(np.int32)
        # reference: scatter into a (cap+1, W) buffer with drop row cap
        ref_buf = np.zeros((cap + 1, W), np.int32)
        ref, ref_ovf = _reference_scatter(ref_buf, vals, new, count, cap)
        # production: compact to a dense prefix block, append at cursor
        esel = dense_prefix_sel(jnp.asarray(new), n_lanes)
        blk = jnp.concatenate(
            [jnp.asarray(vals), jnp.zeros((1, W), jnp.int32)], axis=0
        )[esel]
        buf = jnp.zeros((cap + n_lanes, W), jnp.int32)
        got, got_ovf = emit_append(
            buf, blk, jnp.int32(min(count, cap + 1)), jnp.int32(n_new), cap
        )
        assert bool(got_ovf) == ref_ovf, (count, n_new)
        np.testing.assert_array_equal(
            np.asarray(got)[:cap], ref[:cap],
            err_msg=f"cursor={count} n_new={n_new} rows [0, cap) diverged",
        )


def test_emit_append_1d_journal_parity():
    """Same boundary sweep for the 1-D journal-lane shape."""
    cap, n_lanes = 8, 4
    rng = np.random.default_rng(7)
    for count in range(0, cap + 2):
        new = rng.random(n_lanes) < 0.7
        n_new = int(new.sum())
        vals = rng.integers(1, 100, size=(n_lanes,)).astype(np.int32)
        ref_buf = np.zeros((cap + 1,), np.int32)
        ref, ref_ovf = _reference_scatter(
            ref_buf[:, None], vals[:, None], new, count, cap
        )
        esel = dense_prefix_sel(jnp.asarray(new), n_lanes)
        blk = jnp.concatenate(
            [jnp.asarray(vals), jnp.zeros((1,), jnp.int32)]
        )[esel]
        buf = jnp.zeros((cap + n_lanes,), jnp.int32)
        got, got_ovf = emit_append(
            buf, blk, jnp.int32(min(count, cap + 1)), jnp.int32(n_new), cap
        )
        assert bool(got_ovf) == ref_ovf
        np.testing.assert_array_equal(np.asarray(got)[:cap], ref[:cap, 0])


def test_dense_prefix_sel_compacts_in_order():
    new = jnp.asarray([False, True, False, True, True, False])
    sel = np.asarray(dense_prefix_sel(new, 6))
    # first n_new entries are the new lanes in order; the rest point at
    # the caller's pad row (index n_lanes)
    assert sel[:3].tolist() == [1, 3, 4]
    assert (sel[3:] == 6).all()


# ---------------- 2. engine parity (>= 2 models x 2 chunk geometries) --


@pytest.mark.slow
@pytest.mark.parametrize("params", [TINY, SMALL], ids=["raft2", "raft3"])
@pytest.mark.parametrize("chunk", [256, 1024])
def test_append_emit_engine_parity(params, chunk):
    """Device (append emit) vs host (cursor-append buffers) end-to-end:
    counts, depth profile, terminal states and the coverage table must
    be identical across both models and both chunk geometries."""
    model = cached_model(params)
    host = BFSChecker(model, invariants=INVS, symmetry=True, chunk=chunk)
    hres = host.run()
    dev = DeviceBFS(
        model, invariants=INVS, symmetry=True, chunk=chunk,
        frontier_cap=1 << 14, seen_cap=1 << 17, journal_cap=1 << 17,
    )
    dres = dev.run()
    assert dres.violation is None and hres.violation is None
    assert dres.distinct == hres.distinct
    assert dres.depth_counts == hres.depth_counts
    assert dres.total == hres.total
    assert dres.terminal == hres.terminal
    assert dres.coverage == hres.coverage
    assert dres.exhausted


# ---------------- 3. engine-level overflow threshold ----------------


def _exact_journal_run(journal_cap):
    model = cached_model(TINY)
    dev = DeviceBFS(
        model, invariants=(), symmetry=True, chunk=256,
        frontier_cap=1 << 12, seen_cap=1 << 14,
        journal_cap=journal_cap, max_journal_cap=journal_cap,
    )
    return dev.run()


@pytest.mark.slow
def test_journal_overflow_threshold_exact():
    """journal_cap == distinct-beyond-init completes; one less raises.
    The append path's drop REGION must preserve the retired drop-row
    threshold to the single row."""
    base = _exact_journal_run(1 << 14)
    assert base.exhausted
    exact = base.distinct - base.depth_counts[0]
    res = _exact_journal_run(exact)
    assert res.exhausted and res.distinct == base.distinct
    with pytest.raises(OverflowError):
        _exact_journal_run(exact - 1)


@pytest.mark.slow
def test_frontier_overflow_threshold():
    """A frontier_cap below the widest wave aborts with the frontier
    overflow bit; at least the widest wave's lanes completes."""
    model = cached_model(TINY)
    base = DeviceBFS(
        model, invariants=(), symmetry=True, chunk=32,
        frontier_cap=1 << 12, seen_cap=1 << 14, journal_cap=1 << 14,
    ).run()
    assert base.exhausted
    widest = max(base.depth_counts)
    # cap below the widest wave (rounded to a chunk multiple, floored at
    # one chunk) must overflow rather than silently drop states
    small = max(32, (widest - 1) // 32 * 32)
    assert small < widest
    with pytest.raises(OverflowError):
        DeviceBFS(
            model, invariants=(), symmetry=True, chunk=32,
            frontier_cap=small, max_frontier_cap=small,
            seen_cap=1 << 14, journal_cap=1 << 14,
        ).run()
