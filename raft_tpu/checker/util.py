"""Capacity-growth policy, sorted-set probe and the contiguous
cursor-append emit shared by the device-resident checkers (DeviceBFS and
the sharded engine), so a policy fix lands once."""

from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.hashing import U64_MAX, eq_u64, ne_u64, split_u64

GROWTH = 4  # enlarge factor per growth step
HEADROOM = 3  # grow when the next wave could need more than cap/HEADROOM
I32_MAX = np.int32(2**31 - 1)  # "no violation" sentinel in journal folds
_U32_MAX = np.uint32(0xFFFFFFFF)


# A sorted run of at most this many lanes per query lane is looked up by
# the one merged sort of ``first_new``; a longer one by binary search.
# Measured once on the v5e at 65,536 queries (scripts/probe_micro.py;
# PERF.md section 6, PR 25, has the table): what a run of 2^21 lanes adds
# to a chunk-step is 9.2 ms merged and 22.3 ms searched, one of 2^22
# lanes 21.8 and 23.3 (and the search's gathers cost more inside the wave
# program than alone), one of 2^23 lanes 48.8 and 24.3. The lanes are
# those the sort would sort: a caller that knows how many of a run's
# lanes are real (``first_new``'s ``real``) asks it of the rung that
# holds them, the others of the run's capacity.
MERGE_LANES_PER_QUERY = 64

# Under some 300,000 lanes a merged sort costs the same on the v5e (1.0 ms
# in place, 1.86-1.92 ms a call for 278,528-327,680 lanes and 3.0-4.3 ns
# for each lane more: PERF.md section 6, PRs 36 and 47), so a run of at
# most this many lanes is sorted whole: there is nothing to cut.
SORT_FLOOR_LANES = 1 << 18


def probe_sorted(sorted_arr, vals):
    """Membership of vals in a sorted u64 array padded with U64_MAX, by
    binary search. Each of its log2(S)+1 steps is a gather of one lane a
    query, and gathers are serial on this TPU: 467.5 us a step for
    65,536 queries whatever they hold, where a 720,896-lane 2-key sort
    takes 1.187 ms (benchmark/testdata/scoped_v5e, PR 24). So it pays
    only for a run too long to sort once a chunk (``first_new``)."""
    pos = jnp.searchsorted(sorted_arr, vals)
    pos = jnp.clip(pos, 0, sorted_arr.shape[0] - 1)
    return eq_u64(sorted_arr[pos], vals)


def merges(run_lanes: int, n_queries: int) -> bool:
    """Whether ``first_new`` merges ``run_lanes`` lanes of a sorted run
    with ``n_queries`` queries or searches the run: the static choice,
    from shapes alone. It is asked of a run's capacity where nothing
    says how much of it is real (the sharded engine's ``RunLSM``
    levels, a seen run at the sort's floor) and of each rung of
    ``merge_rungs`` where the run's real-lane count chooses the rung.
    The wave's append buffer is not asked: it is not sorted, so it
    cannot be searched, and what it costs follows the wave's count and
    not its capacity."""
    return run_lanes <= MERGE_LANES_PER_QUERY * n_queries


def wave_prefix_sizes(r0: int, cap: int) -> tuple[int, ...]:
    """The prefixes of a wave's append buffer that ``first_new`` sorts
    beside a run it does not cut (one at the sort's floor, one it
    searches): none of it, then ``r0`` lanes and four times as many
    again while that is under ``cap``, then all ``cap`` lanes."""
    sizes = [0]
    s = r0
    while s < cap:
        sizes.append(s)
        s <<= 2
    return (*sizes, cap)


def merge_rungs(
    run_lanes: int, n_queries: int, wave_prefix, floor: int = SORT_FLOOR_LANES,
) -> tuple[int, ...]:
    """The rungs of ``first_new``'s merged sort against a sorted run of
    ``run_lanes`` lanes whose real-lane count it is given: rising lane
    counts, each the front of the run with the wave's appended lanes
    laid into its padding, of which a chunk-step sorts the smallest
    that holds the run's real lanes and the wave's count together.

    From ``floor`` they stand in the ratios 1 : 1.5 : 2 : 3 : 4 ... up
    to the run and the whole buffer (``wave_prefix[-1]`` lanes), and
    the run with each of ``wave_prefix`` is a rung too, so no step
    sorts more than the uncut program did. A rung past the run's end
    is the run and padding. Of a run too long to merge whole, only the
    rungs that ``merges`` allows: content past the last is searched.
    A run of at most ``floor`` lanes has no rungs: ``wave_prefix`` is
    the whole choice there, as it was."""
    if run_lanes <= floor:
        return ()
    top = run_lanes + wave_prefix[-1]
    rungs = {run_lanes + p for p in wave_prefix}
    g = floor
    while g < top:
        rungs |= {g, min(g + g // 2, top)}
        g <<= 1
    return tuple(
        r for r in sorted(rungs) if merges(min(r, run_lanes), n_queries))


def dedup_plan(run_lanes, n_queries: int, wave_prefix=(), rungs=()) -> dict:
    """``first_new``'s choice for sorted runs of ``run_lanes`` lanes and
    an append buffer it sorts a prefix of (``wave_prefix``: the sizes;
    empty where the engine has no such buffer), as the engines' run
    records carry it: the run sizes merged, the run sizes searched, the
    prefix sizes, the rungs (``merge_rungs`` of the one run, where its
    real-lane count chooses among them; a run with rungs is listed
    under ``search`` if content past the last rung is searched and
    under ``merge`` otherwise), and the most lanes a chunk-step
    sorts."""
    prefix = [int(p) for p in wave_prefix]
    whole = [int(n) for n in run_lanes if merges(n, n_queries)]
    return {
        "merge": whole,
        "search": [int(n) for n in run_lanes if not merges(n, n_queries)],
        "wave_prefix": prefix,
        "rungs": [int(r) for r in rungs],
        "sort_lanes": max(
            sum(whole) + max(prefix, default=0), max(rungs, default=0),
        ) + int(n_queries),
    }


def _merged_new(vals, merged):
    """``first_new``'s merged sort: bool[n] in lane order, lane i of
    ``vals`` is not U64_MAX, is in none of the arrays ``merged`` (sorted
    or not) and in no lower lane."""
    n = vals.shape[0]
    n_run = sum(r.shape[0] for r in merged)
    hi, lo = split_u64(jnp.concatenate([*merged, vals]))
    tag = jnp.concatenate([
        jnp.zeros((n_run,), jnp.uint32),
        jnp.arange(1, n + 1, dtype=jnp.uint32),
    ])
    hi, lo, tag = lax.sort((hi, lo, tag), num_keys=2, is_stable=True)
    differs = jnp.concatenate([
        jnp.ones((1,), bool),
        (hi[1:] != hi[:-1]) | (lo[1:] != lo[:-1]),
    ])
    query = tag > 0
    new = query & differs & ~((hi == _U32_MAX) & (lo == _U32_MAX))
    back = jnp.where(
        query, (tag - 1) << 1 | new.astype(jnp.uint32), _U32_MAX)
    return (lax.sort(back)[:n] & 1).astype(bool)


def _laid(run, buf, real, lanes: int):
    """u64[lanes]: the front of the sorted run ``run``, U64_MAX past its
    first ``real`` lanes, with the wave buffer's lanes laid into that
    padding from lane ``real`` on — every real lane of both, as long as
    ``lanes`` holds them, in one select over a slice of the buffer that
    starts ``real`` lanes before its front. A rung past the run's end is
    the run and padding."""
    pad = functools.partial(jnp.pad, constant_values=U64_MAX)
    front = pad(run[:lanes], (0, max(0, lanes - run.shape[0])))
    held = buf[:lanes]
    shifted = lax.dynamic_slice(
        pad(held, (lanes, lanes - held.shape[0])), (lanes - real,), (lanes,))
    return jnp.where(jnp.arange(lanes, dtype=jnp.int32) < real, front, shifted)


def first_new(vals, occ, runs, wave=None, real=None):
    """bool[n] in lane order: lane i holds a value that is not U64_MAX,
    is in none of the sorted U64_MAX-padded ``runs``, is not among the
    first ``count`` lanes of the wave's append buffer, and is in no
    lower lane of ``vals`` — the seen-set probe, the in-wave probe and
    first-occurrence-in-chunk as one rule.

    Membership by merging, not searching: the runs that ``merges``
    says to merge, then the buffer's prefix, then ``vals``, are sorted
    together once as u32 pairs, stably, with a payload that is 0 on a
    run's or the buffer's lane and lane + 1 on a query's. Such a lane
    therefore comes before an equal query and equal queries keep lane
    order, and a lane is new iff it is a query, is not U64_MAX and
    differs from its predecessor. A second, single-operand sort of
    (lane << 1 | new) brings the bits back to lane order: no gather, no
    scatter. A merged run is sorted whether ``occ`` says it is occupied
    or not (an unoccupied run is all padding, which sorts after every
    real query); a run above the crossover keeps the binary search
    under its ``lax.cond`` and its hits are and-ed out.

    ``wave`` is ``(buf, count, sizes)``: a U64_MAX-padded u64 buffer the
    wave's earlier chunk-steps appended their new values to (in any
    order: the sort never needs its operands sorted, so the buffer is
    always merged and never searched), the traced i32 count of its real
    lanes, which are its first, and the static prefix sizes
    (``wave_prefix_sizes``; the last covers every lane that can be
    real). What is sorted is the smallest prefix that holds ``count``
    lanes, by a ``lax.switch`` over the sizes, so the sorts cost what
    the wave has written and not what it could hold.

    ``real`` is ``(seen_real, rungs)``, with ``wave`` and one run: the
    traced i32 count of the run's real lanes, which are its first, and
    ``merge_rungs`` of it. The sorts then cost what the run holds too:
    the switch's first cases are the rungs, each the run's front with
    the buffer's lanes laid into its padding (``_laid``), and a
    chunk-step takes the smallest that holds ``seen_real + count``
    lanes. Content past the last rung (a run too long to merge whole)
    takes the cases of ``sizes`` and is searched; content under it is
    not, whatever the run's capacity. Without rungs the count is not
    read.

    With ``wave`` the result is ``(new, lanes, queries)``: lanes the i32
    number of lanes the merged sort sorted, queries the i32 number of
    query lanes handed to ``probe_sorted`` (all ``n`` for each searched
    run that ``occ`` says is occupied, in a step that searches; 0 while
    every run is merged); without, the program is the one it always
    was."""
    n = vals.shape[0]
    assert n < 1 << 31
    merged = [r for r in runs if merges(r.shape[0], n)]
    searched = [(i, r) for i, r in enumerate(runs) if not merges(r.shape[0], n)]
    lanes = None
    past = None  # traced: the step's content is past the last rung
    with jax.named_scope("merge"):
        if wave is None:
            new = _merged_new(vals, merged)
        else:
            buf, count, sizes = wave
            fixed = sum(r.shape[0] for r in merged) + n

            def prefix(p):
                return lambda b, v, *m: (
                    _merged_new(v, [*m, b[:p]]), jnp.int32(fixed + p))

            case = sum((count > p).astype(jnp.int32) for p in sizes[:-1])
            branches = [prefix(p) for p in sizes]
            seen_real, rungs = real if real is not None else (None, ())
            if rungs:
                (run,) = runs

                def rung(r):
                    return lambda b, v, *m: (
                        _merged_new(v, [_laid(run, b, seen_real, r)]),
                        jnp.int32(r + n))

                need = seen_real + count
                held = sum((need > r).astype(jnp.int32) for r in rungs[:-1])
                if merged:  # the last rung is the run and the buffer whole
                    case, branches = held, [rung(r) for r in rungs]
                else:
                    past = need > rungs[-1]
                    case = jnp.where(past, len(rungs) + case, held)
                    branches = [*(rung(r) for r in rungs), *branches]
            new, lanes = lax.switch(case, branches, buf, vals, *merged)

    def searches(i):
        return occ[i] if past is None else occ[i] & past

    queries = jnp.int32(0)
    with jax.named_scope("search"):
        for i, r in searched:
            queries = queries + jnp.where(searches(i), jnp.int32(n), 0)
            hit = lax.cond(
                searches(i),
                lambda rr: probe_sorted(rr, vals),
                # vals != vals: all False, and typed as the other branch
                # is inside a shard_map (a plain zeros is unvarying there
                # and cond refuses the mismatch)
                lambda rr: ne_u64(vals, vals),
                r,
            )
            new = new & ~hit
    return new if wave is None else (new, lanes, queries)


def rank_onehot(rank, mask, n_ranks: int):
    """bool[..., n_ranks]: lane i's slot k says ``mask[i]`` and
    ``rank[i] == k``. A lane under a false mask, or whose rank is
    outside 0..n_ranks-1 (-1 on an invalid candidate), is all False:
    there is no drop bucket."""
    return (
        rank[..., None] == jnp.arange(n_ranks, dtype=rank.dtype)
    ) & mask[..., None]


def rank_counts(rank, mask, n_ranks: int):
    """i32[n_ranks]: for each k < n_ranks the lanes with ``mask`` set and
    ``rank == k`` — the per-action coverage counters of both device
    engines, counted once here.

    By compare and sum, never by scatter: a scatter-add is a serial pass
    on this TPU (60 ns a lane: 13.0 ms for a chunk's 217,088 candidate
    lanes into 13 buckets; PERF.md section 6, PR 27), where the one-hot
    compare fuses into a dense reduction of lanes x n_ranks
    lane-compares. The sum is int32 (int64 reductions are emulated on
    the chip), so a call counts fewer than 2^31 lanes; the caller widens
    once when it adds into the cumulative i64 counters. Sized for the
    12 to 21 ranks the spec lowerings have: a model with hundreds of
    ranks would want another count."""
    assert rank.size < 1 << 31
    return jnp.sum(
        rank_onehot(rank, mask, n_ranks),
        axis=tuple(range(rank.ndim)), dtype=jnp.int32,
    )


def dense_prefix_sel(new, n_lanes: int):
    """Gather indices compacting the ``new`` lanes to a dense prefix.

    Returns ``sel`` [n_lanes] with sel[j] = lane index of the j-th new
    lane for j < n_new, and ``n_lanes`` (the caller's pad/drop row) past
    the prefix: one sort of one int32 key a lane, as ``expand``'s
    compactions are (``engine.compact_chunk``), where a scatter into an
    index buffer is a serial pass of 4.6 ns a lane on this chip.
    """
    return lax.sort(jnp.where(
        new, jnp.arange(n_lanes, dtype=jnp.int32), n_lanes))


def emit_append(buf, block, count, n_new, cap: int):
    """Contiguous cursor-append emit: write ``block`` (B lanes/rows, the
    first n_new of which are real) into ``buf`` at row ``count`` with ONE
    ``lax.dynamic_update_slice``. The destinations of a chunk's survivors
    are provably a dense block at the running cursor, so the append
    lowers to a copy instead of the full-capacity arbitrary-index
    scatter ``.at[dst].set()`` lowers to (scripts/emit_micro.py measures
    the difference; it dominated the stage profile before this path).

    ``buf`` must carry >= B pad rows past ``cap``: rows [cap, cap+B) are
    the drop region — the append analog of the retired scatter's drop
    row ``cap``. The start is clamped to ``cap``, so a cursor past
    capacity (only reachable with the overflow flag already raised, and
    the run aborting) lands the whole block in the pad region and rows
    [0, cap) stay bit-identical to the scatter path's.

    Returns ``(buf, overflow)`` with ``overflow = count + n_new > cap``.
    """
    start = jnp.minimum(count, cap)
    if buf.ndim == 2:
        buf = lax.dynamic_update_slice(buf, block, (start, jnp.int32(0)))
    else:
        buf = lax.dynamic_update_slice(buf, block, (start,))
    return buf, count + n_new > cap


def jit_with_donation(fn, donate_argnums, name, warm_args, **jit_kw):
    """``jax.jit(fn, donate_argnums=...)``, compiled and run once on
    ``warm_args()``, with a refused donation an error naming the program.

    JAX decides at lowering, from shapes alone and the same way on every
    backend, whether a donated input can take an output's place; one
    that cannot is reported as a UserWarning and silently kept alive —
    the caller then holds old + new where it believed the update was in
    place. Callers therefore DECLARE donation only for inputs an output
    can alias (same shape and dtype; see RunLSM.merge_spec and
    DeviceBFS._seen_merge_spec) and build everything else undonated, so
    a warning here means the declaration is wrong. ``warm_args`` must
    return fresh throwaway buffers: a successful donation consumes them.
    """
    jitted = jax.jit(fn, donate_argnums=donate_argnums, **jit_kw)
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "error", message="Some donated buffers were not usable")
        try:
            jax.block_until_ready(jitted(*warm_args()))
        except UserWarning as w:
            raise RuntimeError(
                f"device program {name}: donation refused — {w}"
            ) from None
    return jitted


def next_cap(needed: int, cap: int, max_cap: int, growth: int, unit: int) -> int:
    """Smallest growth**k * cap >= needed, rounded up to a multiple of
    unit, never exceeding max_cap (max_cap is rounded DOWN to a unit
    multiple so the user's bound is a hard ceiling; cap itself is assumed
    unit-aligned already)."""
    eff_max = max(cap, (max_cap // unit) * unit)
    new = cap
    while new < needed and new < eff_max:
        new = min(new * growth, eff_max)
    new = ((new + unit - 1) // unit) * unit
    return min(new, eff_max)
