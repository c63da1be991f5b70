"""Capacity-growth policy, sorted-set probe and the contiguous
cursor-append emit shared by the device-resident checkers (DeviceBFS and
the sharded engine), so a policy fix lands once."""

from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.hashing import eq_u64

GROWTH = 4  # enlarge factor per growth step
HEADROOM = 3  # grow when the next wave could need more than cap/HEADROOM
I32_MAX = np.int32(2**31 - 1)  # "no violation" sentinel in journal folds


def probe_sorted(sorted_arr, vals):
    """Membership of vals in a sorted u64 array padded with U64_MAX.
    (u64 searchsorted is fast on this TPU; elementwise u64 == is not —
    the equality check decomposes to u32, ops/hashing.py.)"""
    pos = jnp.searchsorted(sorted_arr, vals)
    pos = jnp.clip(pos, 0, sorted_arr.shape[0] - 1)
    return eq_u64(sorted_arr[pos], vals)


def dense_prefix_sel(new, npos, n_lanes: int):
    """Gather indices compacting the ``new`` lanes to a dense prefix.

    ``npos = cumsum(new) - 1`` (int32, the destination rank of each new
    lane). Returns ``sel`` [n_lanes] with sel[j] = lane index of the
    j-th new lane for j < n_new, and ``n_lanes`` (the caller's pad/drop
    row) past the prefix. Same one-hot-scatter idiom as the valid-lane
    compaction in the chunk pipeline: the scatter is confined to an
    (n_lanes+1)-sized index buffer, never a capacity-sized one.
    """
    edst = jnp.where(new, npos, n_lanes)
    return (
        jnp.full((n_lanes + 1,), n_lanes, jnp.int32)
        .at[edst]
        .set(jnp.arange(n_lanes, dtype=jnp.int32))[:n_lanes]
    )


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
