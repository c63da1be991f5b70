"""Branchless message-bag kernels.

TLA+ semantics being reproduced (reference ``standard-raft/Raft.tla``):
  - the bag is a function record -> delivery count (``Raft.tla:55-58``);
  - ``Discard`` decrements the count but the record STAYS in the domain
    (``Raft.tla:164-167``) — this is what makes ``_SendOnce`` a permanent
    action-disable latch (``Raft.tla:134-138``). Hence slots are never
    freed: the slot table grows monotonically within a behavior and
    count-0 slots are genuine state that must fingerprint.

Encoding: N key words + a count lane per slot (see ops/packing.py).
``words`` is a list of [M] int32 arrays in lexicographic sort order;
unused slots hold (EMPTY, ..., 0) and sort last; keys are unique, so the
sorted slot table is a canonical form and bag equality is array equality.
The 2-word (hi, lo) kernels used by the BitPacker models are thin
wrappers over the N-word ones.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from .packing import EMPTY


def wide_bag_sort(words, cnt):
    """Canonicalize: sort slots lexicographically by the key words."""
    out = lax.sort((*words, cnt), num_keys=len(words))
    return list(out[:-1]), out[-1]


def wide_bag_put(words, cnt, key):
    """Add one delivery of the key tuple — TLA+ ``_SendNoRestriction``
    (``Raft.tla:129-132``): increment if the record is in the domain, else
    insert with count 1.

    Returns (words, cnt, existed, overflow). ``existed`` lets callers
    implement ``_SendOnce`` (valid iff not existed). ``overflow`` is True
    when an insert was needed but no slot was free — the driver must abort
    and re-run with more slots (never silently dropped).

    The slot table is ALWAYS sorted on entry (the bag invariant: states
    are canonical, and put/discard preserve sort order), so the insert is
    a branchless shift at the key's lexicographic position — bit-identical
    to the retired insert-into-an-empty-then-``lax.sort`` kernel (unique
    keys, ``EMPTY`` = 2**WORD_BITS strictly above every packed word, so
    the insertion point is unique and empties stay a suffix), at a
    fraction of the cost: the M-lane sort network was ~2/3 of every
    message-sending action kernel, paid once per put per candidate lane.
    Elementwise where/roll instead of a traced-index scatter also
    keeps the kernel out of reach of a scatter-drop miscompile (a TPU
    compiler of rounds 2-5 silently dropped traced-index scatter
    writes at batch >= 4096: dedup miscounts with no in-run signal);
    the systematic defense for the remaining traced scatters is the
    two-chunk parity gate (checker/parity.py) plus the CPU chunk-sweep
    tests.
    """
    eq = jnp.ones_like(words[0], dtype=bool)
    for w, k in zip(words, key):
        eq &= w == k
    existed = eq.any()
    cnt_inc = cnt + eq.astype(cnt.dtype)

    have_empty = (words[0] == EMPTY).any()
    # lexicographic rank of the key among the resident slots; empties
    # hold (EMPTY, ..., 0) and EMPTY exceeds every packed word, so they
    # never count and the insert position lands before the empty suffix
    less = jnp.zeros_like(words[0], dtype=bool)
    tie = jnp.ones_like(words[0], dtype=bool)
    for w, k in zip(words, key):
        less |= tie & (w < k)
        tie &= w == k
    pos = jnp.sum(less.astype(jnp.int32))
    lane = jnp.arange(cnt.shape[0], dtype=jnp.int32)
    # lanes < pos keep their slot, lane pos takes the key, lanes > pos
    # take their left neighbor (the shifted-out last lane is an empty
    # whenever a free slot exists; without one, overflow aborts the run
    # before any lane is trusted). roll()'s lane-0 wraparound is never
    # selected: lane 0 is either < pos or == pos.
    ins = [
        jnp.where(lane < pos, w, jnp.where(lane == pos, k, jnp.roll(w, 1)))
        for w, k in zip(words, key)
    ]
    cnt_ins = jnp.where(
        lane < pos, cnt, jnp.where(lane == pos, jnp.int32(1), jnp.roll(cnt, 1))
    )

    out = [jnp.where(existed, w, wi) for w, wi in zip(words, ins)]
    cnt2 = jnp.where(existed, cnt_inc, cnt_ins)
    overflow = (~existed) & (~have_empty)
    return out, cnt2, existed, overflow


def bag_sort(hi, lo, cnt):
    """2-word canonicalization: sort by (hi, lo); empties last."""
    words, cnt = wide_bag_sort([hi, lo], cnt)
    return words[0], words[1], cnt


def bag_count(hi, lo, cnt, khi, klo):
    """Delivery count of a key (0 if not in the domain)."""
    eq = (hi == khi) & (lo == klo)
    return jnp.sum(jnp.where(eq, cnt, 0))


def bag_put(hi, lo, cnt, khi, klo):
    """2-word ``_SendNoRestriction``; see wide_bag_put."""
    words, cnt2, existed, overflow = wide_bag_put([hi, lo], cnt, (khi, klo))
    return words[0], words[1], cnt2, existed, overflow


def bag_discard_at(cnt, slot):
    """``Discard`` (``Raft.tla:164-167``): one fewer delivery; domain keeps
    the record, so keys don't move and no re-sort is needed.

    One-hot subtract for the same scatter-miscompile reason as
    wide_bag_put."""
    onehot = jnp.arange(cnt.shape[0], dtype=jnp.int32) == slot
    return cnt - onehot.astype(cnt.dtype)
