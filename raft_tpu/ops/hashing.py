"""64-bit state fingerprinting — formula v4 (u32-pair internals).

TLC dedups on 64-bit fingerprints of the (VIEW-projected, symmetry-reduced)
state; we reproduce the same collision budget with a vectorized
Zobrist-style hash: each lane of the int32 state vector is avalanche-mixed
together with its position, lanes reduce, and a final mix finishes.

v4 (round 5): all MIXING arithmetic runs as TWO INDEPENDENT 32-bit
streams (murmur3-style fmix32 with distinct multiplicative constants and
positional salts), combined into one u64 only at the end. Rationale,
measured on the TPU backend of round 5 (scripts/hash32_micro.py +
chained micro-benches; not re-measured on the present installation):

  u64 multiply   ~150 ms / 12.5M lanes   (emulated/scalarized)
  u64 == / sort  ~55-58 ms / 12.5M       (comparator path)
  u32 mix stream  ~0.2 ms / 75M lanes    (native VPU)

i.e. the v1-v3 splitmix64 hash paid a ~400x penalty on every lane, which
is why canonicalization owned 96-98% of chunk time through round 4.
Two independent 32-bit streams keep the 2^-64-class collision budget
(the audit's second hash family still fails independently via `seed`).

Empirical TPU rules encoded here (see also `sort_u64` / `ne_u64`):
  - never MULTIPLY u64 lanes -> u32-pair streams
  - never jnp.sort a u64 array -> 2-key (hi, lo) u32 lax.sort
  - never ==/!= u64 lanes at scale -> decomposed u32 compares
  - u64 xor/shift/add/min are fine
  - never look a batch up by searchsorted where a sort can do it: each
    step of the binary search is a gather, and gathers are serial on
    this chip — 467.5 us a step for 65,536 queries whatever they hold,
    where a 720,896-lane 2-key u32 sort takes 1.187 ms (the recorded
    v5e trace benchmark/testdata/scoped_v5e, PR 24). Merge instead
    (checker/util.py first_new); the search pays only against a run too
    long to sort once a chunk

One fusion caveat: TWO separate reductions over one producer hit an XLA
fusion cliff (~400x); the pair streams are therefore STACKED into one
array and reduced by a single op (`_reduce_pair`).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import lax

_C1 = np.uint64(0x9E3779B97F4A7C15)  # golden-ratio increment (splitmix64)
_C2 = np.uint64(0xC2B2AE3D27D4EB4F)
_M1 = np.uint64(0xBF58476D1CE4E5B9)
_M2 = np.uint64(0x94D049BB133111EB)

U64_MAX = np.uint64(0xFFFFFFFFFFFFFFFF)  # "no fingerprint" sentinel
_MASK32 = np.uint64(0xFFFFFFFF)

# u32 stream constants (murmur3 c1/c2 + fmix32 multipliers + golden ratios)
KA = np.uint32(0xCC9E2D51)
KB = np.uint32(0x1B873593)
PA = np.uint32(0x9E3779B9)
PB = np.uint32(0x85EBCA77)
_F1 = np.uint32(0x85EBCA6B)
_F2 = np.uint32(0xC2B2AE35)


def mix64(z):
    """splitmix64 finalizer — full-avalanche 64-bit mix. HOST/setup-time
    and tiny-array use only: u64 multiplies are ~400x slow on this TPU."""
    z = (z ^ (z >> np.uint64(30))) * _M1
    z = (z ^ (z >> np.uint64(27))) * _M2
    return z ^ (z >> np.uint64(31))


def mix32(z):
    """murmur3 fmix32 — full-avalanche 32-bit mix (native TPU u32 ops)."""
    z = (z ^ (z >> np.uint32(16))) * _F1
    z = (z ^ (z >> np.uint32(13))) * _F2
    return z ^ (z >> np.uint32(16))


def combine_pair(a, b):
    """(u32, u32) stream pair -> u64, with a final cross-avalanche so a
    change in either stream diffuses into both output words (u32 ops
    only — no u64 multiply)."""
    a2 = mix32(a + (b ^ KA))
    b2 = mix32(b + (a ^ KB))
    return a2.astype(jnp.uint64) << np.uint64(32) | b2.astype(jnp.uint64)


def _reduce_pair(ha, hb, op="xor"):
    """Reduce two [..., K] u32 streams over the lane axis with ONE reduce
    op (two separate reduces over a shared producer hit the fusion
    cliff, see module docstring)."""
    h = jnp.stack([ha, hb], axis=-1)  # [..., K, 2]
    if op == "xor":
        r = jnp.bitwise_xor.reduce(h, axis=-2)
    else:
        r = jnp.sum(h, axis=-2, dtype=jnp.uint32)
    return r[..., 0], r[..., 1]


def seed_salts(seed: int) -> tuple[np.uint32, np.uint32]:
    """Host-derived per-seed u32 salt pair; (0, 0) for seed=0 so the
    default family is the plain stream."""
    if not seed:
        return np.uint32(0), np.uint32(0)
    m = 0xFFFFFFFFFFFFFFFF
    z = (seed * 0x9E3779B97F4A7C15) & m
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & m
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & m
    z ^= z >> 31
    return np.uint32(z >> 32), np.uint32(z & 0xFFFFFFFF)


def hash_lanes_pair(vec, seed: int = 0):
    """Hash an int32 [..., K] vector to a (u32, u32) stream pair.

    A nonzero seed selects an independent hash family by XORing a
    seed-derived per-lane stream into the inputs BEFORE the multiply —
    a constant additive seed would merely translate every lane's pre-mix
    input, leaving the family invariant on the collision class where two
    states' multisets of pre-mix lane values coincide (the collision
    audit, checker/audit.py, relies on families failing independently)."""
    k = vec.shape[-1]
    x = vec.astype(jnp.uint32)
    pos = jnp.arange(k, dtype=jnp.uint32)
    pa = pos * PA
    pb = pos * PB
    xa = x
    xb = x
    if seed:
        sa, sb = seed_salts(seed)
        xa = x ^ mix32(pa + sa)
        xb = x ^ mix32(pb + sb)
    ha = mix32(xa * KA + pa)
    hb = mix32(xb * KB + pb)
    acc_a, acc_b = _reduce_pair(ha, hb, op="xor")
    ka = np.uint32((k * int(KA)) & 0xFFFFFFFF)
    kb = np.uint32((k * int(KB)) & 0xFFFFFFFF)
    return acc_a ^ ka, acc_b ^ kb


def hash_lanes(vec, seed: int = 0):
    """Hash an int32 [..., K] vector to uint64 [...] (v4 pair scheme)."""
    return combine_pair(*hash_lanes_pair(vec, seed))


# ---------------- u64 lane helpers (decomposed fast paths) ----------------


def split_u64(x):
    """u64 [...] -> (hi, lo) u32 pair (shifts/ands only — fast)."""
    return (x >> np.uint64(32)).astype(jnp.uint32), (x & _MASK32).astype(
        jnp.uint32
    )


def join_u64(hi, lo):
    return hi.astype(jnp.uint64) << np.uint64(32) | lo.astype(jnp.uint64)


def sort_u64(x, axis=-1):
    """Sort u64 values (ascending) via a 2-key u32 lax.sort — ~300x the
    single-array u64 sort on this TPU."""
    hi, lo = split_u64(x)
    shi, slo = lax.sort((hi, lo), num_keys=2, dimension=axis)
    return join_u64(shi, slo)


def sort_u64_with_idx(x, axis=-1):
    """Stable ascending u64 sort returning (sorted, original_index):
    a 3-key u32 sort with the index iota as the tie-breaking key, so
    equal values keep first-occurrence order (gid-numbering parity)."""
    hi, lo = split_u64(x)
    idx = lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1 if axis == -1 else axis)
    shi, slo, sidx = lax.sort((hi, lo, idx), num_keys=3, dimension=axis)
    return join_u64(shi, slo), sidx


def ge_u64(a, b):
    """Elementwise a >= b on u64 via u32 compares (u64 comparator lanes
    are slow on this TPU)."""
    ah, al = split_u64(a)
    bh, bl = split_u64(b)
    return (ah > bh) | ((ah == bh) & (al >= bl))


def ne_u64(a, b):
    """Elementwise a != b on u64 via u32 compares (u64 ==/!= lanes are
    ~180x slow on this TPU)."""
    ah, al = split_u64(a)
    bh, bl = split_u64(b)
    return (ah != bh) | (al != bl)


def eq_u64(a, b):
    ah, al = split_u64(a)
    bh, bl = split_u64(b)
    return (ah == bh) & (al == bl)
