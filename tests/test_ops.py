"""Unit tests for bit packing, bag kernels, and hashing."""

import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.ops.bag import bag_discard_at, bag_put, bag_sort
from raft_tpu.ops.hashing import hash_lanes
from raft_tpu.ops.packing import EMPTY, BitPacker, bits_for


def test_bits_for():
    assert bits_for(0) == 1
    assert bits_for(1) == 1
    assert bits_for(3) == 2
    assert bits_for(4) == 3


def test_packer_roundtrip():
    pk = BitPacker([("a", 3), ("b", 4), ("c", 20), ("d", 10), ("e", 1)])
    hi, lo = pk.pack(a=5, b=9, c=(1 << 20) - 1, d=1023, e=1)
    assert pk.unpack(hi, lo, "a") == 5
    assert pk.unpack(hi, lo, "b") == 9
    assert pk.unpack(hi, lo, "c") == (1 << 20) - 1
    assert pk.unpack(hi, lo, "d") == 1023
    assert pk.unpack(hi, lo, "e") == 1
    assert 0 <= hi < (1 << 30) and 0 <= lo < (1 << 30)


def test_packer_replace():
    pk = BitPacker([("a", 3), ("b", 4), ("c", 20), ("d", 10)])
    hi, lo = pk.pack(a=2, b=3, c=12345, d=77)
    hi2, lo2 = pk.replace(hi, lo, "a", 7)
    hi2, lo2 = pk.replace(hi2, lo2, "d", 3)
    assert pk.unpack(hi2, lo2, "a") == 7
    assert pk.unpack(hi2, lo2, "b") == 3
    assert pk.unpack(hi2, lo2, "c") == 12345
    assert pk.unpack(hi2, lo2, "d") == 3


def test_packer_range_check():
    pk = BitPacker([("a", 3)])
    with pytest.raises(ValueError):
        pk.pack(a=8)


def _empty_bag(m=6):
    hi = jnp.full((m,), int(EMPTY), jnp.int32)
    lo = jnp.full((m,), int(EMPTY), jnp.int32)
    cnt = jnp.zeros((m,), jnp.int32)
    return hi, lo, cnt


def test_bag_put_and_discard():
    hi, lo, cnt = _empty_bag()
    hi, lo, cnt, existed, ovf = bag_put(hi, lo, cnt, jnp.int32(5), jnp.int32(7))
    assert not bool(existed) and not bool(ovf)
    hi, lo, cnt, existed, _ = bag_put(hi, lo, cnt, jnp.int32(5), jnp.int32(7))
    assert bool(existed)
    assert int(cnt[0]) == 2 and int(hi[0]) == 5
    # discard twice: count 0 but key stays in the domain (TLA+ bag semantics)
    cnt = bag_discard_at(cnt, 0)
    cnt = bag_discard_at(cnt, 0)
    assert int(cnt[0]) == 0 and int(hi[0]) == 5
    hi, lo, cnt, existed, _ = bag_put(hi, lo, cnt, jnp.int32(5), jnp.int32(7))
    assert bool(existed) and int(cnt[0]) == 1


def test_bag_sorted_canonical():
    hi, lo, cnt = _empty_bag()
    for k in [(9, 1), (2, 8), (2, 3), (5, 5)]:
        hi, lo, cnt, _, _ = bag_put(hi, lo, cnt, jnp.int32(k[0]), jnp.int32(k[1]))
    keys = list(zip(np.asarray(hi).tolist(), np.asarray(lo).tolist()))
    assert keys[:4] == [(2, 3), (2, 8), (5, 5), (9, 1)]
    assert all(h == int(EMPTY) for h, _ in keys[4:])


def test_bag_overflow_flag():
    hi, lo, cnt = _empty_bag(2)
    hi, lo, cnt, _, o1 = bag_put(hi, lo, cnt, jnp.int32(1), jnp.int32(1))
    hi, lo, cnt, _, o2 = bag_put(hi, lo, cnt, jnp.int32(2), jnp.int32(2))
    hi, lo, cnt, _, o3 = bag_put(hi, lo, cnt, jnp.int32(3), jnp.int32(3))
    assert not bool(o1) and not bool(o2) and bool(o3)


def test_hash_lanes_sensitivity():
    v = jnp.zeros((4, 8), jnp.int32)
    h0 = np.asarray(hash_lanes(v))
    assert len(set(h0.tolist())) == 1
    v2 = v.at[0, 3].set(1)
    v3 = v.at[0, 4].set(1)
    h2 = np.asarray(hash_lanes(v2))
    h3 = np.asarray(hash_lanes(v3))
    assert h2[0] != h0[0] and h3[0] != h0[0] and h2[0] != h3[0]
    assert h2[1] == h0[1]



@pytest.mark.parametrize("shape", [(4,), (4, 6), (4, 4)])
def test_onehot_reads_index_inside_the_axis_and_read_zero_outside(shape):
    """`onehot_row` and `onehot_get2` (models/base.py) equal `arr[i]`
    and `arr[i, j]` for an index inside the axis, per lane under vmap
    as the kernels call them; outside it they read 0 where a gather
    clamps, which is why the kernels clip a computed position first."""
    import jax

    from raft_tpu.models.base import onehot_get2, onehot_row

    arr = jnp.asarray(
        np.random.default_rng(7).integers(1, 99, shape), jnp.int32)
    idx = jnp.arange(-1, shape[0] + 1, dtype=jnp.int32)
    rows = jax.vmap(lambda i: onehot_row(arr, i))(idx)
    np.testing.assert_array_equal(rows[1:-1], arr)
    assert not rows[0].any() and not rows[-1].any()
    if len(shape) == 2:
        jdx = jnp.arange(-1, shape[1] + 1, dtype=jnp.int32)
        got = jax.vmap(lambda i: jax.vmap(
            lambda j: onehot_get2(arr, i, j))(jdx))(idx)
        np.testing.assert_array_equal(got[1:-1, 1:-1], arr)
        assert not got[0].any() and not got[-1].any()
        assert not got[:, 0].any() and not got[:, -1].any()
