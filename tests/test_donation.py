"""Buffer donation must actually stick (round 6).

The wave program and the LSM merges declare donate_argnums so the big
HBM carries (next frontier, journal, seen runs) update in place.
Donation that silently fails is worse than none: XLA copies the buffer
AND emits a UserWarning per dispatch. These tests pin:

  1. no donation warning anywhere in a full DeviceBFS / ShardedBFS run
     under ``-W error`` semantics (the merges declare donation only for
     the input an output can alias, and jit_with_donation turns a
     refused donation into an error instead of an undonated program);
  2. the wave program's donated inputs are really consumed
     (``.is_deleted()`` on the donated carries after a wave);
  3. two back-to-back ``run()`` calls on ONE engine instance produce
     identical results from cold carries — donation must not leak one
     run's buffers into the next.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.checker.device_bfs import DeviceBFS
from raft_tpu.checker.util import jit_with_donation
from raft_tpu.models.raft import RaftParams, cached_model

TINY = RaftParams(n_servers=2, n_values=1, max_elections=2, max_restarts=0, msg_slots=16)
INVS = ("LeaderHasAllAckedValues", "NoLogDivergence")


def _device(**kw):
    kw.setdefault("chunk", 256)
    kw.setdefault("frontier_cap", 1 << 12)
    kw.setdefault("seen_cap", 1 << 14)
    kw.setdefault("journal_cap", 1 << 14)
    return DeviceBFS(cached_model(TINY), invariants=INVS, symmetry=True, **kw)


def test_static_donation_audit_clean():
    """The static pin migrated to the donation lint pass: it lowers the
    wave program and reads the ``tf.aliasing_output`` attributes off
    the StableHLO ``@main`` signature, proving every declared carry
    really aliases an output (and the pinned frontier does not) —
    complementing the runtime ``is_deleted()`` probes below."""
    from raft_tpu.analysis import donation

    res = donation.run(families=("raft",), scopes=("device",))
    assert res.checked > 0
    assert not res.findings, [f.render() for f in res.findings]


def test_device_run_emits_no_donation_warning():
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        res = _device().run()
    assert res.exhausted and res.violation is None


@pytest.mark.slow
def test_sharded_run_emits_no_donation_warning():
    from raft_tpu.parallel.sharded import ShardedBFS

    engine = ShardedBFS(
        cached_model(TINY), invariants=INVS, symmetry=True,
        devices=jax.devices()[:1], chunk=256,
        frontier_cap=1 << 10, seen_cap=1 << 12,
    )
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        res = engine.run()
    assert res.exhausted and res.violation_invariant is None


def test_wave_program_consumes_donated_carries():
    """The wave program donates next_buf/journal/viol/stats/cov
    (argnums 1..6): after a dispatch, those input buffers must be
    deleted — deleted means XLA aliased or freed them instead of keeping
    a live copy per wave."""
    dev = _device()
    W = dev.W
    frontier = jnp.zeros((dev.FCAP + dev.VC, W), jnp.int32)
    donated = dict(
        next_buf=jnp.zeros((dev.FCAP + dev.VC, W), jnp.int32),
        jparent=jnp.zeros((dev.JCAP + dev.VC,), jnp.int32),
        jcand=jnp.zeros((dev.JCAP + dev.VC,), jnp.int32),
        viol=jnp.full((len(INVS),), np.int32(2**31 - 1), jnp.int32),
        stats=jnp.zeros((dev.N_STATS,), jnp.int64),
        cov=jnp.zeros((dev.n_actions, 3), jnp.int64),
    )
    seen = jnp.full((dev._seen_sizes[0],), np.uint64(2**64 - 1), jnp.uint64)
    out = dev._wave_fn(
        frontier, *donated.values(), np.int32(0), np.int32(0),
        np.int32(0), dev._occ_one, seen,
    )
    jax.block_until_ready(out)
    for name, buf in donated.items():
        assert buf.is_deleted(), f"donated carry {name} survived the wave"
    # the frontier (argnum 0) is NOT donated: the host swaps it with
    # next_buf between waves, so it must stay live
    assert not frontier.is_deleted()


def test_jit_with_donation_sticks_or_raises():
    """A donation an output can alias sticks (input deleted, no
    warning); one no output can alias is an error naming the program —
    never a silently undonated program."""
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        fn = jit_with_donation(
            lambda x: x + 1, (0,), "inc",
            lambda: (jnp.zeros((128,), jnp.int32),),
        )
        arg = jnp.zeros((128,), jnp.int32)
        jax.block_until_ready(fn(arg))
        assert arg.is_deleted()
    with pytest.raises(RuntimeError, match="program widen: donation refused"):
        jit_with_donation(
            lambda x: jnp.concatenate([x, x]), (0,), "widen",
            lambda: (jnp.zeros((128,), jnp.int32),),
        )


@pytest.mark.slow
def test_back_to_back_runs_identical():
    """One engine instance, two cold runs: donation must not leak the
    first run's carries (or its seen contents) into the second."""
    dev = _device()
    r1 = dev.run(collect_metrics=True)
    r2 = dev.run(collect_metrics=True)
    assert r1.distinct == r2.distinct
    assert r1.depth_counts == r2.depth_counts
    assert r1.total == r2.total
    assert r1.terminal == r2.terminal
    assert r1.coverage == r2.coverage
    k1 = [{k: m[k] for k in ("new", "distinct", "generated")} for m in r1.metrics]
    k2 = [{k: m[k] for k in ("new", "distinct", "generated")} for m in r2.metrics]
    assert k1 == k2
