"""The audit registry: tiny-constants instances of all six spec
lowerings plus engine factories at lint geometry.

The passes prove contracts on LOWERINGS, not runs, so the constants are
the smallest that exercise every structural feature (the same bindings
tests/test_expand_sparse.py sweeps). Models are cached per lint process
(``cached_model`` shares jitted kernels with the test suite); engines
are built fresh per pass — construction traces nothing beyond the
wave/chunk jit wrappers.

Lint engine geometry: capacities small enough that program LOWERING (the
only cost a pass pays) stays in the tier-1 smoke budget, while keeping
every structural element real — a multi-size seen ladder, VC pad rows,
the wave's fingerprint buffer and its prefix switch.
"""

from __future__ import annotations

import importlib

# family -> (models submodule, params builder kwargs) — tiny constants,
# one binding per spec lowering, mirroring tests/test_expand_sparse.py
FAMILY_PARAMS = {
    "raft": ("raft", "RaftParams", dict(
        n_servers=2, n_values=2, max_elections=2, max_restarts=0,
        msg_slots=16,
    )),
    "pull_raft": ("pull_raft", "PullRaftParams", dict(
        n_servers=3, n_values=1, max_elections=2, max_restarts=0,
        msg_slots=24,
    )),
    "kraft": ("kraft", "KRaftParams", dict(
        n_servers=3, n_values=1, max_elections=2, max_restarts=0,
        msg_slots=24,
    )),
    "joint_raft": ("joint_raft", "JointRaftParams", dict(
        n_servers=3, n_values=1, init_cluster_size=2, max_elections=1,
        max_restarts=0, max_reconfigs=1, max_values_per_term=1,
        reconfig_type=2, msg_slots=64,
    )),
    "reconfig_raft": ("reconfig_raft", "ReconfigRaftParams", dict(
        n_servers=3, n_values=1, init_cluster_size=2, max_elections=1,
        max_restarts=0, max_values_per_term=1, max_add_reconfigs=1,
        max_remove_reconfigs=1, min_cluster_size=2, max_cluster_size=3,
        msg_slots=64,
    )),
    "kraft_reconfig": ("kraft_reconfig", "KRaftReconfigParams", dict(
        n_hosts=3, n_values=1, init_cluster_size=2, min_cluster_size=2,
        max_cluster_size=3, max_elections=1, max_restarts=1,
        max_values_per_epoch=1, max_add_reconfigs=1,
        max_remove_reconfigs=1, max_spawned_servers=4, msg_slots=24,
    )),
}

FAMILIES = tuple(FAMILY_PARAMS)

# the same module set the ACTION_NAMES lock-step contract spans
MODEL_MODULES = (
    "raft", "kraft", "pull_raft", "kraft_reconfig", "joint_raft",
    "reconfig_raft",
)

# lint engine geometry (DeviceBFS): small caps, real structure. The
# max_seen_cap of 1<<20 yields a TWO-size seen ladder (1<<18, 1<<20) so
# the signature pass proves closure over a non-trivial ladder without
# the donation pass paying for extra wave lowerings.
DEVICE_KW = dict(
    chunk=256,
    frontier_cap=1 << 10,
    seen_cap=1 << 12,
    journal_cap=1 << 12,
    max_seen_cap=1 << 20,
)

SHARDED_KW = dict(
    chunk=256,
    frontier_cap=1 << 10,
    seen_cap=1 << 12,
    max_seen_cap=1 << 18,
)

INVARIANTS = {
    "raft": ("NoLogDivergence",),
    "pull_raft": ("NoLogDivergence",),
    "kraft": ("NoLogDivergence",),
    "joint_raft": ("NoLogDivergence",),
    "reconfig_raft": ("NoLogDivergence",),
    "kraft_reconfig": ("NoLogDivergence",),
}


def family_module(name: str):
    mod, _, _ = FAMILY_PARAMS[name]
    return importlib.import_module(f"raft_tpu.models.{mod}")


def tiny_params(name: str):
    mod, cls, kw = FAMILY_PARAMS[name]
    return getattr(family_module(name), cls)(**kw)


def tiny_model(name: str):
    """The shared (memoized) tiny model for ``name`` — reuses the test
    suite's instance and its jitted kernels when already built."""
    return family_module(name).cached_model(tiny_params(name))


def fresh_tiny_model(name: str):
    """A NEVER-cached instance: mutation self-tests patch model-building
    hooks and must not poison the shared ``cached_model`` entry."""
    return type(tiny_model(name))(tiny_params(name))


def device_engine(name: str, model=None, **overrides):
    from ..checker.device_bfs import DeviceBFS

    kw = dict(DEVICE_KW)
    kw.update(overrides)
    return DeviceBFS(
        model if model is not None else tiny_model(name),
        invariants=INVARIANTS[name], symmetry=True, **kw,
    )


def sharded_engine(name: str, **overrides):
    import jax

    from ..parallel.sharded import ShardedBFS

    kw = dict(SHARDED_KW)
    kw.update(overrides)
    return ShardedBFS(
        tiny_model(name), invariants=INVARIANTS[name], symmetry=True,
        devices=jax.devices()[:1], **kw,
    )
