"""The benchmark's whole view of the program under test.

Every import from ``raft_tpu`` sits in this file. What the benchmark
needs the program to keep (PERF.md section 3 lists it for refactors):

  raft_tpu.utils.cfg.parse_cfg(path)
  raft_tpu.models.registry.build_from_cfg(cfg, msg_slots=) -> setup with
      .model, .invariants, .symmetry
  raft_tpu.checker.device_bfs.DeviceBFS(model, invariants=, symmetry=,
      chunk=, frontier_cap=, seen_cap=, journal_cap=, max_frontier_cap=,
      max_seen_cap=, max_journal_cap=) with
      .run(max_depth=, collect_metrics=True, telemetry=) -> CheckResult
  raft_tpu.parallel.sharded.ShardedBFS(..., devices=) with
      .run(max_depth=, collect_metrics=True, telemetry=) -> ShardedResult
  raft_tpu.obs.NULL_TELEMETRY, the do-nothing telemetry facade, and the
      one call of it the benchmark answers itself: the wave loop's
      ``with telemetry.wave_annotation(depth):`` around each wave's
      dispatch and fetch, once a wave, in depth order
  result fields: depth_counts, distinct, total, terminal, exit_cause,
      violation | violation_invariant, metrics (wave rows), stats
  wave-row keys: depth, frontier, overflow_bits, wave_s, elapsed_s
  the compile cache: JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache
      (raft_tpu.enable_compcache, called by the engines' constructors)
"""

from __future__ import annotations

import contextlib
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cache_dir() -> str:
    """Where the program keeps its persistent compile cache on an
    accelerator (raft_tpu.enable_compcache)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")


def build_engine(cfg_path: str, engine: str, params: dict, devices):
    """Model and engine exactly as ``python -m raft_tpu`` builds them
    (raft_tpu/__main__.py), with the cell's engine parameters."""
    from raft_tpu.models.registry import build_from_cfg
    from raft_tpu.utils.cfg import parse_cfg

    params = dict(params)
    cfg = parse_cfg(cfg_path)
    setup = build_from_cfg(cfg, msg_slots=params.pop("msg_slots"))
    common = dict(invariants=setup.invariants, symmetry=setup.symmetry)
    if engine == "device":
        from raft_tpu.checker.device_bfs import DeviceBFS

        return DeviceBFS(setup.model, **common, **params)
    if engine == "sharded":
        from raft_tpu.parallel.sharded import ShardedBFS

        return ShardedBFS(setup.model, devices=devices, **common, **params)
    raise ValueError(f"unknown engine {engine!r} (device or sharded)")


def ident(engine) -> str:
    """The engine's identity string (model parameters, row width,
    symmetry), for the run's log."""
    return engine._ckpt_ident()


class WaveClock:
    """The program's do-nothing telemetry facade, but for the span the
    wave loop opens around each wave: at its end the benchmark reads its
    own clock. ``active`` stays false, so the run is a bare one."""

    def __init__(self, clock):
        from raft_tpu.obs import NULL_TELEMETRY

        self._null = NULL_TELEMETRY
        self._clock = clock
        self.stamps: list = []

    def __getattr__(self, name):
        return getattr(self._null, name)

    @contextlib.contextmanager
    def wave_annotation(self, depth: int):
        try:
            yield
        finally:
            self.stamps.append(self._clock())


def verdict(engine, max_depth: int, clock) -> dict:
    """One exhaustive BFS from Init to ``max_depth``; both engines'
    results in one shape, with ``stamps``: the ``clock``'s reading at
    the call, at the end of each wave and at the return."""
    waves = WaveClock(clock)
    t = clock()
    res = engine.run(max_depth=max_depth, collect_metrics=True,
                     telemetry=waves)
    stamps = [t, *waves.stamps, clock()]
    violation = (
        res.violation_invariant if hasattr(res, "violation_invariant")
        else res.violation and res.violation.invariant
    )
    return {
        "depth_counts": [int(x) for x in res.depth_counts],
        "distinct": int(res.distinct),
        "total": int(res.total),
        "terminal": int(res.terminal),
        "violation": violation,
        "exit_cause": res.exit_cause,
        "waves": res.metrics or [],
        "stats": getattr(res, "stats", None) or {},
        "stamps": stamps,
    }
