"""Seeded-mutation self-test kit: prove each lint pass actually fires.

A linter that never fails is indistinguishable from one that audits
nothing, so each pass ships with one seeded contract violation —
applied as a reversible in-process patch (class attributes, pass
hooks, or source-text overrides), never touching the working tree —
and the CLI's ``--mutate NAME`` re-runs the targeted pass under it.
The acceptance contract: every mutation exits 3 with a finding naming
the pass and a ``file:line``.

  undonated-carry   drop the cov carry from DeviceBFS.WAVE_DONATE
  open-signature    skew _seen_size_for off the seen ladder
  wide-guard-write  leak a W-wide block into a kept guard output
  injected-sync     insert a jax.device_get inside the wave loop
  raw-const-read    read a FLEET_DYN constant around the _cv lane
"""

from __future__ import annotations

import contextlib
import os

PASS_OF = {
    "undonated-carry": "donation",
    "open-signature": "signatures",
    "wide-guard-write": "guard-purity",
    "injected-sync": "hidden-sync",
    "raw-const-read": "lane-discipline",
}


@contextlib.contextmanager
def undonated_carry():
    """Un-donate the coverage carry of the fused wave program: the
    classic regression (a donate tuple losing an argnum), caught by the
    donation auditor's independent carries map."""
    from ..checker.device_bfs import DeviceBFS

    orig = DeviceBFS.WAVE_DONATE
    DeviceBFS.WAVE_DONATE = tuple(a for a in orig if a != 6)
    try:
        yield {"families": ("raft",), "scopes": ("device",)}
    finally:
        DeviceBFS.WAVE_DONATE = orig


@contextlib.contextmanager
def open_signature():
    """Skew the runtime merge-target chooser off the seen ladder —
    the round-5 retrace cliff, reintroduced."""
    from ..checker.device_bfs import DeviceBFS

    orig = DeviceBFS._seen_size_for

    def skewed(self, n):
        return orig(self, n) + 3

    DeviceBFS._seen_size_for = skewed
    try:
        yield {"families": ("raft",)}
    finally:
        DeviceBFS._seen_size_for = orig


@contextlib.contextmanager
def wide_guard_write():
    """Let a W-wide block survive guard DCE: a fresh (never-cached)
    model whose ``_expand1`` threads a [2, W] intermediate into a kept
    guard output, so the derived guard jaxpr materializes it."""
    from . import guard_purity, registry

    def poisoned(fam):
        import jax.numpy as jnp

        m = registry.fresh_tiny_model(fam)
        orig_expand = type(m)._expand1

        def bad_expand(s):
            succs, valid, rank, ovf = orig_expand(m, s)
            wide = jnp.broadcast_to(s[None, :], (2, s.shape[0]))
            leak = wide.sum().astype(rank.dtype)
            return succs, valid, rank + leak * 0, ovf

        m.__dict__["_expand1"] = bad_expand
        return m

    orig = guard_purity.MODEL_FN
    guard_purity.MODEL_FN = poisoned
    try:
        yield {"families": ("raft",)}
    finally:
        guard_purity.MODEL_FN = orig


@contextlib.contextmanager
def injected_sync():
    """Insert a per-wave-loop jax.device_get into a COPY of the
    DeviceBFS source (the tree is untouched) and point the sync pass's
    source override at it."""
    from . import sync
    from .findings import REPO_ROOT

    relpath = os.path.join("raft_tpu", "checker", "device_bfs.py")
    with open(os.path.join(REPO_ROOT, relpath)) as fh:
        src = fh.read()
    anchor = "\n            depth += 1\n"
    assert anchor in src, "mutation anchor vanished from DeviceBFS.run"
    mutated = src.replace(
        anchor,
        "\n            depth += 1\n"
        "            _ = jax.device_get(viol)\n",
        1,
    )
    assert mutated != src
    orig = sync.SOURCE_OVERRIDES
    sync.SOURCE_OVERRIDES = {relpath: mutated}
    try:
        yield {}
    finally:
        sync.SOURCE_OVERRIDES = orig


@contextlib.contextmanager
def raw_const_read():
    """Bypass the ``_cv`` lane for one FLEET_DYN constant in a COPY of
    the raft lowering and point the lane pass's override at it."""
    from . import lanes
    from .findings import REPO_ROOT

    relpath = os.path.join("raft_tpu", "models", "raft.py")
    with open(os.path.join(REPO_ROOT, relpath)) as fh:
        src = fh.read()
    good = 'self._cv(d, "max_restarts")'
    assert good in src, "mutation anchor vanished from models/raft.py"
    mutated = src.replace(good, "self.p.max_restarts", 1)
    orig = lanes.SOURCE_OVERRIDES
    lanes.SOURCE_OVERRIDES = {relpath: mutated}
    try:
        yield {}
    finally:
        lanes.SOURCE_OVERRIDES = orig


MUTATIONS = {
    "undonated-carry": undonated_carry,
    "open-signature": open_signature,
    "wide-guard-write": wide_guard_write,
    "injected-sync": injected_sync,
    "raw-const-read": raw_const_read,
}
