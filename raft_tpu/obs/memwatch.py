"""Analytic HBM watermark accounting (the out-of-core planning input).

The engines already know every buffer's geometry — frontier capacity
and fill, the VC-wide chunk block, the seen run and the wave's
fingerprint buffer / LSM runs, the journal cursor. ``MemWatch`` turns
that geometry into live-bytes per wave WITHOUT reading the device (no syncs, no
allocator introspection — this is the planning model, not a profiler):
each wave the engine hands it a ``{buffer family: live bytes}``
breakdown, it tracks the running peak, and it emits a ``memwatch``
event whenever a wave sets a new watermark (so the stream stays
low-volume and peak_bytes is monotone within a run by construction).

``frac`` = total live bytes / budget is the gauge the progress line
renders (``hbm NN%``) and the wave event carries (``hbm_frac``). On an
accelerator the budget is what the device itself reports
(``memory_stats()["bytes_limit"]``; a device that reports none is an
error, not a guess). On the CPU backend — a dry run whose point is to
predict where the same geometry will sit on a chip — it is the
``RAFT_TPU_HBM_BUDGET`` environment variable (bytes) or 16 GiB, one
TPU v5e chip's HBM. A frac above 1.0 is legal and is exactly the signal
out-of-core planning starts from.

Dependency-free (no jax/numpy): byte math is host ints, and the device
is whatever object the engine hands in.
"""

from __future__ import annotations

import os

# CPU dry runs only: one TPU v5e chip's HBM; RAFT_TPU_HBM_BUDGET (bytes)
# overrides
DEFAULT_BUDGET_BYTES = 16 << 30


def budget_from_env(default: int = DEFAULT_BUDGET_BYTES) -> int:
    raw = os.environ.get("RAFT_TPU_HBM_BUDGET", "")
    try:
        v = int(raw)
    except ValueError:
        return default
    return v if v > 0 else default


def device_budget(device) -> int:
    """The HBM budget of the ``jax.Device`` a run's buffers live on."""
    if device.platform == "cpu":
        return budget_from_env()
    limit = (device.memory_stats() or {}).get("bytes_limit")
    if not limit:
        raise RuntimeError(
            f"{device.platform} device {device.device_kind!r} reports no "
            "memory_stats()['bytes_limit']; cannot size the HBM budget"
        )
    return int(limit)


class MemWatch:
    """Per-run watermark tracker; one instance per engine run().

    ``update(wave, depth, breakdown)`` returns the fraction-of-budget
    gauge for the wave event and emits a ``memwatch`` event through
    ``tel`` iff the wave set a new peak. ``tel`` may be None (or an
    inactive telemetry facade): the gauge still computes, nothing is
    emitted.
    """

    def __init__(self, tel=None, budget_bytes: int | None = None):
        self.tel = tel
        # engines pass device_budget(<their device>); None is the CPU
        # dry-run budget
        self.budget_bytes = int(budget_bytes or budget_from_env())
        self.peak_bytes = 0
        self.peak_wave = 0
        self.peak_breakdown: dict[str, int] = {}

    def update(self, wave: int, depth: int, breakdown: dict) -> float:
        clean = {k: int(v) for k, v in breakdown.items() if v}
        total = sum(clean.values())
        frac = total / self.budget_bytes
        if total > self.peak_bytes:
            self.peak_bytes = total
            self.peak_wave = int(wave)
            self.peak_breakdown = clean
            if self.tel is not None and getattr(self.tel, "active", False):
                self.tel.event(
                    "memwatch",
                    wave=int(wave),
                    depth=int(depth),
                    total_bytes=total,
                    peak_bytes=self.peak_bytes,
                    budget_bytes=self.budget_bytes,
                    frac=frac,
                    breakdown=clean,
                )
        return frac

    def summary_fields(self) -> dict:
        """Extras for the run's summary event."""
        return {
            "hbm_peak_bytes": self.peak_bytes,
            "hbm_peak_wave": self.peak_wave,
            "hbm_budget_bytes": self.budget_bytes,
            "hbm_peak_frac": self.peak_bytes / self.budget_bytes,
        }
