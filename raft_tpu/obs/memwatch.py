"""Device memory of a run: what the allocator says, then what the
geometry plans.

``MemWatch`` is made on every ``run()`` of the three engines, telemetry
or not, with the run's devices (one for ``DeviceBFS``, the mesh's for
``ShardedBFS``, none for ``BFSChecker``, whose arrays are the host's: no
allocator's reading is this engine's, so its measured keys are None on
a chip as on the CPU and its plan, of host RAM, stands against
``RAFT_TPU_HBM_BUDGET`` or 16 GiB), and reports two things under names
that never mix:

**Measured** (the plain names). ``device.memory_stats()`` asks the
allocator, not the stream: no read waits for the device. It is read
where the run's memory changes, all of them boundaries the wave loop
already has: when the watch is made (``run()``'s entry, before any
buffer), in ``init`` once the frontier pair, the journal and the
counters are on the device, at the end of every wave (after the seen
merge and any growth, before the row is built) and at ``finish``; each
reading the max over the run's devices.

  hbm_bytes         row: ``bytes_in_use`` at the wave's end, what the
                    run holds between programs
  hbm_peak_rise     row: by how much the allocator's
                    ``peak_bytes_in_use`` rose since the read before
                    (0 on most waves)
  hbm_init_bytes    ``bytes_in_use`` at the read in ``init``
  hbm_init_rise     the peak's rise from entry to that read
  hbm_live_bytes    the largest ``hbm_bytes`` of the run
  hbm_peak_bytes    ``peak_bytes_in_use`` at ``finish`` (a rise after
                    the last wave's read, such as the last wave of a run
                    that ends ``exhausted``, whose loop leaves before
                    the read, is in here and in no row)
  hbm_peak_frac, hbm_live_frac   peak and live over the budget
  hbm_plan_gap_frac (peak - plan) / peak: what the geometry does not
                    explain (a buffer the plan does not know, the
                    programs' temporaries); negative where the plan
                    over-counts

The allocator's peak only rises, and it is the PROCESS's: the rises
(``hbm_init_rise``, then each row's ``hbm_peak_rise``) say which
interval set the peak in a process's first run; a later run
raises it only by what the process has come to hold since (on the chip
the second run's ``init`` does, by the first run's journal, which the
engine keeps until the next ``finish``, and by what the programs loaded
since keep on the device: 0.12-0.48 GB in the benchmark's cells, PERF.md
section 6, PR 53), and ``hbm_peak_bytes`` is the high-water mark of
everything the process ran. ``hbm_init_bytes`` is read without waiting
for the device, so whether it still counts the zeros the seed rows were
copied out of (a third frontier-size buffer) depends on who is faster,
the host or the copy: a warm ``init`` of a multi-GB frontier reads
three, a first run, whose eager programs load in between, two. A device
that reports nothing (the CPU backend's ``memory_stats()`` is None)
gives None for every measured key; nothing is guessed. One call costs
1.3-1.9 us on a v5e's host, a program in flight or not (PR 53).

**Planned** (``hbm_plan_*``). The engines know every buffer's geometry:
frontier capacity, the VC-wide chunk block, the seen run and the wave's
fingerprint buffer or the LSM's runs, the journal. Each wave the engine
hands in a ``{buffer family: bytes}`` breakdown; the watch keeps the
largest total (``hbm_plan_bytes``, ``hbm_plan_frac``; the breakdown is
on the ``memwatch`` event). The plan reads no device, so it stands on
the CPU too: a dry run there predicts where the same geometry will sit
on a chip. A fraction above 1.0 is legal and is what out-of-core
planning starts from. It does not know the third frontier-size buffer a
``DeviceBFS.run()`` starts with (ROADMAP D5 removes it) nor a program's
temporaries: ``hbm_plan_gap_frac`` is there to show both.

``hbm_frac``, the gauge of a wave's row and of the progress line, is
``hbm_bytes`` over the budget where the device reports and the plan's
fraction otherwise (``hbm NN%`` / ``plan NN%``). A ``memwatch`` event
goes out when a wave sets a new plan peak or the allocator's peak rose
in it, so the stream stays low-volume.

The budget (``hbm_budget_bytes``): on an accelerator what the device
itself reports (``memory_stats()["bytes_limit"]``; a device that reports
none is an error, not a guess); on the CPU backend, and for a run that
hands in no device, the ``RAFT_TPU_HBM_BUDGET`` environment variable
(bytes) or 16 GiB, one TPU v5e chip's HBM.

Dependency-free (no jax/numpy): byte math is host ints, and a device is
whatever object the engine hands in.
"""

from __future__ import annotations

import os

# CPU dry runs only: one TPU v5e chip's HBM; RAFT_TPU_HBM_BUDGET (bytes)
# overrides
DEFAULT_BUDGET_BYTES = 16 << 30

# a row's keys, and the row of a run nobody watches (the packed fleet)
ROW_KEYS = ("hbm_bytes", "hbm_peak_rise", "hbm_frac")
NO_READING = dict.fromkeys(ROW_KEYS)


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


def _over(num, den):
    return None if num is None else num / den


class MemWatch:
    """One run's reader of its devices' memory; made at ``run()``'s
    entry, which is its first read. ``tel`` is the run's telemetry
    facade (an inactive one emits nothing; the readings are the same)."""

    def __init__(self, tel, devices):
        self.tel = tel
        self.devices = tuple(devices)
        self.budget_bytes = (
            device_budget(self.devices[0]) if self.devices
            else budget_from_env())
        self.init_bytes = self.init_rise = self.live_bytes = None
        self.plan_bytes = 0
        _, self._peak_seen = self._read()

    def _read(self) -> tuple:
        """(bytes_in_use, peak_bytes_in_use) of the allocator, each the
        max over the run's devices; (None, None) where none reports."""
        stats = [s for s in (d.memory_stats() for d in self.devices) if s]
        if not stats:
            return None, None
        return (max(int(s["bytes_in_use"]) for s in stats),
                max(int(s["peak_bytes_in_use"]) for s in stats))

    def _rise(self, peak):
        """The peak's rise since the read before, booked to the interval
        that ends here."""
        if peak is None:
            return None
        rise = peak - self._peak_seen
        self._peak_seen = peak
        return rise

    def init(self) -> None:
        """In ``init``, once the run's buffers are on the device."""
        self.init_bytes, peak = self._read()
        self.init_rise = self._rise(peak)

    def wave(self, depth: int, breakdown: dict) -> dict:
        """The end of wave ``depth``, whose geometry is ``breakdown``:
        the row's ``hbm_*`` keys."""
        in_use, peak = self._read()
        rise = self._rise(peak)
        if in_use is not None:
            self.live_bytes = max(self.live_bytes or 0, in_use)
        plan = {k: int(v) for k, v in breakdown.items() if v}
        total = sum(plan.values())
        plan_rose = total > self.plan_bytes
        self.plan_bytes = max(self.plan_bytes, total)
        row = {
            "hbm_bytes": in_use,
            "hbm_peak_rise": rise,
            "hbm_frac": round(
                (total if in_use is None else in_use) / self.budget_bytes, 6),
        }
        if (plan_rose or rise) and self.tel.active:
            self.tel.event(
                "memwatch", wave=depth, depth=depth,
                bytes=in_use, peak_bytes=peak, peak_rise=rise,
                budget_bytes=self.budget_bytes, frac=row["hbm_frac"],
                plan_bytes=total, plan_peak_bytes=self.plan_bytes,
                plan_frac=total / self.budget_bytes, breakdown=plan,
            )
        return row

    def finish(self) -> dict:
        """At ``finish``, the last read: the allocator's peak of the
        process so far, and with it the run's ``hbm_*`` keys of
        ``stats`` and the summary."""
        _, peak = self._read()
        plan = self.plan_bytes
        return {
            "hbm_budget_bytes": self.budget_bytes,
            "hbm_peak_bytes": peak,
            "hbm_live_bytes": self.live_bytes,
            "hbm_init_bytes": self.init_bytes,
            "hbm_init_rise": self.init_rise,
            "hbm_plan_bytes": plan,
            "hbm_plan_frac": plan / self.budget_bytes,
            "hbm_peak_frac": _over(peak, self.budget_bytes),
            "hbm_live_frac": _over(self.live_bytes, self.budget_bytes),
            "hbm_plan_gap_frac": (peak - plan) / peak if peak else None,
        }
