"""Run-time metrics collection for the BFS engines.

``MetricsCollector`` consumes the per-wave host-side snapshot of the
device stats vector — the engines already fetch it once per wave to
drive the loop (overflow check, frontier count), so collection adds ZERO
extra device syncs; tests/test_obs.py pins that. Output is one JSONL
event per wave (events.py schema) plus a manifest/summary pair per run.

The file write is double-buffered: the line for wave N hits disk when
wave N+1's snapshot arrives (or at close), so file I/O overlaps the
device's next wave and never sits between a dispatch and its sync. A
tailing reader therefore lags the run by at most one event.

The wall-clock watchdog keeps a rolling window of wave times and emits a
``stall`` event whenever a wave exceeds ``stall_factor`` x the window
median — the symptom of a mid-run recompile, a growth retrace, a
checkpoint spill on a slow disk, or a preempted device.

``Telemetry`` is the facade the engines thread through ``run()``: one
object bundling the collector, the optional TLC-style progress renderer
and the ``--trace-dir`` profiler session. ``NULL_TELEMETRY`` is the
do-nothing instance engines default to, so the hot loop never branches
on None. The host spans do not depend on either (obs/trace.py opens
them whatever the facade); ``wave_annotation`` is a hook that does
nothing here — it marks where a wave's dispatch and fetch are, and the
benchmark's adapter hangs its clock on it.
"""

from __future__ import annotations

import json
import statistics
import time
from contextlib import nullcontext

from .compiles import COMPILES
from .events import EVENT_KEYS
from .progress import ProgressRenderer
from .trace import TraceSession

_NO_SPAN = nullcontext()


class MetricsCollector:
    """Per-wave event sink with cadence, watchdog and JSONL output."""

    def __init__(
        self,
        path: str | None = None,
        every: int = 1,
        stall_factor: float = 4.0,
        stall_window: int = 16,
        stall_min_waves: int = 5,
        keep: bool = True,
    ):
        assert every >= 1, "cadence is in waves; minimum 1"
        self.every = int(every)
        self.stall_factor = float(stall_factor)
        self.stall_min_waves = int(stall_min_waves)
        self.events: list[dict] = [] if keep else None
        self._fh = open(path, "w") if path else None
        self._pending: str | None = None  # double-buffered JSONL line
        self._listeners: list = []
        self._wave = 0
        self._wave_times: list[float] = []
        self._wave_window = int(stall_window)
        self._last_skipped: dict | None = None
        self._last_skipped_cov: dict | None = None
        self.stalls = 0
        self.last_summary: dict | None = None

    # ---------------- sinks ----------------

    def add_listener(self, fn) -> None:
        """fn(event) is called for EVERY event (cadence does not apply:
        a progress renderer throttles by wall clock, not wave count)."""
        self._listeners.append(fn)

    def _write(self, ev: dict) -> None:
        if self.events is not None:
            self.events.append(ev)
        if self._fh is not None:
            if self._pending is not None:
                self._fh.write(self._pending + "\n")
            self._pending = json.dumps(ev)

    def _notify(self, ev: dict) -> None:
        for fn in self._listeners:
            fn(ev)

    # ---------------- event entry points ----------------

    def manifest(self, fields: dict) -> None:
        """Open a run: reset per-run state, emit the manifest event."""
        self._wave = 0
        self._wave_times = []
        self._last_skipped = None
        self._last_skipped_cov = None
        self.stalls = 0
        ev = {"event": "manifest", **fields}
        self._write(ev)
        self._notify(ev)

    def wave(self, fields: dict) -> None:
        """One wave's host-side snapshot (all values already on host)."""
        self._wave += 1
        ev = {"event": "wave", "wave": self._wave, **fields}
        # watchdog BEFORE the current wave joins the window (a stalled
        # wave must not drag the median it is judged against)
        wave_s = float(fields.get("wave_s", 0.0))
        if len(self._wave_times) >= self.stall_min_waves:
            med = statistics.median(self._wave_times)
            if med > 0 and wave_s > self.stall_factor * med:
                self.stalls += 1
                stall = {
                    "event": "stall",
                    "wave": self._wave,
                    "depth": fields.get("depth"),
                    "wave_s": round(wave_s, 3),
                    "median_wave_s": round(med, 3),
                    "factor": round(wave_s / med, 1),
                    # programs the wave loaded: a growth or ladder-step
                    # compile names itself
                    "compiles": fields.get("compiles"),
                    "compile_s": fields.get("compile_s"),
                }
                self._write(stall)
                self._notify(stall)
        self._wave_times.append(wave_s)
        if len(self._wave_times) > self._wave_window:
            self._wave_times.pop(0)
        if (self._wave - 1) % self.every == 0:
            self._write(ev)
            self._last_skipped = None
        else:
            self._last_skipped = ev
        self._notify(ev)

    def coverage(self, fields: dict, final: bool = False) -> None:
        """Cumulative coverage snapshot for the wave just reported (call
        after ``wave()``; shares its cadence so the JSONL pairs up). The
        ``final`` snapshot — the engine's end-of-run cumulative totals —
        always writes
        and supersedes any cadence-skipped snapshot."""
        ev = {
            "event": "coverage", "wave": self._wave, **fields,
            "final": bool(final),
        }
        if final or (self._wave - 1) % self.every == 0 or self._wave == 0:
            self._write(ev)
            self._last_skipped_cov = None
        else:
            self._last_skipped_cov = ev
        self._notify(ev)

    def event(self, etype: str, **fields) -> None:
        """Low-volume out-of-band event (the resilience events: retry,
        resume, ckpt_generation, preempt). Always written — cadence is
        for per-wave volume; a recovery narrative must never be
        sampled away."""
        assert etype in EVENT_KEYS, f"unknown event type {etype!r}"
        ev = {"event": etype, **fields}
        self._write(ev)
        self._notify(ev)

    def summary(self, fields: dict) -> None:
        """Close a run: flush the newest skipped wave (the stream must
        end count-accurate at any cadence), emit the summary event."""
        if self._last_skipped is not None:
            self._write(self._last_skipped)
            self._last_skipped = None
        if self._last_skipped_cov is not None:
            self._write(self._last_skipped_cov)
            self._last_skipped_cov = None
        ev = {
            "event": "summary",
            **fields,
            "waves": self._wave,
            "stalls": self.stalls,
        }
        self.last_summary = ev
        self._write(ev)
        self._notify(ev)

    def close(self) -> None:
        if self._fh is not None:
            if self._pending is not None:
                self._fh.write(self._pending + "\n")
                self._pending = None
            self._fh.close()
            self._fh = None

    # ---------------- convenience ----------------

    def events_of(self, etype: str) -> list[dict]:
        assert etype in EVENT_KEYS, f"unknown event type {etype!r}"
        return [e for e in (self.events or ()) if e["event"] == etype]


class Telemetry:
    """Everything an engine run() threads through: collector + progress
    renderer + profiler session. Construct once, pass as ``telemetry=``;
    reusable across multiple runs (each emits manifest..summary);
    ``close()`` (or the context manager) flushes the JSONL file and
    stops the profiler trace."""

    active = True

    def __init__(
        self,
        metrics_path: str | None = None,
        every: int = 1,
        progress_every: float | None = None,
        progress_stream=None,
        trace_dir: str | None = None,
        stall_factor: float = 4.0,
        keep_events: bool = True,
        trace: TraceSession | None = None,
    ):
        self.collector = MetricsCollector(
            path=metrics_path, every=every, stall_factor=stall_factor,
            keep=keep_events,
        )
        self.progress = None
        if progress_every is not None:
            self.progress = ProgressRenderer(
                every_s=progress_every, stream=progress_stream
            )
            self.collector.add_listener(self.progress)
            # a cold start is minutes of compiling before the first
            # wave's line: say which program, as each slow load ends
            COMPILES.watchers.append(self.progress.loaded)
        # ``trace``: a session the caller opened earlier (the CLI, before
        # it reads the cfg, so the set-up phases are in it); closed here
        self.trace = trace if trace is not None else TraceSession(trace_dir)

    # -- engine-facing --

    def open_run(self, manifest: dict) -> None:
        self.collector.manifest(manifest)

    def wave(self, fields: dict) -> None:
        self.collector.wave(fields)

    def coverage(self, fields: dict, final: bool = False) -> None:
        self.collector.coverage(fields, final=final)

    def event(self, etype: str, **fields) -> None:
        self.collector.event(etype, **fields)

    def close_run(self, summary: dict) -> None:
        self.collector.summary(summary)

    def wave_annotation(self, depth: int):
        return _NO_SPAN

    # -- caller-facing --

    @property
    def events(self) -> list[dict]:
        return self.collector.events or []

    @property
    def last_summary(self) -> dict | None:
        return self.collector.last_summary

    def wave_events(self) -> list[dict]:
        return self.collector.events_of("wave")

    def coverage_events(self) -> list[dict]:
        return self.collector.events_of("coverage")

    def close(self) -> None:
        self.collector.close()
        self.trace.stop()
        if self.progress is not None \
                and self.progress.loaded in COMPILES.watchers:
            COMPILES.watchers.remove(self.progress.loaded)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class JobTaggedTelemetry:
    """Proxy that stamps a ``job`` field onto every event it forwards.

    The fleet queue arm (DeviceBFS/ShardedBFS.run_fleet) wraps the
    caller's Telemetry with one of these per job, so N sequential runs
    multiplex into ONE metrics stream that obs_report and
    check_metrics_schema can split back out per job. ``close()`` is a
    no-op — the owner of the inner Telemetry closes it once after the
    whole fleet."""

    def __init__(self, inner, job: str):
        self._inner = inner if inner is not None else NULL_TELEMETRY
        self.job = job

    @property
    def active(self) -> bool:
        return self._inner.active

    def open_run(self, manifest: dict) -> None:
        self._inner.open_run({**manifest, "job": self.job})

    def wave(self, fields: dict) -> None:
        self._inner.wave({**fields, "job": self.job})

    def coverage(self, fields: dict, final: bool = False) -> None:
        self._inner.coverage({**fields, "job": self.job}, final=final)

    def event(self, etype: str, **fields) -> None:
        self._inner.event(etype, job=self.job, **fields)

    def close_run(self, summary: dict) -> None:
        self._inner.close_run({**summary, "job": self.job})

    def wave_annotation(self, depth: int):
        return self._inner.wave_annotation(depth)

    @property
    def events(self):
        return self._inner.events

    @property
    def last_summary(self):
        return self._inner.last_summary

    def close(self) -> None:
        pass


class _NullTelemetry:
    """Shared inert instance: the engines' default, so the wave loop
    calls methods unconditionally instead of branching on None."""

    active = False
    events = ()
    last_summary = None

    def open_run(self, manifest: dict) -> None:
        pass

    def wave(self, fields: dict) -> None:
        pass

    def coverage(self, fields: dict, final: bool = False) -> None:
        pass

    def event(self, etype: str, **fields) -> None:
        pass

    def close_run(self, summary: dict) -> None:
        pass

    def wave_annotation(self, depth: int):
        return _NO_SPAN

    def close(self) -> None:
        pass


NULL_TELEMETRY = _NullTelemetry()
