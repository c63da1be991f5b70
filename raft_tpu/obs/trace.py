"""The tracing spine: named scopes on the device, spans on the host,
both on the ``jax.profiler`` clock.

Everything here is always compiled in and free when no profiler session
is open: a ``jax.named_scope`` only names the ops of a traced program
(metadata, no instruction), and a ``TraceAnnotation`` is a flag test.
So a bare run, a ``Telemetry`` run and a benchmark verdict all write the
same spans into whatever session is open — ``--trace-dir``
(``TraceSession``), the benchmark's ``--trace 1``, or a caller's own
``jax.profiler.trace``. PERF.md has the catalogue of names.

  stage(name)    device side: the scope of one stage of the chunk
                 pipeline, a member of ``events.TIMELINE_STAGES``
                 (expand, canon, dedup, emit, seen_merge, exchange).
                 Decorates the engines' stage methods, so the fused wave
                 program and the sharded chunk program carry it: an op
                 of the trace then reads
                 ``jit(_wave_step)/.../canon/...`` where it read
                 ``fusion.1459``.
  span(name)     host side: one phase of the wave loop.
  Phases         the spans of one run: ``init``, a ``wave`` per loop
                 iteration, ``finish``, and inside a wave one bracket
                 with two readings — ``with phases("fetch"):`` opens the
                 span AND adds its ``perf_counter`` seconds to the
                 wave's row, so the row and the trace cannot disagree.
  traced_run     the ``run`` span round an engine's ``run()``, with a
                 per-process run number that the ``wave`` spans repeat.
"""

from __future__ import annotations

import functools
import itertools
import time
from contextlib import contextmanager

import jax

from .events import TIMELINE_STAGES

_RUN_IDS = itertools.count(1)


def stage(name: str):
    """``jax.named_scope`` of one pipeline stage; use as a decorator or
    a ``with``. Finer scopes nest under it with plain
    ``jax.named_scope`` (``emit/invariants``); the vocabulary of the top
    level is ``TIMELINE_STAGES`` and nothing else."""
    assert name in TIMELINE_STAGES, name
    return jax.named_scope(name)


def span(name: str, **attrs):
    """A host span on the profiler's clock; ``attrs`` become its stats
    (``run=``, ``depth=``, ...)."""
    return jax.profiler.TraceAnnotation(name, **attrs)


class Phases:
    """The host spans of one ``run()``, and the seconds of its phases.

    The run is a sequence of top-level spans that tile it — ``init``,
    one ``wave`` per loop iteration, ``finish`` — and opening the next
    one (``top``/``wave``) closes the one before, so the wave loop's
    body is bracketed without being indented under a ``with``. Inside
    them ``with phases("fetch"):`` is one bracket with two readings: it
    opens the span AND adds its ``perf_counter`` seconds to ``s``, from
    which the wave's row is built, so the row and the trace cannot
    disagree. Seconds of a phase entered more than once in a wave add up
    (a sharded wave dispatches once a chunk)."""

    def __init__(self):
        self.s: dict[str, float] = {}
        self._top = None

    def close(self) -> None:
        """Close the open top-level span, if any (``traced_run`` does,
        however ``run()`` ends)."""
        if self._top is not None:
            self._top.__exit__(None, None, None)
            self._top = None

    def top(self, name: str) -> None:
        """Close the open top-level span and open ``name``. A TraceMe
        starts its clock when it is made, so the one before is closed
        first."""
        self.close()
        self._top = span(name)
        self._top.__enter__()

    def wave(self, run: int, depth: int, frontier: int) -> None:
        """The next iteration of the wave loop. A step annotation, so
        xprof's step view shows one step per BFS wave."""
        self.close()
        self._top = jax.profiler.StepTraceAnnotation(
            "wave", step_num=depth, run=run, depth=depth, frontier=frontier)
        self._top.__enter__()

    @contextmanager
    def __call__(self, name: str, **attrs):
        t = time.perf_counter()
        try:
            with span(name, **attrs):
                yield
        finally:
            self.s[name] = self.s.get(name, 0.0) + time.perf_counter() - t

    def take(self) -> dict:
        """The seconds bracketed since the last call. Taken once a wave,
        when the row is built: the ``telemetry`` bracket that follows
        lands in the NEXT wave's reading, which is what ``tel_s`` is."""
        s, self.s = self.s, {}
        return s


def traced_run(engine: str):
    """Decorator of an engine's ``run()``: numbers the run
    (``self._run_id``, counted per process), gives it fresh ``Phases``
    (``self._ph``) and brackets it in the ``run`` span."""

    def deco(fn):
        @functools.wraps(fn)
        def run(self, *args, **kw):
            self._run_id = next(_RUN_IDS)
            self._ph = Phases()
            with span("run", run=self._run_id, engine=engine):
                try:
                    return fn(self, *args, **kw)
                finally:
                    self._ph.close()

        return run

    return deco


class TraceSession:
    """The profiler session of a ``--trace-dir`` run: open from the
    moment its ``Telemetry`` is made (so ``precompile`` and the first
    run's ``init`` are in it) until that closes. With no directory it is
    nothing, and the spans go to whichever session someone else opened."""

    def __init__(self, trace_dir: str | None = None):
        self.trace_dir = trace_dir
        self._open = trace_dir is not None
        if self._open:
            jax.profiler.start_trace(trace_dir)

    def stop(self) -> None:
        if self._open:
            jax.profiler.stop_trace()
            self._open = False
