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
  setup_phase    the same one bracket with two readings for the set-up
                 before a run: the span ``setup/<phase>`` and its
                 seconds on the process's record (``raft_tpu.SETUP_S``).
  HERE           where the host is now: the run, its open top-level
                 span and the innermost bracket, as plain attributes
                 that the brackets above set and obs/compiles.py reads
                 when JAX reports a program traced, lowered or loaded —
                 the record's ``cause``.
"""

from __future__ import annotations

import functools
import itertools
import time
from contextlib import contextmanager

import jax

from .. import SETUP_S
from .events import TIMELINE_STAGES

_RUN_IDS = itertools.count(1)


class _Here:
    """Where the host is now. One per process, like the profiler's
    clock: ``run`` is the open run's number (None outside a run),
    ``top`` its open top-level span (``init``, ``wave``, ``finish``) or
    the open set-up phase (``setup/engine``), ``depth`` the wave's, and
    ``bracket`` the innermost phase bracket (``dispatch``, ``fetch``,
    ``seen_merge``, ``grow``, ...). Written a few times a wave, read
    only when a program is traced, lowered or loaded."""

    __slots__ = ("run", "top", "depth", "bracket")

    def __init__(self):
        self.run = self.top = self.depth = self.bracket = None

    def cause(self) -> dict:
        return {"run": self.run, "top": self.top, "depth": self.depth,
                "bracket": self.bracket}


HERE = _Here()


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
        self._top_s = {"init": 0.0, "wave": 0.0, "finish": 0.0}
        self._name, self._t = None, 0.0

    def close(self) -> None:
        """Close the open top-level span, if any (``traced_run`` does,
        however ``run()`` ends)."""
        if self._top is not None:
            self._top.__exit__(None, None, None)
            self._top = None
            self._top_s[self._name] += time.perf_counter() - self._t

    def _open(self, name: str, annotation, depth=None) -> None:
        """Enter the next top-level span. A TraceMe starts its clock
        when it is made, so the caller makes ``annotation`` after it has
        closed the span before."""
        self._top = annotation
        self._top.__enter__()
        self._name = HERE.top = name
        HERE.depth = depth
        self._t = time.perf_counter()

    def top(self, name: str) -> None:
        """Close the open top-level span and open ``name`` (``init``,
        ``finish``)."""
        self.close()
        self._open(name, span(name))

    def wave(self, run: int, depth: int, frontier: int) -> None:
        """The next iteration of the wave loop. A step annotation, so
        xprof's step view shows one step per BFS wave."""
        self.close()
        self._open("wave", jax.profiler.StepTraceAnnotation(
            "wave", step_num=depth, run=run, depth=depth, frontier=frontier),
            depth)

    def top_seconds(self) -> dict:
        """``init_s``, ``waves_s``, ``finish_s``: the ``perf_counter``
        seconds of the top-level spans so far, the open one's up to now.
        They tile the run, so read beside the run's own wall they add up
        to it; the rows' ``wave_s`` leave out each wave's ``telemetry``
        bracket and the loop's head, which ``waves_s`` holds."""
        s = dict(self._top_s)
        if self._top is not None:
            s[self._name] += time.perf_counter() - self._t
        return {"init_s": s["init"], "waves_s": s["wave"],
                "finish_s": s["finish"]}

    @contextmanager
    def __call__(self, name: str, **attrs):
        outer, HERE.bracket = HERE.bracket, name
        t = time.perf_counter()
        try:
            with span(name, **attrs):
                yield
        finally:
            self.s[name] = self.s.get(name, 0.0) + time.perf_counter() - t
            HERE.bracket = outer

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
            self._run_id = HERE.run = next(_RUN_IDS)
            self._ph = Phases()
            with span("run", run=self._run_id, engine=engine):
                try:
                    return fn(self, *args, **kw)
                finally:
                    self._ph.close()
                    HERE.run = HERE.top = HERE.depth = None

        return run

    return deco


# seconds of the named set-up phases closed inside the open one
_named_inside = 0.0


@contextmanager
def setup_phase(name: str):
    """One bracket with two readings, as ``Phases`` has for a wave, for
    the set-up before a run: the host span ``setup/<name>`` on the
    profiler's clock when a session is open, and its ``perf_counter``
    seconds added to the process's record (``raft_tpu.SETUP_S``)
    always. Opened where the work happens (``parse_cfg``,
    ``build_from_cfg``, the engines' constructors, ``start_backend``),
    so the CLI, the benchmark's adapter and a script pass through it
    without knowing; a decorator too. Only the phases ``SETUP_S`` names
    are kept, each with its SELF seconds, so they never count a second
    twice: a backend that starts inside an engine's constructor is
    ``backend``'s and not ``engine``'s, and ``engine/canon``, a child
    span that ``SETUP_S`` does not name, stays in ``engine``'s."""
    global _named_inside
    outer, HERE.top = HERE.top, "setup/" + name
    inside, _named_inside = _named_inside, 0.0
    t = time.perf_counter()
    try:
        with span(HERE.top):
            yield
    finally:
        took = time.perf_counter() - t
        if name in SETUP_S:
            SETUP_S[name] += took - _named_inside
            _named_inside = inside + took
        else:
            _named_inside += inside
        HERE.top = outer


class TraceSession:
    """The profiler session of a ``--trace-dir`` run: open from the
    moment it is made until its ``Telemetry`` closes. The CLI makes it
    as soon as the backend is up and hands it to its ``Telemetry``, so
    the set-up phases and the first run's ``init`` are in it; a
    ``Telemetry`` given only a directory makes its own. With no
    directory it is nothing, and the spans go to whichever session
    someone else opened."""

    def __init__(self, trace_dir: str | None = None):
        self.trace_dir = trace_dir
        self._open = trace_dir is not None
        if self._open:
            jax.profiler.start_trace(trace_dir)

    def stop(self) -> None:
        if self._open:
            jax.profiler.stop_trace()
            self._open = False
