"""How every program got into the process, recorded where JAX reports
it: one set of ``jax.monitoring`` listeners per process.

JAX 0.9 reports a program's way into the process with these events
(jax/_src/dispatch.py, pjit.py, interpreters/pxla.py, compiler.py). The
first three are brackets: a scalar at the start, a duration and a time
span (start, end on ``time.time()``) at the end, each with ``fun_name``.

  /jax/core/compile/jaxpr_trace_duration       Python traced to a jaxpr.
      Traces nest: tracing ``_wave_step`` traces every jitted function
      it calls, each its own event inside the outer one.
  /jax/core/compile/jaxpr_to_mlir_module_duration   the jaxpr lowered to
      an MLIR module.
  /jax/core/compile/backend_compile_duration    once for every program
      handed to ``compile_or_get_cached``: the cache key hashed, then
      the compiler ran or its persistent cache answered. One per program
      LOADED.
  /jax/compilation_cache/cache_hits             an event, when the
      persistent cache answered; fires inside the load it belongs to.
  /jax/compilation_cache/cache_retrieval_time_sec   a duration: the
      seconds of that read (they are inside the load's).

Each bracket becomes one record (``COMPILES.records``, kept in memory):

  kind      trace | lower | load
  fun_name  as JAX names it (``_wave_step``, ``_merge``, ``zeros``)
  start, end, seconds   the bracket's own (``time.time()``)
  nesting   brackets of any kind open round it: 0 = top level
  cause     the program's own open span when it ended (obs/trace.py
            ``HERE``): ``run`` number, ``top`` (``init``, ``wave``,
            ``finish``, ``setup/<phase>`` or None), the wave's
            ``depth``, the innermost ``bracket`` (``dispatch``,
            ``seen_merge``, ``grow``, ... or None)
  cache_hit, cache_read_s   load only

Totals are kept as records arrive, so reading them costs nothing that
grows with the process's age. Seconds of a kind are the UNION of its
records' intervals, never their sum (a nested trace is inside its
parent's). So: programs loaded = load records; compiled by the backend =
loaded less cache hits; ``load_compile_s`` = the loads' seconds less the
cache-read seconds (the compiler, and the hashing of the key); and
``load_union_s``, the union over all three kinds, is the wall seconds
the process spent getting programs ready. The listeners run only when a
program is traced, lowered or loaded, never on a dispatch.

``install()`` is called from ``raft_tpu.start_backend``, the chokepoint
every checker path goes through once (``enable_compcache``). The
engines read ``COMPILES.snapshot()`` at the start of a run and round
each wave and report the change: per wave (``compiles``, ``compile_s``
in the row), per run (``run_compiles``, ``run_compile_s``,
``run_cache_hits``, ``run_cache_read_s``; the run's top-level records as
``programs`` on the summary event), and cumulative in the process
(``programs_loaded``, ``programs_traced``, ``load_*``, and the set-up
phases ``setup_*_s`` of ``raft_tpu.SETUP_S``): ``run_stats``.
"""

from __future__ import annotations

import jax

from .. import SETUP_S
from .trace import HERE

TRACED = "/jax/core/compile/jaxpr_trace_duration"
LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"
LOADED = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"

KINDS = {TRACED: "trace", LOWERED: "lower", LOADED: "load"}
# a load this long is told to whoever watches (the progress line)
SLOW_LOAD_S = 1.0


class IntervalUnion:
    """Total length of the union of intervals added one at a time.
    Brackets end in order, so an interval overlaps at most the newest
    ones kept: adding is amortised constant."""

    def __init__(self):
        self.total = 0.0
        self._kept: list = []  # disjoint (start, end), ascending

    def add(self, start: float, end: float) -> None:
        kept = self._kept
        if kept and kept[-1][0] > end:
            # ends out of order (a stepped clock, a second thread):
            # rebuild from the whole list, which is still disjoint
            older = [iv for iv in kept if iv[0] > end]
            del kept[len(kept) - len(older):]
            self.add(start, end)
            kept.extend(older)
            return
        while kept and kept[-1][1] >= start:
            s, e = kept.pop()
            self.total -= e - s
            start, end = min(start, s), max(end, e)
        kept.append((start, end))
        self.total += end - start


class CompileRecords:
    """Cumulative in the process; callers take differences."""

    def __init__(self):
        self.records: list[dict] = []
        self.loaded = 0        # programs compiled or read from the cache
        self.cache_hits = 0    # of those, read from the persistent cache
        self.load_s = 0.0      # seconds compiling or reading
        self.cache_read_s = 0.0
        self.traced = 0        # top-level traces: the retrace counter
        self.unions = {kind: IntervalUnion() for kind in KINDS.values()}
        self.union = IntervalUnion()  # of all three kinds
        self.watchers: list = []  # called with each load over SLOW_LOAD_S
        self._open = 0         # brackets entered and not yet left
        self._hit, self._read_s = False, 0.0  # of the load now open
        self._installed = False

    def install(self) -> bool:
        """Switch the listeners on; True the first time."""
        if self._installed:
            return False
        self._installed = True
        jax.monitoring.register_scalar_listener(self._enter)
        jax.monitoring.register_event_time_span_listener(self._leave)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return True

    def _enter(self, event: str, _start, **_kw) -> None:
        if event in KINDS:
            self._open += 1

    def _leave(self, event: str, start: float, end: float,
               fun_name: str = "", **_kw) -> None:
        kind = KINDS.get(event)
        if kind is None:
            return
        self._open = max(0, self._open - 1)
        rec = {"kind": kind, "fun_name": fun_name, "start": start,
               "end": end, "seconds": end - start, "nesting": self._open,
               "cause": HERE.cause()}
        self.records.append(rec)
        self.unions[kind].add(start, end)
        self.union.add(start, end)
        if kind == "trace" and self._open == 0:
            self.traced += 1
        elif kind == "load":
            rec["cache_hit"], rec["cache_read_s"] = self._hit, self._read_s
            self._hit, self._read_s = False, 0.0
            self.loaded += 1
            self.load_s += rec["seconds"]
            if rec["seconds"] >= SLOW_LOAD_S:
                for watch in self.watchers:
                    watch(rec)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == CACHE_READ:
            self.cache_read_s += secs
            self._read_s = secs

    def _event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT:
            self.cache_hits += 1
            self._hit = True

    def snapshot(self) -> tuple:
        """(loaded, load_s, cache_hits, cache_read_s, records) as of
        now."""
        return (self.loaded, self.load_s, self.cache_hits,
                self.cache_read_s, len(self.records))

    def run_stats(self, start: tuple) -> dict:
        """What a run that began at ``start`` loaded, and what the
        process has spent on set-up so far: the keys of a result's
        ``stats`` and of the summary event. ``run_compiles`` counts
        every program loaded, ``run_cache_hits`` those of them the
        persistent cache answered; ``run_compile_s`` is the seconds of
        both, ``run_cache_read_s`` the part spent reading. The rest is
        cumulative in the process, as ``programs_loaded`` is."""
        loaded, load_s, hits, read_s, _ = self.snapshot()
        return {
            "programs_loaded": loaded,
            "programs_traced": self.traced,
            "run_compiles": loaded - start[0],
            "run_compile_s": load_s - start[1],
            "run_cache_hits": hits - start[2],
            "run_cache_read_s": read_s - start[3],
            **{f"setup_{phase}_s": s for phase, s in SETUP_S.items()},
            "load_trace_s": self.unions["trace"].total,
            "load_lower_s": self.unions["lower"].total,
            "load_compile_s": self.unions["load"].total - read_s,
            "load_cache_read_s": read_s,
            "load_union_s": self.union.total,
        }

    def programs(self, start: tuple) -> list:
        """The top-level records since ``start``, as the summary event
        carries them: what a run traced, lowered and loaded, each with
        the span that caused it. A nested trace is inside its parent's
        seconds and stays in ``records``."""
        return [
            {k: v for k, v in rec.items() if k not in ("start", "end")}
            for rec in self.records[start[4]:] if rec["nesting"] == 0
        ]


COMPILES = CompileRecords()
