"""Compile counters, counted where the work happens: one pair of
``jax.monitoring`` listeners per process.

JAX 0.9 reports a program's way into the process with these events
(jax/_src/interpreters/pxla.py, compiler.py):

  /jax/core/compile/backend_compile_duration   a duration, once for
      every program handed to ``compile_or_get_cached``: the compiler
      ran, or its persistent cache answered. One per program LOADED.
  /jax/compilation_cache/cache_hits            an event, when the
      persistent cache answered.
  /jax/compilation_cache/cache_retrieval_time_sec   a duration: the
      seconds of that read (they are inside the duration above).

So: programs loaded = the first count; compiled by the backend = loaded
less cache hits; seconds in the compiler = the first duration less the
cache-read seconds. The listeners run only when a program is loaded,
never on a dispatch.

``install()`` is called from ``raft_tpu.enable_compcache``, the
chokepoint every checker path goes through once its backend is known.
The engines read ``COMPILES.snapshot()`` at the start of a run and
round each wave and report the change: per wave (``compiles``,
``compile_s`` in the row), per run (the summary event) and on the
result (``stats``: ``programs_loaded``, ``run_compiles``,
``run_compile_s``, ``run_cache_hits``, ``run_cache_read_s``).
"""

from __future__ import annotations

import jax

LOADED = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"


class CompileCounters:
    """Cumulative in the process; callers take differences."""

    def __init__(self):
        self.loaded = 0        # programs compiled or read from the cache
        self.cache_hits = 0    # of those, read from the persistent cache
        self.load_s = 0.0      # seconds compiling or reading
        self.cache_read_s = 0.0
        self._installed = False

    def install(self) -> None:
        if self._installed:
            return
        self._installed = True
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == LOADED:
            self.loaded += 1
            self.load_s += secs
        elif event == CACHE_READ:
            self.cache_read_s += secs

    def _event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT:
            self.cache_hits += 1

    def snapshot(self) -> tuple:
        """(loaded, load_s, cache_hits, cache_read_s) as of now."""
        return self.loaded, self.load_s, self.cache_hits, self.cache_read_s

    def run_stats(self, start: tuple) -> dict:
        """What a run that began at ``start`` loaded: the keys of a
        result's ``stats`` and of the summary event. ``run_compiles``
        counts every program loaded, ``run_cache_hits`` those of them
        the persistent cache answered; ``run_compile_s`` is the seconds
        of both, ``run_cache_read_s`` the part spent reading."""
        loaded, load_s, hits, read_s = self.snapshot()
        return {
            "programs_loaded": loaded,
            "run_compiles": loaded - start[0],
            "run_compile_s": load_s - start[1],
            "run_cache_hits": hits - start[2],
            "run_cache_read_s": read_s - start[3],
        }


COMPILES = CompileCounters()
