"""jax.profiler hooks: make an xprof trace line up with the checker.

With ``--trace-dir=DIR`` every wave is bracketed by a
``StepTraceAnnotation("wave", step_num=depth)`` (xprof's step view then
shows one step per BFS wave) and the named host-side phases —
``precompile``, ``seen_merge``, ``checkpoint``, ``consolidate`` — carry
``TraceAnnotation`` spans whose names match the offline stage profiler's
vocabulary (checker/profile.py), so a live trace and a ``--profile`` row
talk about the same things.

Without a trace dir every hook degrades to a shared nullcontext — zero
per-wave overhead on the hot path.
"""

from __future__ import annotations

from contextlib import nullcontext

_NULL = nullcontext()


class TraceHooks:
    """Owns jax.profiler trace lifetime + annotation factories."""

    def __init__(self, trace_dir: str | None = None):
        self.trace_dir = trace_dir
        self._started = False

    @property
    def enabled(self) -> bool:
        return self.trace_dir is not None

    def ensure_started(self) -> None:
        if self.trace_dir is None or self._started:
            return
        import jax

        jax.profiler.start_trace(self.trace_dir)
        self._started = True

    def stop(self) -> None:
        if not self._started:
            return
        import jax

        jax.profiler.stop_trace()
        self._started = False

    def wave(self, depth: int):
        """Context manager bracketing one BFS wave (xprof step = depth)."""
        if self.trace_dir is None:
            return _NULL
        import jax

        self.ensure_started()
        return jax.profiler.StepTraceAnnotation("wave", step_num=depth)

    def section(self, name: str):
        """Named span for a host-side phase (precompile/merge/...)."""
        if self.trace_dir is None:
            return _NULL
        import jax

        self.ensure_started()
        return jax.profiler.TraceAnnotation(name)
