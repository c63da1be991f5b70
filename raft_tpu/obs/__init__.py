"""raft_tpu.obs — run-time telemetry for the BFS engines.

Per-wave JSONL metrics (events.py), a TLC-style progress line
(progress.py), the tracing spine — device scopes, host spans, the
profiler session, the set-up phases (trace.py) — and a record of every
program the process traced, lowered and loaded (compiles.py), the
collector/facade threading them through the engines (collector.py),
and TLC-style per-action coverage rendering (coverage.py).

    from raft_tpu.obs import Telemetry
    tel = Telemetry(metrics_path="m.jsonl", progress_every=10.0)
    res = DeviceBFS(model, ...).run(telemetry=tel)
    tel.close()
"""

from .collector import (
    JobTaggedTelemetry,
    MetricsCollector,
    NULL_TELEMETRY,
    Telemetry,
)
from .coverage import dead_actions, render_coverage_table
from .events import (
    CKPT_GENERATION_KEYS,
    COVERAGE_KEYS,
    DECLARED_EVENTS,
    EVENT_KEYS,
    EXIT_CAUSES,
    MANIFEST_KEYS,
    MEMWATCH_KEYS,
    PREEMPT_KEYS,
    RESUME_KEYS,
    RETRY_KEYS,
    STALL_KEYS,
    SUMMARY_KEYS,
    TIMELINE_STAGES,
    WAVE_KEYS,
    hashv_of,
    validate_event,
    validate_lines,
)
from .memwatch import (
    NO_READING, MemWatch, budget_from_env, device_budget,
)
from .progress import ProgressRenderer, format_count
from .compiles import COMPILES
from .trace import (
    HERE, Phases, TraceSession, setup_phase, span, stage, traced_run,
)

__all__ = [
    "CKPT_GENERATION_KEYS",
    "COVERAGE_KEYS",
    "DECLARED_EVENTS",
    "EVENT_KEYS",
    "EXIT_CAUSES",
    "MANIFEST_KEYS",
    "MEMWATCH_KEYS",
    "PREEMPT_KEYS",
    "RESUME_KEYS",
    "RETRY_KEYS",
    "STALL_KEYS",
    "SUMMARY_KEYS",
    "TIMELINE_STAGES",
    "WAVE_KEYS",
    "COMPILES",
    "HERE",
    "JobTaggedTelemetry",
    "MemWatch",
    "MetricsCollector",
    "NO_READING",
    "NULL_TELEMETRY",
    "Phases",
    "ProgressRenderer",
    "Telemetry",
    "TraceSession",
    "budget_from_env",
    "dead_actions",
    "device_budget",
    "format_count",
    "hashv_of",
    "render_coverage_table",
    "setup_phase",
    "span",
    "stage",
    "traced_run",
    "validate_event",
    "validate_lines",
]
