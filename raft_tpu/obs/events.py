"""Event schema of the live telemetry stream.

A run emits one JSON object per line (JSONL), in order:

  manifest   once per run(), before the first wave: everything a record
             of the run needs to cite its provenance — engine,
             fingerprint-formula identity (the checkpoint ident string),
             capacities, device/mesh topology.
  wave       one per BFS wave (at the collector's cadence): depth,
             frontier lanes, per-wave and cumulative generated/distinct,
             canon's in-chunk duplicate rate, terminal count, overflow
             bits, LSM occupancy, wall seconds, rolling distinct/s.
  stall      emitted by the wall-clock watchdog when a wave exceeds
             stall_factor x the rolling median wave time.
  coverage   cumulative state-space cartography at the collector's
             cadence plus one final snapshot (``final: true``) right
             before the summary: per-action [enabled, fired,
             new-distinct] counters (index == the model's ACTION_NAMES
             rank), seen-set lane occupancy, fingerprint probe depth,
             frontier depth histogram.
  summary    once per run(), after the last wave: final counts, exit
             cause, peak buffer geometry, fleet in-chunk duplicate rate.

The self-healing runtime (raft_tpu/resilience/) adds four low-volume
events — ``retry`` / ``resume`` / ``ckpt_generation`` / ``preempt`` —
documented at their key tuples below; they interleave with the above
(retry between attempts, resume/ckpt_generation right after a resumed
run's manifest, preempt just before a "preempted" summary).

``memwatch`` carries the run's device memory as obs/memwatch.py reads
it: the allocator's reading first (null on a device that reports none,
the CPU), the geometry's plan second; both peaks monotone within a run,
and the event before its run's summary.

``DECLARED_EVENTS`` is pinned by the tier-1 smoke test,
so the schema cannot silently rot when an engine's stats
plumbing changes. Engines may add EXTRA keys (e.g. the sharded checker's
all-to-all volume and per-shard skew); every DECLARED key must be
present. This module is dependency-free (no jax/numpy) so schema
validation runs anywhere — see scripts/check_metrics_schema.py.

``job`` is the reserved extra key of fleet sweeps (raft_tpu/fleet/):
``raft_tpu sweep`` multiplexes every job of a manifest into ONE stream,
and each job-attributed event carries its job name there — the queue arm
stamps it on every forwarded event (obs/collector.py
JobTaggedTelemetry), the packed host arm synthesizes one per-job
manifest/coverage/summary triple after the shared group run. When
present it must be a non-empty string, each job's wave indices must be
strictly increasing within its run, and every job manifest must be
answered by exactly one summary with the same tag (validate_lines
enforces all three).
"""

from __future__ import annotations

import json
import re

MANIFEST_KEYS = (
    "event", "engine", "ident", "hashv", "model", "platform", "device",
    "device_count", "chunk", "frontier_cap", "journal_cap",
    "max_seen_cap", "valid_cap", "symmetry",
    "invariants", "action_names", "when",
)

# Stage names: the first six are the device scopes ``obs.stage`` accepts
# (obs/trace.py; "exchange" only on the sharded mesh), the last two host
# time. The benchmark's scope_time reader is held to this tuple.
TIMELINE_STAGES = (
    "expand",      # guard pass + budgeted sparse apply (or dense expand)
    "canon",       # canonical fingerprints (one canon a raw view of a chunk)
    "dedup",       # seen-set probes + intra-wave first-occurrence
    "emit",        # cursor-append emit + coverage + invariants + stats
    "exchange",    # sharded only: the all-to-all pair on the ICI
    "seen_merge",  # end-of-wave seen merge (sharded: + the LSM cascade)
    "checkpoint",  # wave-boundary checkpoint I/O
    "host",        # host bookkeeping not covered by a device stage
)

# emit_rows: rows the wave's contiguous cursor-append emit landed (the
# new ones).
# enabled_density/expand_budget_ovf (guard-first sparse expansion):
# enabled fraction of the dense [chunk, A] candidate grid this wave
# (the guard-first win scales with its inverse — tune valid_per_group
# from it), and apply-budget overflow (device engines: the abort bit,
# 0 on surviving waves; host engine: extra fixed-size apply blocks run
# beyond one per chunk — it loops instead of aborting). Both derive
# from counters the wave already fetched: zero extra device syncs.
# device_s/host_s/ckpt_s/tel_s: the
# host-side phase split of the wave's wall clock. device_s is the
# HOST'S WAIT on the device, never device time (a profile has that):
# the seconds its thread spent in the wave's dispatch, in the one
# blocking stats fetch and in the seen merge's dispatch. host_s is what
# those and checkpoint I/O (ckpt_s) leave of wave_s, so
# device_s + host_s + ckpt_s == wave_s; tel_s is the telemetry cost of
# the PREVIOUS wave (this wave's own is only known after the event is
# written; 0.0 on wave 1). Every clock of a row is the unrounded
# perf_counter difference of the bracket that also opens the phase's
# profiler span (obs/trace.py Phases): zero extra device syncs. The
# device engines add, as extra keys, the brackets themselves —
# dispatch_s, fetch_s, merge_s (their sum is device_s), grow_s (a wave
# that grew a buffer) — and compiles/compile_s: programs the iteration
# loaded, compiled or read from the persistent cache, and the seconds
# that took (obs/compiles.py).
# canon_dup_lanes: valid lanes that shared a lower lane's raw view in
# their chunk-step, skipped the permutations and left the canon stage
# masked, as an invalid lane does: the lower lane carries the fingerprint
# (ops/symmetry.py fingerprints_by_raw_view, under either canon), summed
# over the wave's chunk-steps; canon_dup_rate: the same over generated.
# canon_tier3_local / canon_tier3_full: lanes the wave's canon routed to
# tier 3's two buckets (ops/symmetry.py: the tie-group-local tables; the
# S!-table masked min, which on a layout without tiers, S <= 4, is every
# lane canonicalised). They count representatives of the in-chunk dedup,
# so together they never exceed generated - canon_dup_lanes. On
# KRaftWithReconfig (the model's own SlotCanonicalizer, no tiers)
# canon_tier3_full is every representative: the lanes its 12
# permutations (rank, remap, hash; no sort) ran on, generated -
# canon_dup_lanes. 0 on the host engines, which have no tiered canon.
# From the stats vector the wave already fetched: no extra device sync.
# dedup_sort_lanes (an extra key of the device engine's rows): the lanes
# its dedup stage's merged sort sorted, summed over the wave's
# chunk-steps: each step the seen run while it is merged, the prefix of
# the wave's fingerprint buffer the step chose (checker/util.py
# first_new) and the chunk's queries. Lane 8 of the same stats vector.
# dedup_search_queries (beside it, lane 9): the query lanes the dedup
# stage handed to the binary search (util.probe_sorted), summed over the
# wave's chunk-steps: a step's VC lanes once the occupied seen run is
# past the merge-or-search crossover (util.merges), 0 while it is merged.
# dedup_search_steps (lane 11): the wave's chunk-steps that searched, so
# dedup_search_queries is VC times it on a one-run engine; on the rows,
# on stats and on the summary.
# seen_lanes: the lanes of the seen run the wave ran against (its size
# before the wave's own merge, which may step it up), so a trace says
# which waves merged and which searched.
# expand_rows_built / expand_rows_budget (the device engine's rows, and
# the run's totals on stats and the summary): the successor rows the
# wave's apply passes built (models/base.py sparse_apply: a group's rows
# in tiles under the group's own count; lane 10 of the same stats
# vector) and the rows their plan budgets, sum(sparse_plan) a
# chunk-step. Built over budget is how far the apply pass follows what
# a chunk keeps; built over generated is what it still builds for
# nothing (whole tiles, a tile at least for a group that keeps a lane).
# hbm_bytes / hbm_peak_rise / hbm_frac (obs/memwatch.py, on every run):
# the allocator's bytes_in_use at the wave's end, after the seen merge
# and any growth (what the run holds between programs); by how much its
# peak_bytes_in_use rose since the read before, so a rise is booked to
# the wave it happened in (0 on most waves; the peak is the process's,
# so a later run of a process rises little or not at all); both null on
# a device that reports nothing (the CPU). hbm_frac is hbm_bytes over the budget
# where the device reports and the geometry's plan over it otherwise
# (the CPU dry run): never null on a watched run. The packed fleet's
# rows, which nobody watches, carry null for all three. The allocator
# is asked, not the stream: zero extra device syncs.
WAVE_KEYS = (
    "event", "wave", "depth", "frontier", "new", "distinct",
    "generated", "generated_total", "terminal", "dedup_hit_rate",
    "canon_dup_lanes", "canon_dup_rate", "canon_tier3_local",
    "canon_tier3_full", "overflow_bits",
    "lsm_runs", "lsm_lanes", "wave_s", "elapsed_s", "distinct_per_s",
    "emit_rows", "enabled_density", "expand_budget_ovf",
    "device_s", "host_s", "ckpt_s", "tel_s",
    "hbm_bytes", "hbm_peak_rise", "hbm_frac",
)

STALL_KEYS = (
    "event", "wave", "depth", "wave_s", "median_wave_s", "factor",
)

# actions: [n_actions][3] cumulative [enabled, fired, new_distinct]
# rows, index == the model's ACTION_NAMES rank (manifest carries the
# names); seen_lanes: allocated seen-set lanes per occupied LSM level
# (occupancy histogram; the host engine reports one level); seen_real:
# real (non-padding) fingerprints resident; probe_runs: sorted runs a
# membership probe binary-searches (fingerprint probe length);
# frontier_hist: distinct states first seen at each depth 0..d.
COVERAGE_KEYS = (
    "event", "wave", "depth", "actions", "actions_total",
    "actions_fired", "seen_lanes", "seen_real", "probe_runs",
    "frontier_hist", "final",
)

# what the process has spent on set-up so far, on every summary
# (obs/compiles.py ``run_stats``): seconds from process start to the
# package's first line and of each set-up phase (obs/trace.py
# ``setup_phase``), and the seconds it spent getting programs ready, by
# kind and as the union of all three; cumulative, as programs_loaded is.
# setup_pre_s is null where the platform has no /proc.
SETUP_KEYS = (
    "setup_pre_s", "setup_import_s", "setup_backend_s", "setup_cfg_s",
    "setup_model_s", "setup_engine_s", "load_trace_s", "load_lower_s",
    "load_compile_s", "load_cache_read_s", "load_union_s",
)
# with the two counts beside them: what validate_event holds to numbers
PROCESS_KEYS = ("programs_loaded", "programs_traced", *SETUP_KEYS)

SUMMARY_KEYS = (
    "event", "engine", "ident", "exit_cause", "violation", "distinct",
    "total", "depth", "terminal", "seconds", "distinct_per_s",
    "exhausted", "waves", "stalls", "peak_frontier_cap",
    "frontier_peak_rows", "restart_fired", "peak_journal_cap",
    "seen_lanes",
    "canon_dup_rate",
    "canon_tier3_local", "canon_tier3_full",
    *PROCESS_KEYS,
)

# a summary's ``programs``: the run's top-level program records
# (obs/compiles.py), each with the span that caused it
PROGRAM_KINDS = ("trace", "lower", "load")
PROGRAM_KEYS = ("kind", "fun_name", "seconds", "nesting", "cause")
CAUSE_KEYS = ("run", "top", "depth", "bracket")

# resilience events (self-healing runtime): the supervisor and the
# engines narrate recovery in the same stream the waves go to, so a
# chaos-ridden or preempted run is explicable from its JSONL alone.
#   retry            emitted by the supervisor between attempts:
#                    monotone ``attempt`` counter, classified ``cause``
#                    (overflow:<what> / crash / transient / ckpt-load),
#                    chosen ``backoff_s``, cumulative capacity
#                    ``growth`` summary string ("-" when none).
#   resume           emitted by an engine that restored state from a
#                    checkpoint, before its first wave: which file,
#                    which generation won, restored depth/distinct.
#   ckpt_generation  emitted when load had to SKIP newer generations
#                    (truncated/corrupt): the generation that verified
#                    and one diagnostic line per rejected candidate.
#   preempt          emitted when SIGTERM/SIGINT caused a wave-boundary
#                    checkpoint-and-exit (summary follows with
#                    exit_cause "preempted"; the CLI maps it to rc 4).
RETRY_KEYS = (
    "event", "attempt", "cause", "backoff_s", "growth",
)

RESUME_KEYS = (
    "event", "path", "generation", "depth", "distinct",
)

CKPT_GENERATION_KEYS = (
    "event", "path", "generation", "skipped",
)

PREEMPT_KEYS = (
    "event", "signame", "depth", "checkpoint",
)

# elastic-mesh events (sharded engine + supervisor):
#   shard_lost   a shard's device died mid-wave (chaos shard_loss=K or a
#                real preemption observed by the engine): which shard of
#                how many, the wave in flight, and whether a
#                redistributable wave-start checkpoint was spilled.
#                Emitted before the engine raises ShardLost — so it must
#                come before the run's summary.
#   reshard      a resume re-routed a checkpoint written on a different
#                mesh size by fp mod D_new. Emitted right after the
#                resumed run's manifest, before any wave.
#   shard_stall  the per-shard stall watchdog aborted a pathologically
#                slow wave instead of hanging the all-to-all: the
#                suspect (most-loaded) shard, the wave's seconds vs the
#                rolling median, and the configured factor. Emitted
#                before the engine raises ShardStall.
SHARD_LOST_KEYS = (
    "event", "wave", "depth", "shard", "device_count", "checkpoint_saved",
)

RESHARD_KEYS = (
    "event", "path", "from_d", "to_d", "depth", "distinct",
)

SHARD_STALL_KEYS = (
    "event", "wave", "depth", "shard", "wave_s", "median_wave_s", "factor",
)

# device memory (obs/memwatch.py):
#   memwatch    a wave's reading, emitted when the wave set a new plan
#               peak or the allocator's peak rose in it (so the stream
#               stays low-volume). Measured first: ``bytes`` /
#               ``peak_bytes`` are the allocator's bytes_in_use and
#               peak_bytes_in_use at the wave's end, ``peak_rise`` the
#               peak's rise since the read before; null on a device
#               that reports nothing (the CPU). The plan second:
#               ``plan_bytes`` is what the run's geometry says its
#               buffers take now, ``breakdown`` its split by buffer
#               family (frontier / chunk / seen / journal / ...),
#               ``plan_peak_bytes`` the run's largest so far,
#               ``plan_frac`` = plan_bytes / budget_bytes (may exceed
#               1.0: the out-of-core planning signal). ``frac`` is the
#               row's hbm_frac: measured where there is a reading, the
#               plan's otherwise. Both peaks are monotone within a run.
MEMWATCH_KEYS = (
    "event", "wave", "depth", "bytes", "peak_bytes", "peak_rise",
    "budget_bytes", "frac", "plan_bytes", "plan_peak_bytes",
    "plan_frac", "breakdown",
)

# a run's device memory on its summary and on its result's ``stats``
# (obs/memwatch.py has each key's meaning; the packed fleet's summaries,
# which nobody watches, carry none). The measured ones are the
# allocator's byte counts, and with the three fractions made of them
# null on a device that reports nothing (the CPU) and on the host
# engine, which hands in none; the budget and the plan's two always
# stand.
HBM_MEASURED_KEYS = (
    "hbm_peak_bytes", "hbm_live_bytes", "hbm_init_bytes", "hbm_init_rise",
)
HBM_KEYS = (
    "hbm_budget_bytes", *HBM_MEASURED_KEYS, "hbm_plan_bytes",
    "hbm_plan_frac", "hbm_peak_frac", "hbm_live_frac", "hbm_plan_gap_frac",
)

DECLARED_EVENTS = (
    ("manifest", MANIFEST_KEYS),
    ("wave", WAVE_KEYS),
    ("stall", STALL_KEYS),
    ("coverage", COVERAGE_KEYS),
    ("summary", SUMMARY_KEYS),
    ("retry", RETRY_KEYS),
    ("resume", RESUME_KEYS),
    ("ckpt_generation", CKPT_GENERATION_KEYS),
    ("preempt", PREEMPT_KEYS),
    ("shard_lost", SHARD_LOST_KEYS),
    ("reshard", RESHARD_KEYS),
    ("shard_stall", SHARD_STALL_KEYS),
    ("memwatch", MEMWATCH_KEYS),
)

EVENT_KEYS = dict(DECLARED_EVENTS)

# exit causes a summary event may carry (one run, one cause);
# "preempted" = SIGTERM/SIGINT honored at a wave boundary with a
# checkpoint written (restart with --resume loses nothing)
EXIT_CAUSES = (
    "exhausted", "violation", "max_depth", "time_budget", "preempted",
)


def hashv_of(ident: str) -> int:
    """Fingerprint-formula revision from a checkpoint ident string (the
    single source of truth for hashv — see DeviceBFS._ckpt_ident)."""
    m = re.search(r"hashv=(\d+)", ident)
    return int(m.group(1)) if m else 0


def _negative_or_no_number(v) -> bool:
    return isinstance(v, bool) or not isinstance(v, (int, float)) or v < 0


def _is_count(v) -> bool:
    return not isinstance(v, bool) and isinstance(v, int) and v >= 0


DEDUP_PLAN_KEYS = ("merge", "search", "wave_prefix", "sort_lanes")


def _dedup_plan_problems(plan) -> list[str]:
    """What is wrong with a manifest's or summary's ``dedup_plan``
    (checker/util.py dedup_plan): the sizes of the runs the dedup stage
    merges and searches, the prefix sizes of the wave's append buffer
    it can sort (none on the sharded engine; else from 0 up), the rungs
    it cuts a longer seen run by, where it says them (rising lane
    counts; none against a run at the sort's floor) and the most lanes
    a chunk-step sorts, which are the merged runs and the largest
    prefix at least, and the last rung."""
    if not isinstance(plan, dict) or any(
            k not in plan for k in DEDUP_PLAN_KEYS):
        return [f"{plan!r} must carry {DEDUP_PLAN_KEYS}"]
    rungs = plan.get("rungs", [])
    lists = [*(plan[k] for k in DEDUP_PLAN_KEYS[:3]), rungs]
    if not all(isinstance(v, list) and all(map(_is_count, v))
               for v in lists) or not _is_count(plan["sort_lanes"]):
        return [f"{plan!r}: sizes are non-negative ints (lanes)"]
    prefix = plan["wave_prefix"]
    found = []
    if prefix and (prefix[0] != 0 or any(
            a >= b for a, b in zip(prefix, prefix[1:]))):
        found.append(f"wave_prefix {prefix!r} must rise strictly from 0")
    if any(a >= b for a, b in zip(rungs, rungs[1:])):
        found.append(f"rungs {rungs!r} must rise strictly")
    if plan["sort_lanes"] < max(
            sum(plan["merge"]) + max(prefix, default=0),
            max(rungs, default=0)):
        found.append(
            f"sort_lanes {plan['sort_lanes']} is under the merged runs "
            f"and the largest prefix together, or under the last rung")
    return found


def _program_problems(programs) -> list[tuple]:
    """(index, problem) for each record of a summary's ``programs`` that
    is not one: a dict with PROGRAM_KEYS, a known kind, non-negative
    seconds, a cause with CAUSE_KEYS, and on a load its cache_hit."""
    if not isinstance(programs, list):
        return [("", f"{type(programs).__name__}, not a list of records")]
    found = []
    for i, rec in enumerate(programs):
        if not isinstance(rec, dict):
            found.append((i, "not an object"))
            continue
        missing = [k for k in PROGRAM_KEYS if k not in rec]
        if missing:
            found.append((i, f"missing {missing}"))
            continue
        if rec["kind"] not in PROGRAM_KINDS:
            found.append(
                (i, f"kind {rec['kind']!r} not in {PROGRAM_KINDS}"))
        if _negative_or_no_number(rec["seconds"]):
            found.append((i, f"seconds {rec['seconds']!r} must be a "
                             f"non-negative number"))
        cause = rec["cause"]
        if not isinstance(cause, dict) or any(
                k not in cause for k in CAUSE_KEYS):
            found.append((i, f"cause {cause!r} must carry {CAUSE_KEYS}"))
        if rec["kind"] == "load" and not isinstance(
                rec.get("cache_hit"), bool):
            found.append((i, "a load record says cache_hit true or false"))
    return found


def validate_event(ev: object, lineno: int | None = None) -> list[str]:
    """Problems with one decoded event (empty list = valid). Extra keys
    are allowed — engines extend the schema; they never shrink it."""
    where = f"line {lineno}: " if lineno is not None else ""
    if not isinstance(ev, dict):
        return [f"{where}not a JSON object: {type(ev).__name__}"]
    etype = ev.get("event")
    if etype not in EVENT_KEYS:
        return [
            f"{where}unknown event type {etype!r} "
            f"(declared: {', '.join(EVENT_KEYS)})"
        ]
    missing = [k for k in EVENT_KEYS[etype] if k not in ev]
    problems = []
    if missing:
        problems.append(
            f"{where}{etype} event missing declared keys: {missing}"
        )
    if "job" in ev and (not isinstance(ev["job"], str) or not ev["job"]):
        problems.append(
            f"{where}job tag {ev['job']!r} must be a non-empty string"
        )
    if etype in ("manifest", "summary") and "dedup_plan" in ev:
        problems += [f"{where}{etype} dedup_plan: {p}"
                     for p in _dedup_plan_problems(ev["dedup_plan"])]
    if etype == "wave":
        lanes = ev.get("dedup_sort_lanes")
        if lanes is not None and not _is_count(lanes):
            problems.append(
                f"{where}wave dedup_sort_lanes {lanes!r} must be a "
                f"non-negative int (lanes the dedup stage sorted)"
            )
        for key, what in (
            ("dedup_search_queries", "query lanes the dedup stage searched"),
            ("dedup_search_steps", "chunk-steps whose dedup stage searched"),
            ("seen_lanes", "lanes of the seen run the wave ran against"),
            ("expand_rows_built", "successor rows the apply pass built"),
            ("expand_rows_budget", "successor rows the apply's plan budgets"),
        ):
            val = ev.get(key)
            if val is not None and not _is_count(val):
                problems.append(
                    f"{where}wave {key} {val!r} must be a non-negative "
                    f"int ({what})"
                )
        dens = ev.get("enabled_density")
        if dens is not None and (
            isinstance(dens, bool) or not isinstance(dens, (int, float))
            or not 0.0 <= dens <= 1.0
        ):
            problems.append(
                f"{where}wave enabled_density {dens!r} must be a number "
                f"in [0, 1] (enabled fraction of the chunk*A grid)"
            )
        bovf = ev.get("expand_budget_ovf")
        if bovf is not None and (
            isinstance(bovf, bool) or not isinstance(bovf, int)
            or bovf < 0
        ):
            problems.append(
                f"{where}wave expand_budget_ovf {bovf!r} must be a "
                f"non-negative int"
            )
        tiers = [ev.get("canon_tier3_local"), ev.get("canon_tier3_full")]
        if any(isinstance(v, bool) or not isinstance(v, int) or v < 0
               for v in tiers if v is not None):
            problems.append(
                f"{where}wave canon_tier3_local/_full {tiers!r} must be "
                f"non-negative ints (lanes)"
            )
        elif None not in tiers and all(
            isinstance(ev.get(k), int)
            for k in ("generated", "canon_dup_lanes")
        ) and sum(tiers) > ev["generated"] - ev["canon_dup_lanes"]:
            problems.append(
                f"{where}wave canon_tier3 lanes {tiers!r} exceed the "
                f"lanes canonicalised less the in-chunk duplicates "
                f"({ev['generated']} - {ev['canon_dup_lanes']})"
            )
        for key in ("device_s", "host_s", "ckpt_s", "tel_s", "dispatch_s",
                    "fetch_s", "merge_s", "grow_s", "compile_s"):
            v = ev.get(key)
            if v is not None and (
                isinstance(v, bool) or not isinstance(v, (int, float))
                or v < 0
            ):
                problems.append(
                    f"{where}wave {key} {v!r} must be a non-negative "
                    f"number (seconds)"
                )
        frac = ev.get("hbm_frac")
        if frac is not None and _negative_or_no_number(frac):
            problems.append(
                f"{where}wave hbm_frac {frac!r} must be null or a "
                f"non-negative number"
            )
        for key in ("hbm_bytes", "hbm_peak_rise"):
            v = ev.get(key)
            if v is not None and not _is_count(v):
                problems.append(
                    f"{where}wave {key} {v!r} must be null or a "
                    f"non-negative int (bytes, by the allocator)"
                )
    if etype == "memwatch":
        for key in ("bytes", "peak_bytes", "peak_rise"):
            v = ev.get(key)
            if v is not None and not _is_count(v):
                problems.append(
                    f"{where}memwatch {key} {v!r} must be null or a "
                    f"non-negative int (bytes, by the allocator)"
                )
        for key in ("plan_bytes", "plan_peak_bytes", "budget_bytes"):
            v = ev.get(key)
            if not _is_count(v):
                problems.append(
                    f"{where}memwatch {key} {v!r} must be a non-negative "
                    f"int"
                )
        for low, high in (("bytes", "peak_bytes"),
                          ("plan_bytes", "plan_peak_bytes")):
            lo, hi = ev.get(low), ev.get(high)
            if _is_count(lo) and _is_count(hi) and lo > hi:
                problems.append(
                    f"{where}memwatch {low} {lo} exceeds {high} {hi} "
                    f"(a peak covers the wave that reports it)"
                )
        br = ev.get("breakdown")
        if not isinstance(br, dict) or any(
            not isinstance(k, str) or isinstance(v, bool)
            or not isinstance(v, int) or v < 0
            for k, v in br.items()
        ):
            problems.append(
                f"{where}memwatch breakdown must map buffer family "
                f"names to non-negative int bytes"
            )
    if etype == "summary":
        if ev.get("exit_cause") not in EXIT_CAUSES:
            problems.append(
                f"{where}summary exit_cause {ev.get('exit_cause')!r} not "
                f"in {EXIT_CAUSES}"
            )
        for key in PROCESS_KEYS:
            v = ev.get(key)
            if v is not None and _negative_or_no_number(v):
                problems.append(
                    f"{where}summary {key} {v!r} must be a non-negative "
                    f"number"
                )
        for key, what in (
                ("frontier_peak_rows", "the most rows a wave wrote"),
                ("restart_fired", "successors the crash actions generated"),
                ("hbm_budget_bytes", "the device's memory"),
                ("hbm_plan_bytes", "the geometry's peak"),
                *((key, "bytes, by the allocator")
                  for key in HBM_MEASURED_KEYS)):
            v = ev.get(key)
            if v is not None and not _is_count(v):
                problems.append(
                    f"{where}summary {key} {v!r} must be a non-negative "
                    f"int ({what})"
                )
        live, peak = ev.get("hbm_live_bytes"), ev.get("hbm_peak_bytes")
        if _is_count(live) and _is_count(peak) and live > peak:
            problems.append(
                f"{where}summary hbm_live_bytes {live} exceeds "
                f"hbm_peak_bytes {peak} (the allocator's peak covers "
                f"every reading of the run)"
            )
        problems += [
            f"{where}summary programs[{i}]: {p}"
            for i, p in _program_problems(ev.get("programs", []))
        ]
    if etype == "retry":
        att = ev.get("attempt")
        if isinstance(att, bool) or not isinstance(att, int) or att < 1:
            problems.append(
                f"{where}retry attempt {att!r} must be an int >= 1"
            )
        back = ev.get("backoff_s")
        if isinstance(back, bool) or not isinstance(back, (int, float)) \
                or back < 0:
            problems.append(
                f"{where}retry backoff_s {back!r} must be a non-negative "
                f"number"
            )
    if etype in ("resume", "ckpt_generation"):
        gen = ev.get("generation")
        if isinstance(gen, bool) or not isinstance(gen, int) or gen < 0:
            problems.append(
                f"{where}{etype} generation {gen!r} must be an int >= 0"
            )
        if etype == "ckpt_generation":
            sk = ev.get("skipped")
            if not isinstance(sk, list) or any(
                not isinstance(s, str) for s in sk
            ):
                problems.append(
                    f"{where}ckpt_generation skipped must be a list of "
                    f"diagnostic strings"
                )
    if etype in ("shard_lost", "shard_stall"):
        shard = ev.get("shard")
        if isinstance(shard, bool) or not isinstance(shard, int) or shard < 0:
            problems.append(
                f"{where}{etype} shard {shard!r} must be an int >= 0"
            )
        if etype == "shard_lost":
            dc = ev.get("device_count")
            if isinstance(dc, bool) or not isinstance(dc, int) or dc < 1:
                problems.append(
                    f"{where}shard_lost device_count {dc!r} must be an "
                    f"int >= 1"
                )
            elif isinstance(shard, int) and not isinstance(shard, bool) \
                    and not 0 <= shard < dc:
                problems.append(
                    f"{where}shard_lost shard {shard} out of range for "
                    f"device_count {dc}"
                )
    if etype == "reshard":
        for key in ("from_d", "to_d"):
            d = ev.get(key)
            if isinstance(d, bool) or not isinstance(d, int) or d < 1:
                problems.append(
                    f"{where}reshard {key} {d!r} must be an int >= 1"
                )
        fd, td = ev.get("from_d"), ev.get("to_d")
        if isinstance(fd, int) and isinstance(td, int) and fd == td:
            problems.append(
                f"{where}reshard from_d == to_d == {fd} (a same-size "
                f"resume must not emit a reshard event)"
            )
    if etype == "coverage":
        acts = ev.get("actions")
        if not isinstance(acts, list) or any(
            not isinstance(row, list) or len(row) != 3
            or any(not isinstance(c, int) or c < 0 for c in row)
            for row in acts
        ):
            problems.append(
                f"{where}coverage actions must be a list of "
                f"[enabled, fired, new] non-negative int triples"
            )
        elif ev.get("actions_total") != len(acts):
            problems.append(
                f"{where}coverage actions_total {ev.get('actions_total')!r}"
                f" != len(actions) {len(acts)}"
            )
    return problems


def validate_lines(lines) -> tuple[dict, list[str]]:
    """Validate an iterable of JSONL lines against DECLARED_EVENTS.

    Returns (counts, problems): counts maps event type -> occurrences.
    Structural rules beyond per-event keys: every line must parse; wave
    indices must be strictly increasing within a run (a new manifest
    starts a new run and resets the expectation); a run's summary must
    come after its waves; coverage events must come before the run's
    summary, carry non-decreasing wave indices (the final snapshot may
    repeat the last wave), and their cumulative per-action counters
    must be monotone non-decreasing cell-by-cell. Supervisor ``retry``
    attempts must be strictly increasing across a supervised session (a
    summary ends the session and resets the counter — a completed run
    means any later retry belongs to a new invocation).

    Elastic-mesh rules: a ``reshard`` event belongs to the load phase —
    it must come after its run's manifest but before the first wave and
    never after the summary; ``shard_lost``/``shard_stall`` abort an
    in-flight wave, so they must come before the run's summary and
    carry a wave index no smaller than the last completed wave (a new
    manifest resets these expectations too, which is the per-job reset
    in a multiplexed fleet stream).

    Job-tagged streams (fleet sweeps) add: per-job wave indices must be
    strictly increasing within that job's run (its ``job``-tagged
    manifest resets the expectation), and every job manifest must be
    matched by exactly one summary carrying the same job tag.

    ``memwatch`` events must come before their run's summary, and their
    peak_bytes must be monotone non-decreasing within a run (a new
    manifest resets the watermark).
    """
    counts: dict[str, int] = {}
    problems: list[str] = []
    last_wave = 0
    summarized = False
    last_cov_wave = 0
    prev_actions: list | None = None
    last_retry_attempt = 0
    last_memwatch_peak = {"peak_bytes": 0, "plan_peak_bytes": 0}
    job_wave: dict[str, int] = {}
    job_manifests: dict[str, int] = {}
    job_summaries: dict[str, int] = {}
    for lineno, raw in enumerate(lines, start=1):
        raw = raw.strip()
        if not raw:
            continue
        try:
            ev = json.loads(raw)
        except ValueError as e:
            problems.append(f"line {lineno}: not valid JSON ({e})")
            continue
        problems += validate_event(ev, lineno)
        etype = ev.get("event") if isinstance(ev, dict) else None
        if etype not in EVENT_KEYS:
            continue
        counts[etype] = counts.get(etype, 0) + 1
        job = ev.get("job")
        job = job if isinstance(job, str) and job else None
        if etype == "manifest":
            last_wave = 0
            summarized = False
            last_cov_wave = 0
            prev_actions = None
            last_memwatch_peak = {"peak_bytes": 0, "plan_peak_bytes": 0}
            if job is not None:
                job_manifests[job] = job_manifests.get(job, 0) + 1
                job_wave[job] = 0
        elif etype == "coverage":
            if summarized:
                problems.append(
                    f"line {lineno}: coverage event after the run's summary"
                )
            w = ev.get("wave")
            if not isinstance(w, int) or w < last_cov_wave:
                problems.append(
                    f"line {lineno}: coverage wave index {w!r} not "
                    f"non-decreasing (previous {last_cov_wave})"
                )
            else:
                last_cov_wave = w
            acts = ev.get("actions")
            if isinstance(acts, list) and prev_actions is not None and (
                len(acts) == len(prev_actions)
            ):
                for r, (row, prow) in enumerate(zip(acts, prev_actions)):
                    if (isinstance(row, list) and isinstance(prow, list)
                            and len(row) == len(prow) == 3
                            and any(c < p for c, p in zip(row, prow))):
                        problems.append(
                            f"line {lineno}: coverage counters for action "
                            f"rank {r} not monotone ({prow} -> {row})"
                        )
            if isinstance(acts, list):
                prev_actions = acts
        elif etype == "wave":
            if summarized:
                problems.append(
                    f"line {lineno}: wave event after the run's summary"
                )
            w = ev.get("wave")
            if not isinstance(w, int) or w <= last_wave:
                problems.append(
                    f"line {lineno}: wave index {w!r} not strictly "
                    f"increasing (previous {last_wave})"
                )
            else:
                last_wave = w
            if job is not None and isinstance(w, int):
                if w <= job_wave.get(job, 0):
                    problems.append(
                        f"line {lineno}: job {job!r} wave index {w} not "
                        f"strictly increasing "
                        f"(previous {job_wave.get(job, 0)})"
                    )
                else:
                    job_wave[job] = w
        elif etype == "reshard":
            if summarized:
                problems.append(
                    f"line {lineno}: reshard event after the run's summary"
                )
            elif last_wave > 0:
                problems.append(
                    f"line {lineno}: reshard event after wave {last_wave} "
                    f"(resharding happens at load time, before any wave)"
                )
        elif etype in ("shard_lost", "shard_stall"):
            if summarized:
                problems.append(
                    f"line {lineno}: {etype} event after the run's summary"
                )
            w = ev.get("wave")
            if isinstance(w, int) and not isinstance(w, bool) \
                    and w < last_wave:
                problems.append(
                    f"line {lineno}: {etype} wave index {w} behind the "
                    f"run's last completed wave {last_wave}"
                )
        elif etype == "memwatch":
            if summarized:
                problems.append(
                    f"line {lineno}: memwatch event after the run's summary"
                )
            for key in ("peak_bytes", "plan_peak_bytes"):
                peak = ev.get(key)
                if not _is_count(peak):
                    continue
                if peak < last_memwatch_peak[key]:
                    problems.append(
                        f"line {lineno}: memwatch {key} {peak} "
                        f"regressed below the run's watermark "
                        f"{last_memwatch_peak[key]} (peaks are monotone "
                        f"within a run)"
                    )
                else:
                    last_memwatch_peak[key] = peak
        elif etype == "retry":
            att = ev.get("attempt")
            if isinstance(att, int) and not isinstance(att, bool):
                if att <= last_retry_attempt:
                    problems.append(
                        f"line {lineno}: retry attempt {att} not strictly "
                        f"increasing (previous {last_retry_attempt})"
                    )
                else:
                    last_retry_attempt = att
        elif etype == "summary":
            summarized = True
            last_retry_attempt = 0
            if job is not None:
                job_summaries[job] = job_summaries.get(job, 0) + 1
    for job in sorted(set(job_manifests) | set(job_summaries)):
        nm = job_manifests.get(job, 0)
        ns = job_summaries.get(job, 0)
        if nm != ns:
            problems.append(
                f"job {job!r}: {nm} manifest(s) but {ns} summar"
                f"{'y' if ns == 1 else 'ies'} (one summary per job "
                f"manifest)"
            )
    return counts, problems
