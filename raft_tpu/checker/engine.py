"""What the BFS engines say and do alike, written once.

``BFSChecker`` (checker/bfs.py), ``DeviceBFS`` (checker/device_bfs.py)
and ``ShardedBFS`` (parallel/sharded.py) each keep their own ``run()``
and their own device program. What does not depend on either is here:

  canon_ident        the fingerprint-formula part of a checkpoint ident
  manifest_fields    the telemetry manifest
  resume_events      what a resumed run says after its manifest
  loop_exit          the wave loop's exits that are not a result
  phase_clocks       a device engine's wave clocks from its brackets
  wave_row           the wave event's declared keys, in schema order
  restart_fired      a run's crashes, from its coverage
  run_stats          what ``stats`` and the summary share of a run
  summary_fields     the summary event
  FleetQueue         ``run_fleet`` / ``_run_supervised`` of the two
                     device engines (the host engine's ``run_fleet`` is
                     the packed arm, a different algorithm, and its own)
  expand_chunk,      stages 1-2 of a chunk-step, traced into
  compact_chunk      ``DeviceBFS``'s wave program and ``ShardedBFS``'s
                     chunk program under their ``expand`` scope

An engine adds its own keys to a row or a summary where it calls the
builder, after the declared ones: one visible place an engine.
"""

from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..obs import COMPILES, JobTaggedTelemetry, hashv_of
from ..resilience import (
    CheckpointMismatch,
    UnrecoverableError,
    ckpt as rckpt,
    supervise as _supervise,
)

# ---------------- identity and manifest ----------------


def canon_ident(canon, seed: bool = False) -> str:
    """The ``sym=.../hashv=.../wl=...`` part of an engine's checkpoint
    ident (``_ckpt_ident``), from the canon, which owns all of it.

    ``hashv`` marks the fingerprint formula's revision and is the
    canon's own (``Canonicalizer`` 5, round 6: the 1-WL refinement
    iterates to a bounded depth, which changes the canonical
    representative of signature-tied states; KRaftWithReconfig's
    ``SlotCanonicalizer`` 6: the bag hashed as a multiset), so a
    checkpoint of another formula is refused on load
    (resilience/ckpt.check_spec) and ``obs.events.hashv_of`` reads it
    back for the manifest. The refinement depth ``wl`` is part of the
    formula and recorded beside it. The in-chunk dedup and the
    tie-group-local tier 3 preserve values and are NOT identity.
    ``seed``: ``DeviceBFS`` alone takes a fingerprint seed and names it
    (between ``sym`` and ``hashv``, where its checkpoints have it)."""
    wl = getattr(canon, "refine_rounds", 1)
    seeded = f"/seed={canon.seed}" if seed else ""
    return f"sym={canon.symmetry}{seeded}/hashv={canon.hashv}/wl={wl}"


def manifest_fields(
    engine, name: str, device, device_count: int = 1,
    frontier_cap: int = 0, journal_cap: int = 0, max_seen_cap: int = 0,
    valid_cap: int = 0, dedup_plan: dict | None = None,
) -> dict:
    """Run-provenance fields of the telemetry manifest event (all
    ``MANIFEST_KEYS`` except the auto-added "event") of ``engine`` under
    its stream name ``name`` on ``device``, the first of
    ``device_count``. The capacities default to the host engine's zeros
    (its arrays are unbounded numpy buffers: not capacity-limited);
    ``dedup_plan`` is there where the engine has one."""
    ident = engine._ckpt_ident()
    fields = {
        "engine": name,
        "ident": ident,
        "hashv": hashv_of(ident),
        "model": engine.model.name,
        "platform": device.platform,
        "device": str(getattr(device, "device_kind", device.platform)),
        "device_count": device_count,
        "chunk": engine.chunk,
        "frontier_cap": frontier_cap,
        "journal_cap": journal_cap,
        "max_seen_cap": max_seen_cap,
        "valid_cap": valid_cap,
        "symmetry": bool(engine.canon.symmetry),
        "invariants": list(engine.invariants),
        "action_names": list(getattr(engine.model, "ACTION_NAMES", ())),
        "when": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    if dedup_plan is not None:
        fields["dedup_plan"] = dedup_plan
    return fields


# ---------------- before the first wave ----------------


def resume_events(tel, path, generation, skipped, depth, distinct) -> None:
    """What a run restored from a checkpoint says right after its
    manifest: the verified load fell back past ``skipped`` newer
    generations (truncated or corrupt, one diagnostic each), and where
    the run picks up."""
    if skipped:
        tel.event(
            "ckpt_generation", path=path, generation=generation,
            skipped=list(skipped),
        )
    tel.event(
        "resume", path=path, generation=generation, depth=depth,
        distinct=distinct,
    )


# ---------------- the wave loop ----------------


def loop_exit(
    tel, preempt, chaos, depth: int, checkpoint_path, max_depth,
    time_budget_s, t0: float,
) -> str | None:
    """Why the wave loop ends before wave ``depth + 1``, or None: the
    head of a device engine's loop. SIGTERM/SIGINT is honoured at the
    wave boundary (the engine's final snapshot block writes the
    checkpoint, the CLI maps "preempted" to rc 4); chaos may raise or
    signal here; then the depth and time bounds. The caller sets
    ``exhausted = False`` and breaks on a cause."""
    if preempt is not None and preempt.requested:
        tel.event(
            "preempt", signame=preempt.signame, depth=depth,
            checkpoint=checkpoint_path,
        )
        return "preempted"
    if chaos is not None:
        chaos.wave_start(depth + 1)
    if max_depth is not None and depth >= max_depth:
        return "max_depth"
    if time_budget_s is not None and time.perf_counter() - t0 > time_budget_s:
        return "time_budget"
    return None


def phase_clocks(ph_s: dict, comp_wave, comp_now) -> dict:
    """A device engine's wave clocks, from the wave's brackets read once
    (``Phases.take``) and the compile snapshots at its two ends. Each
    phase's seconds are those of its span, unrounded. ``device_s`` is
    the host's WAIT on the device (dispatch, the blocking fetch, the
    seen merge's dispatch; the sharded engine enters each once a chunk),
    never device time; ``tel_s`` is the PREVIOUS wave's telemetry
    bracket (only known one wave late); ``grow_s`` exists on a wave that
    grew a buffer; ``compiles`` / ``compile_s`` are the programs the
    iteration loaded (compiled, or read from the persistent cache) and
    the seconds that took, so a growth or ladder-step compile is booked
    to its wave (obs/compiles.py). The first three are ``wave_row``'s
    arguments; the rest ride the row as they are."""
    dispatch_s = ph_s.get("dispatch", 0.0)
    fetch_s = ph_s.get("fetch", 0.0)
    merge_s = ph_s.get("seen_merge", 0.0)
    return {
        "device_s": dispatch_s + fetch_s + merge_s,
        "ckpt_s": ph_s.get("checkpoint", 0.0),
        "tel_s": ph_s.get("telemetry", 0.0),
        "dispatch_s": dispatch_s,
        "fetch_s": fetch_s,
        "merge_s": merge_s,
        "grow_s": ph_s.get("grow", 0.0),
        "compiles": comp_now[0] - comp_wave[0],
        "compile_s": comp_now[1] - comp_wave[1],
    }


def wave_row(
    *, depth, frontier, new, distinct, generated, generated_total,
    terminal, canon, overflow_bits, lsm_runs, lsm_lanes, wave_s,
    elapsed_s, A, expand_budget_ovf, device_s, ckpt_s, tel_s, hbm,
    **own,
) -> dict:
    """One wave's row: the declared keys (``obs.events.WAVE_KEYS`` but
    the collector's "event" and "wave", in that order), then ``own``,
    the caller's own keys in the order given. Everything comes from
    values the wave loop already holds on the host: zero extra device
    syncs. ``canon`` is the wave's (in-chunk duplicate lanes, tier-3
    local lanes, tier-3 full lanes), zeros on the host engine, which
    has no in-chunk dedup and no tiered canon; the declared keys still
    appear so one consumer reads every engine.

    ``emit_rows``: the rows appended this wave (the new ones). The
    sparse-expand gauges: the enabled fraction of the dense
    [frontier, A] candidate grid this wave (the guard-first win scales
    with its inverse) and ``expand_budget_ovf`` (device engines: the
    apply budget's overflow bit, always 0 on a surviving wave, the
    abort fires first; host engine: the extra apply blocks it ran past
    one a chunk). The clocks are unrounded and
    ``device_s + host_s + ckpt_s == wave_s``. ``hbm`` is the wave's
    reading of the run's ``MemWatch`` (``MemWatch.wave``; the packed
    fleet, which nobody watches, hands in ``obs.NO_READING``)."""
    dup, t3_local, t3_full = canon
    return {
        "depth": depth,
        "frontier": frontier,
        "new": new,
        "distinct": distinct,
        "generated": generated,
        "generated_total": generated_total,
        "terminal": terminal,
        "dedup_hit_rate": round(1.0 - new / max(1, generated), 4),
        "canon_dup_lanes": dup,
        "canon_dup_rate": round(dup / max(1, generated), 4),
        "canon_tier3_local": t3_local,
        "canon_tier3_full": t3_full,
        "overflow_bits": overflow_bits,
        "lsm_runs": lsm_runs,
        "lsm_lanes": lsm_lanes,
        "wave_s": wave_s,
        "elapsed_s": elapsed_s,
        "distinct_per_s": round(distinct / elapsed_s, 1),
        "emit_rows": new,
        "enabled_density": round(generated / max(1, frontier * A), 4),
        "expand_budget_ovf": expand_budget_ovf,
        "device_s": device_s,
        "host_s": max(0.0, wave_s - device_s - ckpt_s),
        "ckpt_s": ckpt_s,
        "tel_s": tel_s,
        **hbm,
        **own,
    }


# ---------------- the end of a run ----------------


def restart_fired(engine, coverage) -> int:
    """The ``fired`` column of the run's per-action ``coverage``
    ([actions, 3] as the wave loop fetched it last, or one such block a
    shard or a job), summed over the actions the model declares as
    crashes (``ActionLabelMixin.CRASH_ACTIONS``). 0 on a model that
    declares none."""
    names = getattr(engine.model, "ACTION_NAMES", ())
    crashes = getattr(engine.model, "CRASH_ACTIONS", ())
    if not names:
        return 0
    fired = np.asarray(coverage, np.int64).reshape(-1, len(names), 3)[
        :, :, 1].sum(axis=0)
    return sum(int(f) for n, f in zip(names, fired) if n in crashes)


def run_stats(
    engine, comp_run, ph, memwatch, *, frontier_peak_rows: int, coverage,
    **own,
) -> dict:
    """What a result's ``stats`` and the summary share: what the run
    loaded into the process (obs/compiles.py), its top-level spans'
    seconds, ``frontier_peak_rows``, the most rows a wave of the run
    wrote (the max of the wave rows' ``new``: how full the frontier
    got, beside the summary's ``peak_frontier_cap``, how large it was),
    ``restart_fired`` of ``engine``'s ``coverage``, the ``hbm_*`` keys
    of the run's ``memwatch`` with its last read of the allocator
    (``MemWatch.finish``), then the engine's ``own``. Call it beside the
    run's wall clock: ``init_s + waves_s + finish_s`` add up to that."""
    return {**COMPILES.run_stats(comp_run), **ph.top_seconds(),
            "frontier_peak_rows": int(frontier_peak_rows),
            "restart_fired": restart_fired(engine, coverage),
            **memwatch.finish(), **own}


def summary_fields(
    engine, name: str, *, exit_cause, violation, distinct, total, depth,
    terminal, seconds, exhausted, peak_frontier_cap, peak_journal_cap,
    seen_lanes, canon_dup_rate, stats: dict, programs=None, **own,
) -> dict:
    """The summary event of ``engine`` under its stream name ``name``:
    the declared counts, cause, seconds and rates, the caller's ``own``
    keys beside them, ``stats`` (``run_stats``, the ``hbm_*`` keys among
    them) and the run's program records where the caller has them.
    ``seconds`` is the run's wall, unrounded."""
    return {
        "engine": name,
        "ident": engine._ckpt_ident(),
        "exit_cause": exit_cause,
        "violation": violation,
        "distinct": distinct,
        "total": total,
        "depth": depth,
        "terminal": terminal,
        "seconds": round(seconds, 3),
        "distinct_per_s": (
            round(distinct / seconds, 1) if seconds > 0 else 0.0),
        "exhausted": exhausted,
        "peak_frontier_cap": peak_frontier_cap,
        "peak_journal_cap": peak_journal_cap,
        "seen_lanes": seen_lanes,
        "canon_dup_rate": canon_dup_rate,
        **own,
        **stats,
        **({} if programs is None else {"programs": programs}),
    }


# ---------------- the fleet's queue arm ----------------


class FleetQueue:
    """``run_fleet`` of the device engines: a fleet-bound model's jobs
    one at a time through ONE engine instance, each optionally under the
    resilience supervisor. The engine supplies ``run()``, ``model`` and
    ``_ctor_kw`` (its constructor's arguments as given)."""

    def _rebuild(self, overrides: dict):
        """A fresh engine with this one's constructor kwargs plus
        ``overrides`` (the supervisor's growth / shrunk-mesh dicts)."""
        return type(self)(**{**self._ctor_kw, **overrides})

    def run_fleet(
        self,
        job_names: list[str] | None = None,
        telemetry=None,
        checkpoint_dir: str | None = None,
        checkpoint_every_s: float = 300.0,
        checkpoint_keep: int = rckpt.DEFAULT_KEEP,
        resume: bool = False,
        skip: tuple[str, ...] = (),
        supervise: int | None = None,
        chaos_by_job: dict | None = None,
        recovery_stats: dict | None = None,
        **run_kw,
    ) -> list:
        """Fleet queue arm: run a fleet-bound model's jobs one at a time
        through THIS engine instance. ``fleet_select(j)`` changes only
        which job's constants get stamped into the init states — the
        compiled programs are shared, so every job after the first is a
        jit-cache hit (the programs compile once per layout group).
        Telemetry is job-tagged into one multiplexed stream
        (obs.JobTaggedTelemetry); each job checkpoints to its OWN
        lineage file under ``checkpoint_dir`` (resilience/ckpt.py
        generations, named by ``resilience.lineage_name`` so sanitizer
        collisions between job names cannot alias two lineages), so the
        supervisor restarts / resumes only the failed job. Jobs named in
        ``skip`` (fleet-level resume) yield None in the result list.

        ``supervise``: when set, each job runs under the resilience
        supervisor with that per-job recovery budget; empty-override
        recoveries reuse this instance's compiled programs (zero
        recompiles), and a job whose budget is spent (or whose failure
        has no recovery policy) contributes its terminal exception to
        the results list instead of killing the fleet. ``chaos_by_job``
        maps job name -> ChaosInjector for that job only;
        ``recovery_stats`` is filled in place with job name -> recovery
        count."""
        model = self.model
        J = model.fleet_jobs
        if J == 0:
            raise ValueError(
                "run_fleet needs a fleet-bound model (fleet_bind)"
            )
        names = list(job_names) if job_names else [f"job{j}" for j in range(J)]
        if len(names) != J:
            raise ValueError(f"{len(names)} job names for {J} jobs")
        results = []
        try:
            for j, name in enumerate(names):
                if name in skip:
                    results.append(None)
                    continue
                model.fleet_select(j)
                kw = dict(run_kw)
                if telemetry is not None:
                    kw["telemetry"] = JobTaggedTelemetry(telemetry, name)
                if chaos_by_job and name in chaos_by_job:
                    kw["chaos"] = chaos_by_job[name]
                if checkpoint_dir is not None:
                    ck = os.path.join(
                        checkpoint_dir, rckpt.lineage_name(name, j))
                    kw.setdefault("checkpoint_path", ck)
                    kw.setdefault("checkpoint_every_s", checkpoint_every_s)
                    kw.setdefault("checkpoint_keep", checkpoint_keep)
                    if resume and os.path.exists(ck):
                        kw.setdefault("resume", ck)
                if supervise is None:
                    results.append(self.run(**kw))
                    continue
                results.append(self._run_supervised(
                    kw, int(supervise), j, name, recovery_stats))
        finally:
            model.fleet_select(None)
        return results

    def _run_supervised(self, kw, budget, job_index, name, recovery_stats):
        """One fleet job under the resilience supervisor. Returns the
        run result, or the terminal exception object when the job's
        recovery budget is spent (the fleet driver maps it to an
        ``unrecoverable`` JobResult)."""
        def factory(overrides):
            # empty overrides -> this engine: recoveries that need
            # neither growth nor a shrunk mesh stay recompile-free
            return self if not overrides else self._rebuild(overrides)

        stats: dict = {}
        try:
            res = _supervise(
                factory, kw, max_retries=budget, backoff_base=0.0,
                seed=job_index, telemetry=kw.get("telemetry"),
                stats_out=stats,
            )
        except (UnrecoverableError, CheckpointMismatch) as exc:
            res = exc
        if recovery_stats is not None:
            recovery_stats[name] = int(stats.get("recoveries", 0))
        return res


# ---------------- stages 1-2 of a chunk-step ----------------
#
# Plain functions traced under the caller's ``obs.stage("expand")``
# scope: they add no scope and no equation of their own. They are two
# because the sharded engine tallies per-action coverage on the
# generating chip between them, and the order of a program's equations
# is what tests/test_engine_shared.py pins.


def expand_chunk(model, sparse: bool, frontier, cursor, fcount, C: int):
    """Stage 1: expand the ``C`` frontier rows from ``cursor``. With the
    sparse expand contract (models/base.py SparseExpandMixin) this is
    the guard pass only: valid/rank/ovf over the dense [C, A] grid
    without materializing any W-wide successor row (DCE-derived from
    ``_expand1``, bit-identical by construction), and ``succs`` is None;
    a legacy model expands densely. Rows past ``fcount`` are not live.
    Returns (batch, succs, valid, rank, n_gen, terminal, expand_ovf)."""
    W = model.layout.W
    batch = lax.dynamic_slice(frontier, (cursor, jnp.int32(0)), (C, W))
    live = (jnp.arange(C, dtype=jnp.int32) + cursor) < fcount
    if sparse:
        succs = None
        valid, rank, ovf = jax.vmap(model.guards1)(batch)
    else:
        succs, valid, rank, ovf = jax.vmap(model._expand1)(batch)
    valid = valid & live[:, None]
    expand_ovf = jnp.any(valid & ovf)
    n_gen = jnp.sum(valid)
    terminal = jnp.sum(live & ~jnp.any(valid, axis=1))
    return batch, succs, valid, rank, n_gen, terminal, expand_ovf


def rank_key_bits(chunk: int, A: int, n_actions: int) -> int:
    """log2 of ``R``, the stride of ``compact_chunk``'s sort key: a
    valid lane's key is ``flat * R + (rank + 1)`` and an invalid one's
    ``chunk * A * R``, ``R`` the power of two over ``n_actions + 2``
    (the rank of a lane is -1 .. n_actions - 1), so ``(chunk * A + 1)
    * R`` has to fit an int32. 0, the lanes' indices alone, where the
    engine counts no coverage."""
    bits = (n_actions + 1).bit_length() if n_actions else 0
    if (chunk * A + 1) << bits >= 1 << 31:
        raise ValueError(
            f"chunk={chunk} x A={A} candidate lanes with {n_actions} "
            f"action ranks pass the int32 key of the compaction's sort: "
            f"({chunk} * {A} + 1) * {1 << bits} >= 2^31; lower the chunk")
    return bits


def compact_chunk(
    model, plan, batch, succs, valid, rank, n_actions: int, n_gen, VC: int,
):
    """Stage 2: compact the valid lanes (``sel[j]`` = flat lane of the
    j-th valid successor, ``C * A`` past the last) into the [VC, W]
    successor block, by the first VC of one sort of one int32 key a
    lane. The key carries the lane's action rank under its flat index
    (``rank_key_bits``), so ``sel_rank[j]``, the rank of the j-th valid
    successor and -1 past the last, falls out of the same sort and is
    never gathered through ``sel``; with ``n_actions`` 0 ``rank`` is
    not read and every lane's is -1. With ``succs`` None (the sparse
    contract) this is the apply pass: successors are constructed ONLY
    for the compacted worklist lanes, vmapped per group over the static
    budget ``plan``, and a budget overflow folds into the compaction
    bit: both mean "a static worklist bound was exceeded, raise the
    knob". ``rows_built`` is the successor rows the apply pass built
    (``sparse_apply``'s count: its tiles follow what the worklist holds,
    not the budgets), 0 on the dense arm, which builds none here.
    Returns (flatc, sel, selv, sel_rank, compact_ovf, rows_built)."""
    C, A = valid.shape
    W = batch.shape[1]
    compact_ovf = n_gen > VC
    bits = rank_key_bits(C, A, n_actions)
    flat = jnp.arange(C * A, dtype=jnp.int32)
    if bits:
        flat = (flat << bits) + (rank.reshape(-1) + 1)
    # a stream compaction of int32 lanes is one sort of one key on this
    # chip, never an ``.at[dst].set`` (a serial pass, 4.6 ns a lane),
    # and a 1-D gather by its result is another (7.1 ns a lane)
    key = lax.sort(jnp.where(valid.reshape(-1), flat, (C * A) << bits))[:VC]
    sel = key >> bits
    selv = sel < C * A
    sel_rank = (key & ((1 << bits) - 1)) - 1  # the drop key's is -1
    if succs is None:
        flatc, apply_ovf, rows_built = model.sparse_apply(
            batch, sel, selv, plan)
        compact_ovf = compact_ovf | apply_ovf
    else:
        rows_built = jnp.zeros((), jnp.int32)
        flatp = jnp.concatenate(
            [succs.reshape(C * A, W), jnp.zeros((1, W), jnp.int32)],
            axis=0,
        )
        flatc = flatp[sel]  # [VC, W]
    return flatc, sel, selv, sel_rank, compact_ovf, rows_built
