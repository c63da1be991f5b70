"""BFS model-checking driver (single-device v1).

Replaces TLC's exhaustive BFS loop (SURVEY.md §3.1): frontier expansion and
invariant evaluation are batched on device; dedup runs on 64-bit canonical
fingerprints (VIEW + SYMMETRY, ops/symmetry.py) with the seen-set as a
sorted uint64 array merged per wave (vectorized searchsorted — the Pallas
cuckoo set slots in behind the same interface later). `-deadlock` TLC
semantics: terminal states are legitimate, not errors (reference
README.md:7), though we count them.

Trace reconstruction: a parent-pointer journal (global state id, candidate
id) per distinct state; counterexamples replay the action chain from the
initial state (SURVEY.md §5.1).
"""

from __future__ import annotations

import sys
import time
from dataclasses import dataclass, field

import jax
import numpy as np

from ..obs import (
    COMPILES, MemWatch, NO_READING, NULL_TELEMETRY, setup_phase, span,
    traced_run,
)
from ..ops.hashing import U64_MAX
from ..ops.symmetry import Canonicalizer
from ..resilience import ckpt as rckpt
from ..resilience.errors import CapacityOverflow
from .engine import (
    canon_ident, manifest_fields, restart_fired, resume_events, run_stats,
    summary_fields, wave_row,
)


def _in_sorted(sorted_arr: np.ndarray, vals: np.ndarray) -> np.ndarray:
    """Membership mask of vals in a sorted array (vectorized probe)."""
    if not len(sorted_arr):
        return np.zeros(len(vals), dtype=bool)
    pos = np.clip(np.searchsorted(sorted_arr, vals), 0, len(sorted_arr) - 1)
    return sorted_arr[pos] == vals


def _merge_sorted(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Union of two sorted disjoint uint64 arrays, O(len(a)+len(b))-ish."""
    if not len(b):
        return a
    out = np.concatenate([a, b])
    # both halves sorted and disjoint: a stable mergesort exploits the runs
    out.sort(kind="stable")
    return out


class _AppendBuf:
    """Amortized-doubling cursor-append buffer: the host mirror of the
    device engines' contiguous emit (checker/util.py emit_append). Each
    chunk's survivors land at a running cursor in one contiguous copy,
    replacing the per-wave list-of-arrays + concatenate (which held every
    chunk's fragment live and re-walked them all at wave end)."""

    def __init__(self, cols: int | None, dtype):
        self.n = 0
        self._cols = cols
        self._buf = np.empty((0,) if cols is None else (0, cols), dtype)

    def append(self, rows: np.ndarray) -> None:
        need = self.n + len(rows)
        if need > len(self._buf):
            cap = max(1024, len(self._buf))
            while cap < need:
                cap *= 2
            grown = np.empty(
                (cap,) if self._cols is None else (cap, self._cols),
                self._buf.dtype,
            )
            grown[: self.n] = self._buf[: self.n]
            self._buf = grown
        self._buf[self.n : need] = rows
        self.n = need

    @property
    def nbytes(self) -> int:
        """Bytes of REAL rows (the emit-bytes gauge counts written data,
        not the doubling headroom)."""
        return self._buf[: self.n].nbytes

    def take(self) -> np.ndarray:
        """The real rows as an owning array (drops the headroom, so a
        wave's frontier does not pin the oversized append buffer)."""
        return self._buf[: self.n].copy()


@dataclass
class Violation:
    invariant: str
    global_id: int
    depth: int


@dataclass
class CheckResult:
    distinct: int
    total: int
    depth: int  # BFS diameter reached
    depth_counts: list[int]
    violation: Violation | None
    terminal: int  # states with no successors (reported under -deadlock)
    seconds: float
    states_per_sec: float
    exhausted: bool = True  # False if stopped by max_depth/time budget
    trace: list[tuple[str, dict]] | None = None  # (action label, decoded state)
    metrics: list[dict] | None = None  # per-wave metrics (SURVEY.md §5.5)
    # per-action [enabled, fired, new-distinct] in ACTION_NAMES rank
    # order (TLC -coverage analog); None for models without the
    # rank/name contract
    coverage: list[list[int]] | None = None
    # why the run ended (obs.events.EXIT_CAUSES vocabulary); the CLI
    # maps "preempted" to exit code 4
    exit_cause: str | None = None
    # what the run loaded into the process, by the program's own count
    # (obs/compiles.py): programs_loaded (cumulative in the process),
    # run_compiles, run_compile_s, run_cache_hits, run_cache_read_s.
    # DeviceBFS fills it; ShardedResult.stats carries the same keys
    stats: dict | None = None


class BFSChecker:
    @setup_phase("engine")
    def __init__(
        self,
        model,
        invariants: tuple[str, ...] = (),
        symmetry: bool = True,
        chunk: int = 1024,
        check_deadlock: bool = False,
    ):
        # constructor kwargs, for _rebuild (supervisor growth overrides)
        self._ctor_kw = {k: v for k, v in locals().items() if k != "self"}
        self.model = model
        self.invariants = tuple(invariants)
        self.chunk = chunk
        self.check_deadlock = check_deadlock
        self.n_actions = len(getattr(model, "ACTION_NAMES", ()))
        self.canon = Canonicalizer.for_model(model, symmetry=symmetry)
        self._expand = model.expand  # dense path (trace reconstruction)
        # guard-first sparse expansion (SparseExpandMixin models): the
        # wave loop runs the cheap guard pass over the dense [chunk, A]
        # grid and constructs successor rows only for the enabled lanes
        # (model.host_apply); legacy/custom models keep the dense path
        self._sparse = hasattr(model, "host_apply")
        self._guards = (
            jax.jit(jax.vmap(model.guards1)) if self._sparse else None
        )
        self._fps = self.canon.fingerprints
        # journal: per distinct state (beyond init): parent global id + candidate
        self._parents: list[np.ndarray] = []
        self._cands: list[np.ndarray] = []

    # ---------------- main loop ----------------

    @traced_run("host")
    def run(
        self,
        max_depth: int | None = None,
        verbose: bool = False,
        time_budget_s: float | None = None,
        collect_metrics: bool = False,
        checkpoint_path: str | None = None,
        checkpoint_every_s: float = 300.0,
        checkpoint_keep: int = rckpt.DEFAULT_KEEP,
        resume: str | None = None,
        telemetry=None,
        preempt=None,
        chaos=None,
    ) -> CheckResult:
        model = self.model
        B = self.chunk
        t0 = time.perf_counter()
        # the run's top-level host spans, as the device engines have
        # them (obs/trace.py): init, a wave an iteration, finish
        ph = self._ph
        ph.top("init")
        comp_run = COMPILES.snapshot()
        exhausted = True
        exit_cause = None
        tel = telemetry if telemetry is not None else NULL_TELEMETRY
        # this engine's arrays are the host's: it hands the watch no
        # device, so the measured keys are None here as on the CPU and
        # only the plan below, of host RAM, stands (obs/memwatch.py)
        memwatch = MemWatch(tel, ())
        self._ckpt_keep = checkpoint_keep
        self._chaos = chaos

        init = model.init_states()
        n0 = len(init)
        init_fps = np.asarray(jax.device_get(self._fps(init)), dtype=np.uint64)
        order = np.argsort(init_fps, kind="stable")
        keep = np.ones(len(order), dtype=bool)  # dedup inits (all distinct normally)
        sorted_fps = init_fps[order]
        dup = np.zeros(len(order), dtype=bool)
        dup[1:] = sorted_fps[1:] == sorted_fps[:-1]
        keep[order[dup]] = False
        frontier = init[keep]
        self._init_distinct = frontier  # gid 0..k-1 (post-dedup numbering)
        seen = np.sort(init_fps[keep])

        total = n0
        distinct = len(frontier)
        depth_counts = [distinct]
        terminal = 0
        violation = None
        K = self.n_actions
        cov = np.zeros((K, 3), dtype=np.int64)  # [enabled, fired, new]/rank
        depth = 0
        base_gid = 0  # global id of first state in current frontier
        next_gid = distinct

        ck_gen = 0
        ck_skipped: list[str] = []
        if resume is not None:
            # wave-boundary snapshot: the gid numbering below the saved
            # frontier is deterministic from the model, so only the
            # explored state (frontier/seen/journal/counters) reloads
            ck, ck_gen, ck_skipped = rckpt.load_npz(
                resume, keep=checkpoint_keep
            )
            rckpt.check_spec(ck, self._ckpt_ident(), resume)
            frontier = np.asarray(ck["frontier"], dtype=np.int32)
            seen = np.asarray(ck["seen"], dtype=np.uint64)
            self._parents = [np.asarray(ck["parents"], dtype=np.int64)]
            self._cands = [np.asarray(ck["cands"], dtype=np.int32)]
            distinct = int(ck["distinct"])
            total = int(ck["total"])
            terminal = int(ck["terminal"])
            depth = int(ck["depth"])
            base_gid = int(ck["base_gid"])
            next_gid = int(ck["next_gid"])
            depth_counts = list(int(x) for x in ck["depth_counts"])
            # coverage joined the format after version 1 shipped; older
            # files resume with zeroed counters
            cov = (
                np.asarray(ck["coverage"], dtype=np.int64)
                if "coverage" in ck
                else np.zeros((K, 3), dtype=np.int64)
            )
        else:
            viol = self._check_invariants(frontier, 0, 0)
            if viol is not None:
                violation = viol

        tel.open_run(self._telemetry_manifest())
        if resume is not None:
            resume_events(tel, resume, ck_gen, ck_skipped, depth, distinct)
        metrics: list[dict] | None = [] if collect_metrics else None
        last_ckpt = time.perf_counter()
        # the row's device_s counts the jax-facing sections of a chunk
        # (expand/guards dispatch + fetches, fingerprinting); dedup, emit
        # and the seen merge are host bookkeeping and land in host_s
        memwatch.init()
        tel_s_last = 0.0
        while len(frontier) and violation is None:
            if preempt is not None and preempt.requested:
                exhausted = False
                exit_cause = "preempted"
                tel.event(
                    "preempt", signame=preempt.signame, depth=depth,
                    checkpoint=checkpoint_path,
                )
                break
            if chaos is not None:
                chaos.wave_start(depth + 1)
                inj = chaos.ovf_bits(0, depth + 1, 4)
                if inj:
                    # the host engine has no fixed frontier buffer, so a
                    # spurious overflow still aborts at wave-start state
                    # (the supervisor rebuilds with empty growth and
                    # resumes) — exercising the same recovery path the
                    # device engines take
                    if checkpoint_path is not None:
                        self._save_checkpoint(
                            checkpoint_path, frontier, seen, distinct,
                            total, terminal, depth, base_gid, next_gid,
                            depth_counts, cov,
                        )
                    raise CapacityOverflow(
                        "injected frontier overflow (chaos)",
                        what=("frontier",), bits=int(inj),
                        checkpoint_saved=checkpoint_path is not None,
                    )
            if max_depth is not None and depth >= max_depth:
                exhausted = False
                exit_cause = "max_depth"
                break
            if time_budget_s is not None and time.perf_counter() - t0 > time_budget_s:
                exhausted = False
                exit_cause = "time_budget"
                break
            ph.wave(self._run_id, depth + 1, len(frontier))
            tw = time.perf_counter()
            dev_s = 0.0
            # contiguous cursor-append emit (mirrors the device engines'
            # emit_append): survivors append at a running cursor
            wave_sb = _AppendBuf(model.layout.W, np.int32)
            wave_pb = _AppendBuf(None, np.int64)
            wave_cb = _AppendBuf(None, np.int32)
            # fingerprints first discovered this wave; kept separate from the
            # (much larger) global seen-set so per-chunk dedup only re-sorts
            # wave-sized arrays
            wave_fps = np.empty(0, dtype=np.uint64)
            n_cand_total = 0
            wave_extra = 0  # host apply blocks past one per chunk
            has_succ = np.zeros(len(frontier), dtype=bool)
            with tel.wave_annotation(depth + 1):
                for off in range(0, len(frontier), B):
                    t_exp = time.perf_counter()
                    chunk_states = frontier[off : off + B]
                    nb = len(chunk_states)
                    if nb < B:  # pad to the compiled batch shape
                        pad = np.repeat(chunk_states[-1:], B - nb, axis=0)
                        chunk_states = np.concatenate([chunk_states, pad], axis=0)
                    if self._sparse:
                        # guard pass only: no [B*A, W] successor rows
                        valid, rank, ovf = (
                            np.array(x)
                            for x in jax.device_get(
                                self._guards(chunk_states)
                            )
                        )
                    else:
                        succs, valid, rank, ovf = self._expand(chunk_states)
                        # one fetch for the three per-lane outputs (rank
                        # now feeds the coverage accumulator)
                        valid, rank, ovf = (
                            np.array(x)
                            for x in jax.device_get((valid, rank, ovf))
                        )
                    dev_s += time.perf_counter() - t_exp
                    valid[nb:] = False
                    if np.any(valid & ovf):
                        raise CapacityOverflow(
                            "message-slot overflow: re-run with a larger msg_slots",
                            what=("msg",), bits=1,
                        )
                    if K:
                        # numpy mirror of DeviceBFS._chunk_step 4b:
                        # invalid lanes route to drop bucket K
                        rk = np.where(valid, rank, K)
                        flat_rk = rk.reshape(-1)
                        cov[:, 1] += np.bincount(flat_rk, minlength=K + 1)[:K]
                        hit = np.zeros((len(valid), K + 1), dtype=bool)
                        hit[np.arange(len(valid))[:, None], rk] = True
                        cov[:, 0] += hit[:, :K].sum(axis=0)
                    t_can = time.perf_counter()
                    if self._sparse:
                        # apply pass: construct rows for the enabled
                        # lanes only, then fan their fingerprints back
                        # out to flat-lane indexing so dedup, journal
                        # and coverage below are shared verbatim with
                        # the dense path (bit-identical)
                        en_idx = np.nonzero(valid.reshape(-1))[0]
                        rows, extra = model.host_apply(
                            np.asarray(chunk_states), en_idx
                        )
                        wave_extra += extra
                        fps = np.full(
                            B * model.A, U64_MAX, dtype=np.uint64
                        )
                        if len(en_idx):
                            fps[en_idx] = self._fps_rows(rows)
                    else:
                        flat = succs.reshape(-1, model.layout.W)
                        fps = np.array(
                            jax.device_get(self._fps(flat)),
                            dtype=np.uint64,
                        )
                        fps[~valid.reshape(-1)] = U64_MAX
                    # the apply+fingerprint section mirrors the device
                    # program's canon stage, so it counts as device-facing
                    # time even on the sparse (host_apply) path
                    dev_s += time.perf_counter() - t_can
                    n_cand_total += int(valid.sum())
                    has_succ[off : off + nb] = valid[:nb].any(axis=1)

                    # first-occurrence-in-order selection of unseen fingerprints
                    new_mask = fps != U64_MAX
                    new_mask &= ~_in_sorted(seen, fps)
                    new_mask &= ~_in_sorted(wave_fps, fps)
                    # in-chunk dedup, keeping first occurrence
                    _, first_idx = np.unique(fps, return_index=True)
                    first = np.zeros(len(fps), dtype=bool)
                    first[first_idx] = True
                    new_mask &= first
                    idx = np.nonzero(new_mask)[0]
                    if K:
                        cov[:, 2] += np.bincount(
                            flat_rk[idx], minlength=K + 1)[:K]
                    if len(idx):
                        if self._sparse:
                            # idx lanes are all enabled (U64_MAX-masked
                            # lanes never survive new_mask), so each has
                            # a row in the compact apply output
                            sel = rows[np.searchsorted(en_idx, idx)]
                        else:
                            sel = np.asarray(jax.device_get(flat[idx]))
                        wave_sb.append(sel)
                        wave_pb.append(base_gid + off + idx // model.A)
                        wave_cb.append((idx % model.A).astype(np.int32))
                        wave_fps = np.sort(np.concatenate([wave_fps, fps[idx]]))

            total += n_cand_total
            terminal += int((~has_succ).sum())
            if wave_sb.n == 0:
                exit_cause = "exhausted"
                break
            wave_emit = wave_sb.nbytes + wave_pb.nbytes + wave_cb.nbytes
            wave_states = wave_sb.take()
            wave_parents = wave_pb.take()
            wave_cands = wave_cb.take()
            self._parents.append(wave_parents)
            self._cands.append(wave_cands)
            with ph("seen_merge"):
                seen = _merge_sorted(seen, wave_fps)
            depth += 1
            depth_counts.append(len(wave_states))
            violation = self._check_invariants(wave_states, next_gid, depth)
            base_gid = next_gid
            next_gid += len(wave_states)
            distinct += len(wave_states)
            prev_frontier = len(frontier)
            frontier = wave_states
            ckpt_s = 0.0
            if (
                checkpoint_path is not None
                and violation is None  # a saved file must not mask a violation
                and time.perf_counter() - last_ckpt > checkpoint_every_s
            ):
                t_ck = time.perf_counter()
                self._save_checkpoint(
                    checkpoint_path, frontier, seen, distinct, total,
                    terminal, depth, base_gid, next_gid, depth_counts, cov,
                )
                last_ckpt = time.perf_counter()
                ckpt_s = last_ckpt - t_ck
            wave_s_val = time.perf_counter() - tw
            # the plan here is the host-RAM analog of the device
            # engines': the live working set is the frontier, the sorted
            # seen array, the parent/candidate journal and this wave's
            # emit block
            hbm = memwatch.wave(depth, {
                "frontier": int(frontier.nbytes),
                "seen": int(seen.nbytes),
                "journal": int(
                    sum(p.nbytes for p in self._parents)
                    + sum(c.nbytes for c in self._cands)
                ),
                "wave_emit": int(wave_emit),
            })
            if tel.active or metrics is not None or verbose:
                el = time.perf_counter() - t0
                wm = wave_row(
                    depth=depth, frontier=prev_frontier,
                    new=len(wave_states), distinct=distinct,
                    generated=n_cand_total, generated_total=total,
                    terminal=terminal, canon=(0, 0, 0), overflow_bits=0,
                    lsm_runs=1, lsm_lanes=int(len(seen)),
                    wave_s=wave_s_val, elapsed_s=el,
                    # extra fixed-size apply blocks past one per chunk:
                    # the host analog of the device engines' budget
                    # overflow bit (it loops instead of aborting)
                    A=model.A, expand_budget_ovf=wave_extra,
                    device_s=dev_s, ckpt_s=ckpt_s, tel_s=tel_s_last,
                    hbm=hbm,
                )
                t_tel = time.perf_counter()
                tel.wave(wm)
                if tel.active:
                    tel.coverage(self._coverage_fields(
                        depth, cov, len(seen), depth_counts))
                if metrics is not None:
                    metrics.append(wm)
                if verbose:
                    print(
                        f"depth {depth}: frontier {len(wave_states)}, "
                        f"distinct {distinct}, total {total}, "
                        f"{distinct/el:.0f} distinct/s",
                        file=sys.stderr,
                    )
                tel_s_last = time.perf_counter() - t_tel

        ph.top("finish")
        if checkpoint_path is not None and violation is None and not exhausted:
            # budget/depth/preemption exit at a wave boundary: save a
            # final resumable snapshot (the periodic timer alone can
            # leave no checkpoint at all on short-budget runs)
            self._save_checkpoint(
                checkpoint_path, frontier, seen, distinct, total,
                terminal, depth, base_gid, next_gid, depth_counts, cov,
            )

        dt = time.perf_counter() - t0
        stats_run = run_stats(
            self, comp_run, ph, memwatch,
            frontier_peak_rows=max(depth_counts[1:], default=0),
            coverage=cov)
        if violation is not None:
            exit_cause = "violation"
        elif exit_cause is None:
            exit_cause = "exhausted"
        if tel.active:
            tel.coverage(
                self._coverage_fields(depth, cov, len(seen), depth_counts),
                final=True,
            )
        tel.close_run(summary_fields(
            self, "host",
            exit_cause=exit_cause,
            violation=violation.invariant if violation else None,
            distinct=distinct, total=total, depth=depth,
            terminal=terminal, seconds=dt,
            exhausted=exhausted and violation is None,
            peak_frontier_cap=int(max(depth_counts)),
            peak_journal_cap=int(next_gid - len(self._init_distinct)),
            seen_lanes=int(len(seen)), canon_dup_rate=0.0,
            stats=stats_run, programs=COMPILES.programs(comp_run),
            canon_tier3_local=0, canon_tier3_full=0,
        ))
        trace = self.reconstruct_trace(violation) if violation else None
        return CheckResult(
            distinct=distinct,
            total=total,
            depth=depth,
            depth_counts=depth_counts,
            violation=violation,
            terminal=terminal,
            seconds=dt,
            states_per_sec=distinct / dt if dt > 0 else 0.0,
            exhausted=exhausted and violation is None,
            trace=trace,
            metrics=metrics,
            coverage=[[int(x) for x in row] for row in cov] if K else None,
            exit_cause=exit_cause,
            stats=stats_run,
        )

    # ---------------- fleet (packed co-resident jobs) ----------------

    def run_fleet(
        self,
        job_names: list[str] | None = None,
        max_depth: int | None = None,
        verbose: bool = False,
        time_budget_s: float | None = None,
        telemetry=None,
    ) -> list[CheckResult]:
        """Run every job of a fleet-bound model (models/base.py
        FleetConstMixin) through ONE shared BFS: all jobs' stamped init
        states live in one frontier / seen-set / journal, and the job
        lane keeps their fingerprints disjoint.

        Per-job tallies (distinct/total/terminal/coverage/depth_counts)
        are split out of the shared wave with bincounts on the job lane;
        a job that violates an invariant has its rows masked from the
        next frontier, so finished jobs idle at zero cost while the
        rest keep exploring. Because the frontier stays job-major and
        first-occurrence dedup is fingerprint-value-independent, every
        job's emitted state sequence — and therefore its distinct
        count, depth histogram and counterexample trace — is
        bit-identical to a serial ``run()`` of that job (pinned by
        tests/test_fleet.py). ``seconds`` on each result is the GROUP
        wall time: co-resident jobs do not have separable clocks.

        ``max_depth``/``time_budget_s`` are fleet-global (a per-job
        depth limit would desynchronize the shared wave). Checkpointing
        is not multiplexed on this arm — the driver re-runs a packed
        group on resume (fleet/driver.py); the queue arm has per-job
        lineages.
        """
        model = self.model
        B = self.chunk
        J = model.fleet_jobs
        if J == 0:
            raise ValueError("run_fleet needs a fleet-bound model (fleet_bind)")
        names = list(job_names) if job_names else [f"job{j}" for j in range(J)]
        if len(names) != J:
            raise ValueError(f"{len(names)} job names for {J} jobs")
        tel = telemetry if telemetry is not None else NULL_TELEMETRY
        t0 = time.perf_counter()
        comp_run = COMPILES.snapshot()
        K = self.n_actions

        model.fleet_select(None)
        init = model.init_states()
        init_jobs = model.fleet_job_of(init).astype(np.int64)
        n0_by_job = np.bincount(init_jobs, minlength=J).astype(np.int64)
        init_fps = np.asarray(jax.device_get(self._fps(init)), dtype=np.uint64)
        order = np.argsort(init_fps, kind="stable")
        keep = np.ones(len(order), dtype=bool)
        sorted_fps = init_fps[order]
        dup = np.zeros(len(order), dtype=bool)
        dup[1:] = sorted_fps[1:] == sorted_fps[:-1]
        keep[order[dup]] = False
        frontier = init[keep]
        fjobs = init_jobs[keep]
        fgids = np.arange(len(frontier), dtype=np.int64)
        self._init_distinct = frontier
        self._parents, self._cands = [], []  # fleet-global gid journal
        seen = np.sort(init_fps[keep])

        total_j = n0_by_job.copy()
        distinct_j = np.bincount(fjobs, minlength=J).astype(np.int64)
        depth_counts_j = [[int(x)] for x in distinct_j]
        terminal_j = np.zeros(J, np.int64)
        depth_j = np.zeros(J, np.int64)
        violation_j: list[Violation | None] = [None] * J
        cov_j = np.zeros((J, K, 3), dtype=np.int64)
        active = np.ones(J, dtype=bool)
        depth = peak_rows = 0
        next_gid = len(frontier)
        exit_cause_global = None

        tel.open_run({**self._telemetry_manifest(), "fleet_jobs": J})

        self._fleet_check_invariants(
            frontier, fgids, fjobs, 0, violation_j, active
        )
        if not active.all():
            m = active[fjobs]
            frontier, fjobs, fgids = frontier[m], fjobs[m], fgids[m]

        while len(frontier):
            if max_depth is not None and depth >= max_depth:
                exit_cause_global = "max_depth"
                break
            if (
                time_budget_s is not None
                and time.perf_counter() - t0 > time_budget_s
            ):
                exit_cause_global = "time_budget"
                break
            tw = time.perf_counter()
            wave_sb = _AppendBuf(model.layout.W, np.int32)
            wave_pb = _AppendBuf(None, np.int64)
            wave_cb = _AppendBuf(None, np.int32)
            wave_jb = _AppendBuf(None, np.int64)
            wave_fps = np.empty(0, dtype=np.uint64)
            cand_by_job = np.zeros(J, np.int64)
            has_succ = np.zeros(len(frontier), dtype=bool)
            with tel.wave_annotation(depth + 1):
                for off in range(0, len(frontier), B):
                    chunk_states = frontier[off : off + B]
                    nb = len(chunk_states)
                    jrows = fjobs[off : off + nb]
                    if nb < B:
                        pad = np.repeat(chunk_states[-1:], B - nb, axis=0)
                        chunk_states = np.concatenate(
                            [chunk_states, pad], axis=0
                        )
                        jrows_p = np.concatenate(
                            [jrows, np.repeat(jrows[-1:], B - nb)]
                        )
                    else:
                        jrows_p = jrows
                    if self._sparse:
                        valid, rank, ovf = (
                            np.array(x)
                            for x in jax.device_get(
                                self._guards(chunk_states)
                            )
                        )
                    else:
                        succs, valid, rank, ovf = self._expand(chunk_states)
                        valid, rank, ovf = (
                            np.array(x)
                            for x in jax.device_get((valid, rank, ovf))
                        )
                    valid[nb:] = False
                    if np.any(valid & ovf):
                        raise CapacityOverflow(
                            "message-slot overflow: re-run with a larger msg_slots",
                            what=("msg",), bits=1,
                        )
                    jobs_flat = np.repeat(jrows_p, model.A)
                    if K:
                        # per-job composite bincount: job * (K+1) + rank,
                        # with invalid lanes in each job's drop bucket
                        rk = np.where(valid, rank, K)
                        flat_rk = rk.reshape(-1)
                        cnts = np.bincount(
                            jobs_flat * (K + 1) + flat_rk,
                            minlength=J * (K + 1),
                        ).reshape(J, K + 1)
                        cov_j[:, :, 1] += cnts[:, :K]
                        hit = np.zeros((len(valid), K + 1), dtype=bool)
                        hit[np.arange(len(valid))[:, None], rk] = True
                        np.add.at(cov_j[:, :, 0], jrows_p, hit[:, :K])
                    if self._sparse:
                        en_idx = np.nonzero(valid.reshape(-1))[0]
                        rows, _extra = model.host_apply(
                            np.asarray(chunk_states), en_idx
                        )
                        fps = np.full(
                            B * model.A, U64_MAX, dtype=np.uint64
                        )
                        if len(en_idx):
                            fps[en_idx] = self._fps_rows(rows)
                    else:
                        flat = succs.reshape(-1, model.layout.W)
                        fps = np.array(
                            jax.device_get(self._fps(flat)),
                            dtype=np.uint64,
                        )
                        fps[~valid.reshape(-1)] = U64_MAX
                    cand_by_job += np.bincount(
                        jrows, weights=valid[:nb].sum(axis=1),
                        minlength=J,
                    ).astype(np.int64)
                    has_succ[off : off + nb] = valid[:nb].any(axis=1)

                    new_mask = fps != U64_MAX
                    new_mask &= ~_in_sorted(seen, fps)
                    new_mask &= ~_in_sorted(wave_fps, fps)
                    _, first_idx = np.unique(fps, return_index=True)
                    first = np.zeros(len(fps), dtype=bool)
                    first[first_idx] = True
                    new_mask &= first
                    idx = np.nonzero(new_mask)[0]
                    if K and len(idx):
                        cov_j[:, :, 2] += np.bincount(
                            jobs_flat[idx] * (K + 1) + flat_rk[idx],
                            minlength=J * (K + 1),
                        ).reshape(J, K + 1)[:, :K]
                    if len(idx):
                        if self._sparse:
                            sel = rows[np.searchsorted(en_idx, idx)]
                        else:
                            sel = np.asarray(jax.device_get(flat[idx]))
                        wave_sb.append(sel)
                        # parents carry explicit fleet-global gids: the
                        # serial engine's base_gid+offset arithmetic
                        # assumes a contiguous frontier, which per-job
                        # masking breaks
                        wave_pb.append(fgids[off + idx // model.A])
                        wave_cb.append((idx % model.A).astype(np.int32))
                        wave_jb.append(jobs_flat[idx])
                        wave_fps = np.sort(
                            np.concatenate([wave_fps, fps[idx]])
                        )

            total_j += cand_by_job
            terminal_j += np.bincount(fjobs[~has_succ], minlength=J)
            if wave_sb.n == 0:
                break
            wave_states = wave_sb.take()
            wave_parents = wave_pb.take()
            wave_cands = wave_cb.take()
            wave_jobs = wave_jb.take()
            self._parents.append(wave_parents)
            self._cands.append(wave_cands)
            with span("seen_merge"):
                seen = _merge_sorted(seen, wave_fps)
            depth += 1
            peak_rows = max(peak_rows, len(wave_states))
            new_by_job = np.bincount(wave_jobs, minlength=J)
            for j in range(J):
                if new_by_job[j]:
                    depth_j[j] = depth
                    depth_counts_j[j].append(int(new_by_job[j]))
            distinct_j += new_by_job
            wave_gids = next_gid + np.arange(len(wave_states), dtype=np.int64)
            next_gid += len(wave_states)
            self._fleet_check_invariants(
                wave_states, wave_gids, wave_jobs, depth, violation_j, active
            )
            prev_frontier = len(frontier)
            frontier, fjobs, fgids = wave_states, wave_jobs, wave_gids
            if not active.all():
                m = active[fjobs]
                frontier, fjobs, fgids = frontier[m], fjobs[m], fgids[m]
            if tel.active or verbose:
                wave_s_val = time.perf_counter() - tw
                el = time.perf_counter() - t0
                distinct = int(distinct_j.sum())
                total = int(total_j.sum())
                n_cand_total = int(cand_by_job.sum())
                # packed-fleet waves are not phase-split (the shared
                # group run is throughput-oriented): all of a wave is
                # host_s
                tel.wave(wave_row(
                    depth=depth, frontier=prev_frontier,
                    new=len(wave_states), distinct=distinct,
                    generated=n_cand_total, generated_total=total,
                    terminal=int(terminal_j.sum()), canon=(0, 0, 0),
                    overflow_bits=0, lsm_runs=1, lsm_lanes=int(len(seen)),
                    wave_s=wave_s_val, elapsed_s=el,
                    A=model.A, expand_budget_ovf=0,
                    device_s=0.0, ckpt_s=0.0, tel_s=0.0, hbm=NO_READING,
                    jobs_active=int(active.sum()),
                ))
                if verbose:
                    print(
                        f"fleet depth {depth}: frontier {len(frontier)}, "
                        f"distinct {distinct}, {int(active.sum())}/{J} "
                        f"jobs active",
                        file=sys.stderr,
                    )

        dt = time.perf_counter() - t0
        frontier_jobs = set(int(j) for j in fjobs) if len(frontier) else set()
        results: list[CheckResult] = []
        for j in range(J):
            viol = violation_j[j]
            if viol is not None:
                cause = "violation"
            elif exit_cause_global is not None and j in frontier_jobs:
                cause = exit_cause_global
            else:
                cause = "exhausted"
            exhausted_j = cause == "exhausted"
            results.append(CheckResult(
                distinct=int(distinct_j[j]),
                total=int(total_j[j]),
                depth=int(depth_j[j]),
                depth_counts=depth_counts_j[j],
                violation=viol,
                terminal=int(terminal_j[j]),
                seconds=dt,  # group wall time: jobs are co-resident
                states_per_sec=int(distinct_j[j]) / dt if dt > 0 else 0.0,
                exhausted=exhausted_j,
                trace=self.reconstruct_trace(viol) if viol else None,
                metrics=None,
                coverage=[[int(x) for x in row] for row in cov_j[j]]
                if K else None,
                exit_cause=cause,
            ))

        if tel.active:
            tel.coverage(
                self._coverage_fields(
                    depth, cov_j.sum(axis=0), len(seen),
                    [int(x) for x in np.sum(
                        [np.pad(np.asarray(dc), (0, depth + 1 - len(dc)))
                         for dc in depth_counts_j], axis=0)],
                ),
                final=True,
            )
        first_viol = next((v for v in violation_j if v is not None), None)
        # what the group loaded into the process (obs/compiles.py); the
        # per-job summaries below repeat it, the jobs being co-resident
        stats_run = COMPILES.run_stats(comp_run)
        journal_rows = int(next_gid - len(self._init_distinct))
        tel.close_run(summary_fields(
            self, "host",
            exit_cause="violation" if first_viol is not None
            else (exit_cause_global or "exhausted"),
            violation=first_viol.invariant if first_viol else None,
            distinct=int(distinct_j.sum()), total=int(total_j.sum()),
            depth=depth, terminal=int(terminal_j.sum()), seconds=dt,
            exhausted=all(r.exhausted for r in results),
            peak_frontier_cap=int(max(
                max(dc) for dc in depth_counts_j)),
            peak_journal_cap=journal_rows,
            seen_lanes=int(len(seen)), canon_dup_rate=0.0,
            stats=stats_run, frontier_peak_rows=peak_rows,
            restart_fired=restart_fired(self, cov_j),
            canon_tier3_local=0, canon_tier3_full=0, fleet_jobs=J,
        ))
        # per-job synthesized runs: one manifest/coverage/summary triple
        # per job so obs_report and the schema checker see per-job
        # digests in the one multiplexed stream
        if tel.active:
            man = self._telemetry_manifest()
            for j, (name, r) in enumerate(zip(names, results)):
                tel.open_run({**man, "job": name})
                tel.coverage(
                    {
                        **self._coverage_fields(
                            r.depth, cov_j[j], len(seen), r.depth_counts
                        ),
                        "job": name,
                    },
                    final=True,
                )
                tel.close_run(summary_fields(
                    self, "host",
                    exit_cause=r.exit_cause,
                    violation=r.violation.invariant
                    if r.violation else None,
                    distinct=r.distinct, total=r.total, depth=r.depth,
                    terminal=r.terminal, seconds=dt,
                    exhausted=r.exhausted,
                    peak_frontier_cap=int(max(r.depth_counts)),
                    peak_journal_cap=journal_rows,
                    seen_lanes=int(len(seen)), canon_dup_rate=0.0,
                    stats=stats_run,
                    frontier_peak_rows=max(r.depth_counts[1:], default=0),
                    restart_fired=restart_fired(self, cov_j[j]),
                    canon_tier3_local=0, canon_tier3_full=0, job=name,
                ))
        return results

    def _fleet_check_invariants(
        self, states, gids, jobs, depth, violation_j, active
    ) -> None:
        """Per-job first violation of a shared wave: for each still-
        active job, the first invariant (in declaration order) with a
        bad row, and within it the first row in exploration order —
        exactly serial ``_check_invariants`` restricted to the job's
        rows. Deactivates violated jobs in place."""
        n = len(states)
        if n == 0:
            return
        m = 1 << (n - 1).bit_length()
        padded = states
        if m > n:
            padded = np.concatenate(
                [states, np.repeat(states[:1], m - n, axis=0)], axis=0
            )
        for name in self.invariants:
            ok = np.asarray(
                jax.device_get(self.model.invariants[name](padded))
            )[:n]
            bad = ~ok
            if not bad.any():
                continue
            for j in np.unique(jobs[bad]):
                j = int(j)
                if violation_j[j] is None and active[j]:
                    r = int(np.nonzero(bad & (jobs == j))[0][0])
                    violation_j[j] = Violation(
                        invariant=name, global_id=int(gids[r]), depth=depth
                    )
                    active[j] = False

    def _fps_rows(self, rows: np.ndarray) -> np.ndarray:
        """Canonical fingerprints of a compact [n, W] row block, padded
        to the next power of two so the jitted canon sees a log-bounded
        signature set instead of one per distinct worklist length."""
        n = len(rows)
        m = 1
        while m < n:
            m <<= 1
        if m > n:
            rows = np.concatenate(
                [rows, np.repeat(rows[-1:], m - n, axis=0)]
            )
        fps = np.asarray(
            jax.device_get(self._fps(rows)), dtype=np.uint64
        )
        return fps[:n]

    def _coverage_fields(self, depth, cov, seen_len, depth_counts) -> dict:
        """Coverage-event payload (events.COVERAGE_KEYS). The host engine
        keeps one flat sorted seen array (plus the in-wave probe set), so
        the dedup-structure gauges are trivial."""
        return {
            "depth": depth,
            "actions": [[int(x) for x in row] for row in cov],
            "actions_total": self.n_actions,
            "actions_fired": int(np.count_nonzero(cov[:, 1]))
            if self.n_actions else 0,
            "seen_lanes": [int(seen_len)],
            "seen_real": int(seen_len),
            "probe_runs": 2,  # global seen + current-wave fingerprints
            "frontier_hist": [int(x) for x in depth_counts],
        }

    def grow_for_overflow(self, bits: int) -> dict | None:
        """Supervisor growth policy. The host engine's buffers are
        unbounded numpy arrays, so every recoverable overflow maps to
        the empty override dict (rebuild identically, resume); only the
        msg-slots bit — model shape, not engine capacity — is fatal."""
        return None if int(bits) & 1 else {}

    def _rebuild(self, overrides: dict) -> "BFSChecker":
        """A fresh engine with this one's constructor kwargs plus
        ``overrides`` (the supervisor's growth dicts)."""
        return type(self)(**{**self._ctor_kw, **overrides})

    def _save_checkpoint(
        self, path, frontier, seen, distinct, total, terminal, depth,
        base_gid, next_gid, depth_counts, cov,
    ):
        """Wave-boundary snapshot via the crash-safe writer
        (resilience/ckpt.py: tmp + fsync + rename, content hash,
        generation rotation). The journal is flattened to two arrays;
        resume reloads it as a single segment — _journal_lookup walks
        segments, so a one-element list is equivalent."""
        parents = (
            np.concatenate(self._parents)
            if self._parents else np.zeros(0, np.int64)
        )
        cands = (
            np.concatenate(self._cands)
            if self._cands else np.zeros(0, np.int32)
        )
        rckpt.save_npz(
            path,
            dict(
                version=1,
                spec=self._ckpt_ident(),
                frontier=np.asarray(frontier, dtype=np.int32),
                seen=np.asarray(seen, dtype=np.uint64),
                parents=parents.astype(np.int64),
                cands=cands.astype(np.int32),
                distinct=distinct,
                total=total,
                terminal=terminal,
                depth=depth,
                base_gid=base_gid,
                next_gid=next_gid,
                depth_counts=np.asarray(depth_counts, dtype=np.int64),
                coverage=np.asarray(cov, dtype=np.int64),
            ),
            keep=getattr(self, "_ckpt_keep", rckpt.DEFAULT_KEEP),
            chaos=getattr(self, "_chaos", None),
        )

    def _ckpt_ident(self) -> str:
        """Same identity grammar as the device engines (what it must
        match is in ``DeviceBFS._ckpt_ident``; the formula part is
        ``engine.canon_ident``)."""
        return (
            f"host/{self.model.name}/{self.model.p}/W={self.model.layout.W}"
            f"/{canon_ident(self.canon)}"
            f"/inv={','.join(self.invariants)}"
        )

    def _telemetry_manifest(self) -> dict:
        """Run-provenance fields of the telemetry manifest event. The
        host engine's arrays are unbounded python/numpy buffers, so the
        capacity fields are 0 (= not capacity-limited)."""
        return manifest_fields(self, "host", jax.devices()[0])

    def _check_invariants(self, states: np.ndarray, base_gid: int, depth: int):
        """Batched invariant evaluation; returns the first (in exploration
        order) violation, matching TLC's report-first-found behavior.

        Wave sizes vary every depth, so the batch is padded to the next
        power of two: jit caches per shape, and without bucketing every
        wave recompiles the invariant kernels (a real cost on TPU)."""
        n = len(states)
        if n == 0:
            return None
        m = 1 << (n - 1).bit_length()
        if m > n:  # pad with copies of a real state; slice them off below
            states = np.concatenate(
                [states, np.repeat(states[:1], m - n, axis=0)], axis=0
            )
        for name in self.invariants:
            ok = np.asarray(jax.device_get(self.model.invariants[name](states)))
            bad = np.nonzero(~ok[:n])[0]
            if len(bad):
                return Violation(invariant=name, global_id=base_gid + int(bad[0]), depth=depth)
        return None

    # ---------------- trace reconstruction ----------------

    def _journal_lookup(self, gid: int) -> tuple[int, int]:
        """(parent gid, candidate id) of a non-initial distinct state."""
        off = gid - len(self._init_distinct)
        for parents, cands in zip(self._parents, self._cands):
            if off < len(parents):
                return int(parents[off]), int(cands[off])
            off -= len(parents)
        raise KeyError(gid)

    def reconstruct_trace(self, violation: Violation) -> list[tuple[str, dict]]:
        """Replay the action chain from Init to the violating state.

        Mirrors TLC's predecessor-chain trace reconstruction (SURVEY.md
        §1.2): walk parent pointers to the root, then re-apply the recorded
        candidate actions via the expansion kernel."""
        model = self.model
        n0 = len(self._init_distinct)
        chain: list[tuple[int, int]] = []  # (parent, cand) from violation upward
        gid = violation.global_id
        while gid >= n0:
            parent, cand = self._journal_lookup(gid)
            chain.append((parent, cand))
            gid = parent
        chain.reverse()
        state = self._init_distinct[gid]
        out = [("Initial predicate", model.decode(state))]
        for _parent, cand in chain:
            succs, valid, rank, _ovf = jax.device_get(
                self._expand(np.repeat(state[None, :], self.chunk, axis=0))
            )
            assert valid[0, cand], "journalled candidate not enabled on replay"
            state = np.asarray(succs[0, cand])
            out.append(
                (self.model.action_label(int(rank[0, cand]), cand), model.decode(state))
            )
        return out
