"""Device-resident BFS — the fast path of the TPU checker.

Same exploration semantics as checker/bfs.py (the host-dedup v1 driver):
identical distinct sets, gid numbering, first-occurrence tie-breaking and
violation reporting — but the whole hot loop lives in HBM. Per wave the
host transfers only a handful of scalars; states never round-trip.

Pipeline per chunk (one jitted program, all device):
  1. expand `chunk` frontier states (vmap over the per-action kernels)
  2. compact the valid successor lanes (typically <20% of chunk*A) so
     canonicalization/hashing only runs on real candidates
  3. canonical fingerprints (VIEW + SYMMETRY, ops/symmetry.py)
  4. dedup: one merged sort of what the seen run holds, what the wave
     has written of its new fingerprints so far and the chunk's
     fingerprints gives membership and first-occurrence within the
     chunk at once (checker/util.py first_new)
  5. compact survivors to a dense prefix block and APPEND it at the
     running cursor of the device next-frontier buffer — and their
     (parent gid, candidate) rows at the journal cursor — with one
     dynamic_update_slice each (contiguous writes; the round-6 emit
     redesign retired the full-capacity scatters this step used to do)
  6. evaluate invariants on the compacted candidates, folding the first
     violating gid per invariant into a device accumulator
  7. append the chunk's new fingerprints at the wave's running count
     of the wave's fingerprint buffer, as the rows are in step 5

The seen-set is ONE SORTED RUN between waves and, inside a wave, that
run and an APPEND BUFFER: a u64 buffer of the frontier's capacity,
U64_MAX from the wave's start, to which every chunk-step appends its
new fingerprints at the wave's running count, so its real lanes are a
dense prefix of known length (not sorted: nothing needs it sorted). At
the wave's end one sort-concat (measured faster than scatter-merges on
this TPU, see the note in _st_finish) folds the buffer into the seen
run. Membership is by merging, not searching: on the v5e every step of
a searchsorted is a serial gather, 467.5 us for 65,536 queries whatever
they hold, and four runs of 17-19 steps were 92 ms of a 152 ms
chunk-step, where a 720,896-lane 2-key sort takes 1.187 ms (the
recorded trace benchmark/testdata/scoped_v5e, PR 24). So a chunk-step
sorts its fingerprints together with the seen run and the buffer, and
the sort costs what the two hold, not what they could: the wave's count
(stats lane 0) and the seen run's (``_seen_real``, a scalar argument of
the wave program like ``fcount``) choose among a few static operands.
Against a seen run at the sort's floor (2^18 lanes: nothing to cut)
that is the run whole and the smallest of a few prefixes of the buffer
that holds the wave's count; against a longer one, the smallest rung
(util.merge_rungs: 1 : 1.5 : 2 : 3 : 4 ... from the floor) that holds
both counts together, the run's front with the buffer's lanes laid
into its padding. A seen run is binary-searched only by a chunk-step
whose content is past the last rung short enough to merge (64 lanes a
query), at a cost of O(VC log) that is INDEPENDENT of the total state
count — the round-3 design re-sorted an FCAP-lane buffer per chunk and
SCAP+FCAP lanes per wave, which dominated small and deep runs alike
(round-3 verdict Weak #2, Next #4).

This replaces TLC's shared fingerprint set + BFS queue (SURVEY.md §3.1
hot loop); `-deadlock` semantics are preserved (terminal states counted,
not errors, reference README.md:7).
"""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..obs import (
    COMPILES, MemWatch, NULL_TELEMETRY, setup_phase, stage,
    traced_run,
)
from ..ops.hashing import U64_MAX, sort_u64
from ..ops.symmetry import Canonicalizer, canon_chunk
from ..resilience import ckpt as rckpt
from ..resilience.errors import CapacityOverflow
from .bfs import CheckResult, Violation
from .engine import (
    FleetQueue, canon_ident, compact_chunk, expand_chunk, loop_exit,
    manifest_fields, phase_clocks, rank_key_bits, resume_events,
    run_stats, summary_fields, wave_row,
)
from .lsm import pow2_at_least
from .util import (
    GROWTH, HEADROOM, I32_MAX, SORT_FLOOR_LANES, dedup_plan, emit_append,
    first_new, jit_with_donation, merge_rungs, next_cap, rank_counts,
    rank_onehot, wave_prefix_sizes,
)


class DeviceBFS(FleetQueue):
    """Single-device BFS with device-resident frontier/seen-runs/journal.

    Capacities are static (XLA shapes). The frontier/journal GROW between
    waves (retracing the chunk program); the seen-set grows by LSM level
    creation (also a retrace, log-many times per run). Overflow flags
    remain a hard backstop that aborts rather than dropping states.
      frontier_cap   per-wave distinct states (frontier buffer rows)
      seen_cap       initial seen-set lane budget (sizes the starting
                     LSM levels; capacity bound is max_seen_cap)
      journal_cap    total distinct states beyond Init (trace journal)
      valid_per_state  compaction budget: avg valid successors per state
                       (Raft-family specs average ~5 of A~53; 16 is
                       generous, overflow-checked)
      valid_per_group  apply budget for guard-first sparse expansion:
                       per-group cap on enabled candidates per state
                       (chunk-aggregate; dict maps group name -> cap,
                       fractions legal). None = loose bound, which is
                       overflow-impossible but pays for every slot of
                       wide groups; tune from the coverage table /
                       enabled_density gauge. Ignored for models
                       without the sparse expand contract.

    Checkpoint/resume (SURVEY.md §5.4; TLC has it built in): pass
    checkpoint_path (+ checkpoint_every_s) to run(), and resume= to pick
    a run back up from the saved seen-set/frontier/journal.
    """

    GROWTH = GROWTH
    HEADROOM = HEADROOM
    # a seen run of at most this many lanes is sorted whole (util.py)
    SORT_FLOOR = SORT_FLOOR_LANES

    # overflow-bit vocabulary (mirrors the in-program stats lane); the
    # seen-set has no in-program bit — its host-side guard raises with
    # this synthetic one so the supervisor's growth policy can key on it
    OVF_NAMES = ((1, "msg"), (2, "valid"), (4, "frontier"), (8, "journal"))
    SEEN_OVF_BIT = 16

    # the in-program stats vector, i64[N_STATS]: [wave new count, journal
    # count, cumulative generated, cumulative terminal, overflow bits,
    # then cumulative canon counts: in-chunk duplicates, tier-3 local
    # lanes, tier-3 full lanes; then the dedup stage's two counts, each
    # summed over the wave's chunk-steps: the lanes its merged sort
    # sorted and the query lanes it searched an occupied run with; the
    # successor rows the wave's apply passes built
    # (SparseExpandMixin.sparse_apply's count); and last the dedup
    # stage's third count, the chunk-steps that searched].
    # STATS_KEEP is what a wave's start keeps of it (the wave-new,
    # overflow, dedup and built-rows lanes reset in-program).
    N_STATS = 12
    STATS_KEEP = (0, 1, 1, 1, 0, 1, 1, 1, 0, 0, 0, 0)

    # Donation contract for the wave program: argument indices of
    # the capacity-shaped loop carries updated in place every dispatch
    # (next_buf, jparent, jcand, viol, stats, cov). The frontier
    # (argnum 0) is deliberately NOT donated — the host swaps it with
    # next_buf between waves. analysis/donation.py verifies the lowered
    # program aliases exactly these, so an edit that drops one is named
    # before it costs a per-wave buffer copy.
    WAVE_DONATE = (1, 2, 3, 4, 5, 6)

    @setup_phase("engine")
    def __init__(
        self,
        model,
        invariants: tuple[str, ...] = (),
        symmetry: bool = True,
        chunk: int = 1024,
        frontier_cap: int = 1 << 18,
        seen_cap: int = 1 << 22,
        journal_cap: int = 1 << 22,
        valid_per_state: int = 16,
        valid_per_group: float | dict | None = None,
        check_deadlock: bool = False,
        max_frontier_cap: int = 1 << 22,
        max_seen_cap: int = 1 << 25,
        max_journal_cap: int = 1 << 25,
        fingerprint_seed: int = 0,
    ):
        # constructor kwargs, for _rebuild (supervisor growth overrides)
        self._ctor_kw = {k: v for k, v in locals().items() if k != "self"}
        self.model = model
        self.invariants = tuple(invariants)
        self.chunk = chunk
        self.check_deadlock = check_deadlock
        self.A = model.A
        self.W = model.layout.W
        # per-action coverage width: one row per Next-disjunct rank
        # (the model's ACTION_NAMES order); 0 disables accumulation for
        # models predating the rank/name contract
        self.n_actions = len(getattr(model, "ACTION_NAMES", ()))
        self.FCAP = frontier_cap
        self.JCAP = journal_cap
        self.MAX_FCAP = max(max_frontier_cap, frontier_cap)
        self.MAX_SCAP = max(max_seen_cap, seen_cap)
        self.MAX_JCAP = max(max_journal_cap, journal_cap)
        self.VC = min(chunk * self.A, chunk * valid_per_state)
        rank_key_bits(chunk, self.A, self.n_actions)  # the key fits, or raise
        # guard-first sparse expansion (SparseExpandMixin models): cheap
        # guards over the dense [chunk, A] grid, then a vmapped apply
        # over a static per-group budget plan instead of materializing
        # all chunk*A successor rows. valid_per_group tunes the plan
        # (per-state units, chunk-aggregate; dict maps group name ->
        # cap); None keeps the loose overflow-impossible bound. Legacy
        # / custom models without the mixin keep the dense path.
        self._sparse = hasattr(model, "sparse_apply")
        self.valid_per_group = valid_per_group
        self._plan = (
            model.sparse_plan(chunk, self.VC, valid_per_group)
            if self._sparse
            else None
        )
        assert chunk <= frontier_cap
        # the per-chunk dynamic_slice would clamp an out-of-bounds start and
        # silently re-expand earlier rows (while `live` still used the
        # unclamped cursor, skipping tail states); requiring divisibility
        # keeps every slice in bounds
        assert frontier_cap % chunk == 0, "frontier_cap must be a multiple of chunk"
        # seen-set geometry (round 5): ONE device-resident sorted run,
        # sized from a small pow2 ladder and merged with the wave's
        # fingerprint buffer ON DEVICE once per wave. Every extra
        # multi-million-lane run is one more searchsorted per CHUNK
        # (the old binary-counter LSM probed up to 3 on deep waves),
        # and a host-side repack moves the whole set over PCIe and
        # back; the single-run design probes once and never leaves
        # HBM. The (size -> size) merge signatures are few and finite
        # (signature_inventory).
        self.R0 = pow2_at_least(self.VC)
        self.SCAP = self.MAX_SCAP  # capacity bound (kept for callers)
        self.TOPSZ = pow2_at_least(self.MAX_SCAP)
        sizes = []
        s = min(max(self.R0, 1 << 18), self.TOPSZ)
        while s < self.TOPSZ:
            sizes.append(s)
            s <<= 2
        sizes.append(self.TOPSZ)
        self._seen_sizes = sizes
        self._seen = None  # device u64 [size], sorted, U64_MAX-padded
        self._seen_real = 0
        self._merge_cache: dict = {}
        self.canon = Canonicalizer.for_model(
            model, symmetry=symmetry, seed=fingerprint_seed
        )
        # donated: next_buf, jparent, jcand, viol, stats, cov
        # (seen read-only; the donation set is a class attribute so the
        # static donation auditor — analysis/donation.py — can verify
        # the lowered aliasing against CARRY_NAMES independently)
        self._wave_fn = jax.jit(
            self._wave_step, donate_argnums=self.WAVE_DONATE
        )
        self._flag_true = jnp.asarray(True)
        self._flag_false = jnp.asarray(False)
        self._occ_one = jnp.ones((1,), bool)
        self._init_distinct: np.ndarray | None = None
        self._jparent = None
        self._jcand = None
        self._jcount = 0

    # ---------------- seen-set adapters ----------------

    def _flag(self, v: bool):
        return self._flag_true if v else self._flag_false

    def _seen_size_for(self, n: int) -> int:
        for s in self._seen_sizes:
            if n <= s:
                return s
        raise OverflowError(
            f"seen-set of {n} exceeds the {self.TOPSZ}-lane capacity; "
            "raise max_seen_cap"
        )

    def _seed_seen(self, sorted_fps: np.ndarray) -> None:
        """Upload a sorted host fingerprint array as the seen run,
        host-padded to the ladder size (device pads would compile)."""
        n = len(sorted_fps)
        size = self._seen_size_for(n)
        host = np.full((size,), np.uint64(U64_MAX))
        host[:n] = sorted_fps
        self._seen = jnp.asarray(host)
        self._seen_real = n

    def _merge_seen(self, wave_new, new_real: int) -> None:
        """seen <- sort(concat(seen, wave_new)) resized to EXACTLY the
        ladder size `target` on device. Truncation only drops U64_MAX
        padding (new_real <= target by construction); when the concat is
        SHORTER than target the result is padded back up with U64_MAX —
        appending the sort key's own padding value keeps the run sorted,
        and _lsm_export / probe_sorted are padding-blind. Without the
        pad-up, a merge whose target outgrew the concat total left a
        non-ladder-size seen run, and the NEXT wave retraced + recompiled
        the whole wave program at a shape off the seen ladder: that one
        mid-run compile was round 5's unexplained final-wave cliff at
        depth 32 (most of that wave's wall time)."""
        target = self._seen_size_for(new_real)
        key = (self._seen.shape[0], (wave_new.shape[0],), target)
        fn = self._merge_cache.get(key)
        if fn is None:
            fn = self._make_seen_merge(key)
            self._merge_cache[key] = fn
        self._seen = fn(self._seen, wave_new)
        self._seen_real = new_real

    @staticmethod
    def _seen_merge_spec(key):
        """(body, donate_argnums) of the merge program for one
        (seen size, (wave buffer lanes,), target) signature — the single
        source both the production wrapper below and the static donation
        / signature auditors build from. The old seen run is donated
        where the output can alias it (size == target: the steady-state
        merge sorts into the dead run's HBM instead of holding old + new
        live); a merge that steps the seen ladder up (size < target)
        cannot alias it, and the wave's buffer is never donated (it is
        the output's shape only by an accident of capacities). The
        pad-up branch keeps the output EXACTLY ``target`` lanes even when the concat
        total falls short — the signature-closure invariant
        (_merge_seen) depends on it."""
        size, lshapes, target = key
        total = size + sum(lshapes)

        @stage("seen_merge")
        def merge(s, *lv):
            out = sort_u64(jnp.concatenate([s, *lv]))[:target]
            if total < target:
                out = jnp.concatenate(
                    [out, jnp.full((target - total,), U64_MAX, jnp.uint64)]
                )
            return out

        return merge, ((0,) if size == target else ())

    def _make_seen_merge(self, key):
        """Build the merge program for one signature and (via
        jit_with_donation) compile and run it once on throwaway runs."""
        size, lshapes, _target = key
        merge, donate = self._seen_merge_spec(key)
        return jit_with_donation(
            merge,
            donate,
            f"seen_merge{key}",
            lambda: tuple(
                jnp.full((n,), U64_MAX, jnp.uint64) for n in (size, *lshapes)
            ),
        )

    def _lsm_export(self) -> np.ndarray:
        """All real fingerprints, sorted (host array; checkpoint format)."""
        arr = np.asarray(jax.device_get(self._seen))
        return arr[arr != np.uint64(U64_MAX)]

    # ---------------- device programs ----------------
    #
    # The chunk pipeline is factored into four stage methods
    # (_st_expand -> _st_canon -> _st_dedup -> _st_finish) that
    # _chunk_step composes and _wave_step loops over: one traced
    # program, the only way this engine runs a wave. Each stage method
    # carries its obs.stage scope, so a profile names its ops by stage
    # (obs/trace.py): that is how a stage is timed.

    @stage("expand")
    def _st_expand(self, frontier, cursor, fcount):
        """Stages 1-2: guard/dense expand + compaction (+ the budgeted
        sparse apply). Returns the compacted successor block and every
        lane the later stages consume."""
        batch, succs, valid, rank, n_gen, terminal, expand_ovf = expand_chunk(
            self.model, self._sparse, frontier, cursor, fcount, self.chunk)
        flatc, sel, selv, sel_rank, compact_ovf, rows_built = compact_chunk(
            self.model, self._plan, batch, succs, valid, rank,
            self.n_actions, n_gen, self.VC)
        return (flatc, sel, selv, sel_rank, valid, rank, n_gen, terminal,
                expand_ovf, compact_ovf, rows_built)

    @stage("canon")
    def _st_canon(self, flatc, selv):
        """Stage 3: canonical fingerprints on compacted lanes only, one
        tiered canon per distinct raw view of the chunk, on its first
        lane (duplicate successors skip it; invalid lanes and in-chunk
        duplicates of a lower lane come back masked to U64_MAX).
        ``canon_n`` is i32[3]: the chunk's in-chunk duplicate lanes and
        the lanes its canon routed to tier 3's local and full buckets."""
        return canon_chunk(self.canon, flatc, selv)

    @stage("dedup")
    def _st_dedup(self, fps, occ, wave_new, ncount, seen_real, *runs):
        """Stage 4: ``fps`` is the canon stage's, invalid lanes and
        in-chunk duplicates of a lower lane masked to U64_MAX, which is
        never new. new = not in the seen run, not among the ``ncount``
        fingerprints earlier chunks of this wave appended to
        ``wave_new``, and first occurrence in the chunk (lowest lane:
        two raw views of one canonical class both reach this stage),
        by one merged sort of the chunk with what the seen run and
        ``wave_new`` hold (util.first_new): against a seen run at the
        sort's floor, the run whole and the smallest static prefix of
        ``wave_new`` that holds ``ncount`` lanes; against a longer one,
        the smallest rung (``_rungs``) that holds its ``seen_real``
        fingerprints and the wave's ``ncount`` together, and the binary
        search, under ``occ``, only where no rung does. A fingerprint
        chunk k appended is a run lane for chunk k + 1, so cross-chunk
        in-wave dedup falls out of the same lookup. Returns (new,
        i32[3]: the lanes that sort sorted, the query lanes searched
        against an occupied run, and 1 if the step searched at all)."""
        new, lanes, queries = first_new(
            fps, occ, runs, wave=(wave_new, ncount, self._wave_prefix()),
            real=(seen_real, self._rungs(runs[0].shape[0])))
        return new, jnp.stack(
            [lanes, queries, (queries > 0).astype(jnp.int32)])

    @stage("emit")
    def _st_finish(
        self, next_buf, jparent, jcand, viol, stats, cov, wave_new,
        flatc, fps, sel, sel_rank, valid, rank, new, n_gen, terminal,
        expand_ovf, compact_ovf, canon_n, dedup_n, rows_built, cursor,
        base_gid,
    ):
        """Stages 4b-6: per-action coverage, the cursor-append emit of
        rows, journal and new fingerprints, invariants on the new states
        and the stats fold. Returns the updated carries."""
        model = self.model
        A, W, VC = self.A, self.W, self.VC
        FCAP, JCAP = self.FCAP, self.JCAP
        n_new = jnp.sum(new)

        # 4b. per-action coverage, by compare and sum (util.rank_counts:
        # no scatter-add, no drop bucket) over the rank/valid lanes
        # _expand1 already returns; rank is -1 or under a false mask
        # wherever a lane does not count. enabled counts states where
        # the disjunct's guard held; fired counts valid candidate lanes;
        # new-distinct counts first-writer lanes by `sel_rank`, the rank
        # of each compacted lane, which rode in the compaction's sort
        # key (a new lane is a valid one). A chunk-step counts at most
        # C * A lanes in int32 and widens once into the cumulative i64
        # `cov`.
        K = self.n_actions
        if K:
            with jax.named_scope("coverage"):
                en = rank_onehot(rank, valid, K)  # [C, A, K]
                enabled_k = jnp.sum(
                    jnp.any(en, axis=1), axis=0, dtype=jnp.int32)
                # the one-hot again: XLA keeps one compare for both
                fired_k = rank_counts(rank, valid, K)
                new_k = rank_counts(sel_rank, new, K)
                cov = cov + jnp.stack(
                    [enabled_k, fired_k, new_k], axis=1
                ).astype(jnp.int64)

        # 5. emit: compact survivors to a dense prefix of a [VC, W]
        # block, then ONE dynamic_update_slice per buffer appends the
        # block at the running cursor. What the block needs of a lane
        # besides its row comes out of ONE sort of one int32 key: `esel`,
        # the survivors' lanes in lane order (util.dense_prefix_sel's
        # key), with `sel` as its payload, so `ssel[j]` is the j-th
        # survivor's `sel`, from which the journal's parent and
        # candidate follow by arithmetic. The stage gathers nothing but
        # the rows and scatters nothing: a 1-D gather or scatter by a
        # traced index is a serial pass on this chip, 7.2 and 4.6 ns a
        # lane, where this sort is 0.8 to 1.0 (and two sorts of one
        # operand 1.3 to 1.5: scripts/emit_micro.py --journal; PERF.md
        # section 6, PR 52). The append is a slice update because the
        # destinations ncount + (cumsum(new) - 1) are contiguous and
        # XLA cannot prove it: an `.at[bdst].set()` lowers to a general
        # scatter over the full (FCAP, W)/(JCAP,) buffers. Rows [FCAP,
        # FCAP+VC) / [JCAP, JCAP+VC) are the drop region that replaces
        # such a scatter's drop row; overflow semantics are
        # bit-identical (emit_append).
        # A scope of its own, `emit/append`, beside `emit/coverage` and
        # `emit/invariants`: what a trace charges to writing into the
        # frontier, the journal and the wave's fingerprint buffer.
        with jax.named_scope("append"):
            ncount = stats[0].astype(jnp.int32)
            jcount = stats[1].astype(jnp.int32)
            npos = (jnp.cumsum(new) - 1).astype(jnp.int32)
            lane = jnp.arange(VC, dtype=jnp.int32)
            esel, ssel = lax.sort(
                (jnp.where(new, lane, VC), sel), num_keys=1)
            blk = jnp.concatenate(
                [flatc, jnp.zeros((1, W), jnp.int32)], axis=0
            )[esel]
            live = lane < n_new
            jp_blk = jnp.where(live, base_gid + cursor + ssel // A, 0)
            jc_blk = jnp.where(live, ssel % A, 0)
            next_buf, frontier_ovf = emit_append(
                next_buf, blk, ncount, n_new, FCAP)
            jparent, journal_ovf = emit_append(
                jparent, jp_blk, jcount, n_new, JCAP)
            jcand, _ = emit_append(jcand, jc_blk, jcount, n_new, JCAP)
            # NOTE: a searchsorted+scatter linear merge looks
            # asymptotically better than sort-concat for merging sorted
            # sets, but arbitrary-index scatters serialize on this
            # hardware while XLA's bitonic sort is fast
            # (scripts/emit_micro.py reproduces the scatter penalty on
            # the current backend). All seen merges therefore use
            # sort-concat (as 2-key u32 sorts — hashing.py), and the
            # per-chunk sort below, VC lanes, is the compaction: the
            # chunk's new fingerprints first and padding after, a dense
            # block to append at the wave's count like the rows above.
            # Its padding tail lands on padding, and the next append
            # overwrites it.
            new_run = sort_u64(jnp.where(new, fps, U64_MAX))
            wave_new, _ = emit_append(wave_new, new_run, ncount, n_new, FCAP)

        # 6. invariants on the compacted candidates; fold first-bad gid
        jidx = jnp.where(new, jcount + npos, I32_MAX)
        with jax.named_scope("invariants"):
            for k, name in enumerate(self.invariants):
                ok = model.invariants[name](flatc)
                bad = new & ~ok
                viol = viol.at[k].min(
                    jnp.min(jnp.where(bad, jidx, I32_MAX)))

        ovf_bits = (
            expand_ovf.astype(jnp.int64)
            + 2 * compact_ovf.astype(jnp.int64)
            + 4 * frontier_ovf.astype(jnp.int64)
            + 8 * journal_ovf.astype(jnp.int64)
        )
        stats = jnp.stack(
            [
                stats[0] + n_new,
                stats[1] + n_new,
                stats[2] + n_gen,
                stats[3] + terminal,
                stats[4] | ovf_bits,
                *(stats[5:8] + canon_n),
                *(stats[8:10] + dedup_n[:2]),
                stats[10] + rows_built,
                stats[11] + dedup_n[2],
            ]
        )
        return next_buf, jparent, jcand, viol, stats, cov, wave_new

    def _chunk_step(
        self, frontier, next_buf, jparent, jcand, viol, stats, cov,
        wave_new, cursor, fcount, base_gid, seen_real, occ, *runs,
    ):
        """One chunk of the current wave (the four stage methods above,
        composed — one traced program). stats is the i64[N_STATS]
        vector the class comment lays out; cov is the i64[n_actions, 3]
        per-action coverage accumulator — [enabled, fired, new-distinct]
        per Next-disjunct rank, cumulative over the WHOLE run (never
        reset, so host snapshots are monotone); wave_new is the wave's
        fingerprint buffer, u64[FCAP + VC], whose first stats[0] lanes
        are the fingerprints the wave's earlier chunks found new and
        whose rest is U64_MAX; seen_real is the i32 count of the seen
        run's fingerprints, its first lanes; occ is bool[n_runs] (the
        binary search of an unoccupied run is skipped via lax.cond; a
        merged run is sorted either way). Returns the carries, wave_new with the
        chunk's new fingerprints appended."""
        (flatc, sel, selv, sel_rank, valid, rank, n_gen, terminal,
         expand_ovf, compact_ovf, rows_built) = self._st_expand(
             frontier, cursor, fcount)
        fps, canon_n = self._st_canon(flatc, selv)
        new, dedup_n = self._st_dedup(
            fps, occ, wave_new, stats[0].astype(jnp.int32), seen_real,
            *runs)
        if self._rungs(runs[0].shape[0]):
            # Against a run with rungs, the candidates reach the emit
            # stage only once the dedup stage has its answer. Left
            # free, the compiler pads them for the survivors' gather
            # before canon and holds that block (34 MB in pull3-full)
            # across the dedup switch; with the rungs' longer switch
            # it no longer keeps it in VMEM there, and the gather read
            # HBM at 0.39 ms a chunk-step for 0.23 (PERF.md section 6,
            # PR 49). A first-size wave program is as it was.
            flatc, new = lax.optimization_barrier((flatc, new))
        return self._st_finish(
            next_buf, jparent, jcand, viol, stats, cov, wave_new, flatc,
            fps, sel, sel_rank, valid, rank, new, n_gen, terminal,
            expand_ovf, compact_ovf, canon_n, dedup_n, rows_built, cursor,
            base_gid,
        )

    def _wave_prefix(self) -> tuple[int, ...]:
        """The prefixes of the wave's fingerprint buffer the dedup stage
        can sort (util.wave_prefix_sizes): 0, R0, 4 * R0, ... under
        FCAP, then FCAP, which holds a whole wave's new fingerprints
        (the frontier overflow bit aborts the run otherwise)."""
        return wave_prefix_sizes(self.R0, self.FCAP)

    def _rungs(self, seen_lanes: int) -> tuple[int, ...]:
        """The rungs of the dedup stage's merged sort against a seen run
        of ``seen_lanes`` lanes (util.merge_rungs): none for a run at
        the sort's floor, whose wave program is the prefix switch
        alone."""
        return merge_rungs(
            seen_lanes, self.VC, self._wave_prefix(), self.SORT_FLOOR)

    def _wave_step(
        self, frontier, next_buf, jparent, jcand, viol, stats, cov,
        fcount, base_gid, seen_real, occ, *runs,
    ):
        """One WAVE as a single dispatched program (round 5, verdict Next
        #1): a lax.while_loop drives the chunk pipeline over the frontier,
        deduplicating in-wave against the fingerprints the wave's
        earlier chunks appended to one in-program buffer (wave_new,
        U64_MAX at the wave's start; _st_dedup sorts the prefix of it
        the wave has written) — so the host dispatches ONCE per wave
        and syncs once instead of once per chunk: a 170-chunk deep wave
        is one launch and one host round-trip, and the device never
        idles between chunks waiting for the host.
        Returns (next_buf, jparent, jcand, viol, stats, cov,
        wave_new[:FCAP]); the host merges the last into the seen run."""
        C = self.chunk
        # the wave-new, overflow and sorted-lanes lanes reset in-program:
        # no host->device stats upload a wave (dispatch latency dominates
        # small configs)
        stats = stats * jnp.asarray(self.STATS_KEEP, dtype=stats.dtype)
        # rows [FCAP, FCAP + VC) are the append's drop region
        # (util.emit_append), as next_buf's are
        wave_new = jnp.full((self.FCAP + self.VC,), U64_MAX, jnp.uint64)

        def body(carry):
            k, *carries = carry
            return (k + 1, *self._chunk_step(
                frontier, *carries, k * C, fcount, base_gid, seen_real,
                occ, *runs))

        def cond(carry):
            return carry[0] * C < fcount

        out = lax.while_loop(
            cond, body,
            (jnp.int32(0), next_buf, jparent, jcand, viol, stats, cov,
             wave_new),
        )
        return (*out[1:-1], out[-1][:self.FCAP])

    # ---------------- the signatures a run can dispatch ----------------

    def signature_inventory(self):
        """The FINITE signature universe a run at the CURRENT capacities
        can dispatch, in ladder order: a ``("wave", seen_size)`` per
        seen-ladder size, each followed by the per-wave seen merges that
        size can need — ``("merge", size, (FCAP,), target)``, FCAP the
        lanes of the wave's fingerprint buffer, for every ladder target
        >= size. Nothing compiles them ahead of a run: each compiles in
        the wave that first dispatches it (the row's ``compiles``).
        analysis/signatures.py independently recomputes the reachable
        set from the geometry primitives (_seen_size_for, the wave
        buffer, the pad-up merge contract) and proves the two are equal
        — round 5's retrace-cliff class, checked symbolically.
        """
        lshapes = (self.FCAP,)
        for si, size in enumerate(self._seen_sizes):
            yield ("wave", size)
            # targets >= size only: one wave adds at most pow2(FCAP)
            # real lanes, so targets further than two ladder steps up
            # are unreachable — but naming the whole upper triangle
            # keeps the closure argument one-sided
            for target in self._seen_sizes[si:]:
                yield ("merge", size, lshapes, target)

    # ---------------- static audit surface ----------------

    def audit_programs(self):
        """Every device program this engine dispatches, as audit entries
        for the static donation auditor (analysis/donation.py):

          name     program id (``wave`` / ``seen_merge``)
          fn       a ``.lower()``-able jitted callable — the PRODUCTION
                   jit object where one exists
          args     abstract arguments for ``fn.lower(*args)``
          carries  {argnum: name} of the capacity-shaped loop carries
                   that MUST alias an output in the lowered program
          pinned   {argnum: name} of buffers that must NOT be donated
                   (the host reuses them after the dispatch)
          site     (file, line) anchor for findings
          per_wave dispatches per wave (scales the bytes-copied cost of
                   a donation miss)

        Yields entries without lowering or executing anything — tracing
        is the caller's cost, so passes choose their own coverage. The
        ``carries`` maps are written out independently of the
        ``WAVE_DONATE`` declaration on purpose: the auditor compares the
        lowered aliasing against THIS list, so dropping an argnum from a
        donate tuple (the classic regression) diverges the two.
        """
        import inspect as _inspect

        sds = jax.ShapeDtypeStruct
        W = self.W
        i32s = sds((), np.int32)
        frontier = sds((self.FCAP + self.VC, W), jnp.int32)
        next_buf = sds((self.FCAP + self.VC, W), jnp.int32)
        jparent = sds((self.JCAP + self.VC,), jnp.int32)
        jcand = sds((self.JCAP + self.VC,), jnp.int32)
        viol = sds((max(1, len(self.invariants)),), jnp.int32)
        stats = sds((self.N_STATS,), jnp.int64)
        cov = sds((self.n_actions, 3), jnp.int64)
        occ = sds((1,), jnp.bool_)
        seen = sds((self._seen_sizes[0],), jnp.uint64)
        wave_carries = {
            1: "next_buf", 2: "jparent", 3: "jcand", 4: "viol",
            5: "stats", 6: "cov",
        }

        def site(fn):
            f = _inspect.unwrap(fn)
            return (__file__, _inspect.getsourcelines(f)[1])

        yield {
            "name": "wave", "fn": self._wave_fn,
            "args": (frontier, next_buf, jparent, jcand, viol, stats,
                     cov, i32s, i32s, i32s, occ, seen),
            "carries": dict(wave_carries),
            "pinned": {0: "frontier"},
            "site": site(self._wave_step), "per_wave": 1,
        }
        # the per-wave seen merge, at the first (size, target) signature:
        # spec-built jit (production builds the same body and donation
        # through jit_with_donation)
        key = (self._seen_sizes[0], (self.FCAP,), self._seen_sizes[0])
        body, donate = self._seen_merge_spec(key)
        merge_args = tuple(
            sds((n,), jnp.uint64) for n in (key[0], *key[1])
        )
        yield {
            "name": "seen_merge",
            "fn": jax.jit(body, donate_argnums=donate),
            "args": merge_args,
            "carries": {0: "seen", 1: "wave_new"},
            "pinned": {},
            "site": site(self._seen_merge_spec), "per_wave": 1,
        }

    # ---------------- capacity growth ----------------

    _next_cap = staticmethod(next_cap)

    def _maybe_grow(self, ncount, frontier, next_buf, jparent, jcand, jcount):
        """Between waves: enlarge any buffer the next wave could outgrow.
        Frontier growth is speculative (next wave's new count is unknown;
        observed BFS wave growth is <=~2.2x on Raft.cfg and 2.8x on the
        five-server FlexibleRaft.cfg, depths 15-17: HEADROOM=3 covers
        both, the second barely);
        journal growth is exact (it grows by ncount per wave). The
        seen-set needs no growth pass — LSM levels appear on demand."""
        W = self.W
        grow_f = ncount * self.HEADROOM > self.FCAP and self.FCAP < self.MAX_FCAP
        grow_j = (
            jcount + ncount * self.HEADROOM > self.JCAP
            and self.JCAP < self.MAX_JCAP
        )
        if not (grow_f or grow_j):
            return frontier, next_buf, jparent, jcand
        # the `grow` span and the row's grow_s exist only on a wave that
        # grows; the wave program's retrace at the new shapes lands in
        # the NEXT wave's dispatch (its row's compiles/compile_s)
        with self._ph("grow"):
            if grow_f:
                new = self._next_cap(
                    ncount * self.HEADROOM, self.FCAP, self.MAX_FCAP,
                    self.GROWTH, self.chunk,
                )
                # the old buffer already carries its VC pad rows
                pad = new - self.FCAP
                frontier = jnp.concatenate(
                    [frontier, jnp.zeros((pad, W), jnp.int32)], axis=0
                )
                next_buf = jnp.zeros((new + self.VC, W), jnp.int32)
                self.FCAP = new
            if grow_j:
                new = self._next_cap(
                    jcount + ncount * self.HEADROOM, self.JCAP,
                    self.MAX_JCAP, self.GROWTH, 1,
                )
                pad = new - self.JCAP
                jparent = jnp.concatenate(
                    [jparent, jnp.zeros((pad,), jnp.int32)])
                jcand = jnp.concatenate([jcand, jnp.zeros((pad,), jnp.int32)])
                self.JCAP = new
        return frontier, next_buf, jparent, jcand

    def grow_for_overflow(self, bits: int) -> dict | None:
        """Constructor overrides that would absorb the overflow ``bits``
        of a CapacityOverflow raised by this instance — the supervisor's
        regrow-and-resume policy. Returns None when a bit has no growth
        story: msg-slots is model SHAPE (the bag width every state row
        carries), not an engine buffer, so rebuilding the engine cannot
        fix it — the model must be re-lowered with more slots."""
        bits = int(bits)
        if bits & 1:
            return None
        g: dict = {}
        if bits & 2:
            vps = max(1, -(-self.VC // self.chunk))
            g["valid_per_state"] = min(self.A, vps * 2)
            g["valid_per_group"] = None  # drop the tight budget plan
        if bits & 4:
            g["frontier_cap"] = self.FCAP * 2
            g["max_frontier_cap"] = max(self.MAX_FCAP, self.FCAP * 4)
        if bits & 8:
            g["journal_cap"] = self.JCAP * 2
            g["max_journal_cap"] = max(self.MAX_JCAP, self.JCAP * 4)
        if bits & self.SEEN_OVF_BIT:
            g["max_seen_cap"] = self.MAX_SCAP * 4
        return g

    # ---------------- host driver ----------------

    @traced_run("device")
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
        W = self.W
        t0 = time.perf_counter()
        # host spans (obs/trace.py): `init` up to the first wave, one
        # `wave` per loop iteration, `finish` after the loop; the phases
        # inside a wave are bracketed once, for the trace and the row
        ph = self._ph
        ph.top("init")
        comp_run = COMPILES.snapshot()
        exhausted = True
        exit_cause = None
        # telemetry consumes the SAME once-per-wave host snapshot the
        # loop already fetches (stats_h below), so an instrumented run
        # adds no device syncs and stays bit-identical (tests/test_obs.py)
        tel = telemetry if telemetry is not None else NULL_TELEMETRY
        # the run's device memory, by the allocator (obs/memwatch.py):
        # read here before any buffer, in init once the buffers are
        # made, at the end of every wave and at finish; no read syncs
        memwatch = MemWatch(tel, jax.devices()[:1])
        self._ckpt_keep = checkpoint_keep
        self._chaos = chaos

        init = model.init_states()
        init_fps = np.asarray(
            jax.device_get(self.canon.fingerprints(init)), dtype=np.uint64
        )
        order = np.argsort(init_fps, kind="stable")
        keep = np.ones(len(order), dtype=bool)
        sf = init_fps[order]
        dup = np.zeros(len(order), dtype=bool)
        dup[1:] = sf[1:] == sf[:-1]
        keep[order[dup]] = False
        init_d = np.asarray(init[keep])
        n0 = len(init_d)
        assert n0 <= self.FCAP, "initial states exceed frontier_cap"
        self._init_distinct = init_d

        ck_gen = 0
        ck_skipped: list[str] = []
        if resume is not None:
            # verified load with generation fallback: a truncated latest
            # file falls back to the newest intact .genN and the skipped
            # candidates surface as a ckpt_generation event below
            ck, ck_gen, ck_skipped = rckpt.load_npz(
                resume, keep=checkpoint_keep
            )
            ident = self._ckpt_ident()
            rckpt.check_spec(ck, ident, resume)
            fcount = int(ck["fcount"])
            scount = int(ck["scount"])
            jcount = int(ck["jcount"])
            # round caps up so the saved contents fit with headroom
            self.FCAP = self._next_cap(
                max(self.FCAP, fcount * self.HEADROOM),
                self.FCAP, self.MAX_FCAP, self.GROWTH, self.chunk)
            self.JCAP = self._next_cap(
                max(self.JCAP, jcount + fcount * self.HEADROOM),
                self.JCAP, self.MAX_JCAP, self.GROWTH, 1)
            seed_rows = (np.asarray(ck["frontier"]), np.asarray(ck["jparent"]),
                         np.asarray(ck["jcand"]))
            self._seed_seen(np.asarray(ck["seen"], dtype=np.uint64))
            violation = None
            distinct = int(ck["distinct"])
            total = int(ck["total"])
            terminal = int(ck["terminal"])
            depth = int(ck["depth"])
            base_gid = int(ck["base_gid"])
            gen_prev = int(ck["gen_prev"])
            depth_counts = [int(x) for x in ck["depth_counts"]]
            stats0 = np.zeros((self.N_STATS,), dtype=np.int64)
            stats0[1:4] = jcount, gen_prev, terminal
            # coverage joined the checkpoint format after version 1
            # shipped; older files resume with zeroed counters
            cov_h = (
                np.asarray(ck["coverage"], dtype=np.int64)
                if "coverage" in ck
                else np.zeros((self.n_actions, 3), np.int64)
            )
        else:
            violation = self._check_init(init_d)
            self._seed_seen(np.sort(init_fps[keep]))
            seed_rows = (init_d, np.zeros((0,), np.int32),
                         np.zeros((0,), np.int32))
            fcount = n0
            scount = n0
            distinct = n0
            total = len(init)  # pre-dedup, matching BFSChecker's seeding
            terminal = 0
            depth = 0
            base_gid = 0
            depth_counts = [n0]
            gen_prev = 0
            stats0 = np.zeros((self.N_STATS,), dtype=np.int64)
            cov_h = np.zeros((self.n_actions, 3), np.int64)

        # Buffers are allocated ON DEVICE and only the real rows upload:
        # a host-built (FCAP+VC, W) staging array is GBs of zeros to
        # build and push over PCIe per run() call at a 4M-row frontier.
        fr_h, jp_h, jc_h = seed_rows
        # rows [FCAP, FCAP+VC) / [JCAP, JCAP+VC) are the emit drop
        # region (checker/util.py emit_append)
        frontier = jnp.zeros((self.FCAP + self.VC, W), jnp.int32)
        if len(fr_h):
            frontier = lax.dynamic_update_slice(
                frontier, jnp.asarray(np.ascontiguousarray(fr_h)),
                (jnp.int32(0), jnp.int32(0)))
        next_buf = jnp.zeros((self.FCAP + self.VC, W), jnp.int32)
        jparent = jnp.zeros((self.JCAP + self.VC,), jnp.int32)
        jcand = jnp.zeros((self.JCAP + self.VC,), jnp.int32)
        if len(jp_h):
            jparent = lax.dynamic_update_slice(
                jparent, jnp.asarray(np.ascontiguousarray(jp_h)),
                (jnp.int32(0),))
            jcand = lax.dynamic_update_slice(
                jcand, jnp.asarray(np.ascontiguousarray(jc_h)),
                (jnp.int32(0),))
        viol = jnp.full((max(1, len(self.invariants)),), I32_MAX, jnp.int32)
        stats = jnp.asarray(stats0)
        cov = jnp.asarray(cov_h)  # i64[n_actions, 3], cumulative
        canon_prev = np.zeros((3,), np.int64)
        memwatch.init()

        tel.open_run(self._telemetry_manifest())
        if resume is not None:
            resume_events(tel, resume, ck_gen, ck_skipped, depth, distinct)
        metrics: list[dict] | None = [] if collect_metrics else None
        last_ckpt = time.perf_counter()

        sort_lanes_run = search_queries_run = search_steps_run = 0
        peak_rows = 0
        # the apply pass: the rows its tiles built and the rows its plan
        # budgets, a chunk-step
        rows_built_run = rows_budget_run = 0
        plan_rows = sum(self._plan) if self._sparse else 0

        while fcount and violation is None:
            exit_cause = loop_exit(
                tel, preempt, chaos, depth, checkpoint_path, max_depth,
                time_budget_s, t0)
            if exit_cause is not None:
                exhausted = False
                break
            ph.wave(self._run_id, depth + 1, fcount)
            tw = time.perf_counter()
            comp_wave = COMPILES.snapshot()
            # capacity guard: the top-level absorb truncates at TOPSZ
            # lanes, which is only sound while every real fingerprint is
            # guaranteed to fit; FCAP bounds the wave's new states
            # (conservative vs the round-3 post-wave check, but it spills
            # a resumable checkpoint before raising)
            if scount + min(self.FCAP, fcount * self.VC) > self.TOPSZ:
                if checkpoint_path is not None:
                    self._save_checkpoint(
                        checkpoint_path, frontier, jparent, jcand,
                        fcount, scount, distinct, total, terminal,
                        depth, base_gid, gen_prev, depth_counts, cov_h,
                    )
                raise CapacityOverflow(
                    "seen-set capacity overflow; raise max_seen_cap",
                    what=("seen",), bits=self.SEEN_OVF_BIT,
                    checkpoint_saved=checkpoint_path is not None,
                )
            # a wave whose new count could outgrow even the MAXIMALLY
            # grown frontier will abort mid-wave (not resumable), so
            # spill a resumable snapshot BEFORE attempting it (throttled:
            # every wave in this regime would re-export the whole seen
            # set, which can rival wave time on wide plateaus)
            if (
                checkpoint_path is not None
                and fcount * self.HEADROOM > self.MAX_FCAP
                and time.perf_counter() - last_ckpt > checkpoint_every_s / 4
            ):
                self._save_checkpoint(
                    checkpoint_path, frontier, jparent, jcand, fcount,
                    scount, distinct, total, terminal, depth, base_gid,
                    gen_prev, depth_counts, cov_h,
                )
                last_ckpt = time.perf_counter()
            # ONE dispatch per wave: the chunk loop runs device-side
            # (_wave_step) and returns the wave's new fingerprints in
            # one buffer, merged into the single seen run
            # below AFTER the overflow check (so an aborted wave leaves
            # the seen-set untouched and the run trivially resumable).
            seen_lanes = int(self._seen.shape[0])  # before this wave's merge
            with tel.wave_annotation(depth + 1):
                with ph("dispatch"):
                    out = self._wave_fn(
                        frontier, next_buf, jparent, jcand, viol,
                        stats, cov, np.int32(fcount),
                        np.int32(base_gid), np.int32(self._seen_real),
                        self._occ_one, self._seen,
                    )
                (next_buf, jparent, jcand, viol, stats, cov,
                 wave_new) = out
                # one host round-trip per wave: stats, the invariant
                # fold and the coverage block fetched together (two
                # device_gets are two syncs on small configs, where
                # per-wave latency dominates) — and telemetry rides
                # this same snapshot
                with ph("fetch"):
                    # lint: sync-ok(once-per-wave snapshot)
                    stats_h, viol_h, cov_w = jax.device_get(
                        (stats, viol, cov))
            stats_h = np.asarray(stats_h)
            viol_h = np.asarray(viol_h)
            ncount = int(stats_h[0])
            ovf_bits = int(stats_h[4])
            if chaos is not None:
                # spurious frontier-overflow injection: the wave really
                # completed, but we abort exactly as a real bit-4 would —
                # the wave-start checkpoint below is still consistent
                # because nothing (cov/seen/journal counts) was adopted
                ovf_bits = chaos.ovf_bits(ovf_bits, depth + 1, 4)
            if ovf_bits:
                saved = ""
                if checkpoint_path is not None:
                    # the aborted wave never touched the seen run (its
                    # fingerprints live in the discarded buffer), and the
                    # frontier buffer and journal[:jcount] are untouched
                    # (only next_buf and journal rows past jcount were
                    # written), so the wave-start state is exactly
                    # reconstructible and resumable (round-4 advisor #1)
                    self._save_checkpoint(
                        checkpoint_path, frontier, jparent, jcand, fcount,
                        scount, distinct, total, terminal, depth, base_gid,
                        gen_prev, depth_counts, cov_h,
                    )
                    saved = f"; wave-start checkpoint saved to {checkpoint_path}"
                raise CapacityOverflow(
                    f"device BFS capacity overflow (bits={ovf_bits:04b}: "
                    "1=msg-slots 2=valid_per_state/valid_per_group "
                    "4=frontier_cap 8=journal_cap)"
                    + saved,
                    what=tuple(
                        name for bit, name in self.OVF_NAMES
                        if ovf_bits & bit
                    ),
                    bits=ovf_bits,
                    checkpoint_saved=checkpoint_path is not None,
                )
            # the wave completed: adopt its cumulative coverage (the
            # aborted-wave path above deliberately keeps the wave-start
            # cov_h, matching the discarded buffer/journal rows)
            cov_h = np.asarray(cov_w, dtype=np.int64)
            n_gen = int(stats_h[2])
            wave_gen = n_gen - gen_prev
            total += wave_gen
            gen_prev = n_gen
            terminal = int(stats_h[3])
            if ncount == 0:
                exit_cause = "exhausted"
                break
            scount += ncount
            # fold the wave's buffer into the single seen run (device-side
            # sort-concat; a merge signature's first use compiles inside
            # this bracket)
            with ph("seen_merge"):
                self._merge_seen(wave_new, scount)
            depth += 1
            distinct += ncount
            depth_counts.append(ncount)
            peak_rows = max(peak_rows, ncount)
            if self.invariants:
                for k, name in enumerate(self.invariants):
                    if viol_h[k] != I32_MAX:
                        violation = Violation(
                            invariant=name, global_id=n0 + int(viol_h[k]), depth=depth
                        )
                        break
            base_gid = n0 + int(stats_h[1]) - ncount
            # (the wave-new/overflow stats lanes reset in-program on the
            # next wave's first chunk — no host re-upload needed)
            frontier, next_buf = next_buf, frontier
            prev_fcount = fcount
            fcount = ncount
            # no growth after the wave that max_depth ends: no wave would
            # use the larger buffers, and FCAP would stay grown for the
            # next run() of this engine (a new wave program to compile)
            if max_depth is None or depth < max_depth:
                frontier, next_buf, jparent, jcand = self._maybe_grow(
                    ncount, frontier, next_buf, jparent, jcand, scount - n0
                )
            if (
                checkpoint_path is not None
                and violation is None  # a saved file must not mask a violation
                and time.perf_counter() - last_ckpt > checkpoint_every_s
            ):
                self._save_checkpoint(
                    checkpoint_path, frontier, jparent, jcand, fcount,
                    scount, distinct, total, terminal, depth, base_gid,
                    gen_prev, depth_counts, cov_h,
                )
                last_ckpt = time.perf_counter()
            # the wave's canon counts (in-chunk duplicates, tier-3 local
            # and full lanes), from the cumulative lanes of the same
            # snapshot
            wave_dup, wave_t3l, wave_t3f = (
                int(x) for x in stats_h[5:8] - canon_prev)
            canon_prev = stats_h[5:8].copy()
            sort_lanes_run += int(stats_h[8])
            search_queries_run += int(stats_h[9])
            rows_built_run += int(stats_h[10])
            search_steps_run += int(stats_h[11])
            wave_budget = plan_rows * -(-prev_fcount // self.chunk)
            rows_budget_run += wave_budget
            wave_s_val = time.perf_counter() - tw
            # the wave's brackets, read once a wave whoever listens
            # (engine.phase_clocks makes the row's clocks of them):
            # `telemetry` is the previous wave's bracket (Phases.take)
            ph_s = ph.take()
            comp_now = COMPILES.snapshot()
            # what the allocator holds now, after the merge and any
            # growth, and beside it the plan: what the run's geometry
            # says its buffers take (it changes only on a growth or a
            # step of the seen run)
            hbm = memwatch.wave(depth, {
                "frontier": 2 * (self.FCAP + self.VC) * 4 * W,
                "journal": 2 * (self.JCAP + self.VC) * 4,
                "seen": int(self._seen.shape[0]) * 8,
                "wave_new": (self.FCAP + self.VC) * 8,
                "chunk": self.VC * (4 * W + 8),
            })
            if not (tel.active or metrics is not None or verbose):
                continue
            with ph("telemetry"):
                el = time.perf_counter() - t0
                wm = wave_row(
                    depth=depth, frontier=prev_fcount, new=ncount,
                    distinct=distinct, generated=wave_gen,
                    generated_total=total, terminal=terminal,
                    canon=(wave_dup, wave_t3l, wave_t3f),
                    overflow_bits=ovf_bits,
                    lsm_runs=1, lsm_lanes=int(self._seen.shape[0]),
                    wave_s=wave_s_val, elapsed_s=el,
                    A=self.A, expand_budget_ovf=(ovf_bits >> 1) & 1,
                    hbm=hbm,
                    **phase_clocks(ph_s, comp_wave, comp_now),
                    # this engine's own: the lanes the dedup stage's
                    # merged sort sorted, summed over the wave's
                    # chunk-steps (lane 8 of the stats the wave already
                    # fetched): the seen run, the prefix of the wave's
                    # buffer each step chose and VC; the query lanes
                    # those steps searched the seen run with (lane 9; 0
                    # while the run is merged) and how many of the steps
                    # searched (lane 11); and the run's size as
                    # the wave met it; then the successor rows the
                    # wave's apply passes built (lane 10) beside the
                    # rows their plan budgets, sum(plan) a chunk-step
                    dedup_sort_lanes=int(stats_h[8]),
                    dedup_search_queries=int(stats_h[9]),
                    dedup_search_steps=int(stats_h[11]),
                    seen_lanes=seen_lanes,
                    expand_rows_built=int(stats_h[10]),
                    expand_rows_budget=wave_budget,
                )
                tel.wave(wm)
                if tel.active:
                    tel.coverage(self._coverage_fields(
                        depth, cov_h, scount, depth_counts,
                    ))
                if metrics is not None:
                    metrics.append(wm)
                if verbose:
                    print(
                        f"depth {depth}: frontier {ncount}, distinct {distinct}, "
                        f"total {total}, {distinct/el:.0f} distinct/s",
                        file=sys.stderr,
                    )

        ph.top("finish")
        if checkpoint_path is not None and violation is None and not exhausted:
            # budget/depth-capped exit: the loop broke at a wave boundary,
            # so save a final resumable snapshot (the periodic timer alone
            # can leave no checkpoint at all on short-budget runs)
            self._save_checkpoint(
                checkpoint_path, frontier, jparent, jcand, fcount,
                scount, distinct, total, terminal, depth, base_gid,
                gen_prev, depth_counts, cov_h,
            )

        self._jparent = jparent
        self._jcand = jcand
        self._jcount = int(np.asarray(jax.device_get(stats))[1])

        dt = time.perf_counter() - t0
        stats_run = run_stats(
            self, comp_run, ph, memwatch, frontier_peak_rows=peak_rows,
            coverage=cov_h, dedup_plan=self._dedup_plan(),
            canon_tier3_local=int(canon_prev[1]),
            canon_tier3_full=int(canon_prev[2]),
            dedup_sort_lanes=sort_lanes_run,
            dedup_search_queries=search_queries_run,
            dedup_search_steps=search_steps_run,
            expand_rows_built=rows_built_run,
            expand_rows_budget=rows_budget_run,
        )
        if violation is not None:
            exit_cause = "violation"
        elif exit_cause is None:
            exit_cause = "exhausted"
        if tel.active:
            tel.coverage(
                self._coverage_fields(depth, cov_h, scount, depth_counts),
                final=True)
        tel.close_run(summary_fields(
            self, "device",
            exit_cause=exit_cause,
            violation=violation.invariant if violation else None,
            distinct=distinct, total=total, depth=depth,
            terminal=terminal, seconds=dt,
            exhausted=exhausted and violation is None,
            peak_frontier_cap=self.FCAP, peak_journal_cap=self.JCAP,
            seen_lanes=int(self._seen.shape[0]),
            canon_dup_rate=round(
                int(canon_prev[0]) / max(1, gen_prev), 4),
            stats=stats_run, programs=COMPILES.programs(comp_run),
        ))
        trace = self.reconstruct_trace(violation) if violation else None
        res = CheckResult(
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
            coverage=(
                [[int(x) for x in row] for row in cov_h]
                if self.n_actions else None
            ),
            exit_cause=exit_cause,
            stats=stats_run,
        )
        return res

    def _coverage_fields(self, depth, cov_h, scount, depth_counts) -> dict:
        """Dedup-structure gauges + the per-action block for a coverage
        event, all from values the wave loop already holds on host."""
        return {
            "depth": depth,
            "actions": [[int(x) for x in row] for row in cov_h],
            "actions_total": self.n_actions,
            "actions_fired": int(np.count_nonzero(cov_h[:, 1]))
            if self.n_actions else 0,
            "seen_lanes": [int(self._seen.shape[0])],
            "seen_real": int(scount),
            "probe_runs": 1,  # single consolidated seen run (round 5)
            "frontier_hist": [int(x) for x in depth_counts],
        }

    def _telemetry_manifest(self) -> dict:
        """Run-provenance fields of the telemetry manifest event (all
        MANIFEST_KEYS except the auto-added "event")."""
        return manifest_fields(
            self, "device", jax.devices()[0],
            frontier_cap=self.FCAP, journal_cap=self.JCAP,
            max_seen_cap=self.MAX_SCAP, valid_cap=self.VC,
            dedup_plan=self._dedup_plan(),
        )

    def _dedup_plan(self) -> dict:
        """util.dedup_plan of the wave program as it stands: the seen
        run and the prefixes of the wave's fingerprint buffer against VC
        query lanes (the manifest has it at the run's first seen size,
        ``stats`` and the summary at its last)."""
        size = self._seen.shape[0]
        return dedup_plan(
            [size], self.VC, self._wave_prefix(), self._rungs(size))

    def _ckpt_ident(self) -> str:
        """Everything the saved run's soundness depends on: symmetry mode
        changes the canonical fingerprints, and the INVARIANT SET must
        match too — states explored before the checkpoint (including Init)
        were only checked against the original run's invariants, so a
        resume with different invariants would silently skip them."""
        return (
            f"{self.model.name}/{self.model.p}/W={self.W}"
            f"/{canon_ident(self.canon, seed=True)}"
            f"/inv={','.join(self.invariants)}"
        )

    def _save_checkpoint(
        self, path, frontier, jparent, jcand, fcount, scount, distinct,
        total, terminal, depth, base_gid, gen_prev, depth_counts,
        coverage,
    ):
        """Spill the resumable run state to an .npz (atomic rename).
        Saved at wave boundaries only, so the arrays are consistent."""
        with self._ph("checkpoint"):
            self._write_checkpoint(
                path, frontier, jparent, jcand, fcount, scount, distinct,
                total, terminal, depth, base_gid, gen_prev, depth_counts,
                coverage,
            )

    def _write_checkpoint(
        self, path, frontier, jparent, jcand, fcount, scount, distinct,
        total, terminal, depth, base_gid, gen_prev, depth_counts,
        coverage,
    ):
        n0 = len(self._init_distinct)
        jcount = scount - n0
        seen = self._lsm_export()
        assert len(seen) == scount, f"LSM export {len(seen)} != scount {scount}"
        # crash-safe write (resilience/ckpt.py): tmp + fsync + rename,
        # format_version + content hash embedded, previous generations
        # rotated so a torn write costs one interval, not the run
        rckpt.save_npz(
            path,
            dict(
                version=1,  # engine payload layout revision (unchanged)
                spec=self._ckpt_ident(),
                fcount=fcount,
                scount=scount,
                jcount=jcount,
                frontier=np.asarray(jax.device_get(frontier[:fcount])),
                seen=seen,
                jparent=np.asarray(jax.device_get(jparent[:jcount])),
                jcand=np.asarray(jax.device_get(jcand[:jcount])),
                distinct=distinct,
                total=total,
                terminal=terminal,
                depth=depth,
                base_gid=base_gid,
                gen_prev=gen_prev,
                depth_counts=np.asarray(depth_counts, dtype=np.int64),
                coverage=np.asarray(coverage, dtype=np.int64),
            ),
            keep=getattr(self, "_ckpt_keep", rckpt.DEFAULT_KEEP),
            chaos=getattr(self, "_chaos", None),
        )

    def _check_init(self, init_d: np.ndarray) -> Violation | None:
        for name in self.invariants:
            ok = np.asarray(jax.device_get(self.model.invariants[name](init_d)))
            bad = np.nonzero(~ok)[0]
            if len(bad):
                return Violation(invariant=name, global_id=int(bad[0]), depth=0)
        return None

    # ---------------- trace reconstruction ----------------

    def reconstruct_trace(self, violation: Violation) -> list[tuple[str, dict]]:
        """Parent-pointer replay, identical semantics to BFSChecker's
        (journal is flat (parent gid, candidate) arrays here)."""
        model = self.model
        n0 = len(self._init_distinct)
        jc_n = self._jcount
        jp = np.asarray(jax.device_get(self._jparent))[:jc_n]
        jc = np.asarray(jax.device_get(self._jcand))[:jc_n]
        chain: list[int] = []
        gid = violation.global_id
        while gid >= n0:
            chain.append(int(jc[gid - n0]))
            gid = int(jp[gid - n0])
        chain.reverse()
        state = self._init_distinct[gid]
        out = [("Initial predicate", model.decode(state))]
        expand1 = jax.jit(model._expand1)
        for cand in chain:
            succs, valid, rank, _ovf = jax.device_get(expand1(state))
            assert valid[cand], "journalled candidate not enabled on replay"
            state = np.asarray(succs[cand])
            out.append(
                (model.action_label(int(rank[cand]), cand), model.decode(state))
            )
        return out
