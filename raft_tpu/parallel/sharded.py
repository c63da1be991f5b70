"""Sharded-frontier BFS over a ``jax.sharding.Mesh`` (v3).

The TPU-native replacement for TLC's shared-memory worker threads
(``tlc -workers N``, SURVEY.md §5.8): each chip owns the slice of
fingerprint space ``fp mod D`` (D = mesh size). A wave expands the whole
per-chip frontier by sub-stepping a cursor in ``chunk``-sized chunks; each
chunk is one ``shard_map``-ed program per chip:

    slice `chunk` frontier rows -> expand (vmap over per-action kernels)
    -> compact valid successor lanes -> canonical fingerprints -> route
    each candidate to its owner chip (``fp mod D``) via ``jax.lax.
    all_to_all`` over ICI -> local dedup (probe the chip's LSM seen-runs,
    first-occurrence) -> append survivors to the local next-frontier and
    their (parent shard, parent lgid, candidate) rows to the local
    journal -> batched invariant evaluation folding the first-violating
    journal index per invariant -> emit the chip's new fingerprints as
    one sorted run.

The per-chip seen-set is an LSM of sorted runs (checker/lsm.py RunLSM;
DeviceBFS has kept one run and a wave buffer instead since round 5, and
sorts them with the chunk since PR 36): runs live as [D, lanes]
sharded arrays so every merge/consolidation is a batched per-chip sort
with no collectives; the binary-counter cascade is identical on every
chip (all chips insert one run per chunk), so one host-side occupancy
drives the whole mesh. This removes the per-chunk FCAP-lane sort and the
per-wave SCAP-lane finalize of v2 — per-chunk dedup cost is independent
of total state count.

Parent pointers cross shards (a successor's owner is unrelated to its
parent's shard), so journal entries address states as (shard, local gid);
the parent shard is implicit in the all-to-all block structure (received
rows [d*RC:(d+1)*RC] came from chip d) and is never routed.

Checkpoint/resume (round-4 verdict Next #3): same .npz scheme as
DeviceBFS with per-shard arrays — but the payload is MESH-PORTABLE
(elastic-mesh PR): every per-shard array is a segment routable by
``fp mod D`` (the journal carries each row's fingerprint in ``jfp``
exactly for this), the recorded ``/D=<n>/`` ident component is
provenance rather than identity, and a load-time reshard pass
(``_reshard_payload``) re-routes every segment when the resuming mesh
size differs — D=8 -> D=4 -> D=1 all resume with bit-identical counts.
Pre-``jfp`` checkpoints reshard too: ``_recover_journal_fps`` rebuilds
the journal fingerprints by topological replay through the model's
transition function. On capacity overflow or shard loss the abort path
spills a WAVE-START checkpoint by subtracting the aborted wave's
fingerprints back out of the LSM export (``_wave_start_seen``), so
supervised recoveries lose zero work — matching DeviceBFS semantics.

State counts are exact and deterministic; within-wave discovery ORDER
differs from the sequential driver (first-occurrence tie-breaking is by
owner chip, then source chip), which can pick a different — equally
shortest — counterexample.
"""

from __future__ import annotations

import sys
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..checker.engine import (
    FleetQueue, canon_ident, compact_chunk, expand_chunk, loop_exit,
    manifest_fields, phase_clocks, rank_key_bits, resume_events,
    run_stats, summary_fields, wave_row,
)
from ..checker.lsm import RunLSM, pow2_at_least
from ..obs import (
    COMPILES, MemWatch, NULL_TELEMETRY, setup_phase, span,
    stage, traced_run,
)
from ..checker.util import (
    GROWTH, HEADROOM, I32_MAX, dedup_plan, dense_prefix_sel, emit_append,
    first_new, next_cap as _next_cap, rank_counts, rank_onehot,
)
from ..ops.hashing import (
    U64_MAX, eq_u64, sort_u64, sort_u64_with_idx, split_u64,
)
from ..ops.symmetry import Canonicalizer, canon_chunk
from ..resilience import ckpt as rckpt
from ..resilience.errors import CapacityOverflow, ShardLost, ShardStall

AXIS = "shards"


@dataclass
class ShardedResult:
    distinct: int
    total: int
    depth: int
    depth_counts: list[int]
    violation_invariant: str | None
    seconds: float
    states_per_sec: float
    terminal: int = 0
    exhausted: bool = True
    trace: list[tuple[str, dict]] | None = None
    metrics: list[dict] | None = None  # per-wave (SURVEY.md §5.5)
    # fleet aggregates: canon's in-chunk duplicates and their rate over
    # the shards, per-shard skew (always populated; cheap host arithmetic)
    stats: dict | None = None
    # fleet-summed per-action [enabled, fired, new-distinct] in
    # ACTION_NAMES rank order; None for models without the contract
    coverage: list[list[int]] | None = None
    # why the run ended (obs.events.EXIT_CAUSES vocabulary); the CLI
    # maps "preempted" to exit code 4
    exit_cause: str | None = None


class ShardedBFS(FleetQueue):
    """Multi-chip exhaustive BFS with per-chip frontier/seen-runs/journal.

    Capacities (all per device):
      chunk          frontier states expanded per chunk step
      valid_per_state  compaction budget (avg valid successors per state)
      route_cap      all-to-all slots per (src, dst) pair per chunk step;
                     defaults to the compaction budget, which makes route
                     overflow impossible (a chunk yields at most VC
                     candidates, all of which could share one owner)
      frontier_cap   per-wave distinct states (grows, multiple of chunk)
      seen_cap       initial per-chip LSM lane budget (bound: max_seen_cap)
      journal_cap    journal rows = owned distinct states beyond Init
    """

    GROWTH = GROWTH
    HEADROOM = HEADROOM
    # overflow-bit vocabulary for the stats word (chunk-step assembly);
    # SEEN_OVF_BIT is synthetic — the host TOPSZ guard raises it, the
    # device never sets it
    OVF_NAMES = (
        (1, "msg"), (2, "valid"), (4, "route"), (8, "frontier"),
        (16, "journal"),
    )
    SEEN_OVF_BIT = 32
    N_STATS = 9  # lanes of the per-shard stats vector (_chunk_step)

    # Donation contract (audited by `raft_tpu lint`, pass `donation`):
    # every capacity-shaped per-wave carry must alias an output of the
    # program that rebinds it. The frontier is read-only within a wave
    # (host-swapped with next_buf at the wave boundary), fc/bl/cursor are
    # scalars-per-shard, and occ plus the LSM runs are reused across
    # chunks — none of those donate.
    #   chunk: next_buf, jps, jpl, jcand, jfp, viol, stats, cov
    STEP_DONATE = (2, 3, 4, 5, 6, 7, 8, 9)

    @setup_phase("engine")
    def __init__(
        self,
        model,
        invariants: tuple[str, ...] = (),
        symmetry: bool = True,
        devices=None,
        chunk: int = 256,
        valid_per_state: int = 16,
        valid_per_group: float | dict | None = None,
        route_cap: int | None = None,
        frontier_cap: int = 1 << 12,
        seen_cap: int = 1 << 16,
        journal_cap: int | None = None,
        max_frontier_cap: int = 1 << 20,
        max_seen_cap: int = 1 << 24,
        max_journal_cap: int = 1 << 24,
    ):
        # constructor kwargs, captured before any normalization, so the
        # supervisor/fleet can rebuild this engine with overrides
        # (grown caps, a shrunk device list after a shard loss)
        self._ctor_kw = {k: v for k, v in locals().items() if k != "self"}
        self.model = model
        self.invariants = tuple(invariants)
        # rank-indexed coverage rows; 0 for models without the
        # ACTION_NAMES contract (coverage then disabled)
        self.n_actions = len(getattr(model, "ACTION_NAMES", ()))
        devices = devices if devices is not None else jax.devices()
        self.D = len(devices)
        # the u32-decomposed fp%D owner routing is exact only for D<=2^16
        assert self.D <= (1 << 16), "owner routing supports at most 2^16 shards"
        self.mesh = Mesh(np.array(devices), (AXIS,))
        self.chunk = chunk
        self.A = model.A
        self.W = model.layout.W
        self.VC = min(chunk * self.A, chunk * valid_per_state)
        rank_key_bits(chunk, self.A, self.n_actions)  # the key fits, or raise
        # guard-first sparse expansion (SparseExpandMixin models): see
        # checker/device_bfs.py — same two-phase contract per shard
        self._sparse = hasattr(model, "sparse_apply")
        if self._sparse:
            # derive the guard jaxpr now, outside any trace: built lazily
            # inside the shard_map trace its avals would name this mesh,
            # and the model (shared through cached_model) could then be
            # evaluated on no other mesh and under no plain jit
            model.guards1
        self.valid_per_group = valid_per_group
        self._plan = (
            model.sparse_plan(chunk, self.VC, valid_per_group)
            if self._sparse
            else None
        )
        # a chunk receives at most D*RC routed lanes; RC defaults to VC
        self.RC = route_cap if route_cap is not None else self.VC
        # emit drop-region rows past FCAP/JCAP: one chunk appends at most
        # the D*RC received lanes (checker/util.py emit_append)
        self.EPAD = self.D * self.RC
        frontier_cap = ((frontier_cap + chunk - 1) // chunk) * chunk
        self.FCAP = frontier_cap
        self.JCAP = journal_cap if journal_cap is not None else seen_cap
        self.MAX_FCAP = max(max_frontier_cap, frontier_cap)
        self.MAX_SCAP = max(max_seen_cap, seen_cap)
        self.MAX_JCAP = max(max_journal_cap, self.JCAP)
        # LSM geometry: a chunk inserts the D*RC received lanes' worth of
        # new fps at most, but only its own VC-compacted candidates can
        # be new — the run size is the receive width. Shared
        # implementation (checker/lsm.py): runs are [D, lanes] sharded
        # arrays, merges are collective-free per-chip sorts.
        self.R0 = pow2_at_least(self.D * self.RC)
        self.SCAP = self.MAX_SCAP
        self.canon = Canonicalizer.for_model(model, symmetry=symmetry)
        self._sharding = NamedSharding(self.mesh, P(AXIS))
        self._lsm = RunLSM(
            r0=self.R0, topsz=pow2_at_least(self.MAX_SCAP),
            lead_shape=(self.D,),
            put=lambda h: jax.device_put(h, self._sharding),
            jit_kw={"out_shardings": self._sharding},
        )
        self.TOPSZ = self._lsm.TOPSZ

        self._chunk_fn_cache: dict[int, object] = {}
        self._occ_cache: dict[bytes, object] = {}
        self._journals = None  # (jps, jpl, jcand) per shard after run()
        self._init_by_shard = None

    # ---------------- LSM adapters (per-chip [D, lanes] runs) ----

    def _occ_dev(self):
        """Occupancy flags as a device array, uploaded once per distinct
        pattern (a fresh upload per chunk is a host-to-device transfer
        on the chunk loop's critical path)."""
        key = bytes(self._lsm.occ)
        arr = self._occ_cache.get(key)
        if arr is None:
            arr = jnp.asarray(np.asarray(self._lsm.occ, dtype=bool))
            self._occ_cache[key] = arr
        return arr

    def _lsm_export(self) -> list[np.ndarray]:
        """Per-chip sorted real fingerprints (checkpoint format)."""
        return self._lsm.export_real()

    def _lsm_seed(self, per_shard: list[np.ndarray]):
        n = max((len(a) for a in per_shard), default=0)
        h = np.full((self.D, max(n, 1)), np.uint64(U64_MAX))
        for d, a in enumerate(per_shard):
            h[d, : len(a)] = np.sort(a.astype(np.uint64))
        self._lsm.seed(h)

    # ---------------- device programs (per chip under shard_map) ----------

    def _shard_map(self, f, in_specs, out_specs):
        """``jax.shard_map`` over this engine's mesh with the varying-
        manual-axes check off. ``model.guards1`` evaluates a jaxpr that
        was traced and DCE'd outside any mesh (models/base.py):
        ``eval_jaxpr`` binds its primitives directly, without the
        ``pvary`` casts the jnp-level API inserts, so its equations mix
        the varying state with unvarying closed-over constants and the
        check rejects them. Every out_spec of every program here is
        ``P(AXIS)``, so there is no replication claim for it to prove."""
        return jax.shard_map(
            f, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )

    def _get_chunk_fn(self, n_runs: int):
        """jit(shard_map) per LSM level count (the runs tuple is part of
        the program signature)."""
        fn = self._chunk_fn_cache.get(n_runs)
        if fn is None:
            spec = P(AXIS)
            fn = jax.jit(
                self._shard_map(
                    self._chunk_step,
                    in_specs=(spec,) * 10 + (P(), P(), spec) + (spec,) * n_runs,
                    out_specs=(spec,) * 9,
                ),
                donate_argnums=self.STEP_DONATE,
            )
            self._chunk_fn_cache[n_runs] = fn
        return fn

    # ---------------- static audit surface ----------------

    def audit_programs(self):
        """Every device program a sharded run dispatches, as audit
        entries for the static donation auditor (analysis/donation.py) —
        the same entry schema as ``DeviceBFS.audit_programs``: ``fn`` is
        a ``.lower()``-able jitted callable (the production jit object),
        ``args`` its abstract arguments, ``carries``/``pinned`` the
        independent {argnum: name} donation declarations the auditor
        compares against the lowered aliasing, ``site`` a (file, line)
        anchor, ``per_wave`` the dispatch count per wave. Nothing is
        lowered or executed here; the ``carries`` maps are deliberately
        written out separately from ``STEP_DONATE`` so a
        dropped donate argnum diverges the two."""
        import inspect as _inspect

        sds = jax.ShapeDtypeStruct
        D, W = self.D, self.W
        n_runs = len(self._lsm.runs)
        i32s = sds((), np.int32)
        frontier = sds((D, self.FCAP + self.EPAD, W), jnp.int32)
        next_buf = sds((D, self.FCAP + self.EPAD, W), jnp.int32)
        fc = sds((D, 1), jnp.int32)
        bl = sds((D, 1), jnp.int32)
        jps = sds((D, self.JCAP + self.EPAD), jnp.int32)
        jpl = sds((D, self.JCAP + self.EPAD), jnp.int32)
        jcand = sds((D, self.JCAP + self.EPAD), jnp.int32)
        jfp = sds((D, self.JCAP + self.EPAD), jnp.uint64)
        viol = sds((D, max(1, len(self.invariants))), jnp.int32)
        stats = sds((D, self.N_STATS), jnp.int64)
        cov = sds((D, self.n_actions, 3), jnp.int64)
        occ = sds((n_runs,), jnp.bool_)
        runs = tuple(
            sds((D, self._lsm.lv_size(i)), jnp.uint64)
            for i in range(n_runs)
        )

        def site(fn):
            f = _inspect.unwrap(fn)
            return (__file__, _inspect.getsourcelines(f)[1])

        yield {
            "name": "chunk", "fn": self._get_chunk_fn(n_runs),
            "args": (frontier, fc, next_buf, jps, jpl, jcand, jfp, viol,
                     stats, cov, i32s, occ, bl, *runs),
            "carries": {2: "next_buf", 3: "jps", 4: "jpl", 5: "jcand",
                        6: "jfp", 7: "viol", 8: "stats", 9: "cov"},
            "pinned": {0: "frontier"},
            "site": site(self._chunk_step), "per_wave": 1,
        }

    def _chunk_step(
        self, frontier, fcount, next_buf, jps, jpl, jcand, jfp, viol, stats,
        cov, cursor, occ, base_lgid, *runs,
    ):
        """One chunk of the current wave on one chip.

        frontier [1,F+EPAD,W]; fcount/base_lgid [1,1]; next_buf
        [1,F+EPAD,W]; jps/jpl/jcand [1,JC+EPAD] (the EPAD=D*RC tail rows
        are the emit drop region); jfp [1,JC+EPAD] u64 — each journal
        row's canonical fingerprint, the lane that makes the checkpoint
        mesh-portable (reshard routes rows by jfp mod D_new) and the
        wave-start LSM subtraction exact; viol [1,K]; occ bool[L]
        (replicated); runs: L sharded [1,lanes] sorted u64;
        cov [1,n_actions,3] i64 per-shard cumulative
        [enabled, fired, new] per action rank (enabled/fired tally on the
        GENERATING chip, new on the OWNER chip after the all-to-all);
        stats [1,S] i64 = [wave new, jcount, cum generated,
        cum terminal, ovf bits, routed lanes, then the cumulative canon
        counts: in-chunk duplicate lanes, tier-3 local lanes, tier-3
        full lanes] (N_STATS lanes).
        Returns (+ new_run [1,R0]).
        """
        # strip the leading local-block axis shard_map hands us
        frontier, fcount, base_lgid = frontier[0], fcount[0, 0], base_lgid[0, 0]
        next_buf = next_buf[0]
        jps, jpl, jcand, viol, stats = jps[0], jpl[0], jcand[0], viol[0], stats[0]
        jfp = jfp[0]
        cov = cov[0]
        runs = [r[0] for r in runs]
        send_pay, send_fps, cov_gen, pre_stats = self._cs_pre(
            frontier, fcount, cursor, base_lgid
        )
        # 5. ICI all-to-all: block d of my send goes to chip d; received
        # block d came from chip d (=> parent shard = recv row // RC)
        with stage("exchange"):
            recv_pay = lax.all_to_all(send_pay, AXIS, 0, 0, tiled=True)
            recv_fps = lax.all_to_all(send_fps, AXIS, 0, 0, tiled=True)
        (next_buf, jps, jpl, jcand, jfp, viol, stats, cov, new_run,
         ) = self._cs_post(
            recv_pay, recv_fps, next_buf, jps, jpl, jcand, jfp, viol,
            stats, cov, cov_gen, pre_stats, occ, runs,
        )
        return (
            next_buf[None], jps[None], jpl[None], jcand[None], jfp[None],
            viol[None], stats[None], cov[None], new_run[None],
        )

    def _cs_pre(self, frontier, fcount, cursor, base_lgid):
        """Per-chip pre-exchange stages of one chunk (steps 1-4): expand,
        compact, canon, owner routing. Returns the all-to-all send blocks
        plus everything the post stage needs: ``cov_gen`` [K,2] =
        per-action [enabled, fired] tallied on the generating chip
        ([1,2] zeros when the model has no action ranks) and
        ``pre_stats`` [7] i64 = [n_gen, terminal, pre-exchange ovf bits
        (1=msg 2=valid 4=route), routed lanes (the first lane of each
        distinct raw view of the chunk: an in-chunk duplicate leaves
        the canon masked and is dropped here with the invalid lanes),
        then the chunk's canon counts (ops/symmetry.canon_chunk):
        in-chunk duplicate lanes, tier-3 local lanes, tier-3 full
        lanes]."""
        model, D, A, W = self.model, self.D, self.A, self.W
        C, VC, RC = self.chunk, self.VC, self.RC
        K = self.n_actions

        with stage("expand"):
            # 1. expand `chunk` rows starting at the wave cursor
            (batch, succs, valid, rank, n_gen, term,
             expand_ovf) = expand_chunk(
                model, self._sparse, frontier, cursor, fcount, C)

            # 1b. enabled/fired per action rank, tallied where the lanes are
            # generated (numpy mirror in checker/bfs.py), by compare and
            # sum (util.rank_counts)
            if K:
                en = rank_onehot(rank, valid, K)  # [C, A, K]
                enabled_k = jnp.sum(jnp.any(en, axis=1), axis=0, dtype=jnp.int32)
                fired_k = rank_counts(rank, valid, K)

            # 2. compact the valid lanes into the [VC, W] successor block
            # (the apply pass's count of the rows it built stays here:
            # this engine's stats have no lane for it)
            flatc, sel, selv, sel_rank, compact_ovf, _ = compact_chunk(
                model, self._plan, batch, succs, valid, rank, K, n_gen, VC)
            parent_lgid = base_lgid + cursor + sel // A
            cand = sel % A

        with stage("canon"):
            # 3. canonical fingerprints on the compacted lanes, one canon
            # per distinct raw view, on the GENERATING chip (the
            # all-to-all below only ever moves canonical fingerprints)
            fps, canon_n = canon_chunk(self.canon, flatc, selv)

        with stage("exchange"), jax.named_scope("route"):
            # 4. route to owner chip = fp mod D: sort by owner, positional
            # slots. The action rank rides the payload so the OWNER chip can
            # attribute new-distinct states per action after dedup: it is
            # ``sel_rank``, out of the compaction's sort key.
            payload = jnp.concatenate(
                [flatc, parent_lgid[:, None], cand[:, None],
                 sel_rank[:, None]], axis=1
            )  # [VC, W+3] i32
            # fp mod D in u32 pieces (u64 div/mod lanes are slow on this TPU):
            # (hi*2^32 + lo) % D == ((hi%D) * (2^32%D) + lo%D) % D
            # exact only while (D-1)*(2^32%D) + (D-1) fits u32 — enforced at
            # construction (D <= 2^16), and real meshes are far smaller
            fhi, flo = split_u64(fps)
            t32 = np.uint32((1 << 32) % D)
            owner = (((fhi % np.uint32(D)) * t32 + flo % np.uint32(D))
                     % np.uint32(D)).astype(jnp.int32)
            # invalid, or an in-chunk duplicate of a lower lane -> drop
            owner = jnp.where(eq_u64(fps, U64_MAX), D, owner)
            order = jnp.argsort(owner, stable=True)
            owner_s = owner[order]
            fps_s = fps[order]
            start = jnp.searchsorted(owner_s, jnp.arange(D + 1), side="left")
            pos_in_owner = jnp.arange(VC) - start[owner_s]
            ok = (owner_s < D) & (pos_in_owner < RC)
            route_ovf = jnp.any((owner_s < D) & (pos_in_owner >= RC))
            n_routed = jnp.sum(ok)
            slot = jnp.where(ok, owner_s * RC + pos_in_owner, D * RC)
            send_pay = jnp.zeros((D * RC + 1, W + 3), jnp.int32).at[slot].set(payload[order])[:-1]
            send_fps = jnp.full((D * RC + 1,), U64_MAX, jnp.uint64).at[slot].set(
                jnp.where(ok, fps_s, U64_MAX))[:-1]

        pre_stats = jnp.stack([
            n_gen.astype(jnp.int64),
            term.astype(jnp.int64),
            expand_ovf.astype(jnp.int64)
            + 2 * compact_ovf.astype(jnp.int64)
            + 4 * route_ovf.astype(jnp.int64),
            n_routed.astype(jnp.int64),
            *canon_n.astype(jnp.int64),
        ])
        cov_gen = (
            jnp.stack([enabled_k, fired_k], axis=1).astype(jnp.int64)
            if K else jnp.zeros((1, 2), jnp.int64)
        )
        return send_pay, send_fps, cov_gen, pre_stats

    def _cs_post(
        self, recv_pay, recv_fps, next_buf, jps, jpl, jcand, jfp, viol,
        stats, cov, cov_gen, pre_stats, occ, runs,
    ):
        """Per-chip post-exchange stages of one chunk (steps 6-8): local
        dedup against the LSM runs, emit-append, owner-side coverage,
        invariants, stats fold. ``cov_gen``/``pre_stats`` carry the
        generating-chip tallies from ``_cs_pre``."""
        model, D, W = self.model, self.D, self.W
        RC = self.RC
        F, JC = self.FCAP, self.JCAP
        K = self.n_actions

        with stage("dedup"):
            # 6. local dedup, in sorted order (what the emit below keeps):
            # not in any LSM run and first among equals, by the one
            # merged sort of util.first_new — rf's equal lanes are
            # adjacent in source order, so its lowest-lane rule is the
            # first-occurrence rule here
            rf, sidx = sort_u64_with_idx(recv_fps)
            new = first_new(rf, occ, runs)
            n_new = jnp.sum(new)

        with stage("emit"):
            # 7. emit survivors: compact to a dense prefix of a [D*RC, W]
            # block, then ONE dynamic_update_slice per buffer appends at the
            # running cursor (rows [F, F+D*RC) / [JC, JC+D*RC) are the drop
            # region — checker/util.py emit_append), under `emit/append`,
            # the scope DeviceBFS gives the same code
            with jax.named_scope("append"):
                ncount = stats[0].astype(jnp.int32)
                jcount = stats[1].astype(jnp.int32)
                npos = (jnp.cumsum(new) - 1).astype(jnp.int32)
                states_s = recv_pay[sidx, :W]
                B = D * RC
                esel = dense_prefix_sel(new, B)
                blk = jnp.concatenate(
                    [states_s, jnp.zeros((1, W), jnp.int32)], axis=0
                )[esel]
                jps_blk = jnp.concatenate(
                    [(sidx // RC).astype(jnp.int32),
                     jnp.zeros((1,), jnp.int32)]
                )[esel]
                jpl_blk = jnp.concatenate(
                    [recv_pay[sidx, W], jnp.zeros((1,), jnp.int32)]
                )[esel]
                jc_blk = jnp.concatenate(
                    [recv_pay[sidx, W + 1], jnp.zeros((1,), jnp.int32)]
                )[esel]
                jfp_blk = jnp.concatenate(
                    [rf, jnp.full((1,), U64_MAX, jnp.uint64)]
                )[esel]
                next_buf, frontier_ovf = emit_append(
                    next_buf, blk, ncount, n_new, F)
                jps, journal_ovf = emit_append(
                    jps, jps_blk, jcount, n_new, JC)
                jpl, _ = emit_append(jpl, jpl_blk, jcount, n_new, JC)
                jcand, _ = emit_append(jcand, jc_blk, jcount, n_new, JC)
                jfp, _ = emit_append(jfp, jfp_blk, jcount, n_new, JC)
            if K:
                # new-distinct per rank on the owner chip (a lane that is
                # not new does not count: its routed rank column may be
                # garbage 0s from unfilled send slots)
                recv_rank = recv_pay[sidx, W + 2]
                new_k = rank_counts(recv_rank, new, K).astype(jnp.int64)
                cov = cov + jnp.concatenate(
                    [cov_gen, new_k[:, None]], axis=1)
            # the chip's new fps as one sorted run (LSM level-0 insert)
            new_run = sort_u64(jnp.where(new, rf, U64_MAX))
            DRC = new_run.shape[0]
            if self.R0 > DRC:
                new_run = jnp.concatenate(
                    [new_run, jnp.full((self.R0 - DRC,), U64_MAX, jnp.uint64)]
                )

            # 8. invariants on the received candidates; fold first-bad jidx
            jidx = jnp.where(new, jcount + npos, I32_MAX)
            for k, name in enumerate(self.invariants):
                okv = model.invariants[name](states_s)
                bad = new & ~okv
                viol = viol.at[k].min(jnp.min(jnp.where(bad, jidx, I32_MAX)))

            ovf_bits = (
                pre_stats[2]
                + 8 * frontier_ovf.astype(jnp.int64)
                + 16 * journal_ovf.astype(jnp.int64)
            )
            stats = jnp.stack(
                [
                    stats[0] + n_new,
                    stats[1] + n_new,
                    stats[2] + pre_stats[0],
                    stats[3] + pre_stats[1],
                    stats[4] | ovf_bits,
                    *(stats[5:] + pre_stats[3:]),
                ]
            )
        return next_buf, jps, jpl, jcand, jfp, viol, stats, cov, new_run

    # ---------------- capacity growth (between waves, host-mediated) ------

    def _maybe_grow(self, state, fcounts, jcounts):
        """Host-side: fetch, pad, re-place any buffer the next wave could
        outgrow. Rare (4x growth), so the host round-trip is acceptable;
        the jitted programs retrace automatically at the new shapes. The
        seen-set needs no growth — LSM levels appear on demand."""
        ncount = int(fcounts.max())
        jc = int(jcounts.max())
        D, W = self.D, self.W
        grow_f = ncount * self.HEADROOM > self.FCAP and self.FCAP < self.MAX_FCAP
        grow_j = (
            jc + ncount * self.HEADROOM > self.JCAP
            and self.JCAP < self.MAX_JCAP
        )
        if not (grow_f or grow_j):
            return state

        def repad(key, new_rows, old_rows, fill, cols=None):
            h = np.asarray(jax.device_get(state[key]))
            shape = (D, new_rows) if cols is None else (D, new_rows, cols)
            out = np.full(shape, fill, dtype=h.dtype)
            out[:, :old_rows] = h
            state[key] = jax.device_put(out, self._sharding)

        # the `grow` span and the row's grow_s exist only on a wave that
        # grows
        with self._ph("grow"):
            if grow_f:
                new = _next_cap(ncount * self.HEADROOM, self.FCAP,
                                self.MAX_FCAP, self.GROWTH, self.chunk)
                repad("frontier", new + self.EPAD, self.FCAP + self.EPAD, 0,
                      cols=W)
                state["next_buf"] = jax.device_put(
                    np.zeros((D, new + self.EPAD, W), np.int32),
                    self._sharding)
                self.FCAP = new
            if grow_j:
                new = _next_cap(jc + ncount * self.HEADROOM, self.JCAP,
                                self.MAX_JCAP, self.GROWTH, 1)
                for key in ("jps", "jpl", "jcand"):
                    repad(key, new + self.EPAD, self.JCAP + self.EPAD, 0)
                repad("jfp", new + self.EPAD, self.JCAP + self.EPAD,
                      np.uint64(U64_MAX))
                self.JCAP = new
        return state

    def grow_for_overflow(self, bits: int) -> dict | None:
        """Constructor-kwarg overrides that would clear the overflow
        bits on a rebuilt engine, or None if no growth can help (the
        supervisor then reports the failure as unrecoverable). Mirrors
        DeviceBFS.grow_for_overflow; route_cap is the sharded-only knob."""
        bits = int(bits)
        if bits & 1:
            return None  # msg-slot width is a model property, not a cap
        growth: dict = {}
        if bits & 2:
            vps = min(self.A, -(-self.VC // self.chunk) * 2)
            growth["valid_per_state"] = vps
            growth["valid_per_group"] = None
        if bits & 4:
            growth["route_cap"] = self.RC * 2
        if bits & 8:
            growth["frontier_cap"] = self.FCAP * 2
            growth["max_frontier_cap"] = max(self.MAX_FCAP, self.FCAP * 4)
        if bits & 16:
            growth["journal_cap"] = self.JCAP * 2
            growth["max_journal_cap"] = max(self.MAX_JCAP, self.JCAP * 4)
        if bits & self.SEEN_OVF_BIT:
            growth["max_seen_cap"] = self.MAX_SCAP * 4
        return growth or None

    def survivors_for_shard_loss(self, shard: int) -> dict | None:
        """Constructor-kwarg overrides that rebuild this engine on the
        mesh minus the lost shard's device, or None when there is no
        surviving mesh (D == 1). The supervisor pairs this with a
        reshard-on-resume of the newest checkpoint."""
        devs = list(self.mesh.devices.flat)
        if len(devs) <= 1:
            return None
        devs.pop(int(shard) % len(devs))
        return {"devices": devs}

    # ---------------- checkpoint ----------------

    def _ckpt_ident(self) -> str:
        """The checkpoint identity (what it must match is in
        ``DeviceBFS._ckpt_ident``; the formula part is
        ``engine.canon_ident``). ``/D=<n>/`` is PROVENANCE, not
        identity: resilience/ckpt.check_spec strips it (mesh_neutral)
        when deciding reshardability, and the resume path re-routes the
        payload when it differs."""
        return (
            f"sharded/{self.model.name}/{self.model.p}/W={self.W}"
            f"/D={self.D}/{canon_ident(self.canon)}"
            f"/inv={','.join(self.invariants)}"
        )

    def _save_checkpoint(
        self, path, state, fcounts, scounts, jcounts, n0, base_lgid,
        distinct, total, terminal, depth, gen_prev, routed_prev, depth_counts,
        coverage, seen_override=None,
    ):
        # seen_override: wave-start per-shard fingerprints computed by
        # _wave_start_seen when the LSM is contaminated by an aborted
        # wave (overflow / shard-loss abort paths)
        with self._ph("checkpoint"):
            self._write_checkpoint(
                path, state, fcounts, scounts, jcounts, n0, base_lgid,
                distinct, total, terminal, depth, gen_prev, routed_prev,
                depth_counts, coverage, seen_override,
            )

    def _write_checkpoint(
        self, path, state, fcounts, scounts, jcounts, n0, base_lgid,
        distinct, total, terminal, depth, gen_prev, routed_prev, depth_counts,
        coverage, seen_override,
    ):
        seen = self._lsm_export() if seen_override is None else seen_override
        assert [len(s) for s in seen] == [int(x) for x in scounts], (
            "LSM export does not match per-shard scounts"
        )
        fmax = int(fcounts.max())
        jmax = int(jcounts.max())
        smax = max((len(s) for s in seen), default=0)
        seen_h = np.full((self.D, smax), np.uint64(U64_MAX))
        for d, s in enumerate(seen):
            seen_h[d, : len(s)] = s
        frontier_h = np.asarray(jax.device_get(state["frontier"]))[:, :fmax]
        # crash-safe write (resilience/ckpt.py): tmp + fsync + rename,
        # content hash + format version, generation rotation
        rckpt.save_npz(
            path,
            dict(
                # payload layout v2: + jfp (per-row journal fingerprints,
                # the mesh-portability lane). v1 payloads still load —
                # _recover_journal_fps rebuilds jfp by replay.
                version=2,
                spec=self._ckpt_ident(),
                fcounts=fcounts, scounts=scounts, jcounts=jcounts,
                n0=n0, base_lgid=base_lgid,
                frontier=frontier_h,
                seen=seen_h,
                jps=np.asarray(jax.device_get(state["jps"]))[:, :jmax],
                jpl=np.asarray(jax.device_get(state["jpl"]))[:, :jmax],
                jcand=np.asarray(jax.device_get(state["jcand"]))[:, :jmax],
                jfp=np.asarray(jax.device_get(state["jfp"]))[:, :jmax],
                init_by_shard_flat=np.concatenate(
                    [np.stack(s) if s else np.zeros((0, self.W), np.int32)
                     for s in self._init_by_shard], axis=0),
                init_by_shard_count=np.asarray(
                    [len(s) for s in self._init_by_shard], np.int64),
                distinct=distinct, total=total, terminal=terminal,
                depth=depth,
                gen_prev=gen_prev, routed_prev=routed_prev,
                depth_counts=np.asarray(depth_counts, dtype=np.int64),
                coverage=np.asarray(coverage, dtype=np.int64),
            ),
            keep=getattr(self, "_ckpt_keep", rckpt.DEFAULT_KEEP),
            chaos=getattr(self, "_chaos", None),
        )

    # ------------- mesh portability (reshard / recovery) -------------

    def _wave_start_seen(self, state, stats_h, jcounts, scounts, ovf_bits):
        """Per-shard wave-start seen fingerprints at an abort point, or
        None when they cannot be reconstructed.

        The chunk loop inserts each chunk's new fingerprints into the
        LSM as it goes, so by the time an abort fires the seen-set is
        contaminated with the (partial) aborted wave. But the SAME
        chunk programs journalled those fingerprints into the jfp lane:
        rows [jcounts[d], stats_h[d,1]) are exactly the wave's inserts,
        so subtracting them from the LSM export recovers the wave-start
        set bit-exactly. Fallback chain when lanes overflowed:

          journal intact (bit 16 clear) -> jfp slice (exact);
          journal full but frontier intact (bit 8 clear) -> refingerprint
            next_buf rows [0, stats_h[d,0]) (the same states, undropped);
          both overflowed -> None (some inserted fps are unrecorded).

        Every reconstruction is length-verified against the wave-start
        scounts before use — a mismatch returns None rather than an
        unsound checkpoint.
        """
        D = self.D
        stats_h = np.asarray(stats_h)
        lsm = self._lsm_export()  # wave-start seen + aborted wave's inserts
        if not (ovf_bits & 16):
            jfp_h = np.asarray(jax.device_get(state["jfp"]))
            wave = [
                jfp_h[d, int(jcounts[d]): int(stats_h[d, 1])].astype(np.uint64)
                for d in range(D)
            ]
        elif not (ovf_bits & 8):
            nb = np.asarray(jax.device_get(state["next_buf"]))
            wave = []
            for d in range(D):
                rows = nb[d, : int(stats_h[d, 0])]
                wave.append(
                    np.asarray(
                        jax.device_get(self.canon.fingerprints(rows)),
                        dtype=np.uint64,
                    )
                    if len(rows)
                    else np.zeros(0, np.uint64)
                )
        else:
            return None
        out = []
        for d in range(D):
            ws = np.setdiff1d(lsm[d], wave[d])
            if len(ws) != int(scounts[d]):
                return None
            out.append(ws)
        return out

    def _abort_wave_start(
        self, checkpoint_path, state, stats_h, fcounts, scounts, jcounts,
        n0, base_lgid, distinct, total, terminal, depth, gen_prev,
        routed_prev, depth_counts, cov_hd,
    ):
        """Spill a wave-start checkpoint at an abort point (overflow,
        shard loss, stall). All counters passed in are the HOST wave-
        start values — the journal/jfp tails the aborted wave appended
        are sliced off by _save_checkpoint's jmax, and the seen-set is
        rebuilt by _wave_start_seen. Returns True when a checkpoint was
        written (False: no path routed, or the wave is unreconstructable
        because both the journal and frontier lanes overflowed)."""
        if checkpoint_path is None:
            return False
        stats_h = np.asarray(stats_h)
        ovf_bits = int(np.bitwise_or.reduce(stats_h[:, 4]))
        ws = self._wave_start_seen(state, stats_h, jcounts, scounts, ovf_bits)
        if ws is None:
            return False
        self._save_checkpoint(
            checkpoint_path, state, fcounts, scounts, jcounts, n0,
            base_lgid, distinct, total, terminal, depth, gen_prev,
            routed_prev, depth_counts, cov_hd, seen_override=ws,
        )
        return True

    def _recover_journal_fps(self, ck, d_ck) -> np.ndarray:
        """Rebuild the jfp lane of a pre-v2 (payload ``version=1``)
        checkpoint by topological replay.

        v1 payloads journalled (parent shard, parent lgid, cand) per row
        but not the row's own fingerprint. Every row's STATE is
        recomputable: replay the journalled candidate action on the
        parent state. Rows resolve in rounds — a row is ready once its
        parent is an init state (known immediately) or an already-
        resolved journal row — and each round batches all ready parents
        through one vmapped expansion + fingerprint call. Cost is one
        expansion per journalled state, paid once: the resumed run
        checkpoints in v2 format, so the upgrade never repeats.
        """
        model, W = self.model, self.W
        jcounts = np.asarray(ck["jcounts"], np.int64)
        n0 = np.asarray(ck["n0"], np.int64)
        jmax = int(jcounts.max()) if len(jcounts) else 0
        jfp = np.full((d_ck, jmax), np.uint64(U64_MAX))
        if jmax == 0:
            return jfp
        jps = np.asarray(ck["jps"])
        jpl = np.asarray(ck["jpl"])
        jcand = np.asarray(ck["jcand"])
        counts = np.asarray(ck["init_by_shard_count"], np.int64)
        flat = np.asarray(ck["init_by_shard_flat"])
        ioff = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
        states = np.zeros((d_ck, jmax, W), np.int32)
        known = np.zeros((d_ck, jmax), bool)
        pending = [
            (d, j) for d in range(d_ck) for j in range(int(jcounts[d]))
        ]
        expand1 = jax.jit(jax.vmap(model._expand1))
        CH = 4096  # fixed batch: one compile, garbage-padded tail
        while pending:
            ready: list[tuple[int, int]] = []
            parents: list[np.ndarray] = []
            rest: list[tuple[int, int]] = []
            for d, j in pending:
                pd, pl = int(jps[d, j]), int(jpl[d, j])
                if pl < n0[pd]:
                    parents.append(flat[ioff[pd] + pl])
                elif known[pd, pl - n0[pd]]:
                    parents.append(states[pd, pl - n0[pd]])
                else:
                    rest.append((d, j))
                    continue
                ready.append((d, j))
            assert ready, "journal replay stuck: unresolvable parent row"
            batch = np.stack(parents).astype(np.int32)
            children = np.empty((len(ready), W), np.int32)
            for s in range(0, len(ready), CH):
                blk = batch[s: s + CH]
                pad = CH - len(blk)
                if pad:
                    blk = np.concatenate(
                        [blk, np.repeat(blk[:1], pad, axis=0)], axis=0)
                succs, _valid, _rank, _ovf = jax.device_get(expand1(blk))
                for i, (d, j) in enumerate(ready[s: s + CH]):
                    children[s + i] = succs[i, int(jcand[d, j])]
            fps = np.asarray(
                jax.device_get(self.canon.fingerprints(children)),
                dtype=np.uint64,
            )
            for i, (d, j) in enumerate(ready):
                states[d, j] = children[i]
                jfp[d, j] = fps[i]
                known[d, j] = True
            pending = rest
        return jfp

    def _reshard_payload(self, ck: dict, d_old: int) -> dict:
        """Re-route a mesh-portable checkpoint written on a D=``d_old``
        mesh onto this engine's D=``self.D`` mesh.

        Every persisted structure is a per-shard partition of one global
        set, keyed by fingerprint: seen fps and init states re-route by
        ``fp mod D_new`` directly; journal rows route by their jfp, kept
        in stable (old shard, old row) order per new shard EXCEPT that
        frontier rows (the last fcounts[d] rows of each old shard) are
        ordered LAST per new shard — preserving the engine invariant
        that frontier row i of shard d is journal row
        ``jcounts[d]-fcounts[d]+i``. Parent pointers rewrite through the
        old->new (shard, lgid) maps. Per-shard coverage counters sum
        into shard 0 (only fleet totals are ever reported). The result
        resumes with counts bit-identical to the same run on the
        original mesh.
        """
        D_new, W = self.D, self.W
        fcounts_o = np.asarray(ck["fcounts"], np.int64)
        scounts_o = np.asarray(ck["scounts"], np.int64)
        jcounts_o = np.asarray(ck["jcounts"], np.int64)
        frontier_o = np.asarray(ck["frontier"])
        seen_o = np.asarray(ck["seen"])
        jps_o, jpl_o = np.asarray(ck["jps"]), np.asarray(ck["jpl"])
        jcand_o = np.asarray(ck["jcand"])
        jfp_o = np.asarray(ck["jfp"], np.uint64)
        counts_o = np.asarray(ck["init_by_shard_count"], np.int64)
        flat = np.asarray(ck["init_by_shard_flat"]).astype(np.int32)
        n0_o = np.asarray(ck["n0"], np.int64)

        # --- inits: route by fingerprint, stable flat order per shard
        n_init = len(flat)
        if n_init:
            ifp = np.asarray(
                jax.device_get(self.canon.fingerprints(flat)), np.uint64)
        else:
            ifp = np.zeros(0, np.uint64)
        iowner = (ifp % np.uint64(D_new)).astype(np.int64)
        ioff_o = np.concatenate([[0], np.cumsum(counts_o)]).astype(np.int64)
        n0_n = np.bincount(iowner, minlength=D_new).astype(np.int64)
        iord = np.argsort(iowner, kind="stable")
        new_il = np.empty(n_init, np.int64)
        new_il[iord] = np.concatenate(
            [np.arange(int(c)) for c in n0_n]
        ) if n_init else np.zeros(0, np.int64)
        init_by_shard_n: list[list[np.ndarray]] = [[] for _ in range(D_new)]
        for idx in iord:
            init_by_shard_n[int(iowner[idx])].append(np.asarray(flat[idx]))

        # --- journal rows: flatten, route by jfp, frontier rows last
        nrows = int(jcounts_o.sum())
        glob_d = np.repeat(np.arange(d_old), jcounts_o)
        glob_j = (
            np.concatenate([np.arange(int(c)) for c in jcounts_o])
            if nrows else np.zeros(0, np.int64)
        ).astype(np.int64)
        joff_o = np.concatenate([[0], np.cumsum(jcounts_o)]).astype(np.int64)
        jfp_flat = (
            np.concatenate(
                [jfp_o[d, : int(jcounts_o[d])] for d in range(d_old)])
            if nrows else np.zeros(0, np.uint64)
        )
        jowner = (jfp_flat % np.uint64(D_new)).astype(np.int64)
        front0 = jcounts_o - fcounts_o  # first frontier journal row, per shard
        is_front = glob_j >= front0[glob_d]
        order = np.lexsort((glob_j, glob_d, is_front, jowner))
        jcounts_n = np.bincount(jowner, minlength=D_new).astype(np.int64)
        starts = np.concatenate([[0], np.cumsum(jcounts_n)]).astype(np.int64)
        # old flat row -> new (shard, row); `order` is grouped by owner
        new_jd = np.repeat(np.arange(D_new), jcounts_n)
        new_jj = (
            np.concatenate([np.arange(int(c)) for c in jcounts_n])
            if nrows else np.zeros(0, np.int64)
        ).astype(np.int64)
        jd_of = np.empty(nrows, np.int64)
        jj_of = np.empty(nrows, np.int64)
        jd_of[order] = new_jd
        jj_of[order] = new_jj

        # --- parent pointer rewrite through the old->new maps
        pd = (
            np.concatenate(
                [jps_o[d, : int(jcounts_o[d])] for d in range(d_old)])
            if nrows else np.zeros(0, np.int64)
        ).astype(np.int64)
        pl = (
            np.concatenate(
                [jpl_o[d, : int(jcounts_o[d])] for d in range(d_old)])
            if nrows else np.zeros(0, np.int64)
        ).astype(np.int64)
        cand_flat = (
            np.concatenate(
                [jcand_o[d, : int(jcounts_o[d])] for d in range(d_old)])
            if nrows else np.zeros(0, np.int64)
        )
        isin = pl < n0_o[pd]
        rew_pd = np.empty(nrows, np.int64)
        rew_pl = np.empty(nrows, np.int64)
        fi = ioff_o[pd[isin]] + pl[isin]
        rew_pd[isin] = iowner[fi]
        rew_pl[isin] = new_il[fi]
        fj = joff_o[pd[~isin]] + (pl[~isin] - n0_o[pd[~isin]])
        rew_pd[~isin] = jd_of[fj]
        rew_pl[~isin] = n0_n[jd_of[fj]] + jj_of[fj]

        jmax_n = int(jcounts_n.max()) if nrows else 0
        jps_n = np.zeros((D_new, jmax_n), np.int32)
        jpl_n = np.zeros((D_new, jmax_n), np.int32)
        jcand_n = np.zeros((D_new, jmax_n), np.int32)
        jfp_n = np.full((D_new, jmax_n), np.uint64(U64_MAX))
        rew_pd_s, rew_pl_s = rew_pd[order], rew_pl[order]
        cand_s, fp_s = cand_flat[order], jfp_flat[order]
        for d in range(D_new):
            s, c = int(starts[d]), int(jcounts_n[d])
            jps_n[d, :c] = rew_pd_s[s: s + c]
            jpl_n[d, :c] = rew_pl_s[s: s + c]
            jcand_n[d, :c] = cand_s[s: s + c]
            jfp_n[d, :c] = fp_s[s: s + c]

        # --- frontier: journal-tail rows in new-journal order (or the
        # inits themselves when no wave has committed yet)
        if nrows:
            isf_s = is_front[order]
            gd_s, gj_s = glob_d[order], glob_j[order]
            fcounts_n = np.bincount(
                jowner[is_front], minlength=D_new).astype(np.int64)
            fmax_n = max(1, int(fcounts_n.max()))
            frontier_n = np.zeros((D_new, fmax_n, W), np.int32)
            fpos = np.zeros(D_new, np.int64)
            for k in range(nrows):
                if not isf_s[k]:
                    continue
                d = int(new_jd[k])
                frontier_n[d, fpos[d]] = frontier_o[
                    gd_s[k], int(gj_s[k] - front0[gd_s[k]])]
                fpos[d] += 1
        else:
            fcounts_n = n0_n.copy()
            fmax_n = max(1, int(fcounts_n.max()))
            frontier_n = np.zeros((D_new, fmax_n, W), np.int32)
            for d in range(D_new):
                for i, st in enumerate(init_by_shard_n[d]):
                    frontier_n[d, i] = st

        # --- seen: repartition + sort per new shard
        seen_parts: list[list[np.ndarray]] = [[] for _ in range(D_new)]
        for d in range(d_old):
            s = seen_o[d, : int(scounts_o[d])].astype(np.uint64)
            own = (s % np.uint64(D_new)).astype(np.int64)
            for dn in range(D_new):
                seen_parts[dn].append(s[own == dn])
        seen_n = [
            np.sort(np.concatenate(p)) if p else np.zeros(0, np.uint64)
            for p in seen_parts
        ]
        scounts_n = np.asarray([len(s) for s in seen_n], np.int64)
        assert (scounts_n == n0_n + jcounts_n).all(), (
            "reshard broke the seen = inits + journal invariant"
        )
        smax_n = max(1, int(scounts_n.max()))
        seen_h = np.full((D_new, smax_n), np.uint64(U64_MAX))
        for d, s in enumerate(seen_n):
            seen_h[d, : len(s)] = s

        cov_o = (
            np.asarray(ck["coverage"], np.int64)
            if "coverage" in ck
            else np.zeros((d_old, self.n_actions, 3), np.int64)
        )
        cov_n = np.zeros((D_new, self.n_actions, 3), np.int64)
        if self.n_actions:
            cov_n[0] = cov_o.sum(axis=0)

        out = dict(ck)
        out.update(
            version=np.int64(2),
            spec=self._ckpt_ident(),
            fcounts=fcounts_n, scounts=scounts_n, jcounts=jcounts_n,
            n0=n0_n, base_lgid=n0_n + jcounts_n - fcounts_n,
            frontier=frontier_n, seen=seen_h,
            jps=jps_n, jpl=jpl_n, jcand=jcand_n, jfp=jfp_n,
            init_by_shard_flat=np.concatenate(
                [np.stack(s) if s else np.zeros((0, W), np.int32)
                 for s in init_by_shard_n], axis=0),
            init_by_shard_count=np.asarray(
                [len(s) for s in init_by_shard_n], np.int64),
            coverage=cov_n,
        )
        return out

    # ---------------- host driver ----------------

    @traced_run("sharded")
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
        reshard: bool = True,
        stall_abort_factor: float | None = None,
        telemetry=None,
        preempt=None,
        chaos=None,
    ) -> ShardedResult:
        model, D, W, C = self.model, self.D, self.W, self.chunk
        t0 = time.perf_counter()
        # host spans (obs/trace.py): `init`, one
        # `wave` per loop iteration, `finish`; a wave's phases are
        # bracketed once, for the trace and the row — here `dispatch`,
        # `seen_merge` and (mid-wave, on shard loss) `fetch` once a chunk
        ph = self._ph
        ph.top("init")
        comp_run = COMPILES.snapshot()
        exhausted = True
        exit_cause = None
        self._ckpt_keep = checkpoint_keep
        self._chaos = chaos
        # telemetry rides the once-per-wave stats fetch the loop already
        # does — zero extra collectives or device syncs
        tel = telemetry if telemetry is not None else NULL_TELEMETRY
        # the mesh's device memory, the fullest chip's, by the allocator
        # (obs/memwatch.py): read here before any buffer, in init once
        # the buffers are made, at the end of every wave and at finish
        memwatch = MemWatch(tel, self.mesh.devices.flat)

        init = np.asarray(model.init_states())
        init_fps = np.asarray(
            jax.device_get(self.canon.fingerprints(init)), dtype=np.uint64)
        # dedup inits (first occurrence wins)
        order = np.argsort(init_fps, kind="stable")
        keep = np.ones(len(order), dtype=bool)
        sf = init_fps[order]
        dupm = np.zeros(len(order), dtype=bool)
        dupm[1:] = sf[1:] == sf[:-1]
        keep[order[dupm]] = False
        init_d, init_fps = init[keep], init_fps[keep]

        violation = None
        viol_site = None  # (shard, lgid)
        init_trace = None  # one-entry trace for a depth-0 violation

        ck_gen = 0
        ck_skipped: list[str] = []
        reshard_from: int | None = None
        if resume is not None:
            ck, ck_gen, ck_skipped = rckpt.load_npz(
                resume, keep=checkpoint_keep)
            ident = self._ckpt_ident()
            rckpt.check_spec(ck, ident, resume, allow_reshard=reshard)
            d_ck = rckpt.mesh_d_of(str(ck["spec"])) or D
            if "jfp" not in ck:
                # pre-v2 payload: rebuild the fingerprint lane once by
                # replay — the resumed run saves in v2, so this upgrade
                # cost is paid a single time per lineage
                ck = dict(ck)
                ck["jfp"] = self._recover_journal_fps(ck, d_ck)
            if d_ck != D:
                ck = self._reshard_payload(ck, d_ck)
                reshard_from = d_ck
            fcounts = np.asarray(ck["fcounts"], np.int64)
            scounts = np.asarray(ck["scounts"], np.int64)
            jcounts = np.asarray(ck["jcounts"], np.int64)
            n0 = np.asarray(ck["n0"], np.int64)
            base_lgid = np.asarray(ck["base_lgid"], np.int64)
            fmax, jmax = int(fcounts.max()), int(jcounts.max())
            self.FCAP = _next_cap(max(self.FCAP, fmax * self.HEADROOM),
                                  self.FCAP, self.MAX_FCAP, self.GROWTH, self.chunk)
            self.JCAP = _next_cap(max(self.JCAP, jmax + fmax * self.HEADROOM),
                                  self.JCAP, self.MAX_JCAP, self.GROWTH, 1)
            frontier_h = np.zeros((D, self.FCAP + self.EPAD, W), np.int32)
            frontier_h[:, :fmax] = ck["frontier"]
            jh = {k: np.zeros((D, self.JCAP + self.EPAD), np.int32) for k in
                  ("jps", "jpl", "jcand")}
            for k in jh:
                jh[k][:, :jmax] = ck[k]
            jfp_h = np.full((D, self.JCAP + self.EPAD), np.uint64(U64_MAX))
            jfp_h[:, :jmax] = np.asarray(ck["jfp"], np.uint64)[:, :jmax]
            seen_h = np.asarray(ck["seen"])
            self._lsm_seed(
                [seen_h[d, : scounts[d]] for d in range(D)]
            )
            counts = np.asarray(ck["init_by_shard_count"])
            flat = np.asarray(ck["init_by_shard_flat"])
            self._init_by_shard = []
            off = 0
            for d in range(D):
                self._init_by_shard.append(
                    [flat[off + i] for i in range(int(counts[d]))])
                off += int(counts[d])
            distinct = int(ck["distinct"])
            total = int(ck["total"])
            terminal = int(ck["terminal"])
            depth = int(ck["depth"])
            gen_prev = int(ck["gen_prev"])
            routed_prev = int(ck["routed_prev"])
            depth_counts = [int(x) for x in ck["depth_counts"]]
            # pre-coverage checkpoints resume with zeroed counters
            cov_hd = (
                np.asarray(ck["coverage"], dtype=np.int64)
                if "coverage" in ck
                else np.zeros((D, self.n_actions, 3), np.int64)
            )
            # per-shard generated/terminal/routed cums are not persisted
            # per shard; resume them as deltas from zero and add the saved
            # totals back via the *_base offsets
            stats_h0 = np.zeros((D, self.N_STATS), np.int64)
            stats_h0[:, 1] = jcounts
            gen_base, term_base, routed_base = gen_prev, terminal, routed_prev
            gen_prev = routed_prev = terminal = 0
            state = {
                "frontier": jax.device_put(frontier_h, self._sharding),
                "next_buf": jax.device_put(
                    np.zeros((D, self.FCAP + self.EPAD, W), np.int32),
                    self._sharding),
                "jps": jax.device_put(jh["jps"], self._sharding),
                "jpl": jax.device_put(jh["jpl"], self._sharding),
                "jcand": jax.device_put(jh["jcand"], self._sharding),
                "jfp": jax.device_put(jfp_h, self._sharding),
                "viol": jax.device_put(
                    np.full((D, max(1, len(self.invariants))), I32_MAX,
                            np.int32), self._sharding),
                "stats": jax.device_put(stats_h0, self._sharding),
            }
        else:
            frontier_h = np.zeros((D, self.FCAP + self.EPAD, W), np.int32)
            fcounts = np.zeros(D, np.int64)
            self._init_by_shard = [[] for _ in range(D)]
            per_shard_fps: list[list[int]] = [[] for _ in range(D)]
            for k in range(len(init_d)):
                d = int(init_fps[k] % D)
                frontier_h[d, fcounts[d]] = init_d[k]
                per_shard_fps[d].append(init_fps[k])
                self._init_by_shard[d].append(np.asarray(init_d[k]))
                fcounts[d] += 1
            self._lsm_seed(
                [np.asarray(a, np.uint64) for a in per_shard_fps]
            )
            scounts = fcounts.copy()
            jcounts = np.zeros(D, np.int64)
            n0 = fcounts.copy()  # per-shard init count (lgid < n0[d] => init)
            base_lgid = np.zeros(D, np.int64)
            gen_base = term_base = routed_base = 0

            viol_init = self._check_init(init_d)
            if viol_init is not None:
                violation, bad_idx = viol_init
                init_trace = [("Initial predicate", model.decode(init_d[bad_idx]))]

            state = {
                "frontier": jax.device_put(frontier_h, self._sharding),
                "next_buf": jax.device_put(
                    np.zeros((D, self.FCAP + self.EPAD, W), np.int32),
                    self._sharding),
                "jps": jax.device_put(
                    np.zeros((D, self.JCAP + self.EPAD), np.int32),
                    self._sharding),
                "jpl": jax.device_put(
                    np.zeros((D, self.JCAP + self.EPAD), np.int32),
                    self._sharding),
                "jcand": jax.device_put(
                    np.zeros((D, self.JCAP + self.EPAD), np.int32),
                    self._sharding),
                "jfp": jax.device_put(
                    np.full((D, self.JCAP + self.EPAD), np.uint64(U64_MAX)),
                    self._sharding),
                "viol": jax.device_put(
                    np.full((D, max(1, len(self.invariants))), I32_MAX, np.int32),
                    self._sharding),
                "stats": jax.device_put(
                    np.zeros((D, self.N_STATS), np.int64), self._sharding),
            }
            distinct = int(len(init_d))
            total = int(len(init))  # pre-dedup, matching BFSChecker seeding
            terminal = 0
            gen_prev = 0
            routed_prev = 0
            depth = 0
            depth_counts = [distinct]
            cov_hd = np.zeros((D, self.n_actions, 3), np.int64)

        tel.open_run(self._telemetry_manifest())
        if resume is not None:
            resume_events(tel, resume, ck_gen, ck_skipped, depth, distinct)
            if reshard_from is not None:
                tel.event(
                    "reshard", path=resume, from_d=reshard_from, to_d=D,
                    depth=depth, distinct=distinct)
        metrics: list[dict] | None = [] if collect_metrics else None
        last_ckpt = time.perf_counter()
        state["cov"] = jax.device_put(cov_hd, self._sharding)
        memwatch.init()
        dup_prev = 0
        tiers_prev = np.zeros((2,), np.int64)
        peak_rows = 0
        per_shard_dup = np.zeros(D, np.int64)
        wave_times: list[float] = []  # stall-watchdog rolling window

        while fcounts.sum() and violation is None:
            exit_cause = loop_exit(
                tel, preempt, chaos, depth, checkpoint_path, max_depth,
                time_budget_s, t0)
            if exit_cause is not None:
                exhausted = False
                break
            ph.wave(self._run_id, depth + 1, int(fcounts.sum()))
            tw = time.perf_counter()
            comp_wave = COMPILES.snapshot()
            # top-absorb capacity guard, per chip (see DeviceBFS.run):
            # conservative — a chip's wave-new count is bounded by FCAP
            # and by the WHOLE mesh's routed candidates (fp%D routing can
            # send every chip's successors to one owner)
            worst = int(scounts.max()) + min(self.FCAP, int(fcounts.sum()) * self.VC)
            if worst > self.TOPSZ:
                if checkpoint_path is not None:
                    self._save_checkpoint(
                        checkpoint_path, state, fcounts, scounts,
                        jcounts, n0, base_lgid, distinct, total,
                        terminal + term_base, depth,
                        gen_prev + gen_base, routed_prev + routed_base,
                        depth_counts, cov_hd,
                    )
                raise CapacityOverflow(
                    "sharded seen-set capacity overflow; raise max_seen_cap",
                    what=("seen",), bits=self.SEEN_OVF_BIT,
                    checkpoint_saved=checkpoint_path is not None,
                )
            fc_dev = jax.device_put(
                fcounts.astype(np.int32).reshape(D, 1), self._sharding)
            bl_dev = jax.device_put(
                base_lgid.astype(np.int32).reshape(D, 1), self._sharding)
            max_fc = int(fcounts.max())
            with tel.wave_annotation(depth + 1):
                for cursor in range(0, max_fc, C):
                    occ_dev = self._occ_dev()
                    with ph("dispatch"):
                        chunk_fn = self._get_chunk_fn(len(self._lsm.runs))
                        (state["next_buf"], state["jps"], state["jpl"],
                         state["jcand"], state["jfp"], state["viol"],
                         state["stats"], state["cov"], new_run,
                         ) = chunk_fn(
                            state["frontier"], fc_dev,
                            state["next_buf"], state["jps"],
                            state["jpl"], state["jcand"], state["jfp"],
                            state["viol"], state["stats"], state["cov"],
                            np.int32(cursor), occ_dev, bl_dev,
                            *self._lsm.runs,
                        )
                    with ph("seen_merge"):
                        self._lsm.insert(new_run)
                    if chaos is not None:
                        lost = chaos.shard_loss(depth + 1, D)
                        if lost is not None:
                            # deterministic stand-in for a device dying
                            # mid-wave: spill a wave-start checkpoint
                            # (jfp subtraction — mid-wave the LSM holds
                            # only the chunks already inserted, and the
                            # jfp lane recorded exactly those), classify,
                            # and let the supervisor reshard onto the
                            # survivors
                            with ph("fetch"):
                                # lint: sync-ok(wave-start spill on shard loss)
                                stats_mid = np.asarray(
                                    jax.device_get(state["stats"]))
                            saved = self._abort_wave_start(
                                checkpoint_path, state, stats_mid,
                                fcounts, scounts, jcounts, n0, base_lgid,
                                distinct, total, terminal + term_base,
                                depth, gen_prev + gen_base,
                                routed_prev + routed_base, depth_counts,
                                cov_hd,
                            )
                            tel.event(
                                "shard_lost", wave=depth + 1, depth=depth,
                                shard=int(lost), device_count=D,
                                checkpoint_saved=bool(saved))
                            raise ShardLost(
                                f"shard {lost} lost its device mid-wave "
                                f"{depth + 1} (chaos)",
                                shard=int(lost), checkpoint_saved=saved,
                            )
                # cov rides the same once-per-wave fetch — no extra
                # device_get calls with coverage on
                with ph("fetch"):
                    # lint: sync-ok(once-per-wave snapshot)
                    stats_h, viol_h, cov_w = jax.device_get(
                        (state["stats"], state["viol"], state["cov"]))
            stats_h = np.asarray(stats_h)  # [D, N_STATS]
            viol_h = np.asarray(viol_h)  # [D,K]
            new_d = stats_h[:, 0]
            ovf_bits = int(np.bitwise_or.reduce(stats_h[:, 4]))
            if chaos is not None:
                ovf_bits = chaos.ovf_bits(ovf_bits, depth + 1, 8)
            if ovf_bits:
                # the chunk loop already inserted this wave's fps into
                # the LSM, but the jfp lane journalled exactly what was
                # inserted — _abort_wave_start subtracts the aborted
                # wave back out and spills a wave-start checkpoint, so
                # a grown resume loses zero work (parity with DeviceBFS)
                stats_abort = stats_h.copy()
                stats_abort[:, 4] = ovf_bits  # incl. chaos-injected bits
                saved = self._abort_wave_start(
                    checkpoint_path, state, stats_abort, fcounts, scounts,
                    jcounts, n0, base_lgid, distinct, total,
                    terminal + term_base, depth, gen_prev + gen_base,
                    routed_prev + routed_base, depth_counts, cov_hd,
                )
                raise CapacityOverflow(
                    f"sharded BFS capacity overflow (bits={ovf_bits:05b}: "
                    "1=msg-slots 2=valid_per_state/valid_per_group "
                    "4=route_cap 8=frontier_cap 16=journal_cap)"
                    + (f"; wave-start checkpoint saved to {checkpoint_path}"
                       if saved else ""),
                    what=tuple(
                        name for bit, name in self.OVF_NAMES
                        if ovf_bits & bit),
                    bits=ovf_bits,
                    checkpoint_saved=saved,
                )
            # per-shard stall watchdog: a wave pathologically slower than
            # the rolling median flags a sick device (thermal throttle,
            # ICI link flap) — classify instead of hanging the fleet. The
            # ovf check above already passed, so the jfp lane holds the
            # whole wave and the wave-start spill is exact.
            wave_s_now = time.perf_counter() - tw
            if stall_abort_factor is not None and len(wave_times) >= 3:
                med = float(np.median(wave_times[-16:]))
                if med > 0 and wave_s_now > stall_abort_factor * med:
                    suspect = int(np.argmax(new_d))  # most-loaded shard
                    saved = self._abort_wave_start(
                        checkpoint_path, state, stats_h, fcounts, scounts,
                        jcounts, n0, base_lgid, distinct, total,
                        terminal + term_base, depth, gen_prev + gen_base,
                        routed_prev + routed_base, depth_counts, cov_hd,
                    )
                    tel.event(
                        "shard_stall", wave=depth + 1, depth=depth,
                        shard=suspect, wave_s=round(wave_s_now, 3),
                        median_wave_s=round(med, 3),
                        factor=round(wave_s_now / med, 3))
                    raise ShardStall(
                        f"wave {depth + 1} took {wave_s_now:.3f}s against "
                        f"a rolling median of {med:.3f}s "
                        f"(factor {wave_s_now / med:.1f} > "
                        f"{stall_abort_factor}); suspect shard {suspect}",
                        shard=suspect, wave_s=wave_s_now, median_s=med,
                        checkpoint_saved=saved,
                    )
            wave_times.append(wave_s_now)
            # commit only after the ovf check: an aborted wave keeps the
            # wave-start counters (consistent with what a checkpoint saved)
            cov_hd = np.asarray(cov_w, dtype=np.int64)
            global_new = int(new_d.sum())
            n_gen_cum = int(stats_h[:, 2].sum())
            wave_gen = n_gen_cum - gen_prev
            total += wave_gen
            gen_prev = n_gen_cum
            terminal = int(stats_h[:, 3].sum())
            wave_routed = int(stats_h[:, 5].sum()) - routed_prev
            routed_prev = int(stats_h[:, 5].sum())
            dup_cum = int(stats_h[:, 6].sum())
            wave_dup = dup_cum - dup_prev
            dup_prev = dup_cum
            per_shard_dup = stats_h[:, 6].copy()
            tiers_cum = stats_h[:, 7:9].sum(axis=0)
            wave_t3l, wave_t3f = (int(x) for x in tiers_cum - tiers_prev)
            tiers_prev = tiers_cum
            if global_new == 0:
                exit_cause = "exhausted"
                break
            depth += 1
            distinct += global_new
            depth_counts.append(global_new)
            peak_rows = max(peak_rows, global_new)
            base_lgid = n0 + stats_h[:, 1] - new_d
            scounts += new_d
            jcounts = stats_h[:, 1].copy()
            if self.invariants and (viol_h != I32_MAX).any():
                # first violated invariant (cfg order), lowest jidx,
                # lowest shard as the tie-break
                for k, name in enumerate(self.invariants):
                    col = viol_h[:, k]
                    if (col != I32_MAX).any():
                        d = int(np.argmin(col))
                        violation = name
                        viol_site = (d, int(n0[d] + col[d]))
                        break
            # reset the wave-new counter (stats was donated; rebuild)
            stats_h2 = stats_h.copy()
            stats_h2[:, 0] = 0
            state["stats"] = jax.device_put(stats_h2, self._sharding)
            state["frontier"], state["next_buf"] = (
                state["next_buf"], state["frontier"])
            prev_fcounts = fcounts
            fcounts = new_d.copy()
            if violation is None:
                # not after the wave that max_depth ends: no wave
                # would use the larger buffers
                if max_depth is None or depth < max_depth:
                    state = self._maybe_grow(state, fcounts, jcounts)
                # the floor is per chip: each holds ~1/D of the space
                if self._lsm.lanes() > max(4 * int(scounts.max()), 1 << 20):
                    with span("consolidate"):
                        self._lsm.consolidate(int(scounts.max()))
                if (
                    checkpoint_path is not None
                    and time.perf_counter() - last_ckpt > checkpoint_every_s
                ):
                    self._save_checkpoint(
                        checkpoint_path, state, fcounts, scounts,
                        jcounts, n0, base_lgid, distinct, total,
                        terminal + term_base, depth,
                        gen_prev + gen_base,
                        routed_prev + routed_base, depth_counts,
                        cov_hd,
                    )
                    last_ckpt = time.perf_counter()
            wave_s_val = time.perf_counter() - tw
            # the wave's brackets, read once a wave whoever listens
            # (engine.phase_clocks): here every chunk's dispatch and LSM
            # insert plus the blocking fetch are the wait on the device,
            # and growth, consolidation and the loop's bookkeeping are
            # what they leave of the wave, host_s
            ph_s = ph.take()
            comp_now = COMPILES.snapshot()
            # what the fullest chip's allocator holds now, and beside it
            # the PER-CHIP plan (the budget is one chip's HBM):
            # double-buffered frontier, 4-lane journal, this chip's LSM
            # lanes, the chunk scratch (payload + send/recv blocks)
            hbm = memwatch.wave(depth, {
                "frontier": 2 * (self.FCAP + self.EPAD) * 4 * W,
                "journal": (self.JCAP + self.EPAD) * (4 * 3 + 8),
                "seen": int(self._lsm.lanes()) * 8,
                "chunk": (self.VC + 2 * self.D * self.RC)
                * (4 * (W + 3) + 8),
            })
            if not (tel.active or metrics is not None or verbose):
                continue
            with ph("telemetry"):
                el = time.perf_counter() - t0
                wm = wave_row(
                    depth=depth, frontier=int(prev_fcounts.sum()),
                    new=global_new, distinct=distinct, generated=wave_gen,
                    generated_total=total, terminal=terminal + term_base,
                    canon=(wave_dup, wave_t3l, wave_t3f),
                    overflow_bits=ovf_bits,
                    lsm_runs=sum(self._lsm.occ),
                    lsm_lanes=int(self._lsm.lanes()),
                    wave_s=wave_s_val, elapsed_s=el,
                    A=self.A, expand_budget_ovf=(ovf_bits >> 1) & 1,
                    hbm=hbm,
                    **phase_clocks(ph_s, comp_wave, comp_now),
                    # this engine's own: the all-to-all's lanes and bytes
                    # (payload widened to W+3 by the routed rank column)
                    # and the per-shard balance
                    a2a_lanes=wave_routed,
                    a2a_bytes=wave_routed * (4 * (W + 3) + 8),
                    shard_new=[int(x) for x in new_d],
                    shard_new_min=int(new_d.min()),
                    shard_new_max=int(new_d.max()),
                )
                tel.wave(wm)
                if tel.active:
                    tel.coverage(self._coverage_fields(
                        depth, cov_hd, scounts, depth_counts))
                if metrics is not None:
                    metrics.append(wm)
                if verbose:
                    print(
                        f"depth {depth}: +{global_new} distinct={distinct} "
                        f"a2a={wave_routed} lanes "
                        f"balance={new_d.min()}/{new_d.max()} "
                        f"({distinct/el:.0f} distinct/s)",
                        file=sys.stderr)

        ph.top("finish")
        if (checkpoint_path is not None and violation is None
                and not exhausted):
            self._save_checkpoint(
                checkpoint_path, state, fcounts, scounts, jcounts, n0,
                base_lgid, distinct, total, terminal + term_base, depth,
                gen_prev + gen_base, routed_prev + routed_base, depth_counts,
                cov_hd,
            )

        # fetch journals for trace reconstruction
        jps_h = np.asarray(jax.device_get(state["jps"]))
        jpl_h = np.asarray(jax.device_get(state["jpl"]))
        jcand_h = np.asarray(jax.device_get(state["jcand"]))
        self._journals = (jps_h, jpl_h, jcand_h, jcounts.copy(), n0.copy())

        dt = time.perf_counter() - t0
        stats_run = run_stats(
            self, comp_run, ph, memwatch, frontier_peak_rows=peak_rows,
            coverage=cov_hd, dedup_plan=self._dedup_plan(),
            canon_tier3_local=int(tiers_prev[0]),
            canon_tier3_full=int(tiers_prev[1]),
        )
        if violation is not None:
            exit_cause = "violation"
        elif exit_cause is None:
            exit_cause = "exhausted"
        # fleet aggregates (satellite of the telemetry PR): in-chunk
        # duplicate totals + per-shard skew, from the SAME host stats the
        # loop already fetched — also returned on ShardedResult.stats
        fleet_rate = round(dup_prev / max(1, gen_prev), 4)
        fleet_cov = cov_hd.sum(axis=0)
        fleet_stats = {
            "canon_dup_lanes": dup_prev,
            "canon_dup_rate": fleet_rate,
            "shard_dup_lanes": [int(x) for x in per_shard_dup],
            "shard_distinct": [int(x) for x in scounts],
            "shard_skew": round(
                int(scounts.max()) / max(1, int(scounts.min())), 3),
            "coverage": [[int(x) for x in row] for row in fleet_cov],
            # what the run loaded into the process (obs/compiles.py)
            **stats_run,
        }
        if tel.active:
            tel.coverage(
                self._coverage_fields(depth, cov_hd, scounts, depth_counts),
                final=True)
        tel.close_run(summary_fields(
            self, "sharded",
            exit_cause=exit_cause, violation=violation,
            distinct=distinct, total=total, depth=depth,
            terminal=terminal + term_base, seconds=dt,
            exhausted=exhausted and violation is None,
            peak_frontier_cap=self.FCAP, peak_journal_cap=self.JCAP,
            seen_lanes=int(self._lsm.lanes()),
            canon_dup_rate=fleet_rate,
            stats=stats_run, programs=COMPILES.programs(comp_run),
            # this engine's own (the schema allows extra keys)
            shard_dup_lanes=fleet_stats["shard_dup_lanes"],
            shard_skew=fleet_stats["shard_skew"],
        ))
        trace = init_trace
        if violation is not None and viol_site is not None:
            trace = self.reconstruct_trace(viol_site)
        return ShardedResult(
            distinct=distinct,
            total=total,
            depth=depth,
            depth_counts=depth_counts,
            violation_invariant=violation,
            seconds=dt,
            states_per_sec=distinct / dt if dt > 0 else 0.0,
            terminal=terminal + term_base,
            exhausted=exhausted and violation is None,
            trace=trace,
            metrics=metrics,
            stats=fleet_stats,
            coverage=(fleet_stats["coverage"] if self.n_actions else None),
            exit_cause=exit_cause,
        )

    def _coverage_fields(self, depth, cov_hd, scounts, depth_counts) -> dict:
        """Coverage-event payload (obs.events.COVERAGE_KEYS), fleet-summed
        from the per-shard [D, n_actions, 3] counters. Dedup gauges come
        from the shared LSM geometry (identical on every chip)."""
        fleet = cov_hd.sum(axis=0)
        occ = list(self._lsm.occ)
        return {
            "depth": depth,
            "actions": [[int(x) for x in row] for row in fleet],
            "actions_total": self.n_actions,
            "actions_fired": int(np.count_nonzero(fleet[:, 1]))
            if self.n_actions else 0,
            "seen_lanes": [
                int(r.shape[-1]) for r, o in zip(self._lsm.runs, occ) if o
            ],
            "seen_real": int(scounts.sum()),
            "probe_runs": int(sum(occ)),
            "frontier_hist": [int(x) for x in depth_counts],
        }

    def _telemetry_manifest(self) -> dict:
        """Run-provenance fields of the telemetry manifest event."""
        return manifest_fields(
            self, "sharded", self.mesh.devices.flat[0], device_count=self.D,
            frontier_cap=self.FCAP, journal_cap=self.JCAP,
            max_seen_cap=self.MAX_SCAP, valid_cap=self.VC,
            dedup_plan=self._dedup_plan(),
        )

    def _dedup_plan(self) -> dict:
        """util.dedup_plan of a chip's chunk program as it stands: every
        LSM level against the D*RC lanes a chip receives."""
        return dedup_plan(
            [r.shape[-1] for r in self._lsm.runs], self.D * self.RC)

    def _check_init(self, init_d: np.ndarray):
        """(invariant name, index of first bad init state) or None."""
        for name in self.invariants:
            ok = np.asarray(jax.device_get(self.model.invariants[name](init_d)))
            bad = np.nonzero(~ok)[0]
            if len(bad):
                return name, int(bad[0])
        return None

    # ---------------- trace reconstruction ----------------

    def reconstruct_trace(self, site: tuple[int, int]) -> list[tuple[str, dict]]:
        """Walk (shard, local gid) parent pointers to an Init state, then
        replay the recorded candidate actions forward (same semantics as
        DeviceBFS.reconstruct_trace; journal entries just live per shard)."""
        model = self.model
        jps_h, jpl_h, jcand_h, jcounts, n0 = self._journals
        d, lgid = site
        chain: list[int] = []
        while lgid >= n0[d]:
            j = int(lgid - n0[d])
            assert j < jcounts[d], "journal index out of range"
            chain.append(int(jcand_h[d, j]))
            d, lgid = int(jps_h[d, j]), int(jpl_h[d, j])
        chain.reverse()
        state = self._init_by_shard[d][int(lgid)]
        out = [("Initial predicate", model.decode(state))]
        expand1 = jax.jit(model._expand1)
        for cand in chain:
            succs, valid, rank, _ovf = jax.device_get(expand1(state))
            assert valid[cand], "journalled candidate not enabled on replay"
            state = np.asarray(succs[cand])
            out.append(
                (model.action_label(int(rank[cand]), cand), model.decode(state)))
        return out
