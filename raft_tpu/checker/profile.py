"""Per-stage profiling of the DeviceBFS hot loop (SURVEY.md §5.1).

The chunk pipeline is one fused XLA program in production; to attribute
time we re-run each stage as its own jitted function on REAL buffers
captured from a warmed run (a depth-capped run spills a checkpoint, and
the profiler rebuilds the chunk inputs from it). Stages mirror
``DeviceBFS._chunk_step`` 1:1:

  null_dispatch  a no-op jit call: the dispatch floor every other
                 row also pays once (the rendered table's `net` column
                 and all shares have it subtracted)
  guards       the guard pass of guard-first sparse expansion: valid/
               rank/ovf over the dense [chunk, A] candidate grid with
               no W-wide successor rows (DCE-derived from _expand1);
               0.0 for models without the sparse expand contract
  apply        the budgeted apply pass: per-group vmapped successor
               construction over the compacted enabled worklist only
               (models/base.py sparse_apply); 0.0 when not applicable
  expand       vmap of the full per-action successor kernels over every
               [chunk, A] lane — the production expand for legacy dense
               models, a RETIRED diagnostic row (excluded from the
               stage sum, like `scatter`) when the sparse path is
               active, kept so regenerated profiles show the dense-vs-
               sparse cost side by side
  compact      valid-lane compaction (cumsum + one-hot select; under
               the sparse path the [VC, W] successor gather lives in
               `apply`, so this row times the worklist build alone)
  canon        MEMOIZED canonical fingerprints against the warm run's
               live memo table — the realistic mixed hit/miss path a
               production chunk pays (probe + tiered canon of the
               misses + insert). Unmemoized canonicalizers time the
               plain tiered canon here instead.
  canon_memo_hit  the same memoized call against a table that already
               holds every key of this chunk — the pure-hit floor
               (one raw hash + probe, no tiered canon at all)
  canon_tier3_local  the tier-3 resolve alone (tie-group-local blocks +
               full-table drain, ops/symmetry.py _tier3_apply) with
               tiers 1+2 precomputed outside the timer; 0.0 when the
               canonicalizer has no pruned tier path
  probe        membership probe of the seen run (searchsorted)
  run_emit     sorting the chunk's new fingerprints into its R0-lane run
  emit_append  the production emit (round 6): dense-prefix compaction of
               the survivors to a [VC, W] block plus ONE donated
               dynamic_update_slice cursor append per buffer (frontier,
               jparent, jcand) — checker/util.py emit_append
  scatter      RETIRED diagnostic row: the pre-round-6 emit (arbitrary-
               index scatters into the full-capacity frontier/journal
               buffers), kept so regenerated profiles show old-vs-new
               emit cost side by side against archived PROFILE artifacts
  invariants   batched invariant kernels
  lsm_merge_2r0  one R0+R0 run merge (sort of 2*R0 lanes), fitting the
                 n log n constant for the AMORTIZED per-chunk merge cost

Per-wave cost model: chunks_per_wave * (fused chunk + amortized merge).
``fused_chunk`` times the production program for cross-checking (the sum
of stages normally OVERESTIMATES it — XLA fuses away intermediates).
The per-chunk stage sum counts PRODUCTION stages once: canon_memo_hit
and canon_tier3_local are diagnostic re-measures of sub-paths already
inside the ``canon`` row (the all-hit floor and the tier-3 resolve), and
``scatter`` is the retired emit no production chunk executes — all three
are reported (their visibility is the point) but excluded from the sum
and from ``canon_share_of_stage_sum``.
"""

from __future__ import annotations

import math
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.hashing import U64_MAX, ne_u64, sort_u64
from .device_bfs import DeviceBFS
from .util import dense_prefix_sel, emit_append, probe_sorted as _probe

# every stage key profile_stages() promises to report (the tier-1 smoke
# test asserts each one is present so stage accounting can't silently
# rot when the chunk pipeline changes)
DECLARED_STAGES = (
    "null_dispatch",
    "guards",
    "apply",
    "expand",
    "compact",
    "canon",
    "canon_memo_hit",
    "canon_tier3_local",
    "probe",
    "run_emit",
    "emit_append",
    "scatter",
    "invariants",
    "lsm_merge_2r0",
    "fused_chunk",
)


def _time_donated(fn, make_args, reps: int = 5) -> float:
    """Median wall seconds of fn(*make_args()) where fn donates some of
    its arguments: the args are rebuilt OUTSIDE the timed window each
    rep (donation invalidates them), so the row measures the in-place
    program alone, not the rebuild."""
    out = fn(*make_args())
    jax.block_until_ready(out)  # warm / compile
    ts = []
    for _ in range(reps):
        args = make_args()
        jax.block_until_ready(args)
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _time(fn, *args, reps: int = 5, inner: int = 1) -> float:
    """Median wall seconds of fn(*args) with block_until_ready."""
    out = fn(*args)
    jax.block_until_ready(out)  # warm / compile
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(inner):
            out = fn(*args)
        jax.block_until_ready(out)
        ts.append((time.perf_counter() - t0) / inner)
    return float(np.median(ts))


def profile_stages(
    model,
    invariants: tuple[str, ...] = (),
    symmetry: bool = True,
    chunk: int = 1024,
    frontier_cap: int = 1 << 17,
    seen_cap: int = 1 << 21,
    warm_depth: int = 8,
    reps: int = 5,
    telemetry=None,
    **caps,
) -> dict:
    """Profile the chunk pipeline on a realistic frontier.

    Runs a depth-capped BFS to ``warm_depth`` (checkpoint spill), then
    rebuilds one representative chunk's inputs from the spill and times
    each stage. Returns a dict with per-stage seconds, per-wave totals
    and workload shape facts. ``telemetry`` threads a raft_tpu.obs
    Telemetry through the warm run (its manifest event records the
    profiled engine's exact geometry and identity).
    """
    dev = DeviceBFS(
        model, invariants=invariants, symmetry=symmetry, chunk=chunk,
        frontier_cap=frontier_cap, seen_cap=seen_cap, **caps,
    )
    with tempfile.TemporaryDirectory() as td:
        ck_path = os.path.join(td, "warm.npz")
        res = dev.run(max_depth=warm_depth, checkpoint_path=ck_path,
                      telemetry=telemetry)
        if not os.path.exists(ck_path):
            raise RuntimeError(
                f"workload exhausted at depth {res.depth} < warm_depth="
                f"{warm_depth}; no frontier left to profile"
            )
        ck = np.load(ck_path, allow_pickle=False)
        frontier_h = np.asarray(ck["frontier"])  # [fcount, W]
        seen_h = np.asarray(ck["seen"])  # [scount]
    # caps may have grown during the warm run
    C, A, W, VC = dev.chunk, dev.A, dev.W, dev.VC
    FCAP, JCAP, R0 = dev.FCAP, dev.JCAP, dev.R0
    fcount, scount = len(frontier_h), len(seen_h)

    batch_h = frontier_h[:C]
    if len(batch_h) < C:
        batch_h = np.concatenate(
            [batch_h, np.repeat(batch_h[-1:], C - len(batch_h), axis=0)]
        )
    batch = jnp.asarray(batch_h)
    # the warmed seen-set as the single sorted run production probes
    # (round-5 seen design: one U64_MAX-padded run, no LSM ladder)
    dev._seed_seen(np.sort(seen_h.astype(np.uint64)))
    runs = (dev._seen,)
    occ_dev = dev._occ_one
    occ_runs = runs
    use_memo = getattr(dev, "_use_memo", False)

    out: dict = {
        "workload": {
            "model": model.name,
            "warm_depth": int(res.depth),
            "frontier": int(fcount),
            "seen": int(scount),
            "distinct": int(res.distinct),
        },
        "geometry": {
            "chunk": C, "A": A, "W": W, "VC": VC, "R0": R0,
            "FCAP": FCAP, "JCAP": JCAP, "lsm_levels": len(runs),
            "perms": int(dev.canon.P), "symmetry": bool(symmetry),
            "canon_memo_cap": int(dev.MCAP) if use_memo else 0,
            "refine_rounds": int(getattr(dev.canon, "refine_rounds", 1)),
        },
        "stages_s": {},
    }
    st = out["stages_s"]

    # ---- stage 0: dispatch floor ----
    null_j = jax.jit(lambda x: x + 1)
    st["null_dispatch"] = _time(null_j, jnp.zeros((8,), jnp.int32), reps=reps)

    sparse = getattr(dev, "_sparse", False)

    # ---- stage 1: guard pass (sparse path only) ----
    if sparse:
        guards_j = jax.jit(lambda b: jax.vmap(model.guards1)(b))
        st["guards"] = _time(guards_j, batch, reps=reps)
    else:
        st["guards"] = 0.0
    st["apply"] = 0.0  # placeholder keeps table order; measured below

    # ---- stage 1b: dense expand (production for legacy models; a
    # retired diagnostic when the sparse path is active) ----
    expand = jax.jit(lambda b: jax.vmap(model._expand1)(b))
    st["expand"] = _time(expand, batch, reps=reps)
    succs, valid, _rank, _ovf = expand(batch)

    # ---- stage 2: compact. Under the sparse path the [VC, W]
    # successor gather moved into `apply`, so this times the worklist
    # build alone; the dense variant keeps the gather. ----
    def compact_sel(valid):
        vflat = valid.reshape(-1)
        vpos = jnp.cumsum(vflat) - 1
        sdst = jnp.where(vflat, jnp.minimum(vpos, VC), VC)
        sel = (
            jnp.full((VC + 1,), C * A, jnp.int32)
            .at[sdst]
            .set(jnp.arange(C * A, dtype=jnp.int32))[:VC]
        )
        return sel, sel < C * A

    def compact(succs, valid):
        sel, selv = compact_sel(valid)
        flatp = jnp.concatenate(
            [succs.reshape(C * A, W), jnp.zeros((1, W), jnp.int32)], axis=0
        )
        return flatp[sel], selv

    compact_j = jax.jit(compact)
    sel_j = jax.jit(compact_sel)
    if sparse:
        st["compact"] = _time(sel_j, valid, reps=reps)
    else:
        st["compact"] = _time(compact_j, succs, valid, reps=reps)
    flatc, selv = compact_j(succs, valid)

    # ---- stage 2b: budgeted apply over the compacted worklist (the
    # production successor construction when sparse; its output is
    # bit-identical to the dense gather, so downstream stages reuse
    # flatc either way) ----
    if sparse:
        sel, _ = sel_j(valid)
        apply_j = jax.jit(
            lambda b, s, sv: model.sparse_apply(b, s, sv, dev._plan)
        )
        st["apply"] = _time(apply_j, batch, sel, selv, reps=reps)

    # ---- stage 3: canonical fingerprints ----
    if use_memo:
        fmemo = jax.jit(dev.canon.fingerprints_memo)
        # the warm run left its LAST wave's memo table resident
        # (DeviceBFS.run keeps the final output buffer) — timing
        # against it is the realistic mixed hit/miss path
        m_warm = dev._memo.table
        st["canon"] = _time(fmemo, flatc, selv, m_warm, reps=reps)
        fps, m_hit, _, _ = fmemo(flatc, selv, m_warm)
        # after one pass the table holds every key of this chunk: the
        # second call is the pure-hit floor
        st["canon_memo_hit"] = _time(fmemo, flatc, selv, m_hit, reps=reps)
    else:
        canon_j = jax.jit(dev.canon._fingerprints)
        st["canon"] = _time(canon_j, flatc, reps=reps)
        fps = jnp.where(selv, canon_j(flatc), U64_MAX)
        st["canon_memo_hit"] = 0.0

    # ---- stage 3b: tier-3 resolve alone (tie-group-local + full-table
    # drain), with the tier-1/2 running min precomputed outside ----
    c = dev.canon
    if (
        c.symmetry and getattr(c, "prune", False)
        and getattr(c, "mode", "full") != "full"
    ):
        view = flatc[:, : c.VL]
        sig = jax.jit(c._signatures)(view)
        pre = jax.jit(c._tier_pre)(view, sig)
        t3_j = jax.jit(c._tier3_apply)
        st["canon_tier3_local"] = _time(t3_j, view, sig, *pre, reps=reps)
    else:
        st["canon_tier3_local"] = 0.0

    # ---- stage 4: binary-search the occupied LSM runs. Production
    # (util.first_new) does this only to a run past the merge crossover
    # and sorts the others with the chunk, so on the chip this bucket
    # over-counts by the gathers it no longer pays (PERF.md, PR 25) ----
    def probe_all(f, *rs):
        hit = jnp.zeros(f.shape, bool)
        for r in rs:
            hit = hit | _probe(r, f)
        return hit

    st["probe"] = _time(jax.jit(probe_all), fps, *occ_runs, reps=reps)

    # ---- stage 5: emit the chunk's sorted run ----
    def run_emit(f):
        nr = sort_u64(f)
        if R0 > VC:
            nr = jnp.concatenate(
                [nr, jnp.full((R0 - VC,), U64_MAX, jnp.uint64)]
            )
        return nr

    st["run_emit"] = _time(jax.jit(run_emit), fps, reps=reps)

    # ---- stage 5b: the production emit — dense-prefix compaction +
    # one donated cursor append per buffer (mirrors _chunk_step step 5;
    # the donated carries are rebuilt outside the timer) ----
    def emit_stage(flatc, fps, nb, jp, jc):
        new = ne_u64(fps, U64_MAX)
        n_new = jnp.sum(new)
        npos = (jnp.cumsum(new) - 1).astype(jnp.int32)
        esel = dense_prefix_sel(new, npos, VC)
        blk = jnp.concatenate(
            [flatc, jnp.zeros((1, W), jnp.int32)], axis=0
        )[esel]
        lanes = jnp.concatenate([npos, jnp.zeros((1,), jnp.int32)])[esel]
        nb, _ = emit_append(nb, blk, jnp.int32(0), n_new, FCAP)
        jp, _ = emit_append(jp, lanes, jnp.int32(0), n_new, JCAP)
        jc, _ = emit_append(jc, lanes, jnp.int32(0), n_new, JCAP)
        return nb, jp, jc

    emit_j = jax.jit(emit_stage, donate_argnums=(2, 3, 4))
    st["emit_append"] = _time_donated(
        emit_j,
        lambda: (
            flatc, fps,
            jnp.zeros((FCAP + VC, W), jnp.int32),
            jnp.zeros((JCAP + VC,), jnp.int32),
            jnp.zeros((JCAP + VC,), jnp.int32),
        ),
        reps=reps,
    )

    # ---- stage 5c (RETIRED, diagnostic): the pre-round-6 emit — full-
    # capacity arbitrary-index scatters. Self-contained (allocates its
    # own buffers in-program) so the row stays comparable with archived
    # PROFILE artifacts; excluded from the stage sum. ----
    def scatter(flatc, fps):
        new = ne_u64(fps, U64_MAX)
        npos = (jnp.cumsum(new) - 1).astype(jnp.int32)
        bdst = jnp.where(new, jnp.minimum(npos, FCAP), FCAP)
        nb = jnp.zeros((FCAP + 1, W), jnp.int32).at[bdst].set(flatc)
        jdst = jnp.where(new, jnp.minimum(npos, JCAP), JCAP)
        jp = jnp.zeros((JCAP + 1,), jnp.int32).at[jdst].set(bdst)
        return nb, jp

    st["scatter"] = _time(jax.jit(scatter), flatc, fps, reps=reps)

    # ---- stage 6: invariants ----
    if invariants:
        inv_j = jax.jit(
            lambda v: [model.invariants[n](v) for n in invariants]
        )
        st["invariants"] = _time(inv_j, flatc, reps=reps)
    else:
        st["invariants"] = 0.0

    # ---- LSM merge costs (level 0 measured; series fitted n log n) ----
    r0a = run_emit(fps)
    st["lsm_merge_2r0"] = _time(
        jax.jit(lambda a, b: sort_u64(jnp.concatenate([a, b]))), r0a, r0a,
        reps=reps,
    )
    null = st["null_dispatch"]
    a_fit = max(st["lsm_merge_2r0"] - null, 1e-6) / (2 * R0 * math.log2(2 * R0))
    n_levels = max(1, len(runs))
    amortized = sum(
        a_fit * (R0 << (l + 1)) * math.log2(R0 << (l + 1)) / (1 << (l + 1))
        for l in range(n_levels)
    )

    # ---- the fused production program, for cross-check ----
    frontier_d = jnp.asarray(
        np.concatenate([
            frontier_h,
            np.zeros((FCAP + VC - fcount, W), np.int32),
        ])
    )

    def fused_once():
        # donated args (next_buf, journal, viol, stats, memo) must be
        # rebuilt per call — donation invalidates their buffers. The
        # memo is a COPY of the warm table so the fused row reflects the
        # production mixed hit/miss path.
        nb = jnp.zeros((FCAP + VC, W), jnp.int32)
        jp = jnp.zeros((JCAP + VC,), jnp.int32)
        jc = jnp.zeros((JCAP + VC,), jnp.int32)
        viol = jnp.full((max(1, len(invariants)),), np.int32(2**31 - 1), jnp.int32)
        stats = jnp.zeros((dev.N_STATS,), jnp.int64)
        memo = jnp.array(m_warm) if use_memo else dev._memo.reset()
        cov = jnp.zeros((dev.n_actions, 3), jnp.int64)
        args = [frontier_d, nb, jp, jc, viol, stats, memo, cov,
                np.int32(0), np.int32(min(fcount, C)), np.int32(0),
                occ_dev, jnp.asarray(True), *runs]
        jax.block_until_ready(args)
        t0 = time.perf_counter()
        r = dev._chunk_fn(*args)
        jax.block_until_ready(r)
        return time.perf_counter() - t0

    fused_once()  # compile
    st["fused_chunk"] = float(np.median([fused_once() for _ in range(reps)]))

    # PRODUCTION stages only: canon_memo_hit / canon_tier3_local re-time
    # sub-paths already inside the `canon` row (the all-hit floor and the
    # tier-3 resolve), and `scatter` is the retired emit no production
    # chunk executes — adding them would double-count (or resurrect)
    # work. A chunk pays `canon` and `emit_append` once each. Under the
    # sparse path the production expansion is guards + apply and the
    # dense `expand` row joins the diagnostic set.
    if sparse:
        timed = ["guards", "apply", "compact", "canon", "probe",
                 "run_emit", "emit_append"]
        out["diag_rows"] = [
            "canon_memo_hit", "canon_tier3_local", "scatter", "expand",
        ]
    else:
        timed = [
            "expand", "compact", "canon", "probe", "run_emit",
            "emit_append",
        ]
        out["diag_rows"] = [
            "canon_memo_hit", "canon_tier3_local", "scatter",
        ]
    if invariants:
        timed.append("invariants")
    # each TIMED stage row pays one dispatch floor (floored at 0 so a
    # not-applicable 0.0 stage can't subtract from the sum)
    chunk_sum = sum(max(0.0, st[k] - null) for k in timed)
    n_chunks = max(1, (fcount + C - 1) // C)
    per_chunk = st["fused_chunk"] + amortized
    canon_sum = max(0.0, st["canon"] - null)
    # successor-expansion share: guards + apply under the sparse path,
    # the dense expand row otherwise (the guard-first acceptance gauge)
    exp_sum = sum(
        max(0.0, st[k] - null)
        for k in (("guards", "apply") if sparse else ("expand",))
    )
    out["per_wave_s"] = {
        "chunks_per_wave": n_chunks,
        "stage_sum_per_chunk": round(chunk_sum, 6),
        "canon_share_of_stage_sum": round(
            canon_sum / chunk_sum, 4) if chunk_sum else 0.0,
        "expand_share_of_stage_sum": round(
            exp_sum / chunk_sum, 4) if chunk_sum else 0.0,
        "fused_per_chunk": round(st["fused_chunk"], 6),
        "lsm_merge_amortized_per_chunk": round(amortized, 6),
        "wave_estimate": round(n_chunks * per_chunk, 6),
        "merge_share": round(amortized / per_chunk, 4),
    }
    return out


def render(prof: dict) -> str:
    w, g, s = prof["workload"], prof["geometry"], prof["stages_s"]
    lines = [
        f"workload: {w['model']} depth={w['warm_depth']} "
        f"frontier={w['frontier']} seen={w['seen']}",
        f"geometry: chunk={g['chunk']} VC={g['VC']} R0={g.get('R0')} "
        f"FCAP={g['FCAP']} lsm_levels={g.get('lsm_levels')} "
        f"perms={g['perms']}",
        f"{'stage':<18}{'ms':>10}{'net ms':>10}{'share':>8}",
    ]
    skip = ("fused_chunk", "lsm_merge_2r0", "null_dispatch")
    # diagnostic rows: canon sub-path re-measures, the RETIRED scatter
    # emit, and (sparse-path profiles) the retired dense expand — shown
    # (relative to the production sum) but not part of it, see
    # per_wave_s accounting. Archived PROFILE.json files predate the
    # diag_rows field; the historical tuple is their fallback.
    diag = tuple(prof.get(
        "diag_rows", ("canon_memo_hit", "canon_tier3_local", "scatter")
    ))
    null = s.get("null_dispatch", 0.0)
    tot = sum(max(0.0, v - null) for k, v in s.items()
              if k not in skip and k not in diag)
    for k, v in s.items():
        if v == 0.0 and k in ("guards", "apply"):
            continue  # not-applicable rows (dense-only models)
        net = max(0.0, v - null)
        share = net / tot if k not in skip and tot else 0
        mark = "*" if k in diag else ""
        lines.append(
            f"{k + mark:<18}{v * 1e3:>10.2f}{net * 1e3:>10.2f}"
            f"{share:>8.1%}"
        )
    if any(k in s for k in diag):
        lines.append("(* diagnostic row — canon sub-path re-measure or "
                     "a retired path; not in the stage sum)")
    lines.append(
        "(net ms = ms - null_dispatch: the dispatch floor every "
        "row pays once; shares are over net production rows)"
    )
    pw = prof["per_wave_s"]
    lines.append(
        f"wave: {pw['chunks_per_wave']} chunks x "
        f"({pw['fused_per_chunk']*1e3:.2f} ms fused + "
        f"{pw['lsm_merge_amortized_per_chunk']*1e3:.2f} ms amortized merge)"
    )
    return "\n".join(lines)
