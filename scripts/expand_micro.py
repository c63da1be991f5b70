"""Microbench: what does ONE chunk's successor expansion cost, by path?

Reproduces the "expand wall" numbers behind the guard-first sparse
expansion (models/base.py SparseExpandMixin): the dense path runs every
per-action kernel over all chunk*A candidate lanes and gathers the
VC-compacted survivors, while the guard-first path runs the DCE-derived
guard pass (valid/rank/ovf only, no W-wide rows) over the same grid and
then constructs successors just for the enabled worklist, segmented by
group by one sort of one int32 key and vmapped per action group over a
static budget plan. Both paths produce bit-identical [VC, W] compacted
blocks, and both take their worklist as the engines do, from
``engine.compact_chunk`` (the valid lanes by one sort, PR 50).

``--compaction N [N ...]`` times that idiom alone, on the chip: a stream
compaction of N int32 lanes as one single-key ``lax.sort`` and as the
cumsum and ``.at[dst].set`` scatter it replaced, K times inside one
program at two K, so the call's own 0.8 ms cancels: ns a lane of each
(ROADMAP S3, S8 d and S13 price their sorts from it).

``--fill [CELL ...]`` times what the apply pass costs against what a
chunk keeps (PR 56), on the chip: one chunk's ``sparse_apply`` of a
benchmark cell's model at the cell's chunk, VC and plan, on a real
frontier, with the worklist filled to 1/16 ... 1 of VC in the real
chunk's own mix of groups, under each of ``TILE_RULES`` (the tile a trip
of a block builds, ``models/base.py::apply_tile``, swapped in the
process), ms a call from K calls inside one program at two K; beside it
the last gather alone (``tiled_rows``) at the same fills and tiles. The
rule in the tree is the one this read fixed.

Two dense baselines are timed, because they differ enormously:

  dense_mat  vmap of the full kernels MATERIALIZING the [chunk, A, W]
             successor tensor (what any consumer that keeps raw succs
             pays, and what the legacy engines paid while bag_put
             carried a lax.sort — sorts block producer fusion);
  dense      the same kernels jitted TOGETHER with the compaction
             gather. With the branchless shift-insert bag_put (ops/
             bag.py) every kernel is elementwise, so XLA fuses the
             producer into the gather and computes kernels only for
             gathered rows — the compiler discovers the guard-first
             schedule implicitly. Fusion is a backend heuristic with no
             contract (it vanished with one lax.sort in the kernel);
             the explicit guard-first path makes the sparse schedule a
             guarantee, bounds worst-case work by the audited budget
             plan (overflow aborts instead of silently densifying), and
             exports enabled_density / expand_budget_ovf gauges.

``speedup_mat`` is guard-first vs dense_mat (the lane-ratio claim);
``speedup`` is vs the fused dense baseline — on backends whose fusion
already sparsifies the gather it hovers near or below 1x, which is the
honest cost of the explicit worklist bookkeeping. The grid sweeps the
apply budget (``--vpg``, per-state units; ``loose`` keeps the
overflow-impossible bound) against chunk size on a REAL reachable
frontier (guard density is whatever the model exhibits there — the
``density`` column reports it).

Defaults mirror the raft3 PROFILE workload geometry (3 servers, 2
values, msg_slots=32 -> A=56); ``--vpg tuned`` is that workload's
measured per-group budget dict, ``--vpg 8`` a flat per-group cap of 8
per state, ``loose`` the overflow-impossible bound (all chunk*A lanes,
grouped — isolates the grouping overhead with zero lane savings).

Usage:
  python scripts/expand_micro.py [--chunk 1024 4096]
                                 [--vpg loose 8 tuned]
                                 [--servers 3] [--values 2]
                                 [--elections 3] [--restarts 1]
                                 [--msg-slots 32] [--depth 10]
                                 [--reps 5] [--platform cpu]
  python scripts/expand_micro.py --compaction 16384 65536 217088
  python scripts/expand_micro.py --fill raft3-wide pull3-full

Writes chiprun_out/expand_micro.json (device provenance + one row per
(chunk, vpg) cell), where a chip run's results come back.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _time(fn, *args, reps=5):
    """Median wall seconds of fn(*args) with block_until_ready."""
    import jax

    jax.block_until_ready(fn(*args))  # warm / compile
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def bench_cell(model, batch_h, vpg, reps):
    import jax
    import jax.numpy as jnp
    import numpy as np

    C = len(batch_h)
    A, W = model.A, model.layout.W
    VC = min(C * A, C * 16)
    batch = jnp.asarray(batch_h)

    from raft_tpu.checker.engine import compact_chunk

    # -- dense path: full kernels over every lane + compaction gather
    def dense(b):
        succs, valid, rank, ovf = jax.vmap(model._expand1)(b)
        flatc, _sel, selv, _rank, _ovf, _built = compact_chunk(
            None, None, b, succs, valid, rank,
            len(model.ACTION_NAMES), jnp.sum(valid), VC)
        return flatc, selv

    # -- guard-first path, split so each phase gets its own row
    guards = jax.jit(lambda b: jax.vmap(model.guards1)(b))

    def worklist(valid):
        sel = jnp.sort(jnp.where(
            valid.reshape(-1), jnp.arange(C * A, dtype=jnp.int32), C * A))[:VC]
        return sel, sel < C * A

    plan = model.sparse_plan(C, VC, vpg)
    apply_j = jax.jit(
        lambda b, s, sv: model.sparse_apply(b, s, sv, plan)
    )

    dense_j = jax.jit(dense)
    # full-kernel vmap that must materialize [C, A, W] — no gather for
    # the producer to fuse into (valid/rank fold into the same fusion,
    # so succs-only is the honest materialized cost)
    dense_mat_j = jax.jit(lambda b: jax.vmap(model._expand1)(b)[0])
    wl_j = jax.jit(worklist)
    valid, _, _ = guards(batch)
    sel, selv = wl_j(valid)
    flatc_d, _ = dense_j(batch)
    flatc_s, ovf, _built = apply_j(batch, sel, selv)
    parity = bool(
        np.array_equal(np.asarray(flatc_d), np.asarray(flatc_s))
    )
    density = float(jnp.sum(valid)) / (C * A)

    row = {
        "chunk": C, "A": A, "W": W, "vc": VC,
        "vpg": "loose" if vpg is None else vpg,
        "plan_lanes": int(sum(plan)),
        "dense_lanes": C * A,
        "density": round(density, 4),
        "budget_ovf": bool(ovf),
        "parity": parity,
        "dense_ms": round(_time(dense_j, batch, reps=reps) * 1e3, 3),
        "dense_mat_ms": round(
            _time(dense_mat_j, batch, reps=reps) * 1e3, 3),
        "guards_ms": round(_time(guards, batch, reps=reps) * 1e3, 3),
        "apply_ms": round(
            _time(apply_j, batch, sel, selv, reps=reps) * 1e3, 3),
    }
    sparse_ms = max(row["guards_ms"] + row["apply_ms"], 1e-6)
    row["speedup"] = round(row["dense_ms"] / sparse_ms, 2)
    row["speedup_mat"] = round(row["dense_mat_ms"] / sparse_ms, 2)
    return row


def bench_compaction(lanes, reps):
    """ns a lane of one stream compaction of ``lanes`` int32 lanes, as
    the single-key sort ``expand`` runs and as the scatter it replaced.
    Each form runs K times inside one program (the mask re-drawn from
    the loop's counter, the result folded into the carry so nothing is
    dead) at K = 8 and K = 72: the difference is 64 compactions with no
    call in it."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    N = lanes
    idx = jnp.arange(N, dtype=jnp.int32)

    def mask(i):  # a third of the lanes valid, another third each turn
        return ((idx * 7 + i * 13) % 3) == 0

    def by_sort(i, acc):
        sel = lax.sort(jnp.where(mask(i), idx, N))
        return acc + sel[N // 4]

    def by_scatter(i, acc):
        m = mask(i)
        pos = jnp.cumsum(m) - 1
        sel = (
            jnp.full((N + 1,), N, jnp.int32)
            .at[jnp.where(m, pos, N)]
            .set(idx)[:N]
        )
        return acc + sel[N // 4]

    row = {"lanes": N}
    for name, body in (("sort", by_sort), ("scatter", by_scatter)):
        ts = {}
        for K in (8, 72):
            fn = jax.jit(lambda a, K=K, body=body: lax.fori_loop(
                0, K, body, a))
            ts[K] = _time(fn, jnp.int32(0), reps=reps)
        row[f"{name}_ns_per_lane"] = round(
            (ts[72] - ts[8]) / 64 / N * 1e9, 3)
        row[f"{name}_ms"] = round((ts[72] - ts[8]) / 64 * 1e3, 4)
    # equal answers, outside the timed loop
    m = mask(3)
    want = jnp.full((N + 1,), N, jnp.int32).at[
        jnp.where(m, jnp.cumsum(m) - 1, N)].set(idx)[:N]
    row["parity"] = bool(jnp.array_equal(
        lax.sort(jnp.where(m, idx, N)), want))
    return row


# the tile one trip of a block of ``rows`` builds at chunk C: the rule
# in the tree, ``fine`` (``models/base.py::apply_tile``, whose docstring
# quotes this grid's read on the chip), beside the ones it was chosen
# among; ``whole`` is the one-shot pass at the budget, under a loop of
# at most one trip
TILE_RULES = {
    "whole": lambda rows, C: rows,
    "quarter": lambda rows, C: max(rows // 4, min(rows, C)),
    "eighth": lambda rows, C: max(rows // 8, min(rows, C)),
    "chunk": lambda rows, C: min(rows, C),
    "fine": lambda rows, C: max(1, rows // 16, min(rows, C // 4)),
}
FILLS = (1 / 16, 1 / 8, 1 / 4, 1 / 2, 1.0)


def _frontier(model, rows, depth=64):
    """``rows`` reachable states of ``model``: the first wave of a
    manual wave loop with exact-bytes dedup that holds as many, or the
    wave at ``depth``, tiled if it is short. Guard density and the
    groups' mix on real states are the honest input, random bit
    patterns are not."""
    import jax
    import numpy as np

    frontier = model.init_states()
    seen = set()
    B, W = 1024, model.layout.W
    for _ in range(depth):
        nxt = []
        for off in range(0, len(frontier), B):
            cs = frontier[off:off + B]
            nb = len(cs)
            if nb < B:
                cs = np.concatenate([cs, np.repeat(cs[-1:], B - nb, axis=0)])
            succs, valid, _, _ = jax.device_get(model.expand(cs))
            valid = np.array(valid)
            valid[nb:] = False
            flat = np.array(succs).reshape(-1, W)
            for i in np.nonzero(valid.reshape(-1))[0]:
                t = flat[i].tobytes()
                if t not in seen:
                    seen.add(t)
                    nxt.append(flat[i])
        if not nxt:
            break
        frontier = np.array(nxt, dtype=np.int32)
        if len(frontier) >= rows:
            break
    return np.tile(frontier, (-(-rows // len(frontier)), 1))[:rows]


def _filled(model, valid, n, rng):
    """An ascending worklist of ``n`` flat candidate lanes in the mix of
    groups the chunk's ``valid`` lanes have: each group's enabled lanes
    first, topped up with disabled lanes of the same group (a kernel
    builds a row whatever its guard says, at the same cost)."""
    import numpy as np

    C, A = valid.shape
    flat = np.arange(C * A, dtype=np.int32)
    cand = flat % A
    vflat = valid.reshape(-1)
    groups = model.sparse_groups()
    member = [(cand >= g.off) & (cand < g.off + g.n) for g in groups]
    counts = np.array([int((m & vflat).sum()) for m in member])
    want = np.floor(counts / counts.sum() * n).astype(int)
    want[int(np.argmax(counts))] += n - want.sum()
    picked = []
    for m, k in zip(member, want):
        on, off = flat[m & vflat], flat[m & ~vflat]
        k = min(k, len(on) + len(off))
        take = rng.permutation(on)[:k]
        picked += [take, rng.permutation(off)[:k - len(take)]]
    return np.sort(np.concatenate(picked)).astype(np.int32)


def bench_fill(cell_name, reps):
    """Rows of {rule, fill, apply_ms, gather_ms, rows_built, ...} for one
    benchmark cell's model, chunk, VC and plan."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from benchmark import adapter
    from raft_tpu.models import base

    bench = os.path.join(ROOT, "benchmark")
    with open(os.path.join(bench, "workloads", f"{cell_name}.json")) as f:
        cell = json.load(f)
    cfg_dir = os.path.join(bench, "configs", cell["config"])
    with open(os.path.join(cfg_dir, "config.json")) as f:
        config = json.load(f)
    eng = adapter.build_engine(
        os.path.join(cfg_dir, config["cfg"]), "device",
        cell["engine_params"], jax.devices()[:1])
    model, C, VC, plan = eng.model, eng.chunk, eng.VC, eng._plan
    A, W = model.A, model.layout.W
    rng = np.random.default_rng(56)
    batch = jnp.asarray(_frontier(model, C))
    valid = np.asarray(jax.jit(jax.vmap(model.guards1))(batch)[0])
    sels = {}
    for fill in FILLS:
        lanes = _filled(model, valid, int(VC * fill), rng)
        sels[fill] = jnp.asarray(np.concatenate(
            [lanes, np.full(VC - len(lanes), C * A, np.int32)]))
    total = sum(plan)
    # the last gather alone: VC random rows of a block of the tree's
    # size, VC rows, a tile and the zeros row
    block = jnp.asarray(
        rng.integers(0, 1 << 20, (VC + C + 1, W)).astype(np.int32))
    rowsel = jnp.asarray(rng.integers(0, VC, (VC,)).astype(np.int32))

    def per_call(fn, *args):
        """ms a call: K calls inside one program (K a traced count, so
        one compile), at K = 2 and K = 10; the difference is 8 calls
        with no dispatch in them."""
        ts = {K: _time(fn, jnp.int32(K), *args, reps=reps) for K in (2, 10)}
        return (ts[10] - ts[2]) / 8 * 1e3

    rows = []
    rule_in_tree = base.apply_tile
    want = {}  # the one-shot pass's rows a fill: ``whole`` runs first
    try:
        for rule, tile in TILE_RULES.items():
            base.apply_tile = tile

            def apply_k(K, b, s):
                def body(i, acc):
                    # the chunk re-read through the counter: nothing
                    # here is the loop's invariant
                    flatc, _ovf, built = model.sparse_apply(
                        b + (i >> 20), s, s < C * A, plan)
                    return acc + flatc[i].sum(dtype=jnp.int32) + built
                return lax.fori_loop(jnp.int32(0), K, body, jnp.int32(0))

            def gather_k(K, blk, r, n):
                def body(i, acc):
                    out = base.tiled_rows(
                        blk, r + (i >> 20), n, tile(VC, C))
                    return acc + out[i].sum(dtype=jnp.int32)
                return lax.fori_loop(jnp.int32(0), K, body, jnp.int32(0))

            apply_j, gather_j = jax.jit(apply_k), jax.jit(gather_k)
            once = jax.jit(
                lambda b, s: model.sparse_apply(b, s, s < C * A, plan))
            for fill, sel in sels.items():
                got, _ovf, built = jax.device_get(once(batch, sel))
                want.setdefault(fill, got)
                n = int((np.asarray(sel) < C * A).sum())
                row = {
                    "workload": cell_name, "chunk": C, "A": A, "W": W,
                    "vc": VC, "plan_rows": total, "rule": rule,
                    "tile_of_vc": tile(VC, C), "fill": fill, "lanes": n,
                    "rows_built": int(built),
                    "parity": bool(np.array_equal(got, want[fill])),
                    "apply_ms": round(per_call(apply_j, batch, sel), 4),
                    "gather_ms": round(per_call(
                        gather_j, block, rowsel, jnp.int32(n)), 4),
                }
                rows.append(row)
                print(json.dumps(row), flush=True)
    finally:
        base.apply_tile = rule_in_tree
    return rows


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chunk", type=int, nargs="+", default=[1024, 4096])
    ap.add_argument("--vpg", nargs="+", default=["loose", "8", "tuned"])
    ap.add_argument("--servers", type=int, default=3)
    ap.add_argument("--values", type=int, default=2)
    ap.add_argument("--elections", type=int, default=3)
    ap.add_argument("--restarts", type=int, default=1)
    ap.add_argument("--msg-slots", type=int, default=32)
    ap.add_argument("--depth", type=int, default=10)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--platform", default=None)
    ap.add_argument("--compaction", type=int, nargs="+", default=None,
                    metavar="LANES",
                    help="time one compaction of LANES int32 lanes by "
                         "sort and by scatter, and nothing else")
    ap.add_argument("--fill", nargs="*", default=None, metavar="CELL",
                    help="time sparse_apply of these benchmark cells "
                         "(default raft3-wide pull3-full) against the "
                         "worklist's fill under each tile rule, and "
                         "nothing else")
    args = ap.parse_args()

    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    if args.fill is not None:
        rows = []
        for cell in args.fill or ["raft3-wide", "pull3-full"]:
            rows += bench_fill(cell, args.reps)
        dev = jax.devices()[0]
        path = os.path.join(ROOT, "chiprun_out", "expand_micro.json")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump({
                "meta": {
                    "device": str(dev), "platform": dev.platform,
                    "device_kind": getattr(dev, "device_kind", None),
                    "when": time.strftime("%Y-%m-%d %H:%M:%S"),
                    "reps": args.reps,
                    "note": "ms a call of one chunk's sparse_apply "
                            "(apply_ms) and of its last gather alone "
                            "(gather_ms), 8 calls inside one program, "
                            "by tile rule and by the share of VC the "
                            "worklist holds",
                },
                "rows": rows,
            }, f, indent=1)
        print(f"wrote {path}")
        return
    if args.compaction:
        rows = [bench_compaction(n, args.reps) for n in args.compaction]
        for row in rows:
            print(json.dumps(row), flush=True)
        path = os.path.join(ROOT, "chiprun_out", "expand_compaction.json")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump({"device": str(jax.devices()[0]), "rows": rows}, f,
                      indent=1)
        print(f"wrote {path}")
        return
    from raft_tpu.models.raft import RaftModel, RaftParams

    model = RaftModel(RaftParams(
        n_servers=args.servers, n_values=args.values,
        max_elections=args.elections, max_restarts=args.restarts,
        msg_slots=args.msg_slots,
    ))
    frontier = _frontier(model, max(args.chunk), args.depth)

    rows = []
    hdr = (f"{'chunk':>6} {'vpg':>6} {'lanes':>8} {'dense':>10} "
           f"{'densemat':>10} {'guards':>10} {'apply':>10} "
           f"{'vs_fused':>8} {'vs_mat':>8} {'ovf':>5}")
    print(hdr)
    for C in args.chunk:
        batch_h = frontier[:C]
        for v in args.vpg:
            # "tuned" = per-group budgets measured on this geometry
            if v == "loose":
                vpg = None
            elif v == "tuned":
                vpg = {
                    "Restart": 2.25, "RequestVote": 1.25,
                    "BecomeLeader": 0.1875, "ClientRequest": 1.0,
                    "AdvanceCommitIndex": 0.109375,
                    "AppendEntries": 0.953125, "HandleMessage": 5.75,
                }
            else:
                vpg = float(v)
            row = bench_cell(model, batch_h, vpg, args.reps)
            row["vpg"] = v  # the grid label, not the expanded dict
            rows.append(row)
            if not row["parity"] and not row["budget_ovf"]:
                raise AssertionError(
                    f"sparse/dense parity failed in-budget: {row}")
            print(f"{row['chunk']:>6} {str(row['vpg']):>6} "
                  f"{row['plan_lanes']:>8} {row['dense_ms']:>8.2f}ms "
                  f"{row['dense_mat_ms']:>8.2f}ms "
                  f"{row['guards_ms']:>8.2f}ms {row['apply_ms']:>8.2f}ms "
                  f"{row['speedup']:>7.2f}x {row['speedup_mat']:>7.2f}x "
                  f"{str(row['budget_ovf']):>5}",
                  flush=True)

    out = {
        "meta": {
            "device": str(jax.devices()[0]),
            "when": time.strftime("%Y-%m-%d %H:%M:%S"),
            "model": model.name,
            "params": {
                "n_servers": args.servers, "n_values": args.values,
                "max_elections": args.elections,
                "max_restarts": args.restarts,
                "msg_slots": args.msg_slots,
            },
            "frontier_depth": args.depth,
            "reps": args.reps,
            "note": "ms per chunk of successor expansion on a real "
                    "reachable frontier; dense_mat = full kernels "
                    "materializing [chunk, A, W] (no gather to fuse "
                    "into), dense = same kernels jitted with the "
                    "compaction gather (with the branchless bag_put the "
                    "backend fuses the producer into the gather — an "
                    "implicit, contract-free sparse schedule), "
                    "guard-first = DCE guard pass + per-group budgeted "
                    "apply over the enabled worklist (the explicit, "
                    "budget-audited schedule; bit-identical output, "
                    "parity checked per cell unless the budget "
                    "overflowed). speedup is vs dense, speedup_mat vs "
                    "dense_mat",
        },
        "rows": rows,
    }
    path = os.path.join(ROOT, "chiprun_out", "expand_micro.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
