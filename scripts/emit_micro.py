"""Microbench: what does ONE chunk's frontier emit cost, by strategy?

Reproduces the "capacity-sized scatter penalty" claim that used to live
as a folklore number in device_bfs.py: scattering VC survivor rows into
a full-capacity [FCAP, W] buffer with arbitrary destination indices
(`.at[dst].set()`) versus the round-6 production path (dense-prefix
compaction to a [VC, W] block + ONE donated dynamic_update_slice at the
frontier cursor) versus a sort-based emit (stable argsort of the keep
mask + gather + the same cursor append).

All three variants write the same rows to the same destinations; all
donate the big buffer so XLA may update in place; the donated buffer is
rebuilt OUTSIDE the timed window each rep. The scatter's cost scales
with FCAP (the whole buffer is touched by the lowering), the appends'
with VC — sweeping FCAP at fixed VC is the point of the grid.

``--journal N [N ...]`` times, on the chip, what the emit stage needs
of a chunk's N compacted lanes besides the rows: the survivors' lanes
(``esel``) and the journal's two blocks. Three forms of it, each run K
times inside one program at K = 8 and K = 72 (the difference is 64
repetitions with no call in them): ``gather``, the scatter into an index
buffer and the two gathers through it that the stage ran until PR 52
(with ``rank_gather``, the third gather of that kind, the action rank
through the compaction's ``sel``, timed alone); ``two_sorts``, a sort of
one int32 key for the lanes and another for their ``sel``; and
``payload_sort``, one sort of the lanes' key carrying ``sel``. The
cheaper sort form is the one in ``DeviceBFS._st_finish``.

``--fill N [N ...]`` times the same way what the canon stage's in-chunk
dedup (``ops/symmetry.py::fingerprints_by_raw_view``) does with the N
raw-sorted lanes of a chunk-step besides the sorts it always ran:
``fill_gather``, the u64 gather ``canon_rep[rank]`` (with ``rank``'s
``cumsum``) that handed every lane its representative's fingerprint
until PR 54; ``payload_sort``, ``argsort(~head, stable=True)``, the
sort with a payload that laid the representatives out in raw order until
then; ``perm_sort``, the one sort of one int32 key that lays them out in
lane order now, after which nothing is filled; and
``perm_sort_unstable``, the same sort with ``is_stable=False`` (its keys
are distinct, so the answer is the same, and the compiler carries no
``iota`` beside the key to break ties by).

Usage:
  python scripts/emit_micro.py [--vc 32768 65536] [--fcap 262144 4194304]
                               [--w 64] [--reps 5] [--density 0.5]
                               [--platform cpu]
  python scripts/emit_micro.py --journal 16384 32768 65536
  python scripts/emit_micro.py --fill 16384 32768 65536

Writes chiprun_out/emit_micro.json (device provenance + one row per
(VC, FCAP) cell), where a chip run's results come back.

W defaults to 64 (not a workload's real row width) to keep the 4M-row
cell around 1 GiB/buffer; pass --w to match a specific workload.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _time_donated(fn, make_args, reps):
    """Median wall seconds of fn(*make_args()), args rebuilt outside the
    timed window each rep (donation consumes them)."""
    import jax

    ts = []
    for _ in range(reps):
        args = make_args()
        jax.block_until_ready(args)
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def _ns_per_lane(form, lanes, reps):
    """(ns a lane, ms) of one ``form(i)``: K of them inside one program
    at K = 8 and K = 72, every output summed into the carry so nothing
    is dead, the difference over its 64 repetitions."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def body(i, acc):
        return acc + sum(jnp.sum(x).astype(jnp.int32) for x in form(i))

    fn = jax.jit(lambda k, a: lax.fori_loop(0, k, body, a))
    jax.block_until_ready(fn(jnp.int32(1), jnp.int32(0)))  # compile
    ts = {K: _time_donated(
        fn, lambda K=K: (jnp.int32(K), jnp.int32(0)), reps)
        for K in (8, 72)}
    return (round((ts[72] - ts[8]) / 64 / lanes * 1e9, 3),
            round((ts[72] - ts[8]) / 64 * 1e3, 4))


def bench_cell(vc, fcap, w, reps, density, rng):
    import jax
    import jax.numpy as jnp

    from raft_tpu.checker.util import dense_prefix_sel, emit_append

    new_h = rng.random(vc) < density
    n_new = int(new_h.sum())
    new = jnp.asarray(new_h)
    npos = jnp.asarray((new_h.cumsum() - 1).astype("int32"))
    flatc = jnp.asarray(rng.integers(1, 1 << 20, size=(vc, w), dtype="int64")
                        .astype("int32"))
    count = jnp.int32(0)

    # -- retired production emit: arbitrary-index scatter, drop row fcap
    def scatter_full(nb):
        dst = jnp.where(new, jnp.minimum(count + npos, fcap), fcap)
        return nb.at[dst].set(flatc)

    # -- round-6 production emit: compact to a dense [VC, W] block, one
    #    dynamic_update_slice at the cursor
    def compact_dus(nb):
        esel = dense_prefix_sel(new, vc)
        blk = jnp.concatenate(
            [flatc, jnp.zeros((1, w), jnp.int32)], axis=0)[esel]
        nb, _ = emit_append(nb, blk, count, jnp.int32(n_new), fcap)
        return nb

    # -- alternative: stable sort of the keep mask compacts survivors to
    #    the front (argsort of ~new), then the same cursor append
    def sort_emit(nb):
        order = jnp.argsort(~new, stable=True)
        blk = flatc[order]
        nb, _ = emit_append(nb, blk, count, jnp.int32(n_new), fcap)
        return nb

    variants = {
        # scatter needs only the drop row past fcap; the appends need a
        # full VC-row drop region (same geometry the engines carry)
        "scatter_full": (scatter_full, fcap + 1),
        "compact_dus": (compact_dus, fcap + vc),
        "sort_emit": (sort_emit, fcap + vc),
    }
    row = {"vc": vc, "fcap": fcap, "n_new": n_new}
    for name, (fn, rows) in variants.items():
        jf = jax.jit(fn, donate_argnums=(0,))
        make = lambda rows=rows: (jnp.zeros((rows, w), jnp.int32),)
        jax.block_until_ready(jf(*make()))  # compile outside the timer
        row[f"{name}_ms"] = round(_time_donated(jf, make, reps) * 1e3, 3)
    row["scatter_over_compact"] = round(
        row["scatter_full_ms"] / max(row["compact_dus_ms"], 1e-6), 1)
    return row


def _scatter_sel(new, n):
    """``util.dense_prefix_sel`` as it was until PR 52: a cumsum and a
    scatter into an n + 1 index buffer."""
    import jax.numpy as jnp

    edst = jnp.where(new, jnp.cumsum(new) - 1, n)
    return (jnp.full((n + 1,), n, jnp.int32).at[edst]
            .set(jnp.arange(n, dtype=jnp.int32))[:n])


def bench_journal(lanes, reps):
    """ns a lane of the survivors' lanes and journal blocks of one
    chunk-step of ``lanes`` compacted lanes, by form (module docstring).
    The grid behind ``sel`` is 4 * lanes wide with A = 53, seven eighths
    of the lanes are valid and a third of those new, re-drawn from the
    loop's counter; every output is summed into the carry so nothing is
    dead."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    N, A = lanes, 53
    CA = 4 * N
    lane = jnp.arange(N, dtype=jnp.int32)
    n_valid = N * 7 // 8
    rank_grid = (jnp.arange(CA, dtype=jnp.int32) * 5) % 13

    def draw(i):
        sel = jnp.where(lane < n_valid, 4 * lane + i % 4, CA)
        new = (((lane * 7 + i * 13) % 3) == 0) & (lane < n_valid)
        return sel, new

    def blocks(ssel, n_new):  # the journal's blocks of the sorted sel
        live = lane < n_new
        return (jnp.where(live, 1000 + ssel // A, 0),
                jnp.where(live, ssel % A, 0))

    def gather(i):
        sel, new = draw(i)
        esel = _scatter_sel(new, N)
        z = jnp.zeros((1,), jnp.int32)
        return (esel, jnp.concatenate([1000 + sel // A, z])[esel],
                jnp.concatenate([sel % A, z])[esel])

    def two_sorts(i):
        sel, new = draw(i)
        esel = lax.sort(jnp.where(new, lane, N))
        ssel = lax.sort(jnp.where(new, sel, CA))
        return (esel, *blocks(ssel, jnp.sum(new)))

    def payload_sort(i):
        sel, new = draw(i)
        esel, ssel = lax.sort((jnp.where(new, lane, N), sel), num_keys=1)
        return (esel, *blocks(ssel, jnp.sum(new)))

    def rank_gather(i):
        sel, _ = draw(i)
        return (jnp.concatenate(
            [rank_grid, jnp.full((1,), -1, jnp.int32)])[sel],)

    forms = {"gather": gather, "two_sorts": two_sorts,
             "payload_sort": payload_sort, "rank_gather": rank_gather}
    row = {"lanes": N}
    for name, form in forms.items():
        row[f"{name}_ns_per_lane"], row[f"{name}_ms"] = _ns_per_lane(
            form, N, reps)
    # equal answers, outside the timed loop
    want = [jax.device_get(x) for x in jax.jit(gather)(jnp.int32(3))]
    row["parity"] = all(
        all((jax.device_get(g) == w).all() for g, w in zip(
            jax.jit(form)(jnp.int32(3)), want))
        for form in (two_sorts, payload_sort))
    return row


def bench_fill(lanes, reps):
    """ns a lane of what the in-chunk dedup does with ``lanes``
    raw-sorted lanes, by form (module docstring). ``order`` is a
    permutation of the lanes and two thirds of the sorted lanes head a
    segment, both re-drawn from the loop's counter; the buffer of the
    representatives' fingerprints is drawn once."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from raft_tpu.ops.hashing import U64_MAX, join_u64, split_u64

    N = lanes
    assert N & (N - 1) == 0, "a power of two: the permutation is affine"
    lane = jnp.arange(N, dtype=jnp.int32)
    canon_rep = join_u64(
        (lane * 40503).astype(jnp.uint32), (lane * 25173).astype(jnp.uint32))

    def draw(i):
        order = (lane * 5 + i * 7919) % N  # 5 is odd: a permutation
        head = ((lane * 7 + i * 13) % 3) != 0
        return order, head

    def fill_gather(i):
        _order, head = draw(i)
        rank = jnp.maximum(jnp.cumsum(head.astype(jnp.int32)) - 1, 0)
        return split_u64(jnp.where(head, canon_rep[rank], U64_MAX))

    def payload_sort(i):
        _order, head = draw(i)
        return (jnp.argsort(~head, stable=True).astype(jnp.int32),)

    def perm_sort(i, stable=True):
        order, head = draw(i)
        return (lax.sort(jnp.where(head, order, N + order),
                         is_stable=stable),)

    def perm_sort_unstable(i):
        return perm_sort(i, stable=False)

    row = {"lanes": N}
    for name, form in (("fill_gather", fill_gather),
                       ("payload_sort", payload_sort),
                       ("perm_sort", perm_sort),
                       ("perm_sort_unstable", perm_sort_unstable)):
        row[f"{name}_ns_per_lane"], row[f"{name}_ms"] = _ns_per_lane(
            form, N, reps)
    # the lay-out is the one the engines rely on: the representatives'
    # lanes first and rising, then B + the rest's, rising
    order, head = (jax.device_get(x) for x in draw(jnp.int32(3)))
    perm = jax.device_get(jax.jit(perm_sort)(jnp.int32(3))[0])
    n_rep = int(head.sum())
    row["parity"] = bool(
        (perm[:n_rep] == sorted(order[head])).all()
        and (perm[n_rep:] == [N + x for x in sorted(order[~head])]).all()
        and (perm == jax.device_get(
            jax.jit(perm_sort_unstable)(jnp.int32(3))[0])).all())
    return row


def _write_rows(name, rows):
    import jax

    for row in rows:
        print(json.dumps(row), flush=True)
    path = os.path.join(ROOT, "chiprun_out", name)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump({"device": str(jax.devices()[0]), "rows": rows}, f,
                  indent=1)
    print(f"wrote {path}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--vc", type=int, nargs="+", default=[32768, 65536])
    ap.add_argument("--fcap", type=int, nargs="+",
                    default=[262144, 4194304])
    ap.add_argument("--w", type=int, default=64)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--density", type=float, default=0.5)
    ap.add_argument("--platform", default=None)
    ap.add_argument("--journal", type=int, nargs="+", default=None,
                    metavar="LANES",
                    help="time the survivors' lanes and journal blocks "
                         "of LANES compacted lanes by form, and nothing "
                         "else")
    ap.add_argument("--fill", type=int, nargs="+", default=None,
                    metavar="LANES",
                    help="time the in-chunk dedup's fill gather, the "
                         "payload sort and the one-key sort of LANES "
                         "raw-sorted lanes, and nothing else")
    args = ap.parse_args()

    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    if args.journal:
        return _write_rows(
            "emit_journal.json",
            [bench_journal(n, args.reps) for n in args.journal])
    if args.fill:
        return _write_rows(
            "canon_fill.json", [bench_fill(n, args.reps) for n in args.fill])
    import numpy as np

    rng = np.random.default_rng(0)
    rows = []
    hdr = (f"{'VC':>8} {'FCAP':>9} {'scatter':>10} {'compact':>10} "
           f"{'sort':>10} {'scatter/compact':>16}")
    print(hdr)
    for vc in args.vc:
        for fcap in args.fcap:
            row = bench_cell(vc, fcap, args.w, args.reps, args.density, rng)
            rows.append(row)
            print(f"{row['vc']:>8} {row['fcap']:>9} "
                  f"{row['scatter_full_ms']:>8.2f}ms "
                  f"{row['compact_dus_ms']:>8.2f}ms "
                  f"{row['sort_emit_ms']:>8.2f}ms "
                  f"{row['scatter_over_compact']:>15.1f}x", flush=True)

    out = {
        "meta": {
            "device": str(jax.devices()[0]),
            "when": time.strftime("%Y-%m-%d %H:%M:%S"),
            "w": args.w, "reps": args.reps, "density": args.density,
            "note": "ms per emit of one chunk's survivors into a "
                    "frontier-shaped [rows, W] i32 buffer; all variants "
                    "donate the buffer and rebuild it outside the timer",
        },
        "rows": rows,
    }
    path = os.path.join(ROOT, "chiprun_out", "emit_micro.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
