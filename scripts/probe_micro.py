"""Microbench: looking 65,536 fingerprints up in a sorted run, by
merging or by searching — the measurement behind
``checker/util.py::MERGE_LANES_PER_QUERY``.

For VC queries and one run of S = 2^lo .. 2^hi lanes it times, on whatever
device JAX has (a chip, or ``--platform cpu`` to rehearse):

  probe          ``probe_sorted``: the binary search, log2(S)+1 gathers
                 of VC lanes. What ``util.first_new`` adds for a run
                 above the crossover (``chunk_only`` is the rest of it)
  merge          ``util.first_new`` with the run under the crossover: one
                 stable 2-key u32 sort of S+VC lanes with the lane index
                 as payload, and the single-operand sort that brings the
                 bits back to lane order. What the run adds is this
                 minus ``chunk_only``
  chunk_only     ``util.first_new`` with no run at all: first occurrence
                 in the chunk by the same two sorts over VC lanes
  merge_3key     ``merge`` with the lane index as a third key in place
                 of the stable sort
  rank_sort      ``jnp.searchsorted(..., method="sort")`` + the equality
                 gather: the library's own sort-based lookup (an
                 (S+VC)-update scatter and a u64 argsort)

(the last two at ``--few`` sizes only: every sort is a minute of compile)
and, at the shape the raft3 cells had until PR 36 (a 2^18-lane seen run
and the three levels, 2^16..2^18 lanes, of the wave's ladder of sorted
runs), all four runs merged against all four searched. Every variant's
answer is checked against numpy before it is timed (that first call's
seconds are the compile's). Milliseconds are the host's clock round
``block_until_ready``, median and fastest of ``--reps`` calls.

``--cells`` times instead what the dedup stage's lookup costs a
chunk-step in the benchmark's cells (``benchmark/workloads``; a shape
for each (seen run, VC, frontier) the cells' first wave programs have,
and one for each later size of the seen run that ``kraft3-wide``,
``pull3-full`` and ``addremove4-wide`` reach): ``util.first_new`` with
the seen run, the wave's append buffer and VC queries, one program a
shape, called with a count in each of its prefix sizes, so every branch
of its switch is timed in place, with the lanes it sorted and the
nanoseconds a lane; for a run past the sort's floor also every rung of
``util.merge_rungs`` (``rungs``: the program the engine runs there since
PR 49, called with the run's and the wave's counts filling the rung);
and beside it ``ladder``, the lookup as it was, the seen run and every
level of the ladder sorted whatever they held. ``--only`` keeps the
shapes whose cells' names hold the word.

    python scripts/probe_micro.py [--vc 65536] [--lo 16] [--hi 25]
        [--few 18 22 24] [--reps 10] [--out chiprun_out/probe_micro.json]
        [--platform cpu]
    python scripts/probe_micro.py --cells [--reps 10]
        [--out chiprun_out/probe_cells.json] [--platform cpu]
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _time(fn, args, reps, want):
    """Check ``fn(*args)`` against ``want``, then time it. The checked
    call is the first, so its seconds are the compile's."""
    import jax
    import numpy as np

    t0 = time.perf_counter()
    got = np.asarray(fn(*args))
    first_s = time.perf_counter() - t0
    assert (got == want).all(), "wrong answer"
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return {"median_ms": 1e3 * ts[len(ts) // 2], "min_ms": 1e3 * ts[0],
            "first_call_s": first_s}


# the seen run's later sizes, where a cell's job reaches them
LATER_SEEN = {
    "kraft3-wide": [(1 << 20, "wave 20")],
    "pull3-full": [(1 << 20, "waves 23-32"), (1 << 22, "waves 33-37")],
    "addremove4-wide": [(1 << 20, "waves 15-16")],
}


def cell_shapes():
    """[(cells, seen lanes, VC, FCAP)]: the shapes the dedup stage's
    lookup has in the benchmark's cells, by DeviceBFS's own rules (16
    valid successors a state; the seen run's first size, and its second
    where the cell's job outgrows the first)."""
    import inspect

    from raft_tpu.checker.device_bfs import DeviceBFS

    default = inspect.signature(DeviceBFS).parameters
    shapes: dict = {}
    bench = os.path.join(ROOT, "benchmark", "workloads")
    for name in sorted(os.listdir(bench)):
        with open(os.path.join(bench, name)) as f:
            cell = json.load(f)
        params = cell["engine_params"]
        vc = params["chunk"] * default["valid_per_state"].default
        fcap = params.get("frontier_cap", default["frontier_cap"].default)
        shapes.setdefault((1 << 18, vc, fcap), []).append(cell["name"])
        for size, waves in LATER_SEEN.get(cell["name"], ()):
            shapes.setdefault((size, vc, fcap), []).append(
                f"{cell['name']} {waves}")
    return [(cells, *shape) for shape, cells in sorted(shapes.items())]


def time_cells(args):
    """The ``--cells`` mode: see the module docstring."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import raft_tpu  # noqa: F401  (x64 on, as the engines run)
    from raft_tpu.checker import util
    from raft_tpu.checker.lsm import pow2_at_least
    from raft_tpu.ops.hashing import U64_MAX

    pad = np.uint64(U64_MAX)
    rng = np.random.default_rng(36)
    dev = jax.devices()[0]
    rows = []
    for cells, seen_lanes, vc, fcap in cell_shapes():
        if args.only and not any(args.only in c for c in cells):
            continue
        sizes = util.wave_prefix_sizes(pow2_at_least(vc), fcap)
        seen_h = np.full((seen_lanes,), pad)
        seen_h[: seen_lanes // 2] = np.sort(rng.integers(
            0, 1 << 63, size=seen_lanes // 2, dtype=np.uint64))
        buf_h = rng.integers(0, 1 << 63, size=fcap + vc, dtype=np.uint64)
        v_h = rng.integers(0, 1 << 63, size=vc, dtype=np.uint64)
        v_h[0::16] = seen_h[rng.integers(0, seen_lanes // 2, size=vc // 16)]
        v_h[5::16] = v_h[4::16]  # duplicates inside the chunk
        v_h[7::16] = pad
        first = np.zeros(v_h.shape, bool)
        first[np.unique(v_h, return_index=True)[1]] = True
        fresh = first & (v_h != pad) & ~np.isin(v_h, seen_h)
        occ = jnp.ones((1,), bool)
        seen, v = jnp.asarray(seen_h), jnp.asarray(v_h)

        def buffer_of(count):
            """``count`` lanes written, the first of them among the
            queries; (buffer, what is new then)."""
            b = buf_h.copy()
            b[count:] = pad
            b[: min(count, vc // 16)] = v_h[1::16][: min(count, vc // 16)]
            return jnp.asarray(b), fresh & ~np.isin(v_h, b[:count])

        prefix_fn = jax.jit(lambda v, s, b, c: util.first_new(
            v, occ, (s,), wave=(b, c, sizes))[0])
        row = {"cells": cells, "seen_lanes": seen_lanes, "queries": vc,
               "wave_lanes": fcap, "prefix": []}
        lo = 0
        for p in sizes:
            count = (lo + p + 1) // 2  # inside (previous size, p]
            lo = p
            b, want = buffer_of(count)
            t = _time(prefix_fn, (v, seen, b, np.int32(count)),
                      args.reps, want)
            lanes = seen_lanes + p + vc
            row["prefix"].append({
                "prefix_lanes": p, "count": count, "sort_lanes": lanes,
                "ns_per_lane": 1e6 * t["median_ms"] / lanes, **t})
        # a run past the sort's floor: every rung of its own switch in
        # place, the run's fingerprints filling three quarters of the
        # rung (or the run) and the wave's count the rest
        rungs = util.merge_rungs(seen_lanes, vc, sizes)
        rung_fn = jax.jit(lambda v, s, b, c, r: util.first_new(
            v, occ, (s,), wave=(b, c, sizes), real=(r, rungs))[0])
        row["rungs"] = []
        for r in rungs:
            real = min(3 * r // 4, seen_lanes)
            count = min(r - real, fcap)
            s_h = np.full((seen_lanes,), pad)
            s_h[:real] = np.sort(rng.integers(
                0, 1 << 63, size=real, dtype=np.uint64))
            b, _ = buffer_of(count)
            want = first & (v_h != pad) & ~np.isin(v_h, s_h) & ~np.isin(
                v_h, np.asarray(b)[:count])
            t = _time(rung_fn, (v, jnp.asarray(s_h), b, np.int32(count),
                                np.int32(real)), args.reps, want)
            row["rungs"].append({
                "rung_lanes": r, "real": real, "count": count,
                "sort_lanes": r + vc,
                "ns_per_lane": 1e6 * t["median_ms"] / (r + vc), **t})
        # the lookup as it was: every level of the ladder, sorted
        levels, n = [], pow2_at_least(vc)
        while n < pow2_at_least(fcap):
            levels.append(n)
            n <<= 1
        levels.append(n)
        b, want = buffer_of(levels[0] // 2)
        runs = [jnp.asarray(np.sort(np.asarray(b)[:levels[0]])),
                *(jnp.full((n,), pad, jnp.uint64) for n in levels[1:])]
        lanes = seen_lanes + sum(levels) + vc
        ladder_fn = jax.jit(lambda v, *r: util.first_new(
            v, jnp.ones((len(r),), bool), r))
        t = _time(ladder_fn, (v, seen, *runs), args.reps, want)
        row["ladder"] = {"levels": levels, "sort_lanes": lanes,
                         "ns_per_lane": 1e6 * t["median_ms"] / lanes, **t}
        rows.append(row)
        print(json.dumps(row), flush=True)
    out = {"platform": dev.platform,
           "device": str(getattr(dev, "device_kind", dev.platform)),
           "jax": jax.__version__, "reps": args.reps, "cells": rows}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {args.out}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--vc", type=int, default=65536)
    ap.add_argument("--lo", type=int, default=16)
    ap.add_argument("--hi", type=int, default=25)
    ap.add_argument("--few", type=int, nargs="*", default=[18, 22, 24])
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--cells", action="store_true",
                    help="time first_new at the benchmark cells' shapes")
    ap.add_argument("--only", default=None,
                    help="with --cells: the shapes of cells named so")
    ap.add_argument("--out", default=None)
    ap.add_argument("--platform", default=None)
    args = ap.parse_args(argv)
    if args.platform:
        os.environ["JAX_PLATFORMS"] = args.platform
    args.out = args.out or os.path.join(
        ROOT, "chiprun_out",
        "probe_cells.json" if args.cells else "probe_micro.json")
    if args.cells:
        return time_cells(args)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    import raft_tpu  # noqa: F401  (x64 on, as the engines run)
    from raft_tpu.checker import util
    from raft_tpu.ops.hashing import U64_MAX, eq_u64, split_u64

    vc = args.vc
    rng = np.random.default_rng(25)
    dev = jax.devices()[0]
    occ = jnp.ones((8,), bool)

    def make_run(size):
        real = size // 2
        r = np.full((size,), np.uint64(U64_MAX))
        r[:real] = np.sort(rng.integers(
            0, 1 << 63, size=real, dtype=np.uint64))
        return r

    def make_queries(runs):
        v = rng.integers(0, 1 << 63, size=vc, dtype=np.uint64)
        for j, r in enumerate(runs):  # planted hits, a few per run
            v[j::16] = r[rng.integers(0, r.shape[0] // 2, size=len(v[j::16]))]
        v[5::16] = v[4::16]  # duplicates inside the chunk
        v[7::16] = np.uint64(U64_MAX)
        return v

    def reference(v, runs):
        hit = np.zeros(v.shape, bool)
        for r in runs:
            hit |= np.isin(v, r)
        _, first_idx = np.unique(v, return_index=True)
        first = np.zeros(v.shape, bool)
        first[first_idx] = True
        return first & ~hit & (v != np.uint64(U64_MAX))

    def with_ratio(ratio):
        """first_new traced with every run on one side of the rule."""
        def fn(v, *runs):
            old = util.MERGE_LANES_PER_QUERY
            util.MERGE_LANES_PER_QUERY = ratio
            try:
                return util.first_new(v, occ, runs)
            finally:
                util.MERGE_LANES_PER_QUERY = old
        return jax.jit(fn)

    def merge_3key(v, *runs):
        n = v.shape[0]
        n_run = sum(r.shape[0] for r in runs)
        hi, lo = split_u64(jnp.concatenate([*runs, v]))
        tag = jnp.concatenate([
            jnp.zeros((n_run,), jnp.uint32),
            jnp.arange(1, n + 1, dtype=jnp.uint32)])
        hi, lo, tag = lax.sort((hi, lo, tag), num_keys=3)
        differs = jnp.concatenate([
            jnp.ones((1,), bool), (hi[1:] != hi[:-1]) | (lo[1:] != lo[:-1])])
        query = tag > 0
        m = np.uint32(0xFFFFFFFF)
        new = query & differs & ~((hi == m) & (lo == m))
        back = jnp.where(query, (tag - 1) << 1 | new.astype(jnp.uint32), m)
        return (lax.sort(back)[:n] & 1).astype(bool)

    def rank_sort(v, r):
        pos = jnp.clip(
            jnp.searchsorted(r, v, method="sort"), 0, r.shape[0] - 1)
        return eq_u64(r[pos], v)

    search, merge = with_ratio(0), with_ratio(1 << 40)
    every = {
        "probe": jax.jit(lambda v, r: util.probe_sorted(r, v)),
        "merge": merge,
    }
    few = {"merge_3key": jax.jit(merge_3key), "rank_sort": jax.jit(rank_sort)}
    v_h = make_queries([])
    v = jnp.asarray(v_h)
    chunk_only = _time(merge, (v,), args.reps, reference(v_h, []))
    print(json.dumps({"chunk_only": chunk_only}), flush=True)
    rows = []
    for log2 in range(args.lo, args.hi + 1):
        r_h = make_run(1 << log2)
        v_h = make_queries([r_h])
        want = {"probe": np.isin(v_h, r_h), "merge": reference(v_h, [r_h])}
        want["rank_sort"], want["merge_3key"] = want["probe"], want["merge"]
        r, v = jnp.asarray(r_h), jnp.asarray(v_h)
        row = {"run_lanes": 1 << log2, "queries": vc}
        for name, fn in {**every, **(few if log2 in args.few else {})}.items():
            row[name] = _time(fn, (v, r), args.reps, want[name])
        rows.append(row)
        print(json.dumps(row), flush=True)

    sizes = [1 << 18, 1 << 16, 1 << 17, 1 << 18]
    runs_h = [make_run(s) for s in sizes]
    v_h = make_queries(runs_h)
    want = reference(v_h, runs_h)
    runs = [jnp.asarray(r) for r in runs_h]
    v = jnp.asarray(v_h)
    cell = {"run_lanes": sizes, "queries": vc}
    for name, fn in (("search", search), ("merge", merge)):
        cell[name] = _time(fn, (v, *runs), args.reps, want)
    print(json.dumps(cell), flush=True)

    out = {
        "platform": dev.platform,
        "device": str(getattr(dev, "device_kind", dev.platform)),
        "jax": jax.__version__,
        "reps": args.reps,
        "chunk_only": chunk_only,
        "by_run_size": rows,
        "cell_shape": cell,
    }
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
