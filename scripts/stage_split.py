"""What the stages of a chunk are made of, from chip traces: one traced
verdict at each benchmark cell's depth, reduced by nested scope
(``dedup/merge``, ``emit/coverage``, ...) and by op.

The benchmark's ``scope_time`` reader books an op to its outermost stage
and run.py deletes the trace it read; this keeps the second level and the
op names, which is what PERF.md section 5 ("what the stages are made of")
is written from. One process: the engine of ``--workload`` (default
``raft3-small``; both raft3 cells build the same one), a depth-8 warm-up
verdict, then a traced verdict per ``--depth`` (default: 14 and 20 for
raft3, else the cell's own depth). Chip only in earnest; ``--platform
cpu`` rehearses.

    python scripts/stage_split.py [--workload flexraft5-wide]
        [--depth 14 20] [--top 16] [--gather] [--platform cpu]
        [--out chiprun_out/stage_split.json]

``expand_by_group_s`` is ``expand``'s seconds by the action group whose
successors an op built (``sparse_apply``'s scope round each group's loop
over the tiles of what it keeps, ``expand/Restart``: an op of the loop's
body reads ``expand/Restart/while/body/...`` and is booked to the group,
control flow being no scope; ``-`` is what ``expand`` runs outside any
group: the guard pass, the two sorts, the worklist's assembly and the
last gather's loop). Beside it ``expand_rows_built`` and
``expand_rows_budget``, the program's own count of the rows those loops
built and the rows their plan budgets. ``--top N`` keeps
the N heaviest ops; ``--gather`` puts the per-lane
reads back behind ``models/base.py``'s one-hot read helpers
(``scripts/stage_diff.py``'s switch), so old reads and new are timed from
one tree.

``--trace-dir DIR`` builds no engine: it reduces the newest trace under
``DIR`` (what ``python -m raft_tpu CFG --trace-dir DIR`` wrote) to the
same split and prints it, the whole of it as JSON on the last line:
where the time of an operator's own run went.
"""

import argparse
import collections
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def split(path, top=16):
    """Seconds of device self time by scope path two levels deep (the
    benchmark's own rule, ``xplane.scope_path``), ``expand``'s by action
    group (its second level; ``-`` for its own), and the ``top``
    heaviest (op, name stack) pairs, of one .xplane.pb."""
    from benchmark import xplane, xspace

    with open(path, "rb") as f:
        buf = memoryview(f.read())
    by_scope = collections.Counter()
    by_op = collections.Counter()
    n_planes = 0
    for name, plane in xspace.planes(buf):
        if not xplane.DEVICE_PLANE.match(name):
            continue
        n_planes += 1
        tf_ops = xspace.op_stat(plane, "tf_op")
        op = {key: xplane.op_name(xspace.text(xspace.first(meta, 2)))
              for key, meta in xspace.map_entries(plane, 4)}
        for start, end, meta in xplane.self_pieces(xspace.op_events(plane)):
            stack = tf_ops.get(meta) or ""
            scope = "/".join(xplane.scope_path(stack)) or "unscoped"
            by_scope[scope] += end - start
            by_op[(scope, op[meta], stack[-80:])] += end - start
    per = 1e9 * max(1, n_planes)
    return {
        "by_scope_s": {k: ns / per for k, ns in by_scope.most_common()},
        "expand_by_group_s": {
            (k.partition("/")[2] or "-"): ns / per
            for k, ns in by_scope.most_common()
            if k.partition("/")[0] == "expand"},
        "top_ops_s": [[*k, ns / per] for k, ns in by_op.most_common(top)],
    }


def report(trace_dir, top=16):
    """``split`` of the newest trace under ``trace_dir`` and the device's
    busy seconds, printed as a table."""
    from benchmark import xplane

    path = xplane.find_xplane(trace_dir)
    res = split(path, top)
    res["busy_s"] = busy = xplane.busy_s(xplane.load(path))
    for scope, s in [*res["by_scope_s"].items(), ("busy", busy)]:
        print(f"{scope:<22}{s:>12.6f} s{s / busy:>8.1%}")
    return res


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace-dir", default=None)
    ap.add_argument("--workload", default="raft3-small")
    ap.add_argument("--depth", type=int, nargs="*", default=None)
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "stage_split.json"))
    ap.add_argument("--top", type=int, default=16,
                    help="how many of the heaviest ops to keep")
    ap.add_argument("--gather", action="store_true",
                    help="time the reads as they were before PR 31 "
                         "(stage_diff.gather_reads)")
    ap.add_argument("--platform", default=None)
    args = ap.parse_args(argv)
    if args.platform:
        os.environ["JAX_PLATFORMS"] = args.platform
    if args.trace_dir:
        return print(json.dumps(report(args.trace_dir, args.top)))
    if args.gather:
        from scripts import stage_diff

        stage_diff.gather_reads()

    import jax

    from benchmark import adapter, xplane

    bench = os.path.join(ROOT, "benchmark")
    with open(os.path.join(bench, "workloads", f"{args.workload}.json")) as f:
        cell = json.load(f)
    with open(os.path.join(bench, "traffic", f"{cell['traffic']}.json")) as f:
        traffic = json.load(f)
    depths = args.depth or (
        [14, 20] if cell["config"] == "raft3" else [traffic["max_depth"]])
    cfg_dir = os.path.join(bench, "configs", cell["config"])
    with open(os.path.join(cfg_dir, "config.json")) as f:
        config = json.load(f)
    engine = adapter.build_engine(
        os.path.join(cfg_dir, config["cfg"]), "device",
        cell["engine_params"], jax.devices()[:1])
    clock = time.perf_counter
    adapter.verdict(engine, traffic["warmup_depth"], clock)
    dev = jax.devices()[0]
    out = {"platform": dev.platform, "workload": args.workload,
           "device": str(getattr(dev, "device_kind", dev.platform))}
    for depth in depths:
        adapter.verdict(engine, depth, clock)  # every seen size compiled
        tdir = os.path.join(bench, "out", f"stage-split-{depth}")
        shutil.rmtree(tdir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=options)
        try:
            got = adapter.verdict(engine, depth, clock)
        finally:
            jax.profiler.stop_trace()
        path = xplane.find_xplane(tdir)
        res = split(path, args.top)
        res["busy_s"] = xplane.busy_s(xplane.load(path))
        res["distinct"] = got["distinct"]
        res["dedup_plan"] = got["stats"].get("dedup_plan")
        res["dedup_sort_lanes"] = got["stats"].get("dedup_sort_lanes")
        res["dedup_search_queries"] = got["stats"].get("dedup_search_queries")
        res["dedup_search_steps"] = got["stats"].get("dedup_search_steps")
        res["expand_rows_built"] = got["stats"].get("expand_rows_built")
        res["expand_rows_budget"] = got["stats"].get("expand_rows_budget")
        res["frontier_peak_rows"] = got["stats"].get("frontier_peak_rows")
        res["restart_fired"] = got["stats"].get("restart_fired")
        res["canon_lanes"] = {
            k: sum(w[k] for w in got["waves"])
            for k in ("generated", "canon_dup_lanes", "canon_tier3_local",
                      "canon_tier3_full") if k in got["waves"][0]}
        out[str(depth)] = res
        print(depth, json.dumps(res["by_scope_s"]), flush=True)
        shutil.rmtree(tdir, ignore_errors=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
