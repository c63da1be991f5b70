"""BASELINE.md rows 2-5 benchmarks (run on the real TPU chip).

Row 1 (exhaust standard-raft Raft.cfg) is the driver benchmark
(bench.py). This script measures the remaining rows and writes
BENCH_ROWS.json at the repo root:

  row 2  standard-raft deep BFS: 5 servers, MaxLogLen=5, MaxTerm=5,
         safety-only -> sustained distinct states/sec under a budget
         (the reference gives no numbers; TLC row is "likely
         intractable", BASELINE.md:28)
  row 3  raft-and-fsync RaftFsync.cfg -> parity-gated same-depth
         wall-clock ratio vs the in-repo Python oracle + deep run
  row 4  pull-raft PullRaft.cfg (lenient v2 repair) -> same protocol
  row 5  flexible-raft FlexibleRaft.cfg -> device simulation rate (the
         cfg's prescribed mode, FlexibleRaft.cfg:5) + a bounded-depth
         exhaustive sweep with symmetry (120 server permutations)

Every exhaustive row runs the two-chunk-geometry parity gate first
(checker/parity.py) so no number from a miscompiled batch geometry is
recorded. Protocol notes mirror bench.py: vs_oracle ratios are measured
on the identical same-depth workload, nulled when counts diverge.

Usage:  python scripts/bench_rows.py            (all rows)
        BENCH_ROWS_BUDGET_S=120 python scripts/bench_rows.py 3 4
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BUDGET = float(os.environ.get("BENCH_ROWS_BUDGET_S", "150"))
OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "BENCH_ROWS.json")
REF = "/root/reference/specifications"


def manifest_fields(tel) -> dict:
    """Provenance subset of the telemetry manifest event, attached to
    every row so a BENCH_ROWS number carries the fingerprint-formula
    revision, memo geometry and device kind that produced it."""
    man = next((e for e in tel.events if e["event"] == "manifest"), {})
    return {k: man.get(k) for k in
            ("ident", "hashv", "canon_memo_cap", "device", "platform",
             "chunk")}


def coverage_fields(model, res) -> dict | None:
    """Action-coverage digest for a deep-run provenance block: actions
    fired / total and the least-covered action, so a throughput number
    also says how much of the spec's Next relation the run exercised."""
    from raft_tpu.obs import coverage_digest

    cov = getattr(res, "coverage", None)
    names = getattr(model, "ACTION_NAMES", None)
    if cov is None or not names:
        return None
    return coverage_digest(names, cov)


def gate(model, invs, depth, chunks=(1024, 2048), **caps):
    from raft_tpu.checker.parity import parity_gate

    g = parity_gate(model=model, invariants=invs, symmetry=True,
                    depth=depth, chunks=chunks, **caps)
    return g


def cmp_and_deep(model, invs, oracle, cmp_depth, chunk=2048,
                 frontier_cap=1 << 18, seen_cap=1 << 22, journal_cap=1 << 22):
    from raft_tpu.checker.device_bfs import DeviceBFS

    dev = DeviceBFS(model, invariants=invs, symmetry=True, chunk=chunk,
                    frontier_cap=frontier_cap, seen_cap=seen_cap,
                    journal_cap=journal_cap)
    dev.run(max_depth=1)  # compile outside the timed window (TLC-fair:
    # the oracle pays no compile either; the steady-state rate is what
    # the deep run measures)
    t0 = time.perf_counter()
    dres = dev.run(max_depth=cmp_depth)
    t_tpu = time.perf_counter() - t0
    t0 = time.perf_counter()
    ores = oracle.bfs(invariants=(), symmetry=True, max_depth=cmp_depth,
                      time_budget_s=6 * BUDGET)
    t_oracle = time.perf_counter() - t0
    match = (ores["distinct"] == dres.distinct
             and ores["depth_counts"] == dres.depth_counts)
    from raft_tpu.obs import Telemetry

    tel = Telemetry()
    deep = dev.run(time_budget_s=BUDGET, telemetry=tel)
    return {
        "manifest": manifest_fields(tel),
        "same_depth_cmp": {
            "depth": cmp_depth,
            "distinct": dres.distinct,
            "tpu_s": round(t_tpu, 2),
            "oracle_s": round(t_oracle, 2),
            "counts_match": match,
        },
        "vs_oracle_wallclock": (
            round(t_oracle / t_tpu, 2) if t_tpu > 0 and match else None
        ),
        "deep": {
            "distinct": deep.distinct,
            "depth": deep.depth,
            "exhausted": deep.exhausted,
            "terminal": deep.terminal,
            "seconds": round(deep.seconds, 2),
            "distinct_per_s": round(deep.states_per_sec, 1),
            "violation": deep.violation.invariant if deep.violation else None,
            "coverage": coverage_fields(model, deep),
        },
    }


def row2():
    """Deep-BFS stress: 5 servers / 5 values (MaxLogLen=5) / MaxTerm=5."""
    from raft_tpu.checker.device_bfs import DeviceBFS
    from raft_tpu.models.raft import RaftParams, cached_model

    p = RaftParams(n_servers=5, n_values=5, max_elections=4, max_restarts=0,
                   msg_slots=64)
    model = cached_model(p)
    invs = ("LeaderHasAllAckedValues", "NoLogDivergence")
    g = gate(model, invs, depth=4, chunks=(512, 1024),
             frontier_cap=1 << 14, seen_cap=1 << 18)
    out = {"workload": "Raft 5 servers / 5 values / MaxTerm 5, safety-only",
           "parity_gate": str(g)}
    if not g.ok:
        out["error"] = "parity gate failed"
        return out
    dev = DeviceBFS(model, invariants=invs, symmetry=True, chunk=2048,
                    frontier_cap=1 << 19, seen_cap=1 << 23,
                    journal_cap=1 << 23, max_frontier_cap=1 << 21,
                    max_seen_cap=1 << 25, max_journal_cap=1 << 25)
    dev.run(max_depth=1)  # compile outside the budgeted window (the v3
    # canonicalizer's three tiers push compile past 2 min on this chip)
    from raft_tpu.obs import Telemetry

    tel = Telemetry()
    deep = dev.run(time_budget_s=BUDGET, collect_metrics=True, telemetry=tel)
    last = deep.metrics[-1] if deep.metrics else {}
    out["manifest"] = manifest_fields(tel)
    # round 6 provenance: (a) the emit is the compact+cursor-append path
    # (scripts/emit_micro.py measures it against the retired scatter);
    # (b) round 5's 4.3x final-wave cliff at depth 32 was NOT emit
    # cost — the seen truncate-merge's `[:target]` left a non-ladder-size
    # run when target > concat, forcing a full wave-program retrace at a
    # never-precompiled shape on the next wave. The merge now pads its
    # output to exactly `target` with U64_MAX sentinels (invisible to
    # export/probe), so every wave re-enters a precompiled signature.
    out["notes"] = {
        "emit": "compact+cursor-append (round 6); per-wave emit_rows/"
                "frontier_fill gauges in the metrics stream",
        "expand": "guard-first sparse (round 7): DCE guard pass + "
                  "per-group budgeted apply, loose plan at default "
                  "knobs; per-wave enabled_density/expand_budget_ovf "
                  "gauges in the metrics stream; scripts/expand_micro."
                  "py prices it against both dense baselines "
                  "(materialized and gather-fused)",
        "final_wave_cliff": "round-5 depth-32 4.3x wave-time cliff "
                            "diagnosed as a seen-merge shape retrace "
                            "(truncated non-ladder run size), fixed by "
                            "padding merged seen runs to the ladder "
                            "target; wave times now stay on precompiled "
                            "signatures",
    }
    out["deep"] = {
        "distinct": deep.distinct,
        "depth": deep.depth,
        "exhausted": deep.exhausted,
        "seconds": round(deep.seconds, 2),
        "sustained_distinct_per_s": round(deep.states_per_sec, 1),
        "final_wave": last,
        "coverage": coverage_fields(model, deep),
    }
    return out


def row3():
    from raft_tpu.models.registry import build_from_cfg, oracle_for_setup
    from raft_tpu.utils.cfg import parse_cfg

    cfg = parse_cfg(f"{REF}/raft-and-fsync/RaftFsync.cfg")
    setup = build_from_cfg(cfg, msg_slots=40)
    g = gate(setup.model, setup.invariants, depth=8,
             frontier_cap=1 << 15, seen_cap=1 << 19)
    out = {"workload": "RaftFsync.cfg (3 servers, fsync policy F/T/T)",
           "parity_gate": str(g)}
    if not g.ok:
        out["error"] = "parity gate failed"
        return out
    # depth 15 (round 4): at depth 13 the whole device run is ~6 s of
    # mostly per-wave dispatch latency and the 1-core oracle arm's
    # wall-clock fluctuates 2x run-to-run, so the ratio was noise
    out.update(cmp_and_deep(setup.model, setup.invariants,
                            oracle_for_setup(setup), cmp_depth=15))
    return out


def row4():
    from raft_tpu.models.registry import build_from_cfg, oracle_for_setup
    from raft_tpu.utils.cfg import parse_cfg

    cfg = parse_cfg(f"{REF}/pull-raft/PullRaft.cfg", lenient=True)
    setup = build_from_cfg(cfg, msg_slots=40)
    g = gate(setup.model, setup.invariants, depth=8,
             frontier_cap=1 << 15, seen_cap=1 << 19)
    out = {"workload": "PullRaft.cfg (3 servers; lenient v2 repair)",
           "parity_gate": str(g)}
    if not g.ok:
        out["error"] = "parity gate failed"
        return out
    out.update(cmp_and_deep(setup.model, setup.invariants,
                            oracle_for_setup(setup), cmp_depth=15))
    return out


def row5():
    from raft_tpu.checker.device_bfs import DeviceBFS
    from raft_tpu.checker.simulate import Simulator
    from raft_tpu.models.registry import build_from_cfg
    from raft_tpu.utils.cfg import parse_cfg

    cfg = parse_cfg(f"{REF}/flexible-raft/FlexibleRaft.cfg")
    setup = build_from_cfg(cfg, msg_slots=48)
    out = {"workload": "FlexibleRaft.cfg (5 servers, EQ=3/RQ=4; cfg "
                       "prescribes simulation)"}
    sim = Simulator(setup.model, invariants=setup.invariants, walks=256,
                    max_behavior_depth=40, seed=0)
    t0 = time.perf_counter()
    sres = sim.run(max_behaviors=1024)
    out["simulation"] = {
        "behaviors": sres.behaviors,
        "steps": sres.steps,
        "seconds": round(time.perf_counter() - t0, 2),
        "steps_per_s": round(sres.states_per_sec, 1),
        "violation": sres.violation.invariant if sres.violation else None,
    }
    # bounded-depth exhaustive sweep (symmetry = 120 permutations)
    dev = DeviceBFS(setup.model, invariants=setup.invariants, symmetry=True,
                    chunk=1024, frontier_cap=1 << 17, seen_cap=1 << 21,
                    journal_cap=1 << 21)
    dev.run(max_depth=1)  # compile outside the budgeted window
    from raft_tpu.obs import Telemetry

    tel = Telemetry()
    deep = dev.run(time_budget_s=BUDGET, telemetry=tel)
    out["manifest"] = manifest_fields(tel)
    out["bounded_bfs"] = {
        "distinct": deep.distinct,
        "depth": deep.depth,
        "exhausted": deep.exhausted,
        "seconds": round(deep.seconds, 2),
        "distinct_per_s": round(deep.states_per_sec, 1),
        "violation": deep.violation.invariant if deep.violation else None,
        "coverage": coverage_fields(setup.model, deep),
    }
    return out


def main():
    import jax

    rows = {"2": row2, "3": row3, "4": row4, "5": row5}
    pick = sys.argv[1:] or list(rows)
    results = {}
    if os.path.exists(OUT):
        with open(OUT) as f:
            results = json.load(f)
    results.setdefault("meta", {})
    results["meta"].update({
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0]),
        "budget_s": BUDGET,
        "when": time.strftime("%Y-%m-%d %H:%M:%S"),
    })
    for r in pick:
        print(f"=== row {r} ===", flush=True)
        t0 = time.perf_counter()
        try:
            results[f"row{r}"] = rows[r]()
        except Exception as e:  # record the failure, keep going
            results[f"row{r}"] = {"error": f"{type(e).__name__}: {e}"}
        results[f"row{r}"]["row_wall_s"] = round(time.perf_counter() - t0, 1)
        results[f"row{r}"]["when"] = results["meta"]["when"]
        print(json.dumps({f"row{r}": results[f"row{r}"]}, indent=1), flush=True)
        with open(OUT, "w") as f:
            json.dump(results, f, indent=1)
    print(f"wrote {OUT}")


if __name__ == "__main__":
    main()
