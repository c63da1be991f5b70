"""Benchmark entry point (driver-run, real TPU).

Workload: BASELINE.md row 1 — the reference `standard-raft/Raft.cfg` state
space on the device-resident checker (DeviceBFS), reported as sustained
distinct-states/sec over a time-budgeted deep run.

Round-5 protocol (reproducibility: nothing compiles inside the timed
region). NOTE: this file reads /root/reference (CFG below), which no
longer exists, so it cannot start; ROADMAP S1 replaces it. Until then
`python chip_smoke.py` is the proof that the checker runs on the chip.
  0. PRECOMPILE phase, untimed: the engine is built at its FINAL
     capacities (no growth retraces) and DeviceBFS.precompile() compiles
     the chunk program + the full LSM merge ladder. With a warm
     persistent compile cache (raft_tpu.enable_compcache) this is a disk
     reload; cold it is the one-time compile cost, and either way the TIMED region
     never compiles. LSM consolidation is host-side since round 5, so
     no program signature can appear mid-run.
  1. The deep run comes FIRST (it is the headline number) with per-wave
     metrics; the measured null-dispatch floor is reported alongside.
  2. Parity gate before any number is emitted: depths 1..GATE_DEPTH at
     two chunk geometries must produce bit-identical per-depth counts.
     A gate failure prints value 0 and exits nonzero.
  3. Same-depth comparison for vs_baseline (python oracle = TLC stand-in;
     the reference publishes no numbers and TLC is not in this image) and
     vs_strong_baseline (the SAME engine on the XLA CPU backend).

Prints exactly one JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}
"""

import json
import os
import sys
import time

CFG = "/root/reference/specifications/standard-raft/Raft.cfg"


def _setup_or_fallback():
    """(model, invariants, workload label). The driver benchmark runs
    against the reference Raft.cfg; without a reference checkout an
    equivalent built-in 3-server geometry stands in (same S, same
    symmetry group — the axes the rate depends on)."""
    if os.path.exists(CFG):
        from raft_tpu.models.registry import build_from_cfg
        from raft_tpu.utils.cfg import parse_cfg

        setup = build_from_cfg(parse_cfg(CFG), msg_slots=32)
        return setup.model, setup.invariants, "standard-raft/Raft.cfg"
    from raft_tpu.models.raft import RaftParams, cached_model

    p = RaftParams(n_servers=3, n_values=2, max_elections=3,
                   max_restarts=1, msg_slots=32)
    return (cached_model(p),
            ("LeaderHasAllAckedValues", "NoLogDivergence"),
            "builtin raft3 (no /root/reference checkout)")


def _emit_micro_summary():
    """Digest of EMIT_MICRO.json (scripts/emit_micro.py) when present:
    the measured emit-strategy costs the round-6 append emit rests on,
    attached to the benchmark's provenance so the rate number carries
    the evidence for its emit path. None when the microbench has not
    been run on this checkout."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "EMIT_MICRO.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        em = json.load(f)
    worst = max(em["rows"], key=lambda r: r["scatter_over_compact"])
    return {
        "device": em["meta"]["device"],
        "when": em["meta"]["when"],
        "cells": len(em["rows"]),
        "worst_scatter_over_compact": worst["scatter_over_compact"],
        "worst_cell": {k: worst[k] for k in
                       ("vc", "fcap", "scatter_full_ms", "compact_dus_ms",
                        "sort_emit_ms")},
    }


def repro_main():
    """--repro: two consecutive IN-PROCESS deep runs after one
    precompile, both sustained rates recorded — the reproducibility
    proof (VERDICT task #8). Writes BENCH_r06-style JSON to stdout;
    the caller redirects it into the round file."""
    depth = int(os.environ.get("BENCH_REPRO_DEPTH", "14"))
    chunk = int(os.environ.get("BENCH_CHUNK", "2048"))

    import jax

    from raft_tpu.checker.device_bfs import DeviceBFS

    model, invs, workload = _setup_or_fallback()
    t0 = time.perf_counter()
    # FINAL capacities up front: a growth retrace in run 1 that run 2
    # does not pay would fake a rate difference (raft3 depth 14 peaks
    # at a ~519k frontier, ~913k seen)
    dev = DeviceBFS(
        model, invariants=invs, symmetry=True, chunk=chunk,
        frontier_cap=1 << 20, seen_cap=1 << 21, journal_cap=1 << 21,
        max_frontier_cap=1 << 21, max_seen_cap=1 << 23,
        max_journal_cap=1 << 23,
    )
    dev.precompile()
    precompile_s = time.perf_counter() - t0

    # one untimed warm-up run first: the first post-precompile run
    # page-faults the cap-sized buffers in and warms host-side caches
    # (measured +20-35% one-off on CPU); its rate is recorded anyway
    runs = []
    for _ in range(3):
        t0 = time.perf_counter()
        res = dev.run(max_depth=depth)
        runs.append({
            "distinct": res.distinct,
            "depth": res.depth,
            "seconds": round(time.perf_counter() - t0, 2),
            "distinct_per_s": round(res.states_per_sec, 1),
        })
    warm, r1, r2 = runs
    ratio = (r2["distinct_per_s"] / r1["distinct_per_s"]
             if r1["distinct_per_s"] else 0.0)
    out = {
        "metric": "bench_repro_consecutive_runs",
        "workload": workload,
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0]),
        "when": time.strftime("%Y-%m-%d %H:%M:%S"),
        "protocol": (
            "one engine, one precompile, one untimed warm-up run, then "
            f"two consecutive in-process depth-{depth} runs; nothing "
            "compiles in the timed regions"
        ),
        "precompile_s": round(precompile_s, 1),
        "warmup_run": warm,
        "run1": r1,
        "run2": r2,
        "counts_match": (warm["distinct"] == r1["distinct"] == r2["distinct"]
                         and r1["depth"] == r2["depth"]),
        "rate_ratio": round(ratio, 4),
        "within_10pct": bool(abs(ratio - 1.0) <= 0.10),
    }
    print(json.dumps(out, indent=1))
    return 0 if out["within_10pct"] and out["counts_match"] else 1


SWEEP_MANIFEST = {
    "spec": "Raft",
    "defaults": {
        "constants": {"Server": ["s1", "s2", "s3"], "Value": ["v1"],
                      "MaxElections": 1, "MaxRestarts": 1},
        "invariants": ["NoLogDivergence"],
        "msg_slots": 24,
    },
    # 16 configs, one packed layout: MaxElections 1 and 2 share the
    # 2-bit term width, MaxRestarts never shapes the program
    "grid": {"MaxRestarts": [1, 2, 3, 4, 5, 6, 7, 8],
             "MaxElections": [1, 2]},
}


def sweep_main():
    """--sweep: fleet amortization benchmark (host engine, CPU-friendly).

    Runs the 16-config Raft sweep twice — once as 16 serial runs (one
    fresh model per job, the cost a user pays without the fleet driver)
    and once through `run_sweep` as ONE packed group — asserts per-job
    bit-identical distinct/total/depth/violation, and prints one JSON
    line whose detail carries the fleet amortization stats (precompile
    count vs job count) as provenance."""
    depth = int(os.environ.get("BENCH_SWEEP_DEPTH", "6"))

    import jax

    from raft_tpu.checker.bfs import BFSChecker
    from raft_tpu.fleet import SweepOptions, parse_manifest_obj, run_sweep
    from raft_tpu.fleet.grouping import build_setup, group_jobs

    mf = parse_manifest_obj(SWEEP_MANIFEST, path="bench.py --sweep")

    # serial leg: a fresh model per job = a fresh jit cache per job
    serial = {}
    t0 = time.perf_counter()
    for job in mf.jobs:
        setup = build_setup(job, mf.path)
        res = BFSChecker(
            setup.model, invariants=setup.invariants,
            symmetry=setup.symmetry,
        ).run(max_depth=depth)
        serial[job.name] = {
            "distinct": res.distinct, "total": res.total,
            "depth": res.depth,
            "violation": res.violation.invariant if res.violation else None,
        }
    serial_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    fleet = run_sweep(mf, SweepOptions(engine="host", max_depth=depth))
    fleet_s = time.perf_counter() - t0

    mismatches = []
    for j in fleet.jobs:
        s = serial[j.name]
        f = {
            "distinct": j.distinct, "total": j.total, "depth": j.depth,
            "violation": j.violation["invariant"] if j.violation else None,
        }
        if f != s:
            mismatches.append({"job": j.name, "serial": s, "fleet": f})
    groups = group_jobs(mf)
    am = fleet.amortization
    ok = (not mismatches
          and am["precompiles"] <= am["groups"]
          and fleet_s < serial_s)
    out = {
        "metric": "fleet_sweep_speedup_vs_serial",
        "value": round(serial_s / fleet_s, 2) if fleet_s > 0 else None,
        "unit": "x (16-config Raft sweep, host engine)",
        "platform": jax.devices()[0].platform,
        "when": time.strftime("%Y-%m-%d %H:%M:%S"),
        "detail": {
            "jobs": len(mf.jobs),
            "max_depth": depth,
            "serial_s": round(serial_s, 2),
            "fleet_s": round(fleet_s, 2),
            "amortization": am,
            "group_kinds": [g.kind for g in groups],
            "counts_bit_identical": not mismatches,
            "mismatches": mismatches[:4],
        },
    }
    print(json.dumps(out))
    return 0 if ok else 1


def measure_floor(reps: int = 5) -> float:
    """Median wall seconds of a null dispatch + device_get sync — the
    floor every wave pays once."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros((8,), jnp.int32)
    np.asarray(jax.device_get(f(x)))  # compile
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        np.asarray(jax.device_get(f(x)))
        ts.append(time.perf_counter() - t0)
    return float(sorted(ts)[len(ts) // 2])


def main():
    budget = float(os.environ.get("BENCH_TIME_BUDGET_S", "300"))
    cmp_depth = int(os.environ.get("BENCH_CMP_DEPTH", "16"))
    gate_depth = int(os.environ.get("BENCH_GATE_DEPTH", "12"))
    chunk = int(os.environ.get("BENCH_CHUNK", "4096"))

    from raft_tpu.utils.cfg import parse_cfg
    from raft_tpu.models.registry import build_from_cfg
    from raft_tpu.checker.device_bfs import DeviceBFS
    from raft_tpu.checker.parity import parity_gate
    from raft_tpu.obs import Telemetry, coverage_digest

    cfg = parse_cfg(CFG)
    setup = build_from_cfg(cfg, msg_slots=32)
    model, invs = setup.model, setup.invariants

    # 00. kernel contract audit (raft_tpu lint --strict, in-process):
    # the BENCH row records the static-analysis verdict as provenance,
    # and a dirty strict verdict refuses publication BEFORE any wave
    # runs — mirroring the BENCH_GATE_BASELINE pattern: the bench
    # stays a measurement, the contract verdict travels with it.
    # RAFT_TPU_BENCH_NO_LINT=1 opts out (e.g. a deliberately mutated
    # tree under study).
    lint_row = None
    if os.environ.get("RAFT_TPU_BENCH_NO_LINT") != "1":
        from raft_tpu.analysis.cli import lint_verdict

        try:
            lv = lint_verdict(strict=True)
        except Exception as e:  # a crashed auditor is not a clean one
            lv = {"clean": False, "strict": True,
                  "error": f"{type(e).__name__}: {e}"}
        lint_row = {
            k: lv[k]
            for k in ("strict", "errors", "warnings", "checked",
                      "clean", "error")
            if k in lv
        }
        if not lv.get("clean"):
            findings = [
                f"[{p['pass']}] {f['file']}:{f['line']} {f['message']}"
                for p in lv.get("passes", ())
                for f in p.get("findings", ())
            ]
            print(json.dumps({
                "metric": "distinct_states_per_sec_raft3_cfg",
                "value": 0,
                "unit": "distinct states/s",
                "vs_baseline": None,
                "error": "strict lint FAILED: kernel contract findings "
                         "refuse publication (RAFT_TPU_BENCH_NO_LINT=1 "
                         "to override)",
                "detail": {"lint": lint_row, "findings": findings[:10]},
            }))
            return 1

    # live telemetry for the headline run: the JSONL stream is the
    # benchmark's provenance record (manifest = engine geometry + device;
    # wave events = the trajectory below), schema-checked after the run
    metrics_path = os.environ.get(
        "BENCH_METRICS_OUT", "/tmp/bench_metrics.jsonl")
    tel = Telemetry(metrics_path=metrics_path)

    # 0. build at FINAL capacities (growth would retrace and compile
    # the chunk program mid-run, inside a timed wave) and warm every
    # program signature before anything is timed.
    t0 = time.perf_counter()
    big = DeviceBFS(
        model, invariants=invs, symmetry=True, chunk=chunk,
        frontier_cap=1 << 22, seen_cap=1 << 25, journal_cap=1 << 25,
        max_frontier_cap=1 << 22, max_seen_cap=1 << 25,
        max_journal_cap=1 << 25,
    )
    big.precompile(telemetry=tel)
    precompile_s = time.perf_counter() - t0
    floor_s = measure_floor()

    # 1. deep run: sustained rate under the time budget (the headline),
    # timed in a process region that compiles nothing
    deep = big.run(time_budget_s=budget, telemetry=tel)
    manifest = next(
        (e for e in tel.events if e["event"] == "manifest"), {})
    waves = tel.wave_events()
    trajectory = [
        {k: m[k] for k in ("depth", "new", "wave_s", "distinct_per_s")}
        for m in waves[-10:]
    ]
    deep_summary = tel.last_summary or {}
    tel.close()
    from scripts.check_metrics_schema import validate_file

    _, metrics_problems = validate_file(metrics_path)

    # optional perf-regression gate: when BENCH_GATE_BASELINE names a
    # baseline JSON (scripts/bench_gate.py format), the deep-run summary
    # is gated against it and the verdict rides the provenance block —
    # the bench stays a measurement, the gate verdict travels with it
    gate_baseline = os.environ.get("BENCH_GATE_BASELINE")
    bench_gate_verdict = None
    if gate_baseline:
        from scripts.bench_gate import evaluate as gate_evaluate

        try:
            with open(gate_baseline) as fh:
                bench_gate_verdict = gate_evaluate(deep_summary, json.load(fh))
        except (OSError, ValueError) as e:
            bench_gate_verdict = {"error": f"{type(e).__name__}: {e}"}
        bench_gate_verdict["baseline_file"] = gate_baseline

    # 2. parity gate at a second chunk geometry (defense against the
    # batch-geometry miscompile class, ops/bag.py)
    small_chunk = chunk // 2 if chunk // 2 >= 128 else chunk * 2
    small_fcap = ((1 << 17) + small_chunk - 1) // small_chunk * small_chunk
    small = DeviceBFS(
        model, invariants=invs, symmetry=True, chunk=small_chunk,
        frontier_cap=small_fcap, seen_cap=1 << 21, journal_cap=1 << 21,
    )
    gate = parity_gate(depth=gate_depth, checkers=(small, big))
    if not gate.ok:
        print(json.dumps({
            "metric": "distinct_states_per_sec_raft3_cfg",
            "value": 0,
            "unit": "distinct states/s",
            "vs_baseline": None,
            "error": "parity gate FAILED: chunk-geometry-dependent counts",
            "detail": {"chunks": list(gate.chunks),
                       "counts": [list(c) for c in gate.counts]},
        }))
        return 1

    # 3. same-depth comparison (workload identical on every side).
    # The engine is warm — this times execution, not compilation.
    t0 = time.perf_counter()
    tpu_cmp = big.run(max_depth=cmp_depth)
    t_tpu = time.perf_counter() - t0

    from raft_tpu.models.registry import oracle_for_setup

    oracle = oracle_for_setup(setup)
    t0 = time.perf_counter()
    ores = oracle.bfs(invariants=invs, symmetry=True, max_depth=cmp_depth,
                      time_budget_s=4 * budget)
    t_oracle = time.perf_counter() - t0
    same_workload = (
        ores["distinct"] == tpu_cmp.distinct
        and ores["depth_counts"] == tpu_cmp.depth_counts
    )
    cmp_note = None
    if not same_workload:
        cmp_note = (
            "oracle hit its own time budget before the comparison depth"
            if len(ores["depth_counts"]) - 1 < cmp_depth
            else "oracle counts diverge from device counts"
        )

    # 3b. strong CPU baseline: the SAME engine on the XLA CPU backend,
    # same depth-capped workload (subprocess: JAX platform is
    # process-global). The child forces the CPU platform before any
    # backend starts (scripts/cpu_baseline.py), so it never asks for the
    # chip this process holds.
    import subprocess

    strong = None
    try:
        out_cpu = subprocess.run(
            [sys.executable, "scripts/cpu_baseline.py", CFG,
             str(cmp_depth), str(chunk), "32"],
            capture_output=True, text=True, timeout=40 * 60,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        strong = json.loads(out_cpu.stdout.strip().splitlines()[-1])
    except Exception as e:  # keep the bench alive; record why
        strong = {"error": f"{type(e).__name__}: {e}"}
    strong_match = (
        "error" not in strong
        and strong.get("distinct") == tpu_cmp.distinct
        and list(strong.get("depth_counts", [])) == list(tpu_cmp.depth_counts)
    )

    out = {
        "metric": "distinct_states_per_sec_raft3_cfg",
        "value": round(deep.states_per_sec, 1),
        "unit": "distinct states/s",
        "vs_baseline": (
            round(t_oracle / t_tpu, 2) if t_tpu > 0 and same_workload else None
        ),
        "vs_strong_baseline": (
            round(strong["seconds"] / t_tpu, 2)
            if t_tpu > 0 and strong_match else None
        ),
        "detail": {
            "deep": {
                "distinct": deep.distinct,
                "depth": deep.depth,
                "exhausted": deep.exhausted,
                "seconds": round(deep.seconds, 2),
                "violation": deep.violation.invariant if deep.violation else None,
                # action-coverage digest: a rate number also says how
                # much of the Next relation the run exercised
                "coverage": (
                    coverage_digest(model.ACTION_NAMES, deep.coverage)
                    if deep.coverage is not None
                    and getattr(model, "ACTION_NAMES", None) else None
                ),
            },
            "dispatch_floor_ms": round(floor_s * 1e3, 1),
            "precompile_s": round(precompile_s, 1),
            "wave_trajectory": trajectory,
            # provenance from the telemetry manifest/summary events
            "manifest": {
                k: manifest.get(k)
                for k in ("ident", "hashv", "canon_memo_cap", "device",
                          "platform", "chunk")
            },
            "exit_cause": deep_summary.get("exit_cause"),
            "canon_memo_hit_rate": deep_summary.get("canon_memo_hit_rate"),
            "emit_micro": _emit_micro_summary(),
            "metrics_file": {
                "path": metrics_path,
                "schema_ok": not metrics_problems,
                "problems": metrics_problems[:5],
            },
            "bench_gate": bench_gate_verdict,
            "lint": lint_row,
            "same_depth_cmp": {
                "depth": cmp_depth,
                "distinct": tpu_cmp.distinct,
                "tpu_s": round(t_tpu, 2),
                "oracle_s": round(t_oracle, 2),
                "counts_match": same_workload,
                "note": cmp_note,
            },
            "strong_baseline_cpu": strong,
            "parity_gate": str(gate),
        },
        "baseline_kind": (
            "in-repo python oracle (TLC stand-in): wall-clock ratio on the "
            "identical same-depth workload; value is the deep-run sustained rate"
        ),
    }
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    if "--sweep" in sys.argv[1:]:
        sys.exit(sweep_main())
    sys.exit(repro_main() if "--repro" in sys.argv[1:] else main())
