#!/usr/bin/env python
"""Perf/correctness regression gate: metrics stream vs committed baseline.

    python scripts/bench_gate.py run.jsonl baseline.json [--json verdict.json]

Compares the LAST summary event of a --metrics-out JSONL stream against
a committed baseline file and exits 0 when every gated metric is inside
tolerance, 3 (the strict-gate exit code the CLI already uses for
coverage gates) when any metric is out, 64 on usage errors and 66 when
an input file is missing. A machine-readable verdict is always printed
on stdout as one JSON object; the failing metrics are also named on
stderr so CI logs show the reason without parsing JSON.

Baseline format (JSON)::

    {
      "note": "free-form provenance, ignored by the gate",
      "metrics": {
        "distinct":   {"value": 45,    "direction": "eq"},
        "seconds":    {"value": 12.0,  "rel_tol": 0.25, "direction": "max"},
        "depth":      {"value": 19,    "tol": 0,        "direction": "eq"}
      }
    }

Per-metric rules:

- ``direction: "eq"``  — |run - value| must be <= tolerance (default 0).
  Use for counts the checker must reproduce exactly (distinct, total,
  depth, terminal): a drift here is a correctness bug, not a perf one.
- ``direction: "max"`` — run must be <= value + tolerance. Use for
  costs (seconds, hbm_plan_bytes; on a chip hbm_peak_bytes): bigger is
  worse.
- ``direction: "min"`` — run must be >= value - tolerance. Use for
  rates (distinct_per_s): smaller is worse.
- tolerance is ``tol`` (absolute) or ``rel_tol`` (fraction of the
  baseline value); giving both is a baseline error (exit 64).
- a gated metric missing from the run's summary, or null, fails the
  gate — silently skipping a metric would let a renamed field pass CI.

Dependency-free on purpose (stdlib only, no raft_tpu import): the gate
must run on a bare CI box or on a metrics file copied off a TPU host.
"""

from __future__ import annotations

import json
import sys

DIRECTIONS = ("eq", "max", "min")


def last_summary(lines) -> dict | None:
    """Decode a JSONL iterable and return the last summary event."""
    summ = None
    for raw in lines:
        raw = raw.strip()
        if not raw:
            continue
        try:
            ev = json.loads(raw)
        except ValueError:
            continue
        if isinstance(ev, dict) and ev.get("event") == "summary":
            summ = ev
    return summ


def evaluate(summary: dict, baseline: dict) -> dict:
    """Gate one summary event against a baseline dict.

    Returns the verdict object: ``{"pass": bool, "checked": N,
    "failures": [...], "metrics": {name: {...one row per gate...}}}``.
    Raises ValueError on a malformed baseline (unknown direction, both
    tol and rel_tol, non-dict metrics block) — the caller maps that to
    exit 64, distinct from a legitimate gate failure.
    """
    gates = baseline.get("metrics")
    if not isinstance(gates, dict) or not gates:
        raise ValueError("baseline has no metrics block")
    failures: list[str] = []
    rows: dict[str, dict] = {}
    for name, gate in sorted(gates.items()):
        if not isinstance(gate, dict) or "value" not in gate:
            raise ValueError(f"metric {name}: baseline entry needs a value")
        direction = gate.get("direction", "eq")
        if direction not in DIRECTIONS:
            raise ValueError(f"metric {name}: unknown direction {direction!r}")
        if "tol" in gate and "rel_tol" in gate:
            raise ValueError(f"metric {name}: give tol OR rel_tol, not both")
        want = float(gate["value"])
        tol = (
            float(gate["rel_tol"]) * abs(want)
            if "rel_tol" in gate else float(gate.get("tol", 0.0))
        )
        if tol < 0:
            raise ValueError(f"metric {name}: negative tolerance")
        got = summary.get(name)
        row = {"want": gate["value"], "tol": tol, "direction": direction,
               "got": got}
        if not isinstance(got, (int, float)) or isinstance(got, bool):
            row["ok"] = False
            reason = "missing from summary" if got is None else f"non-numeric ({got!r})"
            failures.append(f"{name}: {reason}")
        else:
            got = float(got)
            if direction == "eq":
                ok = abs(got - want) <= tol
                bound = f"|{got:g} - {want:g}| <= {tol:g}"
            elif direction == "max":
                ok = got <= want + tol
                bound = f"{got:g} <= {want:g} + {tol:g}"
            else:
                ok = got >= want - tol
                bound = f"{got:g} >= {want:g} - {tol:g}"
            row["ok"] = ok
            if not ok:
                failures.append(f"{name}: {bound} is false")
        rows[name] = row
    return {
        "pass": not failures,
        "checked": len(rows),
        "failures": failures,
        "metrics": rows,
    }


def main(argv: list[str]) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="bench_gate",
        description="Gate a metrics JSONL stream against a committed baseline.",
    )
    ap.add_argument("metrics", help="JSONL file written via --metrics-out")
    ap.add_argument("baseline", help="baseline JSON with a metrics block")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the verdict object to this path")
    try:
        args = ap.parse_args(argv)
    except SystemExit as e:
        return 64 if e.code not in (0, None) else 0

    try:
        with open(args.metrics) as fh:
            summ = last_summary(fh)
    except OSError as e:
        print(f"error: cannot read metrics: {e}", file=sys.stderr)
        return 66
    try:
        with open(args.baseline) as fh:
            baseline = json.load(fh)
    except OSError as e:
        print(f"error: cannot read baseline: {e}", file=sys.stderr)
        return 66
    except ValueError as e:
        print(f"error: baseline is not JSON: {e}", file=sys.stderr)
        return 64
    if summ is None:
        print("error: no summary event in metrics stream", file=sys.stderr)
        return 66

    try:
        verdict = evaluate(summ, baseline)
    except ValueError as e:
        print(f"error: bad baseline: {e}", file=sys.stderr)
        return 64
    verdict["metrics_file"] = args.metrics
    verdict["baseline_file"] = args.baseline
    text = json.dumps(verdict, indent=2, sort_keys=True)
    print(text)
    if args.json:
        with open(args.json, "w") as fh:
            fh.write(text + "\n")
    if verdict["pass"]:
        return 0
    for f in verdict["failures"]:
        print(f"GATE FAIL {f}", file=sys.stderr)
    return 3


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
