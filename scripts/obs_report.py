"""Offline telemetry digest: JSONL event stream -> Markdown report.

Renders a run recorded with ``--metrics-out`` (see raft_tpu/obs) into a
human-readable digest: manifest provenance, the summary block, the
TLC-style per-action coverage table, the frontier depth histogram, an
occupancy sparkline over waves, any stall events, a device-memory
digest from the memwatch events (measured, then planned), and — on sharded runs — a
per-shard balance table (work share, skew) from the rows' ``shard_new``.

Deliberately dependency-free (stdlib only — no jax, no numpy, no
raft_tpu import): the report renders on any machine the JSONL file is
copied to, including ones without the accelerator toolchain.

Usage:
    python scripts/obs_report.py run.jsonl [--all] [--out report.md]

By default only the LAST run in the file is reported (a stream may hold
several; each ``manifest`` event starts a new run); --all reports every
run in order.

Fleet streams (one multiplexed file from ``raft_tpu sweep
--metrics-out``) carry job-tagged runs — the queue arm's per-job runs
and the packed arm's synthesized per-job triples all land in the same
file with a ``job`` field on their events. When any are present, the
report opens with a fleet digest table (one row per job: exit cause,
distinct/total/depth/terminal, violation, seconds) built from every
job-tagged run in the file, and each per-run section is titled with its
job name.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys

SPARK = "▁▂▃▄▅▆▇█"
BAR_WIDTH = 40


def sparkline(values) -> str:
    vals = [float(v) for v in values]
    if not vals:
        return ""
    lo, hi = min(vals), max(vals)
    if hi <= lo:
        return SPARK[0] * len(vals)
    return "".join(
        SPARK[min(len(SPARK) - 1, int((v - lo) / (hi - lo) * len(SPARK)))]
        for v in vals
    )


def hbar(value: int, peak: int) -> str:
    if peak <= 0:
        return ""
    return "#" * max(1 if value else 0, round(value / peak * BAR_WIDTH))


def split_runs(lines) -> list[list[dict]]:
    """Group decoded events into runs; a manifest starts a new run."""
    runs: list[list[dict]] = []
    for raw in lines:
        raw = raw.strip()
        if not raw:
            continue
        try:
            ev = json.loads(raw)
        except ValueError:
            continue
        if not isinstance(ev, dict) or "event" not in ev:
            continue
        if ev["event"] == "manifest" or not runs:
            runs.append([])
        runs[-1].append(ev)
    return runs


def _fmt(v) -> str:
    if isinstance(v, float):
        return f"{v:g}"
    return str(v)


def _fmt_bytes(n) -> str:
    n = int(n)
    for unit, div in (("GiB", 1 << 30), ("MiB", 1 << 20), ("KiB", 1 << 10)):
        if n >= div:
            return f"{n / div:.2f} {unit}"
    return f"{n} B"


def _render_memory(out: list[str], events: list[dict]) -> None:
    """Device-memory digest from the memwatch events: the allocator's
    reading where the device reports one, then the geometry's plan."""
    mws = [e for e in events if e["event"] == "memwatch"]
    if not mws:
        return
    out.append("## Memory watermarks")
    out.append("")
    last = mws[-1]
    budget = int(last["budget_bytes"])
    if last["peak_bytes"] is not None:
        rose = [m for m in mws if m["peak_rise"]]
        out.append(
            f"- **measured** (the allocator): peak "
            f"{_fmt_bytes(last['peak_bytes'])} of {_fmt_bytes(budget)} "
            f"({last['peak_bytes'] / budget:.1%}), "
            f"{_fmt_bytes(last['bytes'])} held at wave {last['wave']}; "
            f"the peak rose in wave(s) "
            f"{[m['wave'] for m in rose] or 'none of this run'}"
        )
    out.append(
        f"- **plan** (the geometry): peak "
        f"{_fmt_bytes(last['plan_peak_bytes'])} of {_fmt_bytes(budget)} "
        f"budget ({last['plan_peak_bytes'] / budget:.1%}), as of wave "
        f"{last['wave']} ({len(mws)} memwatch event(s))"
    )
    out.append("- plan trajectory: "
               f"`{sparkline([m['plan_peak_bytes'] for m in mws])}`")
    breakdown = last.get("breakdown") or {}
    if breakdown:
        peak = max(int(v) for v in breakdown.values())
        out.append("")
        out.append("| buffer family | planned bytes |  |")
        out.append("|---|---:|---|")
        for fam, b in sorted(breakdown.items(), key=lambda kv: -int(kv[1])):
            out.append(f"| {fam} | {_fmt_bytes(b)} | {hbar(int(b), peak)} |")
    out.append("")


def _render_shards(out: list[str], waves: list[dict]) -> None:
    """Per-shard balance table from the ``shard_new`` lists of a sharded
    run's wave rows: who owns the new states, and how skewed the mesh
    is."""
    rows = [w["shard_new"] for w in waves if w.get("shard_new")]
    if not rows:
        return
    sums = [sum(int(r[d]) for r in rows) for d in range(len(rows[0]))]
    out += ["## Shard balance", "",
            "| shard | new distinct | work share |", "|---:|---:|---:|"]
    for shard, new in enumerate(sums):
        out.append(f"| {shard} | {new} | {new / max(1, sum(sums)):.1%} |")
    median = statistics.median(sums)
    skew = (max(sums) / median) if median > 0 else 0.0
    out += ["", f"- **shard skew** (max/median new distinct): {skew:.2f}x",
            ""]


def render_run(events: list[dict]) -> str:
    man = next((e for e in events if e["event"] == "manifest"), {})
    summ = next((e for e in events if e["event"] == "summary"), None)
    waves = [e for e in events if e["event"] == "wave"]
    stalls = [e for e in events if e["event"] == "stall"]
    covs = [e for e in events if e["event"] == "coverage"]
    cov = covs[-1] if covs else None
    names = man.get("action_names") or []

    out = []
    title = man.get("model", "unknown model")
    if man.get("job"):
        title += f" — job {man['job']}"
    out.append(f"# Telemetry report: {title} ({man.get('engine', '?')})")
    out.append("")
    for k in ("ident", "platform", "device", "device_count", "chunk",
              "symmetry", "invariants", "when"):
        if k in man:
            out.append(f"- **{k}**: {_fmt(man[k])}")
    out.append("")

    out.append("## Summary")
    out.append("")
    if summ is None:
        out.append("_no summary event — the run did not finish cleanly_")
    else:
        for k in ("exit_cause", "violation", "distinct", "total", "depth",
                  "terminal", "seconds", "distinct_per_s", "exhausted",
                  "waves", "stalls", "canon_dup_rate",
                  "hbm_peak_bytes", "hbm_peak_frac", "hbm_live_bytes",
                  "hbm_plan_bytes", "hbm_plan_frac"):
            if k in summ:
                out.append(f"- **{k}**: {_fmt(summ[k])}")
    out.append("")

    out.append("## Action coverage")
    out.append("")
    if cov is None or not cov.get("actions"):
        out.append("_no coverage events in the stream_")
    else:
        acts = cov["actions"]
        out.append("| action | enabled | fired | new distinct |")
        out.append("|---|---:|---:|---:|")
        dead = []
        for r, row in enumerate(acts):
            name = names[r] if r < len(names) else f"action[{r}]"
            e, f, n = int(row[0]), int(row[1]), int(row[2])
            out.append(f"| {name} | {e} | {f} | {n} |")
            if f == 0:
                dead.append(name)
        out.append("")
        out.append(
            f"{cov.get('actions_fired', 0)}/{cov.get('actions_total', 0)} "
            f"actions fired"
        )
        for name in dead:
            out.append(f"- **WARNING**: action {name} never fired")
    out.append("")

    out.append("## Depth histogram")
    out.append("")
    hist = (cov or {}).get("frontier_hist") or []
    if not hist:
        out.append("_no frontier histogram recorded_")
    else:
        peak = max(int(x) for x in hist)
        out.append("```")
        for d, x in enumerate(hist):
            out.append(f"depth {d:>3}  {int(x):>10}  {hbar(int(x), peak)}")
        out.append("```")
    out.append("")

    out.append("## Wave profile")
    out.append("")
    if not waves:
        out.append("_no wave events in the stream_")
    else:
        out.append(f"- new distinct/wave:  `{sparkline([w['new'] for w in waves])}`")
        out.append(f"- wave seconds:       `{sparkline([w['wave_s'] for w in waves])}`")
        out.append(
            f"- seen-lane occupancy: `{sparkline([w['lsm_lanes'] for w in waves])}`"
            f" (last: {waves[-1]['lsm_lanes']} lanes in "
            f"{waves[-1]['lsm_runs']} runs)"
        )
        if cov is not None and cov.get("seen_lanes"):
            out.append(
                f"- final seen runs: {cov.get('probe_runs')} "
                f"(lanes per run: {cov['seen_lanes']}; "
                f"real fingerprints: {cov.get('seen_real')})"
            )
    out.append("")

    _render_memory(out, events)
    _render_shards(out, waves)

    out.append("## Stalls")
    out.append("")
    if not stalls:
        out.append("_none_")
    else:
        for s in stalls:
            out.append(
                f"- wave {s.get('wave')} (depth {s.get('depth')}): "
                f"{_fmt(s.get('wave_s'))}s vs median "
                f"{_fmt(s.get('median_wave_s'))}s "
                f"({_fmt(s.get('factor'))}x)"
            )
    out.append("")
    return "\n".join(out)


def render_fleet_digest(runs: list[list[dict]]) -> str | None:
    """One table row per job-tagged run in the stream; None when the
    stream carries no fleet (job-tagged) runs at all."""
    rows = []
    for events in runs:
        man = next((e for e in events if e["event"] == "manifest"), {})
        job = man.get("job")
        if not job:
            continue
        summ = next((e for e in events if e["event"] == "summary"), None)
        # per-job wall-clock: summed wave seconds of THIS job's run —
        # unlike summary `seconds` it stays comparable between the queue
        # arm (one process per job) and the packed arm (synthesized
        # per-job summaries share one device program)
        wall = sum(
            float(e.get("wave_s", 0) or 0)
            for e in events if e["event"] == "wave"
        )
        rows.append((job, summ or {}, wall))
    if not rows:
        return None
    out = ["# Fleet digest", ""]
    out.append(f"{len(rows)} job run(s) in this stream.")
    out.append("")
    out.append(
        "| job | exit | distinct | total | depth | terminal "
        "| violation | seconds | wall (waves) |"
    )
    out.append("|---|---|---:|---:|---:|---:|---|---:|---:|")
    for job, s, wall in rows:
        out.append(
            f"| {job} | {s.get('exit_cause', '?')} "
            f"| {s.get('distinct', '')} | {s.get('total', '')} "
            f"| {s.get('depth', '')} | {s.get('terminal', '')} "
            f"| {s.get('violation') or '-'} | {_fmt(s.get('seconds', ''))} "
            f"| {wall:.3f} |"
        )
    out.append("")
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="obs_report",
        description="Render a telemetry JSONL stream as a Markdown digest.",
    )
    ap.add_argument("path", help="JSONL file written via --metrics-out")
    ap.add_argument("--all", action="store_true",
                    help="report every run in the file (default: last only)")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write the report here instead of stdout")
    args = ap.parse_args(argv)

    with open(args.path) as fh:
        runs = split_runs(fh)
    if not runs:
        print(f"error: no telemetry events in {args.path}", file=sys.stderr)
        return 1
    picked = runs if args.all else runs[-1:]
    sections = []
    digest = render_fleet_digest(runs)
    if digest is not None:
        sections.append(digest)
    sections.extend(render_run(r) for r in picked)
    text = "\n---\n\n".join(sections)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(text + "\n")
    else:
        try:
            print(text)
        except BrokenPipeError:  # | head — truncated output is the ask
            sys.stderr.close()
            return 0
    return 0


if __name__ == "__main__":
    sys.exit(main())
