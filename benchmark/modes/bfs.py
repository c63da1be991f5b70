"""Mode `bfs`: whole verdicts of an exhaustive breadth-first check.

The traffic mix (benchmark/traffic/<name>.json) gives `max_depth` and
`warmup_depth`. A run is set-up (build the model and the engine with the
cell's engine parameters, then one untimed warm-up verdict to
`warmup_depth`, deep enough to run every program the job needs), then the
window: the same verdict, `Init` to `max_depth`, back to back. The job is
fixed and deterministic, so a faster program completes more verdicts.
Each verdict is timed in pieces on the benchmark's clock (call to the end
of wave 1, wave to wave, last wave to return), every piece the same work
in every verdict; the verdict's seconds are the sum over the pieces of
each piece's fastest reading in the window (`steady_seconds`). The host
only ever adds time to a piece (a late wake-up, a neighbour on its
cores), so the fastest reading is the program's own, and a run is good as
long as each piece ran undisturbed once in the window. Each
verdict's counts are held to the golden outside the timed region.

With --trace 1 the same set-up is followed by one verdict under
jax.profiler, and the per-layer metrics are reduced from its trace and
its wave rows.
"""

from __future__ import annotations

import os
import shutil
import statistics
import sys
import time
import traceback

from benchmark import adapter, xplane

clock = time.perf_counter


def compare(got: dict, golden: dict, depth: int) -> list:
    """What of one verdict differs from the golden at ``depth``: the
    configuration's guarantees, as far as a run can show them."""
    problems = []
    want = golden["depth_counts"][: depth + 1]
    if len(want) != depth + 1:
        return [f"the golden has no counts to depth {depth}"]
    if got["depth_counts"] != want:
        problems.append(f"per-depth counts {got['depth_counts']} != {want}")
    if got["distinct"] != sum(want):
        problems.append(f"distinct {got['distinct']} != {sum(want)}")
    totals = golden["totals"].get(str(depth))
    if totals is None:
        problems.append(f"the golden pins no total/terminal at depth {depth}")
    else:
        for key in ("total", "terminal"):
            if got[key] != totals[key]:
                problems.append(f"{key} {got[key]} != {totals[key]}")
    if got["violation"] is not None:
        problems.append(f"violation {got['violation']}")
    if got["exit_cause"] != "max_depth":
        problems.append(f"ended by {got['exit_cause']}, not by max_depth")
    ovf = [w["depth"] for w in got["waves"] if w["overflow_bits"]]
    if ovf:
        problems.append(f"overflow bits set at depths {ovf}")
    if [w["depth"] for w in got["waves"]] != list(range(1, depth + 1)):
        problems.append("the wave rows are not depths 1..max_depth")
    return problems


def steady_seconds(pieces: list) -> float:
    """One verdict's seconds from the window's verdicts, each a list of
    its pieces' seconds: piece by piece the fastest reading, summed. What
    the host adds (PERF.md section 6: under 26 spinning processes every
    piece ends on the next 50 ms tick, and the median verdict is 10 %
    long) moves no piece that once ran undisturbed; a slower program
    moves every reading of its piece. Verdicts that do not divide alike
    (one ended early, so the run is not correct anyway) give the fastest
    whole verdict."""
    if len({len(p) for p in pieces}) != 1:
        return min(sum(p) for p in pieces)
    return sum(min(col) for col in zip(*pieces))


def timed_verdict(engine, depth: int, golden: dict, env, tag) -> tuple:
    """(seconds or None, its pieces, verdict or None, problems): a
    verdict that raises is a failed one, with its traceback in the run's
    log."""
    import jax

    try:
        # a span on the profiler's clock when a trace is being taken,
        # nothing otherwise
        with jax.profiler.TraceAnnotation("verdict"):
            got = adapter.verdict(engine, depth, clock)
    except Exception:  # the boundary: the result line must still print
        env.log({"event": "verdict", "n": tag, "raised": traceback.format_exc()})
        return None, None, None, ["the verdict raised"]
    stamps = got.pop("stamps")
    took = stamps[-1] - stamps[0]
    pieces = [b - a for a, b in zip(stamps, stamps[1:])]
    problems = compare(got, golden, depth)
    env.log({"event": "verdict", "n": tag, "seconds": took, "pieces": pieces,
             "distinct": got["distinct"], "problems": problems})
    for problem in problems:  # what a run that is not correct leaves behind
        print(f"benchmark: verdict {tag}: {problem[:300]}", file=sys.stderr)
    for row in got["waves"]:
        env.log({"event": "wave", "n": tag, **row})
    return took, pieces, got, problems


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest device, from its allocator; 0 on
    a backend that keeps no such statistics (the CPU)."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def wave_phases(waves, t0_ns: int, chunk: int) -> list:
    """The traced verdict's waves as spans on the trace's clock, from
    the rows' own clocks (the program's own `wave` spans, in every trace
    since PR 24, carry no such name): narrow where the frontier fits one
    chunk."""
    return [(
        t0_ns + int((w["elapsed_s"] - w["wave_s"]) * 1e9),
        t0_ns + int(w["elapsed_s"] * 1e9),
        "narrow_wave" if w["frontier"] <= chunk else "wide_wave",
    ) for w in waves]


def run(cell: dict, config: dict, traffic: dict, golden: dict, env) -> dict:
    """What every mode is called with; this one needs nothing of
    ``config`` beyond the cfg file the command has already found."""
    import jax

    depth = traffic["max_depth"]
    devices = env.devices[: cell["chips"]]
    cache0 = env.cache_entries()

    # ---- set-up: everything up to the end of the warm-up verdict ----
    t = clock()
    engine = adapter.build_engine(
        env.cfg_path, cell["engine"], cell["engine_params"], devices)
    build_s = clock() - t
    t = clock()
    _, _, warm, problems = timed_verdict(
        engine, traffic["warmup_depth"], golden, env, "warmup")
    warmup_s = clock() - t
    setup_s = clock() - env.t0
    cache1 = env.cache_entries()
    cache_new = len(cache1 - cache0)
    compiles1 = env.compiles()
    failed = int(bool(problems))
    attempted = 1
    env.log({"event": "setup", "setup_s": setup_s, "build_s": build_s,
             "warmup_s": warmup_s, "cache_new_entries": cache_new,
             "compiles": compiles1, "ident": adapter.ident(engine)})

    distinct = sum(golden["depth_counts"][: depth + 1])
    scalars = {
        "build_s": build_s,
        "warmup_s": warmup_s,
        "cache_new_entries": cache_new,
        "mstates": distinct / 1e6,
    }
    out = {"scalars": scalars, "waves": [], "stats": {}, "trace": None,
           "trace_path": None, "trace_dir": None, "end_to_end": {}}

    if not env.trace:
        # ---- the window: whole verdicts while one more still fits ----
        times, pieces = [], []
        t_window = clock()
        while warm is not None:
            took, parts, got, problems = timed_verdict(
                engine, depth, golden, env, len(times) + 1)
            attempted += 1
            failed += int(bool(problems))
            if took is None:
                break
            times.append(took)
            pieces.append(parts)
            # the median, not the longest: one stalled verdict must not
            # cost the window its last one
            if env.seconds - (clock() - t_window) < statistics.median(times):
                break
        if times:
            steady = steady_seconds(pieces)
            out["end_to_end"].update({
                "verdict_s": (steady, "s"),
                "states_per_s": (distinct / steady, "states/s"),
            })
            env.log({"event": "window", "verdicts": times, "steady_s": steady,
                     "median_s": statistics.median(times)})
    elif warm is not None:
        # ---- one verdict under the profiler ----
        # the command removes it, once every metric is read
        trace_dir = out["trace_dir"] = os.path.join(
            env.out_dir, f"trace-{env.tag}")
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # host spans, not every frame
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            took, _, got, problems = timed_verdict(
                engine, depth, golden, env, "traced")
        finally:
            jax.profiler.stop_trace()
        out["traced_at"] = clock()
        attempted += 1
        failed += int(bool(problems))
        if got is not None:
            out["trace_path"] = xplane.find_xplane(trace_dir)
            trace = xplane.load(out["trace_path"])
            span = xplane.span_named(trace, "verdict")
            busy = xplane.busy_s(trace)
            scalars.update({
                "trace_window_s": took,
                "device_busy_s": busy,
                "device_idle": None if busy is None else 1 - busy / took,
            })
            out.update(waves=got["waves"], stats=got["stats"], trace=trace)
            if span is not None:
                phases = wave_phases(
                    got["waves"], span[0], cell["engine_params"]["chunk"])
                out["breakdown"] = {
                    "device_ops": xplane.op_time_by_name(trace),
                    "idle_gaps": xplane.idle_gaps(
                        trace, span[0], span[1], phases),
                }

    # ---- a compile inside the window is a broken measurement ----
    window_entries = len(env.cache_entries() - cache1)
    window_compiles = env.compiles() - compiles1
    peak = memory_peak(devices)
    out["end_to_end"]["setup_s"] = (setup_s, "s")
    env.log({"event": "end", "window_cache_entries": window_entries,
             "window_compiles": window_compiles, "memory_peak_bytes": peak})
    out.update({
        "attempted": attempted,
        "failed": failed,
        "correct": (failed == 0 and attempted > 1
                    and window_entries == 0 and window_compiles == 0),
        # what `correct` compares, each [number, limit]: every count is
        # compared exactly, so a verdict is off the golden or it is not
        "compared": {
            "verdicts_off_golden": [failed, 0],
            "verdicts_short_of_2": [max(0, 2 - attempted), 0],
            "window_compiles": [window_compiles, 0],
            "window_cache_entries": [window_entries, 0],
        },
        "memory_peak_bytes": peak,
    })
    return out
