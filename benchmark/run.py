#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell by name, benchmark/workloads/<cell>.json, from there its
configuration, benchmark/configs/<config>/, the configuration's golden,
benchmark/goldens/<config>.json, its traffic mix,
benchmark/traffic/<traffic>.json, and the mix's mode,
benchmark/modes/<mode>.py; with --trace 1 each per-layer metric,
benchmark/layer_metrics/<name>.json, that the cell's file lists or whose
own file lists the cell, and that metric's reader,
benchmark/readers/<kind>.py. A new cell, configuration, golden, traffic
mix, mode, per-layer metric or reader is a new file, found by its name.

The last line of standard output is one JSON object: correct, attempted,
failed, metrics, device, traced also breakdown, and last compared: what
`correct` rests on, each number beside its limit, which are the last
lines of standard error too. Everything else a run tells goes to
standard error and to benchmark/out/<cell>-<seed>.jsonl.

No chip, no number: on the CPU backend, or with fewer chips than the
cell asks for, the command exits non-zero and prints no result.
--allow-cpu is for rehearsals and the benchmark's tests; such a run says
"platform": "cpu", and no timing from it is a device number.

--seed changes nothing an exhaustive BFS does (the job is the cfg's whole
state graph to a depth, and it is deterministic); it is recorded, and it
makes the walks once a mode draws any.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up counts from here: before any import

import argparse
import dataclasses
import importlib
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# `python3 benchmark/run.py` puts benchmark/ first on the path; the
# package and the program under test are found from the checkout's root
sys.path[0] = ROOT

# exit codes
NO_CHIP, BAD_CELL = 3, 64


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@dataclasses.dataclass
class Env:
    """What a mode gets from the command besides its cell."""

    t0: float
    seed: int
    seconds: float
    trace: bool
    devices: list
    cfg_path: str
    out_dir: str
    tag: str
    log_file: object
    compile_events: list

    def log(self, row: dict) -> None:
        self.log_file.write(json.dumps(row) + "\n")
        self.log_file.flush()

    def cache_entries(self) -> set:
        """Files of the persistent compile cache, as of now."""
        from benchmark import adapter

        found = set()
        for base, _dirs, files in os.walk(adapter.cache_dir()):
            found.update(os.path.join(base, f) for f in files)
        return found

    def compiles(self) -> int:
        """Programs handed to the compiler (or read from its cache) so
        far in this process."""
        return len(self.compile_events)


def resolve(bench_dir: str, name: str) -> tuple:
    """(cell, config, traffic, golden, cfg path) of a cell, each from the
    file its name leads to."""
    cell = load_json(bench_dir, "workloads", f"{name}.json")
    cfg_dir = os.path.join(bench_dir, "configs", cell["config"])
    config = load_json(cfg_dir, "config.json")
    traffic = load_json(bench_dir, "traffic", f"{cell['traffic']}.json")
    golden = load_json(bench_dir, "goldens", f"{cell['config']}.json")
    return cell, config, traffic, golden, os.path.join(cfg_dir, config["cfg"])


def layer_metrics(bench_dir: str, cell: dict, out: dict, peaks: dict) -> dict:
    """The per-layer metrics of a traced run: those the cell's file lists
    under ``per_layer``, then those whose own file lists the cell under
    ``workloads``. So a new cell names what it reports, and a new metric
    names the cells that report it: neither edits a file that is there."""
    from benchmark import readers

    ctx = {"scalars": out["scalars"], "waves": out["waves"],
           "stats": out["stats"], "trace": out["trace"],
           "trace_path": out["trace_path"],
           "params": cell["engine_params"], "peaks": peaks}
    folder = os.path.join(bench_dir, "layer_metrics")
    specs = {f[:-5]: load_json(folder, f)
             for f in sorted(os.listdir(folder)) if f.endswith(".json")}
    names = list(cell["per_layer"])
    names += [name for name, spec in specs.items() if name not in names
              and cell["name"] in spec.get("workloads", ())]
    metrics = {}
    for name in names:
        value = readers.read(specs[name], ctx)
        if value is not None:
            metrics[name] = {"value": value, "unit": specs[name]["unit"]}
    return metrics


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal only: run on the CPU backend")
    ap.add_argument("--bench-dir", default=HERE,
                    help="where workloads/, configs/, goldens/ and "
                         "layer_metrics/ are (the tests add cells elsewhere)")
    args = ap.parse_args(argv)

    try:
        cell, config, traffic, golden, cfg_path = resolve(
            args.bench_dir, args.workload)
    except (OSError, KeyError, ValueError) as e:
        print(f"benchmark: cell {args.workload!r} does not resolve: {e!r}",
              file=sys.stderr)
        return BAD_CELL

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform == "cpu" and not args.allow_cpu:
        print("benchmark: JAX found no accelerator (platform cpu); no chip, "
              "no number", file=sys.stderr)
        return NO_CHIP
    if len(devices) < cell["chips"]:
        print(f"benchmark: cell {args.workload} needs {cell['chips']} chips, "
              f"JAX reports {len(devices)}", file=sys.stderr)
        return NO_CHIP
    peaks = load_json(HERE, "peaks.json").get(dev.device_kind)
    if peaks is None and dev.platform != "cpu":
        print(f"benchmark: no peaks for device kind {dev.device_kind!r} in "
              "benchmark/peaks.json", file=sys.stderr)
        return NO_CHIP

    compile_events: list = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **_kw: compile_events.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)

    out_dir = os.path.join(HERE, "out")
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{args.workload}-{args.seed}" + ("-trace" if args.trace else "")
    mode = importlib.import_module(f"benchmark.modes.{traffic['mode']}")
    with open(os.path.join(out_dir, f"{tag}.jsonl"), "w") as log_file:
        env = Env(
            t0=T0, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace),
            devices=devices, cfg_path=cfg_path, out_dir=out_dir, tag=tag,
            log_file=log_file, compile_events=compile_events)
        env.log({"event": "run", "workload": args.workload, "seed": args.seed,
                 "seconds": args.seconds, "trace": args.trace,
                 "platform": dev.platform, "kind": dev.device_kind})
        out = mode.run(cell, config, traffic, golden, env)

    if args.trace:
        metrics = layer_metrics(args.bench_dir, cell, out, peaks)
        if out["trace_dir"]:  # tens of MB a verdict
            shutil.rmtree(out["trace_dir"], ignore_errors=True)
    else:
        metrics = {
            name: {"value": out["end_to_end"][name][0],
                   "unit": out["end_to_end"][name][1]}
            for name in cell["end_to_end"] if name in out["end_to_end"]
        }
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if args.trace:
        device["busy_s"] = out["scalars"].get("device_busy_s")
        device["window_s"] = out["scalars"].get("trace_window_s")
        if "breakdown" in out:
            result["breakdown"] = out["breakdown"]
        if "traced_at" in out:
            print(f"benchmark: {time.perf_counter() - out['traced_at']:.3f} s "
                  "from stop_trace to the result line", file=sys.stderr)
    result["compared"] = out["compared"]
    for name, (value, limit) in out["compared"].items():
        print(f"benchmark: {name} {value} (limit {limit})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
