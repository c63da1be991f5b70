#!/usr/bin/env python
"""Chip smoke: the checker's main path, once, on the accelerator.

    python chip_smoke.py [LEGS]

LEGS is the letters of the legs to run, `chip_smoke.py AK` or
`chip_smoke.py A K`; none runs them all. Each leg has the deadline to
itself (ROADMAP D20: eleven legs under one no longer fit a call, and leg
K alone compiles four wave programs).

Drives `python -m raft_tpu` — the entry point a user calls — on the
reference configuration at its published constants and checks every
count against the pure-Python oracle's golden
(tests/golden/raft_cfg_depth_counts.json):

  leg A  configs/standard-raft/Raft.cfg, DeviceBFS, to depth 22
         (519,399 distinct states), twice: the second run must find
         every compiled program in the cache the first one filled.
  leg B  a FlexibleRaft deployment with unsafe quorums: exit code 2, the
         violated invariant, its depth and the printed counterexample
         trace equal to the golden — the early exit, journal fetch and
         trace reconstruction on the device.
  leg C  the multi-chip engine (`--checker sharded --devices 4`) to
         depth 16, where four chips are visible; on a one-chip machine
         the result says the leg did not run.
  leg D  configs/standard-raft/RaftWithReconfigJointConsensus.cfg (4
         servers, 24 permutations, 1,042-lane rows; the lowering
         models/config_common.py shares with the AddRemove spec) to
         depth 8 at the registry's own bag width, against
         tests/golden/joint_cfg_depth_counts.json: a second model file
         through the same wave program. On its first contact with a v5e
         (PR 30) this lowering lost writes in the sparse apply and every
         count from depth 4 on was wrong, on the chip only.
  leg E  configs/pull-raft/KRaft.cfg (Kafka's KIP-595 quorum: 3
         servers, 6 permutations, 291-lane rows, 98 actions a state, 80
         of them over the bag's slots; models/kraft.py) to depth 14
         against tests/golden/kraft_cfg_depth_counts.json: a third model
         file through the same wave program, its four invariants
         evaluated on every state.
  leg F  configs/pull-raft/KRaftWithReconfig.cfg under --lenient (KRaft
         with membership change: 3 hosts, up to 5 servers, 12
         permutations, 479-lane rows, 145 actions a state;
         models/kraft_reconfig.py and its own SlotCanonicalizer) to
         depth 5 against tests/golden/kraftrc_cfg_depth_counts.json: a
         fourth model file through the same wave program, the one whose
         canonical fingerprints are not ops/symmetry.py's, its five
         invariants evaluated on every state.
  leg G  configs/pull-raft/PullRaft.cfg under --lenient (follower-pull
         replication: 3 servers, 2 values, 6 permutations, 259-lane
         rows, 85 actions a state, 64 of them over the bag's slots;
         models/pull_raft.py) to depth 14 against
         tests/golden/pull3_cfg_depth_counts.json: a fifth model file
         through the same wave program, at its cell's chunk (a
         32,768-lane worklist, twice the size at which leg D's lowering
         lost writes).
  leg H  configs/standard-raft/RaftWithReconfigAddRemove.cfg under
         --lenient (the thesis's one-at-a-time membership change: 4
         servers, 24 permutations, 735-lane rows, 192 actions a state;
         models/reconfig_raft.py; upstream's file omits MaxClusterSize,
         which --lenient sets to 4) to depth 9 against
         tests/golden/addremove4_cfg_depth_counts.json: a sixth model
         file through the same wave program, at its cell's chunk (a
         16,384-lane worklist, and a wave of nine chunks).
  leg I  configs/raft-and-fsync/RaftFsync.cfg (Raft with an explicit
         fsyncIndex: 3 servers, the published constants and fsync
         policy, 192-lane rows, 78 actions a state in 9 kernel groups;
         models/raft.py's has_fsync branches) to depth 14 against
         tests/golden/fsync3_cfg_depth_counts.json, strictly and at the
         registry's own bag width: the kernels of Timeout,
         RequestVotePair and AdvanceFsyncIndex, which no other leg
         fires, and the fsync gates (MaxRestarts is 0: no crash).
  leg J  configs/pull-raft/PullRaftVariant2.cfg under --lenient
         (PullRaft's second variant: votesLastEntry rides on the votes,
         the new leader notifies every peer with the last common entry
         and LearnOfLeader truncates to it; 289-lane rows, the same 85
         actions a state; models/pull_raft.py under variant2) to depth
         14 against tests/golden/pullv2_cfg_depth_counts.json: leg G's
         model file with the variant's branches taken, at its cell's
         chunk.
  leg K  configs/standard-raft/Raft.cfg again, as the benchmark's cell
         raft3-deep-cross runs it: at the cell's frontier and journal
         (benchmark/workloads/raft3-deep-cross.json) to the cell's
         depth, the first wave that runs entirely against a seen run
         past the merge-or-search crossover, against
         benchmark/goldens/raft3-deep.json: the seen run's four sizes,
         the merges that step it up and the binary search at 65,536
         queries a chunk-step inside the wave program, which no other
         leg reaches.

This process never imports jax or raft_tpu: a chip belongs to one process
at a time, so every leg is a child of its own, one after the other, and
the device is read by a throw-away child that exits before the first leg.
No accelerator, a failed check or an unexpected exit code makes the
script exit non-zero without printing a result. The last stdout line of
a passing run is one JSON object naming the device as JAX reports it.
"""

from __future__ import annotations

import functools
import json
import os
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, ".smoke")
GOLDEN = os.path.join(ROOT, "tests", "golden", "raft_cfg_depth_counts.json")
TRACE_GOLDEN = os.path.join(
    ROOT, "tests", "golden", "flexible_unsafe_quorums_trace.txt")
JOINT_GOLDEN = os.path.join(
    ROOT, "tests", "golden", "joint_cfg_depth_counts.json")
KRAFT_GOLDEN = os.path.join(
    ROOT, "tests", "golden", "kraft_cfg_depth_counts.json")
KRAFTRC_GOLDEN = os.path.join(
    ROOT, "tests", "golden", "kraftrc_cfg_depth_counts.json")
RAFT_CFG = os.path.join(ROOT, "configs", "standard-raft", "Raft.cfg")
JOINT_CFG = os.path.join(
    ROOT, "configs", "standard-raft", "RaftWithReconfigJointConsensus.cfg")
KRAFT_CFG = os.path.join(ROOT, "configs", "pull-raft", "KRaft.cfg")
KRAFTRC_CFG = os.path.join(
    ROOT, "configs", "pull-raft", "KRaftWithReconfig.cfg")
PULL_GOLDEN = os.path.join(
    ROOT, "tests", "golden", "pull3_cfg_depth_counts.json")
PULL_CFG = os.path.join(ROOT, "configs", "pull-raft", "PullRaft.cfg")
ADDREMOVE_GOLDEN = os.path.join(
    ROOT, "tests", "golden", "addremove4_cfg_depth_counts.json")
ADDREMOVE_CFG = os.path.join(
    ROOT, "configs", "standard-raft", "RaftWithReconfigAddRemove.cfg")
FSYNC_GOLDEN = os.path.join(
    ROOT, "tests", "golden", "fsync3_cfg_depth_counts.json")
FSYNC_CFG = os.path.join(ROOT, "configs", "raft-and-fsync", "RaftFsync.cfg")
PULLV2_GOLDEN = os.path.join(
    ROOT, "tests", "golden", "pullv2_cfg_depth_counts.json")
PULLV2_CFG = os.path.join(ROOT, "configs", "pull-raft", "PullRaftVariant2.cfg")
DEEP_GOLDEN = os.path.join(ROOT, "benchmark", "goldens", "raft3-deep.json")
DEEP_CELL = os.path.join(
    ROOT, "benchmark", "workloads", "raft3-deep-cross.json")
DEEP_TRAFFIC = os.path.join(ROOT, "benchmark", "traffic")
UNSAFE_CFG = os.path.join(
    ROOT, "configs", "flexible-raft", "unsafe-quorums", "FlexibleRaft.cfg")
SCHEMA_CHECK = os.path.join(ROOT, "scripts", "check_metrics_schema.py")
LEGS = "ABCDEFGHIJK"
# a leg must finish inside 1200 s; each of its children gets what is left
DEADLINE_S = 1150.0
T0 = time.monotonic()  # of the leg that runs: main() sets it at each


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def child(name: str, argv: list[str]) -> tuple[int, str, float]:
    """Run one child to its end (its own process group, killed whole at
    the deadline); returns (exit code, stdout, wall seconds). stderr goes
    to .smoke/<name>.err."""
    left = DEADLINE_S - (time.monotonic() - T0)
    check(left > 0, f"{name}: no time left before the {DEADLINE_S:.0f} s deadline")
    t0 = time.monotonic()
    with open(os.path.join(OUT, f"{name}.err"), "w") as err:
        proc = subprocess.Popen(
            [sys.executable, *argv], cwd=ROOT, stdout=subprocess.PIPE,
            stderr=err, text=True, start_new_session=True,
        )
        try:
            out, _ = proc.communicate(timeout=left)
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.communicate()
            raise SmokeFailure(f"{name}: killed at the deadline") from None
    return proc.returncode, out, time.monotonic() - t0


def err_tail(name: str, n: int = 12) -> str:
    with open(os.path.join(OUT, f"{name}.err")) as f:
        return "".join(f.readlines()[-n:])


def probe_device() -> dict:
    """Platform, device kind and count as JAX reports them, read by a
    child that has exited (and released the chip) before any leg runs."""
    rc, out, _ = child("probe", ["-c", (
        "import json, jax; d = jax.devices(); print(json.dumps({"
        "'platform': d[0].platform, 'kind': d[0].device_kind, "
        "'count': len(d)}))"
    )])
    check(rc == 0, f"JAX found no usable backend (probe exit {rc}):\n"
          + err_tail("probe"))
    dev = json.loads(out.strip().splitlines()[-1])
    check(dev["platform"] != "cpu",
          "no accelerator found: JAX reports only the CPU platform")
    return dev


def cache_dir() -> str:
    """Where raft_tpu.enable_compcache() keeps the compile cache."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")


def cache_entries() -> set[str]:
    found = set()
    for base, _dirs, files in os.walk(cache_dir()):
        found.update(os.path.join(base, f) for f in files)
    return found


def read_events(path: str) -> list[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def bfs_leg(name: str, dev: dict, golden: dict, extra: list[str],
            max_depth: int, device_count: int, cfg: str = RAFT_CFG,
            chunk: int = 4096) -> dict:
    """One exhaustive-BFS child on ``cfg`` to ``max_depth``; every count
    checked against the oracle golden's prefix. Returns the observations
    (wall, set-up to the end of the first wave)."""
    metrics = os.path.join(OUT, f"{name}.jsonl")
    if os.path.exists(metrics):
        os.remove(metrics)
    rc, out, wall = child(name, [
        "-m", "raft_tpu", cfg, "--platform", dev["platform"],
        "--chunk", str(chunk), "--max-depth", str(max_depth), "--json",
        "--metrics-out", metrics, *extra,
        # a golden with no bag width of its own: the registry's
        *(["--msg-slots", str(golden["msg_slots"])]
          if golden["msg_slots"] else []),
    ])
    check(rc == 0, f"{name}: exit code {rc}, expected 0:\n" + err_tail(name))
    summary = json.loads(out.strip().splitlines()[-1])
    events = read_events(metrics)
    manifest = next(e for e in events if e["event"] == "manifest")
    waves = [e for e in events if e["event"] == "wave"]

    check(manifest["platform"] == dev["platform"]
          and manifest["device"] == dev["kind"],
          f"{name}: ran on {manifest['platform']}/{manifest['device']}, "
          f"not on {dev['platform']}/{dev['kind']}")
    check(manifest["device_count"] == device_count,
          f"{name}: device_count {manifest['device_count']} != {device_count}")
    want = golden["depth_counts"][: max_depth + 1]
    got = [1] + [w["new"] for w in waves]
    check([w["depth"] for w in waves] == list(range(1, max_depth + 1)),
          f"{name}: wave depths are not 1..{max_depth}")
    check(got == want, f"{name}: per-depth counts differ from the oracle "
          f"golden:\n  got  {got}\n  want {want}")
    check(summary["distinct"] == sum(want),
          f"{name}: distinct {summary['distinct']} != {sum(want)}")
    if max_depth == golden["max_depth"]:
        # generated and terminal totals are pinned at the golden's depth
        for key in ("total", "terminal"):
            check(summary[key] == golden[key],
                  f"{name}: {key} {summary[key]} != golden {golden[key]}")
    check(summary["violation"] is None,
          f"{name}: reported violation {summary['violation']}")
    check(summary["exit_cause"] == "max_depth" and summary["depth"] == max_depth,
          f"{name}: ended by {summary['exit_cause']} at depth {summary['depth']}")
    ovf = [w["depth"] for w in waves if w["overflow_bits"]]
    check(not ovf, f"{name}: overflow bits set at depths {ovf}")

    rc, out, _ = child(f"{name}.schema", [SCHEMA_CHECK, metrics])
    check(rc == 0, f"{name}: metrics stream fails the schema:\n{out}")
    return {
        "wall_s": round(wall, 1),
        # process start to the end of the first wave: imports, backend
        # start, the first wave program's compile or cache read
        "setup_s": round(wall - summary["seconds"] + waves[0]["elapsed_s"], 1),
        "distinct": summary["distinct"],
        "total": summary["total"],
    }


def leg_a(dev: dict, golden: dict) -> None:
    depth = golden["max_depth"]
    before = cache_entries()
    first = bfs_leg("legA-1", dev, golden, ["--checker", "tpu"], depth, 1)
    filled = cache_entries()
    check(bool(filled),
          f"leg A: the first run left no compile-cache entry in {cache_dir()}")
    second = bfs_leg("legA-2", dev, golden, ["--checker", "tpu"], depth, 1)
    added = cache_entries() - filled
    check(not added, f"leg A: the second run added {len(added)} compile-cache "
          f"entries to {cache_dir()} (the cache was not found again)")
    wrote = len(filled - before)
    print(f"leg A ok: Raft.cfg to depth {depth}, {first['distinct']} distinct / "
          f"{first['total']} generated, twice; compile cache "
          f"{wrote} new entries then 0")
    # observations, not metrics: process start to the end of the first wave
    state = "cold" if wrote else "warm (the cache was already filled)"
    print(f"  set-up, {state}: {first['setup_s']} s (run wall {first['wall_s']} s)")
    print(f"  set-up, warm: {second['setup_s']} s (run wall {second['wall_s']} s)")


def leg_b(dev: dict) -> None:
    rc, out, _ = child("legB", [
        "-m", "raft_tpu", UNSAFE_CFG, "--platform", dev["platform"],
        "--checker", "tpu", "--chunk", "512", "--msg-slots", "24",
    ])
    check(rc == 2, f"leg B: exit code {rc}, expected 2 (violation found):\n"
          + err_tail("legB"))
    marker = "INVARIANT LeaderHasAllAckedValues VIOLATED (depth 6)\n"
    check(marker in out, f"leg B: no line {marker!r} in:\n{out[:400]}")
    with open(TRACE_GOLDEN) as f:
        want = f.read()
    got = out[out.index(marker) + len(marker):]
    check(got == want, "leg B: the printed trace differs from "
          f"{os.path.relpath(TRACE_GOLDEN, ROOT)}:\n{got}")
    print("leg B ok: unsafe FlexibleRaft quorums, exit code 2, "
          "LeaderHasAllAckedValues at depth 6, trace equals the golden")


def leg_c(dev: dict, golden: dict) -> None:
    if dev["count"] < 4:
        print(f"sharded leg not run: {dev['count']} chip visible")
        return
    res = bfs_leg("legC", dev, golden,
                  ["--checker", "sharded", "--devices", "4"], 16, 4)
    print(f"leg C ok: Raft.cfg to depth 16 sharded over 4 chips, "
          f"{res['distinct']} distinct (run wall {res['wall_s']} s)")


def cfg_leg(letter: str, cfg: str, chunk: int, dev: dict, golden: dict,
            flags: tuple = (), frontier_cap: int = 65536) -> None:
    """Legs D to K: a cfg through the CLI to its golden's depth, at its
    cell's chunk, with the flags the cfg needs."""
    depth = golden["max_depth"]
    res = bfs_leg(f"leg{letter}", dev, golden,
                  ["--checker", "tpu", "--frontier-cap", str(frontier_cap),
                   *flags],
                  depth, 1, cfg=cfg, chunk=chunk)
    print(f"leg {letter} ok: {os.path.basename(cfg)} to depth {depth}, "
          f"{res['distinct']} distinct / {res['total']} generated "
          f"(run wall {res['wall_s']} s)")


def golden_leg(letter: str, dev: dict, cfg: str, chunk: int, path: str,
               flags: tuple) -> None:
    """Legs D to J, each against its own file of tests/golden/."""
    with open(path) as f:
        cfg_leg(letter, cfg, chunk, dev, json.load(f)["depth_limited"],
                flags)


def deep_cell() -> tuple[dict, dict]:
    """(engine parameters, golden in a leg's shape) of the benchmark's
    cell raft3-deep-cross: the cell's depth is its traffic mix's, the
    counts to it the benchmark's golden's."""
    with open(DEEP_CELL) as f:
        cell = json.load(f)
    with open(os.path.join(DEEP_TRAFFIC, f"{cell['traffic']}.json")) as f:
        depth = json.load(f)["max_depth"]
    with open(DEEP_GOLDEN) as f:
        golden = json.load(f)
    return cell["engine_params"], {
        "max_depth": depth, "msg_slots": golden["msg_slots"],
        "depth_counts": golden["depth_counts"][: depth + 1],
        **golden["totals"][str(depth)]}


def leg_k(dev: dict) -> None:
    params, golden = deep_cell()
    cfg_leg("K", RAFT_CFG, params["chunk"], dev, golden,
            ("--journal-cap", str(params["journal_cap"])),
            frontier_cap=params["frontier_cap"])


def main(legs: str = LEGS) -> int:
    global T0
    legs = legs.upper()
    try:
        check(bool(legs) and set(legs) <= set(LEGS),
              f"legs {legs!r}: choose from {LEGS}")
        for path in (GOLDEN, JOINT_GOLDEN, KRAFT_GOLDEN, KRAFTRC_GOLDEN,
                     PULL_GOLDEN, ADDREMOVE_GOLDEN, FSYNC_GOLDEN,
                     PULLV2_GOLDEN, DEEP_GOLDEN, DEEP_CELL, TRACE_GOLDEN,
                     RAFT_CFG, JOINT_CFG,
                     KRAFT_CFG, KRAFTRC_CFG, PULL_CFG, ADDREMOVE_CFG,
                     FSYNC_CFG, PULLV2_CFG, UNSAFE_CFG, SCHEMA_CHECK,
                     os.path.join(ROOT, "raft_tpu", "__main__.py")):
            check(os.path.exists(path),
                  f"{os.path.relpath(path, ROOT)} is missing: chip_smoke.py "
                  "runs from the root of a raft-tpu-checker checkout")
        os.makedirs(OUT, exist_ok=True)
        dev = probe_device()
        with open(GOLDEN) as f:
            golden = json.load(f)["depth_limited"]
        runs = {"A": lambda: leg_a(dev, golden), "B": lambda: leg_b(dev),
                "C": lambda: leg_c(dev, golden), "K": lambda: leg_k(dev)}
        for letter, *its in (
                ("D", JOINT_CFG, 1024, JOINT_GOLDEN, ()),
                ("E", KRAFT_CFG, 2048, KRAFT_GOLDEN, ()),
                # upstream's cfg declares v1 and uses v2
                ("F", KRAFTRC_CFG, 1024, KRAFTRC_GOLDEN, ("--lenient",)),
                ("G", PULL_CFG, 2048, PULL_GOLDEN, ("--lenient",)),
                # upstream's cfg omits MaxClusterSize
                ("H", ADDREMOVE_CFG, 1024, ADDREMOVE_GOLDEN,
                 ("--lenient",)),
                ("I", FSYNC_CFG, 2048, FSYNC_GOLDEN, ()),
                # the same undeclared v2 as leg G's cfg
                ("J", PULLV2_CFG, 2048, PULLV2_GOLDEN, ("--lenient",))):
            runs[letter] = functools.partial(golden_leg, letter, dev, *its)
        for letter in LEGS:
            if letter in legs:
                T0 = time.monotonic()  # the deadline is the leg's
                runs[letter]()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main("".join(sys.argv[1:]) or LEGS))
