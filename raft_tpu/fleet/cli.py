"""`raft_tpu sweep MANIFEST.json` — the fleet-checking subcommand.

Exit code is the WORST job rc (the per-run vocabulary from
raft_tpu/__main__.py: 0 clean, 2 violation, 4 preempted, 5
unrecoverable), with the usual 64 usage / 66 not-found for manifest
problems. Under ``--json`` stdout carries one summary object per job
followed by one fleet aggregate object (amortization stats included);
everything else goes to stderr.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from ..utils.platform import add_platform_arg, select_platform
from .driver import SweepOptions, run_sweep
from .manifest import ManifestError, parse_manifest


def sweep_main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="raft_tpu sweep",
        description="run every job of a sweep manifest, packing "
        "layout-compatible configs into one compiled program",
    )
    ap.add_argument("manifest", help="sweep manifest (JSON; see README "
                    "'Fleet checking' for the grammar)")
    ap.add_argument(
        "--engine",
        default="host",
        choices=["host", "tpu", "sharded"],
        help="host = co-resident packed frontier (BFSChecker); tpu/"
        "sharded = device queue arm, one jit cache per group",
    )
    ap.add_argument("--jobs", default=None, metavar="GLOB",
                    help="fnmatch filter on job names (e.g. 'Raft-*ME=1*')")
    ap.add_argument("--max-depth", type=int, default=None)
    ap.add_argument("--time-budget", type=float, default=None,
                    help="per-run seconds budget (each group/job run)")
    ap.add_argument("--chunk", type=int, default=1024, help="device batch size")
    ap.add_argument(
        "--state-dir",
        default=None,
        metavar="DIR",
        help="sweep state root: fleet_state.json (completed-job ledger) "
        "plus per-job checkpoint lineages under DIR/ckpt/",
    )
    ap.add_argument(
        "--resume",
        action="store_true",
        help="skip jobs already completed per --state-dir's ledger and "
        "resume per-job checkpoints where they exist (packed host "
        "groups rerun wholly unless every member finished)",
    )
    ap.add_argument(
        "--supervise",
        nargs="?",
        const=5,
        default=None,
        type=int,
        metavar="N",
        help="run each job under the resilience supervisor with a "
        "per-job budget of N recoveries (default 5); a job that spends "
        "its budget becomes an rc-5 unrecoverable result without "
        "killing the rest of the sweep, and per-job recovery counts "
        "land in fleet_state.json and each job summary",
    )
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="one multiplexed JSONL telemetry stream; every "
                    "event carries a 'job' field")
    ap.add_argument("--metrics-every", type=int, default=1, metavar="N")
    ap.add_argument("--json", action="store_true",
                    help="stdout: one summary object per job, then the "
                    "fleet aggregate object")
    add_platform_arg(ap)
    ap.add_argument("--verbose", "-v", action="store_true")
    args = ap.parse_args(argv)

    if args.resume and not args.state_dir:
        print("error: --resume needs --state-dir", file=sys.stderr)
        return 64

    rc = select_platform(args.platform)
    if rc:
        return rc

    try:
        mf = parse_manifest(args.manifest)
    except FileNotFoundError as e:
        print(f"error: {e}", file=sys.stderr)
        return 66
    except ManifestError as e:
        print(f"manifest error: {e}", file=sys.stderr)
        return 64

    tel = None
    if args.metrics_out is not None:
        dn = os.path.dirname(args.metrics_out)
        if dn:
            os.makedirs(dn, exist_ok=True)
        from ..obs import Telemetry

        tel = Telemetry(metrics_path=args.metrics_out, every=args.metrics_every)

    opts = SweepOptions(
        engine=args.engine,
        jobs_glob=args.jobs,
        max_depth=args.max_depth,
        time_budget_s=args.time_budget,
        chunk=args.chunk,
        state_dir=args.state_dir,
        resume=args.resume,
        verbose=args.verbose,
        supervise=args.supervise,
    )

    from ..utils.cfg import CfgError

    try:
        res = run_sweep(mf, opts, telemetry=tel)
    except (ManifestError, CfgError) as e:
        print(f"sweep error: {e}", file=sys.stderr)
        return 64
    finally:
        if tel is not None:
            tel.close()

    for j in res.jobs:
        if args.json:
            print(json.dumps(j.to_json()))
        else:
            bits = [f"job={j.name}", f"rc={j.rc}"]
            if j.skipped:
                bits.append("skipped")
            elif j.mode == "check":
                bits += [
                    f"distinct={j.distinct}", f"total={j.total}",
                    f"depth={j.depth}", f"terminal={j.terminal}",
                ]
                if j.violation:
                    bits.append(f"VIOLATED={j.violation['invariant']}")
                if j.exit_cause:
                    bits.append(f"exit={j.exit_cause}")
                if j.recoveries:
                    bits.append(f"recoveries={j.recoveries}")
            else:
                bits += [f"behaviors={j.behaviors}", f"steps={j.steps}"]
                if j.violation:
                    bits.append(f"VIOLATED={j.violation['invariant']}")
            print(" ".join(bits))
    am = res.amortization
    if args.json:
        print(json.dumps(res.to_json()))
    else:
        print(
            f"fleet: jobs={am['jobs']} groups={am['groups']} "
            f"precompiles={am['precompiles']} time={res.seconds:.2f}s rc={res.rc}"
        )
    return res.rc
