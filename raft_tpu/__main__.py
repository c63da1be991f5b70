"""CLI — the `tlc` replacement.

    python -m raft_tpu path/to/Raft.cfg [--checker tpu|oracle] ...

Mirrors the reference workflow `tlc <Spec>.tla -config <Spec>.cfg -deadlock`
(reference README.md:5-7): `-deadlock` semantics are the default (terminal
states are reported, not errors). The CHECKER env var or --checker flag
selects the backend; `oracle` is the pure-Python differential reference.

Exit codes (stable contract, pinned by tests/test_resilience.py):

    0   clean run, no violations (also: `lint` found no findings)
    2   invariant or temporal-property violation found
    3   --coverage=strict dead-action gate tripped; `lint` findings
        (any error, or any warning under --strict)
    4   preempted (SIGTERM/SIGINT): a resumable checkpoint was written
        at the next wave boundary; re-run with --resume to continue
    5   unrecoverable failure (retry budget spent, capacity overflow
        with no growth policy or no checkpoint, all generations corrupt,
        shard lost / shard stalled without --supervise, or the backend
        --platform names cannot start on this machine)
    64  usage/config error (bad flags, bad cfg, checkpoint spec mismatch)
    66  input file not found (cfg or --resume path)
    70  fingerprint-collision audit failed
"""

from __future__ import annotations

import argparse
import os
import sys


def main(argv=None):
    argv = sys.argv[1:] if argv is None else list(argv)
    if argv and argv[0] == "sweep":
        # fleet subcommand: `raft_tpu sweep MANIFEST.json` (fleet/cli.py)
        from .fleet.cli import sweep_main

        return sweep_main(argv[1:])
    if argv and argv[0] == "lint":
        # static-analysis subcommand: `raft_tpu lint [--strict] [--json]
        # [--pass NAME] [--mutate NAME]` (analysis/cli.py)
        from .analysis.cli import lint_main

        return lint_main(argv[1:])
    from .utils.platform import add_platform_arg, select_platform

    ap = argparse.ArgumentParser(prog="raft_tpu")
    ap.add_argument("cfg", help="TLC .cfg file (the spec is inferred from its name)")
    ap.add_argument("--spec", help="spec/module name override")
    ap.add_argument(
        "--checker",
        default=os.environ.get("CHECKER", "tpu"),
        choices=["tpu", "sharded", "tpu-host", "oracle"],
        help="backend: tpu (single-device BFS), sharded (multi-chip "
        "frontier-sharded BFS over a device mesh — the `tlc -workers N` "
        "replacement), tpu-host (device expansion + host dedup, the v1 "
        "driver), or oracle (pure-Python reference)",
    )
    ap.add_argument(
        "--devices",
        type=int,
        default=None,
        metavar="N",
        help="mesh size for --checker sharded (default: all visible "
        "devices; on CPU set XLA_FLAGS=--xla_force_host_platform_"
        "device_count=N before launch to expose N virtual devices)",
    )
    ap.add_argument("--frontier-cap", type=int, default=None,
                    help="device frontier buffer rows (tpu checker)")
    ap.add_argument("--seen-cap", type=int, default=None,
                    help="device seen-set capacity (tpu checker)")
    ap.add_argument("--journal-cap", type=int, default=None,
                    help="device trace-journal capacity (tpu checker)")
    ap.add_argument("--time-budget", type=float, default=None,
                    help="stop (non-exhausted) after this many seconds")
    ap.add_argument("--checkpoint", default=None, metavar="PATH",
                    help="periodically save resumable run state (tpu checker)")
    ap.add_argument("--checkpoint-every", type=float, default=300.0,
                    metavar="S", help="seconds between checkpoints")
    ap.add_argument("--resume", default=None, metavar="PATH",
                    help="resume a run from a --checkpoint file (tpu checker)")
    ap.add_argument(
        "--checkpoint-keep",
        type=int,
        default=3,
        metavar="N",
        help="checkpoint generations to rotate (PATH, PATH.gen1, ...); "
        "a torn newest generation falls back to the previous intact one",
    )
    ap.add_argument(
        "--supervise",
        nargs="?",
        const=5,
        type=int,
        default=None,
        metavar="RETRIES",
        help="wrap the run in the auto-resume supervisor: capacity "
        "overflows rebuild the engine with grown capacities and resume "
        "from the newest intact checkpoint; transient device failures "
        "retry with exponential backoff (default budget: 5 recoveries)",
    )
    ap.add_argument(
        "--chaos",
        default=None,
        metavar="SPEC",
        help="deterministic fault injection for drills and tests: "
        "comma-separated key=int pairs from crash=WAVE (raise at wave "
        "start), transient=WAVE (injected device flake), ovf=WAVE "
        "(spurious frontier-overflow bit), truncate=NTH (tear the Nth "
        "checkpoint write), preempt=WAVE (SIGTERM self-delivery), "
        "shard_loss=WAVE (kill one shard of the sharded mesh mid-wave; "
        "the lost shard is seed mod D), seed=S; each fault fires once",
    )
    ap.add_argument(
        "--no-reshard",
        action="store_true",
        help="refuse to resume a sharded checkpoint written on a "
        "different mesh size (default: re-route the shards by fp mod D "
        "on load — checkpoints are mesh-portable)",
    )
    ap.add_argument(
        "--stall-abort",
        type=float,
        default=None,
        metavar="FACTOR",
        help="sharded checker: abort a wave that runs longer than FACTOR "
        "times the rolling-median wave time, spilling a wave-start "
        "checkpoint and raising a shard-stall (recoverable under "
        "--supervise); needs at least 3 completed waves to calibrate",
    )
    ap.add_argument("--max-frontier-cap", type=int, default=None,
                    help="frontier growth bound (tpu checker)")
    ap.add_argument("--max-seen-cap", type=int, default=None,
                    help="seen-set growth bound (tpu checker)")
    ap.add_argument("--max-journal-cap", type=int, default=None,
                    help="journal growth bound (tpu checker)")
    ap.add_argument("--max-depth", type=int, default=None)
    ap.add_argument(
        "--collision-audit",
        type=int,
        default=None,
        metavar="DEPTH",
        help="before the main run, explore to DEPTH under two independent "
        "fingerprint hash families and require identical counts (bounds "
        "silent hash-collision risk; tpu checker only)",
    )
    ap.add_argument("--chunk", type=int, default=1024, help="device batch size")
    ap.add_argument(
        "--simulate",
        type=int,
        default=None,
        metavar="N",
        help="simulation mode (TLC -simulate): run N random behaviors "
        "instead of exhaustive BFS — the reference's prescribed mode for "
        "FlexibleRaft.cfg and KRaftWithReconfig.cfg",
    )
    ap.add_argument("--sim-depth", type=int, default=50,
                    help="max behavior length in simulation mode")
    ap.add_argument("--sim-walks", type=int, default=128,
                    help="parallel walks per device batch in simulation mode")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--msg-slots", type=int, default=None,
                    help="message-bag slot count (default: per-spec)")
    ap.add_argument(
        "--net-faults",
        action="store_true",
        help="enable the opt-in DuplicateMessage/DropMessage network-"
        "fault actions (Raft.tla:508-523; Raft family only; duplication "
        "bounded to max_msg_copies per record)",
    )
    ap.add_argument("--no-symmetry", action="store_true", help="ignore SYMMETRY")
    ap.add_argument(
        "--trace-format",
        default="default",
        choices=["default", "tlc"],
        help="counterexample trace format: tlc emits TLC's textual error-"
        "trace shape (Error: headers + State N + /\\ var = value) for "
        "offline bit-for-bit diffing against a real TLC run",
    )
    ap.add_argument(
        "--lenient",
        action="store_true",
        help="downgrade recoverable cfg bugs (e.g. PullRaft.cfg's undeclared "
        "v2) to warnings and apply the obvious repair",
    )
    ap.add_argument(
        "--progress",
        nargs="?",
        const=10.0,
        type=float,
        default=None,
        metavar="SECS",
        help="TLC-style progress line on stderr (throttled to one line "
        "per SECS seconds, default 10; stall warnings print immediately)",
    )
    ap.add_argument(
        "--coverage",
        nargs="?",
        const="table",
        choices=["table", "strict"],
        default=None,
        help="after the run, print a TLC-style per-action coverage table "
        "(enabled / fired / new-distinct states per action, cumulative "
        "over the run) with WARNING lines for actions that never fired; "
        "--coverage=strict additionally exits 3 when any action never "
        "fired (dead-action gate for CI); BFS checkers only",
    )
    ap.add_argument(
        "--metrics-out",
        default=None,
        metavar="PATH",
        help="write the live telemetry event stream (manifest/wave/stall/"
        "summary, one JSON object per line) to PATH; validate with "
        "scripts/check_metrics_schema.py",
    )
    ap.add_argument(
        "--metrics-every",
        type=int,
        default=1,
        metavar="N",
        help="write every Nth wave event (the final wave always flushes, "
        "so the stream stays count-accurate)",
    )
    ap.add_argument(
        "--trace-dir",
        default=None,
        metavar="DIR",
        help="open a jax.profiler session for the run and write its trace "
        "to DIR. The spans (run, init, wave with dispatch/fetch/"
        "seen_merge/..., finish; each wave an xprof step) and the stage "
        "scopes on the device ops are always there: this only records "
        "them; scripts/stage_split.py --trace-dir DIR reduces it to stages",
    )
    ap.add_argument(
        "--json",
        action="store_true",
        help="print the run's summary event as the last stdout line "
        "(machine-readable; everything else non-result already goes to "
        "stderr); BFS checkers only",
    )
    add_platform_arg(ap)
    ap.add_argument("--verbose", "-v", action="store_true")
    args = ap.parse_args(argv)

    chaos_spec = None
    if args.chaos:
        from .resilience import ChaosSpec

        try:
            chaos_spec = ChaosSpec.parse(args.chaos)
        except ValueError as e:
            print(f"error: --chaos: {e}", file=sys.stderr)
            return 64

    rc = select_platform(args.platform)
    if rc:
        return rc

    trace = None
    if args.checker != "oracle":
        # the backend starts here, as the set-up phase `backend`, and
        # with it up the --trace-dir session opens before the cfg is
        # read: the trace then holds setup/cfg, setup/model,
        # setup/engine and the first run's init on the device's clock
        from . import start_backend
        from .obs import TraceSession

        start_backend()
        trace = TraceSession(args.trace_dir)
    try:
        return _check(args, chaos_spec, trace)
    finally:
        if trace is not None:
            trace.stop()  # whichever way _check returned; once


def _check(args, chaos_spec, trace) -> int:
    """Everything after the flags are read and the backend is up: cfg,
    model, engine, run, report. Returns the exit code."""
    from .utils.cfg import CfgError, parse_cfg
    from .models.registry import build_from_cfg

    try:
        cfg = parse_cfg(args.cfg, lenient=args.lenient)
        for diag in cfg.diagnostics:
            print(f"config warning: {diag}", file=sys.stderr)
        setup = build_from_cfg(
            cfg, spec=args.spec, msg_slots=args.msg_slots,
            net_faults=args.net_faults,
        )
    except FileNotFoundError as e:
        print(f"error: {e}", file=sys.stderr)
        return 66
    except CfgError as e:
        # includes the deliberately-broken reference cfgs (SURVEY.md §2.2)
        print(f"config error: {e}", file=sys.stderr)
        return 64
    symmetry = setup.symmetry and not args.no_symmetry
    props = tuple(cfg.properties)
    # non-result chatter (banner, config warnings, audit diagnostics,
    # progress) goes to stderr: stdout carries only the result lines —
    # and, under --json, the summary event as its last line
    where = ""
    if args.checker != "oracle":
        # say which device the run lands on: under --platform auto a
        # chipless machine runs `--checker tpu` on the CPU
        import jax

        dev = jax.devices()[0]
        where = f" platform={dev.platform} device={dev.device_kind!r}"
    print(
        f"spec={setup.model.name} servers={setup.server_names} "
        f"values={setup.value_names} invariants={list(setup.invariants)} "
        f"properties={list(props)} symmetry={symmetry} checker={args.checker}"
        + where,
        file=sys.stderr,
    )
    if props:
        # PROPERTY lines are temporal formulas; refuse configurations this
        # build cannot check rather than silently dropping them
        # (round-2 verdict item 5)
        supported = getattr(setup.model, "liveness", {})
        unknown = [p for p in props if p not in supported]
        if unknown:
            print(
                f"error: PROPERTY {' '.join(unknown)}: no liveness support "
                f"for spec {setup.model.name}; remove the PROPERTY line or "
                "use a supported formula "
                f"(supported: {', '.join(supported) or 'none'})",
                file=sys.stderr,
            )
            return 64
        if args.simulate is not None or args.checker == "oracle":
            print(
                "error: PROPERTY checking needs the exhaustive device "
                "graph; run with --checker tpu and no --simulate",
                file=sys.stderr,
            )
            return 64
        if args.max_depth is not None or args.time_budget is not None:
            print(
                "error: PROPERTY checking is unsound on a partially "
                "explored graph; drop --max-depth/--time-budget",
                file=sys.stderr,
            )
            return 64

    if args.checker in ("tpu", "sharded", "tpu-host") and not hasattr(setup.model, "expand"):
        print(
            f"error: spec {setup.model.name} has no TPU lowering yet; use "
            "--checker oracle (exhaustive or --simulate)",
            file=sys.stderr,
        )
        return 64

    if args.coverage is not None and (
        args.checker == "oracle" or args.simulate is not None
    ):
        print(
            "error: --coverage needs a BFS checker (tpu, sharded, or "
            "tpu-host) and no --simulate",
            file=sys.stderr,
        )
        return 64

    # device-checker capacity flags, shared by the collision audit and the
    # main run so both execute at the same geometry
    cli_caps = {
        k: v
        for k, v in {
            "frontier_cap": args.frontier_cap,
            "seen_cap": args.seen_cap,
            "journal_cap": args.journal_cap,
            "max_frontier_cap": args.max_frontier_cap,
            "max_seen_cap": args.max_seen_cap,
            "max_journal_cap": args.max_journal_cap,
        }.items()
        if v is not None
    }

    if args.collision_audit is not None:
        if args.checker != "tpu" or args.simulate is not None:
            print(
                "error: --collision-audit needs --checker tpu and no "
                "--simulate (the audit re-runs the exhaustive BFS)",
                file=sys.stderr,
            )
            return 64
        from .checker.audit import collision_audit

        audit = collision_audit(
            setup.model, invariants=setup.invariants, symmetry=symmetry,
            depth=args.collision_audit, chunk=args.chunk, **cli_caps,
        )
        print(audit, file=sys.stderr)
        if not audit.ok:
            print(
                "error: fingerprint-collision audit failed — counts differ "
                "between hash families; results would be untrustworthy",
                file=sys.stderr,
            )
            return 70

    if args.checker == "oracle" and args.simulate is not None:
        from .models.registry import oracle_for_setup

        oracle = oracle_for_setup(setup)
        if not hasattr(oracle, "simulate"):
            print(
                "error: --simulate with the oracle backend is only "
                "supported for specs whose oracle implements it; use the "
                "tpu checker's --simulate instead",
                file=sys.stderr,
            )
            return 64
        res = oracle.simulate(
            invariants=setup.invariants,
            behaviors=args.simulate,
            max_depth=args.sim_depth,
            seed=args.seed,
        )
        print(f"simulate: behaviors={res['behaviors']} steps={res['steps']}")
        if res["violation"]:
            print(f"INVARIANT {res['violation']['invariant']} VIOLATED")
            return 2
        print("no invariant violations (simulation is not exhaustive)")
        return 0

    if args.checker == "oracle":
        from .models.registry import oracle_for_setup

        oracle = oracle_for_setup(setup)  # carries all variant knobs
        res = oracle.bfs(
            invariants=setup.invariants,
            symmetry=symmetry,
            max_depth=args.max_depth,
            time_budget_s=args.time_budget,
        )
        print(
            f"distinct={res['distinct']} total={res['total']} "
            f"depth={len(res['depth_counts']) - 1}"
        )
        if res["violation"]:
            print(f"INVARIANT {res['violation']['invariant']} VIOLATED")
            return 2
        print("no invariant violations")
        return 0

    if args.simulate is not None:
        from .checker.simulate import Simulator

        sim = Simulator(
            setup.model,
            invariants=setup.invariants,
            walks=args.sim_walks,
            max_behavior_depth=args.sim_depth,
            seed=args.seed,
        )
        res = sim.run(max_behaviors=args.simulate, verbose=args.verbose)
        print(
            f"simulate: behaviors={res.behaviors} steps={res.steps} "
            f"time={res.seconds:.2f}s ({res.states_per_sec:.0f} states/s)"
        )
        if res.violation:
            print(
                f"INVARIANT {res.violation.invariant} VIOLATED "
                f"(walk {res.violation.walk}, depth {res.violation.depth})"
            )
            if res.trace:
                from .utils.pprint import format_trace, format_trace_tlc

                if args.trace_format == "tlc":
                    print(format_trace_tlc(res.trace, setup,
                                           res.violation.invariant))
                else:
                    print(format_trace(res.trace, setup))
            return 2
        print("no invariant violations (simulation is not exhaustive)")
        return 0

    if args.checker == "sharded":
        import jax

        from .parallel.sharded import ShardedBFS

        devs = jax.devices()
        if args.devices is not None:
            if args.devices > len(devs):
                print(
                    f"error: --devices {args.devices} > {len(devs)} visible "
                    "devices (on CPU expose more with XLA_FLAGS="
                    "--xla_force_host_platform_device_count=N)",
                    file=sys.stderr,
                )
                return 64
            devs = devs[: args.devices]

        def make_checker(overrides):
            # the supervisor's shard-loss recovery passes a shrunk
            # "devices" override (the survivors); pop it out of the
            # capacity-override dict so it lands on the keyword
            ov = dict(overrides)
            devs_ = ov.pop("devices", devs)
            return ShardedBFS(
                setup.model,
                invariants=setup.invariants,
                symmetry=symmetry,
                devices=devs_,
                chunk=args.chunk,
                **{**cli_caps, **ov},
            )
    elif args.checker == "tpu":
        from .checker.device_bfs import DeviceBFS

        def make_checker(overrides):
            return DeviceBFS(
                setup.model,
                invariants=setup.invariants,
                symmetry=symmetry,
                chunk=args.chunk,
                **{**cli_caps, **overrides},
            )
    else:
        from .checker.bfs import BFSChecker

        def make_checker(overrides):
            # the host engine's buffers are unbounded; overflow growth
            # policies are the empty dict, so overrides carry no keys
            return BFSChecker(
                setup.model,
                invariants=setup.invariants,
                symmetry=symmetry,
                chunk=args.chunk,
            )

    checker = make_checker({})

    if args.resume is not None:
        # fail fast, BEFORE the run's first compiles (many seconds): prove the
        # checkpoint exists, loads (falling back through generations)
        # and matches this exact model/capacity identity
        from .resilience import ckpt as rckpt
        from .resilience.errors import CheckpointCorrupt, CheckpointMismatch

        try:
            gen, ck_depth = rckpt.validate_resume(
                args.resume, checker._ckpt_ident(), keep=args.checkpoint_keep,
                allow_reshard=(
                    args.checker == "sharded" and not args.no_reshard
                ),
            )
        except FileNotFoundError as e:
            print(f"error: --resume: {e}", file=sys.stderr)
            return 66
        except CheckpointCorrupt as e:
            print(f"error: --resume: {e}", file=sys.stderr)
            for p in e.problems:
                print(f"  {p}", file=sys.stderr)
            return 5
        except CheckpointMismatch as e:
            print(f"error: --resume: {e}", file=sys.stderr)
            return 64
        print(
            f"resume: validated {args.resume} "
            f"(generation {gen}, depth {ck_depth})",
            file=sys.stderr,
        )

    # parent directories for artifact paths, so a fresh machine can point
    # both at a not-yet-existing run directory
    for _p in (args.checkpoint, args.metrics_out):
        if _p:
            _dn = os.path.dirname(_p)
            if _dn:
                os.makedirs(_dn, exist_ok=True)

    tel = None
    if (
        args.progress is not None or args.metrics_out is not None
        or args.trace_dir is not None or args.json
    ):
        from .obs import Telemetry

        tel = Telemetry(
            metrics_path=args.metrics_out,
            every=args.metrics_every,
            progress_every=args.progress,
            trace=trace,
        )

    def _finish(rc: int) -> int:
        """Close telemetry and, under --json, make the summary event the
        last stdout line on EVERY BFS-checker return path."""
        if tel is not None:
            tel.close()
            if args.json and tel.last_summary is not None:
                import json

                print(json.dumps(tel.last_summary))
        return rc

    from .resilience import PreemptionGuard
    from .resilience.errors import (
        CapacityOverflow,
        CheckpointCorrupt,
        CheckpointMismatch,
        ShardLost,
        ShardStall,
        UnrecoverableError,
    )

    # all three BFS engines share the checkpoint/resume/preempt surface
    run_kw = dict(
        max_depth=args.max_depth,
        verbose=args.verbose,
        time_budget_s=args.time_budget,
        telemetry=tel,
        checkpoint_path=args.checkpoint,
        checkpoint_every_s=args.checkpoint_every,
        checkpoint_keep=args.checkpoint_keep,
        resume=args.resume,
    )
    if args.checker == "sharded":
        run_kw["reshard"] = not args.no_reshard
        if args.stall_abort is not None:
            run_kw["stall_abort_factor"] = args.stall_abort
    if chaos_spec is not None:
        # ONE injector for the whole session: each fault fires once even
        # across supervisor attempts (a crash-at-wave-3 must not re-fire
        # after the resume passes wave 3 again)
        from .resilience import ChaosInjector

        run_kw["chaos"] = ChaosInjector(chaos_spec)
    guard = PreemptionGuard().install()
    run_kw["preempt"] = guard
    try:
        if args.supervise is not None:
            from .resilience import supervise

            res = supervise(
                make_checker,
                run_kw,
                max_retries=args.supervise,
                seed=args.seed,
                telemetry=tel,
                verbose=args.verbose,
            )
        else:
            res = checker.run(**run_kw)
    except CheckpointMismatch as e:
        print(f"error: {e}", file=sys.stderr)
        return _finish(64)
    except (CheckpointCorrupt, UnrecoverableError) as e:
        print(f"error: {e}", file=sys.stderr)
        return _finish(5)
    except (ShardLost, ShardStall) as e:
        print(f"error: {e}", file=sys.stderr)
        if getattr(e, "checkpoint_saved", False):
            print(
                f"hint: a wave-start checkpoint was spilled to "
                f"{args.checkpoint}; re-run with --supervise to shrink "
                "the mesh onto the survivors and resume automatically",
                file=sys.stderr,
            )
        else:
            print(
                "hint: re-run with --supervise and --checkpoint PATH to "
                "recover shard failures automatically",
                file=sys.stderr,
            )
        return _finish(5)
    except CapacityOverflow as e:
        print(f"error: {e}", file=sys.stderr)
        print(
            "hint: re-run with --supervise (and --checkpoint PATH) to "
            "auto-grow capacities and resume",
            file=sys.stderr,
        )
        return _finish(5)
    finally:
        guard.uninstall()
    viol_name = (
        res.violation_invariant if args.checker == "sharded"
        else (res.violation.invariant if res.violation else None)
    )

    def _print_coverage() -> int:
        """TLC-style per-action coverage table (--coverage); returns the
        strict-mode exit code (3 when an action never fired)."""
        if args.coverage is None:
            return 0
        cov = getattr(res, "coverage", None)
        names = getattr(setup.model, "ACTION_NAMES", None)
        if cov is None or not names:
            print("coverage: not available for this spec", file=sys.stderr)
            return 0
        from .obs import dead_actions, render_coverage_table

        print(render_coverage_table(names, cov))
        if args.coverage == "strict" and dead_actions(names, cov):
            return 3
        return 0

    print(
        f"distinct={res.distinct} total={res.total} depth={res.depth} "
        f"terminal={res.terminal} time={res.seconds:.2f}s "
        f"({res.states_per_sec:.0f} distinct/s)"
        + (f" devices={checker.D}" if args.checker == "sharded" else "")
    )
    if viol_name:
        vdepth = res.depth if args.checker == "sharded" else res.violation.depth
        print(f"INVARIANT {viol_name} VIOLATED (depth {vdepth})")
        if res.trace:
            from .utils.pprint import format_trace, format_trace_tlc

            if args.trace_format == "tlc":
                print(format_trace_tlc(res.trace, setup, viol_name))
            else:
                print(format_trace(res.trace, setup))
        _print_coverage()  # violation rc 2 outranks the strict gate
        return _finish(2)
    if getattr(res, "exit_cause", None) == "preempted":
        # distinct rc so preemptible-TPU schedulers can tell "requeue
        # me with --resume" (4) apart from clean completion (0)
        print(
            f"preempted ({guard.signame}): "
            + (f"resumable checkpoint saved to {args.checkpoint}; "
               f"re-run with --resume {args.checkpoint}"
               if args.checkpoint
               else "no --checkpoint was set, progress is lost")
        )
        return _finish(4)
    print("no invariant violations")
    cov_rc = _print_coverage()

    if props:
        from .checker.liveness import LivenessChecker

        live = LivenessChecker(setup.model, props, chunk=args.chunk).run(
            verbose=args.verbose
        )
        print(
            f"liveness: graph {live.distinct} states / {live.total_edges} "
            f"edges (symmetry off), properties={list(props)}, "
            f"{live.seconds:.2f}s"
        )
        if live.violation:
            v = live.violation
            kind = "terminal stutter" if v.terminal else "cycle"
            print(
                f"PROPERTY {v.prop}[{v.instance}] VIOLATED "
                f"({kind}; prefix {len(v.prefix) - 1} steps, "
                f"loop {len(v.cycle)} steps)"
            )
            from .utils.pprint import format_trace, format_trace_tlc

            if args.trace_format == "tlc":
                # TLC prints a temporal counterexample as one behavior
                # with a "Back to state" marker at the loop entry
                print(format_trace_tlc(v.prefix, setup, None))
                if v.cycle:
                    print("-- Back to state: the loop below repeats --")
                    print(format_trace(v.cycle, setup))
            else:
                print(format_trace(v.prefix, setup))
                if v.cycle:
                    print("-- loop (repeats forever) --")
                    print(format_trace(v.cycle, setup))
            return _finish(2)
        print("no temporal property violations")
    return _finish(cov_rc)


if __name__ == "__main__":
    sys.exit(main())
