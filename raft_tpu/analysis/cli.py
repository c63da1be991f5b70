"""``raft_tpu lint`` — the kernel contract auditor's CLI.

Usage:
  python -m raft_tpu lint [--strict] [--json] [--pass NAME]...
                          [--list] [--mutate NAME]

Exit codes (the repo-wide convention, see raft_tpu/__main__.py):
  0   clean (no errors; warnings allowed without --strict)
  3   findings: any error, or any warning under --strict
  64  usage error (unknown flag / pass / mutation)

``--pass NAME`` restricts the run (repeatable); ``--list`` prints the
pass catalogue; ``--mutate NAME`` applies one seeded contract
violation from the self-test kit and runs the targeted pass — the
negative control proving the auditor fires (expected exit: 3).
``--json`` emits one machine-readable document on stdout (``verdict``).
"""

from __future__ import annotations

import json
import sys
import time

from . import donation, events_drift, guard_purity, lanes, signatures, sync
from .selftest import MUTATIONS, PASS_OF

PASSES = {
    "donation": donation.run,
    "signatures": signatures.run,
    "guard-purity": guard_purity.run,
    "hidden-sync": sync.run,
    "lane-discipline": lanes.run,
    "events-drift": events_drift.run,
}


def run_lint(pass_names=None, pass_kwargs=None):
    """Run the selected passes (all, in catalogue order, by default);
    returns the list of PassResult."""
    names = tuple(pass_names) if pass_names else tuple(PASSES)
    kwargs = pass_kwargs or {}
    return [PASSES[n](**kwargs.get(n, {})) for n in names]


def exit_code(results, strict: bool) -> int:
    errors = sum(r.errors for r in results)
    warnings = sum(r.warnings for r in results)
    if errors or (strict and warnings):
        return 3
    return 0


def verdict(results, strict: bool) -> dict:
    """The machine-readable summary (``--json``)."""
    return {
        "strict": strict,
        "errors": sum(r.errors for r in results),
        "warnings": sum(r.warnings for r in results),
        "checked": sum(r.checked for r in results),
        "clean": exit_code(results, strict) == 0,
        "passes": [r.to_dict() for r in results],
    }


def _usage(msg: str) -> int:
    print(f"raft_tpu lint: {msg}", file=sys.stderr)
    print(__doc__, file=sys.stderr)
    return 64


def lint_main(argv) -> int:
    strict = as_json = list_only = False
    chosen: list = []
    mutate = None
    it = iter(argv)
    for a in it:
        if a == "--strict":
            strict = True
        elif a == "--json":
            as_json = True
        elif a == "--list":
            list_only = True
        elif a == "--pass":
            name = next(it, None)
            if name is None or name not in PASSES:
                return _usage(
                    f"--pass expects one of {', '.join(PASSES)}")
            chosen.append(name)
        elif a == "--mutate":
            mutate = next(it, None)
            if mutate is None or mutate not in MUTATIONS:
                return _usage(
                    f"--mutate expects one of {', '.join(MUTATIONS)}")
        else:
            return _usage(f"unknown argument {a!r}")

    if list_only:
        for name in PASSES:
            doc = (sys.modules[PASSES[name].__module__].__doc__ or "")
            head = doc.strip().splitlines()[0] if doc.strip() else ""
            print(f"{name:16s} {head}")
        if not as_json:
            return 0

    t0 = time.time()
    if mutate is not None:
        target = PASS_OF[mutate]
        if chosen and target not in chosen:
            return _usage(
                f"--mutate {mutate} targets pass '{target}', which "
                f"--pass excluded")
        with MUTATIONS[mutate]() as kw:
            results = run_lint((target,), {target: kw})
    else:
        results = run_lint(chosen or None)

    if as_json:
        print(json.dumps(verdict(results, strict), indent=2))
    else:
        n_findings = 0
        for r in results:
            status = "clean" if not r.findings else (
                f"{r.errors} error(s), {r.warnings} warning(s)")
            print(f"[{r.pass_id}] checked {r.checked} in "
                  f"{r.seconds:.1f}s: {status}")
            for note in r.notes:
                print(f"    note: {note}")
            for f in r.findings:
                n_findings += 1
                print(f"  {f.render()}")
        rc = exit_code(results, strict)
        label = "MUTATION " + mutate if mutate else "lint"
        print(f"{label}: {n_findings} finding(s) across "
              f"{len(results)} pass(es) in {time.time() - t0:.1f}s -> "
              f"exit {rc}")
    return exit_code(results, strict)
