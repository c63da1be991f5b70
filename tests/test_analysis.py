"""Kernel contract auditor (``raft_tpu lint``): tier-1 coverage.

Four layers, cheapest first:

  1. unit fixtures per pass — the parsers and AST scanners each get a
     positive (violation flagged, right line) and a negative (clean /
     blessed source stays clean) fixture, no jax work involved;
  2. the seeded-mutation kit — every ``--mutate`` name must make its
     targeted pass fire (exit 3) with a ``file:line``-anchored error
     naming that pass: the negative controls proving the auditor is
     alive, not vacuously clean;
  3. the CLI surface — exit-code contract (0 / 3 / 64), ``--json``
     round-trip, ``--list``, and the ``python -m raft_tpu lint``
     dispatch;
  4. the full-registry smoke: every pass over every family on CPU,
     strict-clean.

The events-drift regression for the ``stall`` contract-doc gap found
(and fixed) on this tree is pinned explicitly in
``test_schema_doc_mentions_every_declared_event``.
"""

import ast
import json
import re
import subprocess
import sys
import textwrap

import pytest

from raft_tpu.analysis import events_drift, lanes, sync
from raft_tpu.analysis.cli import (
    PASSES, exit_code, lint_main, run_lint, verdict,
)
from raft_tpu.analysis.donation import parse_main_aliasing, tensor_bytes
from raft_tpu.analysis.findings import Finding, PassResult, rel
from raft_tpu.analysis.selftest import MUTATIONS, PASS_OF

# ------------------------------------------------------ findings model


def test_finding_paths_are_repo_relative():
    f = Finding("donation", "error", rel(__file__), 12, "msg")
    assert not f.path.startswith("/")
    assert f.location == f"{f.path}:12"
    d = f.to_dict()
    assert (d["pass"], d["severity"], d["line"]) == ("donation", "error", 12)


def test_severity_gating():
    def res(sev):
        return [PassResult("p", [Finding("p", sev, "x.py", 1, "m")], 1)]

    assert exit_code(res("error"), strict=False) == 3
    assert exit_code(res("error"), strict=True) == 3
    assert exit_code(res("warning"), strict=False) == 0
    assert exit_code(res("warning"), strict=True) == 3
    assert exit_code(res("info"), strict=True) == 0
    assert exit_code([PassResult("p", [], 1)], strict=True) == 0
    assert verdict(res("warning"), strict=True)["clean"] is False


# -------------------------------------------------- donation: parsing

ALIASED_HLO = (
    "module @jit_wave {\n"
    "  func.func public @main(%arg0: tensor<8x4xi32>, "
    "%arg1: tensor<8x4xi32> {tf.aliasing_output = 0 : i32}, "
    '%arg2: tensor<16xi64> {mhlo.layout_mode = "default", '
    "tf.aliasing_output = 1 : i32}) -> "
    '(tensor<8x4xi32> {jax.result_info = "[0]"}, tensor<16xi64>) {\n'
    "    return\n  }\n}\n"
)


def test_parse_main_aliasing_fixture():
    args, results = parse_main_aliasing(ALIASED_HLO)
    assert args == {
        0: ("8x4xi32", None), 1: ("8x4xi32", 0), 2: ("16xi64", 1),
    }
    assert results == ["8x4xi32", "16xi64"]


def test_tensor_bytes():
    assert tensor_bytes("8x4xi32") == 8 * 4 * 4
    assert tensor_bytes("16xi64") == 16 * 8
    assert tensor_bytes("i1") == 1  # scalar


# --------------------------------------------- hidden-sync: scan_source

SYNC_BAD = textwrap.dedent("""
    def run(self):
        while frontier_count:
            stats = jax.device_get(state)
            n = total.item()
            arr = np.asarray(make_batch())
""")

SYNC_CLEAN = textwrap.dedent("""
    def run(self):
        while frontier_count:
            # lint: sync-ok(once-per-wave snapshot)
            stats = jax.device_get(state)
            host = np.asarray(already_host_array)
        final = jax.device_get(state)
""")


def test_sync_scan_flags_loop_syncs():
    findings = []
    audited = sync.scan_source(SYNC_BAD, "fixture.py", ("run",), findings)
    assert audited == 1
    kinds = sorted(f.detail["call"] for f in findings)
    assert kinds == [".item()", "jax.device_get", "np.asarray(<call>)"]
    assert all(f.severity == "error" and f.line > 1 for f in findings)


def test_sync_scan_blessed_and_off_loop_clean():
    findings = []
    audited = sync.scan_source(SYNC_CLEAN, "fixture.py", ("run",), findings)
    assert audited == 1
    # blessed loop sync, plain-array asarray, and the post-loop
    # device_get are all fine
    assert findings == []


def test_sync_scan_only_hot_functions():
    findings = []
    audited = sync.scan_source(
        SYNC_BAD, "fixture.py", ("other_fn",), findings)
    assert audited == 0 and findings == []


# ---------------------------------------- lane-discipline: AST readers

RANKS_SRC = textwrap.dedent("""
    (R_A, R_B, R_C, R_D, R_E, R_F, R_G, R_H, R_I, R_J) = range(10)
    R_TIMEOUT, R_FSYNC = 10, 11
    SMALL, ENUM = 0, 1
""")


def test_module_max_rank_reads_base_and_extension():
    assert lanes.module_max_rank(RANKS_SRC) == 11


def test_module_max_rank_none_without_table():
    assert lanes.module_max_rank("X = 3\n") is None
    # arity mismatch between targets and range() is a reader refusal
    bad = "(A, B, C, D, E, F, G, H, I, J, K) = range(10)\n"
    assert lanes.module_max_rank(bad) is None


CV_BAD = textwrap.dedent("""
    class M:
        def _restart(self, s, i):
            d = self._dec(s)
            return d + self.p.max_restarts

        def describe(self):
            return self.p.max_restarts
""")

CV_GOOD = textwrap.dedent("""
    class M:
        def _restart(self, s, i):
            d = self._dec(s)
            return d + self._cv(d, "max_restarts")
""")


def test_scan_dyn_consts_flags_raw_read_in_packed_scope():
    findings = []
    audited = lanes.scan_dyn_consts(
        CV_BAD, "fixture.py", {"max_restarts"}, findings)
    assert audited == 1  # describe() has no packed state: out of scope
    assert len(findings) == 1
    assert findings[0].detail == {
        "function": "_restart", "constant": "max_restarts"}


def test_scan_dyn_consts_cv_route_clean():
    findings = []
    audited = lanes.scan_dyn_consts(
        CV_GOOD, "fixture.py", {"max_restarts"}, findings)
    assert audited == 1 and findings == []


# ------------------------------------------------- events-drift: AST

VALIDATOR_SRC = textwrap.dedent("""
    def validate_event(etype, ev):
        if etype == "wave":
            pass
        elif etype in ("stall", "preempt"):
            pass

    def unrelated(etype):
        if etype == "not_scanned":
            pass
""")


def test_branch_literals_fixture():
    lits = events_drift.branch_literals(VALIDATOR_SRC)
    assert set(lits) == {"wave", "stall", "preempt"}
    assert all(line > 1 for line in lits.values())


def test_missing_doc_mentions_word_boundary():
    doc = "covers wave and shard_stall rows"
    missing = events_drift.missing_doc_mentions(
        doc, {"wave", "shard_stall", "stall"})
    # "shard_stall" must NOT mask the missing "stall" mention
    assert missing == ["stall"]


def test_schema_doc_mentions_every_declared_event():
    """Regression for the drift this pass caught on this tree: the
    check_metrics_schema.py contract doc omitted `stall`."""
    import os

    from raft_tpu.analysis.findings import REPO_ROOT
    from raft_tpu.obs.events import EVENT_KEYS

    with open(os.path.join(REPO_ROOT, events_drift.SCHEMA_SCRIPT)) as fh:
        doc = ast.get_docstring(ast.parse(fh.read())) or ""
    assert events_drift.missing_doc_mentions(doc, set(EVENT_KEYS)) == []


def test_events_drift_pass_clean():
    res = events_drift.run()
    assert res.checked > 0
    assert not res.findings, [f.render() for f in res.findings]


# -------------------------------------------------- seeded mutations


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_mutation_fires(name):
    """Each seeded contract violation makes exactly its targeted pass
    report an error anchored at file:line — lint would exit 3."""
    target = PASS_OF[name]
    with MUTATIONS[name]() as kw:
        results = run_lint((target,), {target: kw})
    assert exit_code(results, strict=False) == 3
    errors = [f for r in results for f in r.findings
              if f.severity == "error"]
    assert errors, f"mutation {name} produced no error finding"
    for f in errors:
        assert f.pass_id == target
        assert re.fullmatch(r"[^:]+\.py:\d+", f.location), f.location
        assert f.line > 0


def test_mutations_are_hermetic():
    """After the context exits, the targeted passes are clean again —
    a mutation must not leak into the shipped tree's verdict."""
    for name in ("injected-sync", "raw-const-read"):
        target = PASS_OF[name]
        with MUTATIONS[name]():
            pass
        res = run_lint((target,))
        assert not any(r.findings for r in res), name


# ------------------------------------------------------- CLI surface


def test_cli_usage_errors_exit_64(capsys):
    assert lint_main(["--bogus"]) == 64
    assert lint_main(["--pass", "no-such-pass"]) == 64
    assert lint_main(["--mutate", "no-such-mutation"]) == 64
    # a mutation whose target was excluded by --pass is a usage error
    assert lint_main(
        ["--pass", "events-drift", "--mutate", "injected-sync"]) == 64
    assert "raft_tpu lint" in capsys.readouterr().err


def test_cli_list_names_every_pass(capsys):
    assert lint_main(["--list"]) == 0
    out = capsys.readouterr().out
    for name in PASSES:
        assert name in out


def test_cli_json_verdict_round_trips(capsys):
    rc = lint_main(["--json", "--strict", "--pass", "events-drift"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["strict"] is True and doc["clean"] is True
    assert doc["errors"] == 0 and doc["warnings"] == 0
    assert [p["pass"] for p in doc["passes"]] == ["events-drift"]


def test_cli_mutate_exits_3(capsys):
    rc = lint_main(["--mutate", "raw-const-read"])
    out = capsys.readouterr().out
    assert rc == 3
    assert "lane-discipline" in out
    assert re.search(r"raft_tpu/models/\w+\.py:\d+", out)


def test_module_dispatch_runs_lint():
    out = subprocess.run(
        [sys.executable, "-m", "raft_tpu", "lint", "--strict", "--json",
         "--pass", "events-drift", "--pass", "hidden-sync"],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    doc = json.loads(out.stdout)
    assert doc["clean"] is True
    assert [p["pass"] for p in doc["passes"]] == [
        "events-drift", "hidden-sync"]


# ------------------------------------------------- full-registry smoke


def test_full_lint_strict_clean():
    """The acceptance gate: every pass over the full registry on CPU
    audits something and finds nothing — ``raft_tpu lint --strict``
    exits 0 on the shipped tree."""
    results = run_lint()
    assert [r.pass_id for r in results] == list(PASSES)
    for r in results:
        assert r.checked > 0, f"{r.pass_id} audited nothing"
        assert not r.findings, [f.render() for f in r.findings]
    assert exit_code(results, strict=True) == 0
