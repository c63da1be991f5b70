"""Hidden-sync detector: no device sync inside a chunk/wave loop.

The engines' zero-extra-sync guarantee — one ``device_get`` per wave,
everything else async dispatch — is what keeps the host out of the
device's way (and keeps telemetry from perturbing what it measures: the
observatory PR's first design cost a sync per chunk and skewed every
stage it attributed). This pass walks the AST of the HOT loop bodies
(``DeviceBFS.run``, ``ShardedBFS.run`` and the ``run_fleet`` they share,
``checker/engine.py`` ``FleetQueue``) and flags calls that force a
host-device round trip inside a ``for``/``while`` body:

  * ``jax.device_get(...)`` / ``jax.block_until_ready(...)``
  * ``.item()`` on anything
  * ``np.asarray(<call>)`` — wrapping a device-returning call forces
    materialization (plain ``np.asarray(host_array)`` is not flagged)

Blessed sites carry a ``lint: sync-ok(<why>)`` comment on the
statement or the line above it: the once-per-wave snapshot and the
wave-start spill on shard loss. The analysis is intra-function —
helpers called from the loop (checkpoint writers, abort paths) run
once per EVENT, not per chunk, and are out of scope by design.
"""

from __future__ import annotations

import ast
import os
import time

from .findings import Finding, PassResult, rel

PASS_ID = "hidden-sync"

BLESS_MARK = "lint: sync-ok"

# (repo-relative file) -> hot function names whose loop bodies must be
# sync-free; host-side modules (checker/bfs.py, simulate) are excluded
# by policy — they ARE the host loop.
HOT_SCOPES = {
    os.path.join("raft_tpu", "checker", "device_bfs.py"): ("run",),
    os.path.join("raft_tpu", "parallel", "sharded.py"): ("run",),
    os.path.join("raft_tpu", "checker", "engine.py"): ("run_fleet",),
}

# the hook the mutation self-test overrides: {rel_path: source_text}
SOURCE_OVERRIDES: dict | None = None


def _sync_call_kind(call: ast.Call) -> str | None:
    fn = call.func
    if isinstance(fn, ast.Attribute):
        if fn.attr == "item":
            return ".item()"
        if (isinstance(fn.value, ast.Name) and fn.value.id == "jax"
                and fn.attr in ("device_get", "block_until_ready")):
            return f"jax.{fn.attr}"
        if (isinstance(fn.value, ast.Name) and fn.value.id == "np"
                and fn.attr == "asarray" and call.args
                and isinstance(call.args[0], ast.Call)):
            return "np.asarray(<call>)"
    return None


def _blessed(lines: list[str], stmt: ast.stmt) -> bool:
    lo = max(0, stmt.lineno - 2)  # line above the statement
    hi = min(len(lines), getattr(stmt, "end_lineno", stmt.lineno))
    return any(BLESS_MARK in lines[i] for i in range(lo, hi))


def _loop_statements(fn: ast.FunctionDef):
    """Yield every statement nested inside a For/While body of ``fn``
    (inner functions are their own scopes and are skipped)."""
    def stmts_under(node):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                continue
            if isinstance(child, ast.stmt):
                yield child
            yield from stmts_under(child)

    seen = set()
    for node in ast.walk(fn):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node is not fn:
            continue
        if isinstance(node, (ast.For, ast.While)):
            for stmt in stmts_under(node):
                key = (stmt.lineno, stmt.col_offset)
                if key not in seen:
                    seen.add(key)
                    yield stmt


def scan_source(src: str, path: str, hot_names, findings: list) -> int:
    """Scan one module's source; returns the number of hot functions
    audited. ``path`` is used only for anchoring findings."""
    lines = src.splitlines()
    tree = ast.parse(src)
    audited = 0
    flagged = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.FunctionDef):
            continue
        if node.name not in hot_names:
            continue
        audited += 1
        for stmt in _loop_statements(node):
            for call in ast.walk(stmt):
                if not isinstance(call, ast.Call):
                    continue
                kind = _sync_call_kind(call)
                if kind is None:
                    continue
                key = (call.lineno, call.col_offset)
                if key in flagged:
                    continue
                flagged.add(key)
                if _blessed(lines, stmt):
                    continue
                findings.append(Finding(
                    PASS_ID, "error", path, call.lineno,
                    f"{kind} inside the {node.name}() chunk/wave loop "
                    f"— a host-device sync per iteration; hoist it to "
                    f"the once-per-wave snapshot or bless it with "
                    f"'# {BLESS_MARK}(<why>)'",
                    {"function": node.name, "call": kind},
                ))
    return audited


def run() -> PassResult:
    from .findings import REPO_ROOT

    t0 = time.time()
    findings: list[Finding] = []
    checked = 0
    for relpath, hot_names in sorted(HOT_SCOPES.items()):
        if SOURCE_OVERRIDES and relpath in SOURCE_OVERRIDES:
            src = SOURCE_OVERRIDES[relpath]
        else:
            with open(os.path.join(REPO_ROOT, relpath)) as fh:
                src = fh.read()
        checked += scan_source(src, rel(relpath), hot_names, findings)
    notes = [f"hot loops in {len(HOT_SCOPES)} engine modules"]
    return PassResult(PASS_ID, findings, checked, time.time() - t0, notes)
