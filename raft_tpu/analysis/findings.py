"""The findings model every lint pass reports through.

A finding is ``file:line``-anchored (repo-relative, so output is stable
across checkouts), carries the pass id and a severity, and serializes to
JSON for machine consumers (``raft_tpu lint --json``).
Severity semantics follow the CLI contract:

  error    a broken contract — ``lint`` exits 3 even without --strict
  warning  a drift/coverage gap — exits 3 only under --strict
  info     advisory (reported, never gates)
"""

from __future__ import annotations

import dataclasses
import inspect
import os

SEVERITIES = ("error", "warning", "info")

# raft_tpu/analysis/findings.py -> the repo checkout root
REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def rel(path: str) -> str:
    """Repo-relative form of ``path`` (pass through if already outside
    the checkout — fixture sources in tests report their given name)."""
    ap = os.path.abspath(path)
    if ap.startswith(REPO_ROOT + os.sep):
        return os.path.relpath(ap, REPO_ROOT)
    return path


def site_of(obj) -> tuple[str, int]:
    """(repo-relative file, first line) of a function/method/class —
    the anchor for findings about a program built from that code."""
    obj = inspect.unwrap(obj)
    try:
        path = inspect.getsourcefile(obj) or "<unknown>"
        _, line = inspect.getsourcelines(obj)
    except (OSError, TypeError):
        return "<unknown>", 0
    return rel(path), line


@dataclasses.dataclass
class Finding:
    pass_id: str
    severity: str
    path: str
    line: int
    message: str
    detail: dict = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        assert self.severity in SEVERITIES, self.severity
        self.path = rel(self.path)

    @property
    def location(self) -> str:
        return f"{self.path}:{self.line}"

    def to_dict(self) -> dict:
        return {
            "pass": self.pass_id,
            "severity": self.severity,
            "file": self.path,
            "line": self.line,
            "message": self.message,
            "detail": self.detail,
        }

    def render(self) -> str:
        out = (
            f"{self.severity.upper():7s} [{self.pass_id}] "
            f"{self.location}: {self.message}"
        )
        if self.detail:
            pairs = " ".join(f"{k}={v}" for k, v in sorted(self.detail.items()))
            out += f"  ({pairs})"
        return out


@dataclasses.dataclass
class PassResult:
    """One pass run: its findings plus how much it actually audited
    (``checked`` = programs lowered / modules scanned / families proved —
    a pass that silently audits nothing must not read as clean)."""

    pass_id: str
    findings: list[Finding]
    checked: int
    seconds: float = 0.0
    notes: list[str] = dataclasses.field(default_factory=list)

    @property
    def errors(self) -> int:
        return sum(1 for f in self.findings if f.severity == "error")

    @property
    def warnings(self) -> int:
        return sum(1 for f in self.findings if f.severity == "warning")

    def to_dict(self) -> dict:
        return {
            "pass": self.pass_id,
            "checked": self.checked,
            "seconds": round(self.seconds, 3),
            "findings": [f.to_dict() for f in self.findings],
            "notes": self.notes,
        }
