"""Fingerprint-collision audit — bounding the silent-collision risk.

Dedup runs on 64-bit canonical fingerprints (TLC's collision budget). A
hash collision silently MERGES two distinct states: counts drop and the
successors of the swallowed state are never explored, with no in-run
signal (exactly the failure shape of the round-2 compiler-induced dedup
miscount, just caused by the hash instead of the compiler). The audit re-runs the
same bounded workload under a SECOND independent hash family (different
splitmix64 seed, ops/hashing.py) and demands bit-identical per-depth
counts: a collision under seed A is astronomically unlikely to have a
matching collision under seed B (probability ~ distinct^2 / 2^64 per
family, independent across families), so agreement bounds the silent-
collision probability at the square of the single-run bound.

Complements checker/parity.py (which varies the BATCH GEOMETRY to catch
compiler miscompiles at a fixed hash); together they cover both silent-
dedup failure classes identified in the round-2 verdict.
"""

from __future__ import annotations

from dataclasses import dataclass

from .device_bfs import DeviceBFS


@dataclass
class AuditResult:
    ok: bool
    depth: int
    seeds: tuple[int, int]
    counts: tuple[list[int], list[int]]
    totals: tuple[int, int]
    terminals: tuple[int, int]

    def __str__(self):
        s = "PASS" if self.ok else "FAIL"
        return (
            f"collision audit {s}: depth={self.depth} seeds={self.seeds} "
            f"counts={'==' if self.ok else self.counts}"
        )


def collision_audit(
    model,
    invariants: tuple[str, ...] = (),
    symmetry: bool = True,
    depth: int = 10,
    seeds: tuple[int, int] = (0, 0x5EED5EED),
    chunk: int = 1024,
    frontier_cap: int | None = None,
    seen_cap: int = 1 << 20,
    journal_cap: int = 1 << 20,
    **caps,
) -> AuditResult:
    """Explore to `depth` under two hash seeds; identical depth_counts/
    total/terminal => audit passes. Extra **caps (max_*_cap) forward to
    DeviceBFS so a CLI-tuned geometry audits at its own sizes."""
    assert seeds[0] != seeds[1], "audit needs two distinct hash families"
    if frontier_cap is None:  # smallest chunk-multiple >= 1<<16
        frontier_cap = ((max(1 << 16, chunk) + chunk - 1) // chunk) * chunk
    runs = []
    for seed in seeds:
        ck = DeviceBFS(
            model, invariants=invariants, symmetry=symmetry, chunk=chunk,
            frontier_cap=frontier_cap, seen_cap=seen_cap,
            journal_cap=journal_cap, fingerprint_seed=seed, **caps,
        )
        runs.append(ck.run(max_depth=depth))
    a, b = runs
    ok = (
        a.depth_counts == b.depth_counts
        and a.total == b.total
        and a.terminal == b.terminal
    )
    return AuditResult(
        ok=ok,
        depth=depth,
        seeds=seeds,
        counts=(a.depth_counts, b.depth_counts),
        totals=(a.total, b.total),
        terminals=(a.terminal, b.terminal),
    )
