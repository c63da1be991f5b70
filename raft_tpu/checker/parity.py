"""On-device parity gate — trust-but-verify for the fast path.

A TPU compiler has miscompiled fused expansion programs in a
batch-size-dependent way before (a dynamic-index scatter write silently
dropped at chunk>=4096 on the backend of rounds 2-5; ops/bag.py has
used one-hot writes since). Counts that are wrong but self-consistent cannot be
caught by any in-run check, so before trusting a long run the driver can
run this gate: explore the same workload to a shallow depth at two chunk
sizes and require bit-identical per-depth counts. A compiler bug of that
class changes results when the batch geometry changes; agreement across
geometries (plus the CPU test suite pinning the same counts) bounds the
risk.

Cost: two shallow BFS runs (seconds); run once per (model, platform).
"""

from __future__ import annotations

from dataclasses import dataclass

from .device_bfs import DeviceBFS


@dataclass
class ParityGateResult:
    ok: bool
    depth: int
    chunks: tuple[int, int]
    counts: tuple[list[int], list[int]]

    def __str__(self):
        s = "PASS" if self.ok else "FAIL"
        return (
            f"parity gate {s}: depth={self.depth} chunks={self.chunks} "
            f"counts={'==' if self.ok else self.counts}"
        )


def parity_gate(
    model=None,
    invariants: tuple[str, ...] = (),
    symmetry: bool = True,
    depth: int = 12,
    chunks: tuple[int, int] = (2048, 4096),
    frontier_cap: int = 1 << 16,
    seen_cap: int = 1 << 20,
    checkers: tuple[DeviceBFS, DeviceBFS] | None = None,
) -> ParityGateResult:
    """Run the workload to `depth` at two chunk geometries; identical
    depth_counts/total/terminal => gate passes.

    Pass prebuilt `checkers` (e.g. to reuse a long run's compiled
    instance as one arm) or let the gate build both from `model`. The
    two arms must have different chunk geometries — identical geometries
    would make the gate vacuous."""
    if checkers is None and model is None:
        raise ValueError("parity_gate requires either `model` or prebuilt `checkers`")
    if checkers is None:
        checkers = tuple(
            DeviceBFS(
                model,
                invariants=invariants,
                symmetry=symmetry,
                chunk=chunk,
                frontier_cap=frontier_cap,
                seen_cap=seen_cap,
                journal_cap=seen_cap,
            )
            for chunk in chunks
        )
    if checkers[0].chunk == checkers[1].chunk:
        raise ValueError(
            f"parity gate arms share chunk={checkers[0].chunk}; the gate "
            "needs two different geometries to mean anything"
        )
    sigs = []
    for checker in checkers:
        res = checker.run(max_depth=depth)
        sigs.append((res.depth_counts, res.total, res.terminal))
    ok = sigs[0] == sigs[1]
    return ParityGateResult(
        ok=ok,
        depth=depth,
        chunks=(checkers[0].chunk, checkers[1].chunk),
        counts=(sigs[0][0], sigs[1][0]),
    )
