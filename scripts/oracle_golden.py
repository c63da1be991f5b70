"""Per-depth counts of a cfg from the pure-Python oracle, as one JSON
object on stdout — the provenance of tests/golden/raft_cfg_depth_counts.json
(the engines under test never produce their own golden).

    JAX_PLATFORMS=cpu python scripts/oracle_golden.py \
        configs/standard-raft/Raft.cfg --max-depth 22
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cfg")
    ap.add_argument("--max-depth", type=int, required=True)
    args = ap.parse_args(argv)

    from raft_tpu.models.registry import build_from_cfg, oracle_for_setup
    from raft_tpu.utils.cfg import parse_cfg

    setup = build_from_cfg(parse_cfg(args.cfg))
    res = oracle_for_setup(setup).bfs(
        invariants=setup.invariants,
        symmetry=setup.symmetry,
        max_depth=args.max_depth,
    )
    print(json.dumps({
        "cfg": args.cfg,
        "max_depth": args.max_depth,
        "depth_counts": res["depth_counts"],
        "distinct": res["distinct"],
        "total": res["total"],
        "terminal": res["terminal"],
        "violation": res["violation"] and res["violation"]["invariant"],
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
