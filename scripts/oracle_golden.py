"""Per-depth counts of a cfg from the pure-Python oracle, as one JSON
object on stdout — the provenance of tests/golden/raft_cfg_depth_counts.json
(the engines under test never produce their own golden).

    JAX_PLATFORMS=cpu python scripts/oracle_golden.py \
        configs/standard-raft/Raft.cfg --max-depth 22

`--workers N` maps the oracle's own `successors` and `canon` over each
frontier in a process pool (at five servers `canon` is a brute-force min
over 120 permutations in Python, ~190 successors a second a core); dedup,
the invariants and every count stay in the parent, in the frontier's
order, so the output is the one-process run's byte for byte. The parent
holds a state as its pickle and a canonical key as its compressed
`repr`, which is the key letter for letter (tuples of ints, bools and
strings), so dedup stays exact: as Python objects a Raft.cfg key is
9.8 KB and a state 11.6 KB, 11 GB at the first million states of 8.66 M
(PR 57); packed they are 0.4 and 1.1-1.4 KB.
"""

from __future__ import annotations

import argparse
import json
import os
import pickle
import sys
import zlib

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_WORKER = None  # (oracle, symmetry) of a pool worker


def _setup_and_oracle(cfg):
    from raft_tpu.models.registry import build_from_cfg, oracle_for_setup
    from raft_tpu.utils.cfg import parse_cfg

    setup = build_from_cfg(parse_cfg(cfg))
    return setup, oracle_for_setup(setup)


def _worker_init(cfg):
    global _WORKER
    setup, oracle = _setup_and_oracle(cfg)
    _WORKER = (oracle, setup.symmetry)


def _packed_key(key) -> bytes:
    return zlib.compress(repr(key).encode(), 1)


def _expand(packed):
    """One frontier state's successors, each with its canonical key,
    states and keys packed."""
    oracle, symmetry = _WORKER
    return [(pickle.dumps(s2), _packed_key(oracle.canon(s2, symmetry)))
            for _label, s2 in oracle.successors(pickle.loads(packed))]


def pooled_bfs(cfg, setup, oracle, max_depth, workers):
    """`oracle.bfs(max_depth=...)` with successors and keys from a pool."""
    import multiprocessing as mp

    init = oracle.init_state()
    seen = {_packed_key(oracle.canon(init, setup.symmetry))}
    frontier, depth_counts = [pickle.dumps(init)], [1]
    total, terminal, violation, depth = 1, 0, None, 0
    with mp.get_context("spawn").Pool(workers, _worker_init, (cfg,)) as pool:
        while frontier and violation is None and depth < max_depth:
            next_frontier = []
            chunk = max(1, min(64, len(frontier) // (4 * workers)))
            for succs in pool.imap(_expand, frontier, chunksize=chunk):
                terminal += not succs
                for packed, key in succs:
                    total += 1
                    if key in seen:
                        continue
                    seen.add(key)
                    s2 = pickle.loads(packed)
                    for inv in setup.invariants:
                        if not oracle.INVARIANTS[inv](oracle, s2):
                            violation = {"invariant": inv, "state": s2, "depth": depth + 1}
                            break
                    next_frontier.append(packed)
                    if violation:
                        break
                if violation:
                    break
            frontier = next_frontier
            if frontier:
                depth_counts.append(len(frontier))
            depth += 1
            print(f"depth {depth}: {len(frontier)} new, {len(seen)} distinct, "
                  f"{total} generated, {terminal} terminal", file=sys.stderr, flush=True)
    return {
        "distinct": len(seen),
        "total": total,
        "depth_counts": depth_counts,
        "terminal": terminal,
        "violation": violation,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cfg")
    ap.add_argument("--max-depth", type=int, required=True)
    ap.add_argument("--workers", type=int, default=1,
                    help="processes that compute successors and canonical keys")
    args = ap.parse_args(argv)

    setup, oracle = _setup_and_oracle(args.cfg)
    if args.workers > 1:
        res = pooled_bfs(args.cfg, setup, oracle, args.max_depth, args.workers)
    else:
        res = oracle.bfs(
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
