"""Does per-chunk cost scale with chunk size, or is it op-launch bound?

Times the PRODUCTION fused chunk program at several chunk sizes on the
same warmed raft3 frontier (pipelined 4-deep, device_get sync — the
timer that matches wave walls). If cost is sublinear in C, the cheapest
deep-run multiplier is simply a bigger chunk.

Usage: python scripts/chunk_scaling.py [sizes...]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

SIZES = [int(a) for a in sys.argv[1:]] or [1024, 4096, 16384]


def _sync(out):
    for leaf in jax.tree_util.tree_leaves(out):
        if hasattr(leaf, "ravel"):
            np.asarray(jax.device_get(leaf.ravel()[:1] if leaf.ndim else leaf))


def main():
    from raft_tpu.utils.cfg import parse_cfg
    from raft_tpu.models.registry import build_from_cfg
    from raft_tpu.checker.device_bfs import DeviceBFS

    cfg = parse_cfg("/root/reference/specifications/standard-raft/Raft.cfg")
    setup = build_from_cfg(cfg, msg_slots=32)

    # one warm run to get a real frontier (depth 14: 6608 states)
    import tempfile

    dev0 = DeviceBFS(setup.model, invariants=setup.invariants, symmetry=True,
                     chunk=1024, frontier_cap=1 << 17, seen_cap=1 << 21)
    with tempfile.TemporaryDirectory() as td:
        ck = os.path.join(td, "w.npz")
        dev0.run(max_depth=14, checkpoint_path=ck)
        d = np.load(ck, allow_pickle=False)
        frontier_h = np.asarray(d["frontier"])
        seen_h = np.asarray(d["seen"])
    print(f"warm frontier {len(frontier_h)}, seen {len(seen_h)}")

    for C in SIZES:
        dev = DeviceBFS(setup.model, invariants=setup.invariants,
                        symmetry=True, chunk=C,
                        frontier_cap=max(1 << 18, C), seen_cap=1 << 21)
        W = dev.W
        # round-5 seen design: one sorted U64_MAX-padded run
        dev._seed_seen(np.sort(seen_h.astype(np.uint64)))
        occ_dev = dev._occ_one
        runs = (dev._seen,)
        fh = np.zeros((dev.FCAP + 1, W), np.int32)
        n = min(len(frontier_h), dev.FCAP)
        fh[:n] = frontier_h[:n]
        frontier = jnp.asarray(fh)

        def once_args():
            nb = jnp.zeros((dev.FCAP + 1, W), jnp.int32)
            jp = jnp.zeros((dev.JCAP + 1,), jnp.int32)
            jc = jnp.zeros((dev.JCAP + 1,), jnp.int32)
            viol = jnp.full((max(1, len(dev.invariants)),),
                            np.int32(2**31 - 1), jnp.int32)
            stats = jnp.zeros((dev.N_STATS,), jnp.int64)
            memo = dev._memo.reset()
            cov = jnp.zeros((dev.n_actions, 3), jnp.int64)
            return [frontier, nb, jp, jc, viol, stats, memo, cov,
                    np.int32(0), np.int32(min(n, C)), np.int32(0), occ_dev,
                    jnp.asarray(True), *runs]

        t0 = time.perf_counter()
        _sync(dev._chunk_fn(*once_args()))
        compile_s = time.perf_counter() - t0
        ts = []
        for _ in range(5):
            argsets = [once_args() for _ in range(4)]
            t0 = time.perf_counter()
            out = None
            for a in argsets:
                out = dev._chunk_fn(*a)
            _sync(out)
            ts.append((time.perf_counter() - t0) / 4)
        med = sorted(ts)[len(ts) // 2]
        print(f"C={C:6d} VC={dev.VC:7d}: {med*1e3:8.1f} ms/chunk "
              f"({med*1e6/C:6.1f} us/state)  compile {compile_s:.1f}s")


if __name__ == "__main__":
    main()
