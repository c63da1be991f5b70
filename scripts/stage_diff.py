"""Differential: every stage of a wave, the CPU backend against the chip.

Counts that differ on the chip only (PR 30's first contact of the
joint-consensus lowering: 276 new states at depth 4 for the oracle's 271,
and a NoLogDivergence no oracle finds) are a miscompile or a lowering
fault, and the whole-run counts do not say where. This script computes
each stage's outputs twice, on the same inputs, and names the first stage,
action and state field that differ:

  dense expand   ``model.expand`` of the oracle's full states to
                 ``--depth`` (at most ``--cap``): successors, valid, rank,
                 overflow
  guards         ``vmap(model.guards1)`` of the same rows
  sparse expand  ``DeviceBFS._st_expand``: guard pass, compaction (with
                 the compacted lanes' action ranks) and the budgeted
                 sparse apply, as the wave program runs them
  canon          raw and canonical fingerprints and the engines' canon
                 (in-chunk dedup, then the tiers), on the CPU's rows of
                 the sparse expand (so a fault upstream does not compound).
                 The engines' canon hands the dedup stage a fingerprint
                 on the first lane of each distinct raw view alone (PR 54:
                 a duplicate of a lower lane is never new, so it comes
                 back masked like an invalid lane and nothing is computed
                 for it), so it is held to the plain per-lane entry on
                 those lanes and to U64_MAX on every other, on each side
                 (``canon_dedup_off_rule``: the lanes that break that)
  invariants     each invariant kernel of the cfg, on the same rows
  wave           the fused wave program on the last level as its frontier,
                 the earlier levels in the seen run: stats, violations,
                 emitted rows, journal, coverage, the wave's buffer of
                 new fingerprints: lane for lane, since which lane is new,
                 and what it carries, is what the masking must not move

One command does both sides: before this process imports JAX it starts
itself as a child held to the CPU backend (``JAX_PLATFORMS=cpu``) that
saves the stages' outputs, then recomputes them on the backend JAX picks
(a chip holds one process at a time; the child has exited by then) and
compares element by element. Exit code 0 when every stage is equal, 1
when any differs, 3 without an accelerator (``--platform cpu`` rehearses
the script: both sides are the CPU then, and nothing is learned).

``--scatter`` puts the writes back that PR 30 took out:
``models/base.py``'s ``onehot_set``, ``onehot_set2`` and ``onehot_add``
become ``arr.at[i].set(val)``, ``arr.at[i, j].set(val)`` and
``arr.at[i].add(val)`` in every lowering that uses them, on both sides
(``--scatter set2,add`` swaps only those: which form loses its writes).
On a v5e, with ``configs/standard-raft/RaftWithReconfigJointConsensus.cfg``,
that is the reproducer of the dropped writes: the dense expand equal, the
sparse expand's rows wrong in HandleMessage's log lanes (PERF.md section
6, PR 30). Without it the same command reads ALL STAGES EQUAL.

``--gather`` does the same for the reads PR 31 took out: ``onehot_row``
and ``onehot_get2`` become ``arr[i]`` and ``arr[i, j]`` (``--gather
get2`` swaps only that one), on both sides: the old reads against the
CPU, and with ``scripts/stage_split.py --gather`` timed, from one tree.

    python scripts/stage_diff.py configs/standard-raft/RaftWithReconfigJointConsensus.cfg
        [--msg-slots N] [--chunk 1024] [--depth 3] [--cap 400]
        [--scatter [set,set2,add]] [--gather [row,get2]] [--platform cpu]
        [--out chiprun_out/stage_diff]
"""

import argparse
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
NO_CHIP = 3


SCATTER_FORMS = {
    "set": ("onehot_set", lambda arr, i, val: arr.at[i].set(val)),
    "set2": ("onehot_set2", lambda arr, i, j, val: arr.at[i, j].set(val)),
    "add": ("onehot_add", lambda arr, i, val: arr.at[i].add(val)),
}


GATHER_FORMS = {
    "row": ("onehot_row", lambda arr, i: arr[i]),
    "get2": ("onehot_get2", lambda arr, i, j: arr[i, j]),
}


def _put_back(forms, which):
    import raft_tpu.models.registry  # noqa: F401  (loads every lowering)

    for name, mod in list(sys.modules.items()):
        if name.startswith("raft_tpu.models."):
            for key in which.split(","):
                helper, form = forms[key]
                if hasattr(mod, helper):
                    setattr(mod, helper, form)


def scatter_writes(which="set,set2,add"):
    """Swap the named one-hot write helpers for the scatters they
    replaced, in ``models/base.py`` and in every module that imported
    them."""
    _put_back(SCATTER_FORMS, which)


def gather_reads(which="row,get2"):
    """The same for the one-hot read helpers and the gathers they
    replaced (every lowering reads by them, ``raft.py`` in all its
    kernels since PR 44)."""
    _put_back(GATHER_FORMS, which)


def levels_of(oracle, depth, cap):
    """The oracle's full states by BFS level (no symmetry, no view),
    cut after the level that passes ``cap`` states."""
    first = oracle.init_state()
    seen = {oracle.serialize_full(first)}
    levels = [[first]]
    for _ in range(depth):
        nxt = []
        for st in levels[-1]:
            for _label, s2 in oracle.successors(st):
                key = oracle.serialize_full(s2)
                if key not in seen:
                    seen.add(key)
                    nxt.append(s2)
        if not nxt:
            break
        levels.append(nxt)
        if len(seen) >= cap:
            break
    return levels


def stages(args, ref):
    """{name: array} of every stage's outputs on this process's backend;
    ``ref`` (the CPU side's, or None on the CPU side) supplies the rows
    the stages after the sparse expand run on."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_tpu.checker.device_bfs import I32_MAX, U64_MAX, DeviceBFS
    from raft_tpu.models.registry import build_from_cfg, oracle_for_setup
    from raft_tpu.utils.cfg import parse_cfg

    if args.scatter:
        scatter_writes(args.scatter)
    if args.gather:
        gather_reads(args.gather)
    setup = build_from_cfg(parse_cfg(args.cfg), msg_slots=args.msg_slots)
    model = setup.model
    levels = levels_of(oracle_for_setup(setup), args.depth, args.cap)
    rows = np.stack([model.encode(s) for lv in levels for s in lv])
    rows = rows.astype(np.int32)[: args.chunk]
    n = len(rows)
    print(f"platform {jax.devices()[0].platform}: levels "
          f"{[len(lv) for lv in levels]}, {n} rows of W = {model.layout.W}, "
          f"A = {model.A}", flush=True)
    eng = DeviceBFS(model, invariants=setup.invariants,
                    symmetry=setup.symmetry, chunk=args.chunk,
                    frontier_cap=1 << 12)
    out = {}

    succs, valid, rank, ovf = jax.device_get(model.expand(rows))
    out.update(dense_succs=np.where(valid[..., None], succs, 0),
               dense_valid=valid, dense_rank=np.where(valid, rank, -1),
               dense_ovf=valid & ovf)
    gv, gr, go = jax.device_get(jax.jit(jax.vmap(model.guards1))(rows))
    out.update(guards_valid=gv, guards_rank=np.where(gv, gr, -1),
               guards_ovf=gv & go)

    def padded(block):
        buf = np.zeros((eng.FCAP + eng.VC, eng.W), np.int32)
        buf[: len(block)] = block
        return buf

    flatc, sel, selv, sel_rank, _v, _r, n_gen, _t, eo, co, _b = jax.device_get(
        jax.jit(eng._st_expand)(padded(rows), np.int32(0), np.int32(n)))
    out.update(sparse_rows=flatc, sparse_sel=sel, sparse_selv=selv,
               sparse_sel_rank=sel_rank,
               sparse_ngen=np.asarray(n_gen), sparse_ovf=np.asarray([eo, co]))
    if ref is not None:
        flatc, selv = ref["sparse_rows"], ref["sparse_selv"]

    canon = eng.canon
    out["canon_raw"] = np.asarray(canon.raw_fingerprints(flatc))
    out["canon_fp"] = np.where(selv, np.asarray(canon.fingerprints(flatc)), 0)
    fps, n_dup, tiers = jax.jit(canon.fingerprints_dedup)(flatc, selv)
    lanes = np.flatnonzero(selv)
    _u, first = np.unique(out["canon_raw"][lanes], return_index=True)
    head = np.zeros(len(selv), bool)
    head[lanes[first]] = True  # the first valid lane of each raw view
    rule = np.where(head, out["canon_fp"], np.uint64(U64_MAX))
    out.update(canon_fp_dedup=np.asarray(fps),
               canon_dedup_off_rule=np.asarray(fps) != rule,
               canon_tiers=np.asarray([n_dup, *tiers]))
    for name in setup.invariants:
        holds = np.asarray(jax.jit(model.invariants[name])(flatc))
        out["invariant_" + name] = holds | ~selv

    frontier = np.stack([model.encode(s) for s in levels[-1]])
    frontier = frontier.astype(np.int32)[: eng.FCAP]
    every = np.stack([model.encode(s) for lv in levels for s in lv])
    seen_fp = np.unique(np.asarray(
        canon.fingerprints(every.astype(np.int32)), dtype=np.uint64))
    seen = np.full((eng._seen_sizes[0],), np.uint64(U64_MAX), np.uint64)
    seen[: len(seen_fp)] = seen_fp
    res = eng._wave_fn(
        jnp.asarray(padded(frontier)),
        jnp.zeros((eng.FCAP + eng.VC, eng.W), jnp.int32),
        jnp.zeros((eng.JCAP + eng.VC,), jnp.int32),
        jnp.zeros((eng.JCAP + eng.VC,), jnp.int32),
        jnp.full((max(1, len(eng.invariants)),), I32_MAX, jnp.int32),
        jnp.zeros((eng.N_STATS,), jnp.int64),
        jnp.zeros((eng.n_actions, 3), jnp.int64),
        np.int32(len(frontier)), np.int32(0), np.int32(len(seen_fp)),
        eng._occ_one, jnp.asarray(seen))
    nxt, jparent, jcand, viol, stats, cov, wave_new = jax.device_get(res)
    print(f"wave on {len(frontier)} rows: stats {stats.tolist()} "
          f"violations {viol.tolist()}", flush=True)
    keep = 4 * args.chunk
    out.update(wave_stats=np.asarray(stats), wave_viol=np.asarray(viol),
               wave_rows=np.asarray(nxt[:keep]), wave_cov=np.asarray(cov),
               wave_jcand=np.asarray(jcand[:keep]),
               wave_jparent=np.asarray(jparent[:keep]),
               wave_new=np.asarray(wave_new[:keep]))
    return out, model


def report(out, ref, model):
    """Prints, stage by stage, how many elements differ, and for the two
    expands which action and which state field; True if any did."""
    import numpy as np

    fields = sorted((f.offset, f.name, f.size)
                    for f in model.layout.fields.values())

    def field_of(lane):
        for off, name, size in fields:
            if off <= lane < off + size:
                return name
        return f"lane{lane}"

    def by_action_and_field(diff, cand_of, got, want):
        found = {}
        for idx in np.argwhere(diff):
            idx = tuple(int(x) for x in idx)
            key = (model.bindings[cand_of(idx)][0], field_of(idx[-1]))
            found.setdefault(key, []).append(idx)
        for key, at in sorted(found.items(), key=lambda kv: -len(kv[1]))[:40]:
            i = at[0]
            print(f"     {key[0]} / {key[1]}: {len(at)} lanes in "
                  f"{len({x[:-1] for x in at})} rows, e.g. at {i} got "
                  f"{int(got[i])} for the CPU's {int(want[i])}")

    bad = False
    for name in out:
        got, want = out[name], ref[name]
        if got.shape != want.shape:
            print(f"{name:<32} shape {got.shape} != {want.shape}")
            bad = True
            continue
        diff = got != want
        if name == "canon_dedup_off_rule":  # a lane off it on either side
            diff = got | want
        print(f"{name:<32} {int(diff.sum())} of {diff.size} differ")
        if not diff.any():
            continue
        bad = True
        if name == "dense_succs":
            by_action_and_field(diff, lambda i: i[1], got, want)
        elif name == "sparse_rows":
            sel = ref["sparse_sel"]
            by_action_and_field(
                diff, lambda i: int(sel[i[0]]) % model.A, got, want)
        else:
            for idx in np.argwhere(diff)[:8]:
                idx = tuple(int(x) for x in idx)
                print(f"     at {idx} got {got[idx]} for the CPU's "
                      f"{want[idx]}")
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cfg")
    ap.add_argument("--msg-slots", type=int, default=None)
    ap.add_argument("--chunk", type=int, default=1024)
    ap.add_argument("--depth", type=int, default=3)
    ap.add_argument("--cap", type=int, default=400)
    ap.add_argument("--scatter", nargs="?", const="set,set2,add",
                    default=None, metavar="HELPERS",
                    help="set, set2, add or a comma list; all three "
                         "when given bare")
    ap.add_argument("--gather", nargs="?", const="row,get2", default=None,
                    metavar="HELPERS",
                    help="row, get2 or a comma list; both when given bare")
    ap.add_argument("--platform", choices=("cpu",), default=None)
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "stage_diff"))
    ap.add_argument("--dump", default=None, help=argparse.SUPPRESS)
    argv = sys.argv[1:] if argv is None else list(argv)
    args = ap.parse_args(argv)

    if args.dump:  # the child: the CPU side
        import numpy as np

        out, _model = stages(args, None)
        np.savez_compressed(args.dump, **out)
        return 0

    os.makedirs(args.out, exist_ok=True)
    ref_path = os.path.join(args.out, "cpu_side.npz")
    subprocess.run(
        [sys.executable, os.path.abspath(__file__), *argv,
         "--dump", ref_path],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, check=True)
    if args.platform:
        os.environ["JAX_PLATFORMS"] = args.platform

    import jax
    import numpy as np

    if jax.devices()[0].platform == "cpu" and not args.platform:
        print("stage_diff: JAX found no accelerator; --platform cpu "
              "rehearses", file=sys.stderr)
        return NO_CHIP
    with np.load(ref_path) as f:
        ref = dict(f)
    out, model = stages(args, ref)
    bad = report(out, ref, model)
    print("A STAGE DIFFERS" if bad else "ALL STAGES EQUAL")
    return int(bad)


if __name__ == "__main__":
    sys.exit(main())
