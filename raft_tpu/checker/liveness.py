"""Liveness / temporal-property checking under ``WF_vars(Next)``.

The reference defines its liveness formulas against ``LivenessSpec ==
Init /\\ [][Next]_vars /\\ WF_vars(Next)`` (``Raft.tla:545-550``) in two
shapes:

  - ``[]<>P``  — "always eventually P" (``ValuesNotStuck``,
    ``Raft.tla:567-576``; ``ReconfigurationNotStuck``,
    ``KRaftWithReconfig.tla:1837-1839``);
  - ``P ~> Q`` — leads-to (``ReconfigurationCompletes``,
    ``RaftWithReconfigJointConsensus.tla:1039-1054``).

Semantics on a finite fully-explored state graph: a fair behavior under
weak fairness of the full Next is an infinite path (which must eventually
loop) or a behavior that reaches a TERMINAL state (no successors — Next
disabled forever, so stuttering there is fair; ``-deadlock`` semantics,
reference README.md:7). Therefore

  ``P ~> Q`` is violated  iff  some reachable state satisfies P and from
  it there is a Q-avoiding path that can avoid Q forever;
  ``[]<>P``  is the special case ``TRUE ~> P``.

"Can avoid Q forever" is the largest set S of ~Q-states such that every
member is terminal or has a successor in S — computed by iteratively
peeling ~Q-states with no exit (a nu-fixpoint; equivalent to "reaches a
~Q-cycle or ~Q-terminal within the ~Q-subgraph" but needs no SCC
machinery and is trivially iterative). The counterexample is a lasso:
Init-prefix to the P-state, a Q-free path into S, and the Q-free cycle
(or terminal stutter) it sustains.

SYMMETRY note: liveness checking over a symmetry-reduced graph is
unsound in general (TLC refuses the combination); the graph here is
always built with symmetry OFF, whatever the cfg declares.

Model contract: ``model.liveness`` maps property name ->
list of (instance_label, P_kernel_or_None, Q_kernel) — one instance per
quantified value (``\\A v \\in Value``), P = None meaning ``[]<>Q``.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.hashing import hash_lanes


@dataclass
class LivenessViolation:
    prop: str
    instance: str
    prefix: list[tuple[str, dict]]  # Init -> P-state (action label, state)
    cycle: list[tuple[str, dict]]  # the sustained Q-free loop (or terminal)
    terminal: bool  # True: lasso "cycle" is a terminal stutter


@dataclass
class LivenessResult:
    distinct: int
    total_edges: int
    properties: tuple[str, ...]
    violation: LivenessViolation | None
    seconds: float


class LivenessChecker:
    """Explores the FULL graph (host adjacency, symmetry off) and checks
    the model's registered temporal properties. Intended for the small
    bounded configs the reference runs liveness on (``MaxElections = 0``
    guidance, ``RaftWithReconfigAddRemove.tla:988``); the graph must fit
    on the host."""

    def __init__(self, model, properties: tuple[str, ...], chunk: int = 512,
                 max_states: int = 8_000_000):
        from .. import enable_compcache

        enable_compcache()
        self.model = model
        self.properties = tuple(properties)
        self.chunk = chunk
        self.max_states = max_states
        unknown = [p for p in self.properties
                   if p not in getattr(model, "liveness", {})]
        if unknown:
            raise ValueError(
                f"spec {model.name} has no liveness support for: "
                f"{', '.join(unknown)}"
            )
        # FULL-state fingerprints, not the VIEW projection: aux counters
        # gate actions (electionCtr < MaxElections etc.) and the temporal
        # predicates read them, so VIEW-merged nodes would conflate states
        # with different successor structure — unsound for liveness.
        #
        # Collision budget: graph dedup uses one 64-bit hash family, so a
        # fingerprint collision would silently merge two states and could
        # mask a temporal violation (expected collisions ~ n^2/2^65; at
        # the 8M-state default cap that is ~2e-6). Run run(audit_seed=k) to
        # re-explore under a second seeded family and cross-check
        # state/edge counts — a mismatch proves a collision in one family.
        self._fps = jax.jit(lambda v: hash_lanes(v))

    # ---------------- graph construction ----------------

    def _explore(self):
        """Full-graph build, vectorized end-to-end (round-4 verdict
        Next #7 — the per-unique-fingerprint python dict loop previously
        capped practical graphs well under the host's memory):

          - dedup = numpy searchsorted against a sorted (fp, gid) table,
          - device pass A per chunk returns only fingerprints + validity
            (u64/bool lanes — no [B, A, W] state transfer),
          - device pass B re-expands just the chunks that discovered new
            states and gathers exactly those successor vectors.
        """
        model = self.model
        B, W, A = self.chunk, self.model.layout.W, self.model.A
        fps_fn = self._fps
        if getattr(self, "_exp_fps_j", None) is None:
            def _exp_fps(batch):
                succs, valid, _rank, ovf = model.expand(batch)
                flat = succs.reshape(-1, W)
                return fps_fn(flat), valid.reshape(-1), jnp.any(valid & ovf)

            def _exp_sel(batch, lanes):
                succs, _v, _r, _o = model.expand(batch)
                return succs.reshape(-1, W)[lanes]

            self._exp_fps_j = jax.jit(_exp_fps)
            self._exp_sel_j = jax.jit(_exp_sel)

        init = np.asarray(model.init_states())
        fp0 = np.asarray(jax.device_get(fps_fn(init)), dtype=np.uint64)
        _uq, first = np.unique(fp0, return_index=True)
        first.sort()
        init_d = init[first]  # first-occurrence order = gid order
        n = len(init_d)
        state_blocks: list[np.ndarray] = [init_d]
        order0 = np.argsort(fp0[first], kind="stable")
        sorted_fps = fp0[first][order0]
        sorted_gids = order0.astype(np.int64)
        frontier = init_d
        frontier_gids = np.arange(n, dtype=np.int64)
        esrc_l: list[np.ndarray] = []
        edst_l: list[np.ndarray] = []
        ecand_l: list[np.ndarray] = []

        while len(frontier):
            # ---- pass A: fingerprints + validity only ----
            chunk_batches: list[np.ndarray] = []
            chunk_vidx: list[np.ndarray] = []
            wave_srcs: list[np.ndarray] = []
            wave_fps: list[np.ndarray] = []
            for off in range(0, len(frontier), B):
                batch = frontier[off : off + B]
                nb = len(batch)
                if nb < B:
                    batch = np.concatenate(
                        [batch, np.repeat(batch[-1:], B - nb, axis=0)]
                    )
                fps_c, valid_c, ovf_c = jax.device_get(
                    self._exp_fps_j(jnp.asarray(batch))
                )
                valid_c = np.asarray(valid_c).copy()
                valid_c[nb * A:] = False
                if bool(np.asarray(ovf_c)):
                    raise OverflowError(
                        "message-slot overflow during liveness graph build"
                    )
                vidx = np.nonzero(valid_c)[0]
                chunk_batches.append(batch)
                chunk_vidx.append(vidx)
                wave_srcs.append(frontier_gids[off + vidx // A])
                wave_fps.append(np.asarray(fps_c, dtype=np.uint64)[vidx])
            if not chunk_vidx:
                break
            srcs = np.concatenate(wave_srcs)
            cands = np.concatenate(
                [(v % A).astype(np.int32) for v in chunk_vidx]
            )
            fps_w = np.concatenate(wave_fps)
            if len(fps_w) == 0:
                break

            # ---- resolve against the global table ----
            pos = np.searchsorted(sorted_fps, fps_w)
            pos = np.clip(pos, 0, max(0, len(sorted_fps) - 1))
            hit = (
                (sorted_fps[pos] == fps_w)
                if len(sorted_fps) else np.zeros(len(fps_w), bool)
            )
            gid_w = np.where(hit, sorted_gids[pos], -1)
            nf_mask = ~hit
            new_states = np.zeros((0, W), np.int32)
            if nf_mask.any():
                nf = fps_w[nf_mask]
                uq, first_u = np.unique(nf, return_index=True)
                disc = np.argsort(first_u, kind="stable")  # discovery order
                new_count = len(uq)
                if n + new_count > self.max_states:
                    raise OverflowError(
                        "liveness graph exceeds max_states; raise it or "
                        "use a smaller config (liveness needs the full graph)"
                    )
                uq_gids = np.empty(new_count, np.int64)
                uq_gids[disc] = n + np.arange(new_count)
                gid_w[nf_mask] = uq_gids[np.searchsorted(uq, nf)]

                # ---- pass B: fetch exactly the new states' vectors.
                # lanes are padded to power-of-two buckets so jit compiles
                # a handful of shapes, not one per distinct new-count
                nf_wave_lane = np.nonzero(nf_mask)[0][first_u]  # per uq
                new_states = np.empty((new_count, W), np.int32)
                bounds = np.cumsum([0] + [len(v) for v in chunk_vidx])
                ci = np.searchsorted(bounds, nf_wave_lane, side="right") - 1
                for c in np.unique(ci):
                    sel = np.nonzero(ci == c)[0]  # uq indices in chunk c
                    lanes = chunk_vidx[c][nf_wave_lane[sel] - bounds[c]]
                    k = len(lanes)
                    bucket = 1 << max(5, (k - 1).bit_length())
                    lanes_p = np.zeros(bucket, lanes.dtype)
                    lanes_p[:k] = lanes
                    vecs = np.asarray(jax.device_get(
                        self._exp_sel_j(
                            jnp.asarray(chunk_batches[c]),
                            jnp.asarray(lanes_p),
                        )
                    ))[:k]
                    new_states[uq_gids[sel] - n] = vecs

                state_blocks.append(new_states)
                frontier_gids = n + np.arange(new_count, dtype=np.int64)
                n += new_count
                merged_fps = np.concatenate([sorted_fps, uq])
                merged_gids = np.concatenate([sorted_gids, uq_gids])
                order2 = np.argsort(merged_fps, kind="stable")
                sorted_fps = merged_fps[order2]
                sorted_gids = merged_gids[order2]
            esrc_l.append(srcs)
            edst_l.append(gid_w)
            ecand_l.append(cands)
            frontier = new_states

        self._states = np.concatenate(state_blocks, axis=0)
        self._esrc = np.concatenate(esrc_l) if esrc_l else np.zeros(0, np.int64)
        self._edst = np.concatenate(edst_l) if edst_l else np.zeros(0, np.int64)
        self._ecand = np.concatenate(ecand_l) if ecand_l else np.zeros(0, np.int32)
        self._n_init = len(init)

    def _eval_kernel(self, fn) -> np.ndarray:
        """Batched predicate over all graph states (padded power-of-two
        chunks so jit caches a handful of shapes)."""
        n = len(self._states)
        out = np.zeros(n, dtype=bool)
        B = 1 << min(14, max(8, (self.chunk - 1).bit_length()))
        for off in range(0, n, B):
            part = self._states[off : off + B]
            nb = len(part)
            if nb < B:
                part = np.concatenate([part, np.repeat(part[-1:], B - nb, axis=0)])
            out[off : off + nb] = np.asarray(jax.device_get(fn(part)))[:nb]
        return out

    # ---------------- the nu-fixpoint lasso search ----------------

    def _fwd_adj(self):
        """CSR forward adjacency (edge order, dst-by-src, row starts);
        built once per run and cached."""
        if getattr(self, "_fwd", None) is None:
            n = len(self._states)
            order = np.argsort(self._esrc, kind="stable")
            self._fwd = (
                order,
                self._edst[order],
                np.searchsorted(self._esrc[order], np.arange(n + 1)),
            )
        return self._fwd

    def _sustain_set(self, notq: np.ndarray) -> np.ndarray:
        """Largest S subset of ~Q with: member is terminal (no successors at
        all) or has a successor in S. Incremental peel (round-4 advisor:
        the full per-round recompute was O(rounds*E), quadratic on
        chain-shaped graphs): exit counts are bincounted once, then each
        round only the edges INTO that round's dropped nodes decrement
        their sources — every edge is touched at most once, so the whole
        peel is O(E + rounds*n)."""
        n = len(notq)
        esrc, edst = self._esrc, self._edst
        in_s = notq.copy()
        out_deg = np.bincount(esrc, minlength=n)
        terminal = out_deg == 0
        # reverse CSR (incoming edges by dst) for the incremental rounds
        rev = np.argsort(edst, kind="stable")
        rstart = np.searchsorted(edst[rev], np.arange(n + 1))
        live = in_s[edst] & in_s[esrc]
        exit_count = np.bincount(esrc[live], minlength=n)
        while True:
            drop = in_s & ~terminal & (exit_count == 0)
            dnodes = np.nonzero(drop)[0]
            if not dnodes.size:
                return in_s
            in_s &= ~drop
            # edges into dropped nodes whose src is still a member were
            # all counted (both endpoints were in S) and are dead now
            idx = (
                np.concatenate([rev[rstart[d] : rstart[d + 1]] for d in dnodes])
                if dnodes.size
                else np.empty(0, np.int64)
            )
            srcs = esrc[idx]
            srcs = srcs[in_s[srcs]]
            if srcs.size:
                exit_count -= np.bincount(srcs, minlength=n)

    def _shortest_path(self, from_set: np.ndarray, to_set: np.ndarray):
        """BFS (by gid) from any node in from_set to any node in to_set;
        returns (list of edge indices, target gid), or None."""
        n = len(self._states)
        order, ssorted_dst, sstart = self._fwd_adj()
        prev_edge = np.full(n, -1, np.int64)
        seen = from_set.copy()
        q = list(np.nonzero(seen)[0])
        if any(to_set[g] for g in q):
            g = next(g for g in q if to_set[g])
            return [], int(g)
        qi = 0
        while qi < len(q):
            s = q[qi]
            qi += 1
            for k in range(sstart[s], sstart[s + 1]):
                t = int(ssorted_dst[k])
                if seen[t]:
                    continue
                seen[t] = True
                prev_edge[t] = order[k]
                if to_set[t]:
                    path = []
                    cur = t
                    while prev_edge[cur] >= 0 and not from_set[cur]:
                        path.append(int(prev_edge[cur]))
                        cur = int(self._esrc[prev_edge[cur]])
                    path.reverse()
                    return path, t
                q.append(t)
        return None

    def _decode_path(self, start_gid: int, edge_idxs: list[int]):
        model = self.model
        out = []
        if getattr(self, "_expand1_jit", None) is None:
            self._expand1_jit = jax.jit(model._expand1)  # one cache per checker
        expand1 = self._expand1_jit
        for e in edge_idxs:
            # label via the recorded candidate; re-expand for the rank
            src = int(self._esrc[e])
            cand = int(self._ecand[e])
            succs, valid, rank, _ovf = jax.device_get(
                expand1(self._states[src])
            )
            assert valid[cand]
            out.append(
                (model.action_label(int(rank[cand]), cand),
                 model.decode(np.asarray(self._states[int(self._edst[e])])))
            )
        return out

    # ---------------- driver ----------------

    def run(self, verbose: bool = False,
            audit_seed: int | None = None) -> LivenessResult:
        t0 = time.perf_counter()
        self._explore()
        n = len(self._states)
        if audit_seed is not None:
            if audit_seed == 0:
                # seed 0 IS the primary family (hashing.py): a 0-seed
                # audit would vacuously compare a family against itself
                raise ValueError("audit_seed must be nonzero (seed 0 is "
                                 "the primary fingerprint family)")
            # Two-seed collision audit: rebuild the graph under an
            # independent hash family; a 64-bit collision in either
            # family (merging two distinct states) shifts the
            # state/edge counts with overwhelming probability.
            base = (n, len(self._esrc))
            saved = (self._fps, self._states, self._esrc, self._edst,
                     self._ecand, self._n_init, getattr(self, "_fwd", None),
                     getattr(self, "_exp_fps_j", None))
            self._fps = jax.jit(lambda v: hash_lanes(v, seed=audit_seed))
            self._fwd = self._exp_fps_j = None  # rebuild on the new family
            try:
                try:
                    self._explore()
                except OverflowError as e:
                    # a collision in the PRIMARY family merges states, so
                    # the audit family can see more true states and trip
                    # the cap — that is collision evidence, not a capacity
                    # problem
                    raise RuntimeError(
                        f"liveness collision audit (seed={audit_seed}) "
                        f"overflowed where the primary family did not — "
                        f"likely a fingerprint collision in the primary "
                        f"family merged distinct states ({e})"
                    ) from e
                other = (len(self._states), len(self._esrc))
            finally:
                (self._fps, self._states, self._esrc, self._edst,
                 self._ecand, self._n_init, self._fwd,
                 self._exp_fps_j) = saved
            if other != base:
                raise RuntimeError(
                    f"liveness graph collision audit FAILED: primary family "
                    f"saw {base[0]} states/{base[1]} edges, seed={audit_seed} "
                    f"family saw {other[0]}/{other[1]} — a fingerprint "
                    f"collision merged distinct states in one family"
                )
            if verbose:
                print(f"liveness collision audit (seed={audit_seed}): OK "
                      f"({n} states / {len(self._esrc)} edges both families)")
        if verbose:
            print(f"liveness graph: {n} states, {len(self._esrc)} edges")
        out_deg = np.bincount(self._esrc, minlength=n)
        violation = None
        for prop in self.properties:
            for label, p_fn, q_fn in self.model.liveness[prop]:
                q = self._eval_kernel(q_fn)
                p = (
                    np.ones(n, dtype=bool) if p_fn is None
                    else self._eval_kernel(p_fn)
                )
                sustain = self._sustain_set(~q)
                starts = p & sustain
                if not starts.any():
                    if verbose:
                        print(f"  {prop}[{label}]: OK")
                    continue
                # counterexample lasso
                init_set = np.zeros(n, dtype=bool)
                init_set[: self._n_init] = True
                pre = self._shortest_path(init_set, starts)
                assert pre is not None, "violating state must be reachable"
                pre_edges, s0 = pre
                # inside S: walk to a terminal or until a gid repeats;
                # the walk up to the loop entry is counterexample stem
                walk_edges: list[int] = []
                term = False
                order, ssorted_dst, sstart = self._fwd_adj()
                visited_at: dict[int, int] = {}
                cur = s0
                while True:
                    if out_deg[cur] == 0:
                        term = True
                        stem, loop = walk_edges, []
                        break
                    if cur in visited_at:
                        cut = visited_at[cur]
                        stem, loop = walk_edges[:cut], walk_edges[cut:]
                        break
                    visited_at[cur] = len(walk_edges)
                    nxt = None
                    for k in range(sstart[cur], sstart[cur + 1]):
                        t = int(ssorted_dst[k])
                        if sustain[t]:
                            nxt = (int(order[k]), t)
                            break
                    assert nxt is not None, "sustain set must have an exit"
                    walk_edges.append(nxt[0])
                    cur = nxt[1]
                init_gid = int(self._esrc[pre_edges[0]]) if pre_edges else s0
                prefix = [
                    ("Initial predicate",
                     self.model.decode(np.asarray(self._states[init_gid])))
                ] + self._decode_path(init_gid, pre_edges + stem)
                cycle = self._decode_path(s0, loop)
                violation = LivenessViolation(
                    prop=prop, instance=label, prefix=prefix, cycle=cycle,
                    terminal=term,
                )
                break
            if violation:
                break
        return LivenessResult(
            distinct=n,
            total_edges=len(self._esrc),
            properties=self.properties,
            violation=violation,
            seconds=time.perf_counter() - t0,
        )
