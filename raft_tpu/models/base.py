"""State-vector layout machinery shared by all spec lowerings.

Every spec variant lowers its TLA+ variables to a single flat ``int32[W]``
vector per state. The layout records, per field, the *kind* of the field —
how it transforms under a permutation of the server set — which lets the
generic symmetry canonicalizer (ops/symmetry.py) serve every variant.

Field ordering convention: all VIEW fields first, aux (VIEW-excluded)
fields last, so the VIEW projection (``Raft.tla:115`` excludes
``acked/electionCtr/restartCtr``) is the contiguous prefix
``vec[:layout.view_len]``.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass

import jax.numpy as jnp

import numpy as np

# Field kinds and their transformation under a server permutation sigma
# (sigma maps old server index -> new server index):
#   scalar           unaffected
#   per_server       shape (S, ...): row r moves to row sigma(r)
#   per_server_val   shape (S,), values in 0..S with 0 = Nil: rows move AND
#                    values remap v -> sigma(v-1)+1
#   server_bitmask   shape (S,), each element a bitmask over servers: rows
#                    move AND bit j moves to bit sigma(j)
#   per_server_pair  shape (S, S): new[sigma(a), sigma(b)] = old[a, b]
#   msg_hi/msg_lo/   shape (M,): the message bag; server-valued fields inside
#   msg_cnt          the packed key remap, then slots re-sort
#   msg_word         shape (M,): one word of an N-word bag key (WidePacker);
#                    declared in word order, word 0 first (sort-major)
#   aux              VIEW-excluded scalar/vector (must come last)
KINDS = (
    "scalar",
    "per_server",
    "per_server_val",
    "server_bitmask",
    "per_server_pair",
    "msg_hi",
    "msg_lo",
    "msg_cnt",
    "msg_word",
    "aux",
)


@dataclass(frozen=True)
class Field:
    name: str
    kind: str
    shape: tuple[int, ...]
    offset: int

    @property
    def size(self) -> int:
        return int(math.prod(self.shape)) if self.shape else 1


class Layout:
    def __init__(self, n_servers: int):
        self.n_servers = n_servers
        self.fields: dict[str, Field] = {}
        self.W = 0
        self.view_len: int | None = None  # set when the first aux field lands

    def add(self, name: str, kind: str, shape: tuple[int, ...] = ()) -> Field:
        if kind not in KINDS:
            raise ValueError(f"unknown kind {kind}")
        if name in self.fields:
            raise ValueError(f"duplicate field {name}")
        if kind == "aux":
            if self.view_len is None:
                self.view_len = self.W
        elif self.view_len is not None:
            raise ValueError("non-aux field added after aux fields")
        f = Field(name, kind, shape, self.W)
        self.fields[name] = f
        self.W += f.size
        return f

    def finish(self):
        if self.view_len is None:
            self.view_len = self.W
        return self

    def sl(self, name: str) -> slice:
        f = self.fields[name]
        return slice(f.offset, f.offset + f.size)

    def get(self, vec, name: str):
        """Slice field `name` out of a [..., W] vector, reshaped to its shape."""
        f = self.fields[name]
        out = vec[..., f.offset : f.offset + f.size]
        if f.shape:
            return out.reshape(vec.shape[:-1] + f.shape)
        return out[..., 0]

    def zeros(self, batch: tuple[int, ...] = ()) -> np.ndarray:
        return np.zeros(batch + (self.W,), dtype=np.int32)


class ActionLabelMixin:
    """Human-readable labels for expansion candidates, shared by every
    spec lowering.

    Subclass contract: ``self.bindings`` (the candidate table of
    ``(kernel_name, binding_tuple)`` pairs) and ``self.ACTION_NAMES``
    (the Next-disjunct rank -> action-name table; index == the rank
    that ``_expand1`` reports). Fused ``HandleMessage`` kernels resolve
    their disjunct at run time, so the label comes from the fired rank;
    every other kernel is named by its binding.

    ``CRASH_ACTIONS`` names the actions of ``ACTION_NAMES`` in which a
    server crashes and restarts (a run's ``restart_fired`` sums their
    ``fired`` counts): the family's specs call it ``Restart``."""

    ACTION_NAMES: list[str]
    CRASH_ACTIONS: tuple[str, ...] = ("Restart",)

    def action_label(self, rank: int, cand: int) -> str:
        name, binding = self.bindings[cand]
        if name == "HandleMessage":
            return f"{self.ACTION_NAMES[rank]}(slot {binding[0]})"
        return f"{name}{binding}"


FLEET_JOB = "fleet_job"


class FleetConstMixin:
    """Fleet packing: a config axis embedded in the state vector.

    A fleet-packed model carries two kinds of extra VIEW scalar fields
    (added by the lowering's ``_build_layout`` when ``params.fleet``):

      fleet_job   which manifest job a state belongs to. Because it is a
                  VIEW field, fingerprints of different jobs never
                  collide, so many jobs share one frontier / seen-set /
                  journal without any cross-job dedup.
      c_<name>    one lane per *dynamic* constant in ``params.dyn_consts``
                  (e.g. ``c_max_restarts``). Guards read the lane via
                  ``_cv`` instead of the static param, so one compiled
                  program serves every CONSTANTS point in the group.

    The lanes are inserted after the message-bag fields and before the
    first aux field — scalar kind, so the symmetry canonicalizer leaves
    them alone (PullRaft's ``acked``-after-``msg_cnt`` field pins that
    this position is legal).

    Subclass contract: every lowering's ``init_states`` ends with
    ``return self._fleet_stamp(vec)`` (identity when no fleet table is
    bound), and every guard that reads a dynamic constant goes through
    ``self._cv(d, name)`` / ``self._cv_batch(states, name)``.
    """

    def fleet_bind(self, table) -> None:
        """Bind the per-job dynamic-constant table.

        ``table`` is [J, len(params.dyn_consts)] ints: row j holds job
        j's value for each dynamic constant, in ``dyn_consts`` order.
        The static params must be the element-wise max over the table
        (capacity sizing — e.g. ``max_term`` — is derived from them)."""
        table = np.asarray(table, np.int64)
        dyn = tuple(self.p.dyn_consts)
        if table.ndim != 2 or table.shape[1] != len(dyn):
            raise ValueError(
                f"fleet table must be [J, {len(dyn)}] for dyn_consts {dyn}"
            )
        for k, name in enumerate(dyn):
            cap = int(getattr(self.p, name))
            hi = int(table[:, k].max()) if len(table) else 0
            if hi > cap:
                raise ValueError(
                    f"fleet table {name} max {hi} exceeds static param {cap}"
                    " (representative params must be the per-constant max)"
                )
        self._fleet_table = table
        self._fleet_sel: int | None = None

    @property
    def fleet_jobs(self) -> int:
        t = getattr(self, "_fleet_table", None)
        return 0 if t is None else len(t)

    def fleet_select(self, j: int | None) -> None:
        """Restrict ``init_states`` stamping to job ``j`` (None = all
        jobs). The queue arm runs jobs one at a time through the SAME
        compiled program by re-selecting between runs."""
        if getattr(self, "_fleet_table", None) is None:
            raise ValueError("fleet_select before fleet_bind")
        self._fleet_sel = j

    def fleet_job_of(self, states) -> np.ndarray:
        """[n] job index of each row of a [n, W] state batch."""
        off = self.layout.fields[FLEET_JOB].offset
        return np.asarray(states)[..., off]

    def _fleet_stamp(self, vec: np.ndarray) -> np.ndarray:
        """Stamp init states with the job lane and constant lanes, one
        copy per selected job, job-major. Identity when unbound, so
        serial (non-fleet) models are untouched."""
        table = getattr(self, "_fleet_table", None)
        if table is None:
            return vec
        lay = self.layout
        sel = getattr(self, "_fleet_sel", None)
        jobs = range(len(table)) if sel is None else [sel]
        out = []
        for j in jobs:
            v = vec.copy()
            v[:, lay.fields[FLEET_JOB].offset] = j
            for k, name in enumerate(self.p.dyn_consts):
                v[:, lay.fields["c_" + name].offset] = int(table[j, k])
            out.append(v)
        return np.concatenate(out, axis=0)

    def _cv(self, d: dict, name: str):
        """A constant's value inside a per-state kernel: the state lane
        when fleet-packed, the static param otherwise (bit-identical to
        the pre-fleet guards in the serial case)."""
        key = "c_" + name
        if key in self.layout.fields:
            return d[key]
        return getattr(self.p, name)

    def _cv_batch(self, states, name: str):
        """Batched form of ``_cv`` for invariant/liveness kernels that
        work on [..., W] state batches rather than decoded dicts."""
        key = "c_" + name
        if key in self.layout.fields:
            return self.layout.get(states, key)
        return getattr(self.p, name)


def apply_tile(rows: int, chunk: int) -> int:
    """The tile of ``SparseExpandMixin.sparse_apply``'s loops: how many
    of a block's ``rows`` (a group's budget, or the worklist's VC) one
    trip builds, a function of the static shapes alone: a sixteenth of
    the block, and a quarter of a chunk's lanes at least (a block under
    that is one tile). ``HandleMessage``'s VC = 16 x chunk rows and the
    worklist's go a chunk a trip, a group of one candidate a state a
    quarter of a chunk; a budget of no rows keeps a tile of one, which
    never runs.

    Measured (``scripts/expand_micro.py --fill``, TPU v5 lite, PR 56):
    ms a call of one chunk's apply pass at 1/16, 1/8, 1/4, 1/2 and all
    of VC kept, on ``raft3-wide`` (chunk 4,096, VC 65,536, 151,552
    budgeted rows) 0.89 / 1.23 / 1.72 / 2.92 / 5.36 under this rule,
    against 1.24 / 1.60 / 2.02 / 3.23 / 5.59 with tiles of a chunk for
    every block, 1.62 / 1.61 / 2.27 / 3.16 / 5.51 with an eighth of the
    block and a chunk at least, 2.36 / 2.34 / 2.30 / 3.57 / 5.27 with a
    quarter, and 7.94 / 7.93 / 7.83 / 7.72 / 7.48 for the one-shot pass
    at the budgets; on ``pull3-full`` (2,048; 32,768; 69,632) 0.50 /
    0.70 / 0.95 / 1.58 / 2.72 against 0.68 / 0.87 / 1.09 / 1.73 / 2.79,
    0.84 / 0.85 / 1.16 / 1.63 / 2.72, 1.23 / 1.21 / 1.19 / 1.81 / 2.68
    and 4.29 / 4.28 / 4.24 / 4.18 / 4.07. A built row costs 64 to 75 ns
    from the coarsest rule to this one at a full worklist, so a trip's
    own cost is small beside a tile's rows; the cells keep 17 to 55 %
    of VC a chunk-step, where the smaller tile wins. The last gather
    alone: 0.06 / 0.11 / 0.19 / 0.36 / 0.74 ms in tiles of a chunk
    against 0.82 to 0.84 whole (``raft3-wide``)."""
    return max(1, rows // 16, min(rows, chunk // 4))


def tiled_rows(block, row, n, tile: int):
    """``block[row]`` for the first ``n`` lanes of ``row`` and zeros rows
    past them, gathered ``tile`` lanes a trip under ``ceil(n / tile)``
    trips: a row gather by a traced index costs its lanes, whatever they
    point at."""
    from jax import lax

    lanes = row.shape[0]
    room = -(-lanes // tile) * tile  # whole tiles: no slice clamps
    rowp = jnp.concatenate([row, jnp.zeros((room - lanes,), row.dtype)])

    def trip(t, out):
        at = t * tile
        return lax.dynamic_update_slice(
            out, block[lax.dynamic_slice(rowp, (at,), (tile,))],
            (at, jnp.int32(0)))

    out = lax.fori_loop(
        jnp.int32(0), (n + (tile - 1)) // tile, trip,
        jnp.zeros((room, block.shape[1]), block.dtype))
    return out[:lanes]


@dataclass(frozen=True)
class SparseGroup:
    """One contiguous run of same-named bindings in ``self.bindings``:
    the unit of the guard-first sparse expansion. ``params`` is the
    static [n, arity] int32 binding table the apply pass gathers its
    kernel arguments from."""

    name: str
    off: int  # first candidate index of the group
    n: int  # candidates in the group
    params: np.ndarray  # [n, arity] int32


class SparseExpandMixin:
    """Guard-first sparse expansion, shared by every spec lowering.

    ``_expand1`` materializes a full-width successor row for every one
    of the A candidate bindings — even though coverage shows most are
    guard-disabled on every wave. This mixin splits that contract in
    two without touching (or trusting) any kernel code:

      guards1     valid/rank/ovf over all A candidates of one state,
                  derived from ``_expand1``'s own jaxpr by dead-code-
                  eliminating the succs output. Bit-identical to the
                  dense pass by construction (DCE removes equations, it
                  never rewrites values), and cheap: every W-wide
                  successor assembly and bag sort-insert is dead once
                  succs is unused (ops/bag.py computes existed/overflow
                  BEFORE the sort-insert for exactly this reason).
      apply1      full (valid, succ, rank, ovf) of ONE (state, cand)
                  pair: a lax.switch over the binding groups. With a
                  scalar cand only the selected branch executes.
      sparse_apply  the engine-facing batched apply: successor rows for
                  a compacted [VC] worklist of enabled candidates,
                  built per GROUP in fixed-budget blocks so every wave
                  stays on one precompiled signature. Per-lane switch
                  would execute ALL branches under vmap (costing more
                  than the dense pass it replaces); the worklist is
                  segmented by group by ONE sort of one int32 key
                  (group, flat lane), so each kernel runs only on its
                  own lanes and no compaction is a scatter.

    Subclass contract: ``self.bindings`` (same-named candidates
    contiguous, as every lowering already builds them), kernels named
    ``_snake_case`` of the binding name, overridable per model via
    ``_kernel_overrides`` for the lowerings whose method names predate
    the convention.
    """

    def _kernel_overrides(self) -> dict:
        """binding name -> bound kernel, for names that do not follow
        the ``_snake_case`` derivation."""
        return {}

    def kernel_for(self, name: str):
        """The per-action kernel ``(s, *binding) -> (valid, succ, rank,
        ovf)`` registered for binding name ``name``."""
        ov = self._kernel_overrides()
        if name in ov:
            return ov[name]
        attr = "_" + re.sub(r"(?<!^)(?=[A-Z])", "_", name).lower()
        kern = getattr(self, attr, None)
        if kern is None:
            raise AttributeError(
                f"{type(self).__name__} has no kernel {attr} for binding "
                f"{name!r} (declare it in _kernel_overrides)"
            )
        return kern

    def sparse_groups(self) -> list[SparseGroup]:
        """Contiguous same-named runs of ``self.bindings`` with their
        static parameter tables (cached; bindings are frozen after
        __init__)."""
        cached = self.__dict__.get("_sparse_groups")
        if cached is not None:
            return cached
        b = self.bindings
        groups: list[SparseGroup] = []
        i = 0
        while i < len(b):
            name = b[i][0]
            j = i
            while j < len(b) and b[j][0] == name:
                j += 1
            params = np.asarray(
                [list(t[1]) for t in b[i:j]], np.int32
            ).reshape(j - i, -1)
            groups.append(SparseGroup(name, i, j - i, params))
            i = j
        names = [g.name for g in groups]
        if len(set(names)) != len(names):
            raise ValueError(f"non-contiguous binding groups: {names}")
        self.__dict__["_sparse_groups"] = groups
        return groups

    # ---------------- guard pass ----------------

    @property
    def guards1(self):
        """``(s [W]) -> (valid [A], rank [A], ovf [A])`` — the dense
        guard grid of one state, with every successor write DCE'd out
        of ``_expand1``'s jaxpr (lazy-built, cached)."""
        fn = self.__dict__.get("_guards1_fn")
        if fn is None:
            fn = self._build_guards1()
            self.__dict__["_guards1_fn"] = fn
        return fn

    def _build_guards1(self):
        import jax
        from jax.interpreters import partial_eval as pe

        closed = jax.make_jaxpr(self._expand1)(
            jax.ShapeDtypeStruct((self.layout.W,), jnp.int32)
        )
        # _expand1 returns (succs, valid, rank, ovf): drop succs, keep
        # the three guard outputs
        dced, used_consts, (state_used,) = pe.dce_jaxpr_consts(
            closed.jaxpr, [False, True, True, True]
        )
        kept = [c for c, u in zip(closed.consts, used_consts) if u]

        def guards1(s):
            valid, rank, ovf = jax.core.eval_jaxpr(
                dced, kept, *([s] if state_used else [])
            )
            return valid, rank, ovf

        guards1.jaxpr = dced  # the no-W-wide-writes pin inspects this
        return guards1

    # ---------------- apply pass ----------------

    def apply1(self, s, cand):
        """Full (valid, succ [W], rank, ovf) of ONE (state, candidate)
        pair — trace reconstruction / parity checks; ``cand`` must be a
        scalar so lax.switch executes a single branch."""
        from jax import lax

        groups = self.sparse_groups()
        group_of = np.zeros((self.A,), np.int32)
        for gi, g in enumerate(groups):
            group_of[g.off : g.off + g.n] = gi
        cand = jnp.asarray(cand, jnp.int32)

        def branch(g):
            tbl = jnp.asarray(g.params)
            kern = self.kernel_for(g.name)

            def run(s, cand):
                k = jnp.clip(cand - g.off, 0, g.n - 1)
                args = [tbl[:, c][k] for c in range(tbl.shape[1])]
                return kern(s, *args)

            return run

        return lax.switch(
            jnp.asarray(group_of)[cand], [branch(g) for g in groups], s, cand
        )

    def sparse_plan(
        self,
        chunk: int,
        worklist: int,
        valid_per_group: float | dict | None = None,
    ) -> tuple[int, ...]:
        """Static per-group apply budgets EB_g for a [chunk]-state wave
        chunk whose enabled worklist is [worklist] lanes long.

        ``valid_per_group`` caps the enabled candidates a group may
        contribute per chunk, in per-state units (CHUNK-AGGREGATE:
        EB_g = chunk * cap — a few dense states inside an average
        chunk don't overflow it). A dict maps group name -> cap for
        per-group tuning (groups absent from the dict stay loose);
        fractions are legal (0.25 = one enabled candidate per four
        states). None keeps the loose ``min(chunk * n_g, worklist)``
        bound, under which budget overflow is impossible (a group can
        never hold more enabled worklist lanes than that) but wide
        groups (the message bag) still pay for every slot. The
        per-wave ``enabled_density`` gauge and the coverage table's
        enabled column are the tuning inputs."""
        self.segment_stride(chunk)  # the apply's sort key fits, or raise
        plan = []
        for g in self.sparse_groups():
            if isinstance(valid_per_group, dict):
                vpg = valid_per_group.get(g.name)
            else:
                vpg = valid_per_group
            cap = g.n if vpg is None else min(g.n, vpg)
            plan.append(int(min(math.ceil(chunk * cap), worklist)))
        return tuple(plan)

    def segment_stride(self, chunk: int) -> int:
        """The key stride ``chunk * A + 1`` of ``sparse_apply``'s one
        sort: group g's keys are ``g * stride + flat`` and the drop
        key lies past the last group's, so ``(G + 1) * stride`` has to
        fit an int32."""
        stride = chunk * self.A + 1
        G = len(self.sparse_groups())
        if (G + 1) * stride >= 1 << 31:
            raise ValueError(
                f"chunk={chunk} x A={self.A} candidate lanes in {G} "
                f"groups pass the int32 key of the sparse apply's sort: "
                f"({G} + 1) * ({chunk} * {self.A} + 1) >= 2^31; lower "
                f"the chunk")
        return stride

    def sparse_apply(self, batch, sel, selv, plan):
        """Successor rows of a compacted enabled worklist.

        ``batch`` [C, W] chunk states; ``sel`` [VC] flat candidate ids
        (lane * A + cand), ascending, with the drop value C*A past the
        enabled prefix (``engine.compact_chunk``'s); ``selv`` = sel <
        C*A; ``plan`` the static per-group budgets from sparse_plan.
        Returns (flatc [VC, W], apply_ovf, rows_built): ``flatc``
        bit-identical to the dense ``flatp[sel]`` gather for every
        in-budget worklist lane (drop lanes are zeros rows, exactly as
        the dense path's appended pad row). Lanes of a group past its
        budget are zeros rows too, with ``apply_ovf`` set — the engines
        fold it into the overflow abort, so no surviving wave ever reads
        one.

        The worklist is segmented by group with ONE sort of one int32
        key, ``group * (C*A + 1) + flat``: the group of a candidate is a
        step function of ``sel % A`` over the groups' static offsets
        (compares, no table gather), and group g's lanes are then the
        ``count_g`` sorted keys from the running sum of the counts
        before it, in the worklist's own order. Never a compaction by
        ``.at[dst].set``: a scatter is a serial pass over all VC lanes
        on the TPU, 4.6 ns a lane a group.

        A budget is the overflow bound, not the work: a group's rows are
        built in tiles of ``apply_tile(eb, C)`` lanes under a loop of
        ``ceil(min(count_g, eb) / tile)`` trips, each tile one slice of
        the sorted keys, one row gather, the group's kernel (traced once
        a group, at the tile's width) and one ``dynamic_update_slice``
        into the one block every group writes: [VC + a tile + 1, W],
        the groups' kept rows end to end in the sorted worklist's order
        (together they are VC at most), the last row zeros. A group's
        last tile runs past what the group keeps, into rows the next
        group with a lane writes over or nothing reads. The compacted
        block is gathered out of that one the same way, in tiles of
        ``apply_tile(VC, C)`` worklist lanes under the worklist's own
        count. ``rows_built`` is the rows the groups' tiles built, i32:
        ``sum(plan)`` at most where every budget is whole tiles (the
        loose plan's are)."""
        import jax
        from jax import lax

        C, W = batch.shape
        A = self.A
        groups = self.sparse_groups()
        G = len(groups)
        VC = sel.shape[0]
        tiles = [apply_tile(eb, C) for eb in plan]
        pad = max(tiles)
        stride = self.segment_stride(C)
        cand = sel % A
        wg = jnp.where(
            selv,
            sum((cand >= g.off).astype(jnp.int32) for g in groups[1:]),
            G,
        )
        drop = G * stride + C * A
        # padded by the largest tile so that no slice below clamps
        keys = jnp.concatenate([
            lax.sort(jnp.where(selv, wg * stride + sel, drop)),
            jnp.full((pad,), drop, jnp.int32),
        ])
        zeros_row = VC + pad  # past every tile: nothing writes it
        row = jnp.full((VC,), zeros_row, jnp.int32)
        apply_ovf = jnp.zeros((), bool)
        rows_built = jnp.zeros((), jnp.int32)
        allb = jnp.zeros((zeros_row + 1, W), jnp.int32)
        # where the group's lanes start in the sorted keys (the counts
        # before it) and its rows in the block (what those groups kept)
        start = at_row = jnp.zeros((), jnp.int32)
        for gi, (g, eb, T) in enumerate(zip(groups, plan, tiles)):
            mask = wg == gi
            pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
            count = jnp.sum(mask, dtype=jnp.int32)
            apply_ovf = apply_ovf | (count > eb)
            kept = jnp.minimum(count, eb)
            trips = (kept + (T - 1)) // T
            tbl = jnp.asarray(g.params)
            kern = self.kernel_for(g.name)

            def tile(t, allb):
                # lanes [t*T, (t+1)*T) of the group's segment of the
                # sorted keys: flat candidate ids, C*A (the drop value)
                # past what the group keeps (traced inside this turn of
                # the groups' loop, so it reads this group's names)
                at = t * T
                flat = jnp.where(
                    at + jnp.arange(T, dtype=jnp.int32) < kept,
                    lax.dynamic_slice(keys, (start + at,), (T,))
                    - gi * stride,
                    C * A,
                )
                lane = jnp.clip(flat // A, 0, C - 1)
                k = jnp.clip(flat % A - g.off, 0, g.n - 1)
                srows = batch[lane]
                args = [tbl[:, c][k] for c in range(tbl.shape[1])]
                built = jax.vmap(lambda s, *a: kern(s, *a)[1])(srows, *args)
                return lax.dynamic_update_slice(
                    allb, built, (at_row + at, jnp.int32(0)))

            # what building the group's successors costs is read from a
            # trace by its name (``expand/Restart/fusion.N``): the scope
            # is opened outside the loop and the vmap, which would hide
            # it
            with jax.named_scope(g.name):
                allb = lax.fori_loop(jnp.int32(0), trips, tile, allb)
            row = jnp.where(mask & (pos < eb), at_row + pos, row)
            start = start + count
            at_row = at_row + kept
            rows_built = rows_built + trips * T
        return (tiled_rows(allb, row, jnp.sum(selv, dtype=jnp.int32),
                           apply_tile(VC, C)),
                apply_ovf, rows_built)

    # ---------------- host-engine apply ----------------

    def host_apply(self, batch_np, flat_idx, block: int = 1024):
        """Successor rows for the enabled flat candidates ``flat_idx``
        (sorted, lane * A + cand) of one host chunk ``batch_np`` [C, W].

        Per-group jitted blocks of a fixed ``block`` size keep every
        call on a precompiled signature; a group larger than one block
        LOOPS instead of aborting (the host engine has no fixed device
        worklist), and the extra batches are reported so the engine can
        surface them as the ``expand_budget_ovf`` gauge. Returns
        (rows [len(flat_idx), W] np.int32, extra_batches)."""
        import jax

        A = self.A
        groups = self.sparse_groups()
        out = np.zeros((len(flat_idx), self.layout.W), np.int32)
        cands = flat_idx % A
        extra = 0
        for gi, g in enumerate(groups):
            m = (cands >= g.off) & (cands < g.off + g.n)
            if not m.any():
                continue
            idxs = flat_idx[m]
            srows = batch_np[idxs // A]
            ks = (idxs % A - g.off).astype(np.int32)
            fn = self._host_group_fn(gi, block)
            parts = []
            n = len(idxs)
            extra += (n - 1) // block
            for o in range(0, n, block):
                sb = srows[o : o + block]
                kb = ks[o : o + block]
                if len(sb) < block:
                    pad = block - len(sb)
                    sb = np.concatenate(
                        [sb, np.repeat(sb[-1:], pad, axis=0)]
                    )
                    kb = np.concatenate([kb, np.repeat(kb[-1:], pad)])
                parts.append(np.asarray(jax.device_get(fn(sb, kb))))
            out[m] = np.concatenate(parts, axis=0)[:n]
        return out, extra

    def _host_group_fn(self, gi: int, block: int):
        import jax

        cache = self.__dict__.setdefault("_host_group_cache", {})
        key = (gi, block)
        if key not in cache:
            g = self.sparse_groups()[gi]
            tbl = jnp.asarray(g.params)
            kern = self.kernel_for(g.name)

            @jax.jit
            def fn(srows, ks):
                args = [tbl[:, c][ks] for c in range(tbl.shape[1])]
                return jax.vmap(lambda s, *a: kern(s, *a)[1])(srows, *args)

            cache[key] = fn
        return cache[key]


def onehot_row(arr, i):
    """``arr[i]`` along axis 0 via a one-hot select.

    A per-instance dynamic row gather under vmap lowers to a general
    gather whose cost depends on how scattered the indices are (on the
    TPU backend of round 5 the expansion kernel was several times
    slower on real frontiers than on zeros; not re-measured on the
    present one); the first axis here is the tiny server axis, so an
    S-term select is effectively free and data-independent."""
    S = arr.shape[0]
    oh = jnp.arange(S, dtype=jnp.int32) == i
    ohx = oh.reshape((S,) + (1,) * (arr.ndim - 1))
    return jnp.sum(jnp.where(ohx, arr, 0), axis=0)


def onehot_set(arr, i, val):
    """``arr.at[i].set(val)`` along axis 0 via a one-hot select (see
    onehot_row: dynamic-index row scatters serialize the same way)."""
    S = arr.shape[0]
    oh = jnp.arange(S, dtype=jnp.int32) == i
    ohx = oh.reshape((S,) + (1,) * (arr.ndim - 1))
    return jnp.where(ohx, val, arr)


def onehot_set2(arr, i, j, val):
    """``arr.at[i, j].set(val)`` on a matrix ([S, S], or a server's
    log lanes [S, L]) via one-hot."""
    ohi = (jnp.arange(arr.shape[0], dtype=jnp.int32) == i)[:, None]
    ohj = (jnp.arange(arr.shape[1], dtype=jnp.int32) == j)[None, :]
    return jnp.where(ohi & ohj, val, arr)


def onehot_add(arr, i, val):
    """``arr.at[i].add(val)`` along axis 0 via a one-hot select."""
    oh = jnp.arange(arr.shape[0], dtype=jnp.int32) == i
    ohx = oh.reshape((arr.shape[0],) + (1,) * (arr.ndim - 1))
    return arr + jnp.where(ohx, val, 0)


def messages_are_valid_kernel(layout: Layout, packer):
    """MessagesAreValid — MessagePassing.tla:81-83: no record in the bag
    domain is self-addressed (msource = mdest). A checker self-check
    (SURVEY.md §5.2): the spec never sends to self, so a violation means
    the lowering (not the protocol) corrupted a key. Works for both the
    2-word BitPacker (msg_hi/msg_lo) and N-word WidePacker (msg_w*) bag
    layouts; batched over [..., W] states."""
    import jax.numpy as jnp

    from ..ops.packing import EMPTY, WidePacker

    wide = [f.name for f in layout.fields.values() if f.kind == "msg_word"]

    def kernel(states):
        if isinstance(packer, WidePacker):
            words = tuple(layout.get(states, n) for n in wide)
            occ = words[0] != EMPTY
            src = packer.unpack(words, "msource")
            dst = packer.unpack(words, "mdest")
        else:
            hi = layout.get(states, "msg_hi")
            lo = layout.get(states, "msg_lo")
            occ = hi != EMPTY
            src = packer.unpack(hi, lo, "msource")
            dst = packer.unpack(hi, lo, "mdest")
        return ~jnp.any(occ & (src == dst), axis=-1)

    return kernel


def onehot_get2(arr, i, j):
    """``arr[i, j]`` on a matrix ([S, S], or a server's log lanes
    [S, L]) via one-hot: the read that mirrors onehot_set2. An index
    outside its axis reads 0 where a gather would clamp, so callers
    clip first."""
    ohi = (jnp.arange(arr.shape[0], dtype=jnp.int32) == i)[:, None]
    ohj = (jnp.arange(arr.shape[1], dtype=jnp.int32) == j)[None, :]
    return jnp.sum(jnp.where(ohi & ohj, arr, 0))
