"""VIEW projection + SYMMETRY reduction + fingerprinting, layout-driven.

Reproduces TLC's distinct-state semantics for cfgs that declare
``VIEW view`` / ``SYMMETRY symmServers`` (e.g. ``standard-raft/Raft.cfg:28-29``):

  - VIEW: aux counters are excluded from the fingerprint
    (``Raft.tla:115`` — ``view`` omits ``acked/electionCtr/restartCtr``).
    By layout convention the view is the contiguous prefix
    ``vec[:layout.view_len]``.
  - SYMMETRY: two states related by a server permutation are the same
    distinct state (``Raft.tla:116``).

Fingerprint formula v5 (round 6): v4 below with the 1-WL signature
refinement iterated to a bounded depth (``refine_rounds``, default 3)
instead of exactly one round. Deeper refinement shrinks tie groups of
size >= 3 before any permutation is enumerated, but it also changes
WHICH permutations are admissible for still-tied states — so the masked
min lands on a different (equally canonical) orbit representative and
tied-state fingerprints changed vs v4 (hashv=5 in the checkpoint
identity, with the round count recorded alongside). Canonicalization
itself is restructured around three compounding optimisations, all
value-preserving given the signature: one canon per distinct raw
(identity-permutation) view hash of a chunk, by sorts alone
(``fingerprints_by_raw_view``, which a model's own canonicalizer calls
too; the cross-chunk memo table that stood beside it
until PR 33 cost ten times what its hits saved, PERF.md section 6),
tie-group-LOCAL masked mins over per-pattern static tables for lanes
whose tie groups stay small, and an adaptive blocked ``lax.while_loop``
budget replacing the old static ``B//8`` compaction + whole-batch
``lax.cond`` fallback.

Fingerprint formula v4 (round 5): identical STRUCTURE to v3 below, but
all mixing arithmetic runs as two independent u32 streams combined into
one u64 at the end (u64 multiplies/compares are ~400x/180x slow on this
TPU backend — measured numbers in ops/hashing.py), and the bag multiset
combine is ADDITION mod 2^32 rather than XOR (nonlinear carries; round-4
advisor note). Every fingerprint changed vs v3 (hashv=4 in the
checkpoint identity).

Fingerprint formula v3 (round 4 — the perf round). Two changes vs the
round-1..3 formula (min of a positional hash over ALL S! permutations of
the slot-sorted view):

  1. **Sort-free bag hashing.** The message bag is hashed as a MULTISET:
     each occupied slot's record (key words + delivery count) is hashed
     position-independently and the per-slot hashes XOR-reduce. Slots
     hold DISTINCT keys by construction (bag canonicalization,
     ops/packing.py), so XOR cannot cancel duplicates; the collision
     budget stays 2^-64-class. This removes the M-lane ``lax.sort``
     that every permutation previously paid. ``bag_hash_pair`` is that
     hash (v4's two streams and addition); the slot canon of
     KRaftWithReconfig hashes its remapped bag with it too
     (``SlotCanonicalizer``, whose formula revision is its own
     ``hashv``), so no canon in the tree sorts a bag.

  2. **Signature-pruned permutation set.** A permutation-EQUIVARIANT
     per-server signature (1-WL style: per-server invariant content +
     one refinement round folding neighbor signatures through
     server-valued fields, matrices, bitmask members and message
     endpoints) orders the servers. The canonical fingerprint is the
     min of the permuted view's hash over the *admissible* permutations
     only — those that sort the signature sequence. Equivariance makes
     the admissible set correspond across orbit representatives, so the
     result is exactly as canonical as the full-S! min (property-tested
     bit-identical against the brute-force mask in tests/test_symmetry_v3.py).
     States whose signatures are totally ordered (the common case deep
     in a run) need ONE permutation — the argsort — instead of S!.

  Per chunk the kernel computes the fast single-permutation fingerprint
  for every lane (tier 1), resolves tie groups of size <= 2 with the
  static disjoint-adjacent-swap tables (tier 2), and routes the rare
  lanes holding a tie group >= 3 through tier 3: lanes whose tie
  PATTERN has a small admissible block-permutation group (<= the
  largest non-full pattern, e.g. 24 perms at S=5) take the
  tie-group-LOCAL masked min over a per-pattern static table composed
  with the argsort; only all-tied lanes (admissible group = the full
  S!) still pay the S!-table masked min. Both tier-3 buckets drain
  through fixed-size blocks inside a ``lax.while_loop`` whose trip
  count adapts to the actual heavy-lane count — no static budget, no
  whole-batch fallback cliff.

A permutation sigma acts on the packed view as: row gathers for
server-indexed axes, value remaps for server-valued fields and bitmasks,
and field remaps inside packed message keys (no slot re-sort — multiset
hash). Message keys may be 2-word (BitPacker: msg_hi/msg_lo/msg_cnt
kinds) or N-word (WidePacker: msg_word kinds, declared in word order).
A model declares which packed fields transform under sigma either via
``msg_server_fields`` / ``msg_server_nil_fields`` (plain / nil-valued
server ids) or a full ``msg_perm_spec`` of (field, kind) pairs with kind
in {"server", "server_nil", "server_bitmask"} — the bitmask kind covers
member sets inside reconfig-spec messages
(``RaftWithReconfigAddRemove.tla:874``).
"""

from __future__ import annotations

import functools
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .hashing import (
    KA,
    KB,
    PA,
    PB,
    U64_MAX,
    _reduce_pair,
    combine_pair,
    eq_u64,
    ge_u64,
    hash_lanes_pair,
    join_u64,
    mix32,
    ne_u64,
    seed_salts,
    sort_u64_with_idx,
    split_u64,
)
from .packing import EMPTY, BitPacker, WidePacker
from ..models.base import Layout
from ..obs.trace import setup_phase

_C1 = np.uint64(0x9E3779B97F4A7C15)
_C2 = np.uint64(0xC2B2AE3D27D4EB4F)
_MASK64 = (1 << 64) - 1


def _host_mix64(z: int) -> int:
    """splitmix64 finalizer on python ints (for setup-time salts)."""
    z &= _MASK64
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _MASK64
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _MASK64
    return z ^ (z >> 31)


def _np_mix32(z: np.ndarray) -> np.ndarray:
    """murmur3 fmix32 on numpy arrays (u64 intermediate, masked) — for
    building static seed-family xor-mask tables at construction time."""
    m = 0xFFFFFFFF
    z = z.astype(np.uint64) & m
    z = ((z ^ (z >> 16)) * 0x85EBCA6B) & m
    z = ((z ^ (z >> 13)) * 0xC2B2AE35) & m
    return (z ^ (z >> 16)).astype(np.uint32)


def _salt(field_offset: int, role: int) -> tuple[np.uint32, np.uint32]:
    """Deterministic per-(field, role) u32 salt pair for signature folds.
    Depends only on the field's layout offset and the fold role — never
    on a server index (equivariance)."""
    z = _host_mix64(field_offset * 0x100 + role + 0x5A17)
    return np.uint32(z >> 32), np.uint32(z & 0xFFFFFFFF)


# ---- u32 stream-pair helpers (v4: all device hashing avoids u64 muls) ----


def _pmix(x, salt):
    """int array -> (u32, u32) mixed stream pair under a salt pair."""
    sa, sb = salt
    xx = x.astype(jnp.uint32)
    return mix32(xx * KA + sa), mix32(xx * KB + sb)


def _pfold(pair, salt):
    """Re-avalanche an existing stream pair under a salt pair."""
    sa, sb = salt
    a, b = pair
    return mix32(a + sa), mix32(b + sb)


def _padd(p, q):
    return p[0] + q[0], p[1] + q[1]


def _pwhere(cond, p, zero=np.uint32(0)):
    return jnp.where(cond, p[0], zero), jnp.where(cond, p[1], zero)


def _psum_last(p):
    """Sum a stream pair over the LAST axis with one reduce op (two
    reduces over a shared producer hit the fusion cliff — hashing.py)."""
    return _reduce_pair(p[0], p[1], op="sum")


def bag_hash_pair(words, cnt, seed: int = 0):
    """Multiset hash of a message bag as a (u32, u32) stream pair: key
    words [..., M] each (word 0 EMPTY on a free slot) and delivery counts
    [..., M]. Occupied slots' position-independent record hashes combine
    by ADDITION mod 2^32 (nonlinear carries — a slightly better multiset
    structure than the round-4 XOR, which was linear over GF(2); slots
    hold distinct keys by construction either way, so neither combine
    can cancel duplicates), so the bag's slot order never enters and no
    permutation re-sorts it. A nonzero seed XORs a per-word constant in
    before the multiply (the audit's independent family). The one
    definition: ``Canonicalizer._bag_hash_pair`` and the slot canon of
    KRaftWithReconfig (models/kraft_reconfig.py) both call it."""
    occ = words[0] != EMPTY
    ha = jnp.zeros_like(words[0], dtype=jnp.uint32)
    hb = jnp.zeros_like(words[0], dtype=jnp.uint32)
    for w_i, w in enumerate([*words, cnt]):
        x = w.astype(jnp.uint32)
        if seed:
            sw = _host_mix64(w_i * int(_C2) + seed)
            x = x ^ np.uint32(sw & 0xFFFFFFFF)
        wa, wb = _salt(w_i, 20)
        ha = ha ^ mix32(x * KA + wa)
        hb = hb ^ mix32(x * KB + wb)
    # per-slot finalize, then a single stacked multiset-sum reduce
    ha = mix32(ha + KB)
    hb = mix32(hb + KA)
    return _psum_last(_pwhere(occ, (ha, hb)))


def _lookup(t, idx):
    """A [B, S] per-server table read at int [B, N] server indices in
    0..S-1, as S compares and selects: a gather costs nanoseconds a lane
    on the TPU however small its table, a select chain fuses into the
    arithmetic on both sides of it."""
    out = jnp.zeros(idx.shape, t.dtype)
    for s in range(t.shape[1]):
        out = jnp.where(idx == s, t[:, s : s + 1], out)
    return out


def _plookup(p, idx):
    return _lookup(p[0], idx), _lookup(p[1], idx)


def _adj_swap_products(S: int):
    """All non-identity products of pairwise-DISJOINT adjacent
    transpositions of 0..S-1 (the independent edge subsets of the path
    graph): [T, S] perms + [T, S-1] bool masks of the edges each uses."""
    combos = []
    edges = range(S - 1)
    for r in range(1, S):
        for combo in itertools.combinations(edges, r):
            if all(b - a > 1 for a, b in zip(combo, combo[1:])):
                combos.append(combo)
    perms, masks = [], []
    for combo in combos:
        p = list(range(S))
        for k in combo:
            p[k], p[k + 1] = p[k + 1], p[k]
        perms.append(p)
        masks.append([k in combo for k in range(S - 1)])
    return np.array(perms, np.int32), np.array(masks, bool)


def _tie_pattern_groups(S: int, pat: int) -> list[list[int]]:
    """Sorted-position tie groups of an adjacent-equality bit pattern
    (bit j set <=> sorted positions j and j+1 hold equal signatures)."""
    groups, cur = [], [0]
    for j in range(S - 1):
        if (pat >> j) & 1:
            cur.append(j + 1)
        else:
            groups.append(cur)
            cur = [j + 1]
    groups.append(cur)
    return groups


def _tie_pattern_tables(S: int):
    """Per-tie-pattern admissible block permutations of the SORTED
    positions (products of per-group symmetric groups), for the
    tie-group-local tier-3 min.

    Returns (tab [NP, LCAP, S] int32, mask [NP, LCAP] bool,
    local [NP] bool) over the NP = 2^(S-1) adjacent-equality patterns.
    LCAP is the largest admissible-group size among patterns that
    contain a tie group >= 3 but are not all-tied (24 at S=5: the
    {4,1} pattern). Every pattern whose group fits in LCAP is marked
    ``local`` and its table rows enumerate the COMPLETE admissible set
    (identity included), so the local min is exactly the masked
    full-S! min for those lanes; the rest (the all-tied pattern at
    S=5) route to the full S!-table path."""
    NP = 1 << (S - 1)
    nfull = math.factorial(S)
    counts = []
    for pat in range(NP):
        groups = _tie_pattern_groups(S, pat)
        counts.append(int(np.prod([math.factorial(len(g)) for g in groups])))
    lcap = max(
        (c for pat, c in enumerate(counts)
         if c < nfull
         and max(len(g) for g in _tie_pattern_groups(S, pat)) >= 3),
        default=1,
    )
    tab = np.tile(np.arange(S, dtype=np.int32), (NP, lcap, 1))
    mask = np.zeros((NP, lcap), dtype=bool)
    local = np.zeros(NP, dtype=bool)
    for pat in range(NP):
        if counts[pat] > lcap:
            continue
        local[pat] = True
        groups = _tie_pattern_groups(S, pat)
        row = 0
        for combo in itertools.product(
            *[itertools.permutations(g) for g in groups]
        ):
            p = np.arange(S, dtype=np.int32)
            for g, pg in zip(groups, combo):
                for j, tgt in zip(g, pg):
                    p[j] = tgt
            tab[pat, row] = p
            mask[pat, row] = True
            row += 1
        assert row == counts[pat]
    return tab, mask, local


def canon_chunk(canon, states, valid):
    """The engines' canon stage on one chunk's compacted lanes:
    ``(fps, canon_n)`` with invalid lanes and in-chunk duplicates of a
    lower lane masked to U64_MAX (the dedup stage and the sharded
    engine's route read the mask as "not new", which such a lane is)
    and ``canon_n`` i32[3] = [in-chunk duplicate lanes, tier-3 local
    lanes, tier-3 full lanes], as the canon's ``fingerprints_dedup``
    counts them (``Canonicalizer``'s, or a ``make_canonicalizer``
    model's own)."""
    fps, n_dup, tiers = canon.fingerprints_dedup(states, valid)
    return fps, jnp.concatenate([n_dup[None], tiers])


class Canonicalizer:
    # fingerprint-formula revision, the checkpoint identity's hashv (the
    # engines' _ckpt_ident): a canon whose formula changes takes the next
    # number, and checkpoints of the old one are refused on load
    hashv = 5

    @classmethod
    @setup_phase("engine/canon")
    def for_model(cls, model, symmetry: bool = True, seed: int = 0,
                  mode: str = "auto",
                  refine_rounds: int = 3) -> "Canonicalizer":
        """Build from a model's declared message-field symmetry contract
        (keeps the model -> canonicalization plumbing in one place).

        A model with data-dependent canonicalization (e.g. the
        KRaftWithReconfig slot encoding, where a host permutation re-sorts
        the identity slots) supplies its own via ``make_canonicalizer``;
        the returned object provides the same ``fingerprints`` /
        ``_fingerprints`` / ``symmetry`` surface the checkers use."""
        from .. import enable_compcache

        enable_compcache()  # covers custom make_canonicalizer models too
        if hasattr(model, "make_canonicalizer"):
            return model.make_canonicalizer(symmetry, seed=seed)
        return cls(
            model.layout,
            model.packer,
            msg_server_fields=getattr(
                model, "msg_server_fields", ("msource", "mdest")
            ),
            msg_server_nil_fields=getattr(model, "msg_server_nil_fields", ()),
            msg_perm_spec=getattr(model, "msg_perm_spec", None),
            symmetry=symmetry,
            seed=seed,
            mode=mode,
            refine_rounds=refine_rounds,
        )

    def __init__(
        self,
        layout: Layout,
        packer,
        msg_server_fields: tuple[str, ...] = ("msource", "mdest"),
        msg_server_nil_fields: tuple[str, ...] = (),
        msg_perm_spec: tuple[tuple[str, str], ...] | None = None,
        symmetry: bool = True,
        seed: int = 0,
        mode: str = "auto",
        refine_rounds: int = 3,
    ):
        from .. import enable_compcache

        enable_compcache()  # direct constructions (tests, tools)
        S = layout.n_servers
        VL = layout.view_len
        assert VL is not None
        assert mode in ("auto", "full")
        self.layout = layout
        self.packer = packer
        self.symmetry = symmetry
        self.mode = mode
        # 1-WL refinement depth: part of the fingerprint formula (it
        # selects the admissible permutation set for tied states), so it
        # is fixed per canonicalizer and recorded in the checkpoint
        # identity (hashv=5/wl=k). k=3 empirically reaches the fixpoint
        # on the raft workloads; every round is equivariant, so any k
        # yields a correct (bit-self-consistent) canonical form.
        self.refine_rounds = max(1, int(refine_rounds))
        # fingerprint hash seed: a second independent hash family for the
        # collision audit (checker/audit.py)
        self.seed = seed
        # Unified remap spec: (packed field, kind) with kind one of
        #   server          plain server index (msource/mdest)
        #   server_nil      0 = Nil, i+1 = server i (KRaft mleader)
        #   server_bitmask  member set as a bitmask over servers
        if msg_perm_spec is None:
            msg_perm_spec = tuple(
                (f, "server") for f in msg_server_fields
            ) + tuple((f, "server_nil") for f in msg_server_nil_fields)
        self.msg_perm_spec = msg_perm_spec

        if symmetry:
            perms = np.array(list(itertools.permutations(range(S))), dtype=np.int32)
        else:
            perms = np.arange(S, dtype=np.int32)[None, :]
        P = perms.shape[0]

        val_lanes: list[int] = []
        bm_lanes: list[int] = []
        # key-word slices, ordered by sort significance: (hi, lo) for the
        # 2-word BitPacker bags (collected by kind, so layout declaration
        # order cannot silently flip them), msg_word declaration order for
        # the N-word WidePacker bags (word 0 = sort-major by contract)
        hi_sl: slice | None = None
        lo_sl: slice | None = None
        wide_sls: list[slice] = []
        msg_cnt_sl: slice | None = None
        view_fields = []  # (kind, offset, shape, size), offset order
        for f in layout.fields.values():
            if f.offset >= VL:
                continue  # aux: not fingerprinted
            view_fields.append((f.kind, f.offset, f.shape, f.size))
            if f.kind in ("per_server", "per_server_val", "server_bitmask"):
                lanes = list(range(f.offset, f.offset + f.size))
                if f.kind == "per_server_val":
                    val_lanes += lanes
                elif f.kind == "server_bitmask":
                    bm_lanes += lanes
            elif f.kind == "msg_hi":
                hi_sl = layout.sl(f.name)
            elif f.kind == "msg_lo":
                lo_sl = layout.sl(f.name)
            elif f.kind == "msg_word":
                wide_sls.append(layout.sl(f.name))
            elif f.kind == "msg_cnt":
                msg_cnt_sl = layout.sl(f.name)
        if hi_sl is not None or lo_sl is not None:
            assert hi_sl is not None and lo_sl is not None and not wide_sls
            msg_word_sls = [hi_sl, lo_sl]
        else:
            msg_word_sls = wide_sls
        if msg_word_sls:
            n_expected = 2 if hi_sl is not None else getattr(packer, "n_words", None)
            assert n_expected is None or len(msg_word_sls) == n_expected

        self.S, self.P, self.VL = S, P, VL
        # signature pruning pays only past ~24 permutations (see
        # _fingerprints); the choice is per-layout so fingerprints stay
        # consistent across every checker path for a given model
        self.prune = symmetry and S >= 5
        self._val_lanes = np.array(sorted(val_lanes), dtype=np.int32)
        self._bm_lanes = np.array(sorted(bm_lanes), dtype=np.int32)
        self._msg_word_sls = msg_word_sls
        self._msg_cnt_sl = msg_cnt_sl
        self._view_fields = sorted(view_fields, key=lambda t: t[1])
        assert sum(t[3] for t in self._view_fields) == VL, "view lane gap"
        # non-bag view lanes for the positional half of the hash
        bag_lanes: set[int] = set()
        for sl in msg_word_sls:
            bag_lanes |= set(range(sl.start, sl.stop))
        if msg_cnt_sl is not None:
            bag_lanes |= set(range(msg_cnt_sl.start, msg_cnt_sl.stop))
        self._nonbag_lanes = np.array(
            [i for i in range(VL) if i not in bag_lanes], dtype=np.int32
        )
        # ---- direct-hash structure (round 5): the permuted view is
        # never materialized. The positional hash of a permuted view is
        # the lane-wise XOR of mix32(value * K + position*P) — so a lane
        # permutation is just a permutation of the POSITIONAL SALTS
        # (precomputed numpy tables for static permutation sets; cheap
        # elementwise arithmetic for the dynamic tier-1 argsort), and a
        # value remap is a one-hot select over <= S+1 values. Non-bag
        # lanes split into three groups by how values transform:
        #   plain  value invariant (per_server rows, pair matrices,
        #          scalars) — only the position moves
        #   val    server-valued lanes (0 = Nil, i+1 = server i)
        #   bm     bitmask lanes (member sets over servers)
        # XOR-combining the three group reduces equals the single
        # all-lanes reduce of hash_lanes_pair (XOR is commutative and
        # the salt carries the position), so fingerprints are
        # BIT-IDENTICAL to the round-4 v4 formula (hashv stays 4).
        nb = self._nonbag_lanes
        self._K_nb = len(nb)
        nb_inv = np.full(VL, -1, dtype=np.int64)
        nb_inv[nb] = np.arange(len(nb))
        self._nb_inv = nb_inv
        vset, bset = set(val_lanes), set(bm_lanes)
        self._ln_plain = np.array(
            [l for l in nb if l not in vset and l not in bset], np.int32
        )
        self._ln_val = np.array([l for l in nb if l in vset], np.int32)
        self._ln_bm = np.array([l for l in nb if l in bset], np.int32)
        # dynamic-permutation segment recipe, per group in lane order
        # (view_fields are offset-sorted, so per-group concatenation
        # matches the _ln_* lane order)
        self._dyn_segs: list[tuple[str, str, int, int]] = []
        for kind, off, shape, size in self._view_fields:
            if kind in ("msg_hi", "msg_lo", "msg_word", "msg_cnt"):
                continue
            nbbase = int(nb_inv[off])
            if kind in ("per_server", "per_server_val", "server_bitmask"):
                group = {"per_server_val": "val",
                         "server_bitmask": "bm"}.get(kind, "plain")
                self._dyn_segs.append((group, "rows", nbbase, size // S))
            elif kind == "per_server_pair":
                self._dyn_segs.append(("plain", "pair", nbbase, S))
            else:
                self._dyn_segs.append(("plain", "static", nbbase, size))
        if symmetry:
            self._dt_full = self._build_direct(perms)
        if self.prune:
            # tier-2 static tables: all non-identity products of DISJOINT
            # adjacent transpositions (7 non-identity products at S=5;
            # tier 1's argsort is the identity on the sorted view).
            # Applied to the signature-
            # SORTED view these are exactly the block permutations of any
            # tie pattern whose groups have size <= 2 — measured to be
            # >98% of tied states past depth ~9 on the 5-server workload
            # (the rest fall to the masked full-S! path).
            tperms, tmask = _adj_swap_products(S)
            self._t_sigma = jnp.asarray(tperms)  # [T, S] for composition
            self._t_edge_mask = jnp.asarray(tmask)  # [T, S-1]
            # tier-3 tie-pattern tables: complete admissible block-perm
            # sets for every pattern small enough to enumerate locally
            ptab, pmask, plocal = _tie_pattern_tables(S)
            self._p_tab = jnp.asarray(ptab)  # [NP, LCAP, S]
            self._p_mask = jnp.asarray(pmask)  # [NP, LCAP]
            self._p_local = jnp.asarray(plocal)  # [NP]
        self.fingerprints = jax.jit(self._fingerprints)

    def _np_gidx(self, perms: np.ndarray) -> np.ndarray:
        """[T, VL] lane-gather table: permuted[l] = view[gidx[t, l]]."""
        S, VL = self.S, self.VL
        T = perms.shape[0]
        inv = np.argsort(perms, axis=1).astype(np.int32)
        gidx = np.tile(np.arange(VL, dtype=np.int32), (T, 1))
        for kind, off, shape, size in self._view_fields:
            if kind in ("per_server", "per_server_val", "server_bitmask"):
                rest = size // S
                base = off + inv[:, :, None] * rest + np.arange(rest)
                gidx[:, off : off + size] = base.reshape(T, -1)
            elif kind == "per_server_pair":
                src = off + inv[:, :, None] * S + inv[:, None, :]
                gidx[:, off : off + size] = src.reshape(T, -1)
        return gidx

    def _build_direct(self, perms: np.ndarray) -> dict:
        """Direct-hash tables for a static [T, S] permutation set: per
        nonbag GROUP, u32 positional-salt tables (and seed-family xor
        masks), plus value-remap tables and the inverse permutations for
        the admissibility mask. All numpy at build time; jnp constants."""
        S = self.S
        T = perms.shape[0]
        nb = self._nonbag_lanes
        K = self._K_nb
        gidx = self._np_gidx(perms)
        # outpos[t, j] = hash position (index within the nonbag subset of
        # the PERMUTED view) that source nonbag lane j lands at
        src = self._nb_inv[gidx[:, nb]]  # [T, K] src nonbag idx per outpos
        outpos = np.empty((T, K), dtype=np.int64)
        rows = np.repeat(np.arange(T), K)
        outpos[rows, src.reshape(-1)] = np.tile(np.arange(K), T)
        dt: dict = {
            "perms": jnp.asarray(perms.astype(np.int32)),
            "inv": jnp.asarray(np.argsort(perms, axis=1).astype(np.int32)),
            "pow2": jnp.asarray((1 << perms).astype(np.int32)),
        }
        valmap = np.zeros((T, S + 1), dtype=np.int32)
        valmap[:, 1:] = perms + 1
        dt["valmap"] = jnp.asarray(valmap)
        if self.seed:
            sa, sb = seed_salts(self.seed)
        for gname, lanes in (("plain", self._ln_plain),
                             ("val", self._ln_val), ("bm", self._ln_bm)):
            kpos = self._nb_inv[lanes]  # this group's nonbag indices
            op = outpos[:, kpos] if len(lanes) else outpos[:, :0]
            pa = ((op * int(PA)) & 0xFFFFFFFF).astype(np.uint32)
            pb = ((op * int(PB)) & 0xFFFFFFFF).astype(np.uint32)
            dt[f"pa_{gname}"] = jnp.asarray(pa)
            dt[f"pb_{gname}"] = jnp.asarray(pb)
            if self.seed:
                dt[f"xa_{gname}"] = jnp.asarray(_np_mix32(pa + sa))
                dt[f"xb_{gname}"] = jnp.asarray(_np_mix32(pb + sb))
        return dt

    # packer adapters: BitPacker works on (hi, lo), WidePacker on tuples
    def _unpack_key(self, words, name):
        if isinstance(self.packer, WidePacker):
            return self.packer.unpack(words, name)
        return self.packer.unpack(words[0], words[1], name)

    def _replace_key(self, words, name, value):
        if isinstance(self.packer, WidePacker):
            return list(self.packer.replace(words, name, value))
        hi, lo = self.packer.replace(words[0], words[1], name, value)
        return [hi, lo]

    # ---------------- the v3 hash ----------------

    def _bag_hash_pair(self, v):
        """``bag_hash_pair`` of the message bag region of [B, VL] views
        (zeros for a layout without a bag)."""
        if not self._msg_word_sls:
            z = jnp.zeros(v.shape[:-1], jnp.uint32)
            return z, z
        words = [v[..., sl] for sl in self._msg_word_sls]  # each [B, M]
        return bag_hash_pair(words, v[..., self._msg_cnt_sl], self.seed)

    def _perm_hash(self, v):
        """u64 hash of a permuted [B, VL] view: positional over the
        non-bag lanes XOR the slot-order-free bag multiset hash (all
        mixing in u32 stream pairs; one u64 combine at the end)."""
        na, nb = hash_lanes_pair(v[..., self._nonbag_lanes], seed=self.seed)
        ba, bb = self._bag_hash_pair(v)
        return combine_pair(na ^ ba, nb ^ bb)

    # ---------------- equivariant per-server signatures ----------------

    def _signatures(self, view, rounds: int | None = None):
        """[B, VL] -> u64 [B, S] permutation-EQUIVARIANT signatures:
        sig(perm(x))[sigma(i)] == sig(x)[i]. Built from per-server
        invariant content plus ``rounds`` 1-WL refinement rounds
        (default ``self.refine_rounds``); every fold is either
        self-relative or an unordered multiset sum, and no fold reads a
        raw server index — each round preserves equivariance, so any
        depth yields a correct admissible set. All mixing runs as u32
        stream pairs (v4 — u64 multiplies are ~400x slow on this TPU,
        hashing.py); the streams combine into one orderable u64 at the
        very end. ``rounds=1`` reproduces the v4 signature exactly
        (round-0 fold salts are depth-offset only for r >= 1)."""
        S, B = self.S, view.shape[0]
        srange = jnp.arange(S, dtype=jnp.int32)
        acc = (jnp.zeros((B, S), jnp.uint32), jnp.zeros((B, S), jnp.uint32))

        # ---- round 0: invariant content ----
        val_fields = []  # (offset, vals [B,S]) for refinement
        bm_fields = []  # (offset, masks [B,S])
        pair_fields = []  # (offset, mat [B,S,S])
        for kind, off, shape, size in self._view_fields:
            seg = view[:, off : off + size]
            if kind == "per_server":
                rest = size // S
                rows = seg.reshape(B, S, rest)
                acc = _padd(acc, _pfold(hash_lanes_pair(rows), _salt(off, 0)))
            elif kind == "per_server_val":
                vals = seg  # [B, S], 0 = Nil, i+1 = server i
                cat = jnp.where(
                    vals == 0, 0, jnp.where(vals - 1 == srange, 1, 2)
                )
                acc = _padd(acc, _pmix(cat, _salt(off, 1)))
                indeg = jnp.sum(
                    (vals[:, :, None] - 1 == srange[None, None, :])
                    & (vals[:, :, None] > 0),
                    axis=1,
                )
                acc = _padd(acc, _pmix(indeg, _salt(off, 2)))
                val_fields.append((off, vals))
            elif kind == "server_bitmask":
                masks = seg  # [B, S]
                bits = (masks[:, :, None] >> srange[None, None, :]) & 1  # [B,S,S]
                selfbit = (masks >> srange) & 1
                pop = jnp.sum(bits, axis=2)
                acc = _padd(acc, _pmix(pop * 2 + selfbit, _salt(off, 3)))
                acc = _padd(acc, _pmix(jnp.sum(bits, axis=1), _salt(off, 4)))
                bm_fields.append((off, masks))
            elif kind == "per_server_pair":
                mat = seg.reshape(B, S, S)
                diag = mat[:, srange, srange]
                acc = _padd(acc, _pmix(diag, _salt(off, 5)))
                offd = srange[:, None] != srange[None, :]
                e_row = _pwhere(offd, _pmix(mat, _salt(off, 6)))
                acc = _padd(acc, _psum_last(e_row))
                # column fold: transpose so the multiset sum is over the
                # LAST axis (single stacked reduce, hashing.py cliff note)
                e_col = _pwhere(
                    offd, _pmix(mat.transpose(0, 2, 1), _salt(off, 7))
                )
                acc = _padd(acc, _psum_last(e_col))
                pair_fields.append((off, mat))
            # scalar / msg_* handled below; aux excluded by view

        # messages, round 0: fold each record (server fields masked out)
        # into the servers it references
        msg = None
        if self._msg_word_sls:
            words = [view[:, sl] for sl in self._msg_word_sls]  # [B, M]
            cnt = view[:, self._msg_cnt_sl]
            occ = words[0] != EMPTY
            zwords = list(words)
            for fname, _kind in self.msg_perm_spec:
                zwords = self._replace_key(
                    zwords, fname, jnp.zeros_like(zwords[0])
                )
            r0a = jnp.zeros_like(words[0], dtype=jnp.uint32)
            r0b = jnp.zeros_like(words[0], dtype=jnp.uint32)
            for w_i, w in enumerate([*zwords, cnt]):
                x = w.astype(jnp.uint32)
                wa, wb = _salt(w_i, 21)
                r0a = r0a ^ mix32(x * KA + wa)
                r0b = r0b ^ mix32(x * KB + wb)
            rec0 = (mix32(r0a), mix32(r0b))
            cnt32 = jnp.where(occ, cnt, 0).astype(jnp.uint32)
            svals = [self._unpack_key(words, fname)  # [B, M] each
                     for fname, _kind in self.msg_perm_spec]
            msg = (svals, cnt32, occ, rec0)
            for k, (val, (_f, kind)) in enumerate(
                    zip(svals, self.msg_perm_spec)):
                ck = _pfold(rec0, _salt(k, 8))
                c = (cnt32 * ck[0], cnt32 * ck[1])  # [B, M]
                acc = _padd(acc, self._scatter_by_server(c, val, kind, occ))

        sig0 = (mix32(acc[0]), mix32(acc[1]))

        # ---- refinement: fold neighbor signatures, k rounds ----
        def refine(sigp, r):
            rr = 32 * r  # depth-offset every fold salt past round 0
            acc1 = (jnp.zeros((B, S), jnp.uint32),
                    jnp.zeros((B, S), jnp.uint32))
            for off, vals in val_fields:
                nsig = self._gather_sig_fold(
                    sigp, vals, "server_nil", _salt(off, 9 + rr))
                acc1 = _padd(acc1, _pwhere(vals - 1 != srange, nsig))
            for off, masks in bm_fields:
                bits = ((masks[:, :, None] >> srange[None, None, :]) & 1) == 1
                sa, sb = _salt(off, 10 + rr)
                e = (mix32(sigp[0] ^ sa), mix32(sigp[1] ^ sb))  # [B, S]
                contrib = _pwhere(
                    bits,
                    (
                        jnp.broadcast_to(e[0][:, None, :], bits.shape),
                        jnp.broadcast_to(e[1][:, None, :], bits.shape),
                    ),
                )
                acc1 = _padd(acc1, _psum_last(contrib))
            for off, mat in pair_fields:
                sa, sb = _salt(off, 11 + rr)
                m32 = mat.astype(jnp.uint32)
                era = mix32(m32 * KA + (sigp[0] ^ sa)[:, None, :])
                erb = mix32(m32 * KB + (sigp[1] ^ sb)[:, None, :])
                acc1 = _padd(acc1, _psum_last((era, erb)))
                sa2, sb2 = _salt(off, 12 + rr)
                mt32 = mat.transpose(0, 2, 1).astype(jnp.uint32)
                eca = mix32(mt32 * KA + (sigp[0] ^ sa2)[:, None, :])
                ecb = mix32(mt32 * KB + (sigp[1] ^ sb2)[:, None, :])
                acc1 = _padd(acc1, _psum_last((eca, ecb)))
            if msg is not None:
                svals, cnt32, occ, rec0 = msg
                # per-slot fold of every referenced server's sig, then
                # re-scatter: binds a record's endpoints together
                folds = [
                    self._gather_sig_fold(sigp, val, kind, _salt(k, 13 + rr))
                    for k, (val, (_f, kind)) in enumerate(
                        zip(svals, self.msg_perm_spec))
                ]
                osum = (jnp.zeros_like(rec0[0]), jnp.zeros_like(rec0[1]))
                for own in folds:
                    osum = _padd(osum, own)
                for k, (val, own, (_f, kind)) in enumerate(
                        zip(svals, folds, self.msg_perm_spec)):
                    # exclude the target's own contribution so its fold
                    # is over the OTHER endpoints
                    sa, sb = _salt(k, 14 + rr)
                    c = (
                        cnt32 * mix32(rec0[0] + (osum[0] - own[0]) + sa),
                        cnt32 * mix32(rec0[1] + (osum[1] - own[1]) + sb),
                    )
                    acc1 = _padd(
                        acc1,
                        self._scatter_by_server(c, val, kind, occ),
                    )
            return (mix32(sigp[0] + mix32(acc1[0])),
                    mix32(sigp[1] + mix32(acc1[1])))

        sigp = sig0
        for r in range(self.refine_rounds if rounds is None else rounds):
            sigp = refine(sigp, r)
        return combine_pair(sigp[0], sigp[1])

    def _scatter_by_server(self, contrib, val, kind, occ):
        """Sum [B, M] stream-pair contributions onto the servers
        referenced by a message field ([B, M] values, interpretation per
        kind) -> [B, S] pair. Laid out [B, S, M] so the multiset sum is a
        single stacked last-axis reduce."""
        S = self.S
        srange = jnp.arange(S, dtype=jnp.int32)
        ca = jnp.where(occ, contrib[0], 0)
        cb = jnp.where(occ, contrib[1], 0)
        vt = val[:, None, :]  # [B, 1, M]
        if kind == "server":
            onehot = vt == srange[None, :, None]
        elif kind == "server_nil":
            onehot = (vt - 1 == srange[None, :, None]) & (vt > 0)
        elif kind == "server_bitmask":
            onehot = ((vt >> srange[None, :, None]) & 1) == 1
        else:
            raise ValueError(f"unknown msg perm kind {kind}")
        pa = jnp.where(onehot, ca[:, None, :], 0)
        pb = jnp.where(onehot, cb[:, None, :], 0)
        return _psum_last((pa, pb))

    def _gather_sig_fold(self, sig0, val, kind, salt):
        """Fold the sig0 of servers referenced by a [B, N] field (message
        slots or a votedFor row) into a per-lane stream pair (multiset
        sum; 0 when Nil/absent). The salted mix is taken per server,
        before the lookup: S mixes a row whatever N is."""
        S = self.S
        sa, sb = salt
        e = (mix32(sig0[0] ^ sa), mix32(sig0[1] ^ sb))  # [B, S]
        if kind == "server":
            return _plookup(e, jnp.clip(val, 0, S - 1))
        if kind == "server_nil":
            return _pwhere(val > 0, _plookup(e, jnp.clip(val - 1, 0, S - 1)))
        if kind == "server_bitmask":
            srange = jnp.arange(S, dtype=jnp.int32)
            bits = ((val[:, :, None] >> srange[None, None, :]) & 1) == 1
            pa = jnp.where(bits, jnp.broadcast_to(e[0][:, None, :], bits.shape), 0)
            pb = jnp.where(bits, jnp.broadcast_to(e[1][:, None, :], bits.shape), 0)
            return _psum_last((pa, pb))
        raise ValueError(f"unknown msg perm kind {kind}")

    # ------------- direct permuted hashing (no materialization) -------------

    def _group_stream(self, vals, pa, pb, xa_m, xb_m):
        """XOR-reduced (u32, u32) stream pair of one lane group: vals
        int32 [..., B, K] (already value-remapped), pa/pb u32 positional
        salts (broadcastable), xa_m/xb_m the seed-family xor masks (None
        for seed=0). One stacked reduce (hashing.py fusion-cliff note)."""
        x = vals.astype(jnp.uint32)
        xa = x ^ xa_m if xa_m is not None else x
        xb = x ^ xb_m if xb_m is not None else x
        ha = mix32(xa * KA + pa)
        hb = mix32(xb * KB + pb)
        return _reduce_pair(ha, hb, op="xor")

    def _nb_const(self):
        ka = np.uint32((self._K_nb * int(KA)) & 0xFFFFFFFF)
        kb = np.uint32((self._K_nb * int(KB)) & 0xFFFFFFFF)
        return ka, kb

    def _remap_val_static(self, xv, valmap):
        """One-hot server-value remap under [T, S+1] tables -> [T, B, Kv]."""
        out = jnp.zeros((valmap.shape[0],) + xv.shape, jnp.int32)
        for u in range(1, self.S + 1):  # value 0 (Nil) maps to 0
            out = out + jnp.where(xv[None] == u, valmap[:, u, None, None], 0)
        return out

    def _remap_bm_static(self, xb, pow2):
        """Bitmask remap under [T, S] bit-target tables -> [T, B, Kb]."""
        out = jnp.zeros((pow2.shape[0],) + xb.shape, jnp.int32)
        for j in range(self.S):
            out = out + ((xb[None] >> j) & 1) * pow2[:, j, None, None]
        return out

    def _bag_streams(self, view, remap_field):
        """Shared bag-hash skeleton: ``remap_field(val, kind)`` supplies
        the permuted value of each server-referencing message field
        (with any leading permutation axes); returns the multiset-summed
        stream pair [..., B] (bit-identical to _bag_hash_pair on the
        materialized permuted view — unoccupied slots are masked out
        either way, so their word values never contribute)."""
        words = [view[:, sl] for sl in self._msg_word_sls]
        cnt = view[:, self._msg_cnt_sl]
        occ = words[0] != EMPTY
        nwords = list(words)  # remapped values carry any leading perm axes
        for fname, kind in self.msg_perm_spec:
            val = self._unpack_key(words, fname)  # [B, M], original bits
            nwords = self._replace_key(nwords, fname, remap_field(val, kind))
        ha = hb = jnp.uint32(0)
        for w_i, w in enumerate([*nwords, cnt]):
            x = w.astype(jnp.uint32)
            if self.seed:
                sw = _host_mix64(w_i * int(_C2) + self.seed)
                x = x ^ np.uint32(sw & 0xFFFFFFFF)
            wa, wb = _salt(w_i, 20)
            ha = ha ^ mix32(x * KA + wa)
            hb = hb ^ mix32(x * KB + wb)
        ha = mix32(ha + KB)
        hb = mix32(hb + KA)
        return _psum_last(_pwhere(occ, (ha, hb)))

    def _hash_static(self, view, dt):
        """u64 [T, B] hashes of ``view`` under every permutation of a
        static direct-table set — without materializing permuted views:
        per group, the original values (plain) or one-hot-remapped values
        (val/bm) mix against the PERMUTED positional-salt tables."""
        parts = []
        if self._ln_plain.size:
            parts.append(self._group_stream(
                view[:, self._ln_plain],
                dt["pa_plain"][:, None, :], dt["pb_plain"][:, None, :],
                dt["xa_plain"][:, None, :] if self.seed else None,
                dt["xb_plain"][:, None, :] if self.seed else None,
            ))
        if self._ln_val.size:
            vals = self._remap_val_static(view[:, self._ln_val], dt["valmap"])
            parts.append(self._group_stream(
                vals, dt["pa_val"][:, None, :], dt["pb_val"][:, None, :],
                dt["xa_val"][:, None, :] if self.seed else None,
                dt["xb_val"][:, None, :] if self.seed else None,
            ))
        if self._ln_bm.size:
            vals = self._remap_bm_static(view[:, self._ln_bm], dt["pow2"])
            parts.append(self._group_stream(
                vals, dt["pa_bm"][:, None, :], dt["pb_bm"][:, None, :],
                dt["xa_bm"][:, None, :] if self.seed else None,
                dt["xb_bm"][:, None, :] if self.seed else None,
            ))
        ka, kb = self._nb_const()
        na = parts[0][0]
        nb_ = parts[0][1]
        for a, b in parts[1:]:
            na = na ^ a
            nb_ = nb_ ^ b
        na = na ^ ka
        nb_ = nb_ ^ kb
        if self._msg_word_sls:
            S = self.S

            def remap(val, kind):
                if kind == "server":
                    out = jnp.zeros(dt["perms"].shape[:1] + val.shape, jnp.int32)
                    for u in range(S):
                        out = out + jnp.where(
                            val[None] == u, dt["perms"][:, u, None, None], 0)
                    return out
                if kind == "server_nil":
                    out = jnp.zeros(dt["perms"].shape[:1] + val.shape, jnp.int32)
                    for u in range(S):
                        out = out + jnp.where(
                            val[None] == u + 1,
                            dt["perms"][:, u, None, None] + 1, 0)
                    return out
                if kind == "server_bitmask":
                    out = jnp.zeros(dt["pow2"].shape[:1] + val.shape, jnp.int32)
                    for j in range(S):
                        out = out + ((val[None] >> j) & 1) * dt["pow2"][:, j, None, None]
                    return out
                raise ValueError(f"unknown msg perm kind {kind}")

            ba, bb = self._bag_streams(view, remap)
            na = na ^ ba
            nb_ = nb_ ^ bb
        return combine_pair(na, nb_)

    def _dyn_outpos(self, sigma):
        """Per-group hash positions under dynamic sigma [..., B, S] (old
        server i -> new index sigma[..., i]) -> dict of [..., B, Kg]
        int32. Pure elementwise arithmetic — permutations move whole
        server blocks, so a lane's destination is affine in sigma."""
        S = self.S
        lead = sigma.shape[:-1]  # (..., B)
        segs: dict[str, list] = {"plain": [], "val": [], "bm": []}
        for group, skind, nbbase, n in self._dyn_segs:
            if skind == "rows":
                rest = n
                seg = (nbbase
                       + sigma[..., :, None] * rest
                       + jnp.arange(rest, dtype=jnp.int32))
                seg = seg.reshape(lead + (S * rest,))
            elif skind == "pair":
                seg = nbbase + sigma[..., :, None] * S + sigma[..., None, :]
                seg = seg.reshape(lead + (S * S,))
            else:  # static: scalar lanes keep their position
                seg = jnp.broadcast_to(
                    jnp.arange(nbbase, nbbase + n, dtype=jnp.int32),
                    lead + (n,),
                )
            segs[group].append(seg)
        return {
            g: (jnp.concatenate(s, axis=-1) if len(s) > 1 else s[0])
            if s else None
            for g, s in segs.items()
        }

    def _hash_dyn(self, view, sigma):
        """u64 [..., B] hash of ``view`` under dynamic per-state sigma
        [..., B, S] (leading axes broadcast a permutation batch, e.g.
        tier 2's composed swaps) — again with no materialized view."""
        S = self.S
        outpos = self._dyn_outpos(sigma)
        sa = sbm = None
        if self.seed:
            sa, sbm = seed_salts(self.seed)
        parts = []

        def stream(vals, op):
            pa = op.astype(jnp.uint32) * PA
            pb = op.astype(jnp.uint32) * PB
            xa_m = mix32(pa + sa) if self.seed else None
            xb_m = mix32(pb + sbm) if self.seed else None
            return self._group_stream(vals, pa, pb, xa_m, xb_m)

        if self._ln_plain.size:
            parts.append(stream(view[:, self._ln_plain], outpos["plain"]))
        if self._ln_val.size:
            xv = view[:, self._ln_val]
            out = jnp.zeros(sigma.shape[:-2] + xv.shape, jnp.int32)
            for u in range(S):
                out = out + jnp.where(
                    xv == u + 1, sigma[..., u][..., None] + 1, 0)
            parts.append(stream(out, outpos["val"]))
        if self._ln_bm.size:
            xb = view[:, self._ln_bm]
            out = jnp.zeros(sigma.shape[:-2] + xb.shape, jnp.int32)
            for j in range(S):
                out = out | ((xb >> j) & 1) << sigma[..., j][..., None]
            parts.append(stream(out, outpos["bm"]))
        ka, kb = self._nb_const()
        na = parts[0][0]
        nb_ = parts[0][1]
        for a, b in parts[1:]:
            na = na ^ a
            nb_ = nb_ ^ b
        na = na ^ ka
        nb_ = nb_ ^ kb
        if self._msg_word_sls:
            def remap(val, kind):
                # sigma [..., B, S]; val [B, M] -> [..., B, M]
                if kind == "server":
                    out = jnp.zeros(sigma.shape[:-1] + val.shape[-1:], jnp.int32)
                    for u in range(S):
                        out = out + jnp.where(
                            val == u, sigma[..., u][..., None], 0)
                    return out
                if kind == "server_nil":
                    out = jnp.zeros(sigma.shape[:-1] + val.shape[-1:], jnp.int32)
                    for u in range(S):
                        out = out + jnp.where(
                            val == u + 1, sigma[..., u][..., None] + 1, 0)
                    return out
                if kind == "server_bitmask":
                    out = jnp.zeros(sigma.shape[:-1] + val.shape[-1:], jnp.int32)
                    for j in range(S):
                        out = out | ((val >> j) & 1) << sigma[..., j][..., None]
                    return out
                raise ValueError(f"unknown msg perm kind {kind}")

            # _bag_streams broadcasts words [1, B, M] against the remap's
            # leading axes; for dyn the lead is sigma's [..., ] prefix of
            # [..., B, S] — i.e. [..., B, M] after remap
            ba, bb = self._bag_streams(view, remap)
            na = na ^ ba
            nb_ = nb_ ^ bb
        return combine_pair(na, nb_)

    # ---------------- the static masked-min (tie / full path) ----------------

    def _masked_min(self, view, sig):
        """min over the admissible static permutations (brute force over
        the S! direct tables; sig=None means no mask — the plain full-S!
        min). Blocked scan with a running min: the [PBLK, B, K] stream
        temps are bounded to ~512MB per block (P=120 at chunk-sized B
        would otherwise overflow HBM)."""
        B = view.shape[0]
        per_perm = max(1, B * max(1, self._K_nb) * 8)
        PBLK = max(1, min(self.P, (512 << 20) // per_perm))
        nblk = (self.P + PBLK - 1) // PBLK
        pad = nblk * PBLK - self.P

        def padt(t):
            if not pad:
                return t
            # duplicate perm 0: duplicates cannot change a min
            return jnp.concatenate([t, jnp.repeat(t[:1], pad, axis=0)])

        stacked = {
            k: padt(t).reshape((nblk, PBLK) + t.shape[1:])
            for k, t in self._dt_full.items()
        }

        def block(best, tb):
            h = self._hash_static(view, tb)  # [PBLK, B]
            if sig is not None:
                ssig = jnp.take(sig, tb["inv"], axis=1)  # [B, PBLK, S]
                adm = jnp.all(
                    ge_u64(ssig[..., 1:], ssig[..., :-1]), axis=-1
                ).T  # [PBLK, B]
                h = jnp.where(adm, h, U64_MAX)
            return jnp.minimum(best, jnp.min(h, axis=0)), None

        # derive the init from `view` so it carries the same varying-
        # manual-axes type as the body output under shard_map (a plain
        # jnp.full is unvarying and the scan carry types would mismatch)
        init = (view[:, 0].astype(jnp.uint64) & jnp.uint64(0)) | U64_MAX
        best, _ = lax.scan(block, init, stacked)
        return best

    # ---------------- entry point ----------------

    def _fingerprints(self, states):
        """[B, W] int32 -> uint64 [B] canonical fingerprints.

        Formula per layout (fixed at construction, so every checker path
        agrees): S <= 4 -> plain min over all S! permutations (the
        signature machinery costs more than it saves at 6-24 perms,
        measured on the TPU); S >= 5 -> signature-pruned masked min
        (at 120+ perms the brute force is ~9x the whole chunk budget)."""
        return self._canon_view(states[:, : self.VL])[0]

    def _canon_view(self, view, valid=None):
        """Tiered canonical hash of a [B, VL] view batch. Returns
        ``(fp, tiers)``; ``tiers`` is i32[2], the lanes among ``valid``
        (all, if None) that took ``[tier3_local, tier3_full]``. Lanes
        outside ``valid`` (the zero rows that pad a block) are routed to
        neither bucket: an all-zero view is all-tied, and would drain
        through the S!-table min. What carries the ``tier3_full`` scope
        is the S!-table min wherever it runs, so a layout without tiers
        (S <= 4: every lane takes it) counts every valid lane there.

        The nested device scopes (``canon/tier12`` ...) are what
        scripts/stage_split.py splits the ``canon`` stage by."""
        if not self.symmetry:
            return self._perm_hash(view), jnp.zeros((2,), jnp.int32)
        if not self.prune or self.mode == "full":
            n_all = view.shape[0] if valid is None else jnp.sum(valid)
            with jax.named_scope("tier3_full"):
                sig = self._signatures(view) if self.prune else None
                return self._masked_min(view, sig), jnp.stack(
                    [jnp.zeros((), jnp.int32),
                     jnp.asarray(n_all, jnp.int32)])
        with jax.named_scope("tier12"):
            sig = self._signatures(view)
            pre = self._tier_pre(view, sig, valid)
        tiers = jnp.stack([jnp.sum(pre[3]), jnp.sum(pre[4])])
        return self._tier3_apply(view, sig, *pre), tiers.astype(jnp.int32)

    def _tier_pre(self, view, sig, valid=None):
        """Tiers 1+2 plus tie-pattern classification. Returns
        ``(fp, sigma, pat, is_local, is_full)``: the running min after
        the signature-argsort permutation (tier 1) and the static
        disjoint-adjacent-swap products (tier 2), the tier-1 sigma, each
        lane's adjacent-equality pattern id, and the two tier-3 route
        masks (tie group >= 3 with a locally enumerable admissible
        set / needing the full S! table; never a lane outside
        ``valid``)."""
        S = self.S

        # ---- tier 1: one dynamic permutation (the signature argsort) ----
        order = jnp.argsort(sig, axis=1).astype(jnp.int32)  # = inv
        ssig = _lookup(sig, order)
        adj_eq = eq_u64(ssig[:, 1:], ssig[:, :-1])  # [B, S-1]
        sigma = jnp.argsort(order, axis=1).astype(jnp.int32)
        fp = self._hash_dyn(view, sigma)

        # ---- tier 2: disjoint adjacent-swap products on the SORTED view.
        # t composed with the argsort is admissible iff every swapped pair
        # is signature-tied; for states whose tie groups are all <= 2
        # these are ALL the admissible permutations, so min(tier1, tier2)
        # is exactly the masked full-S! min for them. The composed
        # permutation sigma_c[i] = t_sigma[sigma[i]] feeds the same
        # direct dynamic hash — no sorted view is ever materialized.
        comp = jnp.zeros(
            (self._t_sigma.shape[0],) + sigma.shape, jnp.int32
        )  # [T, B, S]
        for u in range(S):
            comp = comp + jnp.where(
                sigma[None] == u, self._t_sigma[:, u, None, None], 0
            )
        t_fps = self._hash_dyn(view, comp)  # [T, B]
        t_valid = jnp.all(
            adj_eq[None, :, :] | ~self._t_edge_mask[:, None, :], axis=2
        )  # [T, B]
        fp = jnp.minimum(
            fp, jnp.min(jnp.where(t_valid, t_fps, U64_MAX), axis=0)
        )

        # ---- tie classification for tier 3: a lane is heavy iff some
        # tie group has size >= 3 (a run of 2+ adjacent equalities);
        # its adjacent-equality PATTERN decides the route: every
        # pattern whose admissible block-perm group fits the static
        # per-pattern tables takes the tie-group-local min, the rest
        # (all-tied lanes at S=5) take the full S!-table masked min.
        heavy = jnp.any(adj_eq[:, :-1] & adj_eq[:, 1:], axis=1)
        shifts = jnp.arange(S - 1, dtype=jnp.int32)
        pat = jnp.sum(
            adj_eq.astype(jnp.int32) << shifts[None, :], axis=1
        ).astype(jnp.int32)
        loc = self._p_local[pat]
        if valid is not None:
            heavy = heavy & valid
        return fp, sigma, pat, heavy & loc, heavy & ~loc

    def _tier3_apply(self, view, sig, fp, sigma, pat, is_local, is_full):
        """Resolve the tier-3 lanes of ``_tier_pre``'s classification:
        both buckets drain through fixed-size blocks inside a
        ``lax.while_loop`` whose trip count adapts to the actual heavy
        population of the chunk — no static compaction budget, no
        whole-batch ``lax.cond`` fallback cliff."""
        with jax.named_scope("tier3_local"):
            fp = self._tier3_local(view, fp, sigma, pat, is_local)
        with jax.named_scope("tier3_full"):
            return self._tier3_full(view, fp, sig, is_full)

    def _tier3_local(self, view, fp, sigma, pat, is_local):
        """Tie-group-LOCAL masked min: for a lane whose tie pattern has
        an enumerable admissible group (<= 24 perms at S=5 for every
        non-all-tied heavy pattern), enumerate exactly the block
        permutations of its tied groups composed with the argsort —
        the COMPLETE admissible set, so the result is bit-identical to
        the full-table masked min at a fraction of its cost."""
        B = view.shape[0]
        S = self.S
        LCAP = self._p_tab.shape[1]
        TL = min(B, max(32, B // 16))
        nsel = jnp.sum(is_local)
        lsel = jnp.argsort(~is_local).astype(jnp.int32)  # local lanes first
        lsel = jnp.concatenate([lsel, jnp.full((TL,), B, jnp.int32)])
        viewp = jnp.concatenate([view, jnp.zeros((1, self.VL), view.dtype)])
        sigmap = jnp.concatenate(
            [sigma, jnp.arange(S, dtype=jnp.int32)[None, :]]
        )
        patp = jnp.concatenate([pat, jnp.zeros((1,), jnp.int32)])
        fpp = jnp.concatenate([fp, jnp.zeros((1,), jnp.uint64)])
        jtl = jnp.arange(TL, dtype=jnp.int32)

        def cond(c):
            return c[0] * TL < nsel

        def body(c):
            i, acc = c
            sel = lax.dynamic_slice(lsel, (i * TL,), (TL,))
            # guard the block tail: past nsel the lsel order continues
            # with NON-local lanes, whose pattern tables are incomplete
            sel = jnp.where(i * TL + jtl < nsel, sel, B)
            v = viewp[sel]
            sg = sigmap[sel]
            tbl = jnp.transpose(self._p_tab[patp[sel]], (1, 0, 2))
            msk = jnp.transpose(self._p_mask[patp[sel]], (1, 0))
            # composed[c, b, i] = tbl[c, b, sg[b, i]] — per-lane pattern
            # perms act on SORTED positions, so compose with the argsort
            comp = jnp.zeros((LCAP, TL, S), jnp.int32)
            for u in range(S):
                comp = comp + jnp.where(
                    sg[None] == u, tbl[:, :, u][:, :, None], 0
                )
            h = jnp.where(msk, self._hash_dyn(v, comp), U64_MAX)
            return i + 1, acc.at[sel].set(jnp.min(h, axis=0))

        _, fpp = lax.while_loop(
            cond, body, (jnp.asarray(0, jnp.int32), fpp)
        )
        return fpp[:B]

    def _tier3_full(self, view, fp, sig, is_full):
        """Full S!-table masked min for lanes whose admissible group is
        too large to enumerate locally (the all-tied pattern at S=5:
        near-init states), drained in adaptive fixed-size blocks."""
        B = view.shape[0]
        TF = min(B, max(16, B // 64))
        nsel = jnp.sum(is_full)
        fsel = jnp.argsort(~is_full).astype(jnp.int32)
        fsel = jnp.concatenate([fsel, jnp.full((TF,), B, jnp.int32)])
        viewp = jnp.concatenate([view, jnp.zeros((1, self.VL), view.dtype)])
        sigp = jnp.concatenate([sig, jnp.zeros((1, self.S), sig.dtype)])
        fpp = jnp.concatenate([fp, jnp.zeros((1,), jnp.uint64)])
        jtf = jnp.arange(TF, dtype=jnp.int32)

        def cond(c):
            return c[0] * TF < nsel

        def body(c):
            i, acc = c
            sel = lax.dynamic_slice(fsel, (i * TF,), (TF,))
            sel = jnp.where(i * TF + jtf < nsel, sel, B)
            h = self._masked_min(viewp[sel], sigp[sel])
            return i + 1, acc.at[sel].set(h)

        _, fpp = lax.while_loop(
            cond, body, (jnp.asarray(0, jnp.int32), fpp)
        )
        return fpp[:B]

    # ---------------- in-chunk dedup by raw view ----------------

    def raw_fingerprints(self, states):
        """u64 [B] identity-permutation view hashes — the cheap raw key
        the in-chunk dedup groups lanes by (for symmetry=False this IS
        the canonical fingerprint)."""
        return self._perm_hash(states[:, : self.VL])

    def fingerprints_dedup(self, states, valid):
        """Canonical fingerprints of a [B, W] state batch, one tiered
        canon per distinct raw view, on the view's first lane
        (``fingerprints_by_raw_view``). Returns ``(fps, n_dup, tiers)``
        with invalid lanes and in-chunk duplicates of a lower lane
        masked to U64_MAX (``n_dup`` of the latter); ``tiers`` i32[2] is
        the representatives that took ``[tier3_local, tier3_full]``
        (``_canon_view``): together at most the valid lanes less
        ``n_dup``. Without SYMMETRY the raw key is the fingerprint,
        nothing is counted and only the invalid lanes are masked.
        ``fingerprints`` is the plain entry: every lane its canon."""
        view = states[:, : self.VL]
        if not self.symmetry:
            zero, no_tiers = _zero_counts(view)
            with _inchunk():
                raw = self._perm_hash(view)
            return jnp.where(valid, raw, U64_MAX), zero, no_tiers
        B = view.shape[0]
        return fingerprints_by_raw_view(
            view, valid, self._perm_hash, self._canon_view,
            min(B, max(64, B // 4)))


# the `inchunk` scope is the raw hash, the three sorts, the
# representatives' row gather and their fingerprints' buffer; it is
# opened piecewise so that the scopes a canon opens in the loop's body
# stay its siblings under `canon`, not its children
_inchunk = functools.partial(jax.named_scope, "inchunk")


def _zero_counts(rows):
    """An i32 zero and an i32[2] of zeros derived from ``rows``, so that
    a loop carry built on them has the lanes' type under shard_map (as
    _masked_min's init)."""
    zero = rows[0, 0] & 0
    return zero, jnp.zeros((2,), jnp.int32) + zero


def fingerprints_by_raw_view(rows, valid, raw_key, canon_block, block):
    """The in-chunk dedup both canons share: the canon of a [B, L] batch
    of rows run once per distinct raw key, its fingerprint on the FIRST
    lane of each distinct raw key and U64_MAX on every other lane.
    ``raw_key(rows)`` is u64 [B], equal on two lanes only if the canon
    would be (a canon's hash of what it reads, unpermuted);
    ``canon_block(block_rows, real)`` canonicalizes a [CB, L] block of
    rows, ``real`` marking the lanes that hold a representative, and
    returns ``(fps u64 [CB], counts i32 [2])``; ``block`` is CB, the
    block's lanes, the caller's function of the shape. Returns ``(fps,
    n_dup, counts)``: ``fps`` in lane order, invalid lanes and in-chunk
    duplicates of a lower lane masked to U64_MAX; ``n_dup`` the valid
    lanes that shared a lower lane's raw key and so skipped the
    permutations, ``counts`` the blocks' sum (sums over the
    representatives, whatever order the blocks take them in).

    A duplicate comes back masked, not filled, because every consumer
    keeps the lowest lane of a fingerprint alone and reads U64_MAX as
    "not new" (``util.first_new``, the sharded engine's route): the
    lowest lane of a canonical fingerprint is the lowest lane of its
    own raw key, so ``first_new`` marks the same lanes new on this as
    on the canon of every lane, and a lane that is new carries the
    canon of its own raw view. Deduplication never changes a value
    that survives.

    Sorts and the representatives' row gather alone, no 1-D gather or
    scatter by a traced index (each a serial pass of 7 ns a lane on the
    chip where a sort of one int32 key is under 1: PERF.md section 6,
    PR 54): the raw keys are sorted (equal views become segments, whose
    head is their lowest lane: ``sort_u64_with_idx`` breaks ties on the
    lane), one sort of one int32 key lays the lanes out representatives
    first, each half in rising lane order (a head's key is its lane,
    another lane's B + its lane), the representatives drain through the
    canon in fixed-size blocks of an adaptive-trip ``lax.while_loop`` (a
    chunk of one view pays one block, a chunk of distinct views one
    canon a lane), block i's fingerprints land in slots [i * CB, (i + 1)
    * CB) of a dense buffer, which is then already in the order of that
    key, and one sort keyed on the lanes brings it back to lane order
    with the padding past the representatives on every other lane."""
    B, CB = rows.shape[0], block
    NB = -(-B // CB)
    _zero, no_counts = _zero_counts(rows)
    with _inchunk():
        raw = raw_key(rows)
        # a valid raw key equal to the sentinel (p = 2^-64) sorts
        # with the padding and comes back masked, as an invalid lane
        sraw, order = sort_u64_with_idx(jnp.where(valid, raw, U64_MAX))
        head = ne_u64(sraw, U64_MAX) & jnp.concatenate(
            [jnp.ones((1,), bool), ne_u64(sraw[1:], sraw[:-1])]
        )
        n_rep = jnp.sum(head)
        n_dup = (jnp.sum(valid) - n_rep).astype(jnp.int32)
        # slot k < n_rep: the lane of the k-th representative by lane;
        # the slots after them: B + the lane of each other lane
        perm = lax.sort(jnp.where(head, order, B + order))
        # a whole number of blocks: dynamic_slice and
        # dynamic_update_slice clamp a start that would run off the end
        permp = jnp.concatenate(
            [perm, jnp.full((NB * CB - B,), B, jnp.int32)])
        rowsp = jnp.concatenate(
            [rows, jnp.zeros((1, rows.shape[1]), rows.dtype)])
        canon_rep = jnp.full((NB * CB,), U64_MAX, jnp.uint64)
        jcb = jnp.arange(CB, dtype=jnp.int32)

    def cond(c):
        return c[0] * CB < n_rep

    def body(c):
        i, acc, counts = c
        with _inchunk():
            real = i * CB + jcb < n_rep
            lane = lax.dynamic_slice(permp, (i * CB,), (CB,))
            heads = rowsp[jnp.where(real, lane, B)]
        cfp, n = canon_block(heads, real)
        with _inchunk():
            acc = lax.dynamic_update_slice(acc, cfp, (i * CB,))
        return i + 1, acc, counts + n

    _, canon_rep, counts = lax.while_loop(
        cond, body, (jnp.asarray(0, jnp.int32), canon_rep, no_counts)
    )
    with _inchunk():
        slot = jnp.arange(B, dtype=jnp.int32)
        fh, fl = split_u64(
            jnp.where(slot < n_rep, canon_rep[:B], U64_MAX))
        # the keys' lanes are a permutation of the lanes: sorting by
        # them alone is its inverse (util.first_new's return sort)
        _, fh, fl = lax.sort(
            (jnp.where(perm < B, perm, perm - B), fh, fl), num_keys=1)
        fps = join_u64(fh, fl)
    return fps, n_dup, counts
