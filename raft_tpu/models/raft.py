"""TPU lowering of the core Raft spec.

Reference: ``/root/reference/specifications/standard-raft/Raft.tla`` (652
lines). Every action kernel cites the TLA+ lines it lowers so parity can be
audited. The lowering is *not* a translation: actions become branchless,
``vmap``-able successor kernels over a packed int32 state vector; enabling
conditions become validity masks; ``CHOOSE``-determinism (Min/Max,
``Raft.tla:190-192``) is realized as lane reductions.

Derived bounds that make the encoding tight:
  - terms live in [1, 1+MaxElections]: only ``RequestVote`` (``Raft.tla:246``)
    mints a new term and it is gated by ``electionCtr < MaxElections``;
  - each value enters the log system at most once globally — the
    ``acked[v] = Nil`` gate (``Raft.tla:306``) never resets — so per-server
    log length is bounded by |Value| and entries keep their (index, term);
  - the message-bag DOMAIN grows monotonically (see ops/bag.py), so a
    behavior's distinct-message count bounds the slot table; overflow is a
    hard error surfaced to the driver, never silent.

Every read and write whose index is a binding, a decoded server, a log
position or a bag slot goes through models/base.py's one-hot helpers
(``onehot_row``, ``onehot_get2``, ``onehot_set``, ``onehot_set2``,
``onehot_add``): the axes are a handful of servers, log lanes, values and
bag slots, and under the worklist's vmap or the guard pass's an ``arr[i]``
is a per-lane gather and an ``arr.at[i].set`` a scatter the TPU serializes
(and, at some batch sizes, has dropped). A one-hot read of an index outside
its axis yields 0 where a gather would clamp, so log positions are clipped
before they are read.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import bag
from ..ops.packing import EMPTY, BitPacker, bits_for
from .base import (
    ActionLabelMixin,
    FleetConstMixin,
    Layout,
    SparseExpandMixin,
    messages_are_valid_kernel,
    onehot_add,
    onehot_get2,
    onehot_row,
    onehot_set,
    onehot_set2,
)

# state[i] encoding (CONSTANTS Follower/Candidate/Leader, Raft.tla:38)
FOLLOWER, CANDIDATE, LEADER = 0, 1, 2
NIL = 0  # votedFor Nil (Raft.tla:41); server i is stored as i+1
ACK_NIL, ACK_FALSE, ACK_TRUE = 0, 1, 2  # acked[v] (Raft.tla:62-65)
RVREQ, RVRESP, AEREQ, AERESP = 1, 2, 3, 4  # mtype (Raft.tla:44-45)

# Next-disjunct order (Raft.tla:527-539), used for TLC-order tie-breaking.
(
    R_RESTART,
    R_REQUESTVOTE,
    R_BECOMELEADER,
    R_CLIENTREQUEST,
    R_ADVANCECOMMIT,
    R_APPENDENTRIES,
    R_UPDATETERM,
    R_HANDLE_RVREQ,
    R_HANDLE_RVRESP,
    R_REJECT_AE,
    R_ACCEPT_AE,
    R_HANDLE_AERESP,
) = range(12)
R_TIMEOUT, R_ADVANCEFSYNC = 12, 13  # RaftFsync-only disjuncts

ACTION_NAMES = [
    "Restart",
    "RequestVote",
    "BecomeLeader",
    "ClientRequest",
    "AdvanceCommitIndex",
    "AppendEntries",
    "UpdateTerm",
    "HandleRequestVoteRequest",
    "HandleRequestVoteResponse",
    "RejectAppendEntriesRequest",
    "AcceptAppendEntriesRequest",
    "HandleAppendEntriesResponse",
    "Timeout",
    "AdvanceFsyncIndex",
]

STATE_NAMES = {FOLLOWER: "Follower", CANDIDATE: "Candidate", LEADER: "Leader"}
MTYPE_NAMES = {
    RVREQ: "RequestVoteRequest",
    RVRESP: "RequestVoteResponse",
    AEREQ: "AppendEntriesRequest",
    AERESP: "AppendEntriesResponse",
}


@dataclass(frozen=True)
class RaftParams:
    n_servers: int
    n_values: int
    max_elections: int
    max_restarts: int
    msg_slots: int = 48
    # ---- variant knobs (defaults = standard-raft/Raft.tla) ----
    # FlexibleRaft (flexible-raft/FlexibleRaft.tla): count-based quorums
    # (FlexibleRaft.tla:262,296); None means strict majority.
    election_quorum: int | None = None
    replication_quorum: int | None = None
    # FlexibleRaft sends/replies strictly once: Send requires the record
    # not in DOMAIN (FlexibleRaft.tla:127-129) and Reply requires the
    # response not in DOMAIN (FlexibleRaft.tla:148-151).
    strict_send_once: bool = False
    # FlexibleRaft has no pendingResponse flow control (leaderVars,
    # FlexibleRaft.tla:109 vs Raft.tla:103-107).
    has_pending_response: bool = True
    # FlexibleRaft's NeedsTruncation is a term-mismatch test with no
    # empty-entries arm (FlexibleRaft.tla:413-416 vs Raft.tla:445-449).
    trunc_term_mismatch: bool = False
    # RaftFsync (raft-and-fsync/RaftFsync.tla): fsyncIndex var (:92),
    # crash-truncation to it (:211-216), split Timeout (:222) +
    # per-peer RequestVote(i,j) (:234), AdvanceFsyncIndex (:339), and
    # the three fsync policy constants (:50-52). Implies strict
    # send-once (:132-134,149-152), no pendingResponse, and
    # term-mismatch truncation (:441-444).
    has_fsync: bool = False
    fsync_leader_before_ae: bool = False  # LeaderFsyncBeforeAppendEntries
    fsync_leader_quorum: bool = False  # LeaderFsyncBeforeIncludeInQuorum
    fsync_follower_reply: bool = False  # FollowerFsyncBeforeReply
    # Opt-in network-fault actions (Raft.tla:508-523, commented out of
    # Next at :540-541): DuplicateMessage re-delivers a bag record,
    # DropMessage discards one delivery. Duplication is bounded by
    # max_msg_copies per record (the unbounded TLA+ form has an infinite
    # state space; documented divergence).
    net_faults: bool = False
    max_msg_copies: int = 2
    # Fleet packing (models/base.py FleetConstMixin): dyn_consts names
    # the params whose guards read a per-state lane instead of the
    # static value; fleet adds the job + constant lanes to the layout.
    dyn_consts: tuple = ()
    fleet: bool = False

    @property
    def max_term(self) -> int:
        return 1 + self.max_elections

    @property
    def max_log(self) -> int:
        return max(1, self.n_values)


def _build_layout(p: RaftParams) -> Layout:
    S, V, L, M = p.n_servers, p.n_values, p.max_log, p.msg_slots
    lay = Layout(S)
    # VIEW variables (Raft.tla:115): messages, serverVars, candidateVars,
    # leaderVars, logVars.
    lay.add("currentTerm", "per_server", (S,))
    lay.add("state", "per_server", (S,))
    lay.add("votedFor", "per_server_val", (S,))
    lay.add("votesGranted", "server_bitmask", (S,))  # set -> bitmask (Raft.tla:93)
    lay.add("log_term", "per_server", (S, L))
    lay.add("log_value", "per_server", (S, L))
    lay.add("log_len", "per_server", (S,))
    lay.add("commitIndex", "per_server", (S,))
    if p.has_fsync:
        lay.add("fsyncIndex", "per_server", (S,))  # RaftFsync.tla:92,117
    lay.add("nextIndex", "per_server_pair", (S, S))
    lay.add("matchIndex", "per_server_pair", (S, S))
    if p.has_pending_response:
        lay.add("pendingResponse", "server_bitmask", (S,))  # bool matrix -> bitmask
    lay.add("msg_hi", "msg_hi", (M,))
    lay.add("msg_lo", "msg_lo", (M,))
    lay.add("msg_cnt", "msg_cnt", (M,))
    if p.fleet:
        # Fleet config axis (models/base.py FleetConstMixin): VIEW
        # scalars so jobs never dedup against each other.
        lay.add("fleet_job", "scalar")
        for nm in p.dyn_consts:
            lay.add("c_" + nm, "scalar")
    # aux (VIEW-excluded: Raft.tla:60-68,115)
    lay.add("acked", "aux", (V,))
    lay.add("electionCtr", "aux")
    lay.add("restartCtr", "aux")
    return lay.finish()


def _build_packer(p: RaftParams) -> BitPacker:
    tb = bits_for(p.max_term)
    sb = bits_for(p.n_servers - 1)
    lb = bits_for(p.max_log + 1)  # indices in 0..L (+1 headroom for nextIndex-1 math)
    vb = bits_for(p.n_values)
    return BitPacker(
        [
            ("mtype", 3),
            ("mterm", tb),
            ("msource", sb),
            ("mdest", sb),
            ("mlastLogTerm", tb),  # RequestVoteRequest (Raft.tla:251-256)
            ("mlastLogIndex", lb),
            ("mvoteGranted", 1),  # RequestVoteResponse (Raft.tla:374-378)
            ("mprevLogIndex", lb),  # AppendEntriesRequest (Raft.tla:277-284)
            ("mprevLogTerm", tb),
            ("nentries", 1),  # <=1 entry per request (Raft.tla:260-274)
            ("eterm", tb),
            ("evalue", vb),
            ("mcommitIndex", lb),
            ("msuccess", 1),  # AppendEntriesResponse (Raft.tla:422-427,476-482)
            ("mmatchIndex", lb),
        ]
    )


def cached_model(params: "RaftParams") -> "RaftModel":
    """Memoized model factory: reusing one instance shares its jitted
    kernels (compile cost dominates small runs and the test suite)."""
    return _cached_model(params)


class RaftModel(SparseExpandMixin, FleetConstMixin, ActionLabelMixin):
    """Vectorized successor/invariant kernels for one (spec, constants) pair."""

    name = "Raft"

    def __init__(self, params: RaftParams, server_names=None, value_names=None):
        self.p = params
        # Variant-accurate rank table: plain Raft only emits ranks 0..11;
        # Timeout/AdvanceFsyncIndex (12/13) exist only with has_fsync.
        self.ACTION_NAMES = (
            list(ACTION_NAMES) if params.has_fsync else list(ACTION_NAMES[:12])
        )
        if params.net_faults:
            # Raft.tla:508-523 (commented out of Next at :540-541):
            # opt-in ranks appended past the variant's standard table.
            self._r_dup = len(self.ACTION_NAMES)
            self._r_drop = self._r_dup + 1
            self.ACTION_NAMES += ["DuplicateMessage", "DropMessage"]
        self.layout = _build_layout(params)
        self.packer = _build_packer(params)
        S, V, M = params.n_servers, params.n_values, params.msg_slots
        self.server_names = list(server_names or [f"s{i+1}" for i in range(S)])
        self.value_names = list(value_names or [f"v{i+1}" for i in range(V)])

        # Candidate table: Next-disjunct order (Raft.tla:527-539); the six
        # message-receipt disjuncts are mutually exclusive per record, so
        # they fuse into one kernel per slot (rank resolved dynamically).
        self.bindings: list[tuple[str, tuple]] = []
        self._ae_pairs = [(i, j) for i in range(S) for j in range(S) if i != j]
        for i in range(S):
            self.bindings.append(("Restart", (i,)))
        if params.has_fsync:
            # RaftFsync Next order (RaftFsync.tla:522-536): Timeout is split
            # from the per-peer RequestVote(i,j), and AdvanceFsyncIndex
            # follows AppendEntries.
            for i in range(S):
                self.bindings.append(("Timeout", (i,)))
            for ij in self._ae_pairs:
                self.bindings.append(("RequestVotePair", ij))
        else:
            for i in range(S):
                self.bindings.append(("RequestVote", (i,)))
        for i in range(S):
            self.bindings.append(("BecomeLeader", (i,)))
        for i in range(S):
            for v in range(V):
                self.bindings.append(("ClientRequest", (i, v)))
        for i in range(S):
            self.bindings.append(("AdvanceCommitIndex", (i,)))
        for ij in self._ae_pairs:
            self.bindings.append(("AppendEntries", ij))
        if params.has_fsync:
            for i in range(S):
                self.bindings.append(("AdvanceFsyncIndex", (i,)))
        for m in range(M):
            self.bindings.append(("HandleMessage", (m,)))
        if params.net_faults:
            for m in range(M):
                self.bindings.append(("DuplicateMessage", (m,)))
            for m in range(M):
                self.bindings.append(("DropMessage", (m,)))
        self.A = len(self.bindings)

        self.expand = jax.jit(jax.vmap(self._expand1))
        self.invariants = {
            "MessagesAreValid": jax.jit(
                messages_are_valid_kernel(self.layout, self.packer)
            ),
            "NoLogDivergence": jax.jit(self._inv_no_log_divergence),
            "LeaderHasAllAckedValues": jax.jit(self._inv_leader_has_acked),
            "CommittedEntriesReachMajority": jax.jit(self._inv_committed_majority),
            "TestInv": jax.jit(lambda s: jnp.ones(s.shape[:-1], dtype=bool)),
        }
        # temporal properties under WF_vars(Next) (checker/liveness.py):
        # ValuesNotStuck == \A v : []<> ValueAllOrNothing(v)
        # (Raft.tla:567-576); []<>Q instances have P = None
        self.liveness = {
            "ValuesNotStuck": [
                (self.value_names[v], None,
                 jax.jit(partial(self._live_value_all_or_nothing, v)))
                for v in range(V)
            ],
        }

    # ---------------- field access helpers ----------------

    def _dec(self, s):
        g = self.layout.get
        return {f: g(s, f) for f in self.layout.fields}

    def _asm(self, d, **updates):
        """Reassemble a state vector from field dict + updates (layout order)."""
        parts = []
        for name, f in self.layout.fields.items():
            arr = updates.get(name, d[name])
            arr = jnp.asarray(arr, jnp.int32)
            parts.append(arr.reshape(-1) if f.shape else arr.reshape(1))
        return jnp.concatenate(parts)

    def _pack(self, **vals):
        hi, lo = self.packer.pack(**vals)
        return jnp.asarray(hi, jnp.int32), jnp.asarray(lo, jnp.int32)

    @staticmethod
    def _last_term(d, i):
        """LastTerm(log[i]) — Raft.tla:126, by one-hot selects; ``ll - 1``
        is clipped for the empty log."""
        ll = onehot_row(d["log_len"], i)
        lt = onehot_row(d["log_term"], i)
        return jnp.where(ll > 0, onehot_row(lt, jnp.clip(ll - 1, 0)), 0)

    # ---------------- action kernels ----------------
    # Each returns (valid, succ_vec, rank, overflow).

    def _restart(self, s, i):
        """Restart(i) — Raft.tla:226-235 (FlexibleRaft.tla:200-208).
        RaftFsync (RaftFsync.tla:203-218) additionally truncates the log
        back to fsyncIndex[i] — all three IF arms reduce to
        Len' = min(Len, fsyncIndex)."""
        p, S = self.p, self.p.n_servers
        d = self._dec(s)
        valid = d["restartCtr"] < self._cv(d, "max_restarts")
        upd = dict(
            state=onehot_set(d["state"], i, FOLLOWER),
            votesGranted=onehot_set(d["votesGranted"], i, 0),
            nextIndex=onehot_set(d["nextIndex"], i, jnp.ones((S,), jnp.int32)),
            matchIndex=onehot_set(d["matchIndex"], i, jnp.zeros((S,), jnp.int32)),
            commitIndex=onehot_set(d["commitIndex"], i, 0),
            restartCtr=d["restartCtr"] + 1,
        )
        if p.has_pending_response:
            upd["pendingResponse"] = onehot_set(d["pendingResponse"], i, 0)
        if p.has_fsync:
            new_ll = jnp.minimum(
                onehot_row(d["log_len"], i), onehot_row(d["fsyncIndex"], i)
            )
            keep = jnp.arange(p.max_log, dtype=jnp.int32) < new_ll
            upd["log_term"] = onehot_set(
                d["log_term"], i, jnp.where(keep, onehot_row(d["log_term"], i), 0)
            )
            upd["log_value"] = onehot_set(
                d["log_value"], i, jnp.where(keep, onehot_row(d["log_value"], i), 0)
            )
            upd["log_len"] = onehot_set(d["log_len"], i, new_ll)
        succ = self._asm(d, **upd)
        return valid, succ, jnp.int32(R_RESTART), jnp.asarray(False)

    def _timeout(self, s, i):
        """Timeout(i) — RaftFsync.tla:222-230: start an election without
        sending (RequestVote(i,j) sends per peer separately)."""
        p = self.p
        d = self._dec(s)
        st_i = onehot_row(d["state"], i)
        valid = (d["electionCtr"] < self._cv(d, "max_elections")) & (
            (st_i == FOLLOWER) | (st_i == CANDIDATE)
        )
        succ = self._asm(
            d,
            state=onehot_set(d["state"], i, CANDIDATE),
            currentTerm=onehot_add(d["currentTerm"], i, 1),
            votedFor=onehot_set(d["votedFor"], i, i + 1),
            votesGranted=onehot_set(d["votesGranted"], i, jnp.int32(1) << i),
            electionCtr=d["electionCtr"] + 1,
        )
        return valid, succ, jnp.int32(R_TIMEOUT), jnp.asarray(False)

    def _request_vote_pair(self, s, i, j):
        """RequestVote(i, j) — RaftFsync.tla:234-243: candidate i sends one
        send-once RequestVoteRequest (at its current term) to peer j."""
        d = self._dec(s)
        valid = onehot_row(d["state"], i) == CANDIDATE
        khi, klo = self._pack(
            mtype=RVREQ,
            mterm=onehot_row(d["currentTerm"], i),
            mlastLogTerm=self._last_term(d, i),
            mlastLogIndex=onehot_row(d["log_len"], i),
            msource=i,
            mdest=j,
        )
        hi, lo, cnt, existed, ovf = bag.bag_put(
            d["msg_hi"], d["msg_lo"], d["msg_cnt"], khi, klo
        )
        valid &= ~existed  # Send (RaftFsync.tla:132-134) is send-once
        succ = self._asm(d, msg_hi=hi, msg_lo=lo, msg_cnt=cnt)
        return valid, succ, jnp.int32(R_REQUESTVOTE), ovf & valid

    def _advance_fsync_index(self, s, i):
        """AdvanceFsyncIndex(i) — RaftFsync.tla:339-343."""
        d = self._dec(s)
        valid = onehot_row(d["fsyncIndex"], i) < onehot_row(d["log_len"], i)
        succ = self._asm(d, fsyncIndex=onehot_add(d["fsyncIndex"], i, 1))
        return valid, succ, jnp.int32(R_ADVANCEFSYNC), jnp.asarray(False)

    def _request_vote(self, s, i):
        """RequestVote(i) — Raft.tla:242-257 (fused Timeout+RequestVote)."""
        p, S = self.p, self.p.n_servers
        d = self._dec(s)
        st_i = onehot_row(d["state"], i)
        valid = (d["electionCtr"] < self._cv(d, "max_elections")) & (
            (st_i == FOLLOWER) | (st_i == CANDIDATE)
        )
        new_term = onehot_row(d["currentTerm"], i) + 1
        last_t = self._last_term(d, i)
        ll_i = onehot_row(d["log_len"], i)
        hi, lo, cnt = d["msg_hi"], d["msg_lo"], d["msg_cnt"]
        ovf = jnp.asarray(False)
        # SendMultipleOnce of RequestVoteRequest to all peers (Raft.tla:250-256):
        # valid only if none was ever sent before.
        for delta in range(1, S):
            j = jnp.mod(i + delta, S)
            khi, klo = self._pack(
                mtype=RVREQ,
                mterm=new_term,
                mlastLogTerm=last_t,
                mlastLogIndex=ll_i,
                msource=i,
                mdest=j,
            )
            hi, lo, cnt, existed, o = bag.bag_put(hi, lo, cnt, khi, klo)
            valid &= ~existed
            ovf |= o
        succ = self._asm(
            d,
            state=onehot_set(d["state"], i, CANDIDATE),
            currentTerm=onehot_set(d["currentTerm"], i, new_term),
            votedFor=onehot_set(d["votedFor"], i, i + 1),
            votesGranted=onehot_set(d["votesGranted"], i, jnp.int32(1) << i),
            electionCtr=d["electionCtr"] + 1,
            msg_hi=hi,
            msg_lo=lo,
            msg_cnt=cnt,
        )
        return valid, succ, jnp.int32(R_REQUESTVOTE), ovf & valid

    def _become_leader(self, s, i):
        """BecomeLeader(i) — Raft.tla:289-300. Quorum (Raft.tla:123) is a
        popcount threshold, replacing TLC's SUBSET enumeration;
        FlexibleRaft uses Cardinality >= ElectionQuorumSize
        (FlexibleRaft.tla:260-269)."""
        p, S = self.p, self.p.n_servers
        d = self._dec(s)
        votes = jnp.sum(
            (onehot_row(d["votesGranted"], i) >> jnp.arange(S, dtype=jnp.int32)) & 1
        )
        if p.election_quorum is not None:
            quorum = votes >= p.election_quorum
        else:
            quorum = 2 * votes > S
        valid = (onehot_row(d["state"], i) == CANDIDATE) & quorum
        upd = dict(
            state=onehot_set(d["state"], i, LEADER),
            nextIndex=onehot_set(
                d["nextIndex"], i,
                jnp.full((S,), 1, jnp.int32) * (onehot_row(d["log_len"], i) + 1),
            ),
            matchIndex=onehot_set(d["matchIndex"], i, jnp.zeros((S,), jnp.int32)),
        )
        if p.has_pending_response:
            upd["pendingResponse"] = onehot_set(d["pendingResponse"], i, 0)
        succ = self._asm(d, **upd)
        return valid, succ, jnp.int32(R_BECOMELEADER), jnp.asarray(False)

    def _client_request(self, s, i, v):
        """ClientRequest(i, v) — Raft.tla:304-313."""
        L = self.p.max_log
        d = self._dec(s)
        valid = (onehot_row(d["state"], i) == LEADER) & (
            onehot_row(d["acked"], v) == ACK_NIL
        )
        pos = onehot_row(d["log_len"], i)
        ovf = valid & (pos >= L)
        posc = jnp.clip(pos, 0, L - 1)
        succ = self._asm(
            d,
            log_term=onehot_set2(
                d["log_term"], i, posc, onehot_row(d["currentTerm"], i)
            ),
            log_value=onehot_set2(d["log_value"], i, posc, v + 1),
            log_len=onehot_add(d["log_len"], i, 1),
            acked=onehot_set(d["acked"], v, ACK_FALSE),
        )
        return valid, succ, jnp.int32(R_CLIENTREQUEST), ovf

    def _advance_commit_index(self, s, i):
        """AdvanceCommitIndex(i) — Raft.tla:320-344."""
        p = self.p
        S, L, V = p.n_servers, p.max_log, p.n_values
        d = self._dec(s)
        ll_i = onehot_row(d["log_len"], i)
        ci_i = onehot_row(d["commitIndex"], i)
        ct_i = onehot_row(d["currentTerm"], i)
        match_row = onehot_row(d["matchIndex"], i)  # [S]
        idxs = jnp.arange(1, L + 1, dtype=jnp.int32)  # candidate indexes
        # Agree(index) = {i} u {k : matchIndex[i][k] >= index} (Raft.tla:323-324).
        # RaftFsync (RaftFsync.tla:313-315): when LeaderFsyncBeforeIncludeInQuorum
        # and index > fsyncIndex[i], the leader excludes itself.
        self_in = jnp.arange(S, dtype=jnp.int32)[None, :] == i
        if p.has_fsync and p.fsync_leader_quorum:
            self_in = self_in & (idxs[:, None] <= onehot_row(d["fsyncIndex"], i))
        agree = self_in | (match_row[None, :] >= idxs[:, None])
        agree_cnt = jnp.sum(agree, axis=1)
        if p.replication_quorum is not None:
            # FlexibleRaft.tla:296: Cardinality(Agree) >= ReplicationQuorumSize
            quorum_ok = agree_cnt >= p.replication_quorum
        else:
            quorum_ok = 2 * agree_cnt > S
        is_agree = quorum_ok & (idxs <= ll_i)  # quorum + in-log
        max_agree = jnp.max(jnp.where(is_agree, idxs, 0))  # Max (Raft.tla:333)
        # max_agree <= L, so the clip's floor is the only bound the read needs
        term_at = onehot_row(
            onehot_row(d["log_term"], i), jnp.clip(max_agree - 1, 0)
        )
        # current-term gate (Raft.tla:330-335)
        new_ci = jnp.where((max_agree > 0) & (term_at == ct_i), max_agree, ci_i)
        valid = (onehot_row(d["state"], i) == LEADER) & (ci_i < new_ci)
        # acked[v]: FALSE -> (v committed in (ci, new_ci]) (Raft.tla:339-342)
        lanes = jnp.arange(L, dtype=jnp.int32)
        in_range = (lanes + 1 > ci_i) & (lanes + 1 <= new_ci)
        vals_row = onehot_row(d["log_value"], i)
        committed = jnp.any(
            in_range[None, :] & (vals_row[None, :] == jnp.arange(1, V + 1, dtype=jnp.int32)[:, None]),
            axis=1,
        )
        acked = jnp.where((d["acked"] == ACK_FALSE) & committed, ACK_TRUE, d["acked"])
        succ = self._asm(
            d, commitIndex=onehot_set(d["commitIndex"], i, new_ci), acked=acked
        )
        return valid, succ, jnp.int32(R_ADVANCECOMMIT), jnp.asarray(False)

    def _append_entries(self, s, i, j):
        """AppendEntries(i, j) — Raft.tla:263-285 (FlexibleRaft.tla:236-256
        has no pendingResponse gate). i != j statically."""
        p = self.p
        L = p.max_log
        d = self._dec(s)
        valid = onehot_row(d["state"], i) == LEADER
        if p.has_pending_response:
            pend_i = onehot_row(d["pendingResponse"], i)
            valid &= ((pend_i >> j) & 1) == 0
        ni_ij = onehot_get2(d["nextIndex"], i, j)
        prev_idx = ni_ij - 1
        lt_row = onehot_row(d["log_term"], i)
        lv_row = onehot_row(d["log_value"], i)
        prev_term = jnp.where(
            prev_idx > 0, onehot_row(lt_row, jnp.clip(prev_idx - 1, 0, L - 1)), 0
        )
        last_entry = jnp.minimum(onehot_row(d["log_len"], i), ni_ij)  # Min (Raft.tla:273)
        if p.has_fsync and p.fsync_leader_before_ae:
            # LeaderFsyncBeforeAppendEntries gate (RaftFsync.tla:261-263)
            valid &= onehot_row(d["fsyncIndex"], i) >= last_entry
        nent = (last_entry >= ni_ij).astype(jnp.int32)  # <=1 entry
        epos = jnp.clip(ni_ij - 1, 0, L - 1)
        eterm = jnp.where(nent > 0, onehot_row(lt_row, epos), 0)
        evalue = jnp.where(nent > 0, onehot_row(lv_row, epos), 0)
        khi, klo = self._pack(
            mtype=AEREQ,
            mterm=onehot_row(d["currentTerm"], i),
            mprevLogIndex=prev_idx,
            mprevLogTerm=prev_term,
            nentries=nent,
            eterm=eterm,
            evalue=evalue,
            mcommitIndex=jnp.minimum(onehot_row(d["commitIndex"], i), last_entry),
            msource=i,
            mdest=j,
        )
        hi, lo, cnt, existed, ovf = bag.bag_put(
            d["msg_hi"], d["msg_lo"], d["msg_cnt"], khi, klo
        )
        if p.strict_send_once:
            # FlexibleRaft Send (FlexibleRaft.tla:127-129): always send-once.
            valid &= ~existed
        else:
            # Send (Raft.tla:145-149): empty AppendEntriesRequest is send-once.
            valid &= (nent > 0) | ~existed
        upd = dict(msg_hi=hi, msg_lo=lo, msg_cnt=cnt)
        if p.has_pending_response:
            upd["pendingResponse"] = onehot_set(
                d["pendingResponse"], i, pend_i | (jnp.int32(1) << j)
            )
        succ = self._asm(d, **upd)
        return valid, succ, jnp.int32(R_APPENDENTRIES), ovf & valid

    # -------- network-fault kernels (opt-in, params.net_faults) --------

    def _duplicate_message(self, s, m):
        """DuplicateMessage(m) — Raft.tla:508-515: re-deliver a record
        already in the bag DOMAIN (Duplicate == count + 1). The TLA+
        form is unbounded; we gate on count < max_msg_copies so the
        state space stays finite (documented divergence)."""
        p = self.p
        d = self._dec(s)
        cnt = d["msg_cnt"]
        kcnt = onehot_row(cnt, m)
        occupied = onehot_row(d["msg_hi"], m) != EMPTY
        valid = occupied & (kcnt >= 1) & (kcnt < p.max_msg_copies)
        succ = self._asm(d, msg_cnt=onehot_add(cnt, m, 1))
        return valid, succ, jnp.int32(self._r_dup), jnp.asarray(False)

    def _drop_message(self, s, m):
        """DropMessage(m) — Raft.tla:517-523: Discard one delivery of a
        receivable record. The DOMAIN keeps the count-0 record, exactly
        like the receipt kernels' bag_discard (ops/bag.py)."""
        d = self._dec(s)
        cnt = d["msg_cnt"]
        occupied = onehot_row(d["msg_hi"], m) != EMPTY
        valid = occupied & (onehot_row(cnt, m) >= 1)
        succ = self._asm(d, msg_cnt=bag.bag_discard_at(cnt, m))
        return valid, succ, jnp.int32(self._r_drop), jnp.asarray(False)

    # -------- fused message-receipt kernel (slot m) --------
    # The six receipt disjuncts of Next (Raft.tla:534-539) are mutually
    # exclusive for a fixed record m (they partition on mtype and on the
    # mterm-vs-currentTerm[mdest] comparison), so one kernel per slot
    # computes whichever fires; `rank` reports which for trace ordering.

    def _handle_message(self, s, m):
        pk = self.p, self.packer
        p, packer = pk
        L = p.max_log
        d = self._dec(s)
        hi, lo, cnt = d["msg_hi"], d["msg_lo"], d["msg_cnt"]
        khi, klo, kcnt = onehot_row(hi, m), onehot_row(lo, m), onehot_row(cnt, m)
        occupied = khi != EMPTY
        u = partial(packer.unpack, khi, klo)
        mtype, mterm = u("mtype"), u("mterm")
        src, dst = u("msource"), u("mdest")
        ct_dst = onehot_row(d["currentTerm"], dst)
        st_dst = onehot_row(d["state"], dst)
        recv = occupied & (kcnt > 0)  # ReceivableMessage (Raft.tla:181-187)

        # Reply(response, request) — Raft.tla:170-176. The six handler
        # branches are pairwise DISJOINT (mtype/term/LogOk guards), so the
        # incoming Discard and the response Send collapse into ONE
        # bag_discard + ONE bag_put on the branch-selected response at the
        # end (bag_put embeds an M-lane slot sort; the round-4 kernel paid
        # it three times per slot instance).
        c2 = bag.bag_discard_at(cnt, m)

        # --- UpdateTerm (Raft.tla:348-355): any DOMAIN record (count may be
        # 0!) with mterm > currentTerm[mdest]; message untouched.
        b_upd = occupied & (mterm > ct_dst)

        # --- HandleRequestVoteRequest (Raft.tla:360-381)
        last_t = self._last_term(d, dst)
        ll_dst = onehot_row(d["log_len"], dst)
        vf_dst = onehot_row(d["votedFor"], dst)
        rv_logok = (u("mlastLogTerm") > last_t) | (
            (u("mlastLogTerm") == last_t) & (u("mlastLogIndex") >= ll_dst)
        )
        grant = (
            (mterm == ct_dst)
            & rv_logok
            & ((vf_dst == NIL) | (vf_dst == src + 1))
        )
        b_rvreq = recv & (mtype == RVREQ) & (mterm <= ct_dst)
        rhi, rlo = self._pack(
            mtype=RVRESP,
            mterm=ct_dst,
            mvoteGranted=grant.astype(jnp.int32),
            msource=dst,
            mdest=src,
        )

        # --- HandleRequestVoteResponse (Raft.tla:386-401)
        b_rvresp = recv & (mtype == RVRESP) & (mterm == ct_dst)
        vg = jnp.where(
            u("mvoteGranted") > 0,
            onehot_set(
                d["votesGranted"], dst,
                onehot_row(d["votesGranted"], dst) | (jnp.int32(1) << src)),
            d["votesGranted"],
        )

        # --- AppendEntries request handling: LogOk (Raft.tla:406-410)
        prev_idx = u("mprevLogIndex")
        prev_term = u("mprevLogTerm")
        nent = u("nentries")
        lt_row = onehot_row(d["log_term"], dst)
        lv_row = onehot_row(d["log_value"], dst)
        ae_logok = (prev_idx == 0) | (
            (prev_idx > 0)
            & (prev_idx <= ll_dst)
            & (prev_term == onehot_row(lt_row, jnp.clip(prev_idx - 1, 0, L - 1)))
        )

        # --- RejectAppendEntriesRequest (Raft.tla:412-430)
        b_reject = (
            recv
            & (mtype == AEREQ)
            & (mterm <= ct_dst)
            & (
                (mterm < ct_dst)
                | ((mterm == ct_dst) & (st_dst == FOLLOWER) & ~ae_logok)
            )
        )
        rjhi, rjlo = self._pack(
            mtype=AERESP, mterm=ct_dst, msuccess=0, mmatchIndex=0, msource=dst, mdest=src
        )

        # --- AcceptAppendEntriesRequest (Raft.tla:454-485)
        b_accept = (
            recv
            & (mtype == AEREQ)
            & (mterm == ct_dst)
            & ((st_dst == FOLLOWER) | (st_dst == CANDIDATE))
            & ae_logok
        )
        can_append = (nent != 0) & (ll_dst == prev_idx)  # CanAppend (Raft.tla:438-440)
        if p.trunc_term_mismatch:
            # NeedsTruncation (FlexibleRaft.tla:413-416): conflicting term
            # at the incoming index; no empty-entries arm.
            at_idx = onehot_row(lt_row, jnp.clip(prev_idx, 0, L - 1))  # term at prev+1
            needs_trunc = (nent != 0) & (ll_dst >= prev_idx + 1) & (at_idx != u("eterm"))
        else:
            needs_trunc = ((nent != 0) & (ll_dst >= prev_idx + 1)) | (
                (nent == 0) & (ll_dst > prev_idx)
            )  # NeedsTruncation (Raft.tla:445-449)
        appending = can_append | (needs_trunc & (nent != 0))
        new_ll = jnp.where(
            appending, prev_idx + 1, jnp.where(needs_trunc, prev_idx, ll_dst)
        )
        lanes = jnp.arange(L, dtype=jnp.int32)
        changes = appending | needs_trunc
        # truncate to prevLogIndex (TruncateLog, Raft.tla:451-452) then
        # append m.mentries[1] if present; padding lanes stay zero.
        keep = lanes < prev_idx
        app_pos = jnp.clip(prev_idx, 0, L - 1)
        nlt = onehot_set(
            jnp.where(keep, lt_row, 0), app_pos,
            jnp.where(appending, u("eterm"), 0),
        )
        nlv = onehot_set(
            jnp.where(keep, lv_row, 0), app_pos,
            jnp.where(appending, u("evalue"), 0),
        )
        nlt = jnp.where(changes, nlt, lt_row)
        nlv = jnp.where(changes, nlv, lv_row)
        ac_ovf = b_accept & appending & (prev_idx >= L)
        achi, aclo = self._pack(
            mtype=AERESP,
            mterm=ct_dst,
            msuccess=1,
            mmatchIndex=prev_idx + nent,
            msource=dst,
            mdest=src,
        )

        # --- HandleAppendEntriesResponse (Raft.tla:490-505)
        b_aeresp = recv & (mtype == AERESP) & (mterm == ct_dst)
        succm = u("msuccess") > 0
        mmatch = u("mmatchIndex")
        ni_ds = onehot_row(onehot_row(d["nextIndex"], dst), src)
        ni2 = onehot_set2(
            d["nextIndex"], dst, src,
            jnp.where(succm, mmatch + 1, jnp.maximum(ni_ds - 1, 1)),
        )
        mi2 = jnp.where(
            succm, onehot_set2(d["matchIndex"], dst, src, mmatch),
            d["matchIndex"])

        # --- shared Reply: put the branch-selected response once ---
        resp_hi = jnp.where(b_rvreq, rhi, jnp.where(b_reject, rjhi, achi))
        resp_lo = jnp.where(b_rvreq, rlo, jnp.where(b_reject, rjlo, aclo))
        phi, plo, pcnt, ex, povf = bag.bag_put(hi, lo, c2, resp_hi, resp_lo)
        if p.strict_send_once:
            # FlexibleRaft Reply (FlexibleRaft.tla:148-151): disabled when
            # the response already exists (ex is the selected response's).
            b_rvreq &= ~ex
            b_reject &= ~ex
            b_accept &= ~ex
        putb = b_rvreq | b_reject | b_accept
        dropb = b_rvresp | b_aeresp  # Discard only, no response

        # --- per-field combination (disjoint branches => order-free) ---
        upd = dict(
            currentTerm=jnp.where(
                b_upd, onehot_set(d["currentTerm"], dst, mterm),
                d["currentTerm"]),
            state=jnp.where(
                b_upd | b_accept, onehot_set(d["state"], dst, FOLLOWER),
                d["state"]),
            votedFor=jnp.where(
                b_upd, onehot_set(d["votedFor"], dst, NIL),
                jnp.where(b_rvreq & grant,
                          onehot_set(d["votedFor"], dst, src + 1),
                          d["votedFor"])),
            votesGranted=jnp.where(b_rvresp, vg, d["votesGranted"]),
            commitIndex=jnp.where(
                b_accept, onehot_set(d["commitIndex"], dst, u("mcommitIndex")),
                d["commitIndex"]),
            log_term=jnp.where(
                b_accept, onehot_set(d["log_term"], dst, nlt), d["log_term"]),
            log_value=jnp.where(
                b_accept, onehot_set(d["log_value"], dst, nlv), d["log_value"]),
            log_len=jnp.where(
                b_accept, onehot_set(d["log_len"], dst, new_ll), d["log_len"]),
            nextIndex=jnp.where(b_aeresp, ni2, d["nextIndex"]),
            matchIndex=jnp.where(b_aeresp, mi2, d["matchIndex"]),
            msg_hi=jnp.where(putb, phi, hi),
            msg_lo=jnp.where(putb, plo, lo),
            msg_cnt=jnp.where(putb, pcnt, jnp.where(dropb, c2, cnt)),
        )
        if p.has_fsync and p.fsync_follower_reply:
            # FollowerFsyncBeforeReply: fsyncIndex := Len(new_log)
            # (RaftFsync.tla:468-470), even when the log didn't change.
            upd["fsyncIndex"] = jnp.where(
                b_accept, onehot_set(d["fsyncIndex"], dst, new_ll),
                d["fsyncIndex"])
        if p.has_pending_response:
            upd["pendingResponse"] = jnp.where(
                b_aeresp,
                onehot_set(
                    d["pendingResponse"], dst,
                    onehot_row(d["pendingResponse"], dst)
                    & ~(jnp.int32(1) << src)),
                d["pendingResponse"])
        succ = self._asm(d, **upd)

        branches = [
            (b_upd, R_UPDATETERM, jnp.asarray(False)),
            (b_rvreq, R_HANDLE_RVREQ, povf),
            (b_rvresp, R_HANDLE_RVRESP, jnp.asarray(False)),
            (b_reject, R_REJECT_AE, povf),
            (b_accept, R_ACCEPT_AE, povf | ac_ovf),
            (b_aeresp, R_HANDLE_AERESP, jnp.asarray(False)),
        ]
        valid = jnp.asarray(False)
        rank = jnp.int32(-1)
        ovf = jnp.asarray(False)
        for b, rk, ob in branches:
            valid = valid | b
            rank = jnp.where(b, jnp.int32(rk), rank)
            ovf = ovf | (b & ob)
        return valid, succ, rank, ovf

    # ---------------- full expansion ----------------

    def _expand1(self, s):
        """All successor candidates of one state, in Next-disjunct order.

        Returns (succs [A, W], valid [A], rank [A], ovf [A])."""
        p = self.p
        S, V, M = p.n_servers, p.n_values, p.msg_slots
        iota_s = jnp.arange(S, dtype=jnp.int32)
        ae_i = jnp.asarray([ij[0] for ij in self._ae_pairs], jnp.int32)
        ae_j = jnp.asarray([ij[1] for ij in self._ae_pairs], jnp.int32)
        outs = []
        outs.append(jax.vmap(lambda i: self._restart(s, i))(iota_s))
        if p.has_fsync:
            outs.append(jax.vmap(lambda i: self._timeout(s, i))(iota_s))
            outs.append(
                jax.vmap(lambda i, j: self._request_vote_pair(s, i, j))(ae_i, ae_j)
            )
        else:
            outs.append(jax.vmap(lambda i: self._request_vote(s, i))(iota_s))
        outs.append(jax.vmap(lambda i: self._become_leader(s, i))(iota_s))
        cr_i = jnp.repeat(iota_s, V)
        cr_v = jnp.tile(jnp.arange(V, dtype=jnp.int32), S)
        outs.append(jax.vmap(lambda i, v: self._client_request(s, i, v))(cr_i, cr_v))
        outs.append(jax.vmap(lambda i: self._advance_commit_index(s, i))(iota_s))
        outs.append(jax.vmap(lambda i, j: self._append_entries(s, i, j))(ae_i, ae_j))
        if p.has_fsync:
            outs.append(jax.vmap(lambda i: self._advance_fsync_index(s, i))(iota_s))
        outs.append(
            jax.vmap(lambda m: self._handle_message(s, m))(jnp.arange(M, dtype=jnp.int32))
        )
        if p.net_faults:
            iota_m = jnp.arange(M, dtype=jnp.int32)
            outs.append(jax.vmap(lambda m: self._duplicate_message(s, m))(iota_m))
            outs.append(jax.vmap(lambda m: self._drop_message(s, m))(iota_m))
        valid = jnp.concatenate([o[0] for o in outs])
        succs = jnp.concatenate([o[1] for o in outs])
        rank = jnp.concatenate([o[2] for o in outs])
        ovf = jnp.concatenate([o[3] for o in outs])
        return succs, valid, rank, ovf

    # ---------------- initial states ----------------

    def init_states(self) -> np.ndarray:
        """Init — Raft.tla:213-218. A single state."""
        p = self.p
        vec = self.layout.zeros((1,))
        lay = self.layout
        vec[0, lay.sl("currentTerm")] = 1
        vec[0, lay.sl("state")] = FOLLOWER
        vec[0, lay.sl("votedFor")] = NIL
        vec[0, lay.sl("nextIndex")] = 1
        vec[0, lay.sl("msg_hi")] = int(EMPTY)
        vec[0, lay.sl("msg_lo")] = int(EMPTY)
        vec[0, lay.sl("acked")] = ACK_NIL
        return self._fleet_stamp(vec)

    # ---------------- invariants ----------------
    # Each maps states [B, W] -> ok bool [B] (True = invariant holds).

    def _inv_no_log_divergence(self, states):
        """NoLogDivergence — Raft.tla:588-596."""
        lay, L = self.layout, self.p.max_log
        ci = lay.get(states, "commitIndex")  # [B,S]
        lt = lay.get(states, "log_term")  # [B,S,L]
        lv = lay.get(states, "log_value")
        mci = jnp.minimum(ci[:, :, None], ci[:, None, :])  # [B,S,S]
        lanes = jnp.arange(1, L + 1, dtype=jnp.int32)
        in_common = lanes[None, None, None, :] <= mci[..., None]  # [B,S,S,L]
        eq = (lt[:, :, None, :] == lt[:, None, :, :]) & (
            lv[:, :, None, :] == lv[:, None, :, :]
        )
        return jnp.all(~in_common | eq, axis=(1, 2, 3))

    def _inv_leader_has_acked(self, states):
        """LeaderHasAllAckedValues — Raft.tla:604-620."""
        lay, V = self.layout, self.p.n_values
        ct = lay.get(states, "currentTerm")
        st = lay.get(states, "state")
        lv = lay.get(states, "log_value")  # [B,S,L]
        acked = lay.get(states, "acked")  # [B,V]
        # newest (non-stale) leader: no other server has a higher term
        not_stale = jnp.all(ct[:, :, None] >= ct[:, None, :], axis=2)  # [B,S]
        is_lead = (st == LEADER) & not_stale
        vals = jnp.arange(1, V + 1, dtype=jnp.int32)
        has_v = jnp.any(lv[:, :, None, :] == vals[None, None, :, None], axis=3)  # [B,S,V]
        bad = jnp.any(
            (acked[:, None, :] == ACK_TRUE) & is_lead[:, :, None] & ~has_v, axis=(1, 2)
        )
        return ~bad

    def _live_value_all_or_nothing(self, v, states):
        """ValueAllOrNothing(v) — Raft.tla:560-573: TRUE when the last
        permissible election failed with no leader (progress legitimately
        impossible), else v must be on EVERY server log or on NONE."""
        lay, L = self.layout, self.p.max_log
        ec = lay.get(states, "electionCtr")
        st = lay.get(states, "state")
        lv = lay.get(states, "log_value")
        ll = lay.get(states, "log_len")
        lanes = jnp.arange(L, dtype=jnp.int32)
        in_log = lanes[None, None, :] < ll[..., None]
        has_v = jnp.any(in_log & (lv == v + 1), axis=2)  # [B, S]
        all_have = jnp.all(has_v, axis=1)
        none_have = ~jnp.any(has_v, axis=1)
        no_leader = ~jnp.any(st == LEADER, axis=1)
        spent = ec == self._cv_batch(states, "max_elections")
        return (spent & no_leader) | all_have | none_have

    def _inv_committed_majority(self, states):
        """CommittedEntriesReachMajority — Raft.tla:625-636."""
        lay, S, L = self.layout, self.p.n_servers, self.p.max_log
        st = lay.get(states, "state")
        ci = lay.get(states, "commitIndex")
        ll = lay.get(states, "log_len")
        lt = lay.get(states, "log_term")
        lv = lay.get(states, "log_value")
        lead = (st == LEADER) & (ci > 0)  # [B,S]
        pos = jnp.clip(ci - 1, 0, L - 1)  # [B,S]
        lt_i = jnp.take_along_axis(lt, pos[:, :, None], axis=2)[:, :, 0]  # [B,S]
        lv_i = jnp.take_along_axis(lv, pos[:, :, None], axis=2)[:, :, 0]
        # match[b,i,j]: server j has leader i's entry at index ci[i]
        posj = jnp.broadcast_to(pos[:, :, None], pos.shape + (S,))  # [B,S,S] index of i
        lt_j = jnp.take_along_axis(
            jnp.broadcast_to(lt[:, None, :, :], lt.shape[:1] + (S,) + lt.shape[1:]),
            posj[..., None],
            axis=3,
        )[..., 0]
        lv_j = jnp.take_along_axis(
            jnp.broadcast_to(lv[:, None, :, :], lv.shape[:1] + (S,) + lv.shape[1:]),
            posj[..., None],
            axis=3,
        )[..., 0]
        match = (ll[:, None, :] >= ci[:, :, None]) & (lt_j == lt_i[..., None]) & (
            lv_j == lv_i[..., None]
        )
        enough = jnp.sum(match, axis=2) >= (S // 2 + 1)  # quorum incl. i
        ok_exists = jnp.any(lead & enough, axis=1)
        return ~jnp.any(lead, axis=1) | ok_exists

    # ---------------- host-side decode/encode ----------------

    def decode(self, vec: np.ndarray) -> dict:
        """Decode one packed state into the canonical python form shared with
        the oracle interpreter (0-based ints; messages as a frozenset of
        (record, count); record = tuple of sorted (field, value))."""
        lay = self.layout
        p = self.p
        g = lambda n: np.asarray(vec[lay.sl(n)])
        S, L = p.n_servers, p.max_log
        lt = g("log_term").reshape(S, L)
        lv = g("log_value").reshape(S, L)
        ll = g("log_len")
        log = tuple(
            tuple((int(lt[i, k]), int(lv[i, k]) - 1) for k in range(int(ll[i])))
            for i in range(S)
        )
        vg = g("votesGranted")
        votes = tuple(
            frozenset(j for j in range(S) if (int(vg[i]) >> j) & 1) for i in range(S)
        )
        if p.has_pending_response:
            pr = g("pendingResponse")
            pending = tuple(
                tuple(bool((int(pr[i]) >> j) & 1) for j in range(S)) for i in range(S)
            )
        else:  # variant without the var: constant all-False in the shared form
            pending = ((False,) * S,) * S
        msgs = {}
        hi, lo, cnt = g("msg_hi"), g("msg_lo"), g("msg_cnt")
        for k in range(p.msg_slots):
            if int(hi[k]) == int(EMPTY):
                continue
            msgs[self.decode_msg(int(hi[k]), int(lo[k]))] = int(cnt[k])
        extra = (
            {"fsyncIndex": tuple(int(x) for x in g("fsyncIndex"))}
            if p.has_fsync
            else {}
        )
        return extra | {
            "currentTerm": tuple(int(x) for x in g("currentTerm")),
            "state": tuple(int(x) for x in g("state")),
            "votedFor": tuple(int(x) - 1 if x > 0 else None for x in g("votedFor")),
            "votesGranted": votes,
            "log": log,
            "commitIndex": tuple(int(x) for x in g("commitIndex")),
            "nextIndex": tuple(
                tuple(int(x) for x in row) for row in g("nextIndex").reshape(S, S)
            ),
            "matchIndex": tuple(
                tuple(int(x) for x in row) for row in g("matchIndex").reshape(S, S)
            ),
            "pendingResponse": pending,
            "messages": frozenset(msgs.items()),
            "acked": tuple(
                {ACK_NIL: None, ACK_FALSE: False, ACK_TRUE: True}[int(x)]
                for x in g("acked")
            ),
            "electionCtr": int(vec[lay.fields["electionCtr"].offset]),
            "restartCtr": int(vec[lay.fields["restartCtr"].offset]),
        }

    def decode_msg(self, hi: int, lo: int) -> tuple:
        """Packed key -> canonical record tuple (sorted (field, value) pairs)."""
        u = self.packer.unpack_all(hi, lo)
        mtype = int(u["mtype"])
        rec = {
            "mtype": MTYPE_NAMES[mtype],
            "mterm": int(u["mterm"]),
            "msource": int(u["msource"]),
            "mdest": int(u["mdest"]),
        }
        if mtype == RVREQ:
            rec["mlastLogTerm"] = int(u["mlastLogTerm"])
            rec["mlastLogIndex"] = int(u["mlastLogIndex"])
        elif mtype == RVRESP:
            rec["mvoteGranted"] = bool(u["mvoteGranted"])
        elif mtype == AEREQ:
            rec["mprevLogIndex"] = int(u["mprevLogIndex"])
            rec["mprevLogTerm"] = int(u["mprevLogTerm"])
            rec["mentries"] = (
                ((int(u["eterm"]), int(u["evalue"]) - 1),) if u["nentries"] else ()
            )
            rec["mcommitIndex"] = int(u["mcommitIndex"])
        elif mtype == AERESP:
            rec["msuccess"] = bool(u["msuccess"])
            rec["mmatchIndex"] = int(u["mmatchIndex"])
        return tuple(sorted(rec.items()))

    def encode_msg(self, rec: tuple) -> tuple[int, int]:
        d = dict(rec)
        mtype = {v: k for k, v in MTYPE_NAMES.items()}[d["mtype"]]
        kw = dict(mtype=mtype, mterm=d["mterm"], msource=d["msource"], mdest=d["mdest"])
        if mtype == RVREQ:
            kw.update(mlastLogTerm=d["mlastLogTerm"], mlastLogIndex=d["mlastLogIndex"])
        elif mtype == RVRESP:
            kw.update(mvoteGranted=int(d["mvoteGranted"]))
        elif mtype == AEREQ:
            ent = d["mentries"]
            kw.update(
                mprevLogIndex=d["mprevLogIndex"],
                mprevLogTerm=d["mprevLogTerm"],
                nentries=len(ent),
                eterm=ent[0][0] if ent else 0,
                evalue=ent[0][1] + 1 if ent else 0,
                mcommitIndex=d["mcommitIndex"],
            )
        elif mtype == AERESP:
            kw.update(msuccess=int(d["msuccess"]), mmatchIndex=d["mmatchIndex"])
        return self.packer.pack(**kw)

    def encode(self, st: dict) -> np.ndarray:
        """Inverse of decode (canonical slot order for the message bag)."""
        lay, p = self.layout, self.p
        S, L = p.n_servers, p.max_log
        vec = lay.zeros(())
        vec[lay.sl("currentTerm")] = st["currentTerm"]
        vec[lay.sl("state")] = st["state"]
        vec[lay.sl("votedFor")] = [0 if v is None else v + 1 for v in st["votedFor"]]
        vec[lay.sl("votesGranted")] = [
            sum(1 << j for j in vs) for vs in st["votesGranted"]
        ]
        lt = np.zeros((S, L), np.int32)
        lv = np.zeros((S, L), np.int32)
        for i, lg in enumerate(st["log"]):
            for k, (t, v) in enumerate(lg):
                lt[i, k] = t
                lv[i, k] = v + 1
        vec[lay.sl("log_term")] = lt.reshape(-1)
        vec[lay.sl("log_value")] = lv.reshape(-1)
        vec[lay.sl("log_len")] = [len(lg) for lg in st["log"]]
        vec[lay.sl("commitIndex")] = st["commitIndex"]
        if p.has_fsync:
            vec[lay.sl("fsyncIndex")] = st["fsyncIndex"]
        vec[lay.sl("nextIndex")] = np.asarray(st["nextIndex"]).reshape(-1)
        vec[lay.sl("matchIndex")] = np.asarray(st["matchIndex"]).reshape(-1)
        if p.has_pending_response:
            vec[lay.sl("pendingResponse")] = [
                sum(1 << j for j, b in enumerate(row) if b)
                for row in st["pendingResponse"]
            ]
        keys = sorted(
            (self.encode_msg(rec), cnt) for rec, cnt in st["messages"]
        )
        if len(keys) > p.msg_slots:
            raise OverflowError("message bag exceeds msg_slots")
        hi = np.full(p.msg_slots, int(EMPTY), np.int32)
        lo = np.full(p.msg_slots, int(EMPTY), np.int32)
        cn = np.zeros(p.msg_slots, np.int32)
        for k, ((h, l), c) in enumerate(keys):
            hi[k], lo[k], cn[k] = h, l, c
        vec[lay.sl("msg_hi")] = hi
        vec[lay.sl("msg_lo")] = lo
        vec[lay.sl("msg_cnt")] = cn
        vec[lay.sl("acked")] = [
            {None: ACK_NIL, False: ACK_FALSE, True: ACK_TRUE}[a] for a in st["acked"]
        ]
        vec[lay.fields["electionCtr"].offset] = st["electionCtr"]
        vec[lay.fields["restartCtr"].offset] = st["restartCtr"]
        return vec


from functools import lru_cache as _lru_cache


@_lru_cache(maxsize=None)
def _cached_model(params: RaftParams) -> "RaftModel":
    return RaftModel(params)
