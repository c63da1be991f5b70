"""TPU lowering of the Kafka KRaft spec.

Reference: ``/root/reference/specifications/pull-raft/KRaft.tla`` (961
lines). Every action kernel cites the TLA+ lines it lowers. The lowering is
*not* a translation: actions become branchless, ``vmap``-able successor
kernels over a packed int32 state vector.

Structural notes:
  - five server states + IllegalState (``KRaft.tla:69,87``) encoded as a
    small-integer enum; the QuorumState transition machine
    (``HasConsistentLeader:316``, ``MaybeTransition:351``,
    ``MaybeHandleCommonResponse:369``) is a branchless select chain;
  - ``pendingFetch`` (``KRaft.tla:123``) holds the exact FetchRequest the
    follower sent; its ``msource`` is the row index, so it decomposes into
    four plain per-server lanes (epoch/offset/lastFetchedEpoch/dest) with
    epoch > 0 doubling as the non-Nil flag;
  - FetchResponses embed the request as a ``correlation`` field
    (``KRaft.tla:649``); the request's source/dest are the response's
    dest/source, so only its three scalar fields pack into the key;
  - the ``Reply`` anti-cycle rule — a FetchResponse may not be duplicated
    (``KRaft.tla:220-227``) — becomes ``valid &= ~existed``;
  - epochs live in [1, 1+MaxElections] (only ``RequestVote:439`` mints);
    per-server log length is bounded by |Value| (``acked[v] = Nil`` gate,
    ``KRaft.tla:596``); quorums are popcount thresholds.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import bag
from ..ops.packing import EMPTY, BitPacker, bits_for
from .base import (
    ActionLabelMixin,
    Layout,
    SparseExpandMixin,
    messages_are_valid_kernel,
    onehot_add,
    onehot_row,
    onehot_set,
    onehot_set2,
)

# state[i] enum, shared with oracle/kraft_oracle.py (KRaft.tla:69,87)
UNATTACHED, VOTED, FOLLOWER, CANDIDATE, LEADER, ILLEGAL = range(6)
NIL = 0  # votedFor/leader Nil; server i stored as i+1
ACK_NIL, ACK_FALSE, ACK_TRUE = 0, 1, 2

# mtype (KRaft.tla:75-78); BeginQuorumResponse records are sent but never
# received (header note, KRaft.tla:17-21)
RVREQ, RVRESP, BQREQ, BQRESP, FETCHREQ, FETCHRESP = 1, 2, 3, 4, 5, 6
# merror (KRaft.tla:84); 0 = Nil
E_NONE, E_FENCED, E_NOTLEADER, E_UNKNOWN = 0, 1, 2, 3
# mresult (KRaft.tla:81); 0 = absent (non-fetch-response records)
R_NONE, R_OK, R_NOTOK, R_DIVERGING = 0, 1, 2, 3

# Next-disjunct order (KRaft.tla:823-840), for trace labels.
(
    K_RESTART,
    K_REQUESTVOTE,
    K_HANDLE_RVREQ,
    K_HANDLE_RVRESP,
    K_BECOMELEADER,
    K_CLIENTREQUEST,
    K_REJECT_FETCH,
    K_DIVERGING_FETCH,
    K_ACCEPT_FETCH,
    K_HANDLE_BQREQ,
    K_SENDFETCH,
    K_HANDLE_FETCH_OK,
    K_HANDLE_FETCH_DIV,
    K_HANDLE_FETCH_ERR,
) = range(14)

ACTION_NAMES = [
    "Restart",
    "RequestVote",
    "HandleRequestVoteRequest",
    "HandleRequestVoteResponse",
    "BecomeLeader",
    "ClientRequest",
    "RejectFetchRequest",
    "DivergingFetchRequest",
    "AcceptFetchRequest",
    "HandleBeginQuorumRequest",
    "SendFetchRequest",
    "HandleSuccessFetchResponse",
    "HandleDivergingFetchResponse",
    "HandleErrorFetchResponse",
]

STATE_NAMES = {
    UNATTACHED: "Unattached",
    VOTED: "Voted",
    FOLLOWER: "Follower",
    CANDIDATE: "Candidate",
    LEADER: "Leader",
    ILLEGAL: "IllegalState",
}
MTYPE_NAMES = {
    RVREQ: "RequestVoteRequest",
    RVRESP: "RequestVoteResponse",
    BQREQ: "BeginQuorumRequest",
    BQRESP: "BeginQuorumResponse",
    FETCHREQ: "FetchRequest",
    FETCHRESP: "FetchResponse",
}
ERROR_NAMES = {E_NONE: None, E_FENCED: "FencedLeaderEpoch",
               E_NOTLEADER: "NotLeader", E_UNKNOWN: "UnknownLeader"}
RESULT_NAMES = {R_OK: "Ok", R_NOTOK: "NotOk", R_DIVERGING: "Diverging"}


@dataclass(frozen=True)
class KRaftParams:
    n_servers: int
    n_values: int
    max_elections: int
    max_restarts: int
    msg_slots: int = 64

    @property
    def max_epoch(self) -> int:
        return 1 + self.max_elections

    @property
    def max_log(self) -> int:
        return max(1, self.n_values)


def _build_layout(p: KRaftParams) -> Layout:
    S, V, L, M = p.n_servers, p.n_values, p.max_log, p.msg_slots
    lay = Layout(S)
    # VIEW (KRaft.tla:154) = messages, serverVars, candidateVars,
    # leaderVars, logVars AND acked; only electionCtr/restartCtr are aux.
    lay.add("currentEpoch", "per_server", (S,))
    lay.add("state", "per_server", (S,))
    lay.add("votedFor", "per_server_val", (S,))
    lay.add("leader", "per_server_val", (S,))
    # pendingFetch (KRaft.tla:123) decomposed; pf_epoch > 0 <=> non-Nil
    lay.add("pf_epoch", "per_server", (S,))
    lay.add("pf_offset", "per_server", (S,))
    lay.add("pf_lastepoch", "per_server", (S,))
    lay.add("pf_dest", "per_server_val", (S,))
    lay.add("log_epoch", "per_server", (S, L))
    lay.add("log_value", "per_server", (S, L))
    lay.add("log_len", "per_server", (S,))
    lay.add("highWatermark", "per_server", (S,))
    lay.add("votesGranted", "server_bitmask", (S,))
    lay.add("endOffset", "per_server_pair", (S, S))
    lay.add("acked", "scalar", (V,))  # in VIEW (KRaft.tla:154)
    lay.add("msg_hi", "msg_hi", (M,))
    lay.add("msg_lo", "msg_lo", (M,))
    lay.add("msg_cnt", "msg_cnt", (M,))
    lay.add("electionCtr", "aux")
    lay.add("restartCtr", "aux")
    return lay.finish()


def _build_packer(p: KRaftParams) -> BitPacker:
    tb = bits_for(p.max_epoch)
    sb = bits_for(p.n_servers - 1)
    nb = bits_for(p.n_servers)  # nil-valued server fields (0..S)
    lb = bits_for(p.max_log + 1)
    vb = bits_for(p.n_values)
    return BitPacker(
        [
            ("mtype", 3),
            ("mepoch", tb),
            ("msource", sb),
            ("mdest", sb),
            ("mlastLogEpoch", tb),  # RequestVoteRequest (KRaft.tla:450-455)
            ("mlastLogOffset", lb),
            ("mleader", nb),  # RequestVote/Fetch responses (KRaft.tla:500)
            ("mvoteGranted", 1),
            ("merror", 2),
            ("mresult", 2),  # FetchResponse only (KRaft.tla:81)
            ("mfetchOffset", lb),  # FetchRequest (KRaft.tla:616-621)
            ("mlastFetchedEpoch", tb),
            ("mhwm", lb),
            ("nentries", 1),  # <=1 entry per response (KRaft.tla:710-712)
            ("eepoch", tb),
            ("evalue", vb),
            ("mdivergingEpoch", tb),  # Diverging response (KRaft.tla:671-672)
            ("mdivergingEndOffset", lb),
            ("cepoch", tb),  # correlation = embedded request (KRaft.tla:649);
            ("cfetchOffset", lb),  # its source/dest are implied (swapped)
            ("clastFetchedEpoch", tb),
        ]
    )


def cached_model(params: "KRaftParams") -> "KRaftModel":
    return _cached_model(params)


class KRaftModel(SparseExpandMixin, ActionLabelMixin):
    """Vectorized successor/invariant kernels for one (spec, constants) pair."""

    name = "KRaft"
    ACTION_NAMES = ACTION_NAMES
    # symmetry: mleader is a nil-valued server field inside packed records
    msg_server_fields = ("msource", "mdest")
    msg_server_nil_fields = ("mleader",)

    def __init__(self, params: KRaftParams, server_names=None, value_names=None):
        self.p = params
        self.layout = _build_layout(params)
        self.packer = _build_packer(params)
        S, V, M = params.n_servers, params.n_values, params.msg_slots
        self.server_names = list(server_names or [f"s{i+1}" for i in range(S)])
        self.value_names = list(value_names or [f"v{i+1}" for i in range(V)])

        # Candidate table: non-receipt disjuncts in Next order
        # (KRaft.tla:823-840), receipt disjuncts fused per slot at the end
        # (mutually exclusive per record; rank resolved dynamically).
        self.bindings: list[tuple[str, tuple]] = []
        self._pairs = [(i, j) for i in range(S) for j in range(S) if i != j]
        for i in range(S):
            self.bindings.append(("Restart", (i,)))
        for i in range(S):
            self.bindings.append(("RequestVote", (i,)))
        for i in range(S):
            self.bindings.append(("BecomeLeader", (i,)))
        for i in range(S):
            for v in range(V):
                self.bindings.append(("ClientRequest", (i, v)))
        for ij in self._pairs:
            self.bindings.append(("SendFetchRequest", ij))
        for m in range(M):
            self.bindings.append(("HandleMessage", (m,)))
        self.A = len(self.bindings)

        self.expand = jax.jit(jax.vmap(self._expand1))
        self.invariants = {
            "MessagesAreValid": jax.jit(
                messages_are_valid_kernel(self.layout, self.packer)
            ),
            "NoIllegalState": jax.jit(self._inv_no_illegal),
            "NoLogDivergence": jax.jit(self._inv_no_log_divergence),
            "NeverTwoLeadersInSameEpoch": jax.jit(self._inv_never_two_leaders),
            "LeaderHasAllAckedValues": jax.jit(self._inv_leader_has_acked),
            "CommittedEntriesReachMajority": jax.jit(self._inv_committed_majority),
            "TestInv": jax.jit(lambda s: jnp.ones(s.shape[:-1], dtype=bool)),
        }
        # ValuesNotStuck == \A v : []<> ValueAllOrNothing(v)
        # (KRaft.tla:867-879; same shape as core Raft's, checker/liveness.py)
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
        parts = []
        for name, f in self.layout.fields.items():
            arr = updates.get(name, d[name])
            arr = jnp.asarray(arr, jnp.int32)
            parts.append(arr.reshape(-1) if f.shape else arr.reshape(1))
        return jnp.concatenate(parts)

    def _pack(self, **vals):
        hi, lo = self.packer.pack(**vals)
        return jnp.asarray(hi, jnp.int32), jnp.asarray(lo, jnp.int32)

    # Every read and write through a traced index (a binding, a decoded
    # server, a log position, the bag slot) is a one-hot select
    # (models/base.py): under the worklist's vmap `arr[i]` is a per-lane
    # gather and `arr.at[i].set` a batched scatter, which the v5e's
    # compiler drops writes from at a wide worklist (PR 30). Positions
    # are clipped into their axis first; an EMPTY bag word decodes to 0
    # in every field, so no decoded server leaves its axis either.

    @staticmethod
    def _last_epoch(d, i):
        """LastEpoch(log[i]) — KRaft.tla:165."""
        ll = onehot_row(d["log_len"], i)
        row = onehot_row(d["log_epoch"], i)
        return jnp.where(ll > 0, onehot_row(row, jnp.clip(ll - 1, 0)), 0)

    # ---------------- transition machine (KRaft.tla:312-392) ----------------
    # All helpers take/return (state, epoch, leader_enc) int32 triples with
    # leader_enc in 0..S (0 = Nil).

    def _maybe_transition(self, d, i, leader_enc, epoch):
        """MaybeTransition — KRaft.tla:351-367."""
        st_i = onehot_row(d["state"], i)
        cur = onehot_row(d["currentEpoch"], i)
        led = onehot_row(d["leader"], i)
        # HasConsistentLeader (KRaft.tla:316-327)
        hcl = jnp.where(
            leader_enc == i + 1,
            st_i == LEADER,
            (epoch != cur) | (leader_enc == NIL) | (led == NIL) | (led == leader_enc),
        )
        # TransitionToFollower (KRaft.tla:344-349)
        tf_ill = (cur == epoch) & ((st_i == FOLLOWER) | (st_i == LEADER))
        tf = (
            jnp.where(tf_ill, ILLEGAL, FOLLOWER),
            jnp.where(tf_ill, 0, epoch),
            jnp.where(tf_ill, 0, leader_enc),
        )
        una = (jnp.int32(UNATTACHED), epoch, jnp.int32(NIL))
        noop = (st_i, cur, led)
        # CASE chain, first match wins
        c1 = ~hcl
        c2 = epoch > cur
        c2_pick = jnp.where(leader_enc == NIL, 1, 2)  # 1=unattached, 2=follower
        c3 = (leader_enc != NIL) & (led == NIL)
        sel = jnp.where(
            c1, 0, jnp.where(c2, c2_pick, jnp.where(c3, 2, 3))
        )  # 0=illegal,1=unattached,2=follower,3=noop
        out = []
        ill = (jnp.int32(ILLEGAL), jnp.int32(0), jnp.int32(NIL))
        for k in range(3):
            out.append(
                jnp.where(
                    sel == 0,
                    ill[k],
                    jnp.where(sel == 1, una[k], jnp.where(sel == 2, tf[k], noop[k])),
                )
            )
        return tuple(out)

    def _maybe_handle_common(self, d, i, leader_enc, epoch, err):
        """MaybeHandleCommonResponse — KRaft.tla:369-392.
        Returns (state, epoch, leader_enc, handled)."""
        st_i = onehot_row(d["state"], i)
        cur = onehot_row(d["currentEpoch"], i)
        led = onehot_row(d["leader"], i)
        mt = self._maybe_transition(d, i, leader_enc, epoch)
        c_stale = epoch < cur
        c_trans = (epoch > cur) | (err != E_NONE)
        c_follow = (epoch == cur) & (leader_enc != NIL) & (led == NIL)
        sel = jnp.where(
            c_stale, 0, jnp.where(c_trans, 1, jnp.where(c_follow, 2, 3))
        )
        fol = (jnp.int32(FOLLOWER), cur, leader_enc)
        noop = (st_i, cur, led)
        out = []
        for k in range(3):
            out.append(
                jnp.where(
                    sel == 0,
                    noop[k],
                    jnp.where(sel == 1, mt[k], jnp.where(sel == 2, fol[k], noop[k])),
                )
            )
        handled = sel != 3
        return out[0], out[1], out[2], handled

    # ---------------- log-position math (KRaft.tla:247-310) ----------------

    def _end_offset_for_epoch(self, d, i, last_fetched_epoch):
        """EndOffsetForEpoch — KRaft.tla:285-301: (offset, epoch) of the
        highest entry with epoch <= last_fetched_epoch; (0,0) if none."""
        L = self.p.max_log
        lanes = jnp.arange(L, dtype=jnp.int32)
        row = onehot_row(d["log_epoch"], i)
        mask = (lanes < onehot_row(d["log_len"], i)) & (row <= last_fetched_epoch)
        off = jnp.max(jnp.where(mask, lanes + 1, 0))
        ep = jnp.where(off > 0, onehot_row(row, jnp.clip(off - 1, 0)), 0)
        return off, ep

    def _highest_common_offset(self, d, i, end_off, epoch):
        """HighestCommonOffset — KRaft.tla:255-273: highest offset with
        CompareEntries(offset, entry.epoch, end_off, epoch) <= 0."""
        L = self.p.max_log
        lanes = jnp.arange(L, dtype=jnp.int32)
        row = onehot_row(d["log_epoch"], i)
        le = (row < epoch) | ((row == epoch) & (lanes + 1 <= end_off))
        mask = (lanes < onehot_row(d["log_len"], i)) & le
        return jnp.max(jnp.where(mask, lanes + 1, 0))

    def _valid_fetch_position(self, d, i, fetch_off, last_fetched_epoch):
        """ValidFetchPosition — KRaft.tla:305-310."""
        off, ep = self._end_offset_for_epoch(d, i, last_fetched_epoch)
        zero = (fetch_off == 0) & (last_fetched_epoch == 0)
        return zero | ((fetch_off <= off) & (last_fetched_epoch == ep))

    # ---------------- action kernels ----------------

    @staticmethod
    def _pf_cleared(d, i, where=True):
        """pendingFetch[i] := Nil where ``where`` holds: the four
        decomposed lanes of server i zeroed."""
        return {
            pf: jnp.where(where, onehot_set(d[pf], i, 0), d[pf])
            for pf in ("pf_epoch", "pf_offset", "pf_lastepoch", "pf_dest")
        }

    def _restart(self, s, i):
        """Restart(i) — KRaft.tla:423-432: keeps currentEpoch, votedFor,
        log; loses leader belief, votes, endOffset, hwm, pendingFetch."""
        p, S = self.p, self.p.n_servers
        d = self._dec(s)
        valid = d["restartCtr"] < p.max_restarts
        succ = self._asm(
            d,
            state=onehot_set(d["state"], i, FOLLOWER),
            leader=onehot_set(d["leader"], i, NIL),
            votesGranted=onehot_set(d["votesGranted"], i, 0),
            endOffset=onehot_set(d["endOffset"], i, jnp.zeros((S,), jnp.int32)),
            highWatermark=onehot_set(d["highWatermark"], i, 0),
            restartCtr=d["restartCtr"] + 1,
            **self._pf_cleared(d, i),
        )
        return valid, succ, jnp.int32(K_RESTART), jnp.asarray(False)

    def _request_vote(self, s, i):
        """RequestVote(i) — KRaft.tla:439-456 (fused Timeout+RequestVote;
        enabled from Follower, Candidate or Unattached)."""
        p, S = self.p, self.p.n_servers
        d = self._dec(s)
        st_i = onehot_row(d["state"], i)
        valid = (d["electionCtr"] < p.max_elections) & (
            (st_i == FOLLOWER) | (st_i == CANDIDATE) | (st_i == UNATTACHED)
        )
        new_epoch = onehot_row(d["currentEpoch"], i) + 1
        last_ep = self._last_epoch(d, i)
        ll_i = onehot_row(d["log_len"], i)
        hi, lo, cnt = d["msg_hi"], d["msg_lo"], d["msg_cnt"]
        ovf = jnp.asarray(False)
        for delta in range(1, S):
            j = jnp.mod(i + delta, S)
            khi, klo = self._pack(
                mtype=RVREQ,
                mepoch=new_epoch,
                mlastLogEpoch=last_ep,
                mlastLogOffset=ll_i,
                msource=i,
                mdest=j,
            )
            hi, lo, cnt, existed, o = bag.bag_put(hi, lo, cnt, khi, klo)
            valid &= ~existed  # SendMultipleOnce (KRaft.tla:199-201)
            ovf |= o
        succ = self._asm(
            d,
            state=onehot_set(d["state"], i, CANDIDATE),
            currentEpoch=onehot_set(d["currentEpoch"], i, new_epoch),
            leader=onehot_set(d["leader"], i, NIL),
            votedFor=onehot_set(d["votedFor"], i, i + 1),
            votesGranted=onehot_set(d["votesGranted"], i, jnp.int32(1) << i),
            electionCtr=d["electionCtr"] + 1,
            msg_hi=hi,
            msg_lo=lo,
            msg_cnt=cnt,
            **self._pf_cleared(d, i),
        )
        return valid, succ, jnp.int32(K_REQUESTVOTE), ovf & valid

    def _become_leader(self, s, i):
        """BecomeLeader(i) — KRaft.tla:546-558."""
        S = self.p.n_servers
        d = self._dec(s)
        votes = jnp.sum(
            (onehot_row(d["votesGranted"], i) >> jnp.arange(S, dtype=jnp.int32)) & 1)
        valid = (onehot_row(d["state"], i) == CANDIDATE) & (2 * votes > S)
        cur = onehot_row(d["currentEpoch"], i)
        hi, lo, cnt = d["msg_hi"], d["msg_lo"], d["msg_cnt"]
        ovf = jnp.asarray(False)
        for delta in range(1, S):
            j = jnp.mod(i + delta, S)
            khi, klo = self._pack(mtype=BQREQ, mepoch=cur, msource=i, mdest=j)
            hi, lo, cnt, existed, o = bag.bag_put(hi, lo, cnt, khi, klo)
            valid &= ~existed  # SendMultipleOnce
            ovf |= o
        succ = self._asm(
            d,
            state=onehot_set(d["state"], i, LEADER),
            leader=onehot_set(d["leader"], i, i + 1),
            endOffset=onehot_set(d["endOffset"], i, jnp.zeros((S,), jnp.int32)),
            msg_hi=hi,
            msg_lo=lo,
            msg_cnt=cnt,
        )
        return valid, succ, jnp.int32(K_BECOMELEADER), ovf & valid

    def _client_request(self, s, i, v):
        """ClientRequest(i, v) — KRaft.tla:594-603."""
        L = self.p.max_log
        d = self._dec(s)
        valid = (onehot_row(d["state"], i) == LEADER) & (
            onehot_row(d["acked"], v) == ACK_NIL)
        pos = onehot_row(d["log_len"], i)
        ovf = valid & (pos >= L)
        posc = jnp.clip(pos, 0, L - 1)
        succ = self._asm(
            d,
            log_epoch=onehot_set2(
                d["log_epoch"], i, posc, onehot_row(d["currentEpoch"], i)),
            log_value=onehot_set2(d["log_value"], i, posc, v + 1),
            log_len=onehot_add(d["log_len"], i, 1),
            acked=onehot_set(d["acked"], v, ACK_FALSE),
        )
        return valid, succ, jnp.int32(K_CLIENTREQUEST), ovf

    def _send_fetch_request(self, s, i, j):
        """SendFetchRequest(i, j) — KRaft.tla:607-624. FetchRequest is an
        unrestricted send (KRaft.tla:190-194); the pendingFetch[i] = Nil
        gate provides the flow control."""
        d = self._dec(s)
        valid = (
            (onehot_row(d["state"], i) == FOLLOWER)
            & (onehot_row(d["leader"], i) == j + 1)
            & (onehot_row(d["pf_epoch"], i) == 0)
        )
        cur = onehot_row(d["currentEpoch"], i)
        ll_i = onehot_row(d["log_len"], i)
        last_ep = self._last_epoch(d, i)
        khi, klo = self._pack(
            mtype=FETCHREQ,
            mepoch=cur,
            mfetchOffset=ll_i,
            mlastFetchedEpoch=last_ep,
            msource=i,
            mdest=j,
        )
        hi, lo, cnt, _existed, ovf = bag.bag_put(
            d["msg_hi"], d["msg_lo"], d["msg_cnt"], khi, klo
        )
        succ = self._asm(
            d,
            pf_epoch=onehot_set(d["pf_epoch"], i, cur),
            pf_offset=onehot_set(d["pf_offset"], i, ll_i),
            pf_lastepoch=onehot_set(d["pf_lastepoch"], i, last_ep),
            pf_dest=onehot_set(d["pf_dest"], i, j + 1),
            msg_hi=hi,
            msg_lo=lo,
            msg_cnt=cnt,
        )
        return valid, succ, jnp.int32(K_SENDFETCH), ovf & valid

    # -------- fused message-receipt kernel (slot m) --------
    # The nine receipt disjuncts of Next (KRaft.tla:827-840) are mutually
    # exclusive for a fixed record (they partition on mtype, then on
    # error/validity/mresult), so one kernel per slot computes whichever
    # fires; `rank` reports which for trace labels. Being exclusive, the
    # five replying branches share ONE bag_put on the branch-selected
    # response and the successor assembles once, field by field (the
    # shape config_common.py's receipt kernel took in round 5).

    def _handle_message(self, s, m):
        p, packer = self.p, self.packer
        S, L = p.n_servers, p.max_log
        d = self._dec(s)
        hi, lo, cnt = d["msg_hi"], d["msg_lo"], d["msg_cnt"]
        khi, klo, kcnt = onehot_row(hi, m), onehot_row(lo, m), onehot_row(cnt, m)
        occupied = khi != EMPTY
        u = partial(packer.unpack, khi, klo)
        mtype, mepoch = u("mtype"), u("mepoch")
        src, dst = u("msource"), u("mdest")
        cur = onehot_row(d["currentEpoch"], dst)
        st_dst = onehot_row(d["state"], dst)
        led_dst = onehot_row(d["leader"], dst)
        hwm_dst = onehot_row(d["highWatermark"], dst)
        ll_dst = onehot_row(d["log_len"], dst)
        ep_row = onehot_row(d["log_epoch"], dst)
        val_row = onehot_row(d["log_value"], dst)
        recv = occupied & (kcnt > 0)  # ReceivableMessage (KRaft.tla:230-235)
        equal_epoch = mepoch == cur
        cnt_disc = bag.bag_discard_at(cnt, m)

        # --- HandleRequestVoteRequest (KRaft.tla:464-513)
        b_rvreq = recv & (mtype == RVREQ)
        rv_err = mepoch < cur  # FencedLeaderEpoch
        # state0 (KRaft.tla:472-474)
        s0_st = jnp.where(mepoch > cur, UNATTACHED, st_dst)
        s0_ep = jnp.where(mepoch > cur, mepoch, cur)
        s0_ld = jnp.where(mepoch > cur, NIL, led_dst)
        last_ep = self._last_epoch(d, dst)
        # logOk: CompareEntries(mllo, mlle, Len, LastEpoch) >= 0 (:475-478)
        log_ok = (u("mlastLogEpoch") > last_ep) | (
            (u("mlastLogEpoch") == last_ep) & (u("mlastLogOffset") >= ll_dst)
        )
        grant = (
            (s0_st == UNATTACHED)
            | ((s0_st == VOTED) & (onehot_row(d["votedFor"], dst) == src + 1))
        ) & log_ok
        # finalState: TransitionToVoted when grant from Unattached (:483-485);
        # the Unattached precondition makes the illegal arm unreachable.
        take_voted = grant & (s0_st == UNATTACHED)
        f_st = jnp.where(take_voted, VOTED, s0_st)
        f_ep = jnp.where(take_voted, mepoch, s0_ep)
        f_ld = jnp.where(take_voted, NIL, s0_ld)
        # error path replies with (cur, leader[i]); normal with (mepoch, final)
        rv_key = self._pack(
            mtype=RVRESP,
            mepoch=jnp.where(rv_err, cur, mepoch),
            mleader=jnp.where(rv_err, led_dst, f_ld),
            mvoteGranted=jnp.where(rv_err, 0, grant.astype(jnp.int32)),
            merror=jnp.where(rv_err, E_FENCED, E_NONE),
            msource=dst,
            mdest=src,
        )
        rv_ok = b_rvreq & ~rv_err
        # IF state # state' THEN reset pendingFetch (KRaft.tla:495-497)
        rv_pf_reset = rv_ok & (f_st != st_dst)

        # --- HandleRequestVoteResponse (KRaft.tla:519-541)
        mh_st, mh_ep, mh_ld, handled = self._maybe_handle_common(
            d, dst, u("mleader"), mepoch, u("merror")
        )
        b_rvresp = recv & (mtype == RVRESP) & (handled | (st_dst == CANDIDATE))
        rvresp_grant = b_rvresp & (u("mvoteGranted") > 0) & ~handled

        # --- HandleBeginQuorumRequest (KRaft.tla:563-590)
        b_bqreq = recv & (mtype == BQREQ)
        bq_err = mepoch < cur
        bt_st, bt_ep, bt_ld = self._maybe_transition(d, dst, src + 1, mepoch)
        bq_key = self._pack(
            mtype=BQRESP,
            mepoch=jnp.where(bq_err, cur, mepoch),
            msource=dst,
            mdest=src,
            merror=jnp.where(bq_err, E_FENCED, E_NONE),
        )
        bq_ok = b_bqreq & ~bq_err

        # --- FetchRequest branches (KRaft.tla:631-736)
        is_fetchreq = recv & (mtype == FETCHREQ)
        is_leader = st_dst == LEADER
        ferr = jnp.where(
            ~is_leader,
            E_NOTLEADER,
            jnp.where(
                mepoch < cur, E_FENCED, jnp.where(mepoch > cur, E_UNKNOWN, E_NONE)
            ),
        )
        foff = u("mfetchOffset")
        flep = u("mlastFetchedEpoch")
        valid_pos = self._valid_fetch_position(d, dst, foff, flep)
        eo_off, eo_ep = self._end_offset_for_epoch(d, dst, flep)
        # every FetchResponse embeds the request (KRaft.tla:649)
        corr_kw = dict(
            mtype=FETCHRESP,
            mleader=led_dst,
            mepoch=cur,
            msource=dst,
            mdest=src,
            cepoch=mepoch,
            cfetchOffset=foff,
            clastFetchedEpoch=flep,
        )

        # RejectFetchRequest (KRaft.tla:631-651)
        b_reject = is_fetchreq & (ferr != E_NONE)
        rj_key = self._pack(
            mresult=R_NOTOK, merror=ferr, mhwm=hwm_dst, **corr_kw)

        # DivergingFetchRequest (KRaft.tla:658-679)
        b_div = is_fetchreq & equal_epoch & is_leader & ~valid_pos
        dv_key = self._pack(
            mresult=R_DIVERGING,
            merror=E_NONE,
            mdivergingEpoch=eo_ep,
            mdivergingEndOffset=eo_off,
            mhwm=hwm_dst,
            **corr_kw,
        )

        # AcceptFetchRequest (KRaft.tla:703-736)
        b_accept = is_fetchreq & equal_epoch & is_leader & valid_pos
        offset = foff + 1
        have_entry = offset <= ll_dst
        epos = jnp.clip(offset - 1, 0, L - 1)
        ent_ep = jnp.where(have_entry, onehot_row(ep_row, epos), 0)
        ent_v = jnp.where(have_entry, onehot_row(val_row, epos), 0)
        new_end = onehot_set(onehot_row(d["endOffset"], dst), src, foff)
        # NewHighwaterMark (KRaft.tla:689-701)
        idxs = jnp.arange(1, L + 1, dtype=jnp.int32)
        self_in = jnp.arange(S, dtype=jnp.int32)[None, :] == dst
        agree = self_in | (new_end[None, :] >= idxs[:, None])
        quorum_ok = 2 * jnp.sum(agree, axis=1) > S
        in_log = idxs <= ll_dst
        max_agree = jnp.max(jnp.where(quorum_ok & in_log, idxs, 0))
        ep_at = onehot_row(ep_row, jnp.clip(max_agree - 1, 0))
        new_hwm = jnp.where(
            (max_agree > 0) & (ep_at == cur), max_agree, hwm_dst
        )
        # acked: FALSE -> committed in (hwm_old, new_hwm] (KRaft.tla:721-724)
        lanes = jnp.arange(L, dtype=jnp.int32)
        in_range = (lanes + 1 > hwm_dst) & (lanes + 1 <= new_hwm)
        committed = jnp.any(
            in_range[None, :]
            & (val_row[None, :] == jnp.arange(1, p.n_values + 1, dtype=jnp.int32)[:, None]),
            axis=1,
        )
        acked = jnp.where(
            (d["acked"] == ACK_FALSE) & committed, ACK_TRUE, d["acked"]
        )
        ac_key = self._pack(
            mresult=R_OK,
            merror=E_NONE,
            nentries=have_entry.astype(jnp.int32),
            eepoch=ent_ep,
            evalue=ent_v,
            mhwm=jnp.minimum(new_hwm, offset),
            **corr_kw,
        )

        # Reply — KRaft.tla:220-227: discard the request, send the
        # branch's response; a FetchResponse may not be duplicated
        # (:224-227), which disables the three fetch branches on `existed`
        is_fresp_send = b_reject | b_div | b_accept
        replying = b_rvreq | b_bqreq | is_fresp_send
        resp_hi, resp_lo = rv_key
        for b, (k_hi, k_lo) in (
            (b_bqreq, bq_key), (b_reject, rj_key), (b_div, dv_key),
            (b_accept, ac_key),
        ):
            resp_hi = jnp.where(b, k_hi, resp_hi)
            resp_lo = jnp.where(b, k_lo, resp_lo)
        hi_r, lo_r, cnt_r, existed, put_ovf = bag.bag_put(
            hi, lo, cnt_disc, resp_hi, resp_lo)
        b_reject &= ~existed
        b_div &= ~existed
        b_accept &= ~existed

        # --- FetchResponse branches (KRaft.tla:742-801)
        is_fresp = recv & (mtype == FETCHRESP)
        # correlation match: pendingFetch[dst] = m.correlation (:749); the
        # request's msource is dst (implied) and mdest is the responder src.
        pf_ep_dst = onehot_row(d["pf_epoch"], dst)
        corr = (
            (pf_ep_dst > 0)
            & (pf_ep_dst == u("cepoch"))
            & (onehot_row(d["pf_offset"], dst) == u("cfetchOffset"))
            & (onehot_row(d["pf_lastepoch"], dst) == u("clastFetchedEpoch"))
            & (onehot_row(d["pf_dest"], dst) == src + 1)
        )
        mres = u("mresult")

        # HandleSuccessFetchResponse (KRaft.tla:742-757)
        b_ok = is_fresp & ~handled & corr & (mres == R_OK)
        app = b_ok & (u("nentries") > 0)
        apos = jnp.clip(ll_dst, 0, L - 1)
        ok_ovf = app & (ll_dst >= L)

        # HandleDivergingFetchResponse (KRaft.tla:766-780)
        b_divr = is_fresp & ~handled & corr & (mres == R_DIVERGING)
        hco = self._highest_common_offset(
            d, dst, u("mdivergingEndOffset"), u("mdivergingEpoch")
        )
        keep = jnp.arange(L, dtype=jnp.int32) < hco

        # HandleErrorFetchResponse (KRaft.tla:786-801)
        b_err = is_fresp & handled & corr

        # ---- the successor, field by field ----
        # (state, currentEpoch, leader) of dst: the vote request's final
        # state, the transition machine's on a handled response or error,
        # MaybeTransition's on BeginQuorum
        common = (b_rvresp & handled) | b_err
        upd = {}
        for name, rv_v, mh_v, bt_v in (
            ("state", f_st, mh_st, bt_st),
            ("currentEpoch", f_ep, mh_ep, bt_ep),
            ("leader", f_ld, mh_ld, bt_ld),
        ):
            v = jnp.where(rv_ok, rv_v, jnp.where(common, mh_v, bt_v))
            upd[name] = jnp.where(
                rv_ok | common | bq_ok, onehot_set(d[name], dst, v), d[name])
        upd["votedFor"] = jnp.where(
            rv_ok & grant, onehot_set(d["votedFor"], dst, src + 1), d["votedFor"])
        upd["votesGranted"] = jnp.where(
            rvresp_grant,
            onehot_set(
                d["votesGranted"], dst,
                onehot_row(d["votesGranted"], dst) | (jnp.int32(1) << src)),
            d["votesGranted"],
        )
        upd.update(self._pf_cleared(
            d, dst, rv_pf_reset | bq_ok | b_ok | b_divr | b_err))
        upd["endOffset"] = jnp.where(
            b_accept, onehot_set(d["endOffset"], dst, new_end), d["endOffset"])
        upd["highWatermark"] = jnp.where(
            b_accept | b_ok,
            onehot_set(
                d["highWatermark"], dst, jnp.where(b_ok, u("mhwm"), new_hwm)),
            d["highWatermark"],
        )
        upd["acked"] = jnp.where(b_accept, acked, d["acked"])
        upd["log_epoch"] = jnp.where(
            app,
            onehot_set2(d["log_epoch"], dst, apos, u("eepoch")),
            jnp.where(
                b_divr,
                onehot_set(d["log_epoch"], dst, jnp.where(keep, ep_row, 0)),
                d["log_epoch"]),
        )
        upd["log_value"] = jnp.where(
            app,
            onehot_set2(d["log_value"], dst, apos, u("evalue")),
            jnp.where(
                b_divr,
                onehot_set(d["log_value"], dst, jnp.where(keep, val_row, 0)),
                d["log_value"]),
        )
        upd["log_len"] = jnp.where(
            app,
            onehot_add(d["log_len"], dst, 1),
            jnp.where(b_divr, onehot_set(d["log_len"], dst, hco), d["log_len"]),
        )
        upd["msg_hi"] = jnp.where(replying, hi_r, hi)
        upd["msg_lo"] = jnp.where(replying, lo_r, lo)
        upd["msg_cnt"] = jnp.where(replying, cnt_r, cnt_disc)
        succ = self._asm(d, **upd)

        valid = jnp.asarray(False)
        rank = jnp.int32(-1)
        for b, rk in (
            (b_rvreq, K_HANDLE_RVREQ),
            (b_rvresp, K_HANDLE_RVRESP),
            (b_reject, K_REJECT_FETCH),
            (b_div, K_DIVERGING_FETCH),
            (b_accept, K_ACCEPT_FETCH),
            (b_bqreq, K_HANDLE_BQREQ),
            (b_ok, K_HANDLE_FETCH_OK),
            (b_divr, K_HANDLE_FETCH_DIV),
            (b_err, K_HANDLE_FETCH_ERR),
        ):
            valid = valid | b
            rank = jnp.where(b, jnp.int32(rk), rank)
        ovf = (
            (b_rvreq | b_bqreq | b_reject | b_div | b_accept) & put_ovf
        ) | ok_ovf
        succ = jnp.where(valid, succ, s)
        return valid, succ, rank, ovf

    # ---------------- full expansion ----------------

    def _expand1(self, s):
        """All successor candidates of one state.

        Returns (succs [A, W], valid [A], rank [A], ovf [A])."""
        p = self.p
        S, V, M = p.n_servers, p.n_values, p.msg_slots
        iota_s = jnp.arange(S, dtype=jnp.int32)
        pr_i = jnp.asarray([ij[0] for ij in self._pairs], jnp.int32)
        pr_j = jnp.asarray([ij[1] for ij in self._pairs], jnp.int32)
        outs = []
        outs.append(jax.vmap(lambda i: self._restart(s, i))(iota_s))
        outs.append(jax.vmap(lambda i: self._request_vote(s, i))(iota_s))
        outs.append(jax.vmap(lambda i: self._become_leader(s, i))(iota_s))
        cr_i = jnp.repeat(iota_s, V)
        cr_v = jnp.tile(jnp.arange(V, dtype=jnp.int32), S)
        outs.append(jax.vmap(lambda i, v: self._client_request(s, i, v))(cr_i, cr_v))
        outs.append(
            jax.vmap(lambda i, j: self._send_fetch_request(s, i, j))(pr_i, pr_j)
        )
        outs.append(
            jax.vmap(lambda m: self._handle_message(s, m))(jnp.arange(M, dtype=jnp.int32))
        )
        valid = jnp.concatenate([o[0] for o in outs])
        succs = jnp.concatenate([o[1] for o in outs])
        rank = jnp.concatenate([o[2] for o in outs])
        ovf = jnp.concatenate([o[3] for o in outs])
        return succs, valid, rank, ovf

    # ---------------- initial states ----------------

    def init_states(self) -> np.ndarray:
        """Init — KRaft.tla:397-415. A single state; all Unattached."""
        vec = self.layout.zeros((1,))
        lay = self.layout
        vec[0, lay.sl("currentEpoch")] = 1
        vec[0, lay.sl("state")] = UNATTACHED
        vec[0, lay.sl("msg_hi")] = int(EMPTY)
        vec[0, lay.sl("msg_lo")] = int(EMPTY)
        vec[0, lay.sl("acked")] = ACK_NIL
        return vec

    # ---------------- invariants ----------------

    def _live_value_all_or_nothing(self, v, states):
        """ValueAllOrNothing(v) — KRaft.tla:867-875: TRUE when the last
        permissible election failed with no leader, else v must be on
        EVERY server log or on NONE."""
        lay, L = self.layout, self.p.max_log
        ec = lay.get(states, "electionCtr")
        st = lay.get(states, "state")
        lv = lay.get(states, "log_value")
        ll = lay.get(states, "log_len")
        lanes = jnp.arange(L, dtype=jnp.int32)
        in_log = lanes[None, None, :] < ll[..., None]
        has_v = jnp.any(in_log & (lv == v + 1), axis=2)
        all_have = jnp.all(has_v, axis=1)
        none_have = ~jnp.any(has_v, axis=1)
        no_leader = ~jnp.any(st == LEADER, axis=1)
        spent = ec == self.p.max_elections
        return (spent & no_leader) | all_have | none_have

    def _inv_no_illegal(self, states):
        """NoIllegalState — KRaft.tla:887-889."""
        st = self.layout.get(states, "state")
        return jnp.all(st != ILLEGAL, axis=1)

    def _inv_no_log_divergence(self, states):
        """NoLogDivergence — KRaft.tla:894-907 (common prefix up to the
        pairwise-minimum highWatermark)."""
        lay, L = self.layout, self.p.max_log
        hwm = lay.get(states, "highWatermark")
        lt = lay.get(states, "log_epoch")
        lv = lay.get(states, "log_value")
        mh = jnp.minimum(hwm[:, :, None], hwm[:, None, :])
        lanes = jnp.arange(1, L + 1, dtype=jnp.int32)
        in_common = lanes[None, None, None, :] <= mh[..., None]
        eq = (lt[:, :, None, :] == lt[:, None, :, :]) & (
            lv[:, :, None, :] == lv[:, None, :, :]
        )
        return jnp.all(~in_common | eq, axis=(1, 2, 3))

    def _inv_never_two_leaders(self, states):
        """NeverTwoLeadersInSameEpoch — KRaft.tla:916-921."""
        lay = self.layout
        led = lay.get(states, "leader")
        ep = lay.get(states, "currentEpoch")
        both = (led[:, :, None] != NIL) & (led[:, None, :] != NIL)
        conflict = (
            both
            & (led[:, :, None] != led[:, None, :])
            & (ep[:, :, None] == ep[:, None, :])
        )
        return ~jnp.any(conflict, axis=(1, 2))

    def _inv_leader_has_acked(self, states):
        """LeaderHasAllAckedValues — KRaft.tla:925-941."""
        lay, V = self.layout, self.p.n_values
        ep = lay.get(states, "currentEpoch")
        st = lay.get(states, "state")
        lv = lay.get(states, "log_value")
        acked = lay.get(states, "acked")
        not_stale = jnp.all(ep[:, :, None] >= ep[:, None, :], axis=2)
        is_lead = (st == LEADER) & not_stale
        vals = jnp.arange(1, V + 1, dtype=jnp.int32)
        has_v = jnp.any(lv[:, :, None, :] == vals[None, None, :, None], axis=3)
        bad = jnp.any(
            (acked[:, None, :] == ACK_TRUE) & is_lead[:, :, None] & ~has_v,
            axis=(1, 2),
        )
        return ~bad

    def _inv_committed_majority(self, states):
        """CommittedEntriesReachMajority — KRaft.tla:946-957."""
        lay, S, L = self.layout, self.p.n_servers, self.p.max_log
        st = lay.get(states, "state")
        hwm = lay.get(states, "highWatermark")
        ll = lay.get(states, "log_len")
        lt = lay.get(states, "log_epoch")
        lv = lay.get(states, "log_value")
        lead = (st == LEADER) & (hwm > 0)
        pos = jnp.clip(hwm - 1, 0, L - 1)
        lt_i = jnp.take_along_axis(lt, pos[:, :, None], axis=2)[:, :, 0]
        lv_i = jnp.take_along_axis(lv, pos[:, :, None], axis=2)[:, :, 0]
        posj = jnp.broadcast_to(pos[:, :, None], pos.shape + (S,))
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
        match = (
            (ll[:, None, :] >= hwm[:, :, None])
            & (lt_j == lt_i[..., None])
            & (lv_j == lv_i[..., None])
        )
        enough = jnp.sum(match, axis=2) >= (S // 2 + 1)
        ok_exists = jnp.any(lead & enough, axis=1)
        return ~jnp.any(lead, axis=1) | ok_exists

    # ---------------- host-side decode/encode ----------------

    def decode(self, vec: np.ndarray) -> dict:
        """Decode one packed state into the canonical python form shared
        with oracle/kraft_oracle.py."""
        lay, p = self.layout, self.p
        g = lambda n: np.asarray(vec[lay.sl(n)])
        S, L = p.n_servers, p.max_log
        lt = g("log_epoch").reshape(S, L)
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
        pf_ep, pf_off = g("pf_epoch"), g("pf_offset")
        pf_le, pf_d = g("pf_lastepoch"), g("pf_dest")
        pending = []
        for i in range(S):
            if int(pf_ep[i]) == 0:
                pending.append(None)
            else:
                pending.append(
                    tuple(
                        sorted(
                            {
                                "mtype": "FetchRequest",
                                "mepoch": int(pf_ep[i]),
                                "mfetchOffset": int(pf_off[i]),
                                "mlastFetchedEpoch": int(pf_le[i]),
                                "msource": i,
                                "mdest": int(pf_d[i]) - 1,
                            }.items()
                        )
                    )
                )
        msgs = {}
        hi, lo, cnt = g("msg_hi"), g("msg_lo"), g("msg_cnt")
        for k in range(p.msg_slots):
            if int(hi[k]) == int(EMPTY):
                continue
            msgs[self.decode_msg(int(hi[k]), int(lo[k]))] = int(cnt[k])
        return {
            "currentEpoch": tuple(int(x) for x in g("currentEpoch")),
            "state": tuple(int(x) for x in g("state")),
            "votedFor": tuple(int(x) - 1 if x > 0 else None for x in g("votedFor")),
            "leader": tuple(int(x) - 1 if x > 0 else None for x in g("leader")),
            "pendingFetch": tuple(pending),
            "votesGranted": votes,
            "endOffset": tuple(
                tuple(int(x) for x in row) for row in g("endOffset").reshape(S, S)
            ),
            "log": log,
            "highWatermark": tuple(int(x) for x in g("highWatermark")),
            "messages": frozenset(msgs.items()),
            "acked": tuple(
                {ACK_NIL: None, ACK_FALSE: False, ACK_TRUE: True}[int(x)]
                for x in g("acked")
            ),
            "electionCtr": int(vec[lay.fields["electionCtr"].offset]),
            "restartCtr": int(vec[lay.fields["restartCtr"].offset]),
        }

    def decode_msg(self, hi: int, lo: int) -> tuple:
        u = self.packer.unpack_all(hi, lo)
        mtype = int(u["mtype"])
        rec = {
            "mtype": MTYPE_NAMES[mtype],
            "mepoch": int(u["mepoch"]),
            "msource": int(u["msource"]),
            "mdest": int(u["mdest"]),
        }
        if mtype == RVREQ:
            rec["mlastLogEpoch"] = int(u["mlastLogEpoch"])
            rec["mlastLogOffset"] = int(u["mlastLogOffset"])
        elif mtype == RVRESP:
            rec["mleader"] = int(u["mleader"]) - 1 if u["mleader"] else None
            rec["mvoteGranted"] = bool(u["mvoteGranted"])
            rec["merror"] = ERROR_NAMES[int(u["merror"])]
        elif mtype == BQRESP:
            rec["merror"] = ERROR_NAMES[int(u["merror"])]
        elif mtype == FETCHREQ:
            rec["mfetchOffset"] = int(u["mfetchOffset"])
            rec["mlastFetchedEpoch"] = int(u["mlastFetchedEpoch"])
        elif mtype == FETCHRESP:
            res = int(u["mresult"])
            rec["mresult"] = RESULT_NAMES[res]
            rec["merror"] = ERROR_NAMES[int(u["merror"])]
            rec["mleader"] = int(u["mleader"]) - 1 if u["mleader"] else None
            rec["mhwm"] = int(u["mhwm"])
            if res == R_OK:
                rec["mentries"] = (
                    ((int(u["eepoch"]), int(u["evalue"]) - 1),)
                    if u["nentries"]
                    else ()
                )
            if res == R_DIVERGING:
                rec["mdivergingEpoch"] = int(u["mdivergingEpoch"])
                rec["mdivergingEndOffset"] = int(u["mdivergingEndOffset"])
            rec["correlation"] = tuple(
                sorted(
                    {
                        "mtype": "FetchRequest",
                        "mepoch": int(u["cepoch"]),
                        "mfetchOffset": int(u["cfetchOffset"]),
                        "mlastFetchedEpoch": int(u["clastFetchedEpoch"]),
                        "msource": int(u["mdest"]),
                        "mdest": int(u["msource"]),
                    }.items()
                )
            )
        return tuple(sorted(rec.items()))

    def encode_msg(self, rec: tuple) -> tuple[int, int]:
        d = dict(rec)
        inv_err = {v: k for k, v in ERROR_NAMES.items()}
        inv_res = {v: k for k, v in RESULT_NAMES.items()}
        mtype = {v: k for k, v in MTYPE_NAMES.items()}[d["mtype"]]
        kw = dict(
            mtype=mtype, mepoch=d["mepoch"], msource=d["msource"], mdest=d["mdest"]
        )
        if mtype == RVREQ:
            kw.update(
                mlastLogEpoch=d["mlastLogEpoch"], mlastLogOffset=d["mlastLogOffset"]
            )
        elif mtype == RVRESP:
            kw.update(
                mleader=0 if d["mleader"] is None else d["mleader"] + 1,
                mvoteGranted=int(d["mvoteGranted"]),
                merror=inv_err[d["merror"]],
            )
        elif mtype == BQRESP:
            kw.update(merror=inv_err[d["merror"]])
        elif mtype == FETCHREQ:
            kw.update(
                mfetchOffset=d["mfetchOffset"],
                mlastFetchedEpoch=d["mlastFetchedEpoch"],
            )
        elif mtype == FETCHRESP:
            corr = dict(d["correlation"])
            kw.update(
                mresult=inv_res[d["mresult"]],
                merror=inv_err[d["merror"]],
                mleader=0 if d["mleader"] is None else d["mleader"] + 1,
                mhwm=d["mhwm"],
                cepoch=corr["mepoch"],
                cfetchOffset=corr["mfetchOffset"],
                clastFetchedEpoch=corr["mlastFetchedEpoch"],
            )
            if d["mresult"] == "Ok":
                ent = d["mentries"]
                kw.update(
                    nentries=len(ent),
                    eepoch=ent[0][0] if ent else 0,
                    evalue=ent[0][1] + 1 if ent else 0,
                )
            if d["mresult"] == "Diverging":
                kw.update(
                    mdivergingEpoch=d["mdivergingEpoch"],
                    mdivergingEndOffset=d["mdivergingEndOffset"],
                )
        return self.packer.pack(**kw)

    def encode(self, st: dict) -> np.ndarray:
        lay, p = self.layout, self.p
        S, L = p.n_servers, p.max_log
        vec = lay.zeros(())
        vec[lay.sl("currentEpoch")] = st["currentEpoch"]
        vec[lay.sl("state")] = st["state"]
        vec[lay.sl("votedFor")] = [0 if v is None else v + 1 for v in st["votedFor"]]
        vec[lay.sl("leader")] = [0 if v is None else v + 1 for v in st["leader"]]
        pf_ep = [0] * S
        pf_off = [0] * S
        pf_le = [0] * S
        pf_d = [0] * S
        for i, pf in enumerate(st["pendingFetch"]):
            if pf is None:
                continue
            c = dict(pf)
            pf_ep[i] = c["mepoch"]
            pf_off[i] = c["mfetchOffset"]
            pf_le[i] = c["mlastFetchedEpoch"]
            pf_d[i] = c["mdest"] + 1
        vec[lay.sl("pf_epoch")] = pf_ep
        vec[lay.sl("pf_offset")] = pf_off
        vec[lay.sl("pf_lastepoch")] = pf_le
        vec[lay.sl("pf_dest")] = pf_d
        lt = np.zeros((S, L), np.int32)
        lv = np.zeros((S, L), np.int32)
        for i, lg in enumerate(st["log"]):
            for k, (t, v) in enumerate(lg):
                lt[i, k] = t
                lv[i, k] = v + 1
        vec[lay.sl("log_epoch")] = lt.reshape(-1)
        vec[lay.sl("log_value")] = lv.reshape(-1)
        vec[lay.sl("log_len")] = [len(lg) for lg in st["log"]]
        vec[lay.sl("highWatermark")] = st["highWatermark"]
        vec[lay.sl("votesGranted")] = [
            sum(1 << j for j in vs) for vs in st["votesGranted"]
        ]
        vec[lay.sl("endOffset")] = np.asarray(st["endOffset"]).reshape(-1)
        vec[lay.sl("acked")] = [
            {None: ACK_NIL, False: ACK_FALSE, True: ACK_TRUE}[a] for a in st["acked"]
        ]
        keys = sorted((self.encode_msg(rec), cnt) for rec, cnt in st["messages"])
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
        vec[lay.fields["electionCtr"].offset] = st["electionCtr"]
        vec[lay.fields["restartCtr"].offset] = st["restartCtr"]
        return vec


@lru_cache(maxsize=None)
def _cached_model(params: KRaftParams) -> "KRaftModel":
    return KRaftModel(params)
