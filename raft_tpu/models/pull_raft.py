"""TPU lowering of the PullRaft / PullRaftVariant2 specs.

Reference: ``/root/reference/specifications/pull-raft/PullRaft.tla`` (631
lines) and ``PullRaftVariant2.tla`` (648 lines). Same lowering discipline as
models/raft.py: branchless ``vmap``-able action kernels over a packed int32
state vector, enabling conditions as masks, ``CHOOSE`` sites (Min/Max,
``PullRaft.tla:175-177``; ``LastCommonEntry``, ``:211-226``) as lane
reductions.

Variant-defining structure (see oracle/pull_oracle.py for the full delta
list): pull-based replication, ``leader`` belief var, strictly send-once
messaging for ALL messages (``PullRaft.tla:137-161``), and — in Variant2 —
``votedFor`` + ``votesLastEntry`` with last-common-entry piggybacking on
the LeaderNotify (``PullRaftVariant2.tla:361-379``).

Bound note: unlike core Raft, a follower's log can transiently exceed
|Value| entries (stale success PullEntriesResponses with distinct
``mcommitIndex`` each append; ``PullRaft.tla:493-503`` appends
unconditionally), so ``max_log`` is a parameter with headroom above
|Value| and overflow is a hard error, never silent.
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

FOLLOWER, CANDIDATE, LEADER = 0, 1, 2
NIL = 0  # leader/votedFor Nil; server i stored as i+1
ACK_NIL, ACK_FALSE, ACK_TRUE = 0, 1, 2
RVREQ, RVRESP, PULLREQ, PULLRESP, NOTIFY = 1, 2, 3, 4, 5

# Next-disjunct order (PullRaft.tla:542-558 == PullRaftVariant2.tla:560-576).
(
    R_RESTART,
    R_UPDATETERM,
    R_REQUESTVOTE,
    R_HANDLE_RVREQ,
    R_HANDLE_RVRESP,
    R_BECOMELEADER,
    R_CLIENTREQUEST,
    R_REJECT_PULL,
    R_ACCEPT_PULL,
    R_LEARNOFLEADER,
    R_SENDPULL,
    R_HANDLE_SUCCESS_PULL,
    R_HANDLE_FAIL_PULL,
) = range(13)

ACTION_NAMES = [
    "Restart",
    "UpdateTerm",
    "RequestVote",
    "HandleRequestVoteRequest",
    "HandleRequestVoteResponse",
    "BecomeLeader",
    "ClientRequest",
    "RejectPullEntriesRequest",
    "AcceptPullEntriesRequest",
    "LearnOfLeader",
    "SendPullEntriesRequest",
    "HandleSuccessPullEntriesResponse",
    "HandleFailPullEntriesResponse",
]

STATE_NAMES = {FOLLOWER: "Follower", CANDIDATE: "Candidate", LEADER: "Leader"}
MTYPE_NAMES = {
    RVREQ: "RequestVoteRequest",
    RVRESP: "RequestVoteResponse",
    PULLREQ: "PullEntriesRequest",
    PULLRESP: "PullEntriesResponse",
    NOTIFY: "LeaderNotifyRequest",
}


@dataclass(frozen=True)
class PullRaftParams:
    n_servers: int
    n_values: int
    max_elections: int
    max_restarts: int
    msg_slots: int = 64
    variant2: bool = False
    # headroom above |Value| for stale-response appends (see module note);
    # 0 means auto (n_values + 4). Overflow is a hard error either way.
    max_log_override: int = 0
    # Fleet packing (models/base.py FleetConstMixin), same contract as
    # RaftParams: guards for dyn_consts read per-state lanes.
    dyn_consts: tuple = ()
    fleet: bool = False

    @property
    def max_term(self) -> int:
        return 1 + self.max_elections

    @property
    def max_log(self) -> int:
        if self.max_log_override:
            return self.max_log_override
        return self.n_values + 4


def _build_layout(p: PullRaftParams) -> Layout:
    S, V, L, M = p.n_servers, p.n_values, p.max_log, p.msg_slots
    lay = Layout(S)
    # VIEW (PullRaft.tla:123: messages, serverVars, candidateVars,
    # leaderVars, logVars, acked; Variant2.tla:114 drops acked).
    lay.add("currentTerm", "per_server", (S,))
    lay.add("state", "per_server", (S,))
    lay.add("leader", "per_server_val", (S,))
    if p.variant2:
        lay.add("votedFor", "per_server_val", (S,))
        lay.add("vle_has", "per_server_pair", (S, S))  # votesLastEntry # Nil
        lay.add("vle_idx", "per_server_pair", (S, S))
        lay.add("vle_term", "per_server_pair", (S, S))
    lay.add("votesGranted", "server_bitmask", (S,))
    lay.add("log_term", "per_server", (S, L))
    lay.add("log_value", "per_server", (S, L))
    lay.add("log_len", "per_server", (S,))
    lay.add("commitIndex", "per_server", (S,))
    lay.add("matchIndex", "per_server_pair", (S, S))
    lay.add("msg_hi", "msg_hi", (M,))
    lay.add("msg_lo", "msg_lo", (M,))
    lay.add("msg_cnt", "msg_cnt", (M,))
    if p.fleet:
        # Fleet config axis (models/base.py FleetConstMixin): VIEW
        # scalars, before the first aux field in either variant.
        lay.add("fleet_job", "scalar")
        for nm in p.dyn_consts:
            lay.add("c_" + nm, "scalar")
    # acked is IN the view for PullRaft (PullRaft.tla:123) but aux for
    # Variant2 (PullRaftVariant2.tla:114)
    lay.add("acked", "aux" if p.variant2 else "scalar", (V,))
    lay.add("electionCtr", "aux")
    lay.add("restartCtr", "aux")
    return lay.finish()


def _build_packer(p: PullRaftParams) -> BitPacker:
    tb = bits_for(p.max_term)
    sb = bits_for(p.n_servers - 1)
    lb = bits_for(p.max_log + 1)
    vb = bits_for(p.n_values)
    return BitPacker(
        [
            ("mtype", 3),
            ("mterm", tb),
            ("msource", sb),
            ("mdest", sb),
            ("mlastLogTerm", tb),  # RVReq/PullReq (+V2 RVResp)
            ("mlastLogIndex", lb),
            ("mvoteGranted", 1),
            ("msuccess", 1),
            ("nentries", 1),  # success PullResp carries exactly 1 entry
            ("eterm", tb),
            ("evalue", vb),
            ("mcommitIndex", lb),
            ("mlcHas", 1),  # mlastCommonEntry # Nil (V2 notify; fail resp)
            ("mlcIndex", lb),
            ("mlcTerm", tb),
        ]
    )


class PullRaftModel(SparseExpandMixin, FleetConstMixin, ActionLabelMixin):
    """Vectorized successor/invariant kernels for one (spec, constants)."""

    name = "PullRaft"
    ACTION_NAMES = ACTION_NAMES

    def __init__(self, params: PullRaftParams, server_names=None, value_names=None):
        self.p = params
        self.layout = _build_layout(params)
        self.packer = _build_packer(params)
        S, V, M = params.n_servers, params.n_values, params.msg_slots
        self.server_names = list(server_names or [f"s{i+1}" for i in range(S)])
        self.value_names = list(value_names or [f"v{i+1}" for i in range(V)])
        if params.variant2:
            self.name = "PullRaftVariant2"

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
            self.bindings.append(("SendPullEntriesRequest", ij))
        for m in range(M):
            self.bindings.append(("HandleMessage", (m,)))
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

    # ---------------- helpers ----------------

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
    def _last_term(d, i):
        """LastTerm(log[i]) — PullRaft.tla:134."""
        ll = onehot_row(d["log_len"], i)
        row = onehot_row(d["log_term"], i)
        return jnp.where(ll > 0, onehot_row(row, jnp.clip(ll - 1, 0)), 0)

    def _last_common(self, lt_row, ll, last_idx, last_term):
        """LastCommonEntry — PullRaft.tla:211-226. Highest index k in
        1..ll with entry (k, term[k]) <= (last_idx, last_term) under
        CompareEntries' term-precedence order (:203-207); (0,0) if none.
        The CHOOSE is deterministic: max satisfying index."""
        L = self.p.max_log
        lanes = jnp.arange(1, L + 1, dtype=jnp.int32)
        ok = (lanes <= ll) & (
            (lt_row < last_term) | ((lt_row == last_term) & (lanes <= last_idx))
        )
        idx = jnp.max(jnp.where(ok, lanes, 0))
        term = jnp.where(
            idx > 0, onehot_row(lt_row, jnp.clip(idx - 1, 0, L - 1)), 0)
        return idx, term

    # ---------------- action kernels ----------------

    def _restart(self, s, i):
        """Restart(i) — PullRaft.tla:258-265 (keeps currentTerm, leader,
        log); Variant2 (PullRaftVariant2.tla:251-260) keeps votedFor but
        clears leader and votesLastEntry."""
        p, S = self.p, self.p.n_servers
        d = self._dec(s)
        valid = d["restartCtr"] < self._cv(d, "max_restarts")
        zeros = jnp.zeros((S,), jnp.int32)
        upd = dict(
            state=onehot_set(d["state"], i, FOLLOWER),
            votesGranted=onehot_set(d["votesGranted"], i, 0),
            matchIndex=onehot_set(d["matchIndex"], i, zeros),
            commitIndex=onehot_set(d["commitIndex"], i, 0),
            restartCtr=d["restartCtr"] + 1,
        )
        if p.variant2:
            upd["leader"] = onehot_set(d["leader"], i, NIL)
            for f in ("vle_has", "vle_idx", "vle_term"):
                upd[f] = onehot_set(d[f], i, zeros)
        succ = self._asm(d, **upd)
        return valid, succ, jnp.int32(R_RESTART), jnp.asarray(False)

    def _request_vote(self, s, i):
        """RequestVote(i) — PullRaft.tla:283-298 (leader[i] := i);
        Variant2 (PullRaftVariant2.tla:279-295): votedFor := i, leader := Nil."""
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
            valid &= ~existed  # SendMultiple (PullRaft.tla:141-143)
            ovf |= o
        upd = dict(
            state=onehot_set(d["state"], i, CANDIDATE),
            currentTerm=onehot_set(d["currentTerm"], i, new_term),
            votesGranted=onehot_set(d["votesGranted"], i, jnp.int32(1) << i),
            electionCtr=d["electionCtr"] + 1,
            msg_hi=hi,
            msg_lo=lo,
            msg_cnt=cnt,
        )
        if p.variant2:
            upd["votedFor"] = onehot_set(d["votedFor"], i, i + 1)
            upd["leader"] = onehot_set(d["leader"], i, NIL)
        else:
            upd["leader"] = onehot_set(d["leader"], i, i + 1)
        succ = self._asm(d, **upd)
        return valid, succ, jnp.int32(R_REQUESTVOTE), ovf & valid

    def _become_leader(self, s, i):
        """BecomeLeader(i) — PullRaft.tla:354-366: LeaderNotifyRequest to
        Server \\ votesGranted[i]; Variant2 (PullRaftVariant2.tla:361-379):
        notify ALL peers with embedded mlastCommonEntry, leader[i] := i."""
        p, S = self.p, self.p.n_servers
        d = self._dec(s)
        vg_i = onehot_row(d["votesGranted"], i)
        votes = jnp.sum((vg_i >> jnp.arange(S, dtype=jnp.int32)) & 1)
        valid = (onehot_row(d["state"], i) == CANDIDATE) & (2 * votes > S)
        ct_i = onehot_row(d["currentTerm"], i)
        hi, lo, cnt = d["msg_hi"], d["msg_lo"], d["msg_cnt"]
        ovf = jnp.asarray(False)
        for delta in range(1, S):
            j = jnp.mod(i + delta, S)
            if p.variant2:
                send_j = jnp.asarray(True)
                has = onehot_get2(d["vle_has"], i, j) > 0
                lce_i, lce_t = self._last_common(
                    onehot_row(d["log_term"], i),
                    onehot_row(d["log_len"], i),
                    onehot_get2(d["vle_idx"], i, j),
                    onehot_get2(d["vle_term"], i, j),
                )
                khi, klo = self._pack(
                    mtype=NOTIFY,
                    mterm=ct_i,
                    mlcHas=has.astype(jnp.int32),
                    mlcIndex=jnp.where(has, lce_i, 0),
                    mlcTerm=jnp.where(has, lce_t, 0),
                    msource=i,
                    mdest=j,
                )
            else:
                # only peers that did NOT vote for i (PullRaft.tla:364)
                send_j = ((vg_i >> j) & 1) == 0
                khi, klo = self._pack(
                    mtype=NOTIFY, mterm=ct_i, msource=i, mdest=j
                )
            nhi, nlo, ncnt, existed, o = bag.bag_put(hi, lo, cnt, khi, klo)
            valid &= ~(existed & send_j)
            ovf |= o & send_j
            hi = jnp.where(send_j, nhi, hi)
            lo = jnp.where(send_j, nlo, lo)
            cnt = jnp.where(send_j, ncnt, cnt)
        upd = dict(
            state=onehot_set(d["state"], i, LEADER),
            matchIndex=onehot_set(
                d["matchIndex"], i, jnp.zeros((S,), jnp.int32)),
            msg_hi=hi,
            msg_lo=lo,
            msg_cnt=cnt,
        )
        if p.variant2:
            upd["leader"] = onehot_set(d["leader"], i, i + 1)
        succ = self._asm(d, **upd)
        return valid, succ, jnp.int32(R_BECOMELEADER), ovf & valid

    def _client_request(self, s, i, v):
        """ClientRequest(i, v) — PullRaft.tla:370-379."""
        L = self.p.max_log
        d = self._dec(s)
        valid = (onehot_row(d["state"], i) == LEADER) & (
            onehot_row(d["acked"], v) == ACK_NIL)
        pos = onehot_row(d["log_len"], i)
        ovf = valid & (pos >= L)
        posc = jnp.clip(pos, 0, L - 1)
        succ = self._asm(
            d,
            log_term=onehot_set2(
                d["log_term"], i, posc, onehot_row(d["currentTerm"], i)),
            log_value=onehot_set2(d["log_value"], i, posc, v + 1),
            log_len=onehot_add(d["log_len"], i, 1),
            acked=onehot_set(d["acked"], v, ACK_FALSE),
        )
        return valid, succ, jnp.int32(R_CLIENTREQUEST), ovf

    def _send_pull(self, s, i, j):
        """SendPullEntriesRequest(i, j) — PullRaft.tla:396-411."""
        d = self._dec(s)
        valid = (onehot_row(d["state"], i) == FOLLOWER) & (
            onehot_row(d["leader"], i) == j + 1)
        khi, klo = self._pack(
            mtype=PULLREQ,
            mterm=onehot_row(d["currentTerm"], i),
            mlastLogIndex=onehot_row(d["log_len"], i),
            mlastLogTerm=self._last_term(d, i),
            msource=i,
            mdest=j,
        )
        hi, lo, cnt, existed, ovf = bag.bag_put(
            d["msg_hi"], d["msg_lo"], d["msg_cnt"], khi, klo
        )
        valid &= ~existed  # Send (PullRaft.tla:137-139)
        succ = self._asm(d, msg_hi=hi, msg_lo=lo, msg_cnt=cnt)
        return valid, succ, jnp.int32(R_SENDPULL), ovf & valid

    # -------- fused message-receipt kernel (slot m) --------
    # The eight receipt disjuncts (UpdateTerm, HandleRVReq, HandleRVResp,
    # RejectPull, AcceptPull, LearnOfLeader, HandleSuccessPull,
    # HandleFailPull) are mutually exclusive per record: they partition on
    # mtype, the term comparison, ValidPullPosition and msuccess. Being
    # exclusive, the three replying branches share ONE bag_put on the
    # branch-selected response and the successor assembles once, field
    # by field (the shape kraft.py's receipt kernel has).

    def _handle_message(self, s, m):
        p, packer = self.p, self.packer
        S, L, V = p.n_servers, p.max_log, p.n_values
        d = self._dec(s)
        hi, lo, cnt = d["msg_hi"], d["msg_lo"], d["msg_cnt"]
        khi, klo, kcnt = onehot_row(hi, m), onehot_row(lo, m), onehot_row(cnt, m)
        occupied = khi != EMPTY
        u = partial(packer.unpack, khi, klo)
        mtype, mterm = u("mtype"), u("mterm")
        src, dst = u("msource"), u("mdest")
        ct_dst = onehot_row(d["currentTerm"], dst)
        st_dst = onehot_row(d["state"], dst)
        recv = occupied & (kcnt > 0)  # ReceivableMessage (PullRaft.tla:166-172)
        ll_dst = onehot_row(d["log_len"], dst)
        lt_dst = onehot_row(d["log_term"], dst)
        lv_dst = onehot_row(d["log_value"], dst)
        ci_dst = onehot_row(d["commitIndex"], dst)
        cnt_disc = bag.bag_discard_at(cnt, m)
        lanes0 = jnp.arange(L, dtype=jnp.int32)

        # --- UpdateTerm (PullRaft.tla:269-276): count-0 records included;
        # the message stays in the bag.
        b_upd = occupied & (mterm > ct_dst)

        # --- HandleRequestVoteRequest (PullRaft.tla:306-330;
        # PullRaftVariant2.tla:303-326)
        last_t = self._last_term(d, dst)
        rv_logok = (u("mlastLogTerm") > last_t) | (
            (u("mlastLogTerm") == last_t) & (u("mlastLogIndex") >= ll_dst)
        )
        vote_name = "votedFor" if p.variant2 else "leader"
        vote_dst = onehot_row(d[vote_name], dst)
        grant = (
            (mterm == ct_dst)
            & rv_logok
            & ((vote_dst == NIL) | (vote_dst == src + 1))
        )
        b_rvreq = recv & (mtype == RVREQ) & (mterm <= ct_dst)
        resp_kw = dict(
            mtype=RVRESP,
            mterm=ct_dst,
            mvoteGranted=grant.astype(jnp.int32),
            msource=dst,
            mdest=src,
        )
        if p.variant2:  # response carries last entry (PullRaftVariant2.tla:320-321)
            resp_kw["mlastLogIndex"] = ll_dst
            resp_kw["mlastLogTerm"] = last_t
        rv_key = self._pack(**resp_kw)

        # --- HandleRequestVoteResponse (PullRaft.tla:335-350;
        # Variant2 also records votesLastEntry, PullRaftVariant2.tla:339-344)
        b_rvresp = recv & (mtype == RVRESP) & (mterm == ct_dst)
        rvresp_grant = b_rvresp & (u("mvoteGranted") > 0)

        # --- pull-request handling: ValidPullPosition (PullRaft.tla:192-196)
        pull_idx = u("mlastLogIndex")
        pull_term = u("mlastLogTerm")
        valid_pos = (pull_idx == 0) | (
            (pull_idx > 0)
            & (pull_idx <= ll_dst)
            & (pull_term == onehot_row(lt_dst, jnp.clip(pull_idx - 1, 0, L - 1)))
        )
        is_pullreq = recv & (mtype == PULLREQ) & (mterm == ct_dst) & (st_dst == LEADER)

        # --- RejectPullEntriesRequest (PullRaft.tla:418-436)
        b_reject = is_pullreq & ~valid_pos
        lce_i, lce_t = self._last_common(lt_dst, ll_dst, pull_idx, pull_term)
        rj_key = self._pack(
            mtype=PULLRESP,
            mterm=ct_dst,
            msuccess=0,
            mlcHas=1,
            mlcIndex=lce_i,
            mlcTerm=lce_t,
            msource=dst,
            mdest=src,
        )

        # --- AcceptPullEntriesRequest (PullRaft.tla:460-488)
        index = pull_idx + 1
        b_accept = is_pullreq & valid_pos & (index <= ll_dst)
        new_match = onehot_set(onehot_row(d["matchIndex"], dst), src, pull_idx)
        # NewCommitIndex (PullRaft.tla:446-458)
        idxs = jnp.arange(1, L + 1, dtype=jnp.int32)
        self_in = jnp.arange(S, dtype=jnp.int32)[None, :] == dst
        agree = self_in | (new_match[None, :] >= idxs[:, None])
        quorum_ok = 2 * jnp.sum(agree, axis=1) > S
        is_agree = quorum_ok & (idxs <= ll_dst)
        max_agree = jnp.max(jnp.where(is_agree, idxs, 0))
        term_at = onehot_row(lt_dst, jnp.clip(max_agree - 1, 0, L - 1))
        new_ci = jnp.where(
            (max_agree > 0) & (term_at == ct_dst), max_agree, ci_dst
        )
        # acked[v]: FALSE -> v committed in (ci, new_ci] (PullRaft.tla:476-479)
        in_range = (lanes0 + 1 > ci_dst) & (lanes0 + 1 <= new_ci)
        committed = jnp.any(
            in_range[None, :]
            & (lv_dst[None, :] == jnp.arange(1, V + 1, dtype=jnp.int32)[:, None]),
            axis=1,
        )
        acked2 = jnp.where((d["acked"] == ACK_FALSE) & committed, ACK_TRUE, d["acked"])
        epos = jnp.clip(index - 1, 0, L - 1)
        ac_key = self._pack(
            mtype=PULLRESP,
            mterm=ct_dst,
            msuccess=1,
            nentries=1,
            eterm=onehot_row(lt_dst, epos),
            evalue=onehot_row(lv_dst, epos),
            mcommitIndex=jnp.minimum(new_ci, index),
            msource=dst,
            mdest=src,
        )

        # Reply — PullRaft.tla:158-161: discard the request, send the
        # branch's response, which must be absent
        replying = b_rvreq | b_reject | b_accept
        resp_hi, resp_lo = rv_key
        for b, (k_hi, k_lo) in ((b_reject, rj_key), (b_accept, ac_key)):
            resp_hi = jnp.where(b, k_hi, resp_hi)
            resp_lo = jnp.where(b, k_lo, resp_lo)
        hi_r, lo_r, cnt_r, existed, put_ovf = bag.bag_put(
            hi, lo, cnt_disc, resp_hi, resp_lo)
        b_rvreq &= ~existed
        b_reject &= ~existed
        b_accept &= ~existed

        # --- LearnOfLeader (PullRaft.tla:383-391; Variant2 may truncate,
        # PullRaftVariant2.tla:398-410)
        b_learn = recv & (mtype == NOTIFY) & (mterm == ct_dst)

        # --- HandleSuccessPullEntriesResponse (PullRaft.tla:493-503)
        is_pullresp = recv & (mtype == PULLRESP) & (mterm == ct_dst)
        b_succ = is_pullresp & (u("msuccess") > 0)
        app_pos = jnp.clip(ll_dst, 0, L - 1)
        suc_ovf = b_succ & (ll_dst >= L)

        # --- HandleFailPullEntriesResponse (PullRaft.tla:510-520):
        # TruncateLog to mlastCommonEntry.index (clamped to Len).
        b_fail = is_pullresp & (u("msuccess") == 0)
        trunc, new_ll = b_fail, jnp.minimum(u("mlcIndex"), ll_dst)
        if p.variant2:
            # NeedsTruncation (PullRaftVariant2.tla:171-173): mlcHas and
            # Len(log) >= index; TruncateLog to the index (:176-179).
            do_trunc = (u("mlcHas") > 0) & (ll_dst >= u("mlcIndex"))
            new_ll = jnp.where(
                b_learn, jnp.where(do_trunc, u("mlcIndex"), ll_dst), new_ll)
            trunc = trunc | b_learn
        keep = lanes0 < new_ll

        # ---- the successor, field by field ----
        def where_set(b, arr, val):
            return jnp.where(b, onehot_set(arr, dst, val), arr)

        upd = dict(
            currentTerm=where_set(b_upd, d["currentTerm"], mterm),
            state=where_set(b_upd, d["state"], FOLLOWER),
            votesGranted=where_set(
                rvresp_grant, d["votesGranted"],
                onehot_row(d["votesGranted"], dst) | (jnp.int32(1) << src)),
            log_term=jnp.where(
                b_succ,
                onehot_set2(d["log_term"], dst, app_pos, u("eterm")),
                where_set(trunc, d["log_term"], jnp.where(keep, lt_dst, 0))),
            log_value=jnp.where(
                b_succ,
                onehot_set2(d["log_value"], dst, app_pos, u("evalue")),
                where_set(trunc, d["log_value"], jnp.where(keep, lv_dst, 0))),
            log_len=jnp.where(
                b_succ,
                onehot_add(d["log_len"], dst, 1),
                where_set(trunc, d["log_len"], new_ll)),
            commitIndex=where_set(
                b_accept | b_succ, d["commitIndex"],
                jnp.where(b_succ, u("mcommitIndex"), new_ci)),
            matchIndex=where_set(b_accept, d["matchIndex"], new_match),
            acked=jnp.where(b_accept, acked2, d["acked"]),
            msg_hi=jnp.where(replying, hi_r, hi),
            msg_lo=jnp.where(replying, lo_r, lo),
            msg_cnt=jnp.where(
                replying, cnt_r, jnp.where(b_upd, cnt, cnt_disc)),
        )
        # the vote (leader, or Variant2's votedFor): Nil on UpdateTerm,
        # the requester on a granted vote; leader also the notifier's
        vote_to = b_rvreq & grant
        if p.variant2:
            upd["votedFor"] = where_set(
                b_upd | vote_to, d["votedFor"], jnp.where(b_upd, NIL, src + 1))
            upd["leader"] = where_set(
                b_upd | b_learn, d["leader"], jnp.where(b_upd, NIL, src + 1))
            for f, val in (("vle_has", 1), ("vle_idx", u("mlastLogIndex")),
                           ("vle_term", u("mlastLogTerm"))):
                upd[f] = jnp.where(
                    rvresp_grant, onehot_set2(d[f], dst, src, val), d[f])
        else:
            upd["leader"] = where_set(
                b_upd | vote_to | b_learn, d["leader"],
                jnp.where(b_upd, NIL, src + 1))
        succ = self._asm(d, **upd)

        valid = jnp.asarray(False)
        rank = jnp.int32(-1)
        for b, rk in (
            (b_upd, R_UPDATETERM),
            (b_rvreq, R_HANDLE_RVREQ),
            (b_rvresp, R_HANDLE_RVRESP),
            (b_reject, R_REJECT_PULL),
            (b_accept, R_ACCEPT_PULL),
            (b_learn, R_LEARNOFLEADER),
            (b_succ, R_HANDLE_SUCCESS_PULL),
            (b_fail, R_HANDLE_FAIL_PULL),
        ):
            valid = valid | b
            rank = jnp.where(b, jnp.int32(rk), rank)
        ovf = ((b_rvreq | b_reject | b_accept) & put_ovf) | suc_ovf
        succ = jnp.where(valid, succ, s)
        return valid, succ, rank, ovf

    # ---------------- full expansion ----------------

    def _kernel_overrides(self) -> dict:
        return {"SendPullEntriesRequest": self._send_pull}

    def _expand1(self, s):
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
        outs.append(jax.vmap(lambda i, j: self._send_pull(s, i, j))(pr_i, pr_j))
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
        """Init — PullRaft.tla:231-250."""
        lay = self.layout
        vec = lay.zeros((1,))
        vec[0, lay.sl("currentTerm")] = 1
        vec[0, lay.sl("msg_hi")] = int(EMPTY)
        vec[0, lay.sl("msg_lo")] = int(EMPTY)
        return self._fleet_stamp(vec)

    # ---------------- invariants (PullRaft.tla:578-627) ----------------

    def _inv_no_log_divergence(self, states):
        lay, L = self.layout, self.p.max_log
        ci = lay.get(states, "commitIndex")
        lt = lay.get(states, "log_term")
        lv = lay.get(states, "log_value")
        mci = jnp.minimum(ci[:, :, None], ci[:, None, :])
        lanes = jnp.arange(1, L + 1, dtype=jnp.int32)
        in_common = lanes[None, None, None, :] <= mci[..., None]
        eq = (lt[:, :, None, :] == lt[:, None, :, :]) & (
            lv[:, :, None, :] == lv[:, None, :, :]
        )
        return jnp.all(~in_common | eq, axis=(1, 2, 3))

    def _inv_leader_has_acked(self, states):
        lay, V = self.layout, self.p.n_values
        ct = lay.get(states, "currentTerm")
        st = lay.get(states, "state")
        lv = lay.get(states, "log_value")
        acked = lay.get(states, "acked")
        not_stale = jnp.all(ct[:, :, None] >= ct[:, None, :], axis=2)
        is_lead = (st == LEADER) & not_stale
        vals = jnp.arange(1, V + 1, dtype=jnp.int32)
        has_v = jnp.any(lv[:, :, None, :] == vals[None, None, :, None], axis=3)
        bad = jnp.any(
            (acked[:, None, :] == ACK_TRUE) & is_lead[:, :, None] & ~has_v, axis=(1, 2)
        )
        return ~bad

    def _inv_committed_majority(self, states):
        lay, S, L = self.layout, self.p.n_servers, self.p.max_log
        st = lay.get(states, "state")
        ci = lay.get(states, "commitIndex")
        ll = lay.get(states, "log_len")
        lt = lay.get(states, "log_term")
        lv = lay.get(states, "log_value")
        lead = (st == LEADER) & (ci > 0)
        pos = jnp.clip(ci - 1, 0, L - 1)
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
        match = (ll[:, None, :] >= ci[:, :, None]) & (lt_j == lt_i[..., None]) & (
            lv_j == lv_i[..., None]
        )
        enough = jnp.sum(match, axis=2) >= (S // 2 + 1)
        ok_exists = jnp.any(lead & enough, axis=1)
        return ~jnp.any(lead, axis=1) | ok_exists

    # ---------------- host-side decode/encode ----------------

    def decode(self, vec: np.ndarray) -> dict:
        lay, p = self.layout, self.p
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
        msgs = {}
        hi, lo, cnt = g("msg_hi"), g("msg_lo"), g("msg_cnt")
        for k in range(p.msg_slots):
            if int(hi[k]) == int(EMPTY):
                continue
            msgs[self.decode_msg(int(hi[k]), int(lo[k]))] = int(cnt[k])
        extra = {}
        if p.variant2:
            vh = g("vle_has").reshape(S, S)
            vi = g("vle_idx").reshape(S, S)
            vt = g("vle_term").reshape(S, S)
            extra["votedFor"] = tuple(
                int(x) - 1 if x > 0 else None for x in g("votedFor")
            )
            extra["votesLastEntry"] = tuple(
                tuple(
                    (int(vi[a, b]), int(vt[a, b])) if vh[a, b] else None
                    for b in range(S)
                )
                for a in range(S)
            )
        return extra | {
            "currentTerm": tuple(int(x) for x in g("currentTerm")),
            "state": tuple(int(x) for x in g("state")),
            "leader": tuple(int(x) - 1 if x > 0 else None for x in g("leader")),
            "votesGranted": votes,
            "log": log,
            "commitIndex": tuple(int(x) for x in g("commitIndex")),
            "matchIndex": tuple(
                tuple(int(x) for x in row) for row in g("matchIndex").reshape(S, S)
            ),
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
            "mterm": int(u["mterm"]),
            "msource": int(u["msource"]),
            "mdest": int(u["mdest"]),
        }
        if mtype == RVREQ:
            rec["mlastLogTerm"] = int(u["mlastLogTerm"])
            rec["mlastLogIndex"] = int(u["mlastLogIndex"])
        elif mtype == RVRESP:
            rec["mvoteGranted"] = bool(u["mvoteGranted"])
            if self.p.variant2:
                rec["mlastLogIndex"] = int(u["mlastLogIndex"])
                rec["mlastLogTerm"] = int(u["mlastLogTerm"])
        elif mtype == PULLREQ:
            rec["mlastLogIndex"] = int(u["mlastLogIndex"])
            rec["mlastLogTerm"] = int(u["mlastLogTerm"])
        elif mtype == PULLRESP:
            rec["msuccess"] = bool(u["msuccess"])
            if u["msuccess"]:
                rec["mentries"] = ((int(u["eterm"]), int(u["evalue"]) - 1),)
                rec["mcommitIndex"] = int(u["mcommitIndex"])
            else:
                rec["mlastCommonEntry"] = (int(u["mlcIndex"]), int(u["mlcTerm"]))
        elif mtype == NOTIFY:
            if self.p.variant2:
                rec["mlastCommonEntry"] = (
                    (int(u["mlcIndex"]), int(u["mlcTerm"]))
                    if u["mlcHas"]
                    else None
                )
        return tuple(sorted(rec.items()))

    def encode_msg(self, rec: tuple) -> tuple[int, int]:
        d = dict(rec)
        mtype = {v: k for k, v in MTYPE_NAMES.items()}[d["mtype"]]
        kw = dict(mtype=mtype, mterm=d["mterm"], msource=d["msource"], mdest=d["mdest"])
        if mtype == RVREQ:
            kw.update(mlastLogTerm=d["mlastLogTerm"], mlastLogIndex=d["mlastLogIndex"])
        elif mtype == RVRESP:
            kw.update(mvoteGranted=int(d["mvoteGranted"]))
            if self.p.variant2:
                kw.update(
                    mlastLogIndex=d["mlastLogIndex"], mlastLogTerm=d["mlastLogTerm"]
                )
        elif mtype == PULLREQ:
            kw.update(
                mlastLogIndex=d["mlastLogIndex"], mlastLogTerm=d["mlastLogTerm"]
            )
        elif mtype == PULLRESP:
            kw.update(msuccess=int(d["msuccess"]))
            if d["msuccess"]:
                ent = d["mentries"][0]
                kw.update(
                    nentries=1,
                    eterm=ent[0],
                    evalue=ent[1] + 1,
                    mcommitIndex=d["mcommitIndex"],
                )
            else:
                lce = d["mlastCommonEntry"]
                kw.update(mlcHas=1, mlcIndex=lce[0], mlcTerm=lce[1])
        elif mtype == NOTIFY:
            if self.p.variant2:
                lce = d["mlastCommonEntry"]
                if lce is not None:
                    kw.update(mlcHas=1, mlcIndex=lce[0], mlcTerm=lce[1])
        return self.packer.pack(**kw)

    def encode(self, st: dict) -> np.ndarray:
        lay, p = self.layout, self.p
        S, L = p.n_servers, p.max_log
        vec = lay.zeros(())
        vec[lay.sl("currentTerm")] = st["currentTerm"]
        vec[lay.sl("state")] = st["state"]
        vec[lay.sl("leader")] = [0 if v is None else v + 1 for v in st["leader"]]
        if p.variant2:
            vec[lay.sl("votedFor")] = [
                0 if v is None else v + 1 for v in st["votedFor"]
            ]
            vh = np.zeros((S, S), np.int32)
            vi = np.zeros((S, S), np.int32)
            vt = np.zeros((S, S), np.int32)
            for a in range(S):
                for b in range(S):
                    e = st["votesLastEntry"][a][b]
                    if e is not None:
                        vh[a, b], vi[a, b], vt[a, b] = 1, e[0], e[1]
            vec[lay.sl("vle_has")] = vh.reshape(-1)
            vec[lay.sl("vle_idx")] = vi.reshape(-1)
            vec[lay.sl("vle_term")] = vt.reshape(-1)
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
        vec[lay.sl("matchIndex")] = np.asarray(st["matchIndex"]).reshape(-1)
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
        vec[lay.sl("acked")] = [
            {None: ACK_NIL, False: ACK_FALSE, True: ACK_TRUE}[a] for a in st["acked"]
        ]
        vec[lay.fields["electionCtr"].offset] = st["electionCtr"]
        vec[lay.fields["restartCtr"].offset] = st["restartCtr"]
        return vec


@lru_cache(maxsize=None)
def cached_model(params: PullRaftParams) -> "PullRaftModel":
    return PullRaftModel(params)
