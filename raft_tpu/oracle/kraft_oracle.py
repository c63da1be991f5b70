"""Independent pure-Python interpreter of pull-raft/KRaft.tla.

Differential-testing ground truth for the TPU lowering in models/kraft.py,
written directly against the TLA+ text (reference
``/root/reference/specifications/pull-raft/KRaft.tla``, 961 lines) — NOT
against the JAX kernels.

Key structural deltas vs. PullRaft (see SURVEY.md §2.1):
  - five server states plus IllegalState (``KRaft.tla:69,87``): Unattached
    and Voted precede the usual three; an explicit transition machine
    (``HasConsistentLeader:316``, ``MaybeTransition:351``,
    ``MaybeHandleCommonResponse:369``) governs receipt-driven changes;
  - fetch-based replication with a ``pendingFetch`` correlation register
    (``KRaft.tla:123``): the follower records the exact FetchRequest it
    sent and only a FetchResponse whose ``correlation`` field equals it is
    processable (``:749,774,794``);
  - three fetch-response shapes keyed by ``mresult`` (Ok / NotOk /
    Diverging, ``KRaft.tla:81``) plus error codes (``:84``);
  - diverging-epoch truncation via ``EndOffsetForEpoch`` (``:285-301``) and
    ``HighestCommonOffset`` (``:255-273``);
  - ``Reply`` refuses to duplicate a FetchResponse (``KRaft.tla:220-227``),
    the anti-infinite-empty-fetch rule;
  - ``RequestVoteRequest``/``BeginQuorumRequest`` are send-once, FetchRequest
    is unrestricted (``KRaft.tla:190-194``).

State dict format (shared with KRaftModel.decode/encode):
  currentEpoch, state, votedFor (int|None), leader (int|None),
  pendingFetch (None | record tuple), votesGranted (frozensets),
  endOffset (SxS), log, highWatermark, messages, acked, electionCtr,
  restartCtr.
"""

from __future__ import annotations

import itertools

# state encoding shared with models/kraft.py (QuorumState machine,
# KRaft.tla:33-56)
UNATTACHED, VOTED, FOLLOWER, CANDIDATE, LEADER, ILLEGAL = range(6)

# error codes (KRaft.tla:84)
NO_ERROR = None
FENCED = "FencedLeaderEpoch"
NOT_LEADER = "NotLeader"
UNKNOWN_LEADER = "UnknownLeader"

OK, NOT_OK, DIVERGING = "Ok", "NotOk", "Diverging"


def rec(**kw) -> tuple:
    return tuple(sorted(kw.items()))


def last_epoch(log) -> int:
    """LastEpoch(xlog) — KRaft.tla:165."""
    return log[-1][0] if log else 0


def compare_entries(offset1, epoch1, offset2, epoch2) -> int:
    """CompareEntries — KRaft.tla:247-251 (epoch takes precedence)."""
    if epoch1 > epoch2:
        return 1
    if epoch1 == epoch2 and offset1 > offset2:
        return 1
    if epoch1 == epoch2 and offset1 == offset2:
        return 0
    return -1


def end_offset_for_epoch(log, last_fetched_epoch) -> tuple[int, int]:
    """EndOffsetForEpoch(i, lastFetchedEpoch) — KRaft.tla:285-301: the
    highest offset whose entry epoch is <= lastFetchedEpoch, as
    (offset, epoch); (0, 0) when none."""
    best = 0
    for off in range(1, len(log) + 1):
        if log[off - 1][0] <= last_fetched_epoch:
            best = off
    if best == 0:
        return (0, 0)
    return (best, log[best - 1][0])


def highest_common_offset(log, end_offset: int, epoch: int) -> tuple[int, int]:
    """HighestCommonOffset(i, endOffsetForEpoch, epoch) — KRaft.tla:255-273:
    highest offset with CompareEntries(offset, log[offset].epoch,
    end_offset, epoch) <= 0; (0, 0) when none."""
    best = 0
    for off in range(1, len(log) + 1):
        if compare_entries(off, log[off - 1][0], end_offset, epoch) <= 0:
            best = off
    if best == 0:
        return (0, 0)
    return (best, log[best - 1][0])


class KRaftOracle:
    def __init__(
        self,
        n_servers: int,
        n_values: int,
        max_elections: int,
        max_restarts: int,
    ):
        self.S = n_servers
        self.V = n_values
        self.max_elections = max_elections
        self.max_restarts = max_restarts

    # ---------- state helpers ----------

    def init_state(self) -> dict:
        """Init — KRaft.tla:397-415."""
        S, V = self.S, self.V
        return {
            "currentEpoch": (1,) * S,
            "state": (UNATTACHED,) * S,
            "votedFor": (None,) * S,
            "leader": (None,) * S,
            "pendingFetch": (None,) * S,
            "votesGranted": (frozenset(),) * S,
            "endOffset": ((0,) * S,) * S,
            "log": ((),) * S,
            "highWatermark": (0,) * S,
            "messages": frozenset(),
            "acked": (None,) * V,
            "electionCtr": 0,
            "restartCtr": 0,
        }

    @staticmethod
    def _msgs(st) -> dict:
        return dict(st["messages"])

    @staticmethod
    def _with(st, **updates) -> dict:
        out = dict(st)
        out.update(updates)
        return out

    @staticmethod
    def _set(tup, i, val) -> tuple:
        return tup[:i] + (val,) + tup[i + 1 :]

    @classmethod
    def _set2(cls, mat, i, j, val) -> tuple:
        return cls._set(mat, i, cls._set(mat[i], j, val))

    # ---------- message-bag helpers (KRaft.tla:167-227) ----------

    @staticmethod
    def _send_no_restriction(msgs, m):
        """_SendNoRestriction — KRaft.tla:169-173."""
        out = dict(msgs)
        out[m] = out.get(m, 0) + 1
        return frozenset(out.items())

    @staticmethod
    def _send_once(msgs, m):
        """_SendOnce — KRaft.tla:178-180; None when m already in DOMAIN."""
        if m in msgs:
            return None
        out = dict(msgs)
        out[m] = 1
        return frozenset(out.items())

    @classmethod
    def _send(cls, msgs, m):
        """Send — KRaft.tla:190-194: RequestVoteRequest/BeginQuorumRequest
        are send-once, everything else unrestricted."""
        mtype = dict(m)["mtype"]
        if mtype in ("RequestVoteRequest", "BeginQuorumRequest"):
            return cls._send_once(msgs, m)
        return cls._send_no_restriction(msgs, m)

    @staticmethod
    def _send_multiple_once(msgs, ms):
        """SendMultipleOnce — KRaft.tla:199-201; None when any exists."""
        if any(m in msgs for m in ms):
            return None
        out = dict(msgs)
        for m in ms:
            out[m] = 1
        return frozenset(out.items())

    @staticmethod
    def _reply(msgs, response, request):
        """Reply — KRaft.tla:220-227: decrement request, add/increment the
        response; a FetchResponse may not be duplicated (anti-cycle rule).
        Returns None when disabled."""
        out = dict(msgs)
        if out.get(request, 0) < 1:
            return None
        if response in out and dict(response)["mtype"] == "FetchResponse":
            return None
        out[request] -= 1
        out[response] = out.get(response, 0) + 1
        return frozenset(out.items())

    @staticmethod
    def _discard(msgs, m):
        """Discard — KRaft.tla:210-213."""
        out = dict(msgs)
        assert out.get(m, 0) > 0
        out[m] -= 1
        return frozenset(out.items())

    def _receivable(self, st, m, mtype: str, equal_epoch: bool) -> bool:
        """ReceivableMessage — KRaft.tla:230-235."""
        d = dict(m)
        msgs = self._msgs(st)
        if msgs.get(m, 0) < 1 or d["mtype"] != mtype:
            return False
        if equal_epoch and d["mepoch"] != st["currentEpoch"][d["mdest"]]:
            return False
        return True

    def _domain(self, st):
        """DOMAIN messages, in a deterministic order."""
        return sorted((m for m, _c in st["messages"]), key=self._norm_rec)

    # ---------- transition machine (KRaft.tla:312-392) ----------

    def _has_consistent_leader(self, st, i, leader_id, epoch) -> bool:
        """HasConsistentLeader — KRaft.tla:316-327."""
        if leader_id == i:
            return st["state"][i] == LEADER
        return (
            epoch != st["currentEpoch"][i]
            or leader_id is None
            or st["leader"][i] is None
            or st["leader"][i] == leader_id
        )

    @staticmethod
    def _illegal():
        """SetIllegalState — KRaft.tla:329-330."""
        return {"state": ILLEGAL, "epoch": 0, "leader": None}

    def _no_transition(self, st, i):
        """NoTransition — KRaft.tla:332-333."""
        return {
            "state": st["state"][i],
            "epoch": st["currentEpoch"][i],
            "leader": st["leader"][i],
        }

    def _to_voted(self, st, i, epoch, state0):
        """TransitionToVoted — KRaft.tla:335-339."""
        if state0["epoch"] == epoch and state0["state"] != UNATTACHED:
            return self._illegal()
        return {"state": VOTED, "epoch": epoch, "leader": None}

    @staticmethod
    def _to_unattached(epoch):
        """TransitionToUnattached — KRaft.tla:341-342."""
        return {"state": UNATTACHED, "epoch": epoch, "leader": None}

    def _to_follower(self, st, i, leader_id, epoch):
        """TransitionToFollower — KRaft.tla:344-349."""
        if st["currentEpoch"][i] == epoch and st["state"][i] in (FOLLOWER, LEADER):
            return self._illegal()
        return {"state": FOLLOWER, "epoch": epoch, "leader": leader_id}

    def _maybe_transition(self, st, i, leader_id, epoch):
        """MaybeTransition — KRaft.tla:351-367."""
        if not self._has_consistent_leader(st, i, leader_id, epoch):
            return self._illegal()
        if epoch > st["currentEpoch"][i]:
            if leader_id is None:
                return self._to_unattached(epoch)
            return self._to_follower(st, i, leader_id, epoch)
        if leader_id is not None and st["leader"][i] is None:
            return self._to_follower(st, i, leader_id, epoch)
        return self._no_transition(st, i)

    def _maybe_handle_common_response(self, st, i, leader_id, epoch, errors):
        """MaybeHandleCommonResponse — KRaft.tla:369-392."""
        if epoch < st["currentEpoch"][i]:
            return self._no_transition(st, i) | {"handled": True}
        if epoch > st["currentEpoch"][i] or errors is not None:
            return self._maybe_transition(st, i, leader_id, epoch) | {"handled": True}
        if (
            epoch == st["currentEpoch"][i]
            and leader_id is not None
            and st["leader"][i] is None
        ):
            return {
                "state": FOLLOWER,
                "leader": leader_id,
                "epoch": st["currentEpoch"][i],
                "handled": True,
            }
        return self._no_transition(st, i) | {"handled": False}

    def _apply_transition(self, st, i, new, clear_pending=False, **extra):
        """state/leader/currentEpoch := transition record fields."""
        upd = dict(
            state=self._set(st["state"], i, new["state"]),
            leader=self._set(st["leader"], i, new["leader"]),
            currentEpoch=self._set(st["currentEpoch"], i, new["epoch"]),
            **extra,
        )
        if clear_pending:
            upd["pendingFetch"] = self._set(st["pendingFetch"], i, None)
        return self._with(st, **upd)

    # ---------- fetch-position helpers (KRaft.tla:276-310) ----------

    def _truncate_log(self, st, i, m) -> tuple:
        """TruncateLog — KRaft.tla:276-282."""
        d = dict(m)
        hco, _epoch = highest_common_offset(
            st["log"][i], d["mdivergingEndOffset"], d["mdivergingEpoch"]
        )
        return st["log"][i][:hco]

    def _valid_fetch_position(self, st, i, m) -> bool:
        """ValidFetchPosition — KRaft.tla:305-310."""
        d = dict(m)
        if d["mfetchOffset"] == 0 and d["mlastFetchedEpoch"] == 0:
            return True
        off, ep = end_offset_for_epoch(st["log"][i], d["mlastFetchedEpoch"])
        return d["mfetchOffset"] <= off and d["mlastFetchedEpoch"] == ep

    # ---------- actions (Next order, KRaft.tla:823-840) ----------

    def successors(self, st) -> list[tuple[str, dict]]:
        out = []
        S, V = self.S, self.V
        for i in range(S):
            s2 = self.restart(st, i)
            if s2 is not None:
                out.append((f"Restart({i})", s2))
        for i in range(S):
            s2 = self.request_vote(st, i)
            if s2 is not None:
                out.append((f"RequestVote({i})", s2))
        for m in self._domain(st):
            s2 = self.handle_request_vote_request(st, m)
            if s2 is not None:
                out.append(("HandleRequestVoteRequest", s2))
        for m in self._domain(st):
            s2 = self.handle_request_vote_response(st, m)
            if s2 is not None:
                out.append(("HandleRequestVoteResponse", s2))
        for i in range(S):
            s2 = self.become_leader(st, i)
            if s2 is not None:
                out.append((f"BecomeLeader({i})", s2))
        for i in range(S):
            for v in range(V):
                s2 = self.client_request(st, i, v)
                if s2 is not None:
                    out.append((f"ClientRequest({i},{v})", s2))
        for m in self._domain(st):
            s2 = self.reject_fetch_request(st, m)
            if s2 is not None:
                out.append(("RejectFetchRequest", s2))
        for m in self._domain(st):
            s2 = self.diverging_fetch_request(st, m)
            if s2 is not None:
                out.append(("DivergingFetchRequest", s2))
        for m in self._domain(st):
            s2 = self.accept_fetch_request(st, m)
            if s2 is not None:
                out.append(("AcceptFetchRequest", s2))
        for m in self._domain(st):
            s2 = self.handle_begin_quorum_request(st, m)
            if s2 is not None:
                out.append(("HandleBeginQuorumRequest", s2))
        for i in range(S):
            for j in range(S):
                if i != j:
                    s2 = self.send_fetch_request(st, i, j)
                    if s2 is not None:
                        out.append((f"SendFetchRequest({i},{j})", s2))
        for m in self._domain(st):
            s2 = self.handle_success_fetch_response(st, m)
            if s2 is not None:
                out.append(("HandleSuccessFetchResponse", s2))
        for m in self._domain(st):
            s2 = self.handle_diverging_fetch_response(st, m)
            if s2 is not None:
                out.append(("HandleDivergingFetchResponse", s2))
        for m in self._domain(st):
            s2 = self.handle_error_fetch_response(st, m)
            if s2 is not None:
                out.append(("HandleErrorFetchResponse", s2))
        return out

    def restart(self, st, i):
        """Restart(i) — KRaft.tla:423-432: keeps currentEpoch, votedFor and
        log; loses leader belief, votes, endOffset, hwm, pendingFetch."""
        if st["restartCtr"] >= self.max_restarts:
            return None
        return self._with(
            st,
            state=self._set(st["state"], i, FOLLOWER),
            leader=self._set(st["leader"], i, None),
            votesGranted=self._set(st["votesGranted"], i, frozenset()),
            endOffset=self._set(st["endOffset"], i, (0,) * self.S),
            highWatermark=self._set(st["highWatermark"], i, 0),
            pendingFetch=self._set(st["pendingFetch"], i, None),
            restartCtr=st["restartCtr"] + 1,
        )

    def request_vote(self, st, i):
        """RequestVote(i) — KRaft.tla:439-456 (fused Timeout+RequestVote)."""
        if st["electionCtr"] >= self.max_elections:
            return None
        if st["state"][i] not in (FOLLOWER, CANDIDATE, UNATTACHED):
            return None
        new_epoch = st["currentEpoch"][i] + 1
        reqs = {
            rec(
                mtype="RequestVoteRequest",
                mepoch=new_epoch,
                mlastLogEpoch=last_epoch(st["log"][i]),
                mlastLogOffset=len(st["log"][i]),
                msource=i,
                mdest=j,
            )
            for j in range(self.S)
            if j != i
        }
        msgs = self._send_multiple_once(self._msgs(st), reqs)
        if msgs is None:
            return None
        return self._with(
            st,
            state=self._set(st["state"], i, CANDIDATE),
            currentEpoch=self._set(st["currentEpoch"], i, new_epoch),
            leader=self._set(st["leader"], i, None),
            votedFor=self._set(st["votedFor"], i, i),
            votesGranted=self._set(st["votesGranted"], i, frozenset({i})),
            pendingFetch=self._set(st["pendingFetch"], i, None),
            electionCtr=st["electionCtr"] + 1,
            messages=msgs,
        )

    def handle_request_vote_request(self, st, m):
        """HandleRequestVoteRequest — KRaft.tla:464-513."""
        if not self._receivable(st, m, "RequestVoteRequest", equal_epoch=False):
            return None
        d = dict(m)
        i, j = d["mdest"], d["msource"]
        error = FENCED if d["mepoch"] < st["currentEpoch"][i] else None
        if error is not None:
            resp = rec(
                mtype="RequestVoteResponse",
                mepoch=st["currentEpoch"][i],
                mleader=st["leader"][i],
                mvoteGranted=False,
                merror=error,
                msource=i,
                mdest=j,
            )
            msgs = self._reply(self._msgs(st), resp, m)
            if msgs is None:
                return None
            return self._with(st, messages=msgs)
        state0 = (
            self._to_unattached(d["mepoch"])
            if d["mepoch"] > st["currentEpoch"][i]
            else self._no_transition(st, i)
        )
        log_ok = (
            compare_entries(
                d["mlastLogOffset"],
                d["mlastLogEpoch"],
                len(st["log"][i]),
                last_epoch(st["log"][i]),
            )
            >= 0
        )
        grant = (
            state0["state"] == UNATTACHED
            or (state0["state"] == VOTED and st["votedFor"][i] == j)
        ) and log_ok
        final = (
            self._to_voted(st, i, d["mepoch"], state0)
            if grant and state0["state"] == UNATTACHED
            else state0
        )
        resp = rec(
            mtype="RequestVoteResponse",
            mepoch=d["mepoch"],
            mleader=final["leader"],
            mvoteGranted=grant,
            merror=None,
            msource=i,
            mdest=j,
        )
        msgs = self._reply(self._msgs(st), resp, m)
        if msgs is None:
            return None
        extra = {}
        if grant:
            extra["votedFor"] = self._set(st["votedFor"], i, j)
        # IF state # state' THEN reset pendingFetch (KRaft.tla:495-497)
        clear = final["state"] != st["state"][i]
        return self._apply_transition(
            st, i, final, clear_pending=clear, messages=msgs, **extra
        )

    def handle_request_vote_response(self, st, m):
        """HandleRequestVoteResponse — KRaft.tla:519-541."""
        if not self._receivable(st, m, "RequestVoteResponse", equal_epoch=False):
            return None
        d = dict(m)
        i, j = d["mdest"], d["msource"]
        new = self._maybe_handle_common_response(
            st, i, d["mleader"], d["mepoch"], d["merror"]
        )
        msgs = self._discard(self._msgs(st), m)
        if new["handled"]:
            return self._apply_transition(st, i, new, messages=msgs)
        if st["state"][i] != CANDIDATE:
            return None
        vg = st["votesGranted"][i] | {j} if d["mvoteGranted"] else st["votesGranted"][i]
        return self._with(
            st, votesGranted=self._set(st["votesGranted"], i, vg), messages=msgs
        )

    def become_leader(self, st, i):
        """BecomeLeader(i) — KRaft.tla:546-558."""
        if st["state"][i] != CANDIDATE:
            return None
        if 2 * len(st["votesGranted"][i]) <= self.S:
            return None
        reqs = {
            rec(
                mtype="BeginQuorumRequest",
                mepoch=st["currentEpoch"][i],
                msource=i,
                mdest=j,
            )
            for j in range(self.S)
            if j != i
        }
        msgs = self._send_multiple_once(self._msgs(st), reqs)
        if msgs is None:
            return None
        return self._with(
            st,
            state=self._set(st["state"], i, LEADER),
            leader=self._set(st["leader"], i, i),
            endOffset=self._set(st["endOffset"], i, (0,) * self.S),
            messages=msgs,
        )

    def handle_begin_quorum_request(self, st, m):
        """HandleBeginQuorumRequest — KRaft.tla:563-590."""
        if not self._receivable(st, m, "BeginQuorumRequest", equal_epoch=False):
            return None
        d = dict(m)
        i, j = d["mdest"], d["msource"]
        error = FENCED if d["mepoch"] < st["currentEpoch"][i] else None
        if error is None:
            new = self._maybe_transition(st, i, j, d["mepoch"])
            resp = rec(
                mtype="BeginQuorumResponse",
                mepoch=d["mepoch"],
                msource=i,
                mdest=j,
                merror=None,
            )
            msgs = self._reply(self._msgs(st), resp, m)
            if msgs is None:
                return None
            return self._apply_transition(
                st, i, new, clear_pending=True, messages=msgs
            )
        resp = rec(
            mtype="BeginQuorumResponse",
            mepoch=st["currentEpoch"][i],
            msource=i,
            mdest=j,
            merror=error,
        )
        msgs = self._reply(self._msgs(st), resp, m)
        if msgs is None:
            return None
        return self._with(st, messages=msgs)

    def client_request(self, st, i, v):
        """ClientRequest(i, v) — KRaft.tla:594-603."""
        if st["state"][i] != LEADER or st["acked"][v] is not None:
            return None
        entry = (st["currentEpoch"][i], v)
        return self._with(
            st,
            log=self._set(st["log"], i, st["log"][i] + (entry,)),
            acked=self._set(st["acked"], v, False),
        )

    def send_fetch_request(self, st, i, j):
        """SendFetchRequest(i, j) — KRaft.tla:607-624."""
        if st["state"][i] != FOLLOWER:
            return None
        if st["leader"][i] != j or st["pendingFetch"][i] is not None:
            return None
        fetch = rec(
            mtype="FetchRequest",
            mepoch=st["currentEpoch"][i],
            mfetchOffset=len(st["log"][i]),
            mlastFetchedEpoch=last_epoch(st["log"][i]),
            msource=i,
            mdest=j,
        )
        msgs = self._send(self._msgs(st), fetch)
        if msgs is None:
            return None
        return self._with(
            st,
            pendingFetch=self._set(st["pendingFetch"], i, fetch),
            messages=msgs,
        )

    def reject_fetch_request(self, st, m):
        """RejectFetchRequest — KRaft.tla:631-651."""
        if not self._receivable(st, m, "FetchRequest", equal_epoch=False):
            return None
        d = dict(m)
        i, j = d["mdest"], d["msource"]
        if st["state"][i] != LEADER:
            error = NOT_LEADER
        elif d["mepoch"] < st["currentEpoch"][i]:
            error = FENCED
        elif d["mepoch"] > st["currentEpoch"][i]:
            error = UNKNOWN_LEADER
        else:
            return None
        resp = rec(
            mtype="FetchResponse",
            mresult=NOT_OK,
            merror=error,
            mleader=st["leader"][i],
            mepoch=st["currentEpoch"][i],
            mhwm=st["highWatermark"][i],
            msource=i,
            mdest=j,
            correlation=m,
        )
        msgs = self._reply(self._msgs(st), resp, m)
        if msgs is None:
            return None
        return self._with(st, messages=msgs)

    def diverging_fetch_request(self, st, m):
        """DivergingFetchRequest — KRaft.tla:658-679."""
        if not self._receivable(st, m, "FetchRequest", equal_epoch=True):
            return None
        d = dict(m)
        i, j = d["mdest"], d["msource"]
        if st["state"][i] != LEADER or self._valid_fetch_position(st, i, m):
            return None
        off, ep = end_offset_for_epoch(st["log"][i], d["mlastFetchedEpoch"])
        resp = rec(
            mtype="FetchResponse",
            mepoch=st["currentEpoch"][i],
            mresult=DIVERGING,
            merror=None,
            mdivergingEpoch=ep,
            mdivergingEndOffset=off,
            mleader=st["leader"][i],
            mhwm=st["highWatermark"][i],
            msource=i,
            mdest=j,
            correlation=m,
        )
        msgs = self._reply(self._msgs(st), resp, m)
        if msgs is None:
            return None
        return self._with(st, messages=msgs)

    def _new_highwatermark(self, st, i, new_end_offset) -> int:
        """NewHighwaterMark — KRaft.tla:689-701."""
        best = 0
        for off in range(1, len(st["log"][i]) + 1):
            agree = {i} | {k for k in range(self.S) if new_end_offset[k] >= off}
            if 2 * len(agree) > self.S:
                best = off
        if best > 0 and st["log"][i][best - 1][0] == st["currentEpoch"][i]:
            return best
        return st["highWatermark"][i]

    def accept_fetch_request(self, st, m):
        """AcceptFetchRequest — KRaft.tla:703-736."""
        if not self._receivable(st, m, "FetchRequest", equal_epoch=True):
            return None
        d = dict(m)
        i, j = d["mdest"], d["msource"]
        if st["state"][i] != LEADER or not self._valid_fetch_position(st, i, m):
            return None
        offset = d["mfetchOffset"] + 1
        entries = (
            () if offset > len(st["log"][i]) else (st["log"][i][offset - 1],)
        )
        new_end = self._set(st["endOffset"][i], j, d["mfetchOffset"])
        new_hwm = self._new_highwatermark(st, i, new_end)
        committed_vals = {
            st["log"][i][ind - 1][1]
            for ind in range(st["highWatermark"][i] + 1, new_hwm + 1)
        }
        acked = tuple(
            (v in committed_vals) if st["acked"][v] is False else st["acked"][v]
            for v in range(self.V)
        )
        resp = rec(
            mtype="FetchResponse",
            mepoch=st["currentEpoch"][i],
            mleader=st["leader"][i],
            mresult=OK,
            merror=None,
            mentries=entries,
            mhwm=min(new_hwm, offset),
            msource=i,
            mdest=j,
            correlation=m,
        )
        msgs = self._reply(self._msgs(st), resp, m)
        if msgs is None:
            return None
        return self._with(
            st,
            endOffset=self._set(st["endOffset"], i, new_end),
            highWatermark=self._set(st["highWatermark"], i, new_hwm),
            acked=acked,
            messages=msgs,
        )

    def handle_success_fetch_response(self, st, m):
        """HandleSuccessFetchResponse — KRaft.tla:742-757."""
        if not self._receivable(st, m, "FetchResponse", equal_epoch=False):
            return None
        d = dict(m)
        i = d["mdest"]
        new = self._maybe_handle_common_response(
            st, i, d["mleader"], d["mepoch"], d["merror"]
        )
        if new["handled"] or st["pendingFetch"][i] != d["correlation"]:
            return None
        if d["mresult"] != OK:
            return None
        log_i = st["log"][i]
        if len(d["mentries"]) > 0:
            log_i = log_i + (d["mentries"][0],)
        return self._with(
            st,
            highWatermark=self._set(st["highWatermark"], i, d["mhwm"]),
            log=self._set(st["log"], i, log_i),
            pendingFetch=self._set(st["pendingFetch"], i, None),
            messages=self._discard(self._msgs(st), m),
        )

    def handle_diverging_fetch_response(self, st, m):
        """HandleDivergingFetchResponse — KRaft.tla:766-780."""
        if not self._receivable(st, m, "FetchResponse", equal_epoch=False):
            return None
        d = dict(m)
        i = d["mdest"]
        new = self._maybe_handle_common_response(
            st, i, d["mleader"], d["mepoch"], d["merror"]
        )
        if new["handled"] or st["pendingFetch"][i] != d["correlation"]:
            return None
        if d["mresult"] != DIVERGING:
            return None
        return self._with(
            st,
            log=self._set(st["log"], i, self._truncate_log(st, i, m)),
            pendingFetch=self._set(st["pendingFetch"], i, None),
            messages=self._discard(self._msgs(st), m),
        )

    def handle_error_fetch_response(self, st, m):
        """HandleErrorFetchResponse — KRaft.tla:786-801."""
        if not self._receivable(st, m, "FetchResponse", equal_epoch=False):
            return None
        d = dict(m)
        i = d["mdest"]
        new = self._maybe_handle_common_response(
            st, i, d["mleader"], d["mepoch"], d["merror"]
        )
        if not new["handled"] or st["pendingFetch"][i] != d["correlation"]:
            return None
        return self._apply_transition(
            st,
            i,
            new,
            clear_pending=True,
            messages=self._discard(self._msgs(st), m),
        )

    # ---------- VIEW + SYMMETRY ----------

    @staticmethod
    def _norm_rec(m) -> tuple:
        """Make record values totally orderable across None / bool / int /
        str / nested record (correlation) / entry tuples via type tags."""

        def norm_val(v):
            if v is None:
                return (0, 0)
            if isinstance(v, bool):
                return (1, int(v))
            if isinstance(v, int):
                return (2, v)
            if isinstance(v, str):
                return (3, v)
            if isinstance(v, tuple) and v and isinstance(v[0], tuple) and len(
                v[0]
            ) == 2 and isinstance(v[0][0], str):
                return (4, KRaftOracle._norm_rec(v))  # nested record
            return (5, v)

        return tuple((k, norm_val(v)) for k, v in m)

    def _ser_msgs(self, msgs) -> tuple:
        return tuple(sorted((self._norm_rec(m), c) for m, c in msgs))

    def serialize_view(self, st) -> tuple:
        """view — KRaft.tla:154: everything except electionCtr/restartCtr
        (acked IS included)."""
        ack = {None: -1, False: 0, True: 1}
        return (
            st["currentEpoch"],
            st["state"],
            tuple(-1 if v is None else v for v in st["votedFor"]),
            tuple(-1 if v is None else v for v in st["leader"]),
            tuple(
                () if pf is None else self._norm_rec(pf)
                for pf in st["pendingFetch"]
            ),
            tuple(tuple(sorted(vs)) for vs in st["votesGranted"]),
            st["endOffset"],
            st["log"],
            st["highWatermark"],
            self._ser_msgs(st["messages"]),
            tuple(ack[a] for a in st["acked"]),
        )

    def serialize_full(self, st) -> tuple:
        return self.serialize_view(st) + (st["electionCtr"], st["restartCtr"])

    def permute(self, st, sigma) -> dict:
        """Apply a server permutation (old -> new index)."""
        S = self.S
        inv = [0] * S
        for old, new in enumerate(sigma):
            inv[new] = old

        def prow(t):
            return tuple(t[inv[k]] for k in range(S))

        def pmsg(m):
            d = dict(m)
            d["msource"] = sigma[d["msource"]]
            d["mdest"] = sigma[d["mdest"]]
            if d.get("mleader") is not None:
                d["mleader"] = sigma[d["mleader"]]
            if "correlation" in d:
                d["correlation"] = pmsg(d["correlation"])
            return rec(**d)

        return self._with(
            st,
            currentEpoch=prow(st["currentEpoch"]),
            state=prow(st["state"]),
            votedFor=tuple(
                None if v is None else sigma[v] for v in prow(st["votedFor"])
            ),
            leader=tuple(None if v is None else sigma[v] for v in prow(st["leader"])),
            pendingFetch=tuple(
                None if pf is None else pmsg(pf) for pf in prow(st["pendingFetch"])
            ),
            votesGranted=tuple(
                frozenset(sigma[j] for j in vs) for vs in prow(st["votesGranted"])
            ),
            endOffset=tuple(prow(row) for row in prow(st["endOffset"])),
            log=prow(st["log"]),
            highWatermark=prow(st["highWatermark"]),
            messages=frozenset((pmsg(m), c) for m, c in st["messages"]),
        )

    def canon(self, st, symmetry: bool = True) -> tuple:
        if not symmetry:
            return self.serialize_view(st)
        return min(
            self.serialize_view(self.permute(st, list(sigma)))
            for sigma in itertools.permutations(range(self.S))
        )

    # ---------- invariants (KRaft.tla:884-957) ----------

    def no_illegal_state(self, st) -> bool:
        """NoIllegalState — KRaft.tla:887-889."""
        return all(s != ILLEGAL for s in st["state"])

    def no_log_divergence(self, st) -> bool:
        """NoLogDivergence — KRaft.tla:894-907 (common prefix up to the
        MINIMUM highWatermark, not commitIndex)."""
        for s1 in range(self.S):
            for s2 in range(self.S):
                if s1 == s2:
                    continue
                hwm = min(st["highWatermark"][s1], st["highWatermark"][s2])
                for off in range(1, hwm + 1):
                    if st["log"][s1][off - 1] != st["log"][s2][off - 1]:
                        return False
        return True

    def never_two_leaders_in_same_epoch(self, st) -> bool:
        """NeverTwoLeadersInSameEpoch — KRaft.tla:916-921 (conflicting
        leader BELIEFS at equal epochs)."""
        for i in range(self.S):
            for j in range(self.S):
                if (
                    st["leader"][i] is not None
                    and st["leader"][j] is not None
                    and st["leader"][i] != st["leader"][j]
                    and st["currentEpoch"][i] == st["currentEpoch"][j]
                ):
                    return False
        return True

    def leader_has_all_acked_values(self, st) -> bool:
        """LeaderHasAllAckedValues — KRaft.tla:925-941."""
        for v in range(self.V):
            if st["acked"][v] is not True:
                continue
            for i in range(self.S):
                if st["state"][i] != LEADER:
                    continue
                if any(
                    st["currentEpoch"][l] > st["currentEpoch"][i]
                    for l in range(self.S)
                    if l != i
                ):
                    continue
                if not any(e[1] == v for e in st["log"][i]):
                    return False
        return True

    def committed_entries_reach_majority(self, st) -> bool:
        """CommittedEntriesReachMajority — KRaft.tla:946-957."""
        leaders = [
            i
            for i in range(self.S)
            if st["state"][i] == LEADER and st["highWatermark"][i] > 0
        ]
        if not leaders:
            return True
        need = self.S // 2 + 1
        for i in leaders:
            hwm = st["highWatermark"][i]
            entry = st["log"][i][hwm - 1]
            n = sum(
                1
                for j in range(self.S)
                if len(st["log"][j]) >= hwm and st["log"][j][hwm - 1] == entry
            )
            if n >= need:
                return True
        return False

    INVARIANTS = {
        "NoIllegalState": no_illegal_state,
        "NoLogDivergence": no_log_divergence,
        "NeverTwoLeadersInSameEpoch": never_two_leaders_in_same_epoch,
        "LeaderHasAllAckedValues": leader_has_all_acked_values,
        "CommittedEntriesReachMajority": committed_entries_reach_majority,
        "TestInv": lambda self, st: True,
    }

    # ---------- BFS ----------

    def bfs(
        self,
        invariants: tuple[str, ...] = (
            "LeaderHasAllAckedValues",
            "NoLogDivergence",
            "NeverTwoLeadersInSameEpoch",
            "NoIllegalState",
        ),
        symmetry: bool = True,
        max_depth: int | None = None,
        max_states: int | None = None,
        time_budget_s: float | None = None,
    ) -> dict:
        import time

        t0 = time.perf_counter()
        init = self.init_state()
        seen = {self.canon(init, symmetry)}
        frontier = [init]
        total = 1
        distinct = 1
        depth_counts = [1]
        terminal = 0  # expanded states with no successor (`-deadlock`)
        violation = None
        depth = 0
        while frontier and violation is None:
            if max_depth is not None and depth >= max_depth:
                break
            if time_budget_s is not None and time.perf_counter() - t0 > time_budget_s:
                break
            next_frontier = []
            for st in frontier:
                succs = self.successors(st)
                terminal += not succs
                for _label, s2 in succs:
                    total += 1
                    key = self.canon(s2, symmetry)
                    if key in seen:
                        continue
                    seen.add(key)
                    distinct += 1
                    for inv in invariants:
                        if not self.INVARIANTS[inv](self, s2):
                            violation = {
                                "invariant": inv,
                                "state": s2,
                                "depth": depth + 1,
                            }
                            break
                    next_frontier.append(s2)
                    if violation or (max_states and distinct >= max_states):
                        break
                if violation or (max_states and distinct >= max_states):
                    break
                if (
                    time_budget_s is not None
                    and (total & 0x3FF) < 8
                    and time.perf_counter() - t0 > time_budget_s
                ):
                    break
            frontier = next_frontier
            if frontier:
                depth_counts.append(len(frontier))
            depth += 1
        return {
            "distinct": distinct,
            "total": total,
            "depth_counts": depth_counts,
            "terminal": terminal,
            "violation": violation,
        }
