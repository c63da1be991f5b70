"""Independent pure-Python interpreter of standard-raft/Raft.tla.

This is the differential-testing ground truth for the TPU kernels (TLC is
an external Java tool and is not vendored; see SURVEY.md §4). It is written
directly against the TLA+ text — NOT against the JAX lowering — so that the
two implementations only agree if both match the spec.

State format (shared with RaftModel.decode/encode): a dict of
  currentTerm: tuple[int], state: tuple[int 0/1/2], votedFor: tuple[int|None],
  votesGranted: tuple[frozenset[int]], log: tuple[tuple[(term, value)]],
  commitIndex: tuple[int], nextIndex/matchIndex: tuple[tuple[int]],
  pendingResponse: tuple[tuple[bool]], messages: frozenset[(record, count)],
  acked: tuple[None|False|True], electionCtr: int, restartCtr: int
with servers and values as 0-based ints and message records as tuples of
sorted (field, value) pairs.
"""

from __future__ import annotations

import itertools

FOLLOWER, CANDIDATE, LEADER = 0, 1, 2


def oracle_for(params) -> "RaftOracle":
    """Build the oracle matching a models.raft.RaftParams (same variant knobs)."""
    return RaftOracle(
        params.n_servers,
        params.n_values,
        params.max_elections,
        params.max_restarts,
        election_quorum=params.election_quorum,
        replication_quorum=params.replication_quorum,
        strict_send_once=params.strict_send_once,
        has_pending_response=params.has_pending_response,
        trunc_term_mismatch=params.trunc_term_mismatch,
        has_fsync=params.has_fsync,
        fsync_leader_before_ae=params.fsync_leader_before_ae,
        fsync_leader_quorum=params.fsync_leader_quorum,
        fsync_follower_reply=params.fsync_follower_reply,
    )


def rec(**kw) -> tuple:
    return tuple(sorted(kw.items()))


def _last_term(log) -> int:
    """LastTerm(xlog) — Raft.tla:126."""
    return log[-1][0] if log else 0


class RaftOracle:
    """Variant knobs (defaults = standard Raft; see RaftParams in
    models/raft.py for the FlexibleRaft sources):
    count-based quorums, strict send-once messaging, absent
    pendingResponse, term-mismatch NeedsTruncation."""

    def __init__(
        self,
        n_servers: int,
        n_values: int,
        max_elections: int,
        max_restarts: int,
        election_quorum: int | None = None,
        replication_quorum: int | None = None,
        strict_send_once: bool = False,
        has_pending_response: bool = True,
        trunc_term_mismatch: bool = False,
        has_fsync: bool = False,
        fsync_leader_before_ae: bool = False,
        fsync_leader_quorum: bool = False,
        fsync_follower_reply: bool = False,
    ):
        self.S = n_servers
        self.V = n_values
        self.max_elections = max_elections
        self.max_restarts = max_restarts
        self.election_quorum = election_quorum
        self.replication_quorum = replication_quorum
        self.strict_send_once = strict_send_once
        self.has_pending_response = has_pending_response
        self.trunc_term_mismatch = trunc_term_mismatch
        self.has_fsync = has_fsync
        self.fsync_leader_before_ae = fsync_leader_before_ae
        self.fsync_leader_quorum = fsync_leader_quorum
        self.fsync_follower_reply = fsync_follower_reply

    # ---------- state helpers ----------

    def init_state(self) -> dict:
        """Init — Raft.tla:213-218 (RaftFsync.tla:189-194 adds fsyncIndex)."""
        S, V = self.S, self.V
        extra = {"fsyncIndex": (0,) * S} if self.has_fsync else {}
        return extra | {
            "currentTerm": (1,) * S,
            "state": (FOLLOWER,) * S,
            "votedFor": (None,) * S,
            "votesGranted": (frozenset(),) * S,
            "log": ((),) * S,
            "commitIndex": (0,) * S,
            "nextIndex": ((1,) * S,) * S,
            "matchIndex": ((0,) * S,) * S,
            "pendingResponse": ((False,) * S,) * S,
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
        lst = list(tup)
        lst[i] = val
        return tuple(lst)

    @classmethod
    def _set2(cls, mat, i, j, val) -> tuple:
        return cls._set(mat, i, cls._set(mat[i], j, val))

    # ---------- message-bag helpers (Raft.tla:129-176) ----------

    @staticmethod
    def _send_no_restriction(msgs, m):
        msgs = dict(msgs)
        msgs[m] = msgs.get(m, 0) + 1
        return msgs

    @staticmethod
    def _send_once(msgs, m):
        if m in msgs:  # in DOMAIN (even at count 0): permanently disabled
            return None
        msgs = dict(msgs)
        msgs[m] = 1
        return msgs

    def _send(self, msgs, m):
        """Send — Raft.tla:145-149: empty AppendEntriesRequest is send-once.
        FlexibleRaft (FlexibleRaft.tla:127-129): everything is send-once."""
        if self.strict_send_once:
            return self._send_once(msgs, m)
        d = dict(m)
        if d["mtype"] == "AppendEntriesRequest" and d["mentries"] == ():
            return self._send_once(msgs, m)
        return self._send_no_restriction(msgs, m)

    @staticmethod
    def _send_multiple_once(msgs, ms):
        if any(m in msgs for m in ms):
            return None
        msgs = dict(msgs)
        for m in ms:
            msgs[m] = 1
        return msgs

    def _reply(self, msgs, response, request):
        """Reply — Raft.tla:170-176. FlexibleRaft (FlexibleRaft.tla:148-151)
        is disabled (None) when the response already exists."""
        assert msgs.get(request, 0) > 0
        if self.strict_send_once and response in msgs:
            return None
        msgs = dict(msgs)
        msgs[request] -= 1
        msgs[response] = msgs.get(response, 0) + 1
        return msgs

    @staticmethod
    def _discard(msgs, m):
        assert msgs.get(m, 0) > 0
        msgs = dict(msgs)
        msgs[m] -= 1
        return msgs

    def _receivable(self, st, m, mtype: str, equal_term: bool) -> bool:
        """ReceivableMessage — Raft.tla:181-187."""
        msgs = self._msgs(st)
        if msgs.get(m, 0) <= 0:
            return False
        d = dict(m)
        if d["mtype"] != mtype:
            return False
        ct = st["currentTerm"][d["mdest"]]
        return d["mterm"] == ct if equal_term else d["mterm"] <= ct

    def _domain(self, st):
        """DOMAIN messages (count-0 records included), deterministic order."""
        return sorted(dict(st["messages"]).keys())

    # ---------- actions (Next order, Raft.tla:527-539) ----------

    def successors(self, st) -> list[tuple[str, dict]]:
        out = []
        S, V = self.S, self.V
        for i in range(S):
            s2 = self.restart(st, i)
            if s2 is not None:
                out.append((f"Restart({i})", s2))
        if self.has_fsync:
            # RaftFsync Next order (RaftFsync.tla:522-536)
            for i in range(S):
                s2 = self.timeout(st, i)
                if s2 is not None:
                    out.append((f"Timeout({i})", s2))
            for i in range(S):
                for j in range(S):
                    if i != j:
                        s2 = self.request_vote_pair(st, i, j)
                        if s2 is not None:
                            out.append((f"RequestVote({i},{j})", s2))
        else:
            for i in range(S):
                s2 = self.request_vote(st, i)
                if s2 is not None:
                    out.append((f"RequestVote({i})", s2))
        for i in range(S):
            s2 = self.become_leader(st, i)
            if s2 is not None:
                out.append((f"BecomeLeader({i})", s2))
        for i in range(S):
            for v in range(V):
                s2 = self.client_request(st, i, v)
                if s2 is not None:
                    out.append((f"ClientRequest({i},{v})", s2))
        for i in range(S):
            s2 = self.advance_commit_index(st, i)
            if s2 is not None:
                out.append((f"AdvanceCommitIndex({i})", s2))
        for i in range(S):
            for j in range(S):
                if i != j:
                    s2 = self.append_entries(st, i, j)
                    if s2 is not None:
                        out.append((f"AppendEntries({i},{j})", s2))
        if self.has_fsync:
            for i in range(S):
                s2 = self.advance_fsync_index(st, i)
                if s2 is not None:
                    out.append((f"AdvanceFsyncIndex({i})", s2))
        for m in self._domain(st):
            s2 = self.update_term(st, m)
            if s2 is not None:
                out.append((f"UpdateTerm[{dict(m)['mdest']}]", s2))
        for m in self._domain(st):
            s2 = self.handle_request_vote_request(st, m)
            if s2 is not None:
                out.append(("HandleRequestVoteRequest", s2))
        for m in self._domain(st):
            s2 = self.handle_request_vote_response(st, m)
            if s2 is not None:
                out.append(("HandleRequestVoteResponse", s2))
        for m in self._domain(st):
            s2 = self.reject_append_entries_request(st, m)
            if s2 is not None:
                out.append(("RejectAppendEntriesRequest", s2))
        for m in self._domain(st):
            s2 = self.accept_append_entries_request(st, m)
            if s2 is not None:
                out.append(("AcceptAppendEntriesRequest", s2))
        for m in self._domain(st):
            s2 = self.handle_append_entries_response(st, m)
            if s2 is not None:
                out.append(("HandleAppendEntriesResponse", s2))
        return out

    def restart(self, st, i):
        """Restart(i) — Raft.tla:226-235; RaftFsync.tla:203-218 truncates
        the log to fsyncIndex."""
        if st["restartCtr"] >= self.max_restarts:
            return None
        S = self.S
        extra = {}
        if self.has_fsync:
            fi = st["fsyncIndex"][i]
            log_i = st["log"][i]
            if fi == 0:
                new_log = ()
            elif len(log_i) > 0 and len(log_i) > fi:
                new_log = log_i[:fi]
            else:
                new_log = log_i
            extra["log"] = self._set(st["log"], i, new_log)
        return self._with(
            st,
            state=self._set(st["state"], i, FOLLOWER),
            votesGranted=self._set(st["votesGranted"], i, frozenset()),
            nextIndex=self._set(st["nextIndex"], i, (1,) * S),
            matchIndex=self._set(st["matchIndex"], i, (0,) * S),
            pendingResponse=self._set(st["pendingResponse"], i, (False,) * S),
            commitIndex=self._set(st["commitIndex"], i, 0),
            restartCtr=st["restartCtr"] + 1,
            **extra,
        )

    def timeout(self, st, i):
        """Timeout(i) — RaftFsync.tla:222-230."""
        if st["electionCtr"] >= self.max_elections:
            return None
        if st["state"][i] not in (FOLLOWER, CANDIDATE):
            return None
        return self._with(
            st,
            state=self._set(st["state"], i, CANDIDATE),
            currentTerm=self._set(st["currentTerm"], i, st["currentTerm"][i] + 1),
            votedFor=self._set(st["votedFor"], i, i),
            votesGranted=self._set(st["votesGranted"], i, frozenset({i})),
            electionCtr=st["electionCtr"] + 1,
        )

    def request_vote_pair(self, st, i, j):
        """RequestVote(i, j) — RaftFsync.tla:234-243."""
        if i == j or st["state"][i] != CANDIDATE:
            return None
        m = rec(
            mtype="RequestVoteRequest",
            mterm=st["currentTerm"][i],
            mlastLogTerm=_last_term(st["log"][i]),
            mlastLogIndex=len(st["log"][i]),
            msource=i,
            mdest=j,
        )
        msgs = self._send_once(self._msgs(st), m)  # Send (RaftFsync.tla:132-134)
        if msgs is None:
            return None
        return self._with(st, messages=frozenset(msgs.items()))

    def advance_fsync_index(self, st, i):
        """AdvanceFsyncIndex(i) — RaftFsync.tla:339-343."""
        if st["fsyncIndex"][i] >= len(st["log"][i]):
            return None
        return self._with(
            st, fsyncIndex=self._set(st["fsyncIndex"], i, st["fsyncIndex"][i] + 1)
        )

    def request_vote(self, st, i):
        """RequestVote(i) — Raft.tla:242-257."""
        if st["electionCtr"] >= self.max_elections:
            return None
        if st["state"][i] not in (FOLLOWER, CANDIDATE):
            return None
        new_term = st["currentTerm"][i] + 1
        ms = {
            rec(
                mtype="RequestVoteRequest",
                mterm=new_term,
                mlastLogTerm=_last_term(st["log"][i]),
                mlastLogIndex=len(st["log"][i]),
                msource=i,
                mdest=j,
            )
            for j in range(self.S)
            if j != i
        }
        msgs = self._send_multiple_once(self._msgs(st), ms)
        if msgs is None:
            return None
        return self._with(
            st,
            state=self._set(st["state"], i, CANDIDATE),
            currentTerm=self._set(st["currentTerm"], i, new_term),
            votedFor=self._set(st["votedFor"], i, i),
            votesGranted=self._set(st["votesGranted"], i, frozenset({i})),
            electionCtr=st["electionCtr"] + 1,
            messages=frozenset(msgs.items()),
        )

    def become_leader(self, st, i):
        """BecomeLeader(i) — Raft.tla:289-300."""
        if st["state"][i] != CANDIDATE:
            return None
        if self.election_quorum is not None:
            if len(st["votesGranted"][i]) < self.election_quorum:
                return None  # FlexibleRaft.tla:262
        elif 2 * len(st["votesGranted"][i]) <= self.S:  # Quorum (Raft.tla:123)
            return None
        S = self.S
        n = len(st["log"][i]) + 1
        return self._with(
            st,
            state=self._set(st["state"], i, LEADER),
            nextIndex=self._set(st["nextIndex"], i, (n,) * S),
            matchIndex=self._set(st["matchIndex"], i, (0,) * S),
            pendingResponse=self._set(st["pendingResponse"], i, (False,) * S),
        )

    def client_request(self, st, i, v):
        """ClientRequest(i, v) — Raft.tla:304-313."""
        if st["state"][i] != LEADER or st["acked"][v] is not None:
            return None
        entry = (st["currentTerm"][i], v)
        return self._with(
            st,
            log=self._set(st["log"], i, st["log"][i] + (entry,)),
            acked=self._set(st["acked"], v, False),
        )

    def advance_commit_index(self, st, i):
        """AdvanceCommitIndex(i) — Raft.tla:320-344."""
        if st["state"][i] != LEADER:
            return None
        S = self.S
        log_i = st["log"][i]
        mi = st["matchIndex"][i]
        def _quorum(n: int) -> bool:
            if self.replication_quorum is not None:
                return n >= self.replication_quorum  # FlexibleRaft.tla:296
            return 2 * n > S

        def _agree(idx: int) -> set:
            """Agree(index) — Raft.tla:323-324; RaftFsync.tla:313-315
            excludes the leader itself above its fsyncIndex."""
            base = {k for k in range(S) if mi[k] >= idx}
            if (
                self.has_fsync
                and self.fsync_leader_quorum
                and idx > st["fsyncIndex"][i]
            ):
                return base
            return {i} | base

        agree_indexes = [
            idx for idx in range(1, len(log_i) + 1) if _quorum(len(_agree(idx)))
        ]
        ci = st["commitIndex"][i]
        if agree_indexes and log_i[max(agree_indexes) - 1][0] == st["currentTerm"][i]:
            new_ci = max(agree_indexes)
        else:
            new_ci = ci
        if ci >= new_ci:
            return None
        committed_vals = {log_i[idx - 1][1] for idx in range(ci + 1, new_ci + 1)}
        acked = tuple(
            (v in committed_vals) if st["acked"][v] is False else st["acked"][v]
            for v in range(self.V)
        )
        return self._with(
            st, commitIndex=self._set(st["commitIndex"], i, new_ci), acked=acked
        )

    def append_entries(self, st, i, j):
        """AppendEntries(i, j) — Raft.tla:263-285."""
        if i == j or st["state"][i] != LEADER:
            return None
        if self.has_pending_response and st["pendingResponse"][i][j]:
            return None
        log_i = st["log"][i]
        ni = st["nextIndex"][i][j]
        prev_index = ni - 1
        prev_term = log_i[prev_index - 1][0] if prev_index > 0 else 0
        last_entry = min(len(log_i), ni)
        entries = tuple(log_i[ni - 1 : last_entry])
        if self.has_fsync and self.fsync_leader_before_ae:
            # LeaderFsyncBeforeAppendEntries gate (RaftFsync.tla:261-263)
            if st["fsyncIndex"][i] < last_entry:
                return None
        m = rec(
            mtype="AppendEntriesRequest",
            mterm=st["currentTerm"][i],
            mprevLogIndex=prev_index,
            mprevLogTerm=prev_term,
            mentries=entries,
            mcommitIndex=min(st["commitIndex"][i], last_entry),
            msource=i,
            mdest=j,
        )
        msgs = self._send(self._msgs(st), m)
        if msgs is None:
            return None
        pending = st["pendingResponse"]
        if self.has_pending_response:
            pending = self._set2(pending, i, j, True)
        return self._with(
            st, pendingResponse=pending, messages=frozenset(msgs.items())
        )

    def update_term(self, st, m):
        """UpdateTerm — Raft.tla:348-355 (any DOMAIN record, count-0 included)."""
        d = dict(m)
        i = d["mdest"]
        if d["mterm"] <= st["currentTerm"][i]:
            return None
        return self._with(
            st,
            currentTerm=self._set(st["currentTerm"], i, d["mterm"]),
            state=self._set(st["state"], i, FOLLOWER),
            votedFor=self._set(st["votedFor"], i, None),
        )

    def handle_request_vote_request(self, st, m):
        """HandleRequestVoteRequest — Raft.tla:360-381."""
        if not self._receivable(st, m, "RequestVoteRequest", equal_term=False):
            return None
        d = dict(m)
        i, j = d["mdest"], d["msource"]
        log_ok = d["mlastLogTerm"] > _last_term(st["log"][i]) or (
            d["mlastLogTerm"] == _last_term(st["log"][i])
            and d["mlastLogIndex"] >= len(st["log"][i])
        )
        grant = (
            d["mterm"] == st["currentTerm"][i]
            and log_ok
            and st["votedFor"][i] in (None, j)
        )
        resp = rec(
            mtype="RequestVoteResponse",
            mterm=st["currentTerm"][i],
            mvoteGranted=grant,
            msource=i,
            mdest=j,
        )
        msgs = self._reply(self._msgs(st), resp, m)
        if msgs is None:
            return None
        return self._with(
            st,
            votedFor=self._set(st["votedFor"], i, j) if grant else st["votedFor"],
            messages=frozenset(msgs.items()),
        )

    def handle_request_vote_response(self, st, m):
        """HandleRequestVoteResponse — Raft.tla:386-401."""
        if not self._receivable(st, m, "RequestVoteResponse", equal_term=True):
            return None
        d = dict(m)
        i, j = d["mdest"], d["msource"]
        vg = st["votesGranted"]
        if d["mvoteGranted"]:
            vg = self._set(vg, i, vg[i] | {j})
        msgs = self._discard(self._msgs(st), m)
        return self._with(st, votesGranted=vg, messages=frozenset(msgs.items()))

    def _log_ok(self, st, d) -> bool:
        """LogOk — Raft.tla:406-410."""
        i = d["mdest"]
        return d["mprevLogIndex"] == 0 or (
            0 < d["mprevLogIndex"] <= len(st["log"][i])
            and d["mprevLogTerm"] == st["log"][i][d["mprevLogIndex"] - 1][0]
        )

    def reject_append_entries_request(self, st, m):
        """RejectAppendEntriesRequest — Raft.tla:412-430."""
        if not self._receivable(st, m, "AppendEntriesRequest", equal_term=False):
            return None
        d = dict(m)
        i, j = d["mdest"], d["msource"]
        ct = st["currentTerm"][i]
        if not (
            d["mterm"] < ct
            or (
                d["mterm"] == ct
                and st["state"][i] == FOLLOWER
                and not self._log_ok(st, d)
            )
        ):
            return None
        resp = rec(
            mtype="AppendEntriesResponse",
            mterm=ct,
            msuccess=False,
            mmatchIndex=0,
            msource=i,
            mdest=j,
        )
        msgs = self._reply(self._msgs(st), resp, m)
        if msgs is None:
            return None
        return self._with(st, messages=frozenset(msgs.items()))

    def accept_append_entries_request(self, st, m):
        """AcceptAppendEntriesRequest — Raft.tla:454-485."""
        if not self._receivable(st, m, "AppendEntriesRequest", equal_term=True):
            return None
        d = dict(m)
        i, j = d["mdest"], d["msource"]
        if st["state"][i] not in (FOLLOWER, CANDIDATE) or not self._log_ok(st, d):
            return None
        log_i = st["log"][i]
        prev = d["mprevLogIndex"]
        index = prev + 1
        entries = d["mentries"]
        can_append = entries != () and len(log_i) == prev  # CanAppend (Raft.tla:438-440)
        if self.trunc_term_mismatch:
            # NeedsTruncation (FlexibleRaft.tla:413-416)
            needs_trunc = (
                entries != ()
                and len(log_i) >= index
                and log_i[index - 1][0] != entries[0][0]
            )
        else:
            needs_trunc = (entries != () and len(log_i) >= index) or (
                entries == () and len(log_i) > prev
            )  # NeedsTruncation (Raft.tla:445-449)
        if can_append:
            new_log = log_i + (entries[0],)
        elif needs_trunc and entries != ():
            new_log = log_i[:prev] + (entries[0],)
        elif needs_trunc:
            new_log = log_i[:prev]
        else:
            new_log = log_i
        resp = rec(
            mtype="AppendEntriesResponse",
            mterm=st["currentTerm"][i],
            msuccess=True,
            mmatchIndex=prev + len(entries),
            msource=i,
            mdest=j,
        )
        msgs = self._reply(self._msgs(st), resp, m)
        if msgs is None:
            return None
        extra = {}
        if self.has_fsync and self.fsync_follower_reply:
            # fsyncIndex := Len(new_log) (RaftFsync.tla:468-470)
            extra["fsyncIndex"] = self._set(st["fsyncIndex"], i, len(new_log))
        return self._with(
            st,
            state=self._set(st["state"], i, FOLLOWER),
            commitIndex=self._set(st["commitIndex"], i, d["mcommitIndex"]),
            log=self._set(st["log"], i, new_log),
            messages=frozenset(msgs.items()),
            **extra,
        )

    def handle_append_entries_response(self, st, m):
        """HandleAppendEntriesResponse — Raft.tla:490-505."""
        if not self._receivable(st, m, "AppendEntriesResponse", equal_term=True):
            return None
        d = dict(m)
        i, j = d["mdest"], d["msource"]
        ni, mi = st["nextIndex"], st["matchIndex"]
        if d["msuccess"]:
            ni = self._set2(ni, i, j, d["mmatchIndex"] + 1)
            mi = self._set2(mi, i, j, d["mmatchIndex"])
        else:
            ni = self._set2(ni, i, j, max(ni[i][j] - 1, 1))
        msgs = self._discard(self._msgs(st), m)
        pending = st["pendingResponse"]
        if self.has_pending_response:
            pending = self._set2(pending, i, j, False)
        return self._with(
            st,
            nextIndex=ni,
            matchIndex=mi,
            pendingResponse=pending,
            messages=frozenset(msgs.items()),
        )

    # ---------- VIEW + SYMMETRY (Raft.tla:115-116) ----------

    def serialize_view(self, st) -> tuple:
        """Orderable serialization of the VIEW projection (drops aux vars).
        RaftFsync's view includes fsyncIndex (RaftFsync.tla:117)."""
        return ((st["fsyncIndex"],) if self.has_fsync else ()) + (
            st["currentTerm"],
            st["state"],
            tuple(-1 if v is None else v for v in st["votedFor"]),
            tuple(tuple(sorted(vs)) for vs in st["votesGranted"]),
            st["log"],
            st["commitIndex"],
            st["nextIndex"],
            st["matchIndex"],
            st["pendingResponse"],
            tuple(sorted(st["messages"])),
        )

    def serialize_full(self, st) -> tuple:
        """Orderable serialization of the FULL state (view + aux vars)."""
        ack = {None: -1, False: 0, True: 1}
        return self.serialize_view(st) + (
            tuple(ack[a] for a in st["acked"]),
            st["electionCtr"],
            st["restartCtr"],
        )

    def permute(self, st, sigma) -> dict:
        """Apply a server permutation (old index -> new index) to the state."""
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
            return rec(**d)

        extra = {"fsyncIndex": prow(st["fsyncIndex"])} if self.has_fsync else {}
        return self._with(
            st,
            currentTerm=prow(st["currentTerm"]),
            state=prow(st["state"]),
            **extra,
            votedFor=tuple(
                None if v is None else sigma[v] for v in prow(st["votedFor"])
            ),
            votesGranted=tuple(
                frozenset(sigma[j] for j in vs) for vs in prow(st["votesGranted"])
            ),
            log=prow(st["log"]),
            commitIndex=prow(st["commitIndex"]),
            nextIndex=tuple(prow(row) for row in prow(st["nextIndex"])),
            matchIndex=tuple(prow(row) for row in prow(st["matchIndex"])),
            pendingResponse=tuple(prow(row) for row in prow(st["pendingResponse"])),
            messages=frozenset(
                (pmsg(m), c) for m, c in st["messages"]
            ),
        )

    def canon(self, st, symmetry: bool = True) -> tuple:
        """Canonical dedup key: min over server permutations of the view."""
        if not symmetry:
            return self.serialize_view(st)
        return min(
            self.serialize_view(self.permute(st, list(sigma)))
            for sigma in itertools.permutations(range(self.S))
        )

    # ---------- invariants (Raft.tla:588-636) ----------

    def no_log_divergence(self, st) -> bool:
        for s1 in range(self.S):
            for s2 in range(self.S):
                if s1 == s2:
                    continue
                mci = min(st["commitIndex"][s1], st["commitIndex"][s2])
                for idx in range(1, mci + 1):
                    if st["log"][s1][idx - 1] != st["log"][s2][idx - 1]:
                        return False
        return True

    def leader_has_all_acked_values(self, st) -> bool:
        for v in range(self.V):
            if st["acked"][v] is not True:
                continue
            for i in range(self.S):
                if st["state"][i] != LEADER:
                    continue
                if any(
                    st["currentTerm"][l] > st["currentTerm"][i]
                    for l in range(self.S)
                    if l != i
                ):
                    continue
                if not any(e[1] == v for e in st["log"][i]):
                    return False
        return True

    def committed_entries_reach_majority(self, st) -> bool:
        leaders = [
            i
            for i in range(self.S)
            if st["state"][i] == LEADER and st["commitIndex"][i] > 0
        ]
        if not leaders:
            return True
        need = self.S // 2 + 1
        for i in leaders:
            ci = st["commitIndex"][i]
            entry = st["log"][i][ci - 1]
            n = sum(
                1
                for j in range(self.S)
                if len(st["log"][j]) >= ci and st["log"][j][ci - 1] == entry
            )
            if n >= need:
                return True
        return False

    INVARIANTS = {
        "NoLogDivergence": no_log_divergence,
        "LeaderHasAllAckedValues": leader_has_all_acked_values,
        "CommittedEntriesReachMajority": committed_entries_reach_majority,
        "TestInv": lambda self, st: True,
    }

    # ---------- BFS model checking ----------

    def bfs(
        self,
        invariants: tuple[str, ...] = ("LeaderHasAllAckedValues", "NoLogDivergence"),
        symmetry: bool = True,
        max_depth: int | None = None,
        max_states: int | None = None,
        time_budget_s: float | None = None,
    ) -> dict:
        """Exhaustive BFS with TLC semantics: dedup on the canonicalized
        VIEW, invariants checked on every distinct state."""
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
                            violation = {"invariant": inv, "state": s2, "depth": depth + 1}
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
