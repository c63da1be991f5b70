"""Independent pure-Python interpreter of pull-raft/PullRaft.tla and
pull-raft/PullRaftVariant2.tla.

Differential-testing ground truth for the TPU lowering in
models/pull_raft.py, written directly against the TLA+ text (reference
``/root/reference/specifications/pull-raft/PullRaft.tla``, 631 lines;
``PullRaftVariant2.tla``, 648 lines) — NOT against the JAX kernels.

Key structural deltas vs. core Raft (see SURVEY.md §2.1):
  - followers PULL from the leader (`SendPullEntriesRequest`), the leader
    never pushes;
  - `leader` replaces/augments `votedFor` (`PullRaft.tla:92`): in PullRaft a
    vote immediately sets `leader`; Variant2 keeps both (`:78,81`) and
    followers wait for a `LeaderNotifyRequest`;
  - ALL sends are strictly send-once (`PullRaft.tla:137-143`) and replies
    require the response to be absent (`:158-161`);
  - `view` includes `acked` in PullRaft (`PullRaft.tla:123`) but NOT in
    Variant2 (`PullRaftVariant2.tla:114`);
  - Variant2 tracks `votesLastEntry` (`PullRaftVariant2.tla:98`) so
    `BecomeLeader` can embed per-peer `mlastCommonEntry` in the notify
    (`:361-379`) and `LearnOfLeader` may truncate (`:398-410`).

State dict format (shared with PullRaftModel.decode/encode):
  currentTerm, state, leader (int|None per server), [votedFor (V2)],
  votesGranted (frozensets), [votesLastEntry (V2): tuple[tuple[None|(idx,term)]]],
  log, commitIndex, matchIndex, messages, acked, electionCtr, restartCtr.
"""

from __future__ import annotations

import itertools

FOLLOWER, CANDIDATE, LEADER = 0, 1, 2


def rec(**kw) -> tuple:
    return tuple(sorted(kw.items()))


def _last_term(log) -> int:
    """LastTerm(xlog) — PullRaft.tla:134."""
    return log[-1][0] if log else 0


def compare_entries(index1, term1, index2, term2) -> int:
    """CompareEntries — PullRaft.tla:203-207 (term precedence)."""
    if term1 > term2:
        return 1
    if term1 == term2 and index1 > index2:
        return 1
    if term1 == term2 and index1 == index2:
        return 0
    return -1


def last_common_entry(log_i, last_index, last_term) -> tuple[int, int]:
    """LastCommonEntry(i, lastIndex, lastTerm) — PullRaft.tla:211-226:
    the highest entry of log_i at-or-below (lastIndex, lastTerm) in the
    CompareEntries order; (0, 0) when none."""
    best = 0
    for idx in range(1, len(log_i) + 1):
        if compare_entries(idx, log_i[idx - 1][0], last_index, last_term) <= 0:
            best = idx
    if best == 0:
        return (0, 0)
    return (best, log_i[best - 1][0])


class PullRaftOracle:
    def __init__(
        self,
        n_servers: int,
        n_values: int,
        max_elections: int,
        max_restarts: int,
        variant2: bool = False,
    ):
        self.S = n_servers
        self.V = n_values
        self.max_elections = max_elections
        self.max_restarts = max_restarts
        self.variant2 = variant2

    # ---------- state helpers ----------

    def init_state(self) -> dict:
        """Init — PullRaft.tla:231-250 (Variant2: adds votedFor,
        votesLastEntry, PullRaftVariant2.tla:222-243)."""
        S, V = self.S, self.V
        extra = (
            {"votedFor": (None,) * S, "votesLastEntry": ((None,) * S,) * S}
            if self.variant2
            else {}
        )
        return extra | {
            "currentTerm": (1,) * S,
            "state": (FOLLOWER,) * S,
            "leader": (None,) * S,
            "votesGranted": (frozenset(),) * S,
            "log": ((),) * S,
            "commitIndex": (0,) * S,
            "matchIndex": ((0,) * S,) * S,
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

    # ---------- message-bag helpers (PullRaft.tla:137-172) ----------

    @staticmethod
    def _send(msgs, m):
        """Send — PullRaft.tla:137-139: strictly send-once."""
        if m in msgs:
            return None
        msgs = dict(msgs)
        msgs[m] = 1
        return msgs

    @staticmethod
    def _send_multiple(msgs, ms):
        """SendMultiple — PullRaft.tla:141-143: all must be absent."""
        if any(m in msgs for m in ms):
            return None
        msgs = dict(msgs)
        for m in ms:
            msgs[m] = 1
        return msgs

    @staticmethod
    def _reply(msgs, response, request):
        """Reply — PullRaft.tla:158-161: response must be absent."""
        assert msgs.get(request, 0) > 0
        if response in msgs:
            return None
        msgs = dict(msgs)
        msgs[request] -= 1
        msgs[response] = 1
        return msgs

    @staticmethod
    def _discard(msgs, m):
        """Discard — PullRaft.tla:152-155."""
        assert msgs.get(m, 0) > 0
        msgs = dict(msgs)
        msgs[m] -= 1
        return msgs

    def _receivable(self, st, m, mtype: str, equal_term: bool) -> bool:
        """ReceivableMessage — PullRaft.tla:166-172."""
        msgs = self._msgs(st)
        if msgs.get(m, 0) <= 0:
            return False
        d = dict(m)
        if d["mtype"] != mtype:
            return False
        ct = st["currentTerm"][d["mdest"]]
        return d["mterm"] == ct if equal_term else d["mterm"] <= ct

    def _domain(self, st):
        # sort on the None-normalized form: Variant2 notify records mix
        # mlastCommonEntry=None and (index, term), which are not orderable
        return sorted(
            dict(st["messages"]).keys(),
            key=lambda m: tuple((k, (-1, -1) if v is None else v) for k, v in m),
        )

    def _valid_pull_position(self, st, d) -> bool:
        """ValidPullPosition(i, m) — PullRaft.tla:192-196 (i = mdest)."""
        i = d["mdest"]
        if d["mlastLogIndex"] == 0:
            return True
        return (
            0 < d["mlastLogIndex"] <= len(st["log"][i])
            and d["mlastLogTerm"] == st["log"][i][d["mlastLogIndex"] - 1][0]
        )

    # ---------- actions (Next order, PullRaft.tla:542-558) ----------

    def successors(self, st) -> list[tuple[str, dict]]:
        out = []
        S, V = self.S, self.V
        for i in range(S):
            s2 = self.restart(st, i)
            if s2 is not None:
                out.append((f"Restart({i})", s2))
        for m in self._domain(st):
            s2 = self.update_term(st, m)
            if s2 is not None:
                out.append((f"UpdateTerm[{dict(m)['mdest']}]", s2))
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
            s2 = self.reject_pull_entries_request(st, m)
            if s2 is not None:
                out.append(("RejectPullEntriesRequest", s2))
        for m in self._domain(st):
            s2 = self.accept_pull_entries_request(st, m)
            if s2 is not None:
                out.append(("AcceptPullEntriesRequest", s2))
        for m in self._domain(st):
            s2 = self.learn_of_leader(st, m)
            if s2 is not None:
                out.append(("LearnOfLeader", s2))
        for i in range(S):
            for j in range(S):
                if i != j:
                    s2 = self.send_pull_entries_request(st, i, j)
                    if s2 is not None:
                        out.append((f"SendPullEntriesRequest({i},{j})", s2))
        for m in self._domain(st):
            s2 = self.handle_success_pull_entries_response(st, m)
            if s2 is not None:
                out.append(("HandleSuccessPullEntriesResponse", s2))
        for m in self._domain(st):
            s2 = self.handle_fail_pull_entries_response(st, m)
            if s2 is not None:
                out.append(("HandleFailPullEntriesResponse", s2))
        return out

    def restart(self, st, i):
        """Restart(i) — PullRaft.tla:258-265 keeps currentTerm, leader, log;
        Variant2 (PullRaftVariant2.tla:251-260) keeps votedFor instead of
        leader and also clears votesLastEntry."""
        if st["restartCtr"] >= self.max_restarts:
            return None
        S = self.S
        extra = {}
        if self.variant2:
            extra["leader"] = self._set(st["leader"], i, None)
            extra["votesLastEntry"] = self._set(
                st["votesLastEntry"], i, (None,) * S
            )
        return self._with(
            st,
            state=self._set(st["state"], i, FOLLOWER),
            votesGranted=self._set(st["votesGranted"], i, frozenset()),
            matchIndex=self._set(st["matchIndex"], i, (0,) * S),
            commitIndex=self._set(st["commitIndex"], i, 0),
            restartCtr=st["restartCtr"] + 1,
            **extra,
        )

    def update_term(self, st, m):
        """UpdateTerm — PullRaft.tla:269-276 (resets leader; Variant2
        PullRaftVariant2.tla:264-272 also resets votedFor)."""
        d = dict(m)
        i = d["mdest"]
        if d["mterm"] <= st["currentTerm"][i]:
            return None
        extra = {"votedFor": self._set(st["votedFor"], i, None)} if self.variant2 else {}
        return self._with(
            st,
            currentTerm=self._set(st["currentTerm"], i, d["mterm"]),
            state=self._set(st["state"], i, FOLLOWER),
            leader=self._set(st["leader"], i, None),
            **extra,
        )

    def request_vote(self, st, i):
        """RequestVote(i) — PullRaft.tla:283-298: votes for itself by setting
        leader[i]=i; Variant2 (PullRaftVariant2.tla:279-295) sets votedFor=i
        and leader=Nil."""
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
        msgs = self._send_multiple(self._msgs(st), ms)
        if msgs is None:
            return None
        if self.variant2:
            extra = {
                "votedFor": self._set(st["votedFor"], i, i),
                "leader": self._set(st["leader"], i, None),
            }
        else:
            extra = {"leader": self._set(st["leader"], i, i)}
        return self._with(
            st,
            state=self._set(st["state"], i, CANDIDATE),
            currentTerm=self._set(st["currentTerm"], i, new_term),
            votesGranted=self._set(st["votesGranted"], i, frozenset({i})),
            electionCtr=st["electionCtr"] + 1,
            messages=frozenset(msgs.items()),
            **extra,
        )

    def handle_request_vote_request(self, st, m):
        """HandleRequestVoteRequest — PullRaft.tla:306-330 (grant tracked in
        `leader`); Variant2 (PullRaftVariant2.tla:303-326) tracks the grant
        in `votedFor` and the response carries the last log entry."""
        if not self._receivable(st, m, "RequestVoteRequest", equal_term=False):
            return None
        d = dict(m)
        i, j = d["mdest"], d["msource"]
        log_ok = d["mlastLogTerm"] > _last_term(st["log"][i]) or (
            d["mlastLogTerm"] == _last_term(st["log"][i])
            and d["mlastLogIndex"] >= len(st["log"][i])
        )
        vote_var = st["votedFor"] if self.variant2 else st["leader"]
        grant = (
            d["mterm"] == st["currentTerm"][i]
            and log_ok
            and vote_var[i] in (None, j)
        )
        kw = dict(
            mtype="RequestVoteResponse",
            mterm=st["currentTerm"][i],
            mvoteGranted=grant,
            msource=i,
            mdest=j,
        )
        if self.variant2:  # PullRaftVariant2.tla:320-321
            kw["mlastLogIndex"] = len(st["log"][i])
            kw["mlastLogTerm"] = _last_term(st["log"][i])
        msgs = self._reply(self._msgs(st), rec(**kw), m)
        if msgs is None:
            return None
        if grant:
            extra = (
                {"votedFor": self._set(st["votedFor"], i, j)}
                if self.variant2
                else {"leader": self._set(st["leader"], i, j)}
            )
        else:
            extra = {}
        return self._with(st, messages=frozenset(msgs.items()), **extra)

    def handle_request_vote_response(self, st, m):
        """HandleRequestVoteResponse — PullRaft.tla:335-350; Variant2
        (PullRaftVariant2.tla:331-349) also records votesLastEntry."""
        if not self._receivable(st, m, "RequestVoteResponse", equal_term=True):
            return None
        d = dict(m)
        i, j = d["mdest"], d["msource"]
        vg = st["votesGranted"]
        extra = {}
        if d["mvoteGranted"]:
            vg = self._set(vg, i, vg[i] | {j})
            if self.variant2:
                extra["votesLastEntry"] = self._set2(
                    st["votesLastEntry"], i, j,
                    (d["mlastLogIndex"], d["mlastLogTerm"]),
                )
        msgs = self._discard(self._msgs(st), m)
        return self._with(
            st, votesGranted=vg, messages=frozenset(msgs.items()), **extra
        )

    def become_leader(self, st, i):
        """BecomeLeader(i) — PullRaft.tla:354-366 notifies only non-voters;
        Variant2 (PullRaftVariant2.tla:361-379) notifies ALL peers, embeds
        per-peer mlastCommonEntry, and sets leader[i]=i."""
        if st["state"][i] != CANDIDATE:
            return None
        if 2 * len(st["votesGranted"][i]) <= self.S:  # Quorum (PullRaft.tla:131)
            return None
        S = self.S
        if self.variant2:
            ms = set()
            for j in range(S):
                if j == i:
                    continue
                vle = st["votesLastEntry"][i][j]
                if vle is None:
                    lce = None
                else:
                    lce = last_common_entry(st["log"][i], vle[0], vle[1])
                ms.add(
                    rec(
                        mtype="LeaderNotifyRequest",
                        mterm=st["currentTerm"][i],
                        mlastCommonEntry=lce,
                        msource=i,
                        mdest=j,
                    )
                )
            extra = {"leader": self._set(st["leader"], i, i)}
        else:
            ms = {
                rec(
                    mtype="LeaderNotifyRequest",
                    mterm=st["currentTerm"][i],
                    msource=i,
                    mdest=j,
                )
                for j in range(S)
                if j not in st["votesGranted"][i]
            }
            extra = {}
        msgs = self._send_multiple(self._msgs(st), ms)
        if msgs is None:
            return None
        return self._with(
            st,
            state=self._set(st["state"], i, LEADER),
            matchIndex=self._set(st["matchIndex"], i, (0,) * S),
            messages=frozenset(msgs.items()),
            **extra,
        )

    def client_request(self, st, i, v):
        """ClientRequest(i, v) — PullRaft.tla:370-379."""
        if st["state"][i] != LEADER or st["acked"][v] is not None:
            return None
        entry = (st["currentTerm"][i], v)
        return self._with(
            st,
            log=self._set(st["log"], i, st["log"][i] + (entry,)),
            acked=self._set(st["acked"], v, False),
        )

    def learn_of_leader(self, st, m):
        """LearnOfLeader — PullRaft.tla:383-391; Variant2
        (PullRaftVariant2.tla:398-410) may truncate to mlastCommonEntry."""
        if not self._receivable(st, m, "LeaderNotifyRequest", equal_term=True):
            return None
        d = dict(m)
        i, j = d["mdest"], d["msource"]
        msgs = self._discard(self._msgs(st), m)
        extra = {}
        if self.variant2:
            lce = d["mlastCommonEntry"]
            # NeedsTruncation (PullRaftVariant2.tla:171-173) + TruncateLog
            # (:176-179)
            if lce is not None and len(st["log"][i]) >= lce[0]:
                extra["log"] = self._set(st["log"], i, st["log"][i][: lce[0]])
        return self._with(
            st,
            leader=self._set(st["leader"], i, j),
            messages=frozenset(msgs.items()),
            **extra,
        )

    def send_pull_entries_request(self, st, i, j):
        """SendPullEntriesRequest(i, j) — PullRaft.tla:396-411."""
        if i == j or st["state"][i] != FOLLOWER or st["leader"][i] != j:
            return None
        log_i = st["log"][i]
        m = rec(
            mtype="PullEntriesRequest",
            mterm=st["currentTerm"][i],
            mlastLogIndex=len(log_i),
            mlastLogTerm=_last_term(log_i),
            msource=i,
            mdest=j,
        )
        msgs = self._send(self._msgs(st), m)
        if msgs is None:
            return None
        return self._with(st, messages=frozenset(msgs.items()))

    def reject_pull_entries_request(self, st, m):
        """RejectPullEntriesRequest — PullRaft.tla:418-436."""
        if not self._receivable(st, m, "PullEntriesRequest", equal_term=True):
            return None
        d = dict(m)
        i, j = d["mdest"], d["msource"]
        if st["state"][i] != LEADER or self._valid_pull_position(st, d):
            return None
        resp = rec(
            mtype="PullEntriesResponse",
            mterm=st["currentTerm"][i],
            msuccess=False,
            mlastCommonEntry=last_common_entry(
                st["log"][i], d["mlastLogIndex"], d["mlastLogTerm"]
            ),
            msource=i,
            mdest=j,
        )
        msgs = self._reply(self._msgs(st), resp, m)
        if msgs is None:
            return None
        return self._with(st, messages=frozenset(msgs.items()))

    def _new_commit_index(self, st, i, new_match_row) -> int:
        """NewCommitIndex(i, iMatchIndex) — PullRaft.tla:446-458."""
        S = self.S
        log_i = st["log"][i]
        agree_indexes = [
            idx
            for idx in range(1, len(log_i) + 1)
            if 2 * len({i} | {k for k in range(S) if new_match_row[k] >= idx}) > S
        ]
        if agree_indexes and log_i[max(agree_indexes) - 1][0] == st["currentTerm"][i]:
            return max(agree_indexes)
        return st["commitIndex"][i]

    def accept_pull_entries_request(self, st, m):
        """AcceptPullEntriesRequest — PullRaft.tla:460-488."""
        if not self._receivable(st, m, "PullEntriesRequest", equal_term=True):
            return None
        d = dict(m)
        i, j = d["mdest"], d["msource"]
        index = d["mlastLogIndex"] + 1
        if (
            st["state"][i] != LEADER
            or not self._valid_pull_position(st, d)
            or index > len(st["log"][i])
        ):
            return None
        new_match_row = self._set(st["matchIndex"][i], j, d["mlastLogIndex"])
        new_ci = self._new_commit_index(st, i, new_match_row)
        ci = st["commitIndex"][i]
        committed_vals = {st["log"][i][ind - 1][1] for ind in range(ci + 1, new_ci + 1)}
        acked = tuple(
            (v in committed_vals) if st["acked"][v] is False else st["acked"][v]
            for v in range(self.V)
        )
        resp = rec(
            mtype="PullEntriesResponse",
            mterm=st["currentTerm"][i],
            msuccess=True,
            mentries=(st["log"][i][index - 1],),
            mcommitIndex=min(new_ci, index),
            msource=i,
            mdest=j,
        )
        msgs = self._reply(self._msgs(st), resp, m)
        if msgs is None:
            return None
        return self._with(
            st,
            matchIndex=self._set(st["matchIndex"], i, new_match_row),
            commitIndex=self._set(st["commitIndex"], i, new_ci),
            acked=acked,
            messages=frozenset(msgs.items()),
        )

    def handle_success_pull_entries_response(self, st, m):
        """HandleSuccessPullEntriesResponse — PullRaft.tla:493-503."""
        if not self._receivable(st, m, "PullEntriesResponse", equal_term=True):
            return None
        d = dict(m)
        if not d["msuccess"]:
            return None
        i = d["mdest"]
        msgs = self._discard(self._msgs(st), m)
        return self._with(
            st,
            commitIndex=self._set(st["commitIndex"], i, d["mcommitIndex"]),
            log=self._set(st["log"], i, st["log"][i] + (d["mentries"][0],)),
            messages=frozenset(msgs.items()),
        )

    def handle_fail_pull_entries_response(self, st, m):
        """HandleFailPullEntriesResponse — PullRaft.tla:510-520: truncate to
        mlastCommonEntry.index (TruncateLog, PullRaft.tla:185-188)."""
        if not self._receivable(st, m, "PullEntriesResponse", equal_term=True):
            return None
        d = dict(m)
        if d["msuccess"]:
            return None
        i = d["mdest"]
        idx = d["mlastCommonEntry"][0]
        msgs = self._discard(self._msgs(st), m)
        return self._with(
            st,
            log=self._set(st["log"], i, st["log"][i][:idx]),
            messages=frozenset(msgs.items()),
        )

    # ---------- VIEW + SYMMETRY ----------

    @staticmethod
    def _ser_msgs(msgs) -> tuple:
        """Orderable form of the bag: None field values (Variant2's Nil
        mlastCommonEntry) become (-1, -1) so records compare."""

        def norm(m):
            return tuple(
                (k, (-1, -1) if v is None else v) for k, v in m
            )

        return tuple(sorted((norm(m), c) for m, c in msgs))

    def serialize_view(self, st) -> tuple:
        """PullRaft view INCLUDES acked (PullRaft.tla:123); Variant2's does
        not (PullRaftVariant2.tla:114)."""
        ack = {None: -1, False: 0, True: 1}
        base = (
            st["currentTerm"],
            st["state"],
            tuple(-1 if v is None else v for v in st["leader"]),
        )
        if self.variant2:
            base += (
                tuple(-1 if v is None else v for v in st["votedFor"]),
                tuple(
                    tuple((-1, -1) if e is None else e for e in row)
                    for row in st["votesLastEntry"]
                ),
            )
        base += (
            tuple(tuple(sorted(vs)) for vs in st["votesGranted"]),
            st["log"],
            st["commitIndex"],
            st["matchIndex"],
            self._ser_msgs(st["messages"]),
        )
        if not self.variant2:
            base += (tuple(ack[a] for a in st["acked"]),)
        return base

    def serialize_full(self, st) -> tuple:
        ack = {None: -1, False: 0, True: 1}
        return self.serialize_view(st) + (
            tuple(ack[a] for a in st["acked"]),
            st["electionCtr"],
            st["restartCtr"],
        )

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
            return rec(**d)

        extra = {}
        if self.variant2:
            extra["votedFor"] = tuple(
                None if v is None else sigma[v] for v in prow(st["votedFor"])
            )
            extra["votesLastEntry"] = tuple(
                prow(row) for row in prow(st["votesLastEntry"])
            )
        return self._with(
            st,
            currentTerm=prow(st["currentTerm"]),
            state=prow(st["state"]),
            leader=tuple(None if v is None else sigma[v] for v in prow(st["leader"])),
            votesGranted=tuple(
                frozenset(sigma[j] for j in vs) for vs in prow(st["votesGranted"])
            ),
            log=prow(st["log"]),
            commitIndex=prow(st["commitIndex"]),
            matchIndex=tuple(prow(row) for row in prow(st["matchIndex"])),
            messages=frozenset((pmsg(m), c) for m, c in st["messages"]),
            **extra,
        )

    def canon(self, st, symmetry: bool = True) -> tuple:
        if not symmetry:
            return self.serialize_view(st)
        return min(
            self.serialize_view(self.permute(st, list(sigma)))
            for sigma in itertools.permutations(range(self.S))
        )

    # ---------- invariants (PullRaft.tla:578-627) ----------

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

    # ---------- BFS ----------

    def bfs(
        self,
        invariants: tuple[str, ...] = ("LeaderHasAllAckedValues", "NoLogDivergence"),
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
