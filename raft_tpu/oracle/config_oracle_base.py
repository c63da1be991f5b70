"""Shared machinery of the two reconfiguration-spec oracles.

``joint_oracle.py`` and ``reconfig_oracle.py`` interpret near-identical
TLA+ modules; their message-bag helpers, state-functional utilities and
the BFS driver were byte-identical copies (round-2 verdict Weak #8).
This base class holds them once. Everything where the two specs
genuinely differ (quorum rules, LogOk strictness, reconfig actions,
serialization of the differing entry shapes) stays in the subclasses —
oracles are the differential ground truth, so faithfulness to each
spec's text beats further deduplication.
"""

from __future__ import annotations

import itertools

# enums shared by both specs' oracles (identical values; the moved
# interpreters below resolve them from this module)
FOLLOWER, CANDIDATE, LEADER, NOTMEMBER = range(4)
APPEND_CMD = "AppendCommand"
OK, STALE_TERM, ENTRY_MISMATCH, NEED_SNAPSHOT = (
    "Ok",
    "StaleTerm",
    "EntryMismatch",
    "NeedSnapshot",
)
PENDING_SNAP_REQUEST = -1
PENDING_SNAP_RESPONSE = -2


def rec(**kw) -> tuple:
    return tuple(sorted(kw.items()))


def last_term(log) -> int:
    """LastTerm — JointConsensus :158 / AddRemove :173."""
    return log[-1][1] if log else 0



class ConfigOracleBase:

    @staticmethod
    def _discard(msgs, m):
        out = dict(msgs)
        assert out.get(m, 0) > 0
        out[m] -= 1
        return frozenset(out.items())

    def _set2(self, mat, i, j, val) -> tuple:
        return self._set(mat, i, self._set(mat[i], j, val))

    def _domain(self, st):
        return sorted((m for m, _c in st["messages"]), key=self._norm_rec)

    # ---------- message-bag + state-functional helpers ----------

    @staticmethod
    def _msgs(st) -> dict:
        return dict(st["messages"])

    @staticmethod
    def _send_multiple_once(msgs, ms):
        if any(m in msgs for m in ms):
            return None
        out = dict(msgs)
        for m in ms:
            out[m] = 1
        return frozenset(out.items())

    @staticmethod
    def _send_no_restriction(msgs, m):
        out = dict(msgs)
        out[m] = out.get(m, 0) + 1
        return frozenset(out.items())

    @staticmethod
    def _send_once(msgs, m):
        if m in msgs:
            return None
        out = dict(msgs)
        out[m] = 1
        return frozenset(out.items())

    def _ser_msgs(self, msgs) -> tuple:
        return tuple(sorted((self._norm_rec(m), c) for m, c in msgs))

    @staticmethod
    def _set(tup, i, val) -> tuple:
        return tup[:i] + (val,) + tup[i + 1 :]

    @staticmethod
    def _with(st, **updates) -> dict:
        out = dict(st)
        out.update(updates)
        return out

    def bfs(
        self,
        invariants: tuple[str, ...] = (
            "LeaderHasAllAckedValues",
            "NoLogDivergence",
            "MaxOneReconfigurationAtATime",
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
        terminal = 0  # expanded states with no successor (`-deadlock`)
        depth_counts = [1]
        violation = None
        depth = 0
        while frontier and violation is None:
            if max_states and distinct >= max_states:
                break  # hard cap (the inner breaks alone admitted one
                # extra state per depth level past the cap)
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

    # ---------- shared action interpreters ----------
    #
    # The two specs copy-inline this machinery almost verbatim (spec line
    # citations in the docstrings are the JointConsensus positions; the
    # AddRemove text is the same modulo ~20-line offsets). Subclasses
    # declare MEMBERS_IDX (the member-set slot of their config tuple) and
    # keep everything genuinely variant-specific: quorum rules, reconfig
    # appends, config projection (_config_for), serialization.

    MEMBERS_IDX: int
    # variant-dispatched config machinery (bound by the subclasses to
    # their module-level ConfigFor / MostRecentReconfigEntry)
    _config_for: staticmethod
    _mrre: staticmethod

    def _members(self, st, i):
        """The member set of server i's cached config."""
        return st["config"][i][self.MEMBERS_IDX]

    def restart(self, st, i):
        """Restart(i) — :362-374."""
        if st["restartCtr"] >= self.max_restarts:
            return None
        return self._with(
            st,
            state=self._set(st["state"], i, FOLLOWER),
            votesGranted=self._set(st["votesGranted"], i, frozenset()),
            nextIndex=self._set(st["nextIndex"], i, (1,) * self.S),
            matchIndex=self._set(st["matchIndex"], i, (0,) * self.S),
            pendingResponse=self._set(st["pendingResponse"], i, (False,) * self.S),
            commitIndex=self._set(st["commitIndex"], i, 0),
            restartCtr=st["restartCtr"] + 1,
        )

    def update_term(self, st, m):
        """UpdateTerm — :410-419."""
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

    def request_vote(self, st, i):
        """RequestVote(i) — :431-450."""
        if st["electionCtr"] >= self.max_elections:
            return None
        if st["state"][i] not in (FOLLOWER, CANDIDATE):
            return None
        members = self._members(st, i)
        if i not in members:
            return None
        reqs = {
            rec(
                mtype="RequestVoteRequest",
                mterm=st["currentTerm"][i] + 1,
                mlastLogTerm=last_term(st["log"][i]),
                mlastLogIndex=len(st["log"][i]),
                msource=i,
                mdest=j,
            )
            for j in members
            if j != i
        }
        msgs = self._send_multiple_once(self._msgs(st), reqs)
        if msgs is None:
            return None
        return self._with(
            st,
            state=self._set(st["state"], i, CANDIDATE),
            currentTerm=self._set(st["currentTerm"], i, st["currentTerm"][i] + 1),
            votedFor=self._set(st["votedFor"], i, i),
            votesGranted=self._set(st["votesGranted"], i, frozenset({i})),
            electionCtr=st["electionCtr"] + 1,
            messages=msgs,
        )

    def handle_request_vote_request(self, st, m):
        """HandleRequestVoteRequest — :455-478."""
        if not self._receivable(st, m, "RequestVoteRequest", equal_term=False):
            return None
        d = dict(m)
        i, j = d["mdest"], d["msource"]
        log_ok = d["mlastLogTerm"] > last_term(st["log"][i]) or (
            d["mlastLogTerm"] == last_term(st["log"][i])
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
        extra = {}
        if grant:
            extra["votedFor"] = self._set(st["votedFor"], i, j)
        return self._with(st, messages=msgs, **extra)

    def handle_request_vote_response(self, st, m):
        """HandleRequestVoteResponse — :483-499."""
        if not self._receivable(st, m, "RequestVoteResponse", equal_term=True):
            return None
        d = dict(m)
        i, j = d["mdest"], d["msource"]
        if st["state"][i] != CANDIDATE:
            return None
        vg = st["votesGranted"][i] | {j} if d["mvoteGranted"] else st["votesGranted"][i]
        return self._with(
            st,
            votesGranted=self._set(st["votesGranted"], i, vg),
            messages=self._discard(self._msgs(st), m),
        )

    def client_request(self, st, i, v):
        """ClientRequest(i, v) — :535-550."""
        if st["state"][i] != LEADER or st["acked"][v] is not None:
            return None
        term = st["currentTerm"][i]
        if st["valueCtr"][term - 1] >= self.max_values_per_term:
            return None
        entry = (APPEND_CMD, term, v)
        return self._with(
            st,
            log=self._set(st["log"], i, st["log"][i] + (entry,)),
            acked=self._set(st["acked"], v, False),
            valueCtr=self._set(st["valueCtr"], term - 1, st["valueCtr"][term - 1] + 1),
        )

    def append_entries(self, st, i, j):
        """AppendEntries(i, j) — :556-582."""
        if st["state"][i] != LEADER:
            return None
        if j not in self._members(st, i):
            return None
        ni = st["nextIndex"][i][j]
        if ni < 0 or st["pendingResponse"][i][j]:
            return None
        log_i = st["log"][i]
        prev_idx = ni - 1
        prev_term = log_i[prev_idx - 1][1] if prev_idx > 0 else 0
        last_entry = min(len(log_i), ni)
        entries = tuple(log_i[ni - 1 : last_entry])
        msg = rec(
            mtype="AppendEntriesRequest",
            mterm=st["currentTerm"][i],
            mprevLogIndex=prev_idx,
            mprevLogTerm=prev_term,
            mentries=entries,
            mcommitIndex=min(st["commitIndex"][i], last_entry),
            msource=i,
            mdest=j,
        )
        msgs = self._send(self._msgs(st), msg)
        if msgs is None:
            return None
        return self._with(
            st,
            pendingResponse=self._set2(st["pendingResponse"], i, j, True),
            messages=msgs,
        )

    def _log_ok(self, st, i, d) -> bool:
        """LogOk — :660-677 (strict empty-entries arm)."""
        log_i = st["log"][i]
        if d["mentries"] != ():
            return (
                d["mprevLogIndex"] > 0
                and d["mprevLogIndex"] <= len(log_i)
                and d["mprevLogTerm"] == log_i[d["mprevLogIndex"] - 1][1]
            )
        return (
            d["mprevLogIndex"] == len(log_i)
            and d["mprevLogIndex"] > 0
            and d["mprevLogTerm"] == log_i[d["mprevLogIndex"] - 1][1]
        )

    def reject_append_entries_request(self, st, m):
        """RejectAppendEntriesRequest — :679-703."""
        if not self._receivable(st, m, "AppendEntriesRequest", equal_term=False):
            return None
        d = dict(m)
        i, j = d["mdest"], d["msource"]
        if d["mterm"] < st["currentTerm"][i]:
            rc = STALE_TERM
        elif i not in self._members(st, i):
            rc = NEED_SNAPSHOT
        elif (
            d["mterm"] == st["currentTerm"][i]
            and st["state"][i] == FOLLOWER
            and not self._log_ok(st, i, d)
        ):
            rc = ENTRY_MISMATCH
        else:
            return None
        resp = rec(
            mtype="AppendEntriesResponse",
            mterm=st["currentTerm"][i],
            mresult=rc,
            mmatchIndex=0,
            msource=i,
            mdest=j,
        )
        msgs = self._reply(self._msgs(st), resp, m)
        if msgs is None:
            return None
        return self._with(st, messages=msgs)

    def accept_append_entries_request(self, st, m):
        """AcceptAppendEntriesRequest — :726-763."""
        if not self._receivable(st, m, "AppendEntriesRequest", equal_term=True):
            return None
        d = dict(m)
        i, j = d["mdest"], d["msource"]
        if st["state"][i] not in (FOLLOWER, CANDIDATE):
            return None
        if not self._log_ok(st, i, d):
            return None
        if i not in self._members(st, i):
            return None
        log_i = st["log"][i]
        index = d["mprevLogIndex"] + 1
        if d["mentries"] != () and len(log_i) == d["mprevLogIndex"]:
            new_log = log_i + (d["mentries"][0],)
        elif d["mentries"] != () and len(log_i) >= index:
            new_log = log_i[: d["mprevLogIndex"]] + (d["mentries"][0],)
        else:
            new_log = log_i
        cfg_idx, cfg_entry = self._mrre(new_log)
        new_config = self._config_for(cfg_idx, cfg_entry, d["mcommitIndex"])
        resp = rec(
            mtype="AppendEntriesResponse",
            mterm=st["currentTerm"][i],
            mresult=OK,
            mmatchIndex=d["mprevLogIndex"] + len(d["mentries"]),
            msource=i,
            mdest=j,
        )
        msgs = self._reply(self._msgs(st), resp, m)
        if msgs is None:
            return None
        return self._with(
            st,
            config=self._set(st["config"], i, new_config),
            commitIndex=self._set(st["commitIndex"], i, d["mcommitIndex"]),
            state=self._set(
                st["state"], i, FOLLOWER if i in new_config[self.MEMBERS_IDX] else NOTMEMBER
            ),
            log=self._set(st["log"], i, new_log),
            messages=msgs,
        )

    def handle_append_entries_response(self, st, m):
        """HandleAppendEntriesResponse — :768-798."""
        if not self._receivable(st, m, "AppendEntriesResponse", equal_term=True):
            return None
        d = dict(m)
        i, j = d["mdest"], d["msource"]
        if st["state"][i] != LEADER:
            return None
        ni = st["nextIndex"]
        mi = st["matchIndex"]
        if d["mresult"] == OK:
            ni = self._set2(ni, i, j, d["mmatchIndex"] + 1)
            mi = self._set2(mi, i, j, d["mmatchIndex"])
        elif d["mresult"] == ENTRY_MISMATCH:
            ni = self._set2(ni, i, j, max(st["nextIndex"][i][j] - 1, 1))
        elif d["mresult"] == NEED_SNAPSHOT:
            ni = self._set2(ni, i, j, PENDING_SNAP_REQUEST)
        return self._with(
            st,
            nextIndex=ni,
            matchIndex=mi,
            pendingResponse=self._set2(st["pendingResponse"], i, j, False),
            messages=self._discard(self._msgs(st), m),
        )

    # ---------- reconfiguration (:827-944) ----------

    def send_snapshot(self, st, i, j):
        """SendSnapshot(i, j) — :885-901."""
        if st["state"][i] != LEADER:
            return None
        if j not in self._members(st, i):
            return None
        if st["nextIndex"][i][j] != PENDING_SNAP_REQUEST:
            return None
        msg = rec(
            mtype="SnapshotRequest",
            mterm=st["currentTerm"][i],
            mlog=st["log"][i],
            mcommitIndex=st["commitIndex"][i],
            mmembers=self._members(st, i),
            msource=i,
            mdest=j,
        )
        msgs = self._send(self._msgs(st), msg)
        if msgs is None:
            return None
        return self._with(
            st,
            nextIndex=self._set2(st["nextIndex"], i, j, PENDING_SNAP_RESPONSE),
            messages=msgs,
        )

    def handle_snapshot_request(self, st, m):
        """HandleSnapshotRequest — :905-927."""
        if not self._receivable(st, m, "SnapshotRequest", equal_term=True):
            return None
        d = dict(m)
        i, j = d["mdest"], d["msource"]
        if st["state"][i] != FOLLOWER:
            return None
        cfg_idx, cfg_entry = self._mrre(d["mlog"])
        resp = rec(
            mtype="SnapshotResponse",
            mterm=st["currentTerm"][i],
            msuccess=True,
            mmatchIndex=len(d["mlog"]),
            msource=i,
            mdest=j,
        )
        msgs = self._reply(self._msgs(st), resp, m)
        if msgs is None:
            return None
        return self._with(
            st,
            commitIndex=self._set(st["commitIndex"], i, d["mcommitIndex"]),
            log=self._set(st["log"], i, d["mlog"]),
            config=self._set(
                st["config"], i, self._config_for(cfg_idx, cfg_entry, d["mcommitIndex"])
            ),
            messages=msgs,
        )

    def handle_snapshot_response(self, st, m):
        """HandleSnapshotResponse — :932-944."""
        if not self._receivable(st, m, "SnapshotResponse", equal_term=True):
            return None
        d = dict(m)
        i, j = d["mdest"], d["msource"]
        if st["nextIndex"][i][j] != PENDING_SNAP_RESPONSE:
            return None
        return self._with(
            st,
            nextIndex=self._set2(st["nextIndex"], i, j, d["mmatchIndex"] + 1),
            matchIndex=self._set2(st["matchIndex"], i, j, d["mmatchIndex"]),
            messages=self._discard(self._msgs(st), m),
        )

    # ---------- VIEW + SYMMETRY ----------

    # ---------------- shared Next enumeration (round-5 dedup) ------------
    # Variants supply only their reconfig arms (_config_successors) and
    # any arms between the snapshot handlers and the end of Next
    # (_tail_successors; AddRemove's ResetWithSameIdentity — the joint
    # spec comments it out of Next, :988).

    def _config_successors(self, st) -> list:
        raise NotImplementedError

    def _tail_successors(self, st) -> list:
        return []

    def successors(self, st) -> list:
        out = []
        S, V = self.S, self.V
        for i in range(S):
            s2 = self.restart(st, i)
            if s2 is not None:
                out.append((f"Restart({i})", s2))
        for m in self._domain(st):
            s2 = self.update_term(st, m)
            if s2 is not None:
                out.append(("UpdateTerm", s2))
        for i in range(S):
            s2 = self.request_vote(st, i)
            if s2 is not None:
                out.append((f"RequestVote({i})", s2))
        for i in range(S):
            s2 = self.become_leader(st, i)
            if s2 is not None:
                out.append((f"BecomeLeader({i})", s2))
        for m in self._domain(st):
            s2 = self.handle_request_vote_request(st, m)
            if s2 is not None:
                out.append(("HandleRequestVoteRequest", s2))
        for m in self._domain(st):
            s2 = self.handle_request_vote_response(st, m)
            if s2 is not None:
                out.append(("HandleRequestVoteResponse", s2))
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
        out += self._config_successors(st)
        for i in range(S):
            for j in range(S):
                if i != j:
                    s2 = self.send_snapshot(st, i, j)
                    if s2 is not None:
                        out.append((f"SendSnapshot({i},{j})", s2))
        for m in self._domain(st):
            s2 = self.handle_snapshot_request(st, m)
            if s2 is not None:
                out.append(("HandleSnapshotRequest", s2))
        for m in self._domain(st):
            s2 = self.handle_snapshot_response(st, m)
            if s2 is not None:
                out.append(("HandleSnapshotResponse", s2))
        out += self._tail_successors(st)
        return out

    # ------------- shared VIEW/SYMMETRY serialization (round-5) -----------
    # Variant hooks: per-entry and per-config-row serialization and
    # permutation, plus the spec's extra bounding counters.

    counter_keys: tuple = ()

    def _ser_entry(self, e) -> tuple:
        raise NotImplementedError

    def _ser_config_row(self, c) -> tuple:
        raise NotImplementedError

    def _perm_entry(self, e, sigma) -> tuple:
        raise NotImplementedError

    def _perm_config_row(self, c, sigma) -> tuple:
        raise NotImplementedError

    def _ser_log(self, log) -> tuple:
        return tuple(tuple(self._ser_entry(e) for e in lg) for lg in log)

    def serialize_view(self, st) -> tuple:
        """The cfg VIEW: aux vars excluded (joint :144, add/remove :159)."""
        return (
            tuple(self._ser_config_row(c) for c in st["config"]),
            st["currentTerm"],
            st["state"],
            tuple(-1 if v is None else v for v in st["votedFor"]),
            tuple(tuple(sorted(vs)) for vs in st["votesGranted"]),
            st["nextIndex"],
            st["matchIndex"],
            st["pendingResponse"],
            self._ser_log(st["log"]),
            st["commitIndex"],
            self._ser_msgs(st["messages"]),
        )

    def serialize_full(self, st) -> tuple:
        ack = {None: -1, False: 0, True: 1}
        return (
            self.serialize_view(st)
            + (
                tuple(ack[a] for a in st["acked"]),
                st["electionCtr"],
                st["restartCtr"],
            )
            + tuple(st[k] for k in self.counter_keys)
            + (st["valueCtr"],)
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
            if "mentries" in d:
                d["mentries"] = tuple(
                    self._perm_entry(e, sigma) for e in d["mentries"])
            if "mlog" in d:
                d["mlog"] = tuple(
                    self._perm_entry(e, sigma) for e in d["mlog"])
            if "mmembers" in d:
                d["mmembers"] = frozenset(sigma[x] for x in d["mmembers"])
            return rec(**d)

        return self._with(
            st,
            config=tuple(
                self._perm_config_row(c, sigma) for c in prow(st["config"])
            ),
            currentTerm=prow(st["currentTerm"]),
            state=prow(st["state"]),
            votedFor=tuple(
                None if v is None else sigma[v] for v in prow(st["votedFor"])
            ),
            votesGranted=tuple(
                frozenset(sigma[j] for j in vs) for vs in prow(st["votesGranted"])
            ),
            nextIndex=tuple(prow(row) for row in prow(st["nextIndex"])),
            matchIndex=tuple(prow(row) for row in prow(st["matchIndex"])),
            pendingResponse=tuple(prow(row) for row in prow(st["pendingResponse"])),
            log=tuple(
                tuple(self._perm_entry(e, sigma) for e in lg)
                for lg in prow(st["log"])
            ),
            commitIndex=prow(st["commitIndex"]),
            messages=frozenset((pmsg(m), c) for m, c in st["messages"]),
        )

    def canon(self, st, symmetry: bool = True) -> tuple:
        if not symmetry:
            return self.serialize_view(st)
        return min(
            self.serialize_view(self.permute(st, list(sigma)))
            for sigma in itertools.permutations(range(self.S))
        )

    # -------- shared invariants (round-5 dedup; joint :1058-1140,
    # add/remove :1009-1078 — identical up to the config-row members
    # accessor; MaxOneReconfigurationAtATime stays variant-specific) ----

    def _cfg_members_of(self, c) -> frozenset:
        raise NotImplementedError  # members set inside a config row

    def no_log_divergence(self, st) -> bool:
        """Full-entry equality below the joint commitIndex."""
        for s1 in range(self.S):
            for s2 in range(self.S):
                if s1 == s2:
                    continue
                ci = min(st["commitIndex"][s1], st["commitIndex"][s2])
                for idx in range(1, ci + 1):
                    if st["log"][s1][idx - 1] != st["log"][s2][idx - 1]:
                        return False
        return True

    def leader_has_all_acked_values(self, st) -> bool:
        """Only AppendCommand entries can match a client value."""
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
                if not any(
                    e[0] == APPEND_CMD and e[2] == v for e in st["log"][i]
                ):
                    return False
        return True

    def committed_entries_reach_majority(self, st) -> bool:
        """Quorum drawn from config[i].members and must contain i."""
        leaders = [
            i
            for i in range(self.S)
            if st["state"][i] == LEADER and st["commitIndex"][i] > 0
        ]
        if not leaders:
            return True
        for i in leaders:
            members = self._cfg_members_of(st["config"][i])
            if i not in members:
                continue
            ci = st["commitIndex"][i]
            if len(st["log"][i]) < ci:
                continue
            entry = st["log"][i][ci - 1]
            agree = {
                j
                for j in members
                if len(st["log"][j]) >= ci and st["log"][j][ci - 1] == entry
            }
            if i in agree and len(agree) >= len(members) // 2 + 1:
                return True
        return False

    # ------ shared AdvanceCommitIndex skeleton (round-5 dedup; joint
    # :613-653 dual-quorum, add/remove :605-642 member quorum) ---------

    def _commit_agree_ok(self, st, i, idx) -> bool:
        raise NotImplementedError  # variant quorum rule at log index idx

    def _committed_removal(self, log_i, idx, i) -> bool:
        raise NotImplementedError  # did committing idx remove server i?

    _mrre = None  # staticmethod(most_recent_reconfig_entry) per variant
    _config_for = None  # staticmethod(config_for) per variant

    def advance_commit_index(self, st, i):
        if st["state"][i] != LEADER:
            return None
        log_i = st["log"][i]
        best = 0
        for idx in range(1, len(log_i) + 1):
            if self._commit_agree_ok(st, i, idx):
                best = idx
        new_ci = (
            best
            if best > 0 and log_i[best - 1][1] == st["currentTerm"][i]
            else st["commitIndex"][i]
        )
        if st["commitIndex"][i] >= new_ci:
            return None
        acked = list(st["acked"])
        for idx in range(st["commitIndex"][i] + 1, new_ci + 1):
            cmd, _t, val = log_i[idx - 1]
            if cmd == APPEND_CMD and st["acked"][val] is False:
                acked[val] = True
        cfg_idx, cfg_entry = type(self)._mrre(log_i)
        new_config = type(self)._config_for(cfg_idx, cfg_entry, new_ci)
        removed = any(
            self._committed_removal(log_i, idx, i)
            for idx in range(st["commitIndex"][i] + 1, new_ci + 1)
        )
        upd = dict(
            acked=tuple(acked),
            config=self._set(st["config"], i, new_config),
        )
        if removed:
            upd.update(
                state=self._set(st["state"], i, NOTMEMBER),
                votesGranted=self._set(st["votesGranted"], i, frozenset()),
                nextIndex=self._set(st["nextIndex"], i, (1,) * self.S),
                matchIndex=self._set(st["matchIndex"], i, (0,) * self.S),
                commitIndex=self._set(st["commitIndex"], i, 0),
            )
        else:
            upd["commitIndex"] = self._set(st["commitIndex"], i, new_ci)
        return self._with(st, **upd)

    def _receivable(self, st, m, mtype: str, equal_term: bool) -> bool:
        """ReceivableMessage — :212-218."""
        d = dict(m)
        msgs = self._msgs(st)
        if msgs.get(m, 0) < 1 or d["mtype"] != mtype:
            return False
        if equal_term:
            return d["mterm"] == st["currentTerm"][d["mdest"]]
        return d["mterm"] <= st["currentTerm"][d["mdest"]]

    @staticmethod
    def _norm_rec(m) -> tuple:
        def norm_val(v):
            if v is None:
                return (0, 0)
            if isinstance(v, bool):
                return (1, int(v))
            if isinstance(v, int):
                return (2, v)
            if isinstance(v, str):
                return (3, v)
            if isinstance(v, frozenset):
                return (4, tuple(sorted(v)))
            if isinstance(v, tuple):
                return (5, tuple(norm_val(x) for x in v))
            raise TypeError(v)

        return tuple((k, norm_val(v)) for k, v in m)

    # ---------- config helpers ----------

