"""Independent pure-Python interpreter of pull-raft/KRaftWithReconfig.tla.

The largest reference spec (1,918 lines): KRaft plus one-at-a-time
reconfiguration over a DYNAMIC server universe of composite
``[host, diskId]`` identities. Written directly against the TLA+ text
(reference ``/root/reference/specifications/pull-raft/
KRaftWithReconfig.tla`` + the shared ``MessagePassing.tla`` it EXTENDS).

Key structure (SURVEY.md §2.1):
  - the ``servers`` universe GROWS: ``StartNewServer:1492`` and
    ``RestartWithoutState:906`` mint fresh ``[host, diskId]`` identities
    (``_diskIdGen``), bounded by ``MaxSpawnedServers``;
  - servers carry a ``role`` (Voter/Observer, ``:349-351``); roles flip
    via config commands in the log (``MaybeSwitchConfigurations:753``);
  - states add ``Resigned`` and the terminal ``DeadNoState``
    (``:354-360``);
  - joining is message-driven: ``SendJoinRequest:1524`` ->
    ``AcceptJoinRequest:1558`` (``JoinCheck:1551``) appends an
    AddServerCommand; removal is an admin action
    (``HandleRemoveRequest:1699``, ``RemoveCheck:1692``);
  - a leader that commits its own removal resigns inside
    ``AcceptFetchRequestFromVoter:1317-1324``;
  - ``MessagePassing.tla`` send-once classes: RequestVoteRequest,
    BeginQuorumRequest, JoinRequest (``:40-45``); Reply refuses duplicate
    FetchResponses (``:72-79``);
  - ``endOffset[i]``'s DOMAIN is itself dynamic state (extended by
    ``MaybeSwitchConfigurations:767-771`` and
    ``AcceptJoinRequest:1581``) and must round-trip exactly.

Faithfully-reproduced reference quirks (kept for parity, verified against
the TLA+ text):
  - ``RestartWithoutState:913`` tests ``state[j] = Voter`` — comparing a
    STATE to the ROLE model value Voter, which no state assignment ever
    produces, so the action is never enabled;
  - ``_addReconfigCtr`` is never incremented (only gated on,
    ``SendJoinRequest:1526``) — joins are instead bounded by the
    JoinRequest send-once latch and MaxClusterSize;
  - ``HandleRejectJoinResponse:1653-1672`` tests ``m.mresult`` (Ok/NotOk)
    against the ERROR values NotLeader/FencedLeaderEpoch, so only the
    OTHER arm (plain Discard) is reachable.

State dict format: identities are (host, diskId) tuples; per-server maps
are dicts keyed by identity; entries are (command, epoch, value) with
value = int v | (id, members) | (id, new/old identity, members).
"""

from __future__ import annotations

import itertools

from .config_oracle_base import ConfigOracleBase

# states (KRaftWithReconfig.tla:354-360) — string enums keep the oracle
# readable; the lowering maps them to small ints
UNATTACHED, FOLLOWER, CANDIDATE, LEADER, VOTED, RESIGNED, DEAD, ILLEGAL = (
    "Unattached",
    "Follower",
    "Candidate",
    "Leader",
    "Voted",
    "Resigned",
    "DeadNoState",
    "IllegalState",
)
VOTER, OBSERVER = "Voter", "Observer"  # roles (:349-351)

# errors (:375-376)
FENCED, NOT_LEADER, UNKNOWN_LEADER = (
    "FencedLeaderEpoch",
    "NotLeader",
    "UnknownLeader",
)
UNKNOWN_MEMBER, ALREADY_MEMBER, RECONFIG_IN_PROGRESS, LEADER_NOT_READY = (
    "UnknownMember",
    "AlreadyMember",
    "ReconfigInProgress",
    "LeaderNotReady",
)
OK, NOT_OK, DIVERGING = "Ok", "NotOk", "Diverging"

INIT_CMD = "InitClusterCommand"
APPEND_CMD = "AppendCommand"
ADD_CMD = "AddServerCommand"
REMOVE_CMD = "RemoveServerCommand"
CONFIG_CMDS = (INIT_CMD, ADD_CMD, REMOVE_CMD)

NO_CONFIG = (0, frozenset(), False)  # NoConfig (:737-740)


def rec(**kw) -> tuple:
    return tuple(sorted(kw.items()))


def last_epoch(log) -> int:
    """LastEpoch — :498."""
    return log[-1][1] if log else 0


def compare_entries(o1, e1, o2, e2) -> int:
    """CompareEntries — :513-517."""
    if e1 > e2:
        return 1
    if e1 == e2 and o1 > o2:
        return 1
    if e1 == e2 and o1 == o2:
        return 0
    return -1


def end_offset_for_epoch(log, lfe) -> tuple[int, int]:
    """EndOffsetForEpoch — :551-567."""
    best = 0
    for off in range(1, len(log) + 1):
        if log[off - 1][1] <= lfe:
            best = off
    return (best, log[best - 1][1]) if best else (0, 0)


def highest_common_offset(log, end_off, epoch) -> int:
    """HighestCommonOffset — :521-539."""
    best = 0
    for off in range(1, len(log) + 1):
        if compare_entries(off, log[off - 1][1], end_off, epoch) <= 0:
            best = off
    return best


def is_config_command(entry) -> bool:
    """IsConfigCommand — :718-721."""
    return entry[0] in CONFIG_CMDS


def most_recent_reconfig_entry(log) -> tuple[int, tuple]:
    """MostRecentReconfigEntry — :729-735."""
    best = 0
    for off in range(1, len(log) + 1):
        if is_config_command(log[off - 1]):
            best = off
    assert best > 0, "log has no config command"
    return best, log[best - 1]


def config_for(offset: int, entry: tuple, ci: int) -> tuple:
    """ConfigFor — :743-746."""
    val = entry[2]
    return (val[0], val[-1], ci >= offset)


class KRaftReconfigOracle(ConfigOracleBase):
    def __init__(
        self,
        n_hosts: int,
        n_values: int,
        init_cluster_size: int,
        min_cluster_size: int,
        max_cluster_size: int,
        max_elections: int,
        max_restarts: int,
        max_values_per_epoch: int,
        max_add_reconfigs: int,
        max_remove_reconfigs: int,
        max_spawned_servers: int,
    ):
        self.H = n_hosts
        self.V = n_values
        self.init_cluster_size = init_cluster_size
        self.min_cluster = min_cluster_size
        self.max_cluster = max_cluster_size
        self.max_elections = max_elections
        self.max_restarts = max_restarts
        self.max_values_per_epoch = max_values_per_epoch
        self.max_add = max_add_reconfigs
        self.max_remove = max_remove_reconfigs
        self.max_spawned = max_spawned_servers
        self.max_epoch = 1 + max_elections

    # ---------- state helpers ----------

    def init_state(self) -> dict:
        """Init — :845-859: pre-installed cluster; every initial member has
        diskId 0; CHOOSE realized as lowest host indices / identities."""
        members = frozenset((h, 0) for h in range(self.init_cluster_size))
        init_leader = min(members)
        first = (INIT_CMD, 1, (1, members))
        return {
            "servers": members,
            "config": {i: (1, members, True) for i in members},
            "currentEpoch": {i: 1 for i in members},
            "role": {i: VOTER for i in members},
            "state": {
                i: LEADER if i == init_leader else FOLLOWER for i in members
            },
            "leader": {i: init_leader for i in members},
            "votedFor": {i: None for i in members},
            "pendingFetch": {i: None for i in members},
            "votesGranted": {i: frozenset() for i in members},
            "endOffset": {i: {j: 1 for j in members} for i in members},
            "log": {i: (first,) for i in members},
            "highWatermark": {i: 1 for i in members},
            "messages": frozenset(),
            "_acked": (None,) * self.V,
            "_electionCtr": 0,
            "_valueCtr": (0,) * self.max_epoch,
            "_restartCtr": 0,
            "_addReconfigCtr": 0,
            "_removeReconfigCtr": 0,
            "_diskIdGen": 0,
        }

    @staticmethod
    def _setm(mapping: dict, i, val) -> dict:
        out = dict(mapping)
        out[i] = val
        return out

    # ---------- message-bag helpers (MessagePassing.tla) ----------

    @classmethod
    def _send(cls, msgs, m):
        """Send — MessagePassing.tla:40-45: RequestVoteRequest,
        BeginQuorumRequest and JoinRequest are send-once."""
        mtype = dict(m)["mtype"]
        if mtype in ("RequestVoteRequest", "BeginQuorumRequest", "JoinRequest"):
            return cls._send_once(msgs, m)
        return cls._send_no_restriction(msgs, m)

    @staticmethod
    def _reply(msgs, response, request):
        """Reply — MessagePassing.tla:72-79: a FetchResponse may not be
        duplicated."""
        out = dict(msgs)
        if out.get(request, 0) < 1:
            return None
        if response in out and dict(response)["mtype"] == "FetchResponse":
            return None
        out[request] -= 1
        out[response] = out.get(response, 0) + 1
        return frozenset(out.items())

    def _receivable(self, st, m, mtype: str, equal_epoch: bool) -> bool:
        """ReceivableMessage — :471-477 (adds the DeadNoState guard)."""
        d = dict(m)
        msgs = self._msgs(st)
        if msgs.get(m, 0) < 1 or d["mtype"] != mtype:
            return False
        if st["state"][d["mdest"]] == DEAD:
            return False
        if equal_epoch and d["mepoch"] != st["currentEpoch"][d["mdest"]]:
            return False
        return True

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
            if isinstance(v, tuple) and v and isinstance(v[0], tuple) and len(
                v[0]
            ) == 2 and isinstance(v[0][0], str):
                return (5, KRaftReconfigOracle._norm_rec(v))
            if isinstance(v, tuple):
                return (6, tuple(norm_val(x) for x in v))
            raise TypeError(v)

        return tuple((k, norm_val(v)) for k, v in m)

    def _domain(self, st):
        return sorted((m for m, _c in st["messages"]), key=self._norm_rec)

    # ---------- transition machine (:599-715) ----------

    def _has_consistent_leader(self, st, i, leader_id, epoch) -> bool:
        """HasConsistentLeader — :599-616 (with the resigned/observer
        carve-outs)."""
        if leader_id == i:
            if st["currentEpoch"][i] == epoch and (
                st["role"][i] == OBSERVER or st["state"][i] == RESIGNED
            ):
                return True
            return st["state"][i] == LEADER
        return (
            epoch != st["currentEpoch"][i]
            or leader_id is None
            or st["leader"][i] is None
            or st["leader"][i] == leader_id
        )

    @staticmethod
    def _illegal():
        return {"state": ILLEGAL, "epoch": 0, "leader": None, "transitioned": True}

    def _no_transition(self, st, i):
        return {
            "state": st["state"][i],
            "epoch": st["currentEpoch"][i],
            "leader": st["leader"][i],
            "transitioned": False,
        }

    def _to_voted(self, st, i, epoch, state0):
        """TransitionToVoted — :630-637."""
        if state0["epoch"] == epoch and state0["state"] != UNATTACHED:
            return self._illegal()
        return {"state": VOTED, "epoch": epoch, "leader": None, "transitioned": True}

    @staticmethod
    def _to_unattached(epoch):
        return {
            "state": UNATTACHED,
            "epoch": epoch,
            "leader": None,
            "transitioned": True,
        }

    def _to_follower(self, st, i, leader_id, epoch):
        """TransitionToFollower — :645-653."""
        if st["currentEpoch"][i] == epoch and st["state"][i] in (FOLLOWER, LEADER):
            return self._illegal()
        return {
            "state": FOLLOWER,
            "epoch": epoch,
            "leader": leader_id,
            "transitioned": True,
        }

    def _maybe_transition(self, st, i, leader_id, epoch):
        """MaybeTransition — :656-675 (case 3 adds leaderId # i)."""
        if not self._has_consistent_leader(st, i, leader_id, epoch):
            return self._illegal()
        if epoch > st["currentEpoch"][i]:
            if leader_id is None:
                return self._to_unattached(epoch)
            return self._to_follower(st, i, leader_id, epoch)
        if leader_id is not None and st["leader"][i] is None and leader_id != i:
            return self._to_follower(st, i, leader_id, epoch)
        return self._no_transition(st, i)

    def _mhcr(self, st, i, leader_id, epoch, errors):
        """MaybeHandleCommonResponse — :683-715."""
        if epoch < st["currentEpoch"][i]:
            return self._no_transition(st, i) | {"handled": True, "error": errors}
        if epoch > st["currentEpoch"][i] or errors in (FENCED, NOT_LEADER):
            return self._maybe_transition(st, i, leader_id, epoch) | {
                "handled": True,
                "error": errors,
            }
        if (
            epoch == st["currentEpoch"][i]
            and leader_id is not None
            and st["leader"][i] is None
        ):
            return {
                "state": FOLLOWER,
                "leader": leader_id,
                "epoch": st["currentEpoch"][i],
                "transitioned": True,
                "handled": errors is not None,
                "error": errors,
            }
        return self._no_transition(st, i) | {"handled": False, "error": errors}

    # ---------- config machinery (:718-777) ----------

    def _has_pending_config(self, st, i) -> bool:
        return st["config"][i][2] is False

    def _leader_has_committed_in_epoch(self, st, i) -> bool:
        """LeaderHasCommittedOffsetsInCurrentEpoch — :774-777."""
        return any(
            st["log"][i][off - 1][1] == st["currentEpoch"][i]
            and st["highWatermark"][i] >= off
            for off in range(1, len(st["log"][i]) + 1)
        )

    def _maybe_switch_configurations(self, st, i, curr_config, new_state) -> dict:
        """MaybeSwitchConfigurations — :753-771: updates leader/config,
        flips Voter<->Observer on membership change, and pads endOffset's
        domain to all servers. Returns the field updates."""
        role_i = st["role"][i]
        members = curr_config[1]
        upd = {
            "leader": self._setm(st["leader"], i, new_state["leader"]),
            "config": self._setm(st["config"], i, curr_config),
        }
        if role_i == VOTER and i not in members:
            upd["role"] = self._setm(st["role"], i, OBSERVER)
            upd["state"] = self._setm(st["state"], i, FOLLOWER)
        elif role_i == OBSERVER and i in members:
            upd["role"] = self._setm(st["role"], i, VOTER)
            upd["state"] = self._setm(st["state"], i, FOLLOWER)
        else:
            upd["state"] = self._setm(st["state"], i, new_state["state"])
        eo = dict(st["endOffset"][i])
        for j in st["servers"]:
            if j not in eo:
                eo[j] = 0
        upd["endOffset"] = self._setm(st["endOffset"], i, eo)
        return upd

    def _set_state_of_new_identity(self, st, identity, first_fetch, dead=None):
        """SetStateOfNewAndDeadIdentity — :781-797."""
        upd = dict(
            servers=st["servers"] | {identity},
            config=self._setm(st["config"], identity, NO_CONFIG),
            currentEpoch=self._setm(st["currentEpoch"], identity, 0),
            leader=self._setm(st["leader"], identity, None),
            votedFor=self._setm(st["votedFor"], identity, None),
            pendingFetch=self._setm(st["pendingFetch"], identity, first_fetch),
            votesGranted=self._setm(st["votesGranted"], identity, frozenset()),
            endOffset=self._setm(
                st["endOffset"], identity, {j: 0 for j in st["servers"]}
            ),
            log=self._setm(st["log"], identity, ()),
            highWatermark=self._setm(st["highWatermark"], identity, 0),
        )
        role = self._setm(st["role"], identity, OBSERVER)
        state = self._setm(st["state"], identity, UNATTACHED)
        if dead is not None:
            role[dead] = DEAD
            state[dead] = DEAD
        upd["role"] = role
        upd["state"] = state
        return upd

    def _valid_fetch_position(self, st, i, d) -> bool:
        """ValidFetchPosition — :571-576."""
        if d["mfetchOffset"] == 0 and d["mlastFetchedEpoch"] == 0:
            return True
        off, ep = end_offset_for_epoch(st["log"][i], d["mlastFetchedEpoch"])
        return d["mfetchOffset"] <= off and d["mlastFetchedEpoch"] == ep

    # ---------- actions (Next order, :1730-1756) ----------

    def successors(self, st) -> list[tuple[str, dict]]:
        out = []
        servers = sorted(st["servers"])
        domain = self._domain(st)  # hoisted: 13 receipt loops share one sort
        for i in servers:
            s2 = self.restart_with_state(st, i)
            if s2 is not None:
                out.append((f"RestartWithState({i})", s2))
        # RestartWithoutState (:906-924) is never enabled: its guard
        # compares state[j] to the ROLE value Voter (:913), which no state
        # assignment produces — reproduced faithfully as a no-op.
        for i in servers:
            s2 = self.request_vote(st, i)
            if s2 is not None:
                out.append((f"RequestVote({i})", s2))
        for m in domain:
            s2 = self.handle_request_vote_request(st, m)
            if s2 is not None:
                out.append(("HandleRequestVoteRequest", s2))
        for m in domain:
            s2 = self.handle_request_vote_response(st, m)
            if s2 is not None:
                out.append(("HandleRequestVoteResponse", s2))
        for i in servers:
            s2 = self.become_leader(st, i)
            if s2 is not None:
                out.append((f"BecomeLeader({i})", s2))
        for i in servers:
            for v in range(self.V):
                s2 = self.client_request(st, i, v)
                if s2 is not None:
                    out.append((f"ClientRequest({i},{v})", s2))
        for m in domain:
            s2 = self.reject_fetch_request(st, m)
            if s2 is not None:
                out.append(("RejectFetchRequest", s2))
        for m in domain:
            s2 = self.diverging_fetch_request(st, m)
            if s2 is not None:
                out.append(("DivergingFetchRequest", s2))
        for m in domain:
            s2 = self.accept_fetch_request_from_voter(st, m)
            if s2 is not None:
                out.append(("AcceptFetchRequestFromVoter", s2))
        for m in domain:
            s2 = self.accept_fetch_request_from_observer(st, m)
            if s2 is not None:
                out.append(("AcceptFetchRequestFromObserver", s2))
        for m in domain:
            s2 = self.accept_begin_quorum_request(st, m)
            if s2 is not None:
                out.append(("AcceptBeginQuorumRequest", s2))
        for i in servers:
            for j in servers:
                if i != j:
                    s2 = self.send_fetch_request(st, i, j)
                    if s2 is not None:
                        out.append((f"SendFetchRequest({i},{j})", s2))
        for m in domain:
            s2 = self.handle_success_fetch_response(st, m)
            if s2 is not None:
                out.append(("HandleSuccessFetchResponse", s2))
        for m in domain:
            s2 = self.handle_diverging_fetch_response(st, m)
            if s2 is not None:
                out.append(("HandleDivergingFetchResponse", s2))
        for m in domain:
            s2 = self.handle_non_success_fetch_response(st, m)
            if s2 is not None:
                out.append(("HandleNonSuccessFetchResponse", s2))
        for h in range(self.H):
            for j in servers:
                s2 = self.start_new_server(st, h, j)
                if s2 is not None:
                    out.append((f"StartNewServer({h},{j})", s2))
        for i in servers:
            for j in servers:
                if i != j:
                    s2 = self.send_join_request(st, i, j)
                    if s2 is not None:
                        out.append((f"SendJoinRequest({i},{j})", s2))
        for m in domain:
            s2 = self.accept_join_request(st, m)
            if s2 is not None:
                out.append(("AcceptJoinRequest", s2))
        for m in domain:
            s2 = self.reject_join_request(st, m)
            if s2 is not None:
                out.append(("RejectJoinRequest", s2))
        for m in domain:
            s2 = self.handle_reject_join_response(st, m)
            if s2 is not None:
                out.append(("HandleRejectJoinResponse", s2))
        for i in servers:
            for r in servers:
                s2 = self.handle_remove_request(st, i, r)
                if s2 is not None:
                    out.append((f"HandleRemoveRequest({i},{r})", s2))
        return out

    def restart_with_state(self, st, i):
        """RestartWithState — :873-896: a leader restarts as Resigned
        (voter) or Unattached (observer); keeps epoch/role/votedFor/log."""
        if st["_restartCtr"] >= self.max_restarts:
            return None
        if st["state"][i] == DEAD:
            return None
        was_leader = st["state"][i] == LEADER
        if was_leader and st["role"][i] == VOTER:
            new_state = RESIGNED
        elif was_leader and st["role"][i] == OBSERVER:
            new_state = UNATTACHED
        else:
            new_state = st["state"][i]
        return self._with(
            st,
            state=self._setm(st["state"], i, new_state),
            leader=self._setm(
                st["leader"], i, None if was_leader else st["leader"][i]
            ),
            votesGranted=self._setm(st["votesGranted"], i, frozenset()),
            endOffset=self._setm(
                st["endOffset"], i, {j: 0 for j in st["servers"]}
            ),
            highWatermark=self._setm(st["highWatermark"], i, 0),
            pendingFetch=self._setm(st["pendingFetch"], i, None),
            _restartCtr=st["_restartCtr"] + 1,
        )

    def request_vote(self, st, i):
        """RequestVote — :932-955: Voter only, member of own config."""
        if st["_electionCtr"] >= self.max_elections:
            return None
        if st["role"][i] != VOTER:
            return None
        if st["state"][i] not in (FOLLOWER, CANDIDATE, UNATTACHED):
            return None
        if i not in st["config"][i][1]:
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
            for j in st["config"][i][1]
            if j != i
        }
        msgs = self._send_multiple_once(self._msgs(st), reqs)
        if msgs is None:
            return None
        return self._with(
            st,
            state=self._setm(st["state"], i, CANDIDATE),
            currentEpoch=self._setm(st["currentEpoch"], i, new_epoch),
            leader=self._setm(st["leader"], i, None),
            votedFor=self._setm(st["votedFor"], i, i),
            votesGranted=self._setm(st["votesGranted"], i, frozenset({i})),
            pendingFetch=self._setm(st["pendingFetch"], i, None),
            _electionCtr=st["_electionCtr"] + 1,
            messages=msgs,
        )

    def handle_request_vote_request(self, st, m):
        """HandleRequestVoteRequest — :967-1018."""
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
        upd = dict(
            state=self._setm(st["state"], i, final["state"]),
            currentEpoch=self._setm(st["currentEpoch"], i, final["epoch"]),
            leader=self._setm(st["leader"], i, final["leader"]),
            messages=msgs,
        )
        if grant:
            upd["votedFor"] = self._setm(st["votedFor"], i, j)
        if final["state"] != st["state"][i]:
            upd["pendingFetch"] = self._setm(st["pendingFetch"], i, None)
        return self._with(st, **upd)

    def handle_request_vote_response(self, st, m):
        """HandleRequestVoteResponse — :1025-1050 (adds the Voter gate)."""
        if not self._receivable(st, m, "RequestVoteResponse", equal_epoch=False):
            return None
        d = dict(m)
        i, j = d["mdest"], d["msource"]
        if st["role"][i] != VOTER:
            return None
        new = self._mhcr(st, i, d["mleader"], d["mepoch"], d["merror"])
        msgs = self._discard(self._msgs(st), m)
        if new["handled"]:
            return self._with(
                st,
                state=self._setm(st["state"], i, new["state"]),
                leader=self._setm(st["leader"], i, new["leader"]),
                currentEpoch=self._setm(st["currentEpoch"], i, new["epoch"]),
                messages=msgs,
            )
        if st["state"][i] != CANDIDATE:
            return None
        vg = (
            st["votesGranted"][i] | {j}
            if d["mvoteGranted"]
            else st["votesGranted"][i]
        )
        return self._with(
            st, votesGranted=self._setm(st["votesGranted"], i, vg), messages=msgs
        )

    def become_leader(self, st, i):
        """BecomeLeader — :1056-1071."""
        if st["state"][i] != CANDIDATE:
            return None
        members = st["config"][i][1]
        vg = st["votesGranted"][i]
        if not (vg <= members and 2 * len(vg) > len(members)):
            return None
        reqs = {
            rec(
                mtype="BeginQuorumRequest",
                mepoch=st["currentEpoch"][i],
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
            state=self._setm(st["state"], i, LEADER),
            leader=self._setm(st["leader"], i, i),
            endOffset=self._setm(
                st["endOffset"], i, {j: 0 for j in st["servers"]}
            ),
            messages=msgs,
        )

    def accept_begin_quorum_request(self, st, m):
        """AcceptBeginQuorumRequest — :1082-1102: Voter only; stale
        requests are NOT answered (unlike KRaft.tla)."""
        if not self._receivable(st, m, "BeginQuorumRequest", equal_epoch=False):
            return None
        d = dict(m)
        i = d["mdest"]
        if d["mepoch"] < st["currentEpoch"][i]:  # error # Nil -> not enabled
            return None
        if st["role"][i] != VOTER:
            return None
        new = self._maybe_transition(st, i, d["msource"], d["mepoch"])
        return self._with(
            st,
            state=self._setm(st["state"], i, new["state"]),
            leader=self._setm(st["leader"], i, new["leader"]),
            currentEpoch=self._setm(st["currentEpoch"], i, new["epoch"]),
            pendingFetch=self._setm(st["pendingFetch"], i, None),
            messages=self._discard(self._msgs(st), m),
        )

    def client_request(self, st, i, v):
        """ClientRequest — :1110-1126."""
        if st["state"][i] != LEADER or st["_acked"][v] is not None:
            return None
        epoch = st["currentEpoch"][i]
        if st["_valueCtr"][epoch - 1] >= self.max_values_per_epoch:
            return None
        entry = (APPEND_CMD, epoch, v)
        vc = list(st["_valueCtr"])
        vc[epoch - 1] += 1
        return self._with(
            st,
            log=self._setm(st["log"], i, st["log"][i] + (entry,)),
            _acked=self._set_tuple(st["_acked"], v, False),
            _valueCtr=tuple(vc),
        )

    @staticmethod
    def _set_tuple(tup, i, val):
        return tup[:i] + (val,) + tup[i + 1 :]

    def send_fetch_request(self, st, i, j):
        """SendFetchRequest — :1137-1169: known-leader follower fetch, or
        an Unattached observer probing a random voter of its config."""
        if st["pendingFetch"][i] is not None:
            return None
        path_a = st["leader"][i] == j and st["state"][i] == FOLLOWER
        path_b = (
            st["role"][i] == OBSERVER
            and st["state"][i] == UNATTACHED
            and j in st["config"][i][1]
        )
        if not (path_a or path_b):
            return None
        fetch = rec(
            mtype="FetchRequest",
            mepoch=st["currentEpoch"][i],
            mfetchOffset=len(st["log"][i]),
            mlastFetchedEpoch=last_epoch(st["log"][i]),
            mobserver=st["role"][i] == OBSERVER,
            msource=i,
            mdest=j,
        )
        msgs = self._send(self._msgs(st), fetch)
        if msgs is None:
            return None
        return self._with(
            st,
            pendingFetch=self._setm(st["pendingFetch"], i, fetch),
            messages=msgs,
        )

    def reject_fetch_request(self, st, m):
        """RejectFetchRequest — :1195-1217."""
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
        """DivergingFetchRequest — :1225-1248."""
        if not self._receivable(st, m, "FetchRequest", equal_epoch=True):
            return None
        d = dict(m)
        i, j = d["mdest"], d["msource"]
        if st["state"][i] != LEADER or self._valid_fetch_position(st, i, d):
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

    def _new_hwm(self, st, i, new_end: dict) -> int:
        """NewHighwaterMark — :1266-1284 (leader self-exclusion when not a
        member)."""
        members = st["config"][i][1]
        best = 0
        for off in range(1, len(st["log"][i]) + 1):
            agree = {k for k in members if new_end.get(k, 0) >= off}
            if i in members:
                agree |= {i}
            if agree <= members and 2 * len(agree) > len(members):
                best = off
        if best > 0 and st["log"][i][best - 1][1] == st["currentEpoch"][i]:
            return best
        return st["highWatermark"][i]

    def accept_fetch_request_from_voter(self, st, m):
        """AcceptFetchRequestFromVoter — :1286-1342: advances the hwm, may
        commit a config, and resigns on committing its own removal."""
        if not self._receivable(st, m, "FetchRequest", equal_epoch=True):
            return None
        d = dict(m)
        i, j = d["mdest"], d["msource"]
        if st["state"][i] != LEADER or d["mobserver"]:
            return None
        if not self._valid_fetch_position(st, i, d):
            return None
        offset = d["mfetchOffset"] + 1
        log_i = st["log"][i]
        entries = () if offset > len(log_i) else (log_i[offset - 1],)
        new_end = dict(st["endOffset"][i])
        new_end[j] = d["mfetchOffset"]
        new_hwm = self._new_hwm(st, i, new_end)
        hwm_old = st["highWatermark"][i]
        # IsRemovedFromCluster (:1259-1264)
        leaves = any(
            log_i[off - 1][0] == REMOVE_CMD and i not in log_i[off - 1][2][-1]
            for off in range(hwm_old + 1, new_hwm + 1)
        )
        upd = {}
        if new_hwm > hwm_old:
            cfg_off, cfg_entry = most_recent_reconfig_entry(log_i)
            upd["config"] = self._setm(
                st["config"], i, config_for(cfg_off, cfg_entry, new_hwm)
            )
            acked = list(st["_acked"])
            committed_vals = {
                log_i[off - 1][2]
                for off in range(hwm_old + 1, new_hwm + 1)
                if log_i[off - 1][0] == APPEND_CMD
            }
            for v in range(self.V):
                if st["_acked"][v] is False:
                    acked[v] = v in committed_vals
            upd["_acked"] = tuple(acked)
            if leaves:
                upd["role"] = self._setm(st["role"], i, OBSERVER)
                upd["state"] = self._setm(st["state"], i, UNATTACHED)
                upd["leader"] = self._setm(st["leader"], i, None)
                upd["votesGranted"] = self._setm(
                    st["votesGranted"], i, frozenset()
                )
                upd["endOffset"] = self._setm(
                    st["endOffset"], i, {s: 0 for s in st["servers"]}
                )
                upd["highWatermark"] = self._setm(st["highWatermark"], i, 0)
            else:
                upd["endOffset"] = self._setm(st["endOffset"], i, new_end)
                upd["highWatermark"] = self._setm(
                    st["highWatermark"], i, new_hwm
                )
        else:
            upd["endOffset"] = self._setm(st["endOffset"], i, new_end)
            leaves = False
        resp = rec(
            mtype="FetchResponse",
            mepoch=st["currentEpoch"][i],
            mleader=None if leaves else st["leader"][i],
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
        return self._with(st, messages=msgs, **upd)

    def accept_fetch_request_from_observer(self, st, m):
        """AcceptFetchRequestFromObserver — :1349-1376: no local state
        change, just a response."""
        if not self._receivable(st, m, "FetchRequest", equal_epoch=True):
            return None
        d = dict(m)
        i, j = d["mdest"], d["msource"]
        if st["state"][i] != LEADER or not d["mobserver"]:
            return None
        if not self._valid_fetch_position(st, i, d):
            return None
        offset = d["mfetchOffset"] + 1
        log_i = st["log"][i]
        entries = () if offset > len(log_i) else (log_i[offset - 1],)
        resp = rec(
            mtype="FetchResponse",
            mepoch=st["currentEpoch"][i],
            mleader=st["leader"][i],
            mresult=OK,
            merror=None,
            mentries=entries,
            mhwm=min(offset, st["highWatermark"][i]),
            msource=i,
            mdest=j,
            correlation=m,
        )
        msgs = self._reply(self._msgs(st), resp, m)
        if msgs is None:
            return None
        return self._with(st, messages=msgs)

    def handle_success_fetch_response(self, st, m):
        """HandleSuccessFetchResponse — :1383-1409."""
        if not self._receivable(st, m, "FetchResponse", equal_epoch=False):
            return None
        d = dict(m)
        i = d["mdest"]
        if d["mresult"] != OK:
            return None
        new = self._mhcr(st, i, d["mleader"], d["mepoch"], d["merror"])
        if new["handled"] or st["pendingFetch"][i] != d["correlation"]:
            return None
        log_i = st["log"][i]
        if len(d["mentries"]) > 0:
            log_i = log_i + (d["mentries"][0],)
        cfg_off, cfg_entry = most_recent_reconfig_entry(log_i)
        curr_config = config_for(cfg_off, cfg_entry, d["mhwm"])
        upd = self._maybe_switch_configurations(st, i, curr_config, new)
        upd["highWatermark"] = self._setm(st["highWatermark"], i, d["mhwm"])
        upd["log"] = self._setm(st["log"], i, log_i)
        upd["pendingFetch"] = self._setm(st["pendingFetch"], i, None)
        upd["messages"] = self._discard(self._msgs(st), m)
        return self._with(st, **upd)

    def handle_diverging_fetch_response(self, st, m):
        """HandleDivergingFetchResponse — :1419-1445."""
        if not self._receivable(st, m, "FetchResponse", equal_epoch=False):
            return None
        d = dict(m)
        i = d["mdest"]
        if d["mresult"] != DIVERGING:
            return None
        new = self._mhcr(st, i, d["mleader"], d["mepoch"], d["merror"])
        if new["handled"] or st["pendingFetch"][i] != d["correlation"]:
            return None
        hco = highest_common_offset(
            st["log"][i], d["mdivergingEndOffset"], d["mdivergingEpoch"]
        )
        new_log = st["log"][i][:hco]
        cfg_off, cfg_entry = most_recent_reconfig_entry(new_log)
        curr_config = config_for(cfg_off, cfg_entry, d["mhwm"])
        upd = self._maybe_switch_configurations(st, i, curr_config, new)
        upd["log"] = self._setm(st["log"], i, new_log)
        upd["pendingFetch"] = self._setm(st["pendingFetch"], i, None)
        upd["messages"] = self._discard(self._msgs(st), m)
        return self._with(st, **upd)

    def handle_non_success_fetch_response(self, st, m):
        """HandleNonSuccessFetchResponse — :1459-1483 (UnknownMember
        demotes to Observer)."""
        if not self._receivable(st, m, "FetchResponse", equal_epoch=False):
            return None
        d = dict(m)
        i = d["mdest"]
        new = self._mhcr(st, i, d["mleader"], d["mepoch"], d["merror"])
        if not new["handled"] or st["pendingFetch"][i] != d["correlation"]:
            return None
        upd = dict(
            state=self._setm(st["state"], i, new["state"]),
            leader=self._setm(st["leader"], i, new["leader"]),
            currentEpoch=self._setm(st["currentEpoch"], i, new["epoch"]),
            pendingFetch=self._setm(st["pendingFetch"], i, None),
            messages=self._discard(self._msgs(st), m),
        )
        if d["merror"] == UNKNOWN_MEMBER:
            upd["role"] = self._setm(st["role"], i, OBSERVER)
        return self._with(st, **upd)

    # ---------- reconfiguration (:1492-1724) ----------

    def start_new_server(self, st, host, any_leader):
        """StartNewServer — :1492-1511: mints a fresh [host, diskId]
        observer identity whose first fetch targets a current leader."""
        if len(st["servers"]) >= self.max_spawned:
            return None
        if st["state"][any_leader] != LEADER:
            return None
        disk_id = st["_diskIdGen"] + 1
        identity = (host, disk_id)
        fetch = rec(
            mtype="FetchRequest",
            mepoch=0,
            mfetchOffset=0,
            mlastFetchedEpoch=0,
            mobserver=True,
            msource=identity,
            mdest=any_leader,
        )
        msgs = self._send(self._msgs(st), fetch)
        if msgs is None:
            return None
        upd = self._set_state_of_new_identity(st, identity, fetch)
        upd["_diskIdGen"] = disk_id
        upd["messages"] = msgs
        return self._with(st, **upd)

    def send_join_request(self, st, i, j):
        """SendJoinRequest — :1524-1538 (gated on _addReconfigCtr, which
        the spec never increments — reproduced faithfully)."""
        if st["_addReconfigCtr"] >= self.max_add:
            return None
        if st["role"][i] != OBSERVER:
            return None
        if i in st["config"][i][1]:
            return None
        if st["leader"][i] != j:
            return None
        msg = rec(
            mtype="JoinRequest",
            mepoch=st["currentEpoch"][i],
            mdest=j,
            msource=i,
        )
        msgs = self._send(self._msgs(st), msg)
        if msgs is None:
            return None
        return self._with(st, messages=msgs)

    def _join_check(self, st, i, m):
        """JoinCheck — :1551-1556."""
        d = dict(m)
        if st["state"][i] != LEADER:
            return NOT_LEADER
        if d["msource"] in st["config"][i][1]:
            return ALREADY_MEMBER
        if self._has_pending_config(st, i):
            return RECONFIG_IN_PROGRESS
        if not self._leader_has_committed_in_epoch(st, i):
            return LEADER_NOT_READY
        return OK

    def accept_join_request(self, st, m):
        """AcceptJoinRequest — :1558-1590."""
        if not self._receivable(st, m, "JoinRequest", equal_epoch=False):
            return None
        d = dict(m)
        i, j = d["mdest"], d["msource"]
        if len(st["config"][i][1]) >= self.max_cluster:
            return None
        if self._join_check(st, i, m) != OK:
            return None
        cfg_id, members, _c = st["config"][i]
        entry = (
            ADD_CMD,
            st["currentEpoch"][i],
            (cfg_id + 1, j, members | {j}),
        )
        new_log = st["log"][i] + (entry,)
        resp = rec(
            mtype="JoinResponse",
            mepoch=st["currentEpoch"][i],
            mleader=st["leader"][i],
            mresult=OK,
            merror=None,
            mdest=j,
            msource=i,
        )
        msgs = self._reply(self._msgs(st), resp, m)
        if msgs is None:
            return None
        eo = dict(st["endOffset"][i])
        if j not in eo:
            eo[j] = 0
        return self._with(
            st,
            log=self._setm(st["log"], i, new_log),
            config=self._setm(
                st["config"],
                i,
                config_for(len(new_log), entry, st["highWatermark"][i]),
            ),
            endOffset=self._setm(st["endOffset"], i, eo),
            messages=msgs,
        )

    def reject_join_request(self, st, m):
        """RejectJoinRequest — :1605-1623: only NotLeader/AlreadyMember are
        answered; ReconfigInProgress/LeaderNotReady stay unanswered."""
        if not self._receivable(st, m, "JoinRequest", equal_epoch=False):
            return None
        d = dict(m)
        i, j = d["mdest"], d["msource"]
        check = self._join_check(st, i, m)
        if check not in (NOT_LEADER, ALREADY_MEMBER):
            return None
        resp = rec(
            mtype="JoinResponse",
            mepoch=st["currentEpoch"][i],
            mleader=st["leader"][i],
            mresult=NOT_OK,
            merror=check,
            mdest=j,
            msource=i,
        )
        msgs = self._reply(self._msgs(st), resp, m)
        if msgs is None:
            return None
        return self._with(st, messages=msgs)

    def handle_reject_join_response(self, st, m):
        """HandleRejectJoinResponse — :1643-1674. The first two CASE arms
        test m.mresult against the ERROR values NotLeader/FencedLeaderEpoch
        (:1654,:1664) — mresult is only ever Ok/NotOk, so only the OTHER
        arm (a plain Discard) is reachable; reproduced faithfully."""
        if not self._receivable(st, m, "JoinResponse", equal_epoch=False):
            return None
        d = dict(m)
        i = d["mdest"]
        if st["role"][i] != OBSERVER:
            return None
        if d["mresult"] != NOT_OK:
            return None
        return self._with(st, messages=self._discard(self._msgs(st), m))

    def handle_remove_request(self, st, i, remove_server):
        """HandleRemoveRequest — :1699-1724: admin-initiated removal; a
        self-removing leader becomes an observer but stays leader."""
        if st["_removeReconfigCtr"] >= self.max_remove:
            return None
        if self._remove_check(st, i, remove_server) != OK:
            return None
        if len(st["config"][i][1]) <= self.min_cluster:
            return None
        cfg_id, members, _c = st["config"][i]
        entry = (
            REMOVE_CMD,
            st["currentEpoch"][i],
            (cfg_id + 1, remove_server, members - {remove_server}),
        )
        new_log = st["log"][i] + (entry,)
        upd = dict(
            log=self._setm(st["log"], i, new_log),
            config=self._setm(
                st["config"],
                i,
                config_for(len(new_log), entry, st["highWatermark"][i]),
            ),
            _removeReconfigCtr=st["_removeReconfigCtr"] + 1,
        )
        if i == remove_server:
            upd["role"] = self._setm(st["role"], i, OBSERVER)
        return self._with(st, **upd)

    def _remove_check(self, st, i, j):
        """RemoveCheck — :1692-1697."""
        if st["state"][i] != LEADER:
            return NOT_LEADER
        if j not in st["config"][i][1]:
            return UNKNOWN_MEMBER
        if self._has_pending_config(st, i):
            return RECONFIG_IN_PROGRESS
        if not self._leader_has_committed_in_epoch(st, i):
            return LEADER_NOT_READY
        return OK

    # ---------- VIEW + SYMMETRY ----------

    def _ser_entry(self, e):
        cmd, ep, val = e
        if cmd == APPEND_CMD:
            return (cmd, ep, (val,))
        if cmd == INIT_CMD:
            return (cmd, ep, (val[0], tuple(sorted(val[1]))))
        return (cmd, ep, (val[0], val[1], tuple(sorted(val[2]))))

    def serialize_view(self, st) -> tuple:
        """view — :460: everything except the _-prefixed aux vars, but
        including _acked."""
        servers = tuple(sorted(st["servers"]))
        ack = {None: -1, False: 0, True: 1}

        def by_server(field, default=None, f=lambda x: x):
            return tuple(f(st[field][i]) for i in servers)

        return (
            servers,
            by_server("config", f=lambda c: (c[0], tuple(sorted(c[1])), c[2])),
            by_server("currentEpoch"),
            by_server("role"),
            by_server("state"),
            by_server("votedFor", f=lambda v: v if v is not None else ()),
            by_server("leader", f=lambda v: v if v is not None else ()),
            by_server(
                "pendingFetch", f=lambda p: self._norm_rec(p) if p else ()
            ),
            by_server("votesGranted", f=lambda vs: tuple(sorted(vs))),
            by_server("endOffset", f=lambda eo: tuple(sorted(eo.items()))),
            by_server("log", f=lambda lg: tuple(self._ser_entry(e) for e in lg)),
            by_server("highWatermark"),
            tuple(sorted((self._norm_rec(m), c) for m, c in st["messages"])),
            tuple(ack[a] for a in st["_acked"]),
        )

    def serialize_full(self, st) -> tuple:
        return self.serialize_view(st) + (
            st["_electionCtr"],
            st["_valueCtr"],
            st["_restartCtr"],
            st["_addReconfigCtr"],
            st["_removeReconfigCtr"],
            st["_diskIdGen"],
        )

    def permute(self, st, sigma, tau=None) -> dict:
        """Apply a host permutation sigma (and optional value permutation
        tau) — symmHostsAndValues (:462-463). Identities map
        (host, diskId) -> (sigma[host], diskId)."""
        tau = tau or list(range(self.V))

        def pid(i):
            return None if i is None else (sigma[i[0]], i[1])

        def pentry(e):
            cmd, ep, val = e
            if cmd == APPEND_CMD:
                return (cmd, ep, tau[val])
            if cmd == INIT_CMD:
                return (cmd, ep, (val[0], frozenset(pid(x) for x in val[1])))
            return (
                cmd,
                ep,
                (val[0], pid(val[1]), frozenset(pid(x) for x in val[2])),
            )

        def pmsg(m):
            d = dict(m)
            d["msource"] = pid(d["msource"])
            d["mdest"] = pid(d["mdest"])
            if d.get("mleader") is not None:
                d["mleader"] = pid(d["mleader"])
            if "mentries" in d:
                d["mentries"] = tuple(pentry(e) for e in d["mentries"])
            if "correlation" in d:
                d["correlation"] = pmsg(d["correlation"])
            return rec(**d)

        def pmap(field, f=lambda x: x):
            return {pid(i): f(v) for i, v in st[field].items()}

        return self._with(
            st,
            servers=frozenset(pid(i) for i in st["servers"]),
            config=pmap(
                "config",
                f=lambda c: (c[0], frozenset(pid(x) for x in c[1]), c[2]),
            ),
            currentEpoch=pmap("currentEpoch"),
            role=pmap("role"),
            state=pmap("state"),
            votedFor=pmap("votedFor", f=pid),
            leader=pmap("leader", f=pid),
            pendingFetch=pmap(
                "pendingFetch", f=lambda p: pmsg(p) if p is not None else None
            ),
            votesGranted=pmap(
                "votesGranted", f=lambda vs: frozenset(pid(x) for x in vs)
            ),
            endOffset=pmap(
                "endOffset", f=lambda eo: {pid(j): v for j, v in eo.items()}
            ),
            log=pmap("log", f=lambda lg: tuple(pentry(e) for e in lg)),
            highWatermark=pmap("highWatermark"),
            messages=frozenset((pmsg(m), c) for m, c in st["messages"]),
            _acked=tuple(st["_acked"][tau.index(v)] for v in range(self.V)),
        )

    def canon(self, st, symmetry: bool = True) -> tuple:
        if not symmetry:
            return self.serialize_view(st)
        best = None
        for sigma in itertools.permutations(range(self.H)):
            for tau in itertools.permutations(range(self.V)):
                key = self.serialize_view(self.permute(st, list(sigma), list(tau)))
                if best is None or key < best:
                    best = key
        return best

    # ---------- invariants (:1848-1912) ----------

    def no_illegal_state(self, st) -> bool:
        """NoIllegalState — :1848-1850."""
        return all(s != ILLEGAL for s in st["state"].values())

    def no_log_divergence(self, st) -> bool:
        """NoLogDivergence — :1860-1868."""
        servers = sorted(st["servers"])
        for a in servers:
            for b in servers:
                if a == b:
                    continue
                hwm = min(st["highWatermark"][a], st["highWatermark"][b])
                for off in range(1, hwm + 1):
                    if st["log"][a][off - 1] != st["log"][b][off - 1]:
                        return False
        return True

    def states_match_roles(self, st) -> bool:
        """StatesMatchRoles — :1876-1881."""
        observer_states = {LEADER, FOLLOWER, UNATTACHED, VOTED}
        for i in st["servers"]:
            if st["role"][i] == OBSERVER and st["state"][i] not in observer_states:
                return False
            if st["state"][i] == UNATTACHED and st["leader"][i] is not None:
                return False
        return True

    def never_two_leaders_in_same_epoch(self, st) -> bool:
        """NeverTwoLeadersInSameEpoch — :1886-1892."""
        servers = sorted(st["servers"])
        for a in servers:
            for b in servers:
                if (
                    a != b
                    and st["leader"][a] is not None
                    and st["leader"][b] is not None
                    and st["leader"][a] != st["leader"][b]
                    and st["currentEpoch"][a] == st["currentEpoch"][b]
                ):
                    return False
        return True

    def leader_has_all_acked_values(self, st) -> bool:
        """LeaderHasAllAckedValues — :1896-1912."""
        for v in range(self.V):
            if st["_acked"][v] is not True:
                continue
            for i in st["servers"]:
                if st["state"][i] != LEADER:
                    continue
                if any(
                    st["currentEpoch"][l] > st["currentEpoch"][i]
                    for l in st["servers"]
                    if l != i
                ):
                    continue
                if not any(
                    e[0] == APPEND_CMD and e[2] == v for e in st["log"][i]
                ):
                    return False
        return True

    def messages_are_valid(self, st) -> bool:
        """MessagesAreValid — MessagePassing.tla:81-83 (checker
        self-check)."""
        return not any(
            dict(m)["msource"] == dict(m)["mdest"] for m, _c in st["messages"]
        )

    INVARIANTS = {
        "NoIllegalState": no_illegal_state,
        "NoLogDivergence": no_log_divergence,
        "StatesMatchRoles": states_match_roles,
        "NeverTwoLeadersInSameEpoch": never_two_leaders_in_same_epoch,
        "LeaderHasAllAckedValues": leader_has_all_acked_values,
        "MessagesAreValid": messages_are_valid,
        "TestInv": lambda self, st: True,
    }

    # ---------- BFS / simulation ----------

    def bfs(
        self,
        invariants: tuple[str, ...] = (
            "LeaderHasAllAckedValues",
            "NoLogDivergence",
            "NeverTwoLeadersInSameEpoch",
            "NoIllegalState",
            "StatesMatchRoles",
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

    def simulate(
        self,
        invariants: tuple[str, ...] = (
            "LeaderHasAllAckedValues",
            "NoLogDivergence",
            "NeverTwoLeadersInSameEpoch",
            "NoIllegalState",
            "StatesMatchRoles",
        ),
        behaviors: int = 100,
        max_depth: int = 50,
        seed: int = 0,
    ) -> dict:
        """TLC -simulate equivalent: random behaviors (the cfg's own header
        prescribes simulation for this spec)."""
        import random

        rng = random.Random(seed)
        steps = 0
        violation = None
        completed = 0
        for _b in range(behaviors):
            st = self.init_state()
            for depth in range(max_depth):
                succ = self.successors(st)
                if not succ:
                    break
                _label, st = rng.choice(succ)
                steps += 1
                for inv in invariants:
                    if not self.INVARIANTS[inv](self, st):
                        violation = {
                            "invariant": inv,
                            "state": st,
                            "depth": depth + 1,
                        }
                        break
                if violation:
                    break
            completed += 1
            if violation:
                break
        return {"behaviors": completed, "steps": steps, "violation": violation}
