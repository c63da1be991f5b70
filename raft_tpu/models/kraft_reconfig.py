"""TPU lowering of KRaftWithReconfig — the dynamic-server-universe spec.

Reference: ``/root/reference/specifications/pull-raft/KRaftWithReconfig.tla``
(1,918 lines, 22-action Next at :1730-1756) + the ``MessagePassing.tla`` it
EXTENDS. Every action kernel cites the TLA+ lines it lowers; the
independent Python interpreter (``oracle/kraft_reconfig_oracle.py``) is the
differential ground truth.

Lowering strategy (SURVEY.md §7.2 "dynamic server universe"):
  - the growing ``servers`` universe (``StartNewServer:1492`` mints fresh
    ``[host, diskId]`` identities bounded by MaxSpawnedServers) becomes
    ``NS = MaxSpawnedServers`` fixed identity SLOTS with a ``used`` mask;
    a new identity takes the next free slot, so slot order = creation
    order and — because diskId equals the creation counter — the slot of
    an identity is a function of the identity itself: initial ``(h, 0)``
    sits in slot h, spawned ``(h, d)`` in slot ``ics + d - 1``;
  - all server references (leader/votedFor/msource/mdest/member sets/...)
    are slot indices (0 = Nil / bitmasks over slots);
  - ``endOffset``'s domain is itself dynamic state (extended by
    ``MaybeSwitchConfigurations:767-771`` and ``AcceptJoinRequest:1581``)
    and is carried as an ``eo_dom`` bitmask next to the value matrix;
  - log entries ``(command, epoch, value)`` with value = v |
    (id, members) | (id, identity, members) flatten into six fixed lanes
    per entry (cmd/epoch/val/cfgid/who/members);
  - messages pack into N-word WidePacker keys (correlation embeds the
    originating FetchRequest with source/dest implied-swapped, like the
    KRaft lowering);
  - SYMMETRY (``symmHostsAndValues:462-463``) permutes HOSTS, not slots,
    so the canonical fingerprint is data-dependent: for each (sigma, tau)
    remap host/value fields, re-sort slots by permuted identity
    (reproducing the oracle's sorted-identity view order), remap slot
    references through the sort, hash the rows by position and the
    remapped message bag as a multiset, and take the min
    (``SlotCanonicalizer``).

Faithfully-reproduced reference quirks (same as the oracle):
  - ``RestartWithoutState:906-924`` is never enabled (its guard :913
    compares a STATE to the ROLE value Voter) — lowered as nothing;
  - ``_addReconfigCtr`` is only ever gated on (``SendJoinRequest:1526``),
    never incremented, so it is a constant 0 and not stored;
  - ``HandleRejectJoinResponse:1643-1674`` only reaches its Discard arm.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import bag
from ..ops.hashing import U64_MAX, combine_pair, hash_lanes, hash_lanes_pair
from ..ops.packing import EMPTY, WidePacker, bits_for
from ..ops.symmetry import bag_hash_pair, fingerprints_by_raw_view
from .base import (
    ActionLabelMixin,
    Layout,
    SparseExpandMixin,
    onehot_add,
    onehot_row,
    onehot_set,
    onehot_set2,
)

# server states (KRaftWithReconfig.tla:354-360). UNATTACHED = 0 doubles as
# the all-zero unused-slot filler; every kernel gates on `used`.
UNATTACHED, VOTED, FOLLOWER, CANDIDATE, LEADER, RESIGNED, DEAD, ILLEGAL = range(8)
# roles (:349-351); 0 = unused slot
R_NONE, R_VOTER, R_OBSERVER, R_DEAD = range(4)
NIL = 0  # leader/votedFor Nil; slot i stored as i+1
ACK_NIL, ACK_FALSE, ACK_TRUE = 0, 1, 2

# mtype; BeginQuorumResponse is never sent in this spec (no reply arm)
RVREQ, RVRESP, BQREQ, FETCHREQ, FETCHRESP, JOINREQ, JOINRESP = range(1, 8)
# merror (:375-376); 0 = Nil. ReconfigInProgress/LeaderNotReady are
# deliberately never answered (:1596-1604) so they never appear in a key.
E_NONE, E_FENCED, E_NOTLEADER, E_UNKNOWN_LEADER, E_UNKNOWN_MEMBER, E_ALREADY_MEMBER = range(6)
# mresult; 0 = absent
R_RESULT_NONE, R_OK, R_NOTOK, R_DIVERGING = range(4)
# log entry commands (:363-366); 0 = empty lane
C_NONE, C_INIT, C_APPEND, C_ADD, C_REMOVE = range(5)

# the six lanes of a log entry (command, epoch, value parts)
LOG_FIELDS = ("log_cmd", "log_epoch", "log_val", "log_cfgid", "log_who",
              "log_members")

# Next-disjunct order (:1730-1756) for trace labels
(
    KR_RESTART,
    KR_REQUESTVOTE,
    KR_HANDLE_RVREQ,
    KR_HANDLE_RVRESP,
    KR_BECOMELEADER,
    KR_CLIENTREQUEST,
    KR_REJECT_FETCH,
    KR_DIVERGING_FETCH,
    KR_ACCEPT_FETCH_VOTER,
    KR_ACCEPT_FETCH_OBSERVER,
    KR_ACCEPT_BQREQ,
    KR_SENDFETCH,
    KR_HANDLE_FETCH_OK,
    KR_HANDLE_FETCH_DIV,
    KR_HANDLE_FETCH_NONSUCCESS,
    KR_STARTNEWSERVER,
    KR_SENDJOIN,
    KR_ACCEPT_JOIN,
    KR_REJECT_JOIN,
    KR_HANDLE_REJECT_JOIN,
    KR_HANDLE_REMOVE,
) = range(21)

ACTION_NAMES = [
    "RestartWithState",
    "RequestVote",
    "HandleRequestVoteRequest",
    "HandleRequestVoteResponse",
    "BecomeLeader",
    "ClientRequest",
    "RejectFetchRequest",
    "DivergingFetchRequest",
    "AcceptFetchRequestFromVoter",
    "AcceptFetchRequestFromObserver",
    "AcceptBeginQuorumRequest",
    "SendFetchRequest",
    "HandleSuccessFetchResponse",
    "HandleDivergingFetchResponse",
    "HandleNonSuccessFetchResponse",
    "StartNewServer",
    "SendJoinRequest",
    "AcceptJoinRequest",
    "RejectJoinRequest",
    "HandleRejectJoinResponse",
    "HandleRemoveRequest",
]

STATE_NAMES = {
    UNATTACHED: "Unattached", VOTED: "Voted", FOLLOWER: "Follower",
    CANDIDATE: "Candidate", LEADER: "Leader", RESIGNED: "Resigned",
    DEAD: "DeadNoState", ILLEGAL: "IllegalState",
}
ROLE_NAMES = {R_VOTER: "Voter", R_OBSERVER: "Observer", R_DEAD: "DeadNoState"}
MTYPE_NAMES = {
    RVREQ: "RequestVoteRequest", RVRESP: "RequestVoteResponse",
    BQREQ: "BeginQuorumRequest", FETCHREQ: "FetchRequest",
    FETCHRESP: "FetchResponse", JOINREQ: "JoinRequest",
    JOINRESP: "JoinResponse",
}
ERROR_NAMES = {
    E_NONE: None, E_FENCED: "FencedLeaderEpoch", E_NOTLEADER: "NotLeader",
    E_UNKNOWN_LEADER: "UnknownLeader", E_UNKNOWN_MEMBER: "UnknownMember",
    E_ALREADY_MEMBER: "AlreadyMember",
}
RESULT_NAMES = {R_OK: "Ok", R_NOTOK: "NotOk", R_DIVERGING: "Diverging"}
CMD_NAMES = {
    C_INIT: "InitClusterCommand", C_APPEND: "AppendCommand",
    C_ADD: "AddServerCommand", C_REMOVE: "RemoveServerCommand",
}


@dataclass(frozen=True)
class KRaftReconfigParams:
    n_hosts: int
    n_values: int
    init_cluster_size: int
    min_cluster_size: int
    max_cluster_size: int
    max_elections: int
    max_restarts: int
    max_values_per_epoch: int
    max_add_reconfigs: int
    max_remove_reconfigs: int
    max_spawned_servers: int
    msg_slots: int = 40

    @property
    def max_epoch(self) -> int:
        return 1 + self.max_elections

    @property
    def max_log(self) -> int:
        # values (bounded per epoch) + InitClusterCommand + config commands
        return (
            self.max_values_per_epoch * self.max_epoch
            + 1
            + self.max_add_reconfigs
            + self.max_remove_reconfigs
        )

    @property
    def max_cfg_id(self) -> int:
        return 1 + self.max_add_reconfigs + self.max_remove_reconfigs


def _build_layout(p: KRaftReconfigParams) -> Layout:
    NS, V, L, M, E = (p.max_spawned_servers, p.n_values, p.max_log,
                      p.msg_slots, p.max_epoch)
    lay = Layout(NS)
    # VIEW (:460) = everything except the _-prefixed aux vars, including
    # acked. Identity slots first (host/diskId/used encode `servers`).
    lay.add("host", "per_server", (NS,))
    lay.add("diskId", "per_server", (NS,))
    lay.add("used", "per_server", (NS,))
    lay.add("role", "per_server", (NS,))
    lay.add("state", "per_server", (NS,))
    lay.add("currentEpoch", "per_server", (NS,))
    lay.add("leader", "per_server_val", (NS,))
    lay.add("votedFor", "per_server_val", (NS,))
    # pendingFetch (:409) decomposed; pf_active is the non-Nil flag
    # (mepoch can legitimately be 0 for a spawned server's first fetch)
    lay.add("pf_active", "per_server", (NS,))
    lay.add("pf_epoch", "per_server", (NS,))
    lay.add("pf_offset", "per_server", (NS,))
    lay.add("pf_lastepoch", "per_server", (NS,))
    lay.add("pf_dest", "per_server_val", (NS,))
    lay.add("pf_observer", "per_server", (NS,))
    lay.add("votesGranted", "server_bitmask", (NS,))
    # config cache (:397): (id, members, committed) per server
    lay.add("cfg_id", "per_server", (NS,))
    lay.add("cfg_members", "server_bitmask", (NS,))
    lay.add("cfg_committed", "per_server", (NS,))
    lay.add("eo_dom", "server_bitmask", (NS,))  # endOffset domain mask
    lay.add("endOffset", "per_server_pair", (NS, NS))
    lay.add("log_cmd", "per_server", (NS, L))
    lay.add("log_epoch", "per_server", (NS, L))
    lay.add("log_val", "per_server", (NS, L))
    lay.add("log_cfgid", "per_server", (NS, L))
    lay.add("log_who", "per_server", (NS, L))  # slot+1 of added/removed id
    lay.add("log_members", "per_server", (NS, L))  # member bitmask
    lay.add("log_len", "per_server", (NS,))
    lay.add("highWatermark", "per_server", (NS,))
    lay.add("acked", "scalar", (V,))  # in VIEW (:460)
    n_words = _build_packer(p).n_words
    for k in range(n_words):
        lay.add(f"msg_w{k}", "msg_word", (M,))
    lay.add("msg_cnt", "msg_cnt", (M,))
    lay.add("electionCtr", "aux")
    lay.add("restartCtr", "aux")
    lay.add("removeCtr", "aux")
    lay.add("diskIdGen", "aux")
    lay.add("valueCtr", "aux", (E,))  # per-epoch value counter (:446)
    return lay.finish()


def _build_packer(p: KRaftReconfigParams) -> WidePacker:
    NS = p.max_spawned_servers
    eb = bits_for(p.max_epoch)
    sb = bits_for(NS - 1)  # slot index
    nb = bits_for(NS)  # nil-valued slot (0..NS)
    lb = bits_for(p.max_log)
    vb = bits_for(p.n_values)
    cb = bits_for(p.max_cfg_id)
    fields = [
        ("mtype", 3),
        ("mepoch", eb),
        ("msource", sb),
        ("mdest", sb),
        ("mlastLogEpoch", eb),  # RequestVoteRequest (:947-952)
        ("mlastLogOffset", lb),
        ("mleader", nb),
        ("mvoteGranted", 1),
        ("merror", 3),
        ("mresult", 2),
        ("mfetchOffset", lb),  # FetchRequest (:1155-1162)
        ("mlastFetchedEpoch", eb),
        ("mobserver", 1),
        ("mhwm", lb),
        ("nentries", 1),  # <=1 entry per response (:1306-1310)
        ("e_cmd", 3),  # entry = (command, epoch, value-parts)
        ("e_epoch", eb),
        ("e_val", vb),
        ("e_cfgid", cb),
        ("e_who", nb),
        ("e_members", NS),
        ("mdivergingEpoch", eb),  # Diverging response (:1236-1241)
        ("mdivergingEndOffset", lb),
        ("cepoch", eb),  # correlation = embedded FetchRequest (:1203 etc.);
        ("cfetchOffset", lb),  # its source/dest are implied (swapped)
        ("clastFetchedEpoch", eb),
        ("cobserver", 1),
    ]
    total = sum(b for _n, b in fields)
    for n_words in range(max(1, (total + 29) // 30), 8):
        try:
            return WidePacker(fields, n_words)
        except ValueError:
            continue
    raise ValueError("message schema does not fit in 7 words")


def cached_model(params: "KRaftReconfigParams") -> "KRaftReconfigModel":
    return _cached_model(params)


class KRaftReconfigModel(SparseExpandMixin, ActionLabelMixin):
    """Vectorized successor/invariant kernels for one constants binding."""

    name = "KRaftWithReconfig"
    ACTION_NAMES = ACTION_NAMES
    CRASH_ACTIONS = ("RestartWithState",)

    def __init__(self, params: KRaftReconfigParams, server_names=None,
                 value_names=None):
        self.p = params
        self.layout = _build_layout(params)
        self.packer = _build_packer(params)
        NS, V, H, M = (params.max_spawned_servers, params.n_values,
                       params.n_hosts, params.msg_slots)
        self.NS = NS
        self.server_names = list(server_names or [f"h{i+1}" for i in range(H)])
        self.value_names = list(value_names or [f"v{i+1}" for i in range(V)])

        # candidate table: non-receipt disjuncts in Next order (:1730-1756),
        # receipt disjuncts fused per message slot at the end
        self.bindings: list[tuple[str, tuple]] = []
        self._pairs = [(i, j) for i in range(NS) for j in range(NS) if i != j]
        for i in range(NS):
            self.bindings.append(("RestartWithState", (i,)))
        for i in range(NS):
            self.bindings.append(("RequestVote", (i,)))
        for i in range(NS):
            self.bindings.append(("BecomeLeader", (i,)))
        for i in range(NS):
            for v in range(V):
                self.bindings.append(("ClientRequest", (i, v)))
        for ij in self._pairs:
            self.bindings.append(("SendFetchRequest", ij))
        for h in range(H):
            for j in range(NS):
                self.bindings.append(("StartNewServer", (h, j)))
        for ij in self._pairs:
            self.bindings.append(("SendJoinRequest", ij))
        for i in range(NS):
            for r in range(NS):
                self.bindings.append(("HandleRemoveRequest", (i, r)))
        for m in range(M):
            self.bindings.append(("HandleMessage", (m,)))
        self.A = len(self.bindings)

        self.expand = jax.jit(jax.vmap(self._expand1))
        self.invariants = {
            "NoIllegalState": jax.jit(self._inv_no_illegal),
            "NoLogDivergence": jax.jit(self._inv_no_log_divergence),
            "StatesMatchRoles": jax.jit(self._inv_states_match_roles),
            "NeverTwoLeadersInSameEpoch": jax.jit(self._inv_never_two_leaders),
            "LeaderHasAllAckedValues": jax.jit(self._inv_leader_has_acked),
            "MessagesAreValid": jax.jit(self._inv_messages_are_valid),
            "TestInv": jax.jit(lambda s: jnp.ones(s.shape[:-1], dtype=bool)),
        }

        # temporal properties (:1810-1839), checker/liveness.py:
        # ValuesNotStuck = \A v : []<> CommittedValueOrNothing(v);
        # ReconfigurationNotStuck = \A cid in 1..(MaxAdd+MaxRemove) :
        # []<> ConfigAllOrNothing(cid)
        self.liveness = {
            "ValuesNotStuck": [
                (self.value_names[v], None,
                 jax.jit(partial(self._live_committed_value_or_nothing, v)))
                for v in range(V)
            ],
            "ReconfigurationNotStuck": [
                (f"config_id={cid}", None,
                 jax.jit(partial(self._live_config_all_or_nothing, cid)))
                for cid in range(
                    1, params.max_add_reconfigs + params.max_remove_reconfigs + 1
                )
            ],
        }

    def make_canonicalizer(self, symmetry: bool = True, seed: int = 0) -> "SlotCanonicalizer":
        return SlotCanonicalizer(self, symmetry, seed=seed)

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
        return tuple(jnp.asarray(w, jnp.int32) for w in self.packer.pack(**vals))

    def _words(self, d):
        return [d[f"msg_w{k}"] for k in range(self.packer.n_words)]

    def _wupd(self, words, cnt):
        upd = {f"msg_w{k}": words[k] for k in range(self.packer.n_words)}
        upd["msg_cnt"] = cnt
        return upd

    def _popcount(self, mask):
        return jnp.sum((mask >> jnp.arange(self.NS, dtype=jnp.int32)) & 1, axis=-1)

    @staticmethod
    def _last_epoch(d, i):
        """LastEpoch(log[i]) — :498."""
        ll = onehot_row(d["log_len"], i)
        row = onehot_row(d["log_epoch"], i)
        return jnp.where(ll > 0, onehot_row(row, jnp.clip(ll - 1, 0)), 0)

    # -------- transition machine (:599-715) --------
    # Triples are (state, epoch, leader_enc) int32 with leader_enc 0..NS.

    def _has_consistent_leader(self, d, i, leader_enc, epoch):
        """HasConsistentLeader — :599-616 (resigned/observer carve-outs)."""
        cur = onehot_row(d["currentEpoch"], i)
        st_i, led = onehot_row(d["state"], i), onehot_row(d["leader"], i)
        self_case = jnp.where(
            (cur == epoch)
            & ((onehot_row(d["role"], i) == R_OBSERVER) | (st_i == RESIGNED)),
            True,
            st_i == LEADER,
        )
        other = (
            (epoch != cur) | (leader_enc == NIL) | (led == NIL)
            | (led == leader_enc)
        )
        return jnp.where(leader_enc == i + 1, self_case, other)

    def _to_follower(self, d, i, leader_enc, epoch):
        """TransitionToFollower — :645-653 (illegal arm folded in)."""
        st_i = onehot_row(d["state"], i)
        ill = (onehot_row(d["currentEpoch"], i) == epoch) & (
            (st_i == FOLLOWER) | (st_i == LEADER)
        )
        return (
            jnp.where(ill, ILLEGAL, FOLLOWER),
            jnp.where(ill, 0, epoch),
            jnp.where(ill, 0, leader_enc),
        )

    def _maybe_transition(self, d, i, leader_enc, epoch):
        """MaybeTransition — :656-675 (case 3 adds leaderId # i)."""
        cur = onehot_row(d["currentEpoch"], i)
        st_i, led = onehot_row(d["state"], i), onehot_row(d["leader"], i)
        hcl = self._has_consistent_leader(d, i, leader_enc, epoch)
        tf = self._to_follower(d, i, leader_enc, epoch)
        una = (jnp.int32(UNATTACHED), epoch, jnp.int32(NIL))
        noop = (st_i, cur, led)
        ill = (jnp.int32(ILLEGAL), jnp.int32(0), jnp.int32(NIL))
        c2 = epoch > cur
        c2_pick = jnp.where(leader_enc == NIL, 1, 2)  # 1=unattached 2=follower
        c3 = (leader_enc != NIL) & (led == NIL) & (leader_enc != i + 1)
        sel = jnp.where(~hcl, 0, jnp.where(c2, c2_pick, jnp.where(c3, 2, 3)))
        out = []
        for k in range(3):
            out.append(
                jnp.where(
                    sel == 0, ill[k],
                    jnp.where(sel == 1, una[k], jnp.where(sel == 2, tf[k], noop[k])),
                )
            )
        return tuple(out)

    def _mhcr(self, d, i, leader_enc, epoch, err):
        """MaybeHandleCommonResponse — :683-715.
        Returns (state, epoch, leader_enc, handled)."""
        cur = onehot_row(d["currentEpoch"], i)
        st_i, led = onehot_row(d["state"], i), onehot_row(d["leader"], i)
        mt = self._maybe_transition(d, i, leader_enc, epoch)
        c_stale = epoch < cur
        c_trans = (epoch > cur) | (err == E_FENCED) | (err == E_NOTLEADER)
        c_follow = (epoch == cur) & (leader_enc != NIL) & (led == NIL)
        sel = jnp.where(c_stale, 0, jnp.where(c_trans, 1, jnp.where(c_follow, 2, 3)))
        fol = (jnp.int32(FOLLOWER), cur, leader_enc)
        noop = (st_i, cur, led)
        out = []
        for k in range(3):
            out.append(
                jnp.where(
                    sel == 0, noop[k],
                    jnp.where(sel == 1, mt[k], jnp.where(sel == 2, fol[k], noop[k])),
                )
            )
        handled = jnp.where(
            sel == 2, err != E_NONE, (sel == 0) | (sel == 1)
        )
        return out[0], out[1], out[2], handled

    def _handle_message_part2(
        self, s, d, m, u, recv, mtype, mepoch, src, dst, log_dst, cnt_disc,
        handled, mh_st, mh_ep, mh_ld, branches,
    ):
        """FetchResponse + Join receipt branches and the final select."""
        p, NS, L = self.p, self.NS, self.p.max_log
        is_fresp = recv & (mtype == FETCHRESP)
        # correlation match: pendingFetch[dst] = m.correlation (:1390); the
        # request's msource is dst (implied) and mdest is the responder src
        corr = (
            (onehot_row(d["pf_active"], dst) > 0)
            & (onehot_row(d["pf_epoch"], dst) == u("cepoch"))
            & (onehot_row(d["pf_offset"], dst) == u("cfetchOffset"))
            & (onehot_row(d["pf_lastepoch"], dst) == u("clastFetchedEpoch"))
            & (onehot_row(d["pf_observer"], dst) == u("cobserver"))
            & (onehot_row(d["pf_dest"], dst) == src + 1)
        )
        mres = u("mresult")
        mhwm = u("mhwm")
        used_mask = self._used_mask(d)

        def maybe_switch(upd, cfg_id_v, cfg_members_v, cfg_committed_v,
                         log_cmd_v, log_epoch_v, log_val_v, log_cfgid_v,
                         log_who_v, log_members_v, log_len_v):
            """MaybeSwitchConfigurations (:753-771): leader/config update,
            Voter<->Observer flip on membership change, endOffset domain
            padded to all servers. Applies to row `dst`; the new-state
            (from _mhcr) supplies leader and the default state."""
            member = ((cfg_members_v >> dst) & 1) > 0
            role_dst = onehot_row(d["role"], dst)
            was_voter = role_dst == R_VOTER
            was_obs = role_dst == R_OBSERVER
            demote = was_voter & ~member
            promote = was_obs & member
            new_role = jnp.where(
                demote, R_OBSERVER, jnp.where(promote, R_VOTER, role_dst)
            )
            new_state = jnp.where(demote | promote, FOLLOWER, mh_st)
            upd["leader"] = onehot_set(d["leader"], dst, mh_ld)
            upd["cfg_id"] = onehot_set(d["cfg_id"], dst, cfg_id_v)
            upd["cfg_members"] = onehot_set(d["cfg_members"], dst, cfg_members_v)
            upd["cfg_committed"] = onehot_set(d["cfg_committed"], dst, cfg_committed_v)
            upd["role"] = onehot_set(d["role"], dst, new_role)
            upd["state"] = onehot_set(d["state"], dst, new_state)
            upd["eo_dom"] = onehot_set(
                d["eo_dom"], dst, onehot_row(d["eo_dom"], dst) | used_mask)
            upd["log_cmd"] = onehot_set(d["log_cmd"], dst, log_cmd_v)
            upd["log_epoch"] = onehot_set(d["log_epoch"], dst, log_epoch_v)
            upd["log_val"] = onehot_set(d["log_val"], dst, log_val_v)
            upd["log_cfgid"] = onehot_set(d["log_cfgid"], dst, log_cfgid_v)
            upd["log_who"] = onehot_set(d["log_who"], dst, log_who_v)
            upd["log_members"] = onehot_set(d["log_members"], dst, log_members_v)
            upd["log_len"] = onehot_set(d["log_len"], dst, log_len_v)
            return upd

        # --- HandleSuccessFetchResponse (:1383-1409)
        b_ok = is_fresp & ~handled & corr & (mres == R_OK)
        app = u("nentries") > 0
        ll_dst = onehot_row(d["log_len"], dst)
        apos = jnp.clip(ll_dst, 0, L - 1)
        ok_ovf = b_ok & app & (ll_dst >= L)

        def appended(f, e):  # log[dst]'s lanes of f, the entry's at apos
            return jnp.where(
                app, onehot_set(log_dst[f], apos, u(e)), log_dst[f])

        nl_cmd = appended("log_cmd", "e_cmd")
        nl_ep = appended("log_epoch", "e_epoch")
        nl_val = appended("log_val", "e_val")
        nl_cfgid = appended("log_cfgid", "e_cfgid")
        nl_who = appended("log_who", "e_who")
        nl_members = appended("log_members", "e_members")
        nl_len = ll_dst + app.astype(jnp.int32)
        ok_cfg_off = self._most_recent_reconfig(d, nl_cmd, nl_len)
        b_ok &= ok_cfg_off > 0  # log always has a config cmd when reachable
        ok_lane = jnp.clip(ok_cfg_off - 1, 0, L - 1)
        upd8 = maybe_switch(
            dict(msg_cnt=cnt_disc),
            onehot_row(nl_cfgid, ok_lane), onehot_row(nl_members, ok_lane),
            (mhwm >= ok_cfg_off).astype(jnp.int32),
            nl_cmd, nl_ep, nl_val, nl_cfgid, nl_who, nl_members, nl_len,
        )
        upd8["highWatermark"] = onehot_set(d["highWatermark"], dst, mhwm)
        upd8 = {**upd8, **self._pf_clear_upd(d, dst)}
        s_ok = self._asm(d, **upd8)

        # --- HandleDivergingFetchResponse (:1419-1445): truncate, refresh
        # config from the truncated log, hwm NOT updated
        b_divr = is_fresp & ~handled & corr & (mres == R_DIVERGING)
        hco = self._highest_common_offset(
            d, dst, u("mdivergingEndOffset"), u("mdivergingEpoch")
        )
        keep = jnp.arange(L, dtype=jnp.int32) < hco
        tl_cmd, tl_ep, tl_val, tl_cfgid, tl_who, tl_members = (
            jnp.where(keep, log_dst[f], 0) for f in LOG_FIELDS)
        dv_cfg_off = self._most_recent_reconfig(d, tl_cmd, hco)
        b_divr &= dv_cfg_off > 0
        dv_lane = jnp.clip(dv_cfg_off - 1, 0, L - 1)
        upd9 = maybe_switch(
            dict(msg_cnt=cnt_disc),
            onehot_row(tl_cfgid, dv_lane), onehot_row(tl_members, dv_lane),
            (mhwm >= dv_cfg_off).astype(jnp.int32),
            tl_cmd, tl_ep, tl_val, tl_cfgid, tl_who, tl_members, hco,
        )
        upd9 = {**upd9, **self._pf_clear_upd(d, dst)}
        s_divr = self._asm(d, **upd9)

        # --- HandleNonSuccessFetchResponse (:1459-1483)
        b_err = is_fresp & handled & corr
        upd10 = dict(
            state=onehot_set(d["state"], dst, mh_st),
            currentEpoch=onehot_set(d["currentEpoch"], dst, mh_ep),
            leader=onehot_set(d["leader"], dst, mh_ld),
            role=jnp.where(
                u("merror") == E_UNKNOWN_MEMBER,
                onehot_set(d["role"], dst, R_OBSERVER),
                d["role"],
            ),
            msg_cnt=cnt_disc,
        )
        upd10 = {**upd10, **self._pf_clear_upd(d, dst)}
        s_err = self._asm(d, **upd10)

        # --- Join flow (:1524-1674)
        is_joinreq = recv & (mtype == JOINREQ)
        members = onehot_row(d["cfg_members"], dst)
        msize = self._popcount(members)
        # JoinCheck (:1551-1556)
        jc_notleader = onehot_row(d["state"], dst) != LEADER
        jc_already = ((members >> src) & 1) > 0
        jc_pending = onehot_row(d["cfg_committed"], dst) == 0
        jc_notready = ~self._leader_committed_in_epoch(d, dst)
        jc_ok = ~jc_notleader & ~jc_already & ~jc_pending & ~jc_notready

        # AcceptJoinRequest (:1558-1590)
        b_jacc = is_joinreq & (msize < p.max_cluster_size) & jc_ok
        pos = onehot_row(d["log_len"], dst)
        ja_ovf = b_jacc & (pos >= L)
        posc = jnp.clip(pos, 0, L - 1)
        new_len = pos + 1
        add_members = members | (jnp.int32(1) << src)
        jakey = self._pack(
            mtype=JOINRESP, mepoch=onehot_row(d["currentEpoch"], dst),
            mleader=onehot_row(d["leader"], dst), mresult=R_OK, merror=E_NONE,
            mdest=src, msource=dst,
        )
        wj, cj, _exj, ovfj = self._reply(d, m, jakey)
        updj = dict(
            log_cmd=onehot_set2(d["log_cmd"], dst, posc, C_ADD),
            log_epoch=onehot_set2(
                d["log_epoch"], dst, posc, onehot_row(d["currentEpoch"], dst)),
            log_cfgid=onehot_set2(
                d["log_cfgid"], dst, posc, onehot_row(d["cfg_id"], dst) + 1),
            log_who=onehot_set2(d["log_who"], dst, posc, src + 1),
            log_members=onehot_set2(d["log_members"], dst, posc, add_members),
            log_len=onehot_set(d["log_len"], dst, new_len),
            cfg_id=onehot_set(d["cfg_id"], dst, onehot_row(d["cfg_id"], dst) + 1),
            cfg_members=onehot_set(d["cfg_members"], dst, add_members),
            cfg_committed=onehot_set(
                d["cfg_committed"], dst,
                (onehot_row(d["highWatermark"], dst) >= new_len).astype(jnp.int32),
            ),
            eo_dom=onehot_set(
                d["eo_dom"], dst,
                onehot_row(d["eo_dom"], dst) | (jnp.int32(1) << src),
            ),
            **self._wupd(wj, cj),
        )
        s_jacc = self._asm(d, **updj)

        # RejectJoinRequest (:1605-1623): only NotLeader/AlreadyMember are
        # answered; ReconfigInProgress/LeaderNotReady stay unanswered
        b_jrej = is_joinreq & (jc_notleader | (~jc_notleader & jc_already))
        jr_err = jnp.where(jc_notleader, E_NOTLEADER, E_ALREADY_MEMBER)
        jrkey = self._pack(
            mtype=JOINRESP, mepoch=onehot_row(d["currentEpoch"], dst),
            mleader=onehot_row(d["leader"], dst), mresult=R_NOTOK, merror=jr_err,
            mdest=src, msource=dst,
        )
        wr, cr, _exr, ovfr = self._reply(d, m, jrkey)
        s_jrej = self._asm(d, **self._wupd(wr, cr))

        # HandleRejectJoinResponse (:1643-1674): only the Discard arm is
        # reachable (the CASE tests mresult against ERROR values)
        b_jrr = (
            recv & (mtype == JOINRESP) & (onehot_row(d["role"], dst) == R_OBSERVER)
            & (mres == R_NOTOK)
        )
        s_jrr = self._asm(d, msg_cnt=cnt_disc)

        branches = branches + [
            (b_ok, s_ok, KR_HANDLE_FETCH_OK, ok_ovf),
            (b_divr, s_divr, KR_HANDLE_FETCH_DIV, jnp.asarray(False)),
            (b_err, s_err, KR_HANDLE_FETCH_NONSUCCESS, jnp.asarray(False)),
            (b_jacc, s_jacc, KR_ACCEPT_JOIN, (ja_ovf | ovfj) & b_jacc),
            (b_jrej, s_jrej, KR_REJECT_JOIN, ovfr & b_jrej),
            (b_jrr, s_jrr, KR_HANDLE_REJECT_JOIN, jnp.asarray(False)),
        ]
        valid = jnp.asarray(False)
        succ = s
        rank = jnp.int32(-1)
        ovf = jnp.asarray(False)
        for b, sb, rk, ob in branches:
            valid = valid | b
            succ = jnp.where(b, sb, succ)
            rank = jnp.where(b, jnp.int32(rk), rank)
            ovf = ovf | (b & ob)
        return valid, succ, rank, ovf

    # -------- log-position math (:498-576) --------

    def _end_offset_for_epoch(self, d, i, lfe):
        """EndOffsetForEpoch — :551-567."""
        L = self.p.max_log
        lanes = jnp.arange(L, dtype=jnp.int32)
        row = onehot_row(d["log_epoch"], i)
        mask = (lanes < onehot_row(d["log_len"], i)) & (row <= lfe)
        off = jnp.max(jnp.where(mask, lanes + 1, 0))
        ep = jnp.where(off > 0, onehot_row(row, jnp.clip(off - 1, 0)), 0)
        return off, ep

    def _highest_common_offset(self, d, i, end_off, epoch):
        """HighestCommonOffset — :521-539."""
        L = self.p.max_log
        lanes = jnp.arange(L, dtype=jnp.int32)
        row = onehot_row(d["log_epoch"], i)
        le = (row < epoch) | ((row == epoch) & (lanes + 1 <= end_off))
        mask = (lanes < onehot_row(d["log_len"], i)) & le
        return jnp.max(jnp.where(mask, lanes + 1, 0))

    def _valid_fetch_position(self, d, i, fetch_off, lfe):
        """ValidFetchPosition — :571-576."""
        off, ep = self._end_offset_for_epoch(d, i, lfe)
        zero = (fetch_off == 0) & (lfe == 0)
        return zero | ((fetch_off <= off) & (lfe == ep))

    # -------- config machinery (:718-777) --------

    def _most_recent_reconfig(self, d, log_cmd_row, log_len):
        """MostRecentReconfigEntry — :729-735: (offset, lane index) of the
        last config command; offset 0 if none (callers guard on that)."""
        L = self.p.max_log
        lanes = jnp.arange(L, dtype=jnp.int32)
        is_cfg = (
            (log_cmd_row == C_INIT) | (log_cmd_row == C_ADD)
            | (log_cmd_row == C_REMOVE)
        ) & (lanes < log_len)
        off = jnp.max(jnp.where(is_cfg, lanes + 1, 0))
        return off

    def _leader_committed_in_epoch(self, d, i):
        """LeaderHasCommittedOffsetsInCurrentEpoch — :774-777."""
        L = self.p.max_log
        lanes = jnp.arange(L, dtype=jnp.int32)
        return jnp.any(
            (lanes < onehot_row(d["log_len"], i))
            & (onehot_row(d["log_epoch"], i) == onehot_row(d["currentEpoch"], i))
            & (onehot_row(d["highWatermark"], i) >= lanes + 1)
        )

    # -------- send helpers (MessagePassing.tla) --------

    def _cond_put(self, words, cnt, key, do):
        """bag_put applied only where `do`; returns (words, cnt, existed,
        ovf) with existed/ovf masked by `do`."""
        w2, c2, existed, ovf = bag.wide_bag_put(words, cnt, key)
        words = [jnp.where(do, a, b) for a, b in zip(w2, words)]
        cnt = jnp.where(do, c2, cnt)
        return words, cnt, existed & do, ovf & do

    def _reply(self, d, m, resp_key):
        """Reply — MessagePassing.tla:72-79: discard the request at slot m,
        add the response; returns (words, cnt, resp_existed, ovf)."""
        cnt2 = bag.bag_discard_at(d["msg_cnt"], m)
        return bag.wide_bag_put(self._words(d), cnt2, resp_key)

    # ---------------- action kernels ----------------

    def _restart_with_state(self, s, i):
        """RestartWithState — :873-896: a leader restarts as Resigned
        (voter) or Unattached (observer); keeps epoch/role/votedFor/log."""
        p, NS = self.p, self.NS
        d = self._dec(s)
        valid = (
            (d["restartCtr"] < p.max_restarts)
            & (onehot_row(d["used"], i) > 0)
            & (onehot_row(d["state"], i) != DEAD)
        )
        was_leader = onehot_row(d["state"], i) == LEADER
        new_state = jnp.where(
            was_leader,
            jnp.where(onehot_row(d["role"], i) == R_VOTER, RESIGNED, UNATTACHED),
            onehot_row(d["state"], i),
        )
        used_mask = self._used_mask(d)
        succ = self._asm(
            d,
            state=onehot_set(d["state"], i, new_state),
            leader=onehot_set(
                d["leader"], i,
                jnp.where(was_leader, NIL, onehot_row(d["leader"], i)),
            ),
            votesGranted=onehot_set(d["votesGranted"], i, 0),
            eo_dom=onehot_set(d["eo_dom"], i, used_mask),
            endOffset=onehot_set(d["endOffset"], i, jnp.zeros((NS,), jnp.int32)),
            highWatermark=onehot_set(d["highWatermark"], i, 0),
            **self._pf_clear_upd(d, i),
            restartCtr=d["restartCtr"] + 1,
        )
        return valid, succ, jnp.int32(KR_RESTART), jnp.asarray(False)

    def _used_mask(self, d):
        NS = self.NS
        return jnp.sum(
            jnp.where(d["used"] > 0, jnp.int32(1) << jnp.arange(NS, dtype=jnp.int32), 0)
        ).astype(jnp.int32)

    def _pf_clear_upd(self, d, i):
        return dict(
            pf_active=onehot_set(d["pf_active"], i, 0),
            pf_epoch=onehot_set(d["pf_epoch"], i, 0),
            pf_offset=onehot_set(d["pf_offset"], i, 0),
            pf_lastepoch=onehot_set(d["pf_lastepoch"], i, 0),
            pf_dest=onehot_set(d["pf_dest"], i, 0),
            pf_observer=onehot_set(d["pf_observer"], i, 0),
        )

    def _request_vote(self, s, i):
        """RequestVote — :932-955: Voter only, member of its own config;
        RequestVoteRequests to the config members via SendMultipleOnce."""
        p, NS = self.p, self.NS
        d = self._dec(s)
        st_i = onehot_row(d["state"], i)
        member = ((onehot_row(d["cfg_members"], i) >> i) & 1) > 0
        valid = (
            (d["electionCtr"] < p.max_elections)
            & (onehot_row(d["used"], i) > 0)
            & (onehot_row(d["role"], i) == R_VOTER)
            & ((st_i == FOLLOWER) | (st_i == CANDIDATE) | (st_i == UNATTACHED))
            & member
        )
        new_epoch = onehot_row(d["currentEpoch"], i) + 1
        last_ep = self._last_epoch(d, i)
        ll_i = onehot_row(d["log_len"], i)
        words, cnt = self._words(d), d["msg_cnt"]
        ovf = jnp.asarray(False)
        for delta in range(1, NS):
            j = jnp.mod(i + delta, NS)
            is_member = ((onehot_row(d["cfg_members"], i) >> j) & 1) > 0
            key = self._pack(
                mtype=RVREQ, mepoch=new_epoch, mlastLogEpoch=last_ep,
                mlastLogOffset=ll_i, msource=i, mdest=j,
            )
            words, cnt, existed, o = self._cond_put(words, cnt, key, is_member)
            valid &= ~existed  # SendMultipleOnce (MessagePassing.tla:49-56)
            ovf |= o
        succ = self._asm(
            d,
            state=onehot_set(d["state"], i, CANDIDATE),
            currentEpoch=onehot_set(d["currentEpoch"], i, new_epoch),
            leader=onehot_set(d["leader"], i, NIL),
            votedFor=onehot_set(d["votedFor"], i, i + 1),
            votesGranted=onehot_set(d["votesGranted"], i, jnp.int32(1) << i),
            **self._pf_clear_upd(d, i),
            electionCtr=d["electionCtr"] + 1,
            **self._wupd(words, cnt),
        )
        return valid, succ, jnp.int32(KR_REQUESTVOTE), ovf & valid

    def _become_leader(self, s, i):
        """BecomeLeader — :1056-1071: quorum of the candidate's own config;
        BeginQuorumRequests via SendMultipleOnce; endOffset reset over ALL
        servers."""
        NS = self.NS
        d = self._dec(s)
        members = onehot_row(d["cfg_members"], i)
        vg = onehot_row(d["votesGranted"], i)
        votes = self._popcount(vg)
        msize = self._popcount(members)
        vg_subset = (vg & ~members) == 0
        valid = (
            (onehot_row(d["used"], i) > 0)
            & (onehot_row(d["state"], i) == CANDIDATE)
            & vg_subset
            & (2 * votes > msize)
        )
        words, cnt = self._words(d), d["msg_cnt"]
        ovf = jnp.asarray(False)
        for delta in range(1, NS):
            j = jnp.mod(i + delta, NS)
            is_member = ((members >> j) & 1) > 0
            key = self._pack(
                mtype=BQREQ, mepoch=onehot_row(d["currentEpoch"], i), msource=i, mdest=j
            )
            words, cnt, existed, o = self._cond_put(words, cnt, key, is_member)
            valid &= ~existed
            ovf |= o
        used_mask = self._used_mask(d)
        succ = self._asm(
            d,
            state=onehot_set(d["state"], i, LEADER),
            leader=onehot_set(d["leader"], i, i + 1),
            eo_dom=onehot_set(d["eo_dom"], i, used_mask),
            endOffset=onehot_set(d["endOffset"], i, jnp.zeros((NS,), jnp.int32)),
            **self._wupd(words, cnt),
        )
        return valid, succ, jnp.int32(KR_BECOMELEADER), ovf & valid

    def _client_request(self, s, i, v):
        """ClientRequest — :1110-1126: bounded per-epoch by valueCtr."""
        p, L = self.p, self.p.max_log
        d = self._dec(s)
        ep = onehot_row(d["currentEpoch"], i)
        epc = jnp.clip(ep - 1, 0, p.max_epoch - 1)
        valid = (
            (onehot_row(d["used"], i) > 0)
            & (onehot_row(d["state"], i) == LEADER)
            & (onehot_row(d["acked"], v) == ACK_NIL)
            & (onehot_row(d["valueCtr"], epc) < p.max_values_per_epoch)
        )
        pos = onehot_row(d["log_len"], i)
        ovf = valid & (pos >= L)
        posc = jnp.clip(pos, 0, L - 1)
        succ = self._asm(
            d,
            log_cmd=onehot_set2(d["log_cmd"], i, posc, C_APPEND),
            log_epoch=onehot_set2(d["log_epoch"], i, posc, ep),
            log_val=onehot_set2(d["log_val"], i, posc, v + 1),
            log_len=onehot_add(d["log_len"], i, 1),
            acked=onehot_set(d["acked"], v, ACK_FALSE),
            valueCtr=onehot_add(d["valueCtr"], epc, 1),
        )
        return valid, succ, jnp.int32(KR_CLIENTREQUEST), ovf

    def _send_fetch_request(self, s, i, j):
        """SendFetchRequest — :1137-1169: known-leader follower fetch, or
        an Unattached observer probing a voter of its config."""
        d = self._dec(s)
        path_a = (onehot_row(d["leader"], i) == j + 1) & (
            onehot_row(d["state"], i) == FOLLOWER)
        path_b = (
            (onehot_row(d["role"], i) == R_OBSERVER)
            & (onehot_row(d["state"], i) == UNATTACHED)
            & (((onehot_row(d["cfg_members"], i) >> j) & 1) > 0)
        )
        valid = (
            (onehot_row(d["used"], i) > 0) & (onehot_row(d["used"], j) > 0)
            & (onehot_row(d["pf_active"], i) == 0)
            & (path_a | path_b)
        )
        ll_i = onehot_row(d["log_len"], i)
        last_ep = self._last_epoch(d, i)
        is_obs = (onehot_row(d["role"], i) == R_OBSERVER).astype(jnp.int32)
        key = self._pack(
            mtype=FETCHREQ, mepoch=onehot_row(d["currentEpoch"], i), mfetchOffset=ll_i,
            mlastFetchedEpoch=last_ep, mobserver=is_obs, msource=i, mdest=j,
        )
        words, cnt, _existed, ovf = bag.wide_bag_put(
            self._words(d), d["msg_cnt"], key
        )
        succ = self._asm(
            d,
            pf_active=onehot_set(d["pf_active"], i, 1),
            pf_epoch=onehot_set(d["pf_epoch"], i, onehot_row(d["currentEpoch"], i)),
            pf_offset=onehot_set(d["pf_offset"], i, ll_i),
            pf_lastepoch=onehot_set(d["pf_lastepoch"], i, last_ep),
            pf_dest=onehot_set(d["pf_dest"], i, j + 1),
            pf_observer=onehot_set(d["pf_observer"], i, is_obs),
            **self._wupd(words, cnt),
        )
        return valid, succ, jnp.int32(KR_SENDFETCH), ovf & valid

    def _start_new_server(self, s, h, j):
        """StartNewServer — :1492-1511: mints a fresh [host, diskId]
        observer in the next free slot; its first fetch targets a current
        leader. endOffset domain = the servers BEFORE the spawn."""
        NS = self.NS
        d = self._dec(s)
        n_used = jnp.sum((d["used"] > 0).astype(jnp.int32))
        valid = (
            (n_used < NS)
            & (onehot_row(d["used"], j) > 0)
            & (onehot_row(d["state"], j) == LEADER)
        )
        slot = jnp.clip(n_used, 0, NS - 1)
        disk_id = d["diskIdGen"] + 1
        old_mask = self._used_mask(d)
        key = self._pack(
            mtype=FETCHREQ, mepoch=0, mfetchOffset=0, mlastFetchedEpoch=0,
            mobserver=1, msource=slot, mdest=j,
        )
        words, cnt, _existed, ovf = bag.wide_bag_put(
            self._words(d), d["msg_cnt"], key
        )
        succ = self._asm(
            d,
            used=onehot_set(d["used"], slot, 1),
            host=onehot_set(d["host"], slot, h),
            diskId=onehot_set(d["diskId"], slot, disk_id),
            role=onehot_set(d["role"], slot, R_OBSERVER),
            state=onehot_set(d["state"], slot, UNATTACHED),
            currentEpoch=onehot_set(d["currentEpoch"], slot, 0),
            leader=onehot_set(d["leader"], slot, NIL),
            votedFor=onehot_set(d["votedFor"], slot, NIL),
            votesGranted=onehot_set(d["votesGranted"], slot, 0),
            cfg_id=onehot_set(d["cfg_id"], slot, 0),
            cfg_members=onehot_set(d["cfg_members"], slot, 0),
            cfg_committed=onehot_set(d["cfg_committed"], slot, 0),
            eo_dom=onehot_set(d["eo_dom"], slot, old_mask),
            endOffset=onehot_set(d["endOffset"], slot, jnp.zeros((NS,), jnp.int32)),
            log_len=onehot_set(d["log_len"], slot, 0),
            highWatermark=onehot_set(d["highWatermark"], slot, 0),
            pf_active=onehot_set(d["pf_active"], slot, 1),
            pf_epoch=onehot_set(d["pf_epoch"], slot, 0),
            pf_offset=onehot_set(d["pf_offset"], slot, 0),
            pf_lastepoch=onehot_set(d["pf_lastepoch"], slot, 0),
            pf_dest=onehot_set(d["pf_dest"], slot, j + 1),
            pf_observer=onehot_set(d["pf_observer"], slot, 1),
            diskIdGen=disk_id,
            **self._wupd(words, cnt),
        )
        return valid, succ, jnp.int32(KR_STARTNEWSERVER), ovf & valid

    def _send_join_request(self, s, i, j):
        """SendJoinRequest — :1524-1538: observer, non-member, to its
        known leader; JoinRequest is send-once. The _addReconfigCtr gate
        (:1526) is a constant (the ctr is never incremented)."""
        d = self._dec(s)
        valid = (
            jnp.asarray(self.p.max_add_reconfigs > 0)
            & (onehot_row(d["used"], i) > 0) & (onehot_row(d["used"], j) > 0)
            & (onehot_row(d["role"], i) == R_OBSERVER)
            & (((onehot_row(d["cfg_members"], i) >> i) & 1) == 0)
            & (onehot_row(d["leader"], i) == j + 1)
        )
        key = self._pack(
            mtype=JOINREQ, mepoch=onehot_row(d["currentEpoch"], i), mdest=j, msource=i
        )
        words, cnt, existed, ovf = bag.wide_bag_put(
            self._words(d), d["msg_cnt"], key
        )
        valid &= ~existed  # send-once (MessagePassing.tla:40-45)
        succ = self._asm(d, **self._wupd(words, cnt))
        return valid, succ, jnp.int32(KR_SENDJOIN), ovf & valid

    def _handle_remove_request(self, s, i, r):
        """HandleRemoveRequest — :1699-1724: admin removal appends a
        RemoveServerCommand; a self-removing leader becomes an observer
        but stays leader."""
        p, L = self.p, self.p.max_log
        d = self._dec(s)
        members = onehot_row(d["cfg_members"], i)
        msize = self._popcount(members)
        # RemoveCheck (:1692-1697) = Ok
        check_ok = (
            (onehot_row(d["state"], i) == LEADER)
            & (((members >> r) & 1) > 0)
            & (onehot_row(d["cfg_committed"], i) > 0)  # no pending config
            & self._leader_committed_in_epoch(d, i)
        )
        valid = (
            (onehot_row(d["used"], i) > 0) & (onehot_row(d["used"], r) > 0)
            & (d["removeCtr"] < p.max_remove_reconfigs)
            & check_ok
            & (msize > p.min_cluster_size)
        )
        new_members = members & ~(jnp.int32(1) << r)
        pos = onehot_row(d["log_len"], i)
        ovf = valid & (pos >= L)
        posc = jnp.clip(pos, 0, L - 1)
        new_len = pos + 1
        succ = self._asm(
            d,
            log_cmd=onehot_set2(d["log_cmd"], i, posc, C_REMOVE),
            log_epoch=onehot_set2(
                d["log_epoch"], i, posc, onehot_row(d["currentEpoch"], i)),
            log_cfgid=onehot_set2(
                d["log_cfgid"], i, posc, onehot_row(d["cfg_id"], i) + 1),
            log_who=onehot_set2(d["log_who"], i, posc, r + 1),
            log_members=onehot_set2(d["log_members"], i, posc, new_members),
            log_len=onehot_set(d["log_len"], i, new_len),
            cfg_id=onehot_set(d["cfg_id"], i, onehot_row(d["cfg_id"], i) + 1),
            cfg_members=onehot_set(d["cfg_members"], i, new_members),
            cfg_committed=onehot_set(
                d["cfg_committed"], i,
                (onehot_row(d["highWatermark"], i) >= new_len).astype(jnp.int32),
            ),
            role=onehot_set(
                d["role"], i,
                jnp.where(i == r, R_OBSERVER, onehot_row(d["role"], i)),
            ),
            removeCtr=d["removeCtr"] + 1,
        )
        return valid, succ, jnp.int32(KR_HANDLE_REMOVE), ovf

    # -------- fused message-receipt kernel (slot m) --------
    # The 13 receipt disjuncts of Next are mutually exclusive for a fixed
    # record (they partition on mtype, then on error/validity/mresult/
    # handled), so one kernel per slot computes whichever fires; `rank`
    # reports which for trace labels.

    def _handle_message(self, s, m):
        p, NS, L = self.p, self.NS, self.p.max_log
        d = self._dec(s)
        words, cnt = self._words(d), d["msg_cnt"]
        key = tuple(onehot_row(w, m) for w in words)
        occupied = key[0] != EMPTY
        u = partial(self.packer.unpack, key)
        mtype, mepoch = u("mtype"), u("mepoch")
        src, dst = u("msource"), u("mdest")
        cur = onehot_row(d["currentEpoch"], dst)
        st_dst = onehot_row(d["state"], dst)
        led_dst = onehot_row(d["leader"], dst)
        role_dst = onehot_row(d["role"], dst)
        log_dst = {f: onehot_row(d[f], dst) for f in LOG_FIELDS}
        # ReceivableMessage (:471-477): count > 0 and dest not DeadNoState
        recv = (
            occupied & (onehot_row(cnt, m) > 0)
            & (onehot_row(d["used"], dst) > 0) & (st_dst != DEAD)
        )
        equal_epoch = mepoch == cur

        def pf_clear(upd):
            return {**upd, **self._pf_clear_upd(d, dst)}

        cnt_disc = bag.bag_discard_at(cnt, m)

        # --- HandleRequestVoteRequest (:967-1018)
        b_rvreq = recv & (mtype == RVREQ)
        rv_err = mepoch < cur  # FencedLeaderEpoch
        s0_st = jnp.where(mepoch > cur, UNATTACHED, st_dst)
        s0_ep = jnp.where(mepoch > cur, mepoch, cur)
        s0_ld = jnp.where(mepoch > cur, NIL, led_dst)
        last_ep = self._last_epoch(d, dst)
        ll_dst = onehot_row(d["log_len"], dst)
        log_ok = (u("mlastLogEpoch") > last_ep) | (
            (u("mlastLogEpoch") == last_ep) & (u("mlastLogOffset") >= ll_dst)
        )
        grant = (
            (s0_st == UNATTACHED)
            | ((s0_st == VOTED) & (onehot_row(d["votedFor"], dst) == src + 1))
        ) & log_ok
        # TransitionToVoted (:630-637) when granting from Unattached; the
        # Unattached precondition makes its illegal arm unreachable
        take_voted = grant & (s0_st == UNATTACHED)
        f_st = jnp.where(take_voted, VOTED, s0_st)
        f_ep = jnp.where(take_voted, mepoch, s0_ep)
        f_ld = jnp.where(take_voted, NIL, s0_ld)
        r_ep = jnp.where(rv_err, cur, mepoch)
        r_ld = jnp.where(rv_err, led_dst, f_ld)
        r_grant = jnp.where(rv_err, 0, grant.astype(jnp.int32))
        r_err = jnp.where(rv_err, E_FENCED, E_NONE)
        rkey = self._pack(
            mtype=RVRESP, mepoch=r_ep, mleader=r_ld, mvoteGranted=r_grant,
            merror=r_err, msource=dst, mdest=src,
        )
        w1, c1, _ex1, ovf1 = self._reply(d, m, rkey)
        no_err = ~rv_err
        upd1 = self._wupd(w1, c1)
        upd1["state"] = jnp.where(no_err, onehot_set(d["state"], dst, f_st), d["state"])
        upd1["currentEpoch"] = jnp.where(
            no_err, onehot_set(d["currentEpoch"], dst, f_ep), d["currentEpoch"]
        )
        upd1["leader"] = jnp.where(
            no_err, onehot_set(d["leader"], dst, f_ld), d["leader"]
        )
        upd1["votedFor"] = jnp.where(
            no_err & grant, onehot_set(d["votedFor"], dst, src + 1), d["votedFor"]
        )
        pf_reset = no_err & (f_st != st_dst)
        for pf in ("pf_active", "pf_epoch", "pf_offset", "pf_lastepoch",
                   "pf_dest", "pf_observer"):
            upd1[pf] = jnp.where(pf_reset, onehot_set(d[pf], dst, 0), d[pf])
        s_rvreq = self._asm(d, **upd1)

        # --- HandleRequestVoteResponse (:1025-1050; adds the Voter gate)
        mh_st, mh_ep, mh_ld, handled = self._mhcr(
            d, dst, u("mleader"), mepoch, u("merror")
        )
        b_rvresp = (
            recv & (mtype == RVRESP) & (role_dst == R_VOTER)
            & (handled | (st_dst == CANDIDATE))
        )
        granted_bit = (u("mvoteGranted") > 0) & ~handled
        upd2 = dict(
            state=jnp.where(handled, onehot_set(d["state"], dst, mh_st), d["state"]),
            currentEpoch=jnp.where(
                handled, onehot_set(d["currentEpoch"], dst, mh_ep), d["currentEpoch"]
            ),
            leader=jnp.where(handled, onehot_set(d["leader"], dst, mh_ld), d["leader"]),
            votesGranted=jnp.where(
                granted_bit,
                onehot_set(
                    d["votesGranted"], dst,
                    onehot_row(d["votesGranted"], dst) | (jnp.int32(1) << src),
                ),
                d["votesGranted"],
            ),
            msg_cnt=cnt_disc,
        )
        s_rvresp = self._asm(d, **upd2)

        # --- AcceptBeginQuorumRequest (:1082-1102): Voter only; stale
        # requests are NOT answered (no reply arm in this spec)
        b_bqreq = (
            recv & (mtype == BQREQ) & (mepoch >= cur) & (role_dst == R_VOTER)
        )
        bt_st, bt_ep, bt_ld = self._maybe_transition(d, dst, src + 1, mepoch)
        upd3 = pf_clear(dict(
            state=onehot_set(d["state"], dst, bt_st),
            currentEpoch=onehot_set(d["currentEpoch"], dst, bt_ep),
            leader=onehot_set(d["leader"], dst, bt_ld),
            msg_cnt=cnt_disc,
        ))
        s_bqreq = self._asm(d, **upd3)

        # --- FetchRequest branches (:1195-1376)
        is_fetchreq = recv & (mtype == FETCHREQ)
        is_leader = st_dst == LEADER
        foff = u("mfetchOffset")
        flep = u("mlastFetchedEpoch")
        fobs = u("mobserver")
        corr_kw = dict(
            cepoch=mepoch, cfetchOffset=foff, clastFetchedEpoch=flep,
            cobserver=fobs,
        )
        ferr = jnp.where(
            ~is_leader, E_NOTLEADER,
            jnp.where(mepoch < cur, E_FENCED,
                      jnp.where(mepoch > cur, E_UNKNOWN_LEADER, E_NONE)),
        )
        valid_pos = self._valid_fetch_position(d, dst, foff, flep)
        eo_off, eo_ep = self._end_offset_for_epoch(d, dst, flep)

        # RejectFetchRequest (:1195-1217)
        b_reject = is_fetchreq & (ferr != E_NONE)
        rjkey = self._pack(
            mtype=FETCHRESP, mresult=R_NOTOK, merror=ferr, mleader=led_dst,
            mepoch=cur, mhwm=onehot_row(d["highWatermark"], dst),
            msource=dst, mdest=src,
            **corr_kw,
        )
        w4, c4, ex4, ovf4 = self._reply(d, m, rjkey)
        b_reject &= ~ex4  # FetchResponse no-duplicate (MessagePassing:72-79)
        s_reject = self._asm(d, **self._wupd(w4, c4))

        # DivergingFetchRequest (:1225-1248)
        b_div = is_fetchreq & equal_epoch & is_leader & ~valid_pos
        dvkey = self._pack(
            mtype=FETCHRESP, mepoch=cur, mresult=R_DIVERGING, merror=E_NONE,
            mdivergingEpoch=eo_ep, mdivergingEndOffset=eo_off,
            mleader=led_dst, mhwm=onehot_row(d["highWatermark"], dst),
            msource=dst, mdest=src, **corr_kw,
        )
        w5, c5, ex5, ovf5 = self._reply(d, m, dvkey)
        b_div &= ~ex5
        s_div = self._asm(d, **self._wupd(w5, c5))

        # shared accept-fetch entry lookup
        offset = foff + 1
        have_entry = offset <= ll_dst
        epos = jnp.clip(offset - 1, 0, L - 1)
        ent = {
            f: jnp.where(have_entry, onehot_row(log_dst[f], epos), 0)
            for f in LOG_FIELDS
        }
        ent_kw = dict(
            nentries=have_entry.astype(jnp.int32), e_cmd=ent["log_cmd"],
            e_epoch=ent["log_epoch"], e_val=ent["log_val"],
            e_cfgid=ent["log_cfgid"], e_who=ent["log_who"],
            e_members=ent["log_members"],
        )

        # AcceptFetchRequestFromVoter (:1286-1342)
        b_acc_v = is_fetchreq & equal_epoch & is_leader & valid_pos & (fobs == 0)
        new_end = onehot_set(onehot_row(d["endOffset"], dst), src, foff)
        new_eo_dom = onehot_set(
            d["eo_dom"], dst, onehot_row(d["eo_dom"], dst) | (jnp.int32(1) << src)
        )
        members = onehot_row(d["cfg_members"], dst)
        msize = self._popcount(members)
        # NewHighwaterMark (:1266-1284): leader self-exclusion when removed
        idxs = jnp.arange(1, L + 1, dtype=jnp.int32)
        mem_bits = ((members >> jnp.arange(NS, dtype=jnp.int32)) & 1) > 0
        is_self = jnp.arange(NS, dtype=jnp.int32) == dst
        agree = mem_bits[None, :] & (
            (new_end[None, :] >= idxs[:, None]) | is_self[None, :]
        )
        quorum_ok = 2 * jnp.sum(agree, axis=1) > msize
        in_log = idxs <= ll_dst
        best = jnp.max(jnp.where(quorum_ok & in_log, idxs, 0))
        ep_at = onehot_row(log_dst["log_epoch"], jnp.clip(best - 1, 0))
        hwm_old = onehot_row(d["highWatermark"], dst)
        new_hwm = jnp.where((best > 0) & (ep_at == cur), best, hwm_old)
        advanced = new_hwm > hwm_old
        # IsRemovedFromCluster (:1259-1264) over (hwm_old, new_hwm]
        lanes = jnp.arange(L, dtype=jnp.int32)
        in_range = (lanes + 1 > hwm_old) & (lanes + 1 <= new_hwm)
        leaves = advanced & jnp.any(
            in_range
            & (log_dst["log_cmd"] == C_REMOVE)
            & (((log_dst["log_members"] >> dst) & 1) == 0)
        )
        # config refresh from the most recent reconfig entry (ci = new_hwm)
        cfg_off = self._most_recent_reconfig(d, log_dst["log_cmd"], ll_dst)
        cfg_lane = jnp.clip(cfg_off - 1, 0, L - 1)
        # acked: in-flight values committed in (hwm_old, new_hwm] (:1331-1338)
        committed = jnp.any(
            in_range[None, :]
            & (log_dst["log_cmd"][None, :] == C_APPEND)
            & (
                log_dst["log_val"][None, :]
                == jnp.arange(1, p.n_values + 1, dtype=jnp.int32)[:, None]
            ),
            axis=1,
        )
        acked_v = jnp.where(
            advanced & (d["acked"] == ACK_FALSE) & committed, ACK_TRUE, d["acked"]
        )
        used_mask = self._used_mask(d)
        upd6 = dict(
            acked=acked_v,
            cfg_id=jnp.where(
                advanced,
                onehot_set(
                    d["cfg_id"], dst, onehot_row(log_dst["log_cfgid"], cfg_lane)
                ),
                d["cfg_id"],
            ),
            cfg_members=jnp.where(
                advanced,
                onehot_set(
                    d["cfg_members"], dst,
                    onehot_row(log_dst["log_members"], cfg_lane),
                ),
                d["cfg_members"],
            ),
            cfg_committed=jnp.where(
                advanced,
                onehot_set(
                    d["cfg_committed"], dst, (new_hwm >= cfg_off).astype(jnp.int32)
                ),
                d["cfg_committed"],
            ),
            role=jnp.where(
                leaves, onehot_set(d["role"], dst, R_OBSERVER), d["role"]
            ),
            state=jnp.where(
                leaves, onehot_set(d["state"], dst, UNATTACHED), d["state"]
            ),
            leader=jnp.where(leaves, onehot_set(d["leader"], dst, NIL), d["leader"]),
            votesGranted=jnp.where(
                leaves, onehot_set(d["votesGranted"], dst, 0), d["votesGranted"]
            ),
            eo_dom=jnp.where(
                leaves,
                onehot_set(d["eo_dom"], dst, used_mask),
                new_eo_dom,
            ),
            endOffset=jnp.where(
                leaves,
                onehot_set(d["endOffset"], dst, jnp.zeros((NS,), jnp.int32)),
                onehot_set(d["endOffset"], dst, new_end),
            ),
            highWatermark=jnp.where(
                leaves,
                onehot_set(d["highWatermark"], dst, 0),
                jnp.where(
                    advanced,
                    onehot_set(d["highWatermark"], dst, new_hwm),
                    d["highWatermark"],
                ),
            ),
        )
        ackey = self._pack(
            mtype=FETCHRESP, mepoch=cur,
            mleader=jnp.where(leaves, NIL, led_dst), mresult=R_OK,
            merror=E_NONE, mhwm=jnp.minimum(new_hwm, offset),
            msource=dst, mdest=src, **ent_kw, **corr_kw,
        )
        w6, c6, ex6, ovf6 = self._reply(d, m, ackey)
        b_acc_v &= ~ex6
        s_acc_v = self._asm(d, **upd6, **self._wupd(w6, c6))

        # AcceptFetchRequestFromObserver (:1349-1376): response only
        b_acc_o = is_fetchreq & equal_epoch & is_leader & valid_pos & (fobs == 1)
        aokey = self._pack(
            mtype=FETCHRESP, mepoch=cur, mleader=led_dst, mresult=R_OK,
            merror=E_NONE, mhwm=jnp.minimum(offset, hwm_old),
            msource=dst, mdest=src, **ent_kw, **corr_kw,
        )
        w7, c7, ex7, ovf7 = self._reply(d, m, aokey)
        b_acc_o &= ~ex7
        s_acc_o = self._asm(d, **self._wupd(w7, c7))

        # Part 4 (fetch responses, join handling, branch select) below.
        return self._handle_message_part2(
            s, d, m, u, recv, mtype, mepoch, src, dst, log_dst, cnt_disc,
            handled, mh_st, mh_ep, mh_ld,
            [
                (b_rvreq, s_rvreq, KR_HANDLE_RVREQ, ovf1),
                (b_rvresp, s_rvresp, KR_HANDLE_RVRESP, jnp.asarray(False)),
                (b_reject, s_reject, KR_REJECT_FETCH, ovf4),
                (b_div, s_div, KR_DIVERGING_FETCH, ovf5),
                (b_acc_v, s_acc_v, KR_ACCEPT_FETCH_VOTER, ovf6),
                (b_acc_o, s_acc_o, KR_ACCEPT_FETCH_OBSERVER, ovf7),
                (b_bqreq, s_bqreq, KR_ACCEPT_BQREQ, jnp.asarray(False)),
            ],
        )


    # -------- temporal-property kernels (:1775-1839) --------

    def _no_progress_possible(self, states):
        r"""NoProgressPossible — :1775-1781. The \E j conjunct compares
        state[j] to the ROLE model value Voter (:1780), which no state
        assignment ever produces — same quirk class as
        RestartWithoutState:913 — so the ~\E i arm is vacuously TRUE and
        the definition reduces to _electionCtr = MaxElections; reproduced
        faithfully."""
        ec = self.layout.get(states, "electionCtr")
        return ec == self.p.max_elections

    def _is_current_leader(self, states):
        """IsCurrentLeader(i) — :1787-1792: Leader with no higher-epoch
        peer. [B, NS] mask (used slots only)."""
        lay = self.layout
        used = lay.get(states, "used") > 0
        st = lay.get(states, "state")
        ep = lay.get(states, "currentEpoch")
        higher = jnp.any(
            used[:, None, :] & (ep[:, None, :] > ep[:, :, None]), axis=2
        )
        return used & (st == LEADER) & ~higher

    def _live_committed_value_or_nothing(self, v, states):
        """CommittedValueOrNothing(v) — :1794-1808: a current leader's
        whole member set either has v committed or has v nowhere."""
        lay, L, NS = self.layout, self.p.max_log, self.NS
        cmd = lay.get(states, "log_cmd")
        lv = lay.get(states, "log_val")
        ll = lay.get(states, "log_len")
        hwm = lay.get(states, "highWatermark")
        lanes = jnp.arange(L, dtype=jnp.int32)
        has = (
            (lanes[None, None, :] < ll[..., None])
            & (cmd == C_APPEND)
            & (lv == v + 1)
        )
        in_log = jnp.any(has, axis=2)  # ValueNotInServerLog = ~in_log
        committed = jnp.any(
            has & (hwm[..., None] >= lanes[None, None, :] + 1), axis=2
        )
        return self._live_all_or_nothing(states, committed, in_log)

    def _live_all_or_nothing(self, states, committed, in_log):
        """Shared tail of the []<> formulas (:1804-1808 / :1829-1834):
        NoProgressPossible, or some current leader whose whole member set
        either has the thing committed or lacks it entirely. `committed`
        and `in_log` are [B, NS] per-server presence masks."""
        lay, NS = self.layout, self.NS
        icl = self._is_current_leader(states)
        member = (
            (lay.get(states, "cfg_members")[:, :, None]
             >> jnp.arange(NS, dtype=jnp.int32)[None, None, :]) & 1
        ) > 0  # [B, l, i]
        all_committed = jnp.all(~member | committed[:, None, :], axis=2)
        all_absent = jnp.all(~member | ~in_log[:, None, :], axis=2)
        ok = jnp.any(icl & (all_committed | all_absent), axis=1)
        return self._no_progress_possible(states) | ok

    def _live_config_all_or_nothing(self, cid, states):
        """ConfigAllOrNothing(config_id) — :1817-1834."""
        lay, L, NS = self.layout, self.p.max_log, self.NS
        cmd = lay.get(states, "log_cmd")
        cfgid = lay.get(states, "log_cfgid")
        ll = lay.get(states, "log_len")
        hwm = lay.get(states, "highWatermark")
        lanes = jnp.arange(L, dtype=jnp.int32)
        is_cfg = (
            ((cmd == C_INIT) | (cmd == C_ADD) | (cmd == C_REMOVE))
            & (lanes[None, None, :] < ll[..., None])
            & (cfgid == cid)
        )
        in_log = jnp.any(is_cfg, axis=2)
        committed = jnp.any(
            is_cfg & (hwm[..., None] >= lanes[None, None, :] + 1), axis=2
        )
        return self._live_all_or_nothing(states, committed, in_log)

    # ---------------- full expansion ----------------

    def _expand1(self, s):
        """All successor candidates of one state.

        Returns (succs [A, W], valid [A], rank [A], ovf [A])."""
        p, NS = self.p, self.NS
        V, H, M = p.n_values, p.n_hosts, p.msg_slots
        iota = jnp.arange(NS, dtype=jnp.int32)
        pr_i = jnp.asarray([ij[0] for ij in self._pairs], jnp.int32)
        pr_j = jnp.asarray([ij[1] for ij in self._pairs], jnp.int32)
        outs = []
        outs.append(jax.vmap(lambda i: self._restart_with_state(s, i))(iota))
        outs.append(jax.vmap(lambda i: self._request_vote(s, i))(iota))
        outs.append(jax.vmap(lambda i: self._become_leader(s, i))(iota))
        cr_i = jnp.repeat(iota, V)
        cr_v = jnp.tile(jnp.arange(V, dtype=jnp.int32), NS)
        outs.append(jax.vmap(lambda i, v: self._client_request(s, i, v))(cr_i, cr_v))
        outs.append(
            jax.vmap(lambda i, j: self._send_fetch_request(s, i, j))(pr_i, pr_j)
        )
        sn_h = jnp.repeat(jnp.arange(H, dtype=jnp.int32), NS)
        sn_j = jnp.tile(iota, H)
        outs.append(jax.vmap(lambda h, j: self._start_new_server(s, h, j))(sn_h, sn_j))
        outs.append(
            jax.vmap(lambda i, j: self._send_join_request(s, i, j))(pr_i, pr_j)
        )
        rm_i = jnp.repeat(iota, NS)
        rm_r = jnp.tile(iota, NS)
        outs.append(
            jax.vmap(lambda i, r: self._handle_remove_request(s, i, r))(rm_i, rm_r)
        )
        outs.append(
            jax.vmap(lambda m: self._handle_message(s, m))(
                jnp.arange(M, dtype=jnp.int32)
            )
        )
        valid = jnp.concatenate([o[0] for o in outs])
        succs = jnp.concatenate([o[1] for o in outs])
        rank = jnp.concatenate([o[2] for o in outs])
        ovf = jnp.concatenate([o[3] for o in outs])
        return succs, valid, rank, ovf

    # ---------------- initial states ----------------

    def init_states(self) -> np.ndarray:
        """Init — :845-859: pre-installed cluster of the first
        InitClusterSize hosts (identities (h, 0) in slot h), leader = the
        lowest identity, one InitClusterCommand entry committed."""
        p, lay = self.p, self.layout
        NS, ics = self.NS, p.init_cluster_size
        vec = lay.zeros((1,))
        members_mask = (1 << ics) - 1
        host = np.zeros(NS, np.int32)
        used = np.zeros(NS, np.int32)
        role = np.zeros(NS, np.int32)
        state = np.zeros(NS, np.int32)
        epoch = np.zeros(NS, np.int32)
        leader = np.zeros(NS, np.int32)
        cfg_id = np.zeros(NS, np.int32)
        cfg_members = np.zeros(NS, np.int32)
        cfg_committed = np.zeros(NS, np.int32)
        eo_dom = np.zeros(NS, np.int32)
        hwm = np.zeros(NS, np.int32)
        log_cmd = np.zeros((NS, p.max_log), np.int32)
        log_epoch = np.zeros((NS, p.max_log), np.int32)
        log_cfgid = np.zeros((NS, p.max_log), np.int32)
        log_members = np.zeros((NS, p.max_log), np.int32)
        log_len = np.zeros(NS, np.int32)
        eo = np.zeros((NS, NS), np.int32)
        for h in range(ics):
            host[h] = h
            used[h] = 1
            role[h] = R_VOTER
            state[h] = LEADER if h == 0 else FOLLOWER
            epoch[h] = 1
            leader[h] = 1  # slot 0 + 1 (lowest identity, CHOOSE as min)
            cfg_id[h] = 1
            cfg_members[h] = members_mask
            cfg_committed[h] = 1
            eo_dom[h] = members_mask
            hwm[h] = 1
            log_cmd[h, 0] = C_INIT
            log_epoch[h, 0] = 1
            log_cfgid[h, 0] = 1
            log_members[h, 0] = members_mask
            log_len[h] = 1
            eo[h, :ics] = 1
        vec[0, lay.sl("host")] = host
        vec[0, lay.sl("used")] = used
        vec[0, lay.sl("role")] = role
        vec[0, lay.sl("state")] = state
        vec[0, lay.sl("currentEpoch")] = epoch
        vec[0, lay.sl("leader")] = leader
        vec[0, lay.sl("cfg_id")] = cfg_id
        vec[0, lay.sl("cfg_members")] = cfg_members
        vec[0, lay.sl("cfg_committed")] = cfg_committed
        vec[0, lay.sl("eo_dom")] = eo_dom
        vec[0, lay.sl("endOffset")] = eo.reshape(-1)
        vec[0, lay.sl("log_cmd")] = log_cmd.reshape(-1)
        vec[0, lay.sl("log_epoch")] = log_epoch.reshape(-1)
        vec[0, lay.sl("log_cfgid")] = log_cfgid.reshape(-1)
        vec[0, lay.sl("log_members")] = log_members.reshape(-1)
        vec[0, lay.sl("log_len")] = log_len
        vec[0, lay.sl("highWatermark")] = hwm
        for k in range(self.packer.n_words):
            vec[0, lay.sl(f"msg_w{k}")] = int(EMPTY)
        return vec

    # ---------------- invariants (:1848-1912) ----------------

    def _inv_no_illegal(self, states):
        """NoIllegalState — :1848-1850."""
        st = self.layout.get(states, "state")
        return jnp.all(st != ILLEGAL, axis=1)

    def _inv_no_log_divergence(self, states):
        """NoLogDivergence — :1860-1868: committed prefixes (up to the
        pairwise-min hwm) must agree on FULL entry equality."""
        lay, L = self.layout, self.p.max_log
        used = lay.get(states, "used") > 0
        hwm = lay.get(states, "highWatermark")
        mh = jnp.minimum(hwm[:, :, None], hwm[:, None, :])
        lanes = jnp.arange(1, L + 1, dtype=jnp.int32)
        in_common = lanes[None, None, None, :] <= mh[..., None]
        eq = jnp.ones_like(in_common)
        for f in LOG_FIELDS:
            v = lay.get(states, f)
            eq &= v[:, :, None, :] == v[:, None, :, :]
        both = used[:, :, None] & used[:, None, :]
        return jnp.all(~(both[..., None] & in_common) | eq, axis=(1, 2, 3))

    def _inv_states_match_roles(self, states):
        """StatesMatchRoles — :1876-1881."""
        lay = self.layout
        used = lay.get(states, "used") > 0
        role = lay.get(states, "role")
        st = lay.get(states, "state")
        led = lay.get(states, "leader")
        obs_ok = (
            (st == LEADER) | (st == FOLLOWER) | (st == UNATTACHED) | (st == VOTED)
        )
        bad = used & (
            ((role == R_OBSERVER) & ~obs_ok)
            | ((st == UNATTACHED) & (led != NIL))
        )
        return ~jnp.any(bad, axis=1)

    def _inv_never_two_leaders(self, states):
        """NeverTwoLeadersInSameEpoch — :1886-1892."""
        lay = self.layout
        used = lay.get(states, "used") > 0
        led = lay.get(states, "leader")
        ep = lay.get(states, "currentEpoch")
        both = (
            used[:, :, None] & used[:, None, :]
            & (led[:, :, None] != NIL) & (led[:, None, :] != NIL)
        )
        conflict = (
            both
            & (led[:, :, None] != led[:, None, :])
            & (ep[:, :, None] == ep[:, None, :])
        )
        return ~jnp.any(conflict, axis=(1, 2))

    def _inv_leader_has_acked(self, states):
        """LeaderHasAllAckedValues — :1896-1912 (APPEND entries only)."""
        lay, V = self.layout, self.p.n_values
        used = lay.get(states, "used") > 0
        ep = lay.get(states, "currentEpoch")
        st = lay.get(states, "state")
        cmd = lay.get(states, "log_cmd")
        lv = lay.get(states, "log_val")
        acked = lay.get(states, "acked")
        # "no other server has a strictly higher epoch"; l = i contributes
        # nothing (ep[i] > ep[i] is false), so no off-diagonal mask needed
        higher = used[:, None, :] & (ep[:, None, :] > ep[:, :, None])
        not_stale = ~jnp.any(higher, axis=2)
        is_lead = used & (st == LEADER) & not_stale
        vals = jnp.arange(1, V + 1, dtype=jnp.int32)
        has_v = jnp.any(
            (cmd[:, :, None, :] == C_APPEND)
            & (lv[:, :, None, :] == vals[None, None, :, None]),
            axis=3,
        )
        bad = jnp.any(
            (acked[:, None, :] == ACK_TRUE) & is_lead[:, :, None] & ~has_v,
            axis=(1, 2),
        )
        return ~bad

    def _inv_messages_are_valid(self, states):
        """MessagesAreValid — MessagePassing.tla:81-83: no self-addressed
        record in the bag domain (checker self-check)."""
        lay = self.layout
        w0 = lay.get(states, "msg_w0")
        occupied = w0 != EMPTY
        src = self.packer.unpack([lay.get(states, f"msg_w{k}")
                                  for k in range(self.packer.n_words)], "msource")
        dst = self.packer.unpack([lay.get(states, f"msg_w{k}")
                                  for k in range(self.packer.n_words)], "mdest")
        return ~jnp.any(occupied & (src == dst), axis=1)

    # ---------------- host-side decode/encode ----------------
    # Slot assignment rule (see module docstring): initial identity (h, 0)
    # <-> slot h; spawned identity (h, d) with d >= 1 <-> slot ics + d - 1.
    # Device evolution preserves it (new servers take the next free slot
    # and diskId equals the creation counter), so encode() of any oracle-
    # reachable state round-trips through the device kernels.

    def _slot_ident(self, vec, slot: int) -> tuple[int, int]:
        lay = self.layout
        return (
            int(vec[lay.fields["host"].offset + slot]),
            int(vec[lay.fields["diskId"].offset + slot]),
        )

    def decode(self, vec: np.ndarray) -> dict:
        """Decode a packed state into the oracle's dict format
        (identity-keyed maps, entry tuples, frozenset message bag)."""
        lay, p = self.layout, self.p
        NS = self.NS
        vec = np.asarray(vec)
        g = lambda n: np.asarray(vec[lay.sl(n)])
        used = g("used")
        slots = [i for i in range(NS) if used[i]]
        ids = {i: self._slot_ident(vec, i) for i in slots}

        def ref(v):  # slot+1 encoded reference -> identity | None
            return None if v == 0 else ids[int(v) - 1]

        def mask_set(mask):
            return frozenset(ids[i] for i in slots if (int(mask) >> i) & 1)

        from ..oracle import kraft_reconfig_oracle as KO

        state_names = {
            UNATTACHED: KO.UNATTACHED, VOTED: KO.VOTED, FOLLOWER: KO.FOLLOWER,
            CANDIDATE: KO.CANDIDATE, LEADER: KO.LEADER, RESIGNED: KO.RESIGNED,
            DEAD: KO.DEAD, ILLEGAL: KO.ILLEGAL,
        }
        role_names = {R_VOTER: KO.VOTER, R_OBSERVER: KO.OBSERVER, R_DEAD: KO.DEAD}

        lt = {f: g(f).reshape(NS, p.max_log) for f in LOG_FIELDS}
        ll = g("log_len")

        def entry(i, k):
            cmd = int(lt["log_cmd"][i, k])
            ep = int(lt["log_epoch"][i, k])
            if cmd == C_APPEND:
                return (KO.APPEND_CMD, ep, int(lt["log_val"][i, k]) - 1)
            members = mask_set(lt["log_members"][i, k])
            cid = int(lt["log_cfgid"][i, k])
            if cmd == C_INIT:
                return (KO.INIT_CMD, ep, (cid, members))
            who = ids[int(lt["log_who"][i, k]) - 1]
            name = KO.ADD_CMD if cmd == C_ADD else KO.REMOVE_CMD
            return (name, ep, (cid, who, members))

        pf_act, pf_ep = g("pf_active"), g("pf_epoch")
        pf_off, pf_le = g("pf_offset"), g("pf_lastepoch")
        pf_d, pf_o = g("pf_dest"), g("pf_observer")

        def pending(i):
            if not pf_act[i]:
                return None
            return KO.rec(
                mtype="FetchRequest", mepoch=int(pf_ep[i]),
                mfetchOffset=int(pf_off[i]), mlastFetchedEpoch=int(pf_le[i]),
                mobserver=bool(pf_o[i]), msource=ids[i], mdest=ref(pf_d[i]),
            )

        eo = g("endOffset").reshape(NS, NS)
        eo_dom = g("eo_dom")
        words = [g(f"msg_w{k}") for k in range(self.packer.n_words)]
        cnts = g("msg_cnt")
        msgs = {}
        for k in range(p.msg_slots):
            if int(words[0][k]) == int(EMPTY):
                continue
            keyk = tuple(int(w[k]) for w in words)
            msgs[self.decode_msg(keyk, ids)] = int(cnts[k])
        ack_map = {ACK_NIL: None, ACK_FALSE: False, ACK_TRUE: True}
        scalar = lambda n: int(vec[lay.fields[n].offset])
        return {
            "servers": frozenset(ids.values()),
            "config": {
                ids[i]: (
                    int(g("cfg_id")[i]),
                    mask_set(g("cfg_members")[i]),
                    bool(g("cfg_committed")[i]),
                )
                for i in slots
            },
            "currentEpoch": {ids[i]: int(g("currentEpoch")[i]) for i in slots},
            "role": {ids[i]: role_names[int(g("role")[i])] for i in slots},
            "state": {ids[i]: state_names[int(g("state")[i])] for i in slots},
            "leader": {ids[i]: ref(g("leader")[i]) for i in slots},
            "votedFor": {ids[i]: ref(g("votedFor")[i]) for i in slots},
            "pendingFetch": {ids[i]: pending(i) for i in slots},
            "votesGranted": {ids[i]: mask_set(g("votesGranted")[i]) for i in slots},
            "endOffset": {
                ids[i]: {
                    ids[j]: int(eo[i, j])
                    for j in slots
                    if (int(eo_dom[i]) >> j) & 1
                }
                for i in slots
            },
            "log": {
                ids[i]: tuple(entry(i, k) for k in range(int(ll[i])))
                for i in slots
            },
            "highWatermark": {ids[i]: int(g("highWatermark")[i]) for i in slots},
            "messages": frozenset(msgs.items()),
            "_acked": tuple(ack_map[int(a)] for a in g("acked")),
            "_electionCtr": scalar("electionCtr"),
            "_valueCtr": tuple(int(x) for x in g("valueCtr")),
            "_restartCtr": scalar("restartCtr"),
            "_addReconfigCtr": 0,  # never incremented (:1526) — constant
            "_removeReconfigCtr": scalar("removeCtr"),
            "_diskIdGen": scalar("diskIdGen"),
        }

    def decode_msg(self, key: tuple, ids: dict) -> tuple:
        from ..oracle import kraft_reconfig_oracle as KO

        u = self.packer.unpack_all(key)
        mtype = int(u["mtype"])
        src, dst = ids[int(u["msource"])], ids[int(u["mdest"])]
        kw = dict(
            mtype=MTYPE_NAMES[mtype], mepoch=int(u["mepoch"]),
            msource=src, mdest=dst,
        )
        mlead = None if not u["mleader"] else ids[int(u["mleader"]) - 1]
        if mtype == RVREQ:
            kw.update(
                mlastLogEpoch=int(u["mlastLogEpoch"]),
                mlastLogOffset=int(u["mlastLogOffset"]),
            )
        elif mtype == RVRESP:
            kw.update(
                mleader=mlead, mvoteGranted=bool(u["mvoteGranted"]),
                merror=ERROR_NAMES[int(u["merror"])],
            )
        elif mtype == FETCHREQ:
            kw.update(
                mfetchOffset=int(u["mfetchOffset"]),
                mlastFetchedEpoch=int(u["mlastFetchedEpoch"]),
                mobserver=bool(u["mobserver"]),
            )
        elif mtype == JOINRESP:
            kw.update(
                mleader=mlead, mresult=RESULT_NAMES[int(u["mresult"])],
                merror=ERROR_NAMES[int(u["merror"])],
            )
        elif mtype == FETCHRESP:
            res = int(u["mresult"])
            kw.update(
                mresult=RESULT_NAMES[res],
                merror=ERROR_NAMES[int(u["merror"])],
                mleader=mlead, mhwm=int(u["mhwm"]),
            )
            if res == R_OK:
                if int(u["nentries"]):
                    cmd = int(u["e_cmd"])
                    ep = int(u["e_epoch"])
                    if cmd == C_APPEND:
                        ent = (KO.APPEND_CMD, ep, int(u["e_val"]) - 1)
                    else:
                        members = frozenset(
                            ids[i] for i in ids if (int(u["e_members"]) >> i) & 1
                        )
                        cid = int(u["e_cfgid"])
                        if cmd == C_INIT:
                            ent = (KO.INIT_CMD, ep, (cid, members))
                        else:
                            ent = (
                                KO.ADD_CMD if cmd == C_ADD else KO.REMOVE_CMD,
                                ep,
                                (cid, ids[int(u["e_who"]) - 1], members),
                            )
                    kw["mentries"] = (ent,)
                else:
                    kw["mentries"] = ()
            if res == R_DIVERGING:
                kw.update(
                    mdivergingEpoch=int(u["mdivergingEpoch"]),
                    mdivergingEndOffset=int(u["mdivergingEndOffset"]),
                )
            kw["correlation"] = KO.rec(
                mtype="FetchRequest", mepoch=int(u["cepoch"]),
                mfetchOffset=int(u["cfetchOffset"]),
                mlastFetchedEpoch=int(u["clastFetchedEpoch"]),
                mobserver=bool(u["cobserver"]), msource=dst, mdest=src,
            )
        return KO.rec(**kw)

    def _ident_slot(self, ident: tuple[int, int]) -> int:
        h, dk = ident
        if dk == 0:
            assert h < self.p.init_cluster_size, ident
            return h
        return self.p.init_cluster_size + dk - 1

    def encode_msg(self, m: tuple, slot_of: dict) -> tuple:
        from ..oracle import kraft_reconfig_oracle as KO

        d = dict(m)
        inv_err = {v: k for k, v in ERROR_NAMES.items()}
        inv_res = {v: k for k, v in RESULT_NAMES.items()}
        inv_mt = {v: k for k, v in MTYPE_NAMES.items()}
        mtype = inv_mt[d["mtype"]]
        kw = dict(
            mtype=mtype, mepoch=d["mepoch"],
            msource=slot_of[d["msource"]], mdest=slot_of[d["mdest"]],
        )
        if mtype == RVREQ:
            kw.update(
                mlastLogEpoch=d["mlastLogEpoch"],
                mlastLogOffset=d["mlastLogOffset"],
            )
        elif mtype == RVRESP:
            kw.update(
                mleader=0 if d["mleader"] is None else slot_of[d["mleader"]] + 1,
                mvoteGranted=int(d["mvoteGranted"]),
                merror=inv_err[d["merror"]],
            )
        elif mtype == FETCHREQ:
            kw.update(
                mfetchOffset=d["mfetchOffset"],
                mlastFetchedEpoch=d["mlastFetchedEpoch"],
                mobserver=int(d["mobserver"]),
            )
        elif mtype == JOINRESP:
            kw.update(
                mleader=0 if d["mleader"] is None else slot_of[d["mleader"]] + 1,
                mresult=inv_res[d["mresult"]],
                merror=inv_err[d["merror"]],
            )
        elif mtype == FETCHRESP:
            corr = dict(d["correlation"])
            kw.update(
                mresult=inv_res[d["mresult"]],
                merror=inv_err[d["merror"]],
                mleader=0 if d["mleader"] is None else slot_of[d["mleader"]] + 1,
                mhwm=d["mhwm"],
                cepoch=corr["mepoch"],
                cfetchOffset=corr["mfetchOffset"],
                clastFetchedEpoch=corr["mlastFetchedEpoch"],
                cobserver=int(corr["mobserver"]),
            )
            if d["mresult"] == "Ok" and d.get("mentries"):
                cmd_name, ep, val = d["mentries"][0]
                inv_cmd = {v: k for k, v in CMD_NAMES.items()}
                cmd = inv_cmd[cmd_name]
                kw.update(nentries=1, e_cmd=cmd, e_epoch=ep)
                if cmd == C_APPEND:
                    kw["e_val"] = val + 1
                else:
                    if cmd == C_INIT:
                        cid, members = val
                    else:
                        cid, who, members = val
                        kw["e_who"] = slot_of[who] + 1
                    kw["e_cfgid"] = cid
                    kw["e_members"] = sum(
                        1 << slot_of[x] for x in members
                    )
            elif d["mresult"] == "Ok":
                kw["nentries"] = 0
            if d["mresult"] == "Diverging":
                kw.update(
                    mdivergingEpoch=d["mdivergingEpoch"],
                    mdivergingEndOffset=d["mdivergingEndOffset"],
                )
        return self.packer.pack(**kw)

    def encode(self, st: dict) -> np.ndarray:
        """Encode an oracle state dict into the packed slot vector."""
        from ..oracle import kraft_reconfig_oracle as KO

        lay, p = self.layout, self.p
        NS = self.NS
        vec = lay.zeros(())
        slot_of = {ident: self._ident_slot(ident) for ident in st["servers"]}
        inv_state = {v: k for k, v in STATE_NAMES.items()}
        inv_role = {v: k for k, v in ROLE_NAMES.items()}

        def put(name, slot, val):
            vec[lay.fields[name].offset + slot] = val

        def mask_of(idset):
            return sum(1 << slot_of[x] for x in idset)

        for ident, slot in slot_of.items():
            put("host", slot, ident[0])
            put("diskId", slot, ident[1])
            put("used", slot, 1)
            put("role", slot, inv_role[st["role"][ident]])
            put("state", slot, inv_state[st["state"][ident]])
            put("currentEpoch", slot, st["currentEpoch"][ident])
            led = st["leader"][ident]
            put("leader", slot, 0 if led is None else slot_of[led] + 1)
            vf = st["votedFor"][ident]
            put("votedFor", slot, 0 if vf is None else slot_of[vf] + 1)
            pf = st["pendingFetch"][ident]
            if pf is not None:
                c = dict(pf)
                put("pf_active", slot, 1)
                put("pf_epoch", slot, c["mepoch"])
                put("pf_offset", slot, c["mfetchOffset"])
                put("pf_lastepoch", slot, c["mlastFetchedEpoch"])
                put("pf_dest", slot, slot_of[c["mdest"]] + 1)
                put("pf_observer", slot, int(c["mobserver"]))
            put("votesGranted", slot, mask_of(st["votesGranted"][ident]))
            cid, members, committed = st["config"][ident]
            put("cfg_id", slot, cid)
            put("cfg_members", slot, mask_of(members))
            put("cfg_committed", slot, int(committed))
            eo = st["endOffset"][ident]
            put("eo_dom", slot, mask_of(eo.keys()))
            for j, v in eo.items():
                vec[lay.fields["endOffset"].offset + slot * NS + slot_of[j]] = v
            for k, e in enumerate(st["log"][ident]):
                cmd_name, ep, val = e
                inv_cmd = {v: kk for kk, v in CMD_NAMES.items()}
                cmd = inv_cmd[cmd_name]
                base = slot * p.max_log + k
                vec[lay.fields["log_cmd"].offset + base] = cmd
                vec[lay.fields["log_epoch"].offset + base] = ep
                if cmd == C_APPEND:
                    vec[lay.fields["log_val"].offset + base] = val + 1
                else:
                    if cmd == C_INIT:
                        cid2, mem2 = val
                    else:
                        cid2, who2, mem2 = val
                        vec[lay.fields["log_who"].offset + base] = slot_of[who2] + 1
                    vec[lay.fields["log_cfgid"].offset + base] = cid2
                    vec[lay.fields["log_members"].offset + base] = mask_of(mem2)
            put("log_len", slot, len(st["log"][ident]))
            put("highWatermark", slot, st["highWatermark"][ident])
        ack_inv = {None: ACK_NIL, False: ACK_FALSE, True: ACK_TRUE}
        vec[lay.sl("acked")] = [ack_inv[a] for a in st["_acked"]]
        keys = sorted(
            (self.encode_msg(rec, slot_of), cnt) for rec, cnt in st["messages"]
        )
        if len(keys) > p.msg_slots:
            raise OverflowError("message bag exceeds msg_slots")
        nw = self.packer.n_words
        words = [np.full(p.msg_slots, int(EMPTY), np.int32) for _ in range(nw)]
        cn = np.zeros(p.msg_slots, np.int32)
        for k, (kt, c) in enumerate(keys):
            for w in range(nw):
                words[w][k] = kt[w]
            cn[k] = c
        for w in range(nw):
            vec[lay.sl(f"msg_w{w}")] = words[w]
        vec[lay.sl("msg_cnt")] = cn
        vec[lay.fields["electionCtr"].offset] = st["_electionCtr"]
        vec[lay.fields["restartCtr"].offset] = st["_restartCtr"]
        vec[lay.fields["removeCtr"].offset] = st["_removeReconfigCtr"]
        vec[lay.fields["diskIdGen"].offset] = st["_diskIdGen"]
        vec[lay.sl("valueCtr")] = list(st["_valueCtr"])
        return vec


def _lookup(table, idx):
    """``table[idx]`` of a tiny 1-D table at an int array of indices, as
    compares and selects (base.onehot_row for an array of indices): a
    per-lane gather is serial on the TPU however small its table. An
    index outside the table reads 0."""
    k = jnp.arange(table.shape[0], dtype=jnp.int32)
    return jnp.sum(jnp.where(idx[..., None] == k, table, 0), axis=-1)


class SlotCanonicalizer:
    """Canonical fingerprints for the slot encoding under
    ``symmHostsAndValues`` (:462-463).

    A host permutation sigma maps identity (h, d) -> (sigma(h), d); slots
    do NOT move (they are creation-order), but the oracle's view serializes
    servers in sorted-identity order, so canonicalization is data-dependent:
    for each (sigma, tau) (1) remap host values, (2) rank slots by the
    permuted (host, diskId) key — used slots first, creation order for the
    unused — (3) remap every slot reference (leader/votedFor/pf_dest/
    bitmasks/endOffset axes/message source/dest/leader/e_who/e_members)
    through the ranking, (4) remap values through tau (log_val/e_val/acked
    lanes), (5) hash the remapped bag as a MULTISET, its slots as they
    lie (``ops.symmetry.bag_hash_pair``, the bag hash of every other
    canon since formula v3: the oracle serialises the bag sorted, and a
    hash that takes no notice of slot order names the same classes),
    (6) hash the per-server rows and acked by position and XOR the two.
    The fingerprint is the min over all permutations — exactly the
    oracle's ``canon`` equivalence, hashed.

    Every index set is tiny (NS slots, H hosts, V values), so each read
    and write through one is compares and selects, and nothing sorts
    (a re-sort of the bag under every permutation was 40 % of
    kraftrc3-wide's wall, PERF.md section 6, PR 45). The four steps run
    under the scopes ``slot_sort``, ``slot_remap``, ``slot_bag`` and
    ``slot_hash`` (``canon/slot_*`` in an engine's trace), each opened
    OUTSIDE the vmaps over lanes and permutations: a scope opened under
    a vmap reaches the trace as ``vmap(slot_bag)``, which the trace's
    reduction does not read.

    With symmetry off only the identity permutation runs; the slot ranking
    is then the identity by construction (device slot order IS
    sorted-identity order for unpermuted states), kept for uniformity.
    """

    # fingerprints_dedup runs the permutations on blocks of a BLOCKS-th
    # of the batch's lanes: a lane costs microseconds here, a loop trip
    # nothing beside it, and a smaller block leaves fewer padding lanes
    # after the last representative (PERF.md section 6, PR 42: B // 4 to
    # B // 64 measured in kraftrc3-wide; B // 64 reads as this one does)
    BLOCKS = 32

    # fingerprint-formula revision, as ``Canonicalizer.hashv``: 6 = the
    # bag hashed as a multiset. A checkpoint of the sorted-bag formula
    # carries 5 and is refused on load
    hashv = 6

    def __init__(self, model: KRaftReconfigModel, symmetry: bool = True,
                 seed: int = 0):
        self.model = model
        self.symmetry = symmetry
        self.seed = seed
        H, V = model.p.n_hosts, model.p.n_values
        if symmetry:
            sigmas = list(itertools.permutations(range(H)))
            taus = list(itertools.permutations(range(V)))
        else:
            sigmas = [tuple(range(H))]
            taus = [tuple(range(V))]
        pairs = [(s, t) for s in sigmas for t in taus]
        self._sigmas = jnp.asarray([p0 for p0, _ in pairs], jnp.int32)
        self._taus = jnp.asarray([t for _, t in pairs], jnp.int32)
        # the VIEW is the per-server fields and acked, then the bag
        fields = list(model.layout.fields)
        self._row_fields = fields[: fields.index("msg_w0")]
        self.fingerprints = jax.jit(self._fingerprints)

    def _fingerprints(self, states):
        states = jnp.asarray(states, jnp.int32)

        def pairs(f, *per_pair):
            """``f(vec, sigma, tau, *x)`` on every (lane, permutation)."""
            n = len(per_pair)
            g = jax.vmap(f, in_axes=(None, 0, 0) + (0,) * n)
            g = jax.vmap(g, in_axes=(0, None, None) + (0,) * n)
            return g(states, self._sigmas, self._taus, *per_pair)

        with jax.named_scope("slot_sort"):
            host2, inv = pairs(self._slot_sort)
        with jax.named_scope("slot_remap"):
            rows, words, cnt = pairs(self._slot_remap, host2, inv)
        with jax.named_scope("slot_bag"):
            ba, bb = bag_hash_pair(words, cnt, self.seed)  # no slot order
        with jax.named_scope("slot_hash"):
            ra, rb = hash_lanes_pair(rows, seed=self.seed)
            return jnp.min(combine_pair(ra ^ ba, rb ^ bb), axis=-1)

    def raw_fingerprints(self, states):
        """u64 [B] hashes of the unpermuted view prefix, all that
        ``_fingerprints`` reads of a row: the raw key the in-chunk dedup
        groups lanes by."""
        view = jnp.asarray(states, jnp.int32)[:, : self.model.layout.view_len]
        return hash_lanes(view, seed=self.seed)

    def fingerprints_dedup(self, states, valid):
        """The engines' canon stage (``ops.symmetry.canon_chunk``):
        ``(fps, n_dup, tiers)`` with invalid lanes and in-chunk
        duplicates of a lower lane masked to U64_MAX. The permutations
        run once per distinct raw view of the batch
        (``fingerprints_by_raw_view``, the in-chunk dedup
        ``Canonicalizer`` uses), whose first lane carries the
        fingerprint: ``n_dup`` is the valid lanes that shared a lower
        lane's view, skipped them and come back masked, and there are no
        tiers, so ``tiers`` is [0, representatives]
        (``canon_tier3_full``: the full table wherever it runs). With
        symmetry off one permutation runs on every lane, nothing is
        counted and only the invalid lanes are masked."""
        if not self.symmetry:
            fps = jnp.where(valid, self._fingerprints(states), U64_MAX)
            zero = jnp.sum(valid).astype(jnp.int32) * 0  # typed as the lanes
            return fps, zero, jnp.stack([zero, zero])

        def canon_block(rows, real):
            n = jnp.sum(real).astype(jnp.int32)
            return self._fingerprints(rows), jnp.stack([n * 0, n])

        B = states.shape[0]
        return fingerprints_by_raw_view(
            states, valid, self.raw_fingerprints, canon_block,
            min(B, max(64, B // self.BLOCKS)))

    def _slot_sort(self, vec, sigma, _tau):
        """The permuted host of each slot and the slot's rank under the
        permuted identity (old slot -> new row; keys are unique, so a
        rank is a count of smaller keys and no sort runs)."""
        model = self.model
        lay, NS, H = model.layout, model.NS, model.p.n_hosts
        iota = jnp.arange(NS, dtype=jnp.int32)
        used = lay.get(vec, "used") > 0
        host2 = _lookup(sigma, jnp.clip(lay.get(vec, "host"), 0, H - 1))
        BIG = jnp.int32(max(NS, H) + 2)  # > any diskId/host
        # unused slots last, in creation order
        key = jnp.where(
            used, host2 * BIG + lay.get(vec, "diskId"), BIG * BIG + iota)
        inv = jnp.sum((key[None, :] < key[:, None]).astype(jnp.int32), axis=1)
        return host2, inv

    def _slot_remap(self, vec, _sigma, tau, host2, inv):
        """The VIEW's per-server fields and acked in the new slot order,
        flat, and the bag's words and counts with their slot and value
        fields remapped, in the slots they lay in."""
        model = self.model
        d = model._dec(vec)
        NS = model.NS
        iota = jnp.arange(NS, dtype=jnp.int32)
        used = d["used"] > 0
        take = inv[None, :] == iota[:, None]  # new row r <- old slot i

        def gather(x):  # per-slot rows into the new order
            tk = take.reshape(take.shape + (1,) * (x.ndim - 1))
            return jnp.sum(jnp.where(tk, x[None], 0), axis=1)

        ref = jnp.concatenate([jnp.zeros((1,), jnp.int32), inv + 1])

        def refmap(x):  # slot+1 valued (0 = Nil)
            return _lookup(ref, x)

        def maskmap(mask):  # bitmask over slots: old bit i -> new bit inv[i]
            bits = (mask[..., None] >> iota) & 1
            return jnp.sum(bits << inv, axis=-1).astype(jnp.int32)

        def valmap(cmd, val):  # APPEND entries only carry a value
            return jnp.where(
                (cmd == C_APPEND) & (val > 0),
                _lookup(tau, jnp.clip(val - 1, 0)) + 1,
                val,
            )

        upd = {}
        upd["host"] = gather(jnp.where(used, host2, 0))
        for f in ("diskId", "used", "role", "state", "currentEpoch",
                  "pf_active", "pf_epoch", "pf_offset", "pf_lastepoch",
                  "pf_observer", "cfg_id", "cfg_committed", "log_cmd",
                  "log_epoch", "log_cfgid", "log_len", "highWatermark"):
            upd[f] = gather(d[f])
        for f in ("leader", "votedFor", "pf_dest", "log_who"):
            upd[f] = gather(refmap(d[f]))
        for f in ("votesGranted", "cfg_members", "eo_dom", "log_members"):
            upd[f] = gather(maskmap(d[f]))
        upd["endOffset"] = gather(gather(d["endOffset"]).T).T
        upd["log_val"] = gather(valmap(d["log_cmd"], d["log_val"]))
        # acked'[tau[v]] = acked[v]
        upd["acked"] = jnp.sum(
            jnp.where(tau[None, :] == jnp.arange(tau.shape[0])[:, None],
                      d["acked"][None, :], 0), axis=1)
        rows = jnp.concatenate(
            [upd[f].reshape(-1) for f in self._row_fields])

        # message bag: remap slot/value fields inside the packed keys of
        # occupied slots
        words = model._words(d)
        occ = words[0] != EMPTY
        pk = model.packer

        def wreplace(ws, name, val):
            out = pk.replace(tuple(ws), name, val)
            return [jnp.where(occ, o, w) for o, w in zip(out, ws)]

        u = partial(pk.unpack, tuple(words))
        ws = list(words)
        ws = wreplace(ws, "msource", _lookup(inv, u("msource")))
        ws = wreplace(ws, "mdest", _lookup(inv, u("mdest")))
        ws = wreplace(ws, "mleader", refmap(u("mleader")))
        ws = wreplace(ws, "e_who", refmap(u("e_who")))
        ws = wreplace(ws, "e_members", maskmap(u("e_members")))
        ws = wreplace(ws, "e_val", valmap(u("e_cmd"), u("e_val")))
        return rows, ws, d["msg_cnt"]


@lru_cache(maxsize=None)
def _cached_model(params: KRaftReconfigParams) -> "KRaftReconfigModel":
    return KRaftReconfigModel(params)
