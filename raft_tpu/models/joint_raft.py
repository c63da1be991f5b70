"""TPU lowering of the joint-consensus reconfiguration Raft spec.

Reference: ``/root/reference/specifications/standard-raft/
RaftWithReconfigJointConsensus.tla`` (1,145 lines). Every action kernel
cites the TLA+ lines it lowers.

Structural deltas vs. models/reconfig_raft.py (the add/remove variant):
  - log entries carry up to THREE member sets (``OldNewConfigCommand``'s
    old/new/joint-members, ``:837-842``) — seven parallel lane arrays, the
    sets as bitmasks;
  - configs track ``jointConsensus`` plus ``old``/``new``
    (``ConfigFor:279-290``);
  - dual quorums while joint: ``BecomeLeader:511-528`` and
    ``AdvanceCommitIndex:613-653`` need simultaneous majorities of old
    and new (popcount thresholds over both bitmasks);
  - the reconfiguration parameter space is pairs of member subsets
    constrained by ``ReconfigType`` (``IsValidReconfiguration:813-825``);
    the candidate table enumerates exactly the admitted (add, remove)
    mask pairs statically;
  - ``AppendNewConfigToLog:861-876`` fires on the unique committed
    OldNew entry with no later config command
    (``CommittedOldNewWithoutNew:232-242``);
  - ``MaxOneReconfigurationAtATime:1080-1101`` is an adjacency rule over
    every server's log;
  - ``ResetWithSameIdentity:391`` is NOT in ``Next`` (commented, ``:988``).
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import bag
from ..ops.packing import EMPTY, WidePacker, bits_for
from .base import (
    Layout, messages_are_valid_kernel, onehot_add, onehot_get2, onehot_row,
    onehot_set, onehot_set2,
)

from .config_common import (  # shared enums: single source of truth
    ACK_FALSE, ACK_NIL, ACK_TRUE, CANDIDATE, FOLLOWER, LEADER, NIL,
    NOTMEMBER, PENDING_SNAP_REQUEST, PENDING_SNAP_RESPONSE,
    AEREQ, AERESP, RVREQ, RVRESP, SNAPREQ, SNAPRESP,
)

# log-entry commands (:58-60); 0 = empty lane
CMD_NONE, CMD_APPEND, CMD_OLDNEW, CMD_NEW = range(4)
CMD_NAMES = {
    CMD_APPEND: "AppendCommand",
    CMD_OLDNEW: "OldNewConfigCommand",
    CMD_NEW: "NewConfigCommand",
}

RC_OK, RC_STALE, RC_MISMATCH, RC_NEEDSNAP = 1, 2, 3, 4


# Next-disjunct ranks (:966-988), for trace labels.
(
    J_RESTART,
    J_UPDATETERM,
    J_REQUESTVOTE,
    J_BECOMELEADER,
    J_HANDLE_RVREQ,
    J_HANDLE_RVRESP,
    J_CLIENTREQUEST,
    J_ADVANCECOMMIT,
    J_APPENDENTRIES,
    J_REJECT_AE,
    J_ACCEPT_AE,
    J_HANDLE_AERESP,
    J_APPEND_OLDNEW,
    J_APPEND_NEW,
    J_SENDSNAP,
    J_HANDLE_SNAPREQ,
    J_HANDLE_SNAPRESP,
) = range(17)

from .config_common import (
    ConfigRaftCommon,
    MTYPE_NAMES,
    RC_NAMES,
    R_ACCEPT_AE as _R_AC,
    R_APPENDENTRIES as _R_AE,
    R_CLIENTREQUEST as _R_CR,
    R_HANDLE_AERESP as _R_HA,
    R_HANDLE_RVREQ as _R_HQ,
    R_HANDLE_RVRESP as _R_HP,
    R_HANDLE_SNAPREQ as _R_SQ,
    R_HANDLE_SNAPRESP as _R_SP,
    R_REJECT_AE as _R_RJ,
    R_REQUESTVOTE as _R_RV,
    R_RESTART as _R_RS,
    R_SENDSNAP as _R_SS,
    R_UPDATETERM as _R_UT,
)

# the mixin's kernels emit the shared rank constants; both variants lay
# their Next out so these coincide (config_common.py docstring)
assert (J_RESTART, J_REQUESTVOTE, J_CLIENTREQUEST,
        J_APPENDENTRIES, J_SENDSNAP) == (
    _R_RS, _R_RV, _R_CR, _R_AE, _R_SS)
assert (J_UPDATETERM, J_HANDLE_RVREQ, J_HANDLE_RVRESP,
        J_REJECT_AE, J_ACCEPT_AE, J_HANDLE_AERESP,
        J_HANDLE_SNAPREQ, J_HANDLE_SNAPRESP) == (
    _R_UT, _R_HQ, _R_HP, _R_RJ, _R_AC, _R_HA, _R_SQ, _R_SP)

ACTION_NAMES = [
    "Restart",
    "UpdateTerm",
    "RequestVote",
    "BecomeLeader",
    "HandleRequestVoteRequest",
    "HandleRequestVoteResponse",
    "ClientRequest",
    "AdvanceCommitIndex",
    "AppendEntries",
    "RejectAppendEntriesRequest",
    "AcceptAppendEntriesRequest",
    "HandleAppendEntriesResponse",
    "AppendOldNewConfigToLog",
    "AppendNewConfigToLog",
    "SendSnapshot",
    "HandleSnapshotRequest",
    "HandleSnapshotResponse",
]

ENTRY_SET_FIELDS = ("old", "new", "members")
ENTRY_FIELDS = ("term", "cmd", "val", "cid") + ENTRY_SET_FIELDS


@dataclass(frozen=True)
class JointRaftParams:
    n_servers: int
    n_values: int
    init_cluster_size: int
    max_elections: int
    max_restarts: int
    max_reconfigs: int
    max_values_per_term: int
    reconfig_type: int
    msg_slots: int = 112

    @property
    def max_term(self) -> int:
        return 1 + self.max_elections

    @property
    def max_cfg_id(self) -> int:
        return max(1, self.max_reconfigs)

    @property
    def max_log(self) -> int:
        appends = min(self.n_values, self.max_term * self.max_values_per_term)
        return 1 + appends + 2 * self.max_reconfigs


def reconfig_shapes(n_servers: int, reconfig_type: int):
    """The (addMembers, removeMembers) subset pairs admitted by
    IsValidReconfiguration (:813-825), as bitmask pairs, deterministic
    order (matches oracle/joint_oracle.py's enumeration)."""
    servers = range(n_servers)
    subsets = []
    for r in range(n_servers + 1):
        subsets += [frozenset(c) for c in itertools.combinations(servers, r)]

    def valid(add, remove):
        if reconfig_type == 2:
            return len(add) == 1 and len(remove) == 1
        if reconfig_type == 3:
            return len(add) > 0 and len(remove) == 0
        if reconfig_type == 4:
            return len(add) == 0 and len(remove) > 0
        return bool(add) or bool(remove)

    out = []
    for add in subsets:
        for remove in subsets:
            if valid(add, remove):
                out.append(
                    (sum(1 << x for x in add), sum(1 << x for x in remove))
                )
    return out


def _entry_widths(p: JointRaftParams) -> list[tuple[str, int]]:
    tb = bits_for(p.max_term)
    return [
        ("term", tb),
        ("cmd", 2),
        ("val", bits_for(p.n_values)),
        ("cid", bits_for(p.max_cfg_id)),
        ("old", p.n_servers),
        ("new", p.n_servers),
        ("members", p.n_servers),
    ]


def _build_layout(p: JointRaftParams, n_words: int) -> Layout:
    S, V, L, M = p.n_servers, p.n_values, p.max_log, p.msg_slots
    lay = Layout(S)
    # VIEW (:144): all aux vars excluded.
    lay.add("config_id", "per_server", (S,))
    lay.add("config_joint", "per_server", (S,))
    lay.add("config_members", "server_bitmask", (S,))
    lay.add("config_old", "server_bitmask", (S,))
    lay.add("config_new", "server_bitmask", (S,))
    lay.add("config_committed", "per_server", (S,))
    lay.add("currentTerm", "per_server", (S,))
    lay.add("state", "per_server", (S,))
    lay.add("votedFor", "per_server_val", (S,))
    lay.add("votesGranted", "server_bitmask", (S,))
    lay.add("log_term", "per_server", (S, L))
    lay.add("log_cmd", "per_server", (S, L))
    lay.add("log_val", "per_server", (S, L))
    lay.add("log_cid", "per_server", (S, L))
    lay.add("log_old", "server_bitmask", (S, L))
    lay.add("log_new", "server_bitmask", (S, L))
    lay.add("log_members", "server_bitmask", (S, L))
    lay.add("log_len", "per_server", (S,))
    lay.add("commitIndex", "per_server", (S,))
    lay.add("nextIndex", "per_server_pair", (S, S))  # may hold -1/-2
    lay.add("matchIndex", "per_server_pair", (S, S))
    lay.add("pendingResponse", "server_bitmask", (S,))
    for k in range(n_words):
        lay.add(f"msg_w{k}", "msg_word", (M,))
    lay.add("msg_cnt", "msg_cnt", (M,))
    lay.add("acked", "aux", (V,))
    lay.add("electionCtr", "aux")
    lay.add("restartCtr", "aux")
    lay.add("reconfigCtr", "aux")
    lay.add("valueCtr", "aux", (p.max_term,))
    return lay.finish()


def _build_packer(p: JointRaftParams) -> WidePacker:
    tb = bits_for(p.max_term)
    sb = bits_for(p.n_servers - 1)
    lb = bits_for(p.max_log + 1)
    ew = _entry_widths(p)
    fields = [
        ("mtype", 3),
        ("mterm", tb),
        ("msource", sb),
        ("mdest", sb),
        ("mlastLogTerm", tb),
        ("mlastLogIndex", lb),
        ("mvoteGranted", 1),
        ("mprevLogIndex", lb),
        ("mprevLogTerm", tb),
        ("nentries", 1),
        *[(f"e_{n}", w) for n, w in ew],
        ("mcommitIndex", lb),
        ("mresult", 3),
        ("mmatchIndex", lb),
        ("msuccess", 1),
        ("mloglen", lb),
        ("mmembers", p.n_servers),
        *[(f"l{k}_{n}", w) for k in range(p.max_log) for n, w in ew],
    ]
    for n_words in range(2, 16):
        try:
            return WidePacker(fields, n_words)
        except ValueError:
            continue
    raise ValueError("message schema too wide")


def cached_model(params: "JointRaftParams") -> "JointRaftModel":
    return _cached_model(params)


class JointRaftModel(ConfigRaftCommon):
    """Vectorized successor/invariant kernels for one (spec, constants) pair."""

    name = "RaftWithReconfigJointConsensus"
    ENTRY_FIELDS = ENTRY_FIELDS
    CMD_SEED = CMD_NEW  # Init's seeded first entry (:341-354)
    MEMBERS_FIELD = "members"
    CMD_APPEND = CMD_APPEND
    ACTION_NAMES = ACTION_NAMES

    def __init__(self, params, server_names=None, value_names=None):
        self.p = params
        self.packer = _build_packer(params)
        self.n_words = self.packer.n_words
        self.layout = _build_layout(params, self.n_words)
        S, V, M, L = params.n_servers, params.n_values, params.msg_slots, params.max_log
        self.server_names = list(server_names or [f"s{i+1}" for i in range(S)])
        self.value_names = list(value_names or [f"v{i+1}" for i in range(V)])

        spec = [("msource", "server"), ("mdest", "server"),
                ("mmembers", "server_bitmask")]
        for n in ENTRY_SET_FIELDS:
            spec.append((f"e_{n}", "server_bitmask"))
        for k in range(L):
            for n in ENTRY_SET_FIELDS:
                spec.append((f"l{k}_{n}", "server_bitmask"))
        self.msg_perm_spec = tuple(spec)

        self.shapes = reconfig_shapes(S, params.reconfig_type)
        self._finish_init()

    # ---------------- field access helpers ----------------

    def _mrce(self, d, i):
        """MostRecentReconfigEntry — :251-257. Returns (index, cmd, cid,
        old, new, members) of the latest config command."""
        L = self.p.max_log
        lanes = jnp.arange(L, dtype=jnp.int32)
        cmd = onehot_row(d["log_cmd"], i)
        is_cfg = (cmd == CMD_OLDNEW) | (cmd == CMD_NEW)
        mask = (lanes < onehot_row(d["log_len"], i)) & is_cfg
        idx = jnp.max(jnp.where(mask, lanes + 1, 0))
        pos = jnp.clip(idx - 1, 0)
        return (
            idx,
            onehot_row(cmd, pos),
            onehot_get2(d["log_cid"], i, pos),
            onehot_get2(d["log_old"], i, pos),
            onehot_get2(d["log_new"], i, pos),
            onehot_get2(d["log_members"], i, pos),
        )

    def _config_for_upd(self, d, i, idx, cmd, cid, old, new, members, ci):
        """ConfigFor (:279-290) applied to server i's config fields."""
        joint = (cmd == CMD_OLDNEW).astype(jnp.int32)
        z = jnp.int32(0)
        return dict(
            config_id=onehot_set(d["config_id"], i, cid),
            config_joint=onehot_set(d["config_joint"], i, joint),
            config_members=onehot_set(d["config_members"], i, members),
            config_old=onehot_set(d["config_old"], i, jnp.where(joint > 0, old, z)),
            config_new=onehot_set(d["config_new"], i, jnp.where(joint > 0, new, z)),
            config_committed=onehot_set(d["config_committed"], i,
                (ci >= idx).astype(jnp.int32)
            ),
        )

    # ---------------- action kernels ----------------

    def _become_leader(self, s, i):
        """BecomeLeader(i) — :511-528: dual quorums while joint."""
        S = self.p.n_servers
        d = self._dec(s)
        vg = onehot_row(d["votesGranted"], i)
        joint = onehot_row(d["config_joint"], i) > 0
        members = onehot_row(d["config_members"], i)
        old = onehot_row(d["config_old"], i)
        new = onehot_row(d["config_new"], i)
        q_plain = ((vg & ~members) == 0) & (
            2 * self._popcount(vg, S) > self._popcount(members, S)
        )
        q_old = 2 * self._popcount(vg & old, S) > self._popcount(old, S)
        q_new = 2 * self._popcount(vg & new, S) > self._popcount(new, S)
        valid = (onehot_row(d["state"], i) == CANDIDATE) & jnp.where(
            joint, q_old & q_new, q_plain
        )
        succ = self._asm(
            d,
            state=onehot_set(d["state"], i, LEADER),
            nextIndex=onehot_set(d["nextIndex"], i,
                jnp.full((S,), 1, jnp.int32)
                * (onehot_row(d["log_len"], i) + 1)
            ),
            matchIndex=onehot_set(d["matchIndex"], i, jnp.zeros((S,), jnp.int32)),
            pendingResponse=onehot_set(d["pendingResponse"], i, 0),
        )
        return valid, succ, jnp.int32(J_BECOMELEADER), jnp.asarray(False)

    def _commit_quorum_ok(self, d, i, idxs, match_row, ks):
        """Dual-quorum agreement while joint (:626-629)."""
        S = self.p.n_servers
        joint = onehot_row(d["config_joint"], i) > 0

        def quorum_over(member_mask):
            member_k = ((member_mask >> ks) & 1) > 0
            in_agree = member_k[None, :] & (
                (match_row[None, :] >= idxs[:, None]) | (ks[None, :] == i)
            )
            return 2 * jnp.sum(in_agree, axis=1) > self._popcount(member_mask, S)

        q_plain = quorum_over(onehot_row(d["config_members"], i))
        q_joint = quorum_over(onehot_row(d["config_old"], i)) & quorum_over(
            onehot_row(d["config_new"], i))
        return jnp.where(joint, q_joint, q_plain)

    def _commit_config_upd(self, d, i, new_ci) -> dict:
        idx, cmd, cid, c_old, c_new, c_members = self._mrce(d, i)
        return self._config_for_upd(
            d, i, idx, cmd, cid, c_old, c_new, c_members, new_ci
        )

    def _commit_removed(self, d, i, in_range):
        """IsRemovedFromCluster (:606-611): NewConfigCommand without i."""
        return jnp.any(
            in_range
            & (onehot_row(d["log_cmd"], i) == CMD_NEW)
            & (((onehot_row(d["log_members"], i) >> i) & 1) == 0)
        )

    def _append_old_new(self, s, i, add_mask, rem_mask):
        """AppendOldNewConfigToLog(i) for one admitted (add, remove) subset
        pair — :827-856."""
        p, S, L = self.p, self.p.n_servers, self.p.max_log
        d = self._dec(s)
        members = onehot_row(d["config_members"], i)
        add_m = jnp.int32(add_mask)
        rem_m = jnp.int32(rem_mask)
        # HasPendingConfigCommand (:246-248)
        pending = (onehot_row(d["config_committed"], i) == 0) | (
            onehot_row(d["config_joint"], i) > 0)
        valid = (
            (onehot_row(d["state"], i) == LEADER)
            & (d["reconfigCtr"] < p.max_reconfigs)
            & ~pending
            & ((add_m & members) == 0)  # addMembers disjoint (:834)
            & ((rem_m & members) == rem_m)  # removeMembers subset (:835)
        )
        old = members
        new = (members & ~rem_m) | add_m
        joint_members = members | add_m
        new_id = d["reconfigCtr"] + 1  # id = reconfigCtr + 1 (:839)
        ci_i = onehot_row(d["commitIndex"], i)
        pos = onehot_row(d["log_len"], i)
        ovf = valid & (pos >= L)
        posc = jnp.clip(pos, 0, L - 1)
        # nextIndex := PendingSnapshotRequest for s in new \ old (:849-853)
        ks = jnp.arange(S, dtype=jnp.int32)
        fresh = (((new >> ks) & 1) > 0) & (((old >> ks) & 1) == 0)
        ni_row = jnp.where(
            fresh, jnp.int32(PENDING_SNAP_REQUEST),
            onehot_row(d["nextIndex"], i),
        )
        succ = self._asm(
            d,
            log_term=onehot_set2(
                d["log_term"], i, posc, onehot_row(d["currentTerm"], i)),
            log_cmd=onehot_set2(d["log_cmd"], i, posc, CMD_OLDNEW),
            log_cid=onehot_set2(d["log_cid"], i, posc, new_id),
            log_old=onehot_set2(d["log_old"], i, posc, old),
            log_new=onehot_set2(d["log_new"], i, posc, new),
            log_members=onehot_set2(d["log_members"], i, posc, joint_members),
            log_len=onehot_add(d["log_len"], i, 1),
            config_id=onehot_set(d["config_id"], i, new_id),
            config_joint=onehot_set(d["config_joint"], i, 1),
            config_members=onehot_set(d["config_members"], i, joint_members),
            config_old=onehot_set(d["config_old"], i, old),
            config_new=onehot_set(d["config_new"], i, new),
            config_committed=onehot_set(d["config_committed"], i,
                (ci_i >= pos + 1).astype(jnp.int32)
            ),
            reconfigCtr=d["reconfigCtr"] + 1,
            nextIndex=onehot_set(d["nextIndex"], i, ni_row),
        )
        return valid, succ, jnp.int32(J_APPEND_OLDNEW), ovf

    def _append_new(self, s, i):
        """AppendNewConfigToLog(i) — :861-876: fires on the unique
        committed OldNew with no later config command."""
        p, L = self.p, self.p.max_log
        d = self._dec(s)
        lanes = jnp.arange(L, dtype=jnp.int32)
        cmd_row = onehot_row(d["log_cmd"], i)
        ll_i = onehot_row(d["log_len"], i)
        ci_i = onehot_row(d["commitIndex"], i)
        in_log = lanes < ll_i
        is_oldnew = in_log & (cmd_row == CMD_OLDNEW)
        is_new = in_log & (cmd_row == CMD_NEW)
        last_oldnew = jnp.max(jnp.where(is_oldnew, lanes + 1, 0))
        last_new = jnp.max(jnp.where(is_new, lanes + 1, 0))
        # CommittedOldNewWithoutNew (:232-242)
        qualifies = (
            (last_oldnew > 0)
            & (ci_i >= last_oldnew)
            & (last_new < last_oldnew)
        )
        valid = (onehot_row(d["state"], i) == LEADER) & qualifies
        tpos = jnp.clip(last_oldnew - 1, 0)
        new_members = onehot_get2(d["log_new"], i, tpos)
        new_id = onehot_get2(d["log_cid"], i, tpos)
        pos = ll_i
        ovf = valid & (pos >= L)
        posc = jnp.clip(pos, 0, L - 1)
        succ = self._asm(
            d,
            log_term=onehot_set2(
                d["log_term"], i, posc, onehot_row(d["currentTerm"], i)),
            log_cmd=onehot_set2(d["log_cmd"], i, posc, CMD_NEW),
            log_cid=onehot_set2(d["log_cid"], i, posc, new_id),
            log_members=onehot_set2(d["log_members"], i, posc, new_members),
            log_len=onehot_add(d["log_len"], i, 1),
            config_id=onehot_set(d["config_id"], i, new_id),
            config_joint=onehot_set(d["config_joint"], i, 0),
            config_members=onehot_set(d["config_members"], i, new_members),
            config_old=onehot_set(d["config_old"], i, 0),
            config_new=onehot_set(d["config_new"], i, 0),
            config_committed=onehot_set(d["config_committed"], i,
                (ci_i >= pos + 1).astype(jnp.int32)
            ),
        )
        return valid, succ, jnp.int32(J_APPEND_NEW), ovf

    # -------- fused message-receipt kernel (slot m) --------

    def _is_cfg_cmd(self, cmd):
        """OldNewConfig / NewConfig entries carry a configuration
        (:58-60); hook for the shared receipt kernel."""
        return (cmd == CMD_OLDNEW) | (cmd == CMD_NEW)

    def _config_updates_from_log(self, d, dst, logs, cfg_pos, cfg_idx, mci):
        """ConfigFor projection (:279-290): id, jointConsensus flag,
        members, old/new sets, committed watermark; in_new = membership
        of dst in the installed config's member set."""
        z = jnp.int32(0)
        cfg_cmd = onehot_row(logs["cmd"], cfg_pos)
        cfg_joint = (cfg_cmd == CMD_OLDNEW).astype(jnp.int32)
        cfg_members = onehot_row(logs["members"], cfg_pos)
        upd = dict(
            config_id=onehot_set(
                d["config_id"], dst, onehot_row(logs["cid"], cfg_pos)),
            config_joint=onehot_set(d["config_joint"], dst, cfg_joint),
            config_members=onehot_set(d["config_members"], dst, cfg_members),
            config_old=onehot_set(d["config_old"], dst,
                jnp.where(cfg_joint > 0, onehot_row(logs["old"], cfg_pos), z)
            ),
            config_new=onehot_set(d["config_new"], dst,
                jnp.where(cfg_joint > 0, onehot_row(logs["new"], cfg_pos), z)
            ),
            config_committed=onehot_set(d["config_committed"], dst,
                (mci >= cfg_idx).astype(jnp.int32)
            ),
        )
        in_new = ((cfg_members >> dst) & 1) > 0
        return upd, in_new

    # ---------------- full expansion ----------------

    def _kernel_overrides(self) -> dict:
        return {
            "AppendOldNewConfigToLog": self._append_old_new,
            "AppendNewConfigToLog": self._append_new,
        }

    def _config_bindings(self) -> list:
        b = []
        for i in range(self.p.n_servers):
            for add_m, rem_m in self.shapes:
                b.append(("AppendOldNewConfigToLog", (i, add_m, rem_m)))
        for i in range(self.p.n_servers):
            b.append(("AppendNewConfigToLog", (i,)))
        return b

    def _config_outs(self, s) -> list:
        import jax

        S = self.p.n_servers
        iota_s = jnp.arange(S, dtype=jnp.int32)
        on_i = jnp.asarray(
            [i for i in range(S) for _ in self.shapes], jnp.int32
        )
        on_add = jnp.asarray(
            [a for _ in range(S) for a, _r in self.shapes], jnp.int32
        )
        on_rem = jnp.asarray(
            [r for _ in range(S) for _a, r in self.shapes], jnp.int32
        )
        return [
            jax.vmap(lambda i, a, r: self._append_old_new(s, i, a, r))(
                on_i, on_add, on_rem
            ),
            jax.vmap(lambda i: self._append_new(s, i))(iota_s),
        ]

    def _old_new_committed(self, states):
        """OldNewCommitted(i, index) over all (i, lane): committed
        OldNewConfigCommand entries — :1023-1025. [B,S,L] mask."""
        lay, L = self.layout, self.p.max_log
        cmd = lay.get(states, "log_cmd")
        ll = lay.get(states, "log_len")
        ci = lay.get(states, "commitIndex")
        lanes = jnp.arange(L, dtype=jnp.int32)
        return (
            (cmd == CMD_OLDNEW)
            & (lanes[None, None, :] < ll[..., None])
            & (ci[..., None] >= lanes[None, None, :] + 1)
        )

    def _live_reconfig_p(self, states):
        """ReconfigurationCompletes antecedent — :1040-1043: some server
        has a committed OldNewConfigCommand."""
        return jnp.any(self._old_new_committed(states), axis=(1, 2))

    def _live_reconfig_q(self, states):
        """ReconfigurationCompletes consequent — :1044-1054: the last
        permissible election failed leaderless, OR a majority of the new
        member set are self-aware members in {Leader,Follower,Candidate}
        holding the matching NewConfigCommand — :1027-1037."""
        lay, S, L = self.layout, self.p.n_servers, self.p.max_log
        st = lay.get(states, "state")
        ec = lay.get(states, "electionCtr")
        cmd = lay.get(states, "log_cmd")
        cid = lay.get(states, "log_cid")
        lnew = lay.get(states, "log_new")
        ll = lay.get(states, "log_len")
        cm = lay.get(states, "config_members")
        lanes = jnp.arange(L, dtype=jnp.int32)
        onc = self._old_new_committed(states)  # [B,S,L]
        # server j qualifies for config id c: self-aware member, active
        # state, and holds a NewConfigCommand with id c somewhere
        iota = jnp.arange(S, dtype=jnp.int32)
        self_member = ((cm >> iota[None, :]) & 1) > 0  # [B,S]
        active = st != NOTMEMBER  # Leader/Follower/Candidate
        has_new = (cmd == CMD_NEW) & (lanes[None, None, :] < ll[..., None])
        # qualifies[b, j, i, l]: j holds NewConfigCommand with the id of
        # entry (i, l)
        id_match = jnp.any(
            has_new[:, :, None, None, :]
            & (cid[:, :, None, None, :] == cid[:, None, :, :, None]),
            axis=4,
        )  # [B,j,i,l]
        qual = (self_member & active)[:, :, None, None] & id_match
        # majority of the entry's NEW member set
        new_bit = (
            (lnew[:, None, :, :] >> iota[None, :, None, None]) & 1
        ) > 0  # [B,j,i,l]
        count = jnp.sum(qual & new_bit, axis=1)  # [B,i,l]
        size = jnp.sum(new_bit, axis=1)  # [B,i,l]
        reached = jnp.any(onc & (2 * count > size), axis=(1, 2))
        no_leader = ~jnp.any(st == LEADER, axis=1)
        spent = ec == self.p.max_elections
        return (spent & no_leader) | reached

    def _inv_max_one_reconfig(self, states):
        """MaxOneReconfigurationAtATime — :1080-1101: same-type config
        commands need the opposite type strictly between them."""
        lay, L = self.layout, self.p.max_log
        cmd = lay.get(states, "log_cmd")  # [B,S,L]
        ll = lay.get(states, "log_len")
        lanes = jnp.arange(L, dtype=jnp.int32)
        in_log = lanes[None, None, :] < ll[:, :, None]
        ok = jnp.ones(cmd.shape[:2], dtype=bool)
        for c, other in ((CMD_OLDNEW, CMD_NEW), (CMD_NEW, CMD_OLDNEW)):
            is_c = in_log & (cmd == c)
            is_o = in_log & (cmd == other)
            # pair [.., k1, k2] with k1 < k2 both command c
            pair = is_c[..., :, None] & is_c[..., None, :]
            k1 = lanes[:, None]
            k2 = lanes[None, :]
            upper = k2 > k1
            # between[k1, k2]: exists opposite-type at k with k1 < k < k2
            between = (lanes[None, None, :] > k1[..., None]) & (
                lanes[None, None, :] < k2[..., None]
            )  # [L, L, L]
            has_between = jnp.any(
                between[None, None] & is_o[:, :, None, None, :], axis=-1
            )  # [B,S,L,L]
            bad = pair & upper[None, None] & ~has_between
            ok &= ~jnp.any(bad, axis=(2, 3))
        return jnp.all(ok, axis=1)

    def _inv_committed_majority(self, states):
        """CommittedEntriesReachMajority — :1129-1140."""
        lay, S, L = self.layout, self.p.n_servers, self.p.max_log
        st = lay.get(states, "state")
        ci = lay.get(states, "commitIndex")
        ll = lay.get(states, "log_len")
        members = lay.get(states, "config_members")
        lead = (st == LEADER) & (ci > 0)
        pos = jnp.clip(ci - 1, 0, L - 1)
        match = jnp.ones(st.shape[:1] + (S, S), dtype=bool)
        for n in ENTRY_FIELDS:
            f = lay.get(states, f"log_{n}")
            fi = jnp.take_along_axis(f, pos[:, :, None], axis=2)[:, :, 0]
            fj = jnp.take_along_axis(
                jnp.broadcast_to(f[:, None, :, :], f.shape[:1] + (S,) + f.shape[1:]),
                jnp.broadcast_to(pos[:, :, None, None], pos.shape + (S, 1)),
                axis=3,
            )[..., 0]
            match &= fj == fi[..., None]
        match &= ll[:, None, :] >= ci[:, :, None]
        ks = jnp.arange(S, dtype=jnp.int32)
        member_j = ((members[:, :, None] >> ks[None, None, :]) & 1) > 0
        agree = match & member_j
        n_members = jnp.sum(member_j, axis=2)
        eye = jnp.eye(S, dtype=bool)
        self_in = jnp.any(agree & eye[None, :, :], axis=2)
        enough = (jnp.sum(agree, axis=2) >= (n_members // 2 + 1)) & self_in
        ok_exists = jnp.any(lead & enough, axis=1)
        return ~jnp.any(lead, axis=1) | ok_exists

    # ---------------- host-side decode/encode ----------------

    def _decode_entry(self, term, cmd, val, cid, old, new, members):
        cmd_name = CMD_NAMES[int(cmd)]
        if cmd_name == "AppendCommand":
            return (cmd_name, int(term), int(val) - 1)
        if cmd_name == "NewConfigCommand":
            return (cmd_name, int(term), (int(cid), self._fs(members)))
        return (
            cmd_name,
            int(term),
            (int(cid), self._fs(old), self._fs(new), self._fs(members)),
        )

    def _encode_entry(self, entry):
        cmd_name, term, val = entry
        inv_cmd = {v: k for k, v in CMD_NAMES.items()}
        cmd = inv_cmd[cmd_name]
        mk = lambda fs: sum(1 << j for j in fs)
        if cmd == CMD_APPEND:
            return dict(term=term, cmd=cmd, val=val + 1, cid=0, old=0, new=0, members=0)
        if cmd == CMD_NEW:
            return dict(
                term=term, cmd=cmd, val=0, cid=val[0], old=0, new=0,
                members=mk(val[1]),
            )
        return dict(
            term=term, cmd=cmd, val=0, cid=val[0], old=mk(val[1]),
            new=mk(val[2]), members=mk(val[3]),
        )

    counter_fields = ("reconfigCtr",)

    def _decode_config(self, g):
        return tuple(
            (
                int(g("config_id")[i]),
                bool(g("config_joint")[i]),
                self._fs(g("config_members")[i]),
                self._fs(g("config_old")[i]),
                self._fs(g("config_new")[i]),
                bool(g("config_committed")[i]),
            )
            for i in range(self.p.n_servers)
        )

    def _encode_config(self, vec, st) -> None:
        lay = self.layout
        mk = lambda fs: sum(1 << j for j in fs)
        vec[lay.sl("config_id")] = [c[0] for c in st["config"]]
        vec[lay.sl("config_joint")] = [int(c[1]) for c in st["config"]]
        vec[lay.sl("config_members")] = [mk(c[2]) for c in st["config"]]
        vec[lay.sl("config_old")] = [mk(c[3]) for c in st["config"]]
        vec[lay.sl("config_new")] = [mk(c[4]) for c in st["config"]]
        vec[lay.sl("config_committed")] = [int(c[5]) for c in st["config"]]


@lru_cache(maxsize=None)
def _cached_model(params: "JointRaftParams") -> "JointRaftModel":
    return JointRaftModel(params)
