"""TPU lowering of the thesis-style add/remove reconfiguration Raft spec.

Reference: ``/root/reference/specifications/standard-raft/
RaftWithReconfigAddRemove.tla`` (1,083 lines). Every action kernel cites
the TLA+ lines it lowers.

Structural notes:
  - log entries are (command, term, value) records where the value of a
    config command carries (id, new/old member, member set); entries lower
    to six parallel per-server lane arrays, with member sets as bitmasks;
  - the current config is DERIVED state — ``MostRecentReconfigEntry:252``
    + ``ConfigFor:265`` — lowered to a masked lane max + gather;
  - snapshot messages embed the sender's whole log
    (``SendSnapshot:862-876``), so records pack into N-word WidePacker
    keys with one packed field set per log lane; the ``msg_word`` layout
    kind + generalized Canonicalizer handle N-word bags;
  - ``nextIndex`` carries the snapshot sentinels ``-1``/``-2``
    (``:271-272``) directly in its int32 lanes;
  - quorums are popcount thresholds over the config-member bitmask —
    replacing the ``SUBSET``-based ``Quorum:169`` the reference itself
    flags as a TLC hot spot ("Very inefficient for TLC - TODO replace");
  - ``ResetWithSameIdentity:385`` is enabled in ``Next:965``; its
    ``CHOOSE``-a-leader is lowered as lowest-index-first.

Derived bounds: terms in [0, 1+MaxElections] (term 0 = never-member);
log length <= 1 (InitClusterCommand) + min(|Value|, terms*MaxValuesPerTerm)
+ MaxAddReconfigs + MaxRemoveReconfigs; config ids <= 1 + MaxAddReconfigs
+ MaxRemoveReconfigs.
"""

from __future__ import annotations

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

# log-entry commands (RaftWithReconfigAddRemove.tla:66-69); 0 = empty lane
CMD_NONE, CMD_INIT, CMD_APPEND, CMD_ADD, CMD_REMOVE = range(5)
CMD_NAMES = {
    CMD_INIT: "InitClusterCommand",
    CMD_APPEND: "AppendCommand",
    CMD_ADD: "AddServerCommand",
    CMD_REMOVE: "RemoveServerCommand",
}

# mtype (:78-80)
# AppendEntries result codes (:75); Ok=1 so 0 means "field absent"
RC_OK, RC_STALE, RC_MISMATCH, RC_NEEDSNAP = 1, 2, 3, 4


# Next-disjunct ranks (:943-965), for trace labels.
ENTRY_FIELDS = ("term", "cmd", "val", "cid", "cmem", "cmembers")

(
    A_RESTART,
    A_UPDATETERM,
    A_REQUESTVOTE,
    A_BECOMELEADER,
    A_HANDLE_RVREQ,
    A_HANDLE_RVRESP,
    A_CLIENTREQUEST,
    A_ADVANCECOMMIT,
    A_APPENDENTRIES,
    A_REJECT_AE,
    A_ACCEPT_AE,
    A_HANDLE_AERESP,
    A_APPEND_ADD,
    A_APPEND_REMOVE,
    A_SENDSNAP,
    A_HANDLE_SNAPREQ,
    A_HANDLE_SNAPRESP,
    A_RESET_IDENTITY,
) = range(18)

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
assert (A_RESTART, A_REQUESTVOTE, A_CLIENTREQUEST,
        A_APPENDENTRIES, A_SENDSNAP) == (
    _R_RS, _R_RV, _R_CR, _R_AE, _R_SS)
assert (A_UPDATETERM, A_HANDLE_RVREQ, A_HANDLE_RVRESP,
        A_REJECT_AE, A_ACCEPT_AE, A_HANDLE_AERESP,
        A_HANDLE_SNAPREQ, A_HANDLE_SNAPRESP) == (
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
    "AppendAddServerCommandToLog",
    "AppendRemoveServerCommandToLog",
    "SendSnapshot",
    "HandleSnapshotRequest",
    "HandleSnapshotResponse",
    "ResetWithSameIdentity",
]


@dataclass(frozen=True)
class ReconfigRaftParams:
    n_servers: int
    n_values: int
    init_cluster_size: int
    max_elections: int
    max_restarts: int
    max_values_per_term: int
    max_add_reconfigs: int
    max_remove_reconfigs: int
    min_cluster_size: int
    max_cluster_size: int
    include_thesis_bug: bool = False
    msg_slots: int = 96

    @property
    def max_term(self) -> int:
        return 1 + self.max_elections

    @property
    def max_cfg_id(self) -> int:
        return 1 + self.max_add_reconfigs + self.max_remove_reconfigs

    @property
    def max_log(self) -> int:
        appends = min(self.n_values, self.max_term * self.max_values_per_term)
        return 1 + appends + self.max_add_reconfigs + self.max_remove_reconfigs


# per-lane log-entry field widths (shared by state arrays and message keys)
def _entry_fields(p: ReconfigRaftParams) -> list[tuple[str, int]]:
    tb = bits_for(p.max_term)
    return [
        ("term", tb),
        ("cmd", 3),
        ("val", bits_for(p.n_values)),
        ("cid", bits_for(p.max_cfg_id)),
        ("cmem", bits_for(p.n_servers)),  # new/old member, nil-valued
        ("cmembers", p.n_servers),  # member-set bitmask
    ]


def _build_layout(p: ReconfigRaftParams, n_words: int) -> Layout:
    S, V, L, M = p.n_servers, p.n_values, p.max_log, p.msg_slots
    lay = Layout(S)
    # VIEW (:159) = messages, serverVars, candidateVars, leaderVars,
    # logVars. ALL aux vars (acked + five counters) are excluded.
    lay.add("config_id", "per_server", (S,))
    lay.add("config_members", "server_bitmask", (S,))
    lay.add("config_committed", "per_server", (S,))
    lay.add("currentTerm", "per_server", (S,))
    lay.add("state", "per_server", (S,))
    lay.add("votedFor", "per_server_val", (S,))
    lay.add("votesGranted", "server_bitmask", (S,))
    lay.add("log_term", "per_server", (S, L))
    lay.add("log_cmd", "per_server", (S, L))
    lay.add("log_val", "per_server", (S, L))
    lay.add("log_cid", "per_server", (S, L))
    lay.add("log_cmem", "per_server_val", (S, L))  # 0 = none, i+1 = server i
    lay.add("log_cmembers", "server_bitmask", (S, L))
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
    lay.add("addReconfigCtr", "aux")
    lay.add("removeReconfigCtr", "aux")
    lay.add("valueCtr", "aux", (p.max_term,))
    return lay.finish()


def _build_packer(p: ReconfigRaftParams) -> WidePacker:
    tb = bits_for(p.max_term)
    sb = bits_for(p.n_servers - 1)
    lb = bits_for(p.max_log + 1)
    ef = _entry_fields(p)
    fields = [
        ("mtype", 3),
        ("mterm", tb),
        ("msource", sb),
        ("mdest", sb),
        ("mlastLogTerm", tb),  # RequestVoteRequest (:437-442)
        ("mlastLogIndex", lb),
        ("mvoteGranted", 1),  # RequestVoteResponse (:465-470)
        ("mprevLogIndex", lb),  # AppendEntriesRequest (:563-570)
        ("mprevLogTerm", tb),
        ("nentries", 1),
        *[(f"e_{n}", w) for n, w in ef],  # the <=1 entry
        ("mcommitIndex", lb),  # also SnapshotRequest (:873)
        ("mresult", 3),  # AppendEntriesResponse (:685-691)
        ("mmatchIndex", lb),  # also SnapshotResponse (:900)
        ("msuccess", 1),  # SnapshotResponse (:897-902)
        ("mloglen", lb),  # SnapshotRequest embedded log (:872)
        ("mmembers", p.n_servers),
        *[(f"l{k}_{n}", w) for k in range(p.max_log) for n, w in ef],
    ]
    for n_words in range(2, 12):
        try:
            return WidePacker(fields, n_words)
        except ValueError:
            continue
    raise ValueError("message schema too wide")


def cached_model(params: "ReconfigRaftParams") -> "ReconfigRaftModel":
    return _cached_model(params)


class ReconfigRaftModel(ConfigRaftCommon):
    """Vectorized successor/invariant kernels for one (spec, constants) pair."""

    name = "RaftWithReconfigAddRemove"
    ENTRY_FIELDS = ENTRY_FIELDS
    CMD_SEED = CMD_INIT  # Init's seeded first entry (:324-338)
    MEMBERS_FIELD = "cmembers"
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

        # symmetry contract: packed fields that transform under sigma
        spec = [("msource", "server"), ("mdest", "server"),
                ("e_cmem", "server_nil"), ("e_cmembers", "server_bitmask"),
                ("mmembers", "server_bitmask")]
        for k in range(L):
            spec.append((f"l{k}_cmem", "server_nil"))
            spec.append((f"l{k}_cmembers", "server_bitmask"))
        self.msg_perm_spec = tuple(spec)

        # Candidate table: non-receipt disjuncts in Next order (:943-965),
        # receipt disjuncts fused per slot at the end.
        self._all_pairs = [(i, j) for i in range(S) for j in range(S)]
        self._finish_init()

    # ---------------- field access helpers ----------------

    def _mrce(self, d, i):
        """MostRecentReconfigEntry over log[i] — :252-258. Returns
        (index, cid, cmembers); index 0 = no config command (callers gate
        on reachability, member logs always carry InitClusterCommand)."""
        L = self.p.max_log
        lanes = jnp.arange(L, dtype=jnp.int32)
        cmd = onehot_row(d["log_cmd"], i)
        is_cfg = (cmd == CMD_INIT) | (cmd == CMD_ADD) | (cmd == CMD_REMOVE)
        mask = (lanes < onehot_row(d["log_len"], i)) & is_cfg
        idx = jnp.max(jnp.where(mask, lanes + 1, 0))
        pos = jnp.clip(idx - 1, 0)
        return (
            idx,
            onehot_get2(d["log_cid"], i, pos),
            onehot_get2(d["log_cmembers"], i, pos),
        )

    # ---------------- action kernels ----------------

    def _become_leader(self, s, i):
        """BecomeLeader(i) — :505-518: votesGranted must be a quorum OF the
        member set (subset + majority)."""
        S = self.p.n_servers
        d = self._dec(s)
        members = onehot_row(d["config_members"], i)
        vg = onehot_row(d["votesGranted"], i)
        subset = (vg & ~members) == 0
        quorum = 2 * self._popcount(vg, S) > self._popcount(members, S)
        valid = (onehot_row(d["state"], i) == CANDIDATE) & subset & quorum
        succ = self._asm(
            d,
            state=onehot_set(d["state"], i, LEADER),
            nextIndex=onehot_set(d["nextIndex"], i,
                jnp.full((S,), 1, jnp.int32) * (onehot_row(d["log_len"], i) + 1)
            ),
            matchIndex=onehot_set(d["matchIndex"], i, jnp.zeros((S,), jnp.int32)),
            pendingResponse=onehot_set(d["pendingResponse"], i, 0),
        )
        return valid, succ, jnp.int32(A_BECOMELEADER), jnp.asarray(False)

    def _commit_quorum_ok(self, d, i, idxs, match_row, ks):
        """Member-set quorum with leader self-inclusion (:612-618)."""
        S = self.p.n_servers
        members = onehot_row(d["config_members"], i)
        member_k = ((members >> ks) & 1) > 0  # [S]
        in_agree = member_k[None, :] & (
            (match_row[None, :] >= idxs[:, None]) | (ks[None, :] == i)
        )
        return 2 * jnp.sum(in_agree, axis=1) > self._popcount(members, S)

    def _commit_config_upd(self, d, i, new_ci) -> dict:
        """Config re-derivation (:627-632)."""
        cfg_idx, cfg_id, cfg_members = self._mrce(d, i)
        cfg_committed = (new_ci >= cfg_idx).astype(jnp.int32)
        return dict(
            config_id=onehot_set(d["config_id"], i, cfg_id),
            config_members=onehot_set(d["config_members"], i, cfg_members),
            config_committed=onehot_set(d["config_committed"], i, cfg_committed),
        )

    def _commit_removed(self, d, i, in_range):
        """IsRemovedFromCluster (:598-603)."""
        return jnp.any(
            in_range
            & (onehot_row(d["log_cmd"], i) == CMD_REMOVE)
            & (((onehot_row(d["log_cmembers"], i) >> i) & 1) == 0)
        )

    def _append_add(self, s, i, a):
        """AppendAddServerCommandToLog(i, a) — :795-824."""
        p, S, L = self.p, self.p.n_servers, self.p.max_log
        d = self._dec(s)
        members = onehot_row(d["config_members"], i)
        term_i = onehot_row(d["currentTerm"], i)
        ci_i = onehot_row(d["commitIndex"], i)
        valid = (
            (onehot_row(d["state"], i) == LEADER)
            & (d["addReconfigCtr"] < p.max_add_reconfigs)
            & (self._popcount(members, S) < p.max_cluster_size)
            # ~HasPendingConfigCommand (:248)
            & (onehot_row(d["config_committed"], i) > 0)
            & (((members >> a) & 1) == 0)
        )
        if not p.include_thesis_bug:
            # LeaderHasCommittedEntriesInCurrentTerm (:275-278)
            lanes = jnp.arange(L, dtype=jnp.int32)
            has_committed = jnp.any(
                (lanes < onehot_row(d["log_len"], i))
                & (onehot_row(d["log_term"], i) == term_i)
                & (ci_i >= lanes + 1)
            )
            valid &= has_committed
        new_members = members | (jnp.int32(1) << a)
        new_id = onehot_row(d["config_id"], i) + 1
        pos = onehot_row(d["log_len"], i)
        ovf = valid & (pos >= L)
        posc = jnp.clip(pos, 0, L - 1)
        succ = self._asm(
            d,
            log_term=onehot_set2(d["log_term"], i, posc, term_i),
            log_cmd=onehot_set2(d["log_cmd"], i, posc, CMD_ADD),
            log_cid=onehot_set2(d["log_cid"], i, posc, new_id),
            log_cmem=onehot_set2(d["log_cmem"], i, posc, a + 1),
            log_cmembers=onehot_set2(d["log_cmembers"], i, posc, new_members),
            log_len=onehot_add(d["log_len"], i, 1),
            config_id=onehot_set(d["config_id"], i, new_id),
            config_members=onehot_set(d["config_members"], i, new_members),
            # committed = ci >= Len(newLog) — always FALSE here (:814-816)
            config_committed=onehot_set(d["config_committed"], i,
                (ci_i >= pos + 1).astype(jnp.int32)
            ),
            addReconfigCtr=d["addReconfigCtr"] + 1,
            nextIndex=onehot_set2(d["nextIndex"], i, a, PENDING_SNAP_REQUEST),
        )
        return valid, succ, jnp.int32(A_APPEND_ADD), ovf

    def _append_remove(self, s, i, r):
        """AppendRemoveServerCommandToLog(i, r) — :828-853."""
        p, S, L = self.p, self.p.n_servers, self.p.max_log
        d = self._dec(s)
        members = onehot_row(d["config_members"], i)
        term_i = onehot_row(d["currentTerm"], i)
        ci_i = onehot_row(d["commitIndex"], i)
        valid = (
            (onehot_row(d["state"], i) == LEADER)
            & (d["removeReconfigCtr"] < p.max_remove_reconfigs)
            & (self._popcount(members, S) > p.min_cluster_size)
            & (onehot_row(d["config_committed"], i) > 0)
            & (((members >> r) & 1) > 0)
        )
        if not p.include_thesis_bug:
            lanes = jnp.arange(L, dtype=jnp.int32)
            has_committed = jnp.any(
                (lanes < onehot_row(d["log_len"], i))
                & (onehot_row(d["log_term"], i) == term_i)
                & (ci_i >= lanes + 1)
            )
            valid &= has_committed
        new_members = members & ~(jnp.int32(1) << r)
        new_id = onehot_row(d["config_id"], i) + 1
        pos = onehot_row(d["log_len"], i)
        ovf = valid & (pos >= L)
        posc = jnp.clip(pos, 0, L - 1)
        succ = self._asm(
            d,
            log_term=onehot_set2(d["log_term"], i, posc, term_i),
            log_cmd=onehot_set2(d["log_cmd"], i, posc, CMD_REMOVE),
            log_cid=onehot_set2(d["log_cid"], i, posc, new_id),
            log_cmem=onehot_set2(d["log_cmem"], i, posc, r + 1),
            log_cmembers=onehot_set2(d["log_cmembers"], i, posc, new_members),
            log_len=onehot_add(d["log_len"], i, 1),
            config_id=onehot_set(d["config_id"], i, new_id),
            config_members=onehot_set(d["config_members"], i, new_members),
            config_committed=onehot_set(d["config_committed"], i,
                (ci_i >= pos + 1).astype(jnp.int32)
            ),
            removeReconfigCtr=d["removeReconfigCtr"] + 1,
        )
        return valid, succ, jnp.int32(A_APPEND_REMOVE), ovf

    def _reset_with_same_identity(self, s, i):
        """ResetWithSameIdentity(i) — :385-400; CHOOSE-a-leader lowered as
        lowest index with IsCurrentLeader (:367-373)."""
        p, S = self.p, self.p.n_servers
        d = self._dec(s)
        ct = d["currentTerm"]
        is_cur_leader = (d["state"] == LEADER) & jnp.all(
            ct[:, None] >= ct[None, :], axis=1
        )
        exists = jnp.any(is_cur_leader)
        leader = jnp.argmax(is_cur_leader)  # lowest index
        valid = (
            (onehot_row(ct, i) > 0)
            & exists
            & (leader != i)
            & (((onehot_row(d["config_members"], leader) >> i) & 1) == 0)
            & (onehot_row(d["config_committed"], leader) > 0)
        )
        L = p.max_log
        succ = self._asm(
            d,
            state=onehot_set(d["state"], i, NOTMEMBER),
            config_id=onehot_set(d["config_id"], i, 0),
            config_members=onehot_set(d["config_members"], i, 0),
            config_committed=onehot_set(d["config_committed"], i, 0),
            currentTerm=onehot_set(d["currentTerm"], i, 0),
            votedFor=onehot_set(d["votedFor"], i, NIL),
            votesGranted=onehot_set(d["votesGranted"], i, 0),
            nextIndex=onehot_set(d["nextIndex"], i, jnp.ones((S,), jnp.int32)),
            matchIndex=onehot_set(d["matchIndex"], i, jnp.zeros((S,), jnp.int32)),
            pendingResponse=onehot_set(d["pendingResponse"], i, 0),
            commitIndex=onehot_set(d["commitIndex"], i, 0),
            log_term=onehot_set(d["log_term"], i, jnp.zeros((L,), jnp.int32)),
            log_cmd=onehot_set(d["log_cmd"], i, jnp.zeros((L,), jnp.int32)),
            log_val=onehot_set(d["log_val"], i, jnp.zeros((L,), jnp.int32)),
            log_cid=onehot_set(d["log_cid"], i, jnp.zeros((L,), jnp.int32)),
            log_cmem=onehot_set(d["log_cmem"], i, jnp.zeros((L,), jnp.int32)),
            log_cmembers=onehot_set(d["log_cmembers"], i, jnp.zeros((L,), jnp.int32)),
            log_len=onehot_set(d["log_len"], i, 0),
        )
        return valid, succ, jnp.int32(A_RESET_IDENTITY), jnp.asarray(False)

    # -------- fused message-receipt kernel (slot m) --------

    def _is_cfg_cmd(self, cmd):
        """InitCluster / AddServer / RemoveServer entries carry a
        configuration (:66-69); hook for the shared receipt kernel."""
        return (cmd == CMD_INIT) | (cmd == CMD_ADD) | (cmd == CMD_REMOVE)

    def _config_updates_from_log(self, d, dst, logs, cfg_pos, cfg_idx, mci):
        """Config cache from the most recent config entry (:734-739):
        id, member set, committed watermark; in_new = membership of dst
        in the installed member set."""
        cmembers = onehot_row(logs["cmembers"], cfg_pos)
        upd = dict(
            config_id=onehot_set(
                d["config_id"], dst, onehot_row(logs["cid"], cfg_pos)),
            config_members=onehot_set(d["config_members"], dst, cmembers),
            config_committed=onehot_set(d["config_committed"], dst,
                (mci >= cfg_idx).astype(jnp.int32)
            ),
        )
        in_new = ((cmembers >> dst) & 1) > 0
        return upd, in_new

    # ---------------- full expansion ----------------

    def _kernel_overrides(self) -> dict:
        return {
            "AppendAddServerCommandToLog": self._append_add,
            "AppendRemoveServerCommandToLog": self._append_remove,
        }

    def _config_bindings(self) -> list:
        b = []
        for ij in self._all_pairs:
            b.append(("AppendAddServerCommandToLog", ij))
        for ij in self._all_pairs:
            b.append(("AppendRemoveServerCommandToLog", ij))
        return b

    def _pre_msg_bindings(self) -> list:
        return [("ResetWithSameIdentity", (i,))
                for i in range(self.p.n_servers)]

    def _config_outs(self, s) -> list:
        import jax

        ap_i = jnp.asarray([ij[0] for ij in self._all_pairs], jnp.int32)
        ap_j = jnp.asarray([ij[1] for ij in self._all_pairs], jnp.int32)
        return [
            jax.vmap(lambda i, a: self._append_add(s, i, a))(ap_i, ap_j),
            jax.vmap(lambda i, r: self._append_remove(s, i, r))(ap_i, ap_j),
        ]

    def _pre_msg_outs(self, s, iota_s) -> list:
        import jax

        return [
            jax.vmap(lambda i: self._reset_with_same_identity(s, i))(iota_s)
        ]

    def _live_reconfig_p(self, states):
        """ReconfigurationCompletes antecedent — :992-996: some leader has
        a config command in its log."""
        lay, L = self.layout, self.p.max_log
        st = lay.get(states, "state")
        cmd = lay.get(states, "log_cmd")
        ll = lay.get(states, "log_len")
        lanes = jnp.arange(L, dtype=jnp.int32)
        is_cfg = (
            (cmd == CMD_INIT) | (cmd == CMD_ADD) | (cmd == CMD_REMOVE)
        ) & (lanes[None, None, :] < ll[..., None])
        return jnp.any((st == LEADER)[..., None] & is_cfg, axis=(1, 2))

    def _live_reconfig_q(self, states):
        """ReconfigurationCompletes consequent — :998-1005: some leader
        has a config command that every member of that entry's member set
        has replicated identically at the same index."""
        lay, S, L = self.layout, self.p.n_servers, self.p.max_log
        st = lay.get(states, "state")
        cmd = lay.get(states, "log_cmd")
        ll = lay.get(states, "log_len")
        lanes = jnp.arange(L, dtype=jnp.int32)
        is_cfg = (
            (cmd == CMD_INIT) | (cmd == CMD_ADD) | (cmd == CMD_REMOVE)
        ) & (lanes[None, None, :] < ll[..., None])
        # entry equality between server i and j at each lane: [B,S,S,L]
        eq = jnp.ones(st.shape[:1] + (S, S, L), dtype=bool)
        for n in ENTRY_FIELDS:
            f = lay.get(states, f"log_{n}")
            eq &= f[:, :, None, :] == f[:, None, :, :]
        in_log_j = lanes[None, None, None, :] < ll[:, None, :, None]  # [B,1,S,L]
        member_j = (
            (lay.get(states, "log_cmembers")[:, :, None, :]
             >> jnp.arange(S, dtype=jnp.int32)[None, None, :, None]) & 1
        ) > 0  # [B,S(i),S(j),L]
        ok_j = ~member_j | (in_log_j & eq)
        complete = jnp.all(ok_j, axis=2)  # [B,S,L]
        return jnp.any((st == LEADER)[..., None] & is_cfg & complete, axis=(1, 2))

    def _inv_max_one_reconfig(self, states):
        """MaxOneReconfigurationAtATime — :1031-1039."""
        lay, L = self.layout, self.p.max_log
        st = lay.get(states, "state")
        ci = lay.get(states, "commitIndex")
        cmd = lay.get(states, "log_cmd")
        ll = lay.get(states, "log_len")
        lanes = jnp.arange(1, L + 1, dtype=jnp.int32)
        is_cfg = (cmd == CMD_INIT) | (cmd == CMD_ADD) | (cmd == CMD_REMOVE)
        uncommitted = (
            is_cfg
            & (lanes[None, None, :] <= ll[:, :, None])
            & (lanes[None, None, :] > ci[:, :, None])
        )
        n_uncommitted = jnp.sum(uncommitted, axis=2)
        bad = (st == LEADER) & (n_uncommitted >= 2)
        return ~jnp.any(bad, axis=1)

    def _inv_committed_majority(self, states):
        """CommittedEntriesReachMajority — :1067-1078 (quorum drawn from
        config[i].members, exact majority size, i in quorum)."""
        lay, S, L = self.layout, self.p.n_servers, self.p.max_log
        st = lay.get(states, "state")
        ci = lay.get(states, "commitIndex")
        ll = lay.get(states, "log_len")
        members = lay.get(states, "config_members")
        lead = (st == LEADER) & (ci > 0)
        pos = jnp.clip(ci - 1, 0, L - 1)
        match = jnp.ones(st.shape[:1] + (S, S), dtype=bool)  # [B, i, j]
        for n in ENTRY_FIELDS:
            f = lay.get(states, f"log_{n}")  # [B,S,L]
            fi = jnp.take_along_axis(f, pos[:, :, None], axis=2)[:, :, 0]  # [B,S]
            fj = jnp.take_along_axis(
                jnp.broadcast_to(f[:, None, :, :], f.shape[:1] + (S,) + f.shape[1:]),
                jnp.broadcast_to(pos[:, :, None, None], pos.shape + (S, 1)),
                axis=3,
            )[..., 0]
            match &= fj == fi[..., None]
        match &= ll[:, None, :] >= ci[:, :, None]
        ks = jnp.arange(S, dtype=jnp.int32)
        member_j = ((members[:, :, None] >> ks[None, None, :]) & 1) > 0  # [B,i,j]
        agree = match & member_j
        n_members = jnp.sum(member_j, axis=2)
        eye = jnp.eye(S, dtype=bool)
        self_in = jnp.any(agree & eye[None, :, :], axis=2)  # i \in quorum
        enough = (jnp.sum(agree, axis=2) >= (n_members // 2 + 1)) & self_in
        ok_exists = jnp.any(lead & enough, axis=1)
        return ~jnp.any(lead, axis=1) | ok_exists

    # ---------------- host-side decode/encode ----------------

    def _decode_entry(self, term, cmd, val, cid, cmem, cmembers):
        cmd_name = CMD_NAMES[int(cmd)]
        members = frozenset(
            j for j in range(self.p.n_servers) if (int(cmembers) >> j) & 1
        )
        if cmd_name == "AppendCommand":
            return (cmd_name, int(term), int(val) - 1)
        if cmd_name == "InitClusterCommand":
            return (cmd_name, int(term), (int(cid), members))
        return (cmd_name, int(term), (int(cid), int(cmem) - 1, members))

    def _encode_entry(self, entry):
        cmd_name, term, val = entry
        inv_cmd = {v: k for k, v in CMD_NAMES.items()}
        cmd = inv_cmd[cmd_name]
        if cmd == CMD_APPEND:
            return dict(term=term, cmd=cmd, val=val + 1, cid=0, cmem=0, cmembers=0)
        if cmd == CMD_INIT:
            return dict(
                term=term, cmd=cmd, val=0, cid=val[0], cmem=0,
                cmembers=sum(1 << j for j in val[1]),
            )
        return dict(
            term=term, cmd=cmd, val=0, cid=val[0], cmem=val[1] + 1,
            cmembers=sum(1 << j for j in val[2]),
        )

    counter_fields = ("addReconfigCtr", "removeReconfigCtr")

    def _decode_config(self, g):
        return tuple(
            (
                int(g("config_id")[i]),
                self._fs(g("config_members")[i]),
                bool(g("config_committed")[i]),
            )
            for i in range(self.p.n_servers)
        )

    def _encode_config(self, vec, st) -> None:
        lay = self.layout
        vec[lay.sl("config_id")] = [c[0] for c in st["config"]]
        vec[lay.sl("config_members")] = [
            sum(1 << j for j in c[1]) for c in st["config"]
        ]
        vec[lay.sl("config_committed")] = [int(c[2]) for c in st["config"]]


@lru_cache(maxsize=None)
def _cached_model(params: "ReconfigRaftParams") -> "ReconfigRaftModel":
    return ReconfigRaftModel(params)
