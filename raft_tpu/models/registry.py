"""Spec registry: maps a TLA+ module name to its TPU lowering builder.

Each builder consumes a parsed TLC cfg (utils/cfg.py) and returns a ready
model plus checking options — the ``CHECKER=tpu`` toggle's dispatch table.
Variants land here as they are lowered (SURVEY.md §7.1 order).
"""

from __future__ import annotations

from dataclasses import dataclass

from ..obs.trace import setup_phase
from ..utils.cfg import Cfg, CfgError
from .kraft import KRaftModel, KRaftParams
from .pull_raft import PullRaftModel, PullRaftParams
from .raft import RaftModel, RaftParams


@dataclass
class CheckSetup:
    model: object
    invariants: tuple[str, ...]
    symmetry: bool
    server_names: list[str]
    value_names: list[str]


def _require_int(cfg: Cfg, name: str) -> int:
    if name not in cfg.constants:
        raise CfgError(f"{cfg.path}: required constant {name} is missing")
    v = cfg.constants[name]
    if not isinstance(v, int) or isinstance(v, bool):
        raise CfgError(f"{cfg.path}: constant {name} must be a number, got {v!r}")
    return v


def _require_bool(cfg: Cfg, name: str) -> bool:
    if name not in cfg.constants:
        raise CfgError(f"{cfg.path}: required constant {name} is missing")
    v = cfg.constants[name]
    if not isinstance(v, bool):
        raise CfgError(f"{cfg.path}: constant {name} must be TRUE/FALSE, got {v!r}")
    return v


def _check_invariants(cfg: Cfg, model) -> None:
    unknown = [i for i in cfg.invariants if i not in model.invariants]
    if unknown:
        raise CfgError(f"{cfg.path}: unknown invariant(s) {unknown}")


def build_raft(
    cfg: Cfg, msg_slots: int | None = None, net_faults: bool = False
) -> CheckSetup:
    """standard-raft/Raft.tla + Raft.cfg."""
    servers = cfg.server_like("Server")
    values = cfg.server_like("Value")
    params = RaftParams(
        n_servers=len(servers),
        n_values=len(values),
        max_elections=_require_int(cfg, "MaxElections"),
        max_restarts=_require_int(cfg, "MaxRestarts"),
        msg_slots=msg_slots if msg_slots is not None else 48,
        net_faults=net_faults,
    )
    model = RaftModel(params, server_names=servers, value_names=values)
    _check_invariants(cfg, model)
    return CheckSetup(
        model=model,
        invariants=tuple(cfg.invariants),
        symmetry=cfg.symmetry is not None,
        server_names=servers,
        value_names=values,
    )


def build_flexible_raft(
    cfg: Cfg, msg_slots: int | None = None, net_faults: bool = False
) -> CheckSetup:
    """flexible-raft/FlexibleRaft.tla + FlexibleRaft.cfg: structurally core
    Raft with count-based quorums (FlexibleRaft.tla:262,296), strictly
    send-once messaging (:127-151), no pendingResponse (:109), and
    term-mismatch-only truncation (:413-416)."""
    servers = cfg.server_like("Server")
    values = cfg.server_like("Value")
    params = RaftParams(
        n_servers=len(servers),
        n_values=len(values),
        max_elections=_require_int(cfg, "MaxElections"),
        max_restarts=_require_int(cfg, "MaxRestarts"),
        msg_slots=msg_slots if msg_slots is not None else 48,
        election_quorum=_require_int(cfg, "ElectionQuorumSize"),
        replication_quorum=_require_int(cfg, "ReplicationQuorumSize"),
        strict_send_once=True,
        has_pending_response=False,
        trunc_term_mismatch=True,
        net_faults=net_faults,
    )
    model = RaftModel(params, server_names=servers, value_names=values)
    model.name = "FlexibleRaft"
    _check_invariants(cfg, model)
    return CheckSetup(
        model=model,
        invariants=tuple(cfg.invariants),
        symmetry=cfg.symmetry is not None,
        server_names=servers,
        value_names=values,
    )


def build_raft_fsync(
    cfg: Cfg, msg_slots: int | None = None, net_faults: bool = False
) -> CheckSetup:
    """raft-and-fsync/RaftFsync.tla + RaftFsync.cfg: core Raft plus
    fsyncIndex durability (RaftFsync.tla:92), crash-truncation restart
    (:203-218), split Timeout/RequestVote (:222-243), AdvanceFsyncIndex
    (:339), three fsync policy constants (:50-52), strictly send-once
    messaging (:132-152), and no pendingResponse flow control."""
    servers = cfg.server_like("Server")
    values = cfg.server_like("Value")
    params = RaftParams(
        n_servers=len(servers),
        n_values=len(values),
        max_elections=_require_int(cfg, "MaxElections"),
        max_restarts=_require_int(cfg, "MaxRestarts"),
        msg_slots=msg_slots if msg_slots is not None else 48,
        strict_send_once=True,
        has_pending_response=False,
        trunc_term_mismatch=True,
        has_fsync=True,
        fsync_leader_before_ae=_require_bool(cfg, "LeaderFsyncBeforeAppendEntries"),
        fsync_leader_quorum=_require_bool(cfg, "LeaderFsyncBeforeIncludeInQuorum"),
        fsync_follower_reply=_require_bool(cfg, "FollowerFsyncBeforeReply"),
        net_faults=net_faults,
    )
    model = RaftModel(params, server_names=servers, value_names=values)
    model.name = "RaftFsync"
    _check_invariants(cfg, model)
    return CheckSetup(
        model=model,
        invariants=tuple(cfg.invariants),
        symmetry=cfg.symmetry is not None,
        server_names=servers,
        value_names=values,
    )


def _build_pull(cfg: Cfg, msg_slots: int | None, variant2: bool) -> CheckSetup:
    servers = cfg.server_like("Server")
    values = cfg.server_like("Value")
    params = PullRaftParams(
        n_servers=len(servers),
        n_values=len(values),
        max_elections=_require_int(cfg, "MaxElections"),
        max_restarts=_require_int(cfg, "MaxRestarts"),
        # pull specs need extra bag headroom: every message type is
        # send-once, so count-0 records pile up across a behavior
        msg_slots=msg_slots if msg_slots is not None else 64,
        variant2=variant2,
    )
    model = PullRaftModel(params, server_names=servers, value_names=values)
    _check_invariants(cfg, model)
    return CheckSetup(
        model=model,
        invariants=tuple(cfg.invariants),
        symmetry=cfg.symmetry is not None,
        server_names=servers,
        value_names=values,
    )


def build_pull_raft(cfg: Cfg, msg_slots: int | None = None) -> CheckSetup:
    """pull-raft/PullRaft.tla + PullRaft.cfg (note: the reference cfg
    references the undeclared model value `v2`, PullRaft.cfg:9-11 — parse
    with lenient=True to diagnose-and-repair)."""
    return _build_pull(cfg, msg_slots, variant2=False)


def build_pull_raft_v2(cfg: Cfg, msg_slots: int | None = None) -> CheckSetup:
    """pull-raft/PullRaftVariant2.tla + PullRaftVariant2.cfg (same cfg bug)."""
    return _build_pull(cfg, msg_slots, variant2=True)


def build_kraft(cfg: Cfg, msg_slots: int | None = None) -> CheckSetup:
    """pull-raft/KRaft.tla + KRaft.cfg: Kafka KRaft (KIP-595) with five
    server states + IllegalState, fetch-based replication with correlation,
    error codes, and the BeginQuorumRequest leadership notify."""
    servers = cfg.server_like("Server")
    values = cfg.server_like("Value")
    params = KRaftParams(
        n_servers=len(servers),
        n_values=len(values),
        max_elections=_require_int(cfg, "MaxElections"),
        max_restarts=_require_int(cfg, "MaxRestarts"),
        # fetch responses carry full correlation records, so distinct-record
        # counts run higher than the push-based variants
        msg_slots=msg_slots if msg_slots is not None else 80,
    )
    model = KRaftModel(params, server_names=servers, value_names=values)
    _check_invariants(cfg, model)
    return CheckSetup(
        model=model,
        invariants=tuple(cfg.invariants),
        symmetry=cfg.symmetry is not None,
        server_names=servers,
        value_names=values,
    )


def build_reconfig_add_remove(cfg: Cfg, msg_slots: int | None = None) -> CheckSetup:
    """standard-raft/RaftWithReconfigAddRemove.tla + its cfg. The reference
    cfg omits the required ``MaxClusterSize`` constant
    (RaftWithReconfigAddRemove.tla:88 vs the cfg; SURVEY.md §2.2) — strict
    mode raises, lenient mode repairs it to |Server| (the physical bound)
    and records a diagnostic."""
    from .reconfig_raft import ReconfigRaftModel, ReconfigRaftParams

    servers = cfg.server_like("Server")
    values = cfg.server_like("Value")
    if "MaxClusterSize" not in cfg.constants:
        diag = (
            f"{cfg.path}: required constant MaxClusterSize "
            "(RaftWithReconfigAddRemove.tla:88) is missing from the cfg; "
            f"lenient mode repairs this by defaulting it to |Server| = {len(servers)}"
        )
        if not cfg.lenient:
            raise CfgError(diag)
        cfg.diagnostics.append(diag)
        cfg.constants["MaxClusterSize"] = len(servers)
    params = ReconfigRaftParams(
        n_servers=len(servers),
        n_values=len(values),
        init_cluster_size=_require_int(cfg, "InitClusterSize"),
        max_elections=_require_int(cfg, "MaxElections"),
        max_restarts=_require_int(cfg, "MaxRestarts"),
        max_values_per_term=_require_int(cfg, "MaxValuesPerTerm"),
        max_add_reconfigs=_require_int(cfg, "MaxAddReconfigs"),
        max_remove_reconfigs=_require_int(cfg, "MaxRemoveReconfigs"),
        min_cluster_size=_require_int(cfg, "MinClusterSize"),
        max_cluster_size=_require_int(cfg, "MaxClusterSize"),
        include_thesis_bug=_require_bool(cfg, "IncludeThesisBug"),
        # snapshot records embed whole logs and AppendEntries pile up per
        # (term, prev, entry) combination: needs the most headroom so far
        msg_slots=msg_slots if msg_slots is not None else 112,
    )
    model = ReconfigRaftModel(params, server_names=servers, value_names=values)
    _check_invariants(cfg, model)
    return CheckSetup(
        model=model,
        invariants=tuple(cfg.invariants),
        symmetry=cfg.symmetry is not None,
        server_names=servers,
        value_names=values,
    )


def build_reconfig_joint(cfg: Cfg, msg_slots: int | None = None) -> CheckSetup:
    """standard-raft/RaftWithReconfigJointConsensus.tla + its cfg: joint
    consensus reconfiguration with dual quorums and the ReconfigType knob
    (RaftWithReconfigJointConsensus.tla:79-80)."""
    from .joint_raft import JointRaftModel, JointRaftParams

    servers = cfg.server_like("Server")
    values = cfg.server_like("Value")
    params = JointRaftParams(
        n_servers=len(servers),
        n_values=len(values),
        init_cluster_size=_require_int(cfg, "InitClusterSize"),
        max_elections=_require_int(cfg, "MaxElections"),
        max_restarts=_require_int(cfg, "MaxRestarts"),
        max_reconfigs=_require_int(cfg, "MaxReconfigs"),
        max_values_per_term=_require_int(cfg, "MaxValuesPerTerm"),
        reconfig_type=_require_int(cfg, "ReconfigType"),
        msg_slots=msg_slots if msg_slots is not None else 112,
    )
    model = JointRaftModel(params, server_names=servers, value_names=values)
    _check_invariants(cfg, model)
    return CheckSetup(
        model=model,
        invariants=tuple(cfg.invariants),
        symmetry=cfg.symmetry is not None,
        server_names=servers,
        value_names=values,
    )


def build_kraft_reconfig(cfg: Cfg, msg_slots: int | None = None) -> CheckSetup:
    """pull-raft/KRaftWithReconfig.tla + its cfg: the dynamic-server
    universe spec, device-lowered with MaxSpawnedServers identity slots
    (its cfg prescribes simulation, KRaftWithReconfig.cfg:5). The cfg
    shares PullRaft.cfg's latent bug: Value = {v1, v2} with v2 undeclared
    (lenient repairs)."""
    from .kraft_reconfig import KRaftReconfigParams

    hosts = cfg.server_like("Hosts")
    values = cfg.server_like("Value")
    params = KRaftReconfigParams(
        n_hosts=len(hosts),
        n_values=len(values),
        init_cluster_size=_require_int(cfg, "InitClusterSize"),
        min_cluster_size=_require_int(cfg, "MinClusterSize"),
        max_cluster_size=_require_int(cfg, "MaxClusterSize"),
        max_elections=_require_int(cfg, "MaxElections"),
        max_restarts=_require_int(cfg, "MaxRestarts"),
        max_values_per_epoch=_require_int(cfg, "MaxValuesPerEpoch"),
        max_add_reconfigs=_require_int(cfg, "MaxAddReconfigs"),
        max_remove_reconfigs=_require_int(cfg, "MaxRemoveReconfigs"),
        max_spawned_servers=_require_int(cfg, "MaxSpawnedServers"),
        msg_slots=msg_slots if msg_slots is not None else 40,
    )
    # fresh model per setup (names differ per cfg; the lru cache is keyed
    # on params only, so mutating a cached instance would alias setups)
    from .kraft_reconfig import KRaftReconfigModel

    model = KRaftReconfigModel(params, server_names=hosts, value_names=values)
    _check_invariants(cfg, model)
    return CheckSetup(
        model=model,
        invariants=tuple(cfg.invariants),
        symmetry=cfg.symmetry is not None,
        server_names=hosts,
        value_names=values,
    )


BUILDERS = {
    "Raft": build_raft,
    "FlexibleRaft": build_flexible_raft,
    "RaftFsync": build_raft_fsync,
    "PullRaft": build_pull_raft,
    "PullRaftVariant2": build_pull_raft_v2,
    "KRaft": build_kraft,
    "RaftWithReconfigAddRemove": build_reconfig_add_remove,
    "RaftWithReconfigJointConsensus": build_reconfig_joint,
    "KRaftWithReconfig": build_kraft_reconfig,
}


def oracle_for_setup(setup: CheckSetup):
    """Pure-Python differential oracle matching the setup's model params."""
    p = setup.model.p
    if isinstance(p, PullRaftParams):
        from ..oracle.pull_oracle import PullRaftOracle

        return PullRaftOracle(
            p.n_servers, p.n_values, p.max_elections, p.max_restarts,
            variant2=p.variant2,
        )
    if isinstance(p, KRaftParams):
        from ..oracle.kraft_oracle import KRaftOracle

        return KRaftOracle(p.n_servers, p.n_values, p.max_elections, p.max_restarts)
    from .reconfig_raft import ReconfigRaftParams

    if isinstance(p, ReconfigRaftParams):
        from ..oracle.reconfig_oracle import ReconfigRaftOracle

        return ReconfigRaftOracle(
            p.n_servers, p.n_values, p.init_cluster_size, p.max_elections,
            p.max_restarts, p.max_values_per_term, p.max_add_reconfigs,
            p.max_remove_reconfigs, p.min_cluster_size, p.max_cluster_size,
            include_thesis_bug=p.include_thesis_bug,
        )
    from .kraft_reconfig import KRaftReconfigParams

    if isinstance(p, KRaftReconfigParams):
        from ..oracle.kraft_reconfig_oracle import KRaftReconfigOracle

        return KRaftReconfigOracle(
            p.n_hosts, p.n_values, p.init_cluster_size, p.min_cluster_size,
            p.max_cluster_size, p.max_elections, p.max_restarts,
            p.max_values_per_epoch, p.max_add_reconfigs,
            p.max_remove_reconfigs, p.max_spawned_servers,
        )
    from .joint_raft import JointRaftParams

    if isinstance(p, JointRaftParams):
        from ..oracle.joint_oracle import JointRaftOracle

        return JointRaftOracle(
            p.n_servers, p.n_values, p.init_cluster_size, p.max_elections,
            p.max_restarts, p.max_reconfigs, p.max_values_per_term,
            p.reconfig_type,
        )
    from ..oracle.raft_oracle import oracle_for

    return oracle_for(p)


# Spec families whose lowering implements the opt-in DuplicateMessage /
# DropMessage kernels (Raft.tla:508-523).
NET_FAULT_SPECS = ("Raft", "FlexibleRaft", "RaftFsync")


@setup_phase("model")
def build_from_cfg(
    cfg: Cfg,
    spec: str | None = None,
    msg_slots: int | None = None,
    net_faults: bool = False,
) -> CheckSetup:
    import os

    name = spec or os.path.splitext(os.path.basename(cfg.path))[0]
    if name not in BUILDERS:
        raise CfgError(
            f"no TPU lowering registered for spec {name!r} "
            f"(available: {', '.join(sorted(BUILDERS))})"
        )
    if net_faults:
        if name not in NET_FAULT_SPECS:
            raise CfgError(
                f"{cfg.path}: --net-faults is only lowered for the Raft "
                f"family (available: {', '.join(NET_FAULT_SPECS)}), not "
                f"{name!r}"
            )
        return BUILDERS[name](cfg, msg_slots=msg_slots, net_faults=True)
    return BUILDERS[name](cfg, msg_slots=msg_slots)
