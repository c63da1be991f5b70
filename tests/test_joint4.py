"""joint4: upstream's four-server RaftWithReconfigJointConsensus.cfg (4
servers, 1 value, InitClusterSize 3, MaxElections 1, MaxReconfigs 2,
MaxValuesPerTerm 1, ReconfigType 2, 24 permutations), at the published
constants and the registry's own bag width, against the pure-Python
oracle: 1,042-lane rows, 224 candidate actions a state, and the canon
branch a layout without tiers takes (S <= 4: the plain min over all S!
tables on every representative of a chunk's raw views), with the server-bitmask
remaps of the configuration fields, the log entries and the N-word
message keys.

The cfg in the tree is reconstructed (its header says from what). One
DeviceBFS verdict to depth 6 (as the CLI builds it) serves every
test of the engine here; the oracle's is its twin.
"""

import filecmp
import io
import itertools
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from raft_tpu.models.registry import build_from_cfg, oracle_for_setup
from raft_tpu.utils.cfg import parse_cfg

from conftest import collect_states, lower_dedup_canon, scatter_kernels

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = os.path.join(
    ROOT, "configs", "standard-raft", "RaftWithReconfigJointConsensus.cfg")
BENCH = os.path.join(ROOT, "benchmark")
DEPTH = 6
INVARIANTS = (
    "LeaderHasAllAckedValues",
    "NoLogDivergence",
    "MaxOneReconfigurationAtATime",
)


@pytest.fixture(scope="module")
def setup():
    # strict parsing, the registry's own bag width: the CLI's path
    return build_from_cfg(parse_cfg(CFG))


@pytest.fixture(scope="module")
def oracle(setup):
    return oracle_for_setup(setup)


@pytest.fixture(scope="module")
def sample(oracle):
    """A deterministic sample of full states reached by depth 5 (the
    first 200 in BFS order)."""
    return collect_states(oracle, max_depth=5, cap=200)


@pytest.fixture(scope="module")
def oracle_run(setup, oracle):
    return oracle.bfs(invariants=setup.invariants, symmetry=True,
                      max_depth=DEPTH)


@pytest.fixture(scope="module")
def device_run(setup, tmp_path_factory):
    """(engine, result, the wave and summary events as the telemetry
    wrote them, the metrics file): one verdict to depth 6."""
    from raft_tpu.checker.device_bfs import DeviceBFS
    from raft_tpu.obs import Telemetry

    path = str(tmp_path_factory.mktemp("joint4") / "metrics.jsonl")
    eng = DeviceBFS(setup.model, invariants=setup.invariants, symmetry=True,
                    chunk=256, frontier_cap=1 << 12)
    with Telemetry(metrics_path=path) as tel:
        res = eng.run(max_depth=DEPTH, collect_metrics=True, telemetry=tel)
    with open(path) as f:
        events = [json.loads(line) for line in f]
    return eng, res, events, path


def test_in_tree_joint_cfg_builds_the_published_constants(setup):
    from raft_tpu.ops.symmetry import Canonicalizer

    p = setup.model.p
    assert (p.n_servers, p.n_values, p.init_cluster_size) == (4, 1, 3)
    assert (p.max_elections, p.max_reconfigs) == (1, 2)
    assert (p.max_values_per_term, p.reconfig_type) == (1, 2)
    assert p.max_restarts == 0  # assumed: Raft.cfg's
    assert p.msg_slots == 112  # the registry's own
    assert setup.model.name == "RaftWithReconfigJointConsensus"
    assert setup.symmetry and setup.invariants == INVARIANTS
    # the row the cell is named for
    assert (setup.model.layout.W, setup.model.A) == (1042, 224)
    canon = Canonicalizer.for_model(setup.model, symmetry=True)
    assert (canon.P, canon.VL) == (24, 1036) and not canon.prune
    # the benchmark's configuration runs a copy of this very file
    assert filecmp.cmp(CFG, os.path.join(
        BENCH, "configs", "joint4", "RaftWithReconfigJointConsensus.cfg"),
        shallow=False)


def test_successor_sets_match_oracle_at_four_servers(setup, oracle, sample):
    model = setup.model
    vecs = np.stack([model.encode(st) for st in sample])
    succs, valid, _rank, ovf = jax.device_get(model.expand(vecs))
    assert not np.any(valid & ovf)
    for b, st in enumerate(sample):
        got = sorted(
            oracle.serialize_full(model.decode(succs[b, a]))
            for a in range(model.A)
            if valid[b, a]
        )
        want = sorted(
            oracle.serialize_full(s2) for _l, s2 in oracle.successors(st))
        assert got == want, f"successor mismatch at state {b}"


def test_no_kernel_writes_through_a_dynamic_index_scatter(setup):
    """On the v5e the sparse apply of this model dropped writes
    (PR 30: at a 16,384-lane worklist every AcceptAppendEntriesRequest
    onto server 2 and every HandleSnapshotRequest onto server 3 lost its
    log rows, while the dense expand of the same kernels was right): a
    batched `.at[i].set` with a per-lane index, the class models/raft.py
    and ops/bag.py left for one-hot selects in round 5. Every write of
    the shared reconfiguration kernels is a one-hot select now
    (models/base.py onehot_set, onehot_set2, onehot_add); every family
    is walked in tests/test_expand_sparse.py, and the differential that
    found the lanes is scripts/stage_diff.py (--scatter puts the
    scatters back). Nothing is compiled."""
    assert scatter_kernels(setup.model) == {}


def _joint(st) -> bool:
    """Some server holds a joint (old/new) configuration."""
    return any(c[1] for c in st["config"])


def _snapshot_in_flight(st) -> bool:
    return any("mlog" in dict(m) for m, _count in st["messages"])


def test_canon_is_brute_force_over_24_permutations_of_the_oracle(
        setup, oracle, sample):
    """The engine's canonical fingerprint of a state is the least, over
    all 24 server permutations, of the plain view hash of the oracle's
    permuted state as the model encodes it: the static tables' remaps of
    the bitmask fields (configurations, log entries, vote sets, the
    N-word message keys' member sets) against the oracle's `permute`."""
    from raft_tpu.ops.symmetry import Canonicalizer

    model = setup.model
    canon = Canonicalizer.for_model(model, symmetry=True)
    states = sample[::2]
    kinds = {(_joint(st), _snapshot_in_flight(st)) for st in states}
    assert (True, True) in kinds and (False, False) in kinds, kinds
    perms = list(itertools.permutations(range(4)))
    assert len(perms) == canon.P
    rows = np.stack([
        model.encode(oracle.permute(st, list(sigma)))
        for st in states for sigma in perms
    ]).astype(np.int32)
    raw = np.asarray(canon.raw_fingerprints(rows)).reshape(
        len(states), len(perms))
    got = np.asarray(canon.fingerprints(rows)).reshape(raw.shape)
    assert np.array_equal(got[:, 0], raw.min(axis=1))
    # the same for every member of the orbit
    assert np.array_equal(got, np.broadcast_to(got[:, :1], got.shape))
    # and two states share a fingerprint only where the oracle's own
    # canonical views are equal
    keys = [oracle.canon(st, True) for st in states]
    assert len(set(keys)) == len(set(got[:, 0].tolist()))


def test_device_bfs_counts_match_oracle_to_depth_6(device_run, oracle_run):
    _eng, res, _events, _path = device_run
    want = oracle_run
    assert res.violation is None and want["violation"] is None
    assert res.exit_cause == "max_depth"
    assert [int(x) for x in res.depth_counts] == want["depth_counts"]
    assert (res.distinct, res.total, res.terminal) == (
        want["distinct"], want["total"], want["terminal"])
    assert res.distinct == 2781
    rows = res.metrics
    assert [w["depth"] for w in rows] == list(range(1, DEPTH + 1))
    assert not any(w["overflow_bits"] for w in rows)


def test_golden_prefix_is_what_the_oracle_and_the_engine_count(
        device_run, oracle_run):
    """benchmark/goldens/joint4.json, the pooled oracle run's record,
    starts with this process's one-process oracle counts."""
    with open(os.path.join(BENCH, "goldens", "joint4.json")) as f:
        golden = json.load(f)
    assert golden["msg_slots"] == 112
    assert golden["independent_to_depth"] >= 11
    assert golden["depth_counts"][: DEPTH + 1] == oracle_run["depth_counts"]
    _eng, res, _events, _path = device_run
    assert golden["depth_counts"][: DEPTH + 1] == [
        int(x) for x in res.depth_counts]
    with open(os.path.join(BENCH, "traffic", "init-d11.json")) as f:
        traffic = json.load(f)
    for depth in (traffic["warmup_depth"], traffic["max_depth"]):
        assert str(depth) in golden["totals"]
        assert len(golden["depth_counts"]) > depth
    # and what chip_smoke.py's leg D holds the CLI to is its prefix
    with open(os.path.join(
            ROOT, "tests", "golden", "joint_cfg_depth_counts.json")) as f:
        smoke = json.load(f)["depth_limited"]
    depth = smoke["max_depth"]
    assert smoke["depth_counts"] == golden["depth_counts"][: depth + 1]
    assert smoke["distinct"] == sum(smoke["depth_counts"])
    assert {k: smoke[k] for k in ("total", "terminal")} == golden[
        "totals"][str(depth)]


def test_every_canonicalised_lane_takes_the_full_table_at_four_servers(
        device_run):
    """A layout without tiers: every representative of the in-chunk
    dedup goes through the 24-table min, one lane a distinct raw view of
    a chunk-step, so the wave row's `canon_tier3_full` is generated less
    the in-chunk duplicates, and `canon_tier3_local` is 0."""
    _eng, res, _events, _path = device_run
    rows = res.metrics
    for w in rows:
        assert w["canon_tier3_local"] == 0, w
        assert 0 < w["canon_tier3_full"] == (
            w["generated"] - w["canon_dup_lanes"]), w
        assert w["canon_dup_rate"] == round(
            w["canon_dup_lanes"] / w["generated"], 4)
    # Init's eight successors are eight raw views
    assert rows[0]["canon_tier3_full"] == rows[0]["generated"] == 8
    assert res.stats["canon_tier3_local"] == 0
    assert res.stats["canon_tier3_full"] == sum(
        w["canon_tier3_full"] for w in rows)
    assert sum(w["canon_dup_lanes"] for w in rows) > 0


def test_progress_line_and_summary_show_the_table_share(device_run):
    from raft_tpu.obs.progress import ProgressRenderer

    _eng, res, events, _path = device_run
    waves = [ev for ev in events if ev["event"] == "wave"]
    assert len(waves) == DEPTH
    last = waves[-1]
    share = last["canon_tier3_full"] / last["generated"]
    line = ProgressRenderer(stream=io.StringIO()).render_wave(last)
    assert f"tier3 {share:.0%}" in line
    assert f"dup {last['canon_dup_rate']:.0%}" in line
    (summary,) = [ev for ev in events if ev["event"] == "summary"]
    assert summary["canon_tier3_local"] == 0
    assert summary["canon_dup_rate"] == round(
        sum(w["canon_dup_lanes"] for w in waves) / res.total, 4)
    assert summary["canon_tier3_full"] == res.stats["canon_tier3_full"] > 0


def test_metrics_schema_holds_the_tier_bound_at_four_servers(device_run):
    """scripts/check_metrics_schema.py on the run's own stream, and on a
    copy whose last wave claims one lane too many."""
    _eng, _res, events, path = device_run
    script = os.path.join(ROOT, "scripts", "check_metrics_schema.py")
    ok = subprocess.run([sys.executable, script, path],
                        capture_output=True, text=True)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    bad_path = path + ".bad"
    with open(bad_path, "w") as f:
        for ev in events:
            if ev["event"] == "wave" and ev["depth"] == DEPTH:
                ev = dict(ev, canon_tier3_full=(
                    ev["generated"] - ev["canon_dup_lanes"] + 1))
            f.write(json.dumps(ev) + "\n")
    bad = subprocess.run([sys.executable, script, bad_path],
                         capture_output=True, text=True)
    assert bad.returncode != 0
    assert "canon_tier3" in bad.stdout + bad.stderr


@pytest.fixture(scope="module")
def dedup_canon_lowered(setup):
    """Lowered text of the engines' canon at four servers."""
    return lower_dedup_canon(setup.model)


@pytest.mark.parametrize("scope", ["inchunk", "tier3_full"])
def test_canon_scopes_nest_as_siblings_at_four_servers(
        dedup_canon_lowered, scope):
    """What scripts/stage_split.py splits `canon` by at S <= 4: the
    in-chunk dedup and the S!-table min, which runs in the body of the
    dedup's loop and is not booked under it; the tiers a five-server
    layout has are not traced at all."""
    assert f"/{scope}/" in dedup_canon_lowered
    assert "inchunk/tier" not in dedup_canon_lowered
    assert "/inchunk/while/" not in dedup_canon_lowered
    assert "/tier12/" not in dedup_canon_lowered
    assert "/tier3_local/" not in dedup_canon_lowered


def test_no_buffer_grows_after_the_wave_that_max_depth_ends(device_run):
    """Depth 6 writes 1,679 rows and 3 x 1,679 > 4,096: growing for a
    wave that never runs would leave FCAP grown and make the next
    verdict compile a new wave program inside a benchmark's window."""
    eng, first, _events, _path = device_run
    assert int(first.depth_counts[-1]) * eng.HEADROOM > 1 << 12
    assert eng.FCAP == 1 << 12
    again = eng.run(max_depth=DEPTH)
    assert eng.FCAP == 1 << 12
    assert again.stats["run_compiles"] == 0
    assert again.stats["programs_loaded"] == first.stats["programs_loaded"]
    assert again.depth_counts == first.depth_counts
