"""kraftrc3: upstream's KRaftWithReconfig.cfg (KRaft with one-at-a-time
membership change over a growing universe of servers: 3 hosts, up to 5
servers [host, diskId], 2 values, 12 permutations, five invariants)
against the pure-Python oracle, `KRaftReconfigOracle`: 479-lane rows,
145 candidate actions a state, 40 of them HandleMessage over the bag's
slots, and a canonicalizer of the model's own.

The cfg in the tree is reconstructed, latent bug and all (its header
says from what); the benchmark's copy has the bug repaired in the file.
What runs at the published constants: the cfg, the successor sets, the
fingerprint classes (a permuted state only encodes where every host
holds an initial server, InitClusterSize = 3) and one verdict to depth 4
through the CLI. The BFS counts with symmetry on and off run at
`test_kraft_reconfig.SMALLP`, where a wave program compiles in half the
time.
"""

import contextlib
import io
import itertools
import json
import os
import random

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import eqns, first_lane_masked, indexed_ops, scope_paths
from test_kraft_reconfig import SMALLP, small_oracle
from raft_tpu.models import kraft_reconfig
from raft_tpu.models.registry import build_from_cfg, oracle_for_setup
from raft_tpu.ops.packing import EMPTY
from raft_tpu.utils.cfg import CfgError, parse_cfg

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = os.path.join(ROOT, "configs", "pull-raft", "KRaftWithReconfig.cfg")
BENCH = os.path.join(ROOT, "benchmark")
BENCH_CFG = os.path.join(BENCH, "configs", "kraftrc3", "KRaftWithReconfig.cfg")
DEPTH = 4
INVARIANTS = (
    "LeaderHasAllAckedValues",
    "NoLogDivergence",
    "NeverTwoLeadersInSameEpoch",
    "NoIllegalState",
    "StatesMatchRoles",
)


@pytest.fixture(scope="module")
def setup():
    # lenient parsing, the registry's own bag width: the CLI's path
    return build_from_cfg(parse_cfg(CFG, lenient=True))


@pytest.fixture(scope="module")
def oracle(setup):
    return oracle_for_setup(setup)


@pytest.fixture(scope="module")
def golden():
    with open(os.path.join(BENCH, "goldens", "kraftrc3.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def walked(setup, oracle):
    """{action: states it was taken from} on seeded random walks of 50
    steps at the published constants, at most 6 states an action, each
    with room in its bag for what one more action sends. A walk reaches
    a diverging log and a committed removal in a second; BFS order would
    stop twenty steps short of them."""
    rng = random.Random(40)
    room = setup.model.p.msg_slots - setup.model.NS
    taken = {}
    for _ in range(120):
        st = oracle.init_state()
        for _step in range(50):
            succs = oracle.successors(st)
            if not succs:
                break
            label, nxt = rng.choice(succs)
            if len(st["messages"]) <= room:
                taken.setdefault(label.split("(")[0], []).append(st)
            st = nxt
    return {name: sts[:: max(1, len(sts) // 6)][:6]
            for name, sts in taken.items()}


@pytest.fixture(scope="module")
def sample(walked):
    return [st for name in sorted(walked) for st in walked[name]]


def test_in_tree_cfg_is_refused_strictly_and_builds_the_published_constants(
        setup):
    with pytest.raises(CfgError, match="undeclared model value 'v2'"):
        parse_cfg(CFG)
    p = setup.model.p
    assert (p.n_hosts, p.n_values) == (3, 2)  # v2 after the repair
    assert (p.init_cluster_size, p.min_cluster_size, p.max_cluster_size) == (
        3, 2, 4)
    assert (p.max_spawned_servers, p.max_restarts) == (5, 1)
    assert (p.max_values_per_epoch, p.max_add_reconfigs,
            p.max_remove_reconfigs) == (1, 1, 1)
    assert p.max_elections == 2  # assumed: config.json says why
    assert p.msg_slots == 40  # the registry's own
    assert setup.model.name == "KRaftWithReconfig"
    assert setup.symmetry and setup.invariants == INVARIANTS
    # the row the cell is named for
    assert (setup.model.layout.W, setup.model.A) == (479, 145)
    groups = {g.name: g.n for g in setup.model.sparse_groups()}
    assert groups["HandleMessage"] == 40
    canon = setup.model.make_canonicalizer(True)
    assert canon._sigmas.shape == (12, 3) and canon._taus.shape == (12, 2)


def test_the_two_cfg_copies_differ_in_one_line_and_build_one_model():
    """The benchmark's copy is the in-tree cfg with `v2` declared, which
    is all --lenient does to it; the adapter, which parses strictly,
    builds from it the engine the CLI builds from the in-tree file."""
    from benchmark import adapter
    from raft_tpu.checker.device_bfs import DeviceBFS

    with open(CFG) as f:
        tree = f.read().splitlines()
    with open(BENCH_CFG) as f:
        bench = f.read().splitlines()
    added = [line for line in bench if line not in tree]
    assert [line.split() for line in added] == [["v2", "=", "v2"]]
    assert [line for line in bench if line not in added] == tree
    lenient, strict = parse_cfg(CFG, lenient=True), parse_cfg(BENCH_CFG)
    assert lenient.constants == strict.constants
    assert lenient.invariants == strict.invariants
    assert lenient.symmetry == strict.symmetry
    with open(os.path.join(BENCH, "workloads", "kraftrc3-wide.json")) as f:
        cell = json.load(f)
    params = dict(cell["engine_params"], chunk=64, frontier_cap=1 << 12)
    bench_eng = adapter.build_engine(BENCH_CFG, cell["engine"], params, None)
    cli = build_from_cfg(lenient, msg_slots=params["msg_slots"])
    cli_eng = DeviceBFS(cli.model, invariants=cli.invariants,
                        symmetry=cli.symmetry, chunk=64, frontier_cap=1 << 12)
    assert adapter.ident(bench_eng) == cli_eng._ckpt_ident()
    assert "StatesMatchRoles" in adapter.ident(bench_eng)


def test_successor_sets_match_oracle_on_states_that_take_every_action(
        setup, oracle, walked, sample):
    """Every action the spec can take was taken from some sampled state:
    HandleMessage's thirteen kinds, a spawn, a join accepted and
    rejected, a removal, a restart (RestartWithoutState is never enabled
    upstream, :913, and the oracle never offers it); per state the
    (action, successor) pairs equal the oracle's."""
    model = setup.model
    assert set(walked) == set(model.ACTION_NAMES)
    assert len(model.ACTION_NAMES) == 21
    vecs = np.stack([model.encode(st) for st in sample]).astype(np.int32)
    succs, valid, rank, ovf = jax.device_get(model.expand(vecs))
    assert not np.any(valid & ovf)
    for b, st in enumerate(sample):
        got = sorted(
            (model.ACTION_NAMES[rank[b, a]],
             oracle.serialize_full(model.decode(succs[b, a])))
            for a in np.nonzero(valid[b])[0])
        want = sorted((label.split("(")[0], oracle.serialize_full(s2))
                      for label, s2 in oracle.successors(st))
        assert got == want, f"successor mismatch at state {b}"


def test_no_gather_and_no_scatter_in_the_canonicalizer_and_its_scopes(setup):
    """`SlotCanonicalizer._fingerprints` reads and writes through its
    tiny index sets by compares and selects and sorts nothing (the bag
    is hashed as a multiset since PR 45; its re-sort under each of the
    12 permutations was 40 % of kraftrc3-wide's wall), and its four
    steps reach a trace as `canon/slot_*`: each scope is opened outside
    the vmaps it covers."""
    from raft_tpu.obs import stage

    model = setup.model
    canon = model.make_canonicalizer(True)
    rows = jax.ShapeDtypeStruct((16, model.layout.W), np.int32)
    names = [e.primitive.name
             for e in eqns(jax.make_jaxpr(canon._fingerprints)(rows).jaxpr)]
    assert not [n for n in names if n == "gather" or n.startswith("scatter")]
    assert names.count("sort") == 0
    lowered = jax.jit(stage("canon")(canon.fingerprints_dedup)).lower(
        rows, jax.ShapeDtypeStruct((16,), bool)).as_text(debug_info=True)
    paths = scope_paths(lowered)
    for scope in ("slot_sort", "slot_remap", "slot_bag", "slot_hash"):
        assert ("canon", scope) in paths  # in the in-chunk dedup's loop
        assert f"({scope})" not in lowered  # not vmap(slot_bag)
    # the only sorts left in the canon stage are the in-chunk dedup's
    assert "/slot_bag/" in lowered and "/inchunk/sort" in lowered
    assert not [ln for ln in lowered.splitlines()
                if "/slot_" in ln and ln.split('"')[1].endswith("/sort")]


def test_canon_stage_at_the_cells_chunk_shape_indexes_rows_alone(setup):
    """The slot canon's stage as `kraftrc3-wide` runs it, 16,384
    compacted lanes a chunk-step (nothing compiled): no scatter, and no
    gather from a one-dimensional array (the in-chunk dedup's fill and
    its loop's index read were two such until PR 54; each is a serial
    pass over its lanes on the chip). What is left reads rows: the raw
    key's view prefix is a slice, so the stage's one gather is a block's
    representatives."""
    from raft_tpu.obs import stage

    model = setup.model
    canon = model.make_canonicalizer(True)
    lowered = jax.jit(stage("canon")(canon.fingerprints_dedup)).lower(
        jax.ShapeDtypeStruct((16384, model.layout.W), np.int32),
        jax.ShapeDtypeStruct((16384,), bool)).as_text(debug_info=True)
    ops = indexed_ops(lowered)
    assert not [op for op in ops if op[0] == "scatter" or len(op[1]) < 2]
    assert [(dims, stack.split("/")[-2]) for _kind, dims, stack in ops] == [
        (["16385", str(model.layout.W)], "inchunk")]
    assert lowered.count("stablehlo.sort") == 3  # raw, lay-out, return


def test_fingerprints_are_equal_iff_the_oracles_canon_is_under_all_12(
        setup, oracle, sample):
    """On the sampled states and their images under every host and value
    permutation: an image's fingerprint is its state's, and two states
    share one iff the oracle's brute-force `canon` (the least serialised
    view over the 12 permuted states) is equal."""
    model = setup.model
    canon = model.make_canonicalizer(True)
    perms = [(list(s), list(t))
             for s in itertools.permutations(range(3))
             for t in itertools.permutations(range(2))]
    assert len(perms) == 12
    states = sample[::3]
    # a state and a proper image of a later one: equal keys that are not
    # one state's rows
    states = states + [oracle.permute(states[-1], [1, 2, 0], [1, 0])]
    rows = np.stack([
        model.encode(oracle.permute(st, sigma, tau))
        for st in states for sigma, tau in perms
    ]).astype(np.int32)
    got = np.asarray(canon.fingerprints(rows)).reshape(len(states), 12)
    assert np.array_equal(got, np.broadcast_to(got[:, :1], got.shape))
    keys = [oracle.canon(st, True) for st in states]
    assert keys[-1] == keys[-2]
    fps = got[:, 0].tolist()
    for a, b in itertools.combinations(range(len(states)), 2):
        assert (fps[a] == fps[b]) == (keys[a] == keys[b]), (a, b)
    # with symmetry off a permuted state is another state
    plain = model.make_canonicalizer(False)
    off = np.asarray(plain.fingerprints(rows)).reshape(len(states), 12)
    assert len(set(off[0].tolist())) > 1


def _remapped_views(canon, rows, sort_bag=False):
    """[B, 12, view_len]: every row's view under every permutation as
    `_slot_sort` and `_slot_remap` leave it, the bag as it lies or, as
    the canon did until PR 45, re-sorted."""
    from raft_tpu.ops import bag

    def one(vec, sigma, tau):
        host2, inv = canon._slot_sort(vec, sigma, tau)
        r, ws, cnt = canon._slot_remap(vec, sigma, tau, host2, inv)
        if sort_bag:
            ws, cnt = bag.wide_bag_sort(ws, cnt)
        return jnp.concatenate([r, *ws, cnt])

    f = jax.vmap(jax.vmap(one, (None, 0, 0)), (0, None, None))
    return f(jnp.asarray(rows, jnp.int32), canon._sigmas, canon._taus)


def _sorted_bag_fingerprints(canon, rows):
    """The slot canon's formula until PR 45, kept as the reference: the
    positional hash of the whole 472-lane view, its bag re-sorted under
    every permutation, then the min."""
    from raft_tpu.ops.hashing import hash_lanes

    views = _remapped_views(canon, rows, sort_bag=True)
    return jnp.min(hash_lanes(views, seed=canon.seed), axis=-1)


def _same_partition(a, b):
    a, b = np.asarray(a).tolist(), np.asarray(b).tolist()
    return len(set(a)) == len(set(b)) == len(set(zip(a, b)))


def test_multiset_bag_fingerprints_partition_the_sample_as_the_sorted_bag_did(
        setup, oracle, sample, views):
    """Which 64-bit number names a class changed with PR 45, the classes
    did not: on the walked sample, its images under two permutations and
    a block of exact repeats, two rows share a new fingerprint iff they
    shared one under the sorted-bag formula, and no number carried over."""
    canon = setup.model.make_canonicalizer(True)
    rows = np.concatenate([
        views,
        np.stack([setup.model.encode(st) for st in sample]).astype(np.int32),
        views[:40]])
    new = np.asarray(canon.fingerprints(rows))
    old = np.asarray(jax.jit(partial(_sorted_bag_fingerprints, canon))(rows))
    assert _same_partition(new, old)
    classes = len(set(new.tolist()))
    assert 40 < classes < len(rows)  # images and repeats collapsed
    assert not set(new.tolist()) & set(old.tolist())


def _bag_lanes(model, w):
    """The lanes of bag word ``w`` (``len(words)`` = the counts)."""
    names = [f"msg_w{i}" for i in range(model.packer.n_words)] + ["msg_cnt"]
    sl = model.layout.sl(names[w])
    return np.arange(sl.start, sl.stop)


@pytest.fixture(scope="module")
def busy_rows(setup, sample):
    """Sampled rows with at least three messages in flight."""
    model = setup.model
    rows = np.stack([model.encode(st) for st in sample
                     if len(st["messages"]) >= 3]).astype(np.int32)
    assert len(rows) >= 16
    return rows[:32]


@pytest.mark.parametrize(
    "edit", ["shuffle_occupied", "rotate_all_slots", "count", "key_word"])
def test_the_bags_slot_order_is_no_part_of_a_fingerprint_and_its_content_is(
        setup, busy_rows, edit):
    """The bag enters as a multiset: the same records in other slots,
    the free slots among them or not, are the same fingerprint (a
    remapped bag is hashed as it lies, unsorted); one delivery count or
    one key word changed is another."""
    model = setup.model
    canon = model.make_canonicalizer(True)
    nw = model.packer.n_words
    M = model.p.msg_slots
    rng = np.random.default_rng(45)
    out = busy_rows.copy()
    for b, row in enumerate(busy_rows):
        occ = np.nonzero(row[_bag_lanes(model, 0)] != int(EMPTY))[0]
        assert len(occ) >= 3
        if edit == "shuffle_occupied":
            src = np.arange(M)
            src[occ] = rng.permutation(occ)
            while np.array_equal(src, np.arange(M)):
                src[occ] = rng.permutation(occ)
        elif edit == "rotate_all_slots":
            src = np.roll(np.arange(M), 1 + b)  # free slots in the middle
        if edit in ("shuffle_occupied", "rotate_all_slots"):
            for w in range(nw + 1):
                lanes = _bag_lanes(model, w)
                out[b, lanes] = row[lanes][src]
        elif edit == "count":
            out[b, _bag_lanes(model, nw)[occ[-1]]] += 1
        else:
            out[b, _bag_lanes(model, nw - 1)[occ[0]]] ^= 1
    assert not (out == busy_rows).all(axis=1).any()
    before = np.asarray(canon.fingerprints(busy_rows))
    after = np.asarray(canon.fingerprints(out))
    if edit in ("shuffle_occupied", "rotate_all_slots"):
        assert np.array_equal(after, before)
        # the raw key of the in-chunk dedup is positional: other lanes
        assert (np.asarray(canon.raw_fingerprints(out))
                != np.asarray(canon.raw_fingerprints(busy_rows))).all()
    else:
        assert (after != before).all()


@pytest.mark.parametrize("seed", [0, 0x5EED])
def test_both_canons_hash_a_bag_through_the_one_helper(setup, views, seed):
    """`ops.symmetry.bag_hash_pair` is the tree's one multiset hash of a
    bag: a plain `Canonicalizer` over the same layout and packer gives
    the same pair for the same words, counts and seed, and the slot
    canon's fingerprint is the least of `Canonicalizer`'s own hash (the
    312 non-bag lanes by position XOR that pair) over the 12 remapped
    views, their bags as they lie, bit for bit under either seed."""
    from raft_tpu.ops import symmetry

    model = setup.model
    assert kraft_reconfig.bag_hash_pair is symmetry.bag_hash_pair
    slot = model.make_canonicalizer(True, seed=seed)
    plain = symmetry.Canonicalizer(
        model.layout, model.packer, symmetry=False, seed=seed)
    nb = len(plain._nonbag_lanes)
    assert nb == 312 and np.array_equal(plain._nonbag_lanes, np.arange(nb))
    rows = views[:64]
    words = [rows[:, _bag_lanes(model, w)]
             for w in range(model.packer.n_words)]
    cnt = rows[:, _bag_lanes(model, len(words))]
    ba, bb = symmetry.bag_hash_pair(words, cnt, seed)
    pa, pb = plain._bag_hash_pair(jnp.asarray(rows[:, :model.layout.view_len]))
    assert np.array_equal(ba, pa) and np.array_equal(bb, pb)
    images = _remapped_views(slot, rows)
    assert images.shape == (64, 12, model.layout.view_len)
    want = jnp.min(plain._perm_hash(images), axis=-1)
    assert np.array_equal(np.asarray(slot.fingerprints(rows)),
                          np.asarray(want))


def test_seeded_slot_canon_is_another_family_over_the_same_classes(
        setup, oracle, views):
    """The collision audit's second family: another seed gives other
    fingerprints to the same rows (the rows' hash and the bag's hash both
    take the seed: rows with an empty bag and a bag alone both move) and
    the same partition."""
    model = setup.model
    a0 = model.make_canonicalizer(True, seed=0)
    a1 = model.make_canonicalizer(True, seed=0x5EED)
    init = model.encode(oracle.init_state()).astype(np.int32)
    assert (init[_bag_lanes(model, 0)] == int(EMPTY)).all()
    rows = np.concatenate([views[:96], views[:32], init[None]])
    f0, f1 = (np.asarray(a.fingerprints(rows)) for a in (a0, a1))
    assert (f0 != f1).all()
    assert _same_partition(f0, f1)
    words = [rows[:96, _bag_lanes(model, w)]
             for w in range(model.packer.n_words)]
    cnt = rows[:96, _bag_lanes(model, len(words))]
    p0 = np.stack(kraft_reconfig.bag_hash_pair(words, cnt, 0))
    p1 = np.stack(kraft_reconfig.bag_hash_pair(words, cnt, 0x5EED))
    busy = (words[0] != int(EMPTY)).any(axis=1)
    assert busy.sum() > 48 and (p0 != p1).all(axis=0)[busy].all()


def test_the_formula_revision_is_in_the_checkpoint_identity(tmp_path):
    """The slot canon's fingerprints changed with PR 45 and no other
    canon's did: a KRaftWithReconfig engine's ident carries the canon's
    own `hashv`, a checkpoint written under the sorted-bag formula
    (`hashv=5`) is refused on load, and a Raft engine's ident is the
    string it was."""
    from raft_tpu.checker.bfs import BFSChecker
    from raft_tpu.checker.device_bfs import DeviceBFS
    from raft_tpu.models.raft import RaftParams, cached_model
    from raft_tpu.obs import hashv_of
    from raft_tpu.ops.symmetry import Canonicalizer
    from raft_tpu.parallel.sharded import ShardedBFS
    from raft_tpu.resilience import CheckpointMismatch
    from raft_tpu.resilience import ckpt as rckpt

    model = kraft_reconfig.cached_model(SMALLP)
    invs = INVARIANTS[:2]
    assert (Canonicalizer.hashv, kraft_reconfig.SlotCanonicalizer.hashv) == (
        5, 6)
    host = BFSChecker(model, invariants=invs, symmetry=True)
    dev = DeviceBFS(model, invariants=invs, symmetry=True, chunk=64,
                    frontier_cap=1 << 8, seen_cap=1 << 10,
                    journal_cap=1 << 10)
    mesh = ShardedBFS(model, invariants=invs, symmetry=True,
                      devices=jax.devices()[:2], chunk=64)
    for eng in (host, dev, mesh):
        ident = eng._ckpt_ident()
        assert "/hashv=6/wl=1/" in ident and hashv_of(ident) == 6
    assert host._telemetry_manifest()["hashv"] == 6

    path = str(tmp_path / "run.npz")
    first = host.run(max_depth=2, checkpoint_path=path, checkpoint_every_s=0)
    ck, _gen, _skipped = rckpt.load_npz(path)
    assert str(ck["spec"]) == host._ckpt_ident()
    resumed = host.run(max_depth=3, resume=path)
    assert resumed.depth_counts[: len(first.depth_counts)] == (
        first.depth_counts)
    old = str(tmp_path / "sorted_bag.npz")
    rckpt.save_npz(old, dict(
        ck, spec=host._ckpt_ident().replace("/hashv=6/", "/hashv=5/")))
    with pytest.raises(CheckpointMismatch, match="checkpoint is for spec"):
        host.run(max_depth=3, resume=old)

    raft = DeviceBFS(cached_model(RaftParams(
        n_servers=3, n_values=1, max_elections=2, max_restarts=0,
        msg_slots=32)), invariants=("LeaderHasAllAckedValues",
                                    "NoLogDivergence"),
        symmetry=True, chunk=64, frontier_cap=1 << 8, seen_cap=1 << 10,
        journal_cap=1 << 10)
    assert raft._ckpt_ident() == (
        "Raft/RaftParams(n_servers=3, n_values=1, max_elections=2, "
        "max_restarts=0, msg_slots=32, election_quorum=None, "
        "replication_quorum=None, strict_send_once=False, "
        "has_pending_response=True, trunc_term_mismatch=False, "
        "has_fsync=False, fsync_leader_before_ae=False, "
        "fsync_leader_quorum=False, fsync_follower_reply=False, "
        "net_faults=False, max_msg_copies=2, dyn_consts=(), fleet=False)"
        "/W=144/sym=True/seed=0/hashv=5/wl=3"
        "/inv=LeaderHasAllAckedValues,NoLogDivergence")


def _break_roles(st):
    """An Unattached server that believes in a leader."""
    i, j = sorted(st["servers"])[:2]
    return dict(st, state={**st["state"], i: "Unattached"},
                leader={**st["leader"], i: j})


def test_states_match_roles_kernel_equals_the_oracles_predicate(
        setup, oracle, sample):
    """The invariant no other cell evaluates: the kernel and the oracle's
    predicate agree on reachable states (where both hold) and on the same
    states broken by hand (where neither does)."""
    model = setup.model
    states = sample[::3]
    states = states + [_break_roles(st) for st in states]
    name = "StatesMatchRoles"
    want = np.array([oracle.INVARIANTS[name](oracle, st) for st in states])
    vecs = np.stack([model.encode(st) for st in states]).astype(np.int32)
    got = np.asarray(model.invariants[name](vecs))
    assert np.array_equal(got, want)
    half = len(states) // 2
    assert want[:half].all() and not want[half:].any()


@pytest.fixture(scope="module")
def cli_run(tmp_path_factory):
    """One verdict to depth 4 at the published constants, as a user gets
    it: the in-tree cfg through `python -m raft_tpu`'s main path."""
    from raft_tpu.__main__ import main

    metrics = tmp_path_factory.mktemp("kraftrc3") / "m.jsonl"
    out, err = io.StringIO(), io.StringIO()
    argv = [CFG, "--platform", "cpu", "--checker", "tpu", "--chunk", "256",
            "--msg-slots", "40", "--frontier-cap", "4096",
            "--max-depth", str(DEPTH), "--json", "--metrics-out", str(metrics)]
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        refused = main(argv)
        rc = main(argv + ["--lenient"])
    events = [json.loads(x) for x in metrics.read_text().splitlines()]
    return refused, rc, out.getvalue(), err.getvalue(), events


def test_cli_refuses_the_cfg_and_under_lenient_counts_the_goldens_prefix(
        cli_run, golden):
    """2,600 distinct states to depth 4, all five invariants on each,
    equal to the pooled oracle run's record."""
    refused, rc, out, err, events = cli_run
    assert refused == 64 and "undeclared model value 'v2'" in err
    assert rc == 0, err
    summary = json.loads(out.strip().splitlines()[-1])
    waves = [e for e in events if e["event"] == "wave"]
    want = golden["depth_counts"][: DEPTH + 1]
    assert want == [1, 9, 65, 406, 2119]
    assert [1] + [w["new"] for w in waves] == want
    assert (summary["distinct"], summary["violation"]) == (2600, None)
    assert {"total": summary["total"], "terminal": summary["terminal"]} == (
        golden["totals"][str(DEPTH)])
    assert summary["exit_cause"] == "max_depth"
    assert all(w["overflow_bits"] == 0 for w in waves)
    # the one cell whose cfg lets a server crash (MaxRestarts = 1), under
    # a name of its own: the model declares it (CRASH_ACTIONS, PR 51)
    (manifest,) = [e for e in events if e["event"] == "manifest"]
    coverage = [e for e in events if e["event"] == "coverage"][-1]
    crash = manifest["action_names"].index("RestartWithState")
    assert summary["restart_fired"] == coverage["actions"][crash][1] > 0


def test_permutations_run_once_a_distinct_raw_view_and_the_rows_say_so(
        cli_run):
    """`SlotCanonicalizer.fingerprints_dedup` runs the 12 permutations on
    one lane of each distinct raw view of a chunk-step: a wave's
    `canon_tier3_full` (the representatives) and `canon_dup_lanes` (the
    lanes that skipped) add up to its valid successor lanes, duplicates
    appear from the first wave that has any (depth 2: two servers'
    timeouts commute) and grow, and there are no tiers."""
    _refused, _rc, out, _err, events = cli_run
    waves = [e for e in events if e["event"] == "wave"]
    assert len(waves) == DEPTH
    for w in waves:
        assert w["canon_tier3_full"] + w["canon_dup_lanes"] == w["generated"]
        assert w["canon_tier3_full"] > 0 and w["canon_tier3_local"] == 0
        assert w["canon_dup_rate"] == pytest.approx(
            w["canon_dup_lanes"] / w["generated"], abs=1e-4)
    dups = [w["canon_dup_lanes"] for w in waves]
    assert dups[0] == 0 and all(d > 0 for d in dups[1:])
    assert dups == sorted(dups)
    summary = json.loads(out.strip().splitlines()[-1])
    assert summary["canon_tier3_full"] == summary["total"] - 1 - sum(dups)


B_EDGE = 256  # one shape, one program: 4 blocks of 64 lanes


@pytest.fixture(scope="module")
def views(setup, oracle, sample):
    """A batch's worth of rows of distinct raw views: the sampled states
    and their images under two host and value permutations."""
    model = setup.model
    rows = np.stack([
        model.encode(oracle.permute(st, sigma, tau))
        for sigma, tau in (([0, 1, 2], [0, 1]), ([1, 2, 0], [1, 0]),
                           ([2, 0, 1], [0, 1]))
        for st in sample]).astype(np.int32)
    VL = model.layout.view_len
    _u, first = np.unique(rows[:, :VL], axis=0, return_index=True)
    rows = rows[np.sort(first)]
    assert len(rows) >= B_EDGE
    return rows[:B_EDGE]


@pytest.fixture(scope="module")
def slot_canon(setup):
    canon = setup.model.make_canonicalizer(True)
    assert min(B_EDGE, max(64, B_EDGE // canon.BLOCKS)) == 64
    return canon, jax.jit(canon.fingerprints_dedup)


# the edges of the in-chunk dedup -> the distinct raw views among the
# valid lanes of the batch built for each
EDGES = {
    "duplicates_far_apart": 216, "invalid_interleaved": 128,
    "all_invalid": 0, "one_view_everywhere": 1,
    "more_representatives_than_a_block": 100,
    "representatives_fill_one_block_exactly": 64,
    "representatives_fill_three_blocks_exactly": 192,
    "every_lane_its_own_view": 256,
    "duplicates_differ_in_the_aux_lanes": 50,
}


@pytest.fixture(scope="module")
def edge_batches(views, setup):
    """name -> (rows [B_EDGE, W], valid [B_EDGE])."""
    B = B_EDGE
    VL, W = setup.model.layout.view_len, setup.model.layout.W
    rng = np.random.default_rng(42)
    lanes, on = np.arange(B), np.ones(B, bool)
    out = {}
    # 20 views at both ends of the batch and in the middle
    idx = lanes.copy()
    idx[-20:] = idx[100:120] = idx[:20]
    out["duplicates_far_apart"] = (views[idx], on)
    # every other lane invalid, and the invalid lanes hold rows whose
    # view a valid lane holds too: they must not count as its duplicate
    out["invalid_interleaved"] = (views[lanes // 2], lanes % 2 == 0)
    out["all_invalid"] = (views, ~on)
    out["one_view_everywhere"] = (views[np.zeros(B, int)], on)
    out["more_representatives_than_a_block"] = (views[lanes % 100], on)
    out["representatives_fill_one_block_exactly"] = (views[lanes % 64], on)
    out["representatives_fill_three_blocks_exactly"] = (
        views[rng.permutation(lanes % 192)], on)
    out["every_lane_its_own_view"] = (views, on)
    # raw duplicates that differ past the view: the aux lanes
    rows = views[lanes % 50].copy()
    rows[50:, VL:] = rng.integers(0, 7, size=(B - 50, W - VL))
    out["duplicates_differ_in_the_aux_lanes"] = (rows, on)
    assert set(out) == set(EDGES)
    return out


@pytest.mark.parametrize("edge", sorted(EDGES))
def test_inchunk_dedup_is_the_canon_of_every_lane_bit_for_bit(
        setup, slot_canon, edge_batches, edge):
    """Dedup decides where the 12 permutations run, never their value:
    `fingerprints_dedup` is `_fingerprints` on the first valid lane of
    every distinct raw view, U64_MAX on every other lane (invalid, or a
    duplicate of a lower lane), `n_dup` the valid lanes less their
    distinct raw views and `tiers` [0, representatives]."""
    from raft_tpu.ops.hashing import U64_MAX

    canon, dedup = slot_canon
    rows, valid = edge_batches[edge]
    VL = setup.model.layout.view_len
    fps, n_dup, tiers = jax.device_get(dedup(rows, valid))
    want, want_dup = first_lane_masked(canon, rows, valid)
    assert np.array_equal(fps, want)
    distinct = len(np.unique(rows[valid][:, :VL], axis=0))
    assert distinct == EDGES[edge] == int(np.sum(fps != U64_MAX))
    # the lanes that carry a fingerprint: each view's first valid one
    _u, first = np.unique(rows[valid][:, :VL], axis=0, return_index=True)
    assert np.array_equal(np.flatnonzero(fps != U64_MAX),
                          np.sort(np.flatnonzero(valid)[first]))
    assert int(n_dup) == want_dup == int(valid.sum()) - distinct
    assert [int(t) for t in tiers] == [0, distinct]


def test_a_permuted_image_is_no_raw_duplicate_and_both_lanes_run(
        setup, oracle, sample, slot_canon):
    """A lane that holds a permuted image of another lane's state shares
    its fingerprint, not its raw view: the dedup runs the permutations on
    both, and both come back with the one value, for the dedup stage to
    choose between; the four lanes that repeat them come back masked."""
    from raft_tpu.ops.hashing import U64_MAX

    canon, dedup = slot_canon
    model = setup.model
    st = sample[-1]
    image = oracle.permute(st, [1, 2, 0], [1, 0])
    pair = np.stack([model.encode(st), model.encode(image)]).astype(np.int32)
    assert not np.array_equal(pair[0], pair[1])
    rows = np.tile(pair, (B_EDGE // 2, 1))
    valid = np.arange(B_EDGE) < 6
    fps, n_dup, tiers = jax.device_get(dedup(rows, valid))
    assert fps[0] == fps[1] == np.asarray(canon.fingerprints(pair))[0]
    assert fps[0] != U64_MAX and np.all(fps[2:] == U64_MAX)
    assert (int(n_dup), [int(t) for t in tiers]) == (4, [0, 2])


def test_the_canon_reads_nothing_past_the_view(setup, views):
    """What makes the hash of the view prefix a sound raw key: rows that
    differ only in the aux lanes (the counters VIEW leaves out) have one
    fingerprint, and the raw key is theirs too."""
    model = setup.model
    canon = model.make_canonicalizer(True)
    VL, W = model.layout.view_len, model.layout.W
    assert W - VL == 7  # four counters and valueCtr, one an epoch
    rows = views[:64]
    other = rows.copy()
    other[:, VL:] = np.random.default_rng(7).integers(
        -3, 1 << 20, size=(len(rows), W - VL))
    assert not np.array_equal(rows, other)
    assert np.array_equal(np.asarray(canon.fingerprints(rows)),
                          np.asarray(canon.fingerprints(other)))
    raw = np.asarray(canon.raw_fingerprints(rows))
    assert np.array_equal(raw, np.asarray(canon.raw_fingerprints(other)))
    assert len(set(raw.tolist())) == len(rows)


def test_golden_is_the_oracles_and_covers_the_cell(golden):
    """benchmark/goldens/kraftrc3.json is the pooled oracle run's record
    at the bag width the cell runs, its totals cover the cell's depth,
    and what chip_smoke.py's leg F holds the CLI to is its prefix."""
    assert golden["msg_slots"] == 40
    assert golden["independent_to_depth"] >= 7
    assert "oracle" in golden["source"] and "oracle_golden.py" in golden["command"]
    with open(os.path.join(BENCH, "traffic", "init-d7-warm7.json")) as f:
        traffic = json.load(f)
    assert traffic["warmup_depth"] == traffic["max_depth"] == 7
    for depth in (4, 7):
        assert str(depth) in golden["totals"]
    assert sum(golden["depth_counts"][:8]) == 188494
    assert golden["totals"]["7"] == {"total": 490155, "terminal": 0}
    with open(os.path.join(
            ROOT, "tests", "golden", "kraftrc_cfg_depth_counts.json")) as f:
        smoke = json.load(f)["depth_limited"]
    depth = smoke["max_depth"]
    assert smoke["msg_slots"] == 40
    assert smoke["depth_counts"] == golden["depth_counts"][: depth + 1]
    assert smoke["distinct"] == sum(smoke["depth_counts"])
    assert {k: smoke[k] for k in ("total", "terminal")} == golden[
        "totals"][str(depth)]


@pytest.mark.parametrize("sym", [True, False])
def test_device_bfs_counts_match_oracle_and_a_second_verdict_compiles_nothing(
        sym):
    """Per-depth counts, generated and terminal equal the oracle's through
    the slot canonicalizer, symmetry on and off; the engine's next
    verdict finds every program, the canonicalizer's among them."""
    from raft_tpu.checker.device_bfs import DeviceBFS

    model = kraft_reconfig.cached_model(SMALLP)
    eng = DeviceBFS(model, invariants=INVARIANTS, symmetry=sym, chunk=256,
                    frontier_cap=1 << 12, seen_cap=1 << 15,
                    journal_cap=1 << 15)
    first = eng.run(max_depth=DEPTH, collect_metrics=True)
    want = small_oracle().bfs(invariants=INVARIANTS, symmetry=sym,
                              max_depth=DEPTH)
    assert first.violation is None and want["violation"] is None
    assert [int(x) for x in first.depth_counts] == want["depth_counts"]
    assert (first.distinct, first.total, first.terminal) == (
        want["distinct"], want["total"], want["terminal"])
    assert not any(w["overflow_bits"] for w in first.metrics)
    full = [w["canon_tier3_full"] for w in first.metrics]
    dup = [w["canon_dup_lanes"] for w in first.metrics]
    assert [f + d for f, d in zip(full, dup)] == (
        [w["generated"] for w in first.metrics] if sym else [0] * DEPTH)
    assert (sum(dup) > 0) == sym
    assert first.stats["run_compiles"] >= 1
    again = eng.run(max_depth=DEPTH, collect_metrics=True)
    assert again.stats["run_compiles"] == 0
    assert not any(w["compiles"] for w in again.metrics)
    assert again.stats["programs_loaded"] == first.stats["programs_loaded"]
    assert again.depth_counts == first.depth_counts
    assert (again.distinct, again.total) == (first.distinct, first.total)
