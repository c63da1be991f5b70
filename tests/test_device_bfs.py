"""DeviceBFS (the device-resident fast path) parity + trace tests.

These are the round-2 hand-run checks promoted to tests (oracle parity,
trace validity, chunk-size sweep), per the round-2 verdict. The chunk sweep
is the CPU half of the defense against scatter-drop miscompiles (ops/bag.py
uses one-hot writes because of one); the chip half is the runtime parity
gate in checker/parity.py.
"""

import collections

import numpy as np
import pytest

from raft_tpu.checker.bfs import BFSChecker
from raft_tpu.checker.device_bfs import DeviceBFS
from raft_tpu.models.raft import RaftModel, RaftParams, cached_model

SMALL = RaftParams(n_servers=3, n_values=1, max_elections=1, max_restarts=0, msg_slots=16)
INVS = ("LeaderHasAllAckedValues", "NoLogDivergence")


def _device(params, invariants, symmetry=True, chunk=512, **kw):
    kw.setdefault("frontier_cap", 1 << 14)
    kw.setdefault("seen_cap", 1 << 17)
    kw.setdefault("journal_cap", 1 << 17)
    return DeviceBFS(
        cached_model(params), invariants=invariants, symmetry=symmetry, chunk=chunk, **kw
    )


@pytest.mark.slow
@pytest.mark.parametrize("symmetry", [True, False])
def test_device_bfs_matches_host_checker(symmetry):
    model = cached_model(SMALL)
    host = BFSChecker(model, invariants=INVS, symmetry=symmetry, chunk=256)
    hres = host.run()
    dres = _device(SMALL, INVS, symmetry=symmetry).run()
    assert dres.violation is None and hres.violation is None
    assert dres.distinct == hres.distinct
    assert dres.depth_counts == hres.depth_counts
    assert dres.total == hres.total
    assert dres.terminal == hres.terminal
    assert dres.exhausted


@pytest.mark.slow
def test_device_bfs_chunk_sweep():
    """Identical counts at several chunk sizes — the invariance that the
    round-2 TPU dedup miscount silently broke."""
    base = None
    for chunk in (256, 512, 1024):
        res = _device(SMALL, INVS, chunk=chunk).run()
        sig = (res.distinct, res.total, res.depth_counts, res.terminal)
        if base is None:
            base = sig
        else:
            assert sig == base, f"chunk={chunk} diverged: {sig} != {base}"


def test_device_bfs_trace_on_injected_invariant():
    import jax.numpy as jnp

    model = cached_model(SMALL)
    lay = model.layout

    def no_commit(states):
        ci = lay.get(states, "commitIndex")
        return jnp.all(ci == 0, axis=1)

    model.invariants["NoCommit"] = no_commit
    try:
        res = _device(SMALL, ("NoCommit",)).run()
    finally:
        del model.invariants["NoCommit"]
    assert res.violation is not None
    assert res.trace is not None
    assert res.violation.depth == len(res.trace) - 1
    final = res.trace[-1][1]
    assert any(ci > 0 for ci in final["commitIndex"])
    # shortest-counterexample depth must agree with the host checker's
    model.invariants["NoCommit"] = no_commit
    try:
        hres = BFSChecker(model, invariants=("NoCommit",), symmetry=True, chunk=256).run()
    finally:
        del model.invariants["NoCommit"]
    assert res.violation.depth == hres.violation.depth


@pytest.mark.slow
def test_device_bfs_max_depth_and_time_budget():
    res = _device(SMALL, INVS).run(max_depth=5)
    assert not res.exhausted
    assert res.depth == 5
    full = _device(SMALL, INVS).run()
    assert full.exhausted
    assert full.depth_counts[:6] == res.depth_counts[:6]


@pytest.mark.parametrize("engine", ["device", "sharded"])
def test_tiled_apply_keeps_the_host_checkers_counts(engine):
    """A depth-bounded run of each device engine at a chunk of 32 rows,
    where a chunk-step of the wider waves holds several tiles of
    ``HandleMessage`` (PR 56: a group's rows are built a tile a trip
    under the group's count) and the narrow ones hold none of most
    groups: per-depth distinct, generated and terminal counts equal the
    host checker's."""
    import jax

    from raft_tpu.parallel.sharded import ShardedBFS

    model = cached_model(SMALL)
    want = BFSChecker(
        model, invariants=INVS, symmetry=True, chunk=256).run(max_depth=14)
    kw = dict(invariants=INVS, symmetry=True, chunk=32,
              frontier_cap=1 << 10, seen_cap=1 << 12)
    if engine == "device":
        eng = DeviceBFS(model, journal_cap=1 << 12, **kw)
    else:
        eng = ShardedBFS(model, devices=jax.devices()[:2], **kw)
    got = eng.run(max_depth=14)
    assert want.violation is None and got.trace is None
    assert (got.distinct, got.total, got.terminal, got.depth_counts) == (
        want.distinct, want.total, want.terminal, want.depth_counts)
    assert max(got.depth_counts) > 2 * eng.chunk


def test_expand_rows_built_is_the_hand_count():
    """``expand_rows_built`` on the wave rows, ``stats`` and the summary
    (PR 56): the rows the apply pass's tiles built, beside
    ``expand_rows_budget``, ``sum(plan)`` a chunk-step. Wave 1 expands
    ``Init`` alone, so the hand count is each group's enabled lanes of
    that one state in whole tiles; every wave builds no more than it
    budgets, far less while the frontier is narrow, and the run's
    totals are the rows' sums."""
    import jax

    from raft_tpu.models.base import apply_tile

    eng = _device(SMALL, INVS, chunk=64, frontier_cap=1 << 10,
                  seen_cap=1 << 12, journal_cap=1 << 12)
    model, C = eng.model, eng.chunk
    res = eng.run(max_depth=8, collect_metrics=True)
    rows = res.metrics
    init = model.init_states()
    assert len(init) == 1
    valid = np.asarray(jax.vmap(model.guards1)(init)[0])
    counts = [int(valid[:, g.off:g.off + g.n].sum())
              for g in model.sparse_groups()]
    assert sum(counts) == rows[0]["generated"] > 0
    tiles = [apply_tile(eb, C) for eb in eng._plan]
    assert rows[0]["expand_rows_built"] == sum(
        -(-min(n, eb) // T) * T for n, eb, T in zip(counts, eng._plan, tiles))
    for w in rows:
        steps = -(-w["frontier"] // C)
        assert w["expand_rows_budget"] == steps * sum(eng._plan)
        assert w["generated"] <= w["expand_rows_built"] <= (
            w["expand_rows_budget"])
    # one state's wave builds a tile of each group that has a lane, not
    # the plan's 2.3 x VC rows
    assert rows[0]["expand_rows_built"] * 4 < rows[0]["expand_rows_budget"]
    for key in ("expand_rows_built", "expand_rows_budget"):
        assert res.stats[key] == sum(w[key] for w in rows)


def test_device_bfs_rejects_indivisible_chunk():
    with pytest.raises(AssertionError):
        _device(SMALL, INVS, chunk=768, frontier_cap=1 << 13)


@pytest.mark.slow
def test_device_bfs_capacity_growth():
    """Tiny initial caps; the run must grow all three buffers between
    waves and still produce exact counts (no states dropped)."""
    ref = _device(SMALL, INVS).run()
    grown = _device(
        SMALL,
        INVS,
        chunk=128,
        frontier_cap=256,
        seen_cap=512,
        journal_cap=512,
        max_frontier_cap=1 << 14,
        max_seen_cap=1 << 17,
        max_journal_cap=1 << 17,
    )
    res = grown.run()
    assert grown.FCAP > 256 and grown.JCAP > 512
    # the LSM seen-set grows by occupying levels, not by resizing SCAP
    assert grown._lsm.lanes() > 512
    assert res.distinct == ref.distinct
    assert res.depth_counts == ref.depth_counts
    assert res.total == ref.total
    assert res.terminal == ref.terminal


@pytest.mark.slow
def test_device_bfs_checkpoint_resume(tmp_path):
    """Split a run at a depth cap via checkpoint, resume in a fresh
    checker, and require the stitched result to equal a straight run —
    including a violation trace that crosses the checkpoint boundary."""
    import jax.numpy as jnp

    model = cached_model(SMALL)
    lay = model.layout

    def no_commit(states):
        ci = lay.get(states, "commitIndex")
        return jnp.all(ci == 0, axis=1)

    ck = str(tmp_path / "run.ckpt.npz")
    model.invariants["NoCommit"] = no_commit
    try:
        first = _device(SMALL, ("NoCommit",))
        r1 = first.run(max_depth=4, checkpoint_path=ck, checkpoint_every_s=0.0)
        assert r1.violation is None and not r1.exhausted
        second = _device(SMALL, ("NoCommit",))
        r2 = second.run(resume=ck)
        straight = _device(SMALL, ("NoCommit",)).run()
    finally:
        del model.invariants["NoCommit"]
    assert r2.violation is not None and straight.violation is not None
    assert r2.violation.depth == straight.violation.depth
    assert r2.distinct == straight.distinct
    assert r2.depth_counts == straight.depth_counts
    assert [a for a, _ in r2.trace] == [a for a, _ in straight.trace]


@pytest.mark.slow
def test_device_bfs_final_checkpoint_on_capped_exit(tmp_path):
    """A depth/budget-capped run with checkpoint_path must leave a
    resumable file even when the periodic timer never fired (default
    300 s cadence on a short run used to produce NO checkpoint at all)."""
    import os

    ck = str(tmp_path / "final.ckpt.npz")
    r1 = _device(SMALL, INVS).run(max_depth=3, checkpoint_path=ck)
    assert not r1.exhausted
    assert os.path.exists(ck)
    r2 = _device(SMALL, INVS).run(resume=ck)
    straight = _device(SMALL, INVS).run()
    assert r2.distinct == straight.distinct
    assert r2.depth_counts == straight.depth_counts


def test_device_bfs_checkpoint_invariant_mismatch(tmp_path):
    """Resuming with a different invariant set must be refused: states
    explored before the checkpoint were never evaluated against the new
    invariants, so the resumed run's verdict would be unsound."""
    ck = str(tmp_path / "inv.ckpt.npz")
    _device(SMALL, INVS).run(max_depth=3, checkpoint_path=ck)
    with pytest.raises(ValueError, match="checkpoint is for spec"):
        _device(SMALL, ("NoLogDivergence",)).run(resume=ck)


def test_device_bfs_checkpoint_spec_mismatch(tmp_path):
    other = RaftParams(
        n_servers=2, n_values=1, max_elections=1, max_restarts=0, msg_slots=16
    )
    ck = str(tmp_path / "run.ckpt.npz")
    _device(SMALL, INVS).run(max_depth=3, checkpoint_path=ck, checkpoint_every_s=0.0)
    with pytest.raises(ValueError, match="checkpoint is for spec"):
        _device(other, INVS).run(resume=ck)


# ------------------------------------------------ no scatter-add in a wave


def _device_wave():
    eng = _device(SMALL, INVS, chunk=256, frontier_cap=1 << 12,
                  seen_cap=1 << 14, journal_cap=1 << 14)
    return eng, "wave"


def _sharded_chunk():
    import jax

    from raft_tpu.parallel.sharded import ShardedBFS

    eng = ShardedBFS(
        cached_model(SMALL), invariants=INVS, symmetry=True,
        devices=jax.devices()[:2], chunk=256, frontier_cap=1 << 10,
        seen_cap=1 << 12,
    )
    return eng, "chunk"


def _traced(make):
    """(engine, program name, every equation) of the program a wave
    dispatches, each under the scopes its control flow was traced in
    (``conftest.eqns``), traced from the engine's own audit entry;
    nothing compiled."""
    import jax

    from conftest import eqns

    eng, name = make()
    (prog,) = [p for p in eng.audit_programs() if p["name"] == name]
    return eng, name, list(
        eqns(jax.make_jaxpr(prog["fn"])(*prog["args"]).jaxpr))


def _spec_body(stack: str) -> bool:
    """An equation of the spec lowering's own per-state body: the guard
    pass, ``expand/vmap()``, or a group's kernels under the group's
    scope and, since PR 56, its loop over the tiles of what the group
    keeps, ``expand/Restart/while/body/vmap()``."""
    head, _, rest = stack.partition("/")
    path = [p for p in rest.split("/") if p not in ("while", "body")]
    return head == "expand" and "vmap()" in path[:2]


@pytest.mark.parametrize(
    "make", [_device_wave, _sharded_chunk], ids=["device", "sharded"])
def test_wave_program_scatter_adds_nothing(make):
    """The engagement check of the compare-and-sum coverage counters
    (util.rank_counts): the program a wave dispatches, traced with K > 0
    actions, holds no ``scatter-add`` of the engine's own. A scatter-add
    is a serial pass on the TPU (PERF.md section 6, PR 27: 13 ms a
    chunk-step for the counters it used to make), so a feature that
    brings a ``segment_sum`` or an ``.at[].add`` into the wave fails
    here. The one place allowed is the spec lowering's own per-state
    body (``_spec_body``; ``log_len.at[i].add(1)``): the model's, not
    the engine's."""
    eng, name, eqns = _traced(make)
    assert eng.n_actions > 0
    stacks = {str(e.source_info.name_stack) for e in eqns}
    # the scopes this test reads are there, so an empty list means none
    assert any(s.startswith("emit/coverage") or s.startswith("expand")
               for s in stacks), sorted(stacks)[:20]
    adds = [
        str(e.source_info.name_stack) for e in eqns
        if e.primitive.name == "scatter-add"
        and not _spec_body(str(e.source_info.name_stack))
    ]
    assert not adds, f"scatter-add in the {name} program under: {adds}"


@pytest.mark.parametrize(
    "make", [_device_wave, _sharded_chunk], ids=["device", "sharded"])
def test_wave_program_expand_compacts_by_sorts(make):
    """The engagement check of ``expand``'s two stream compactions
    (PR 50): the valid lanes of a chunk (``engine.compact_chunk``) and
    the worklist's segmentation by group (``sparse_apply``) are each ONE
    sort of one int32 key. An ``.at[dst].set`` over the lanes is a
    serial pass on the TPU (4.6 ns a lane: PERF.md section 6, PR 50;
    seven of them a chunk-step were 11.6 % of ``raft3-wide``'s device
    time), so the program a wave dispatches holds no ``scatter`` under
    the ``expand`` scope outside the spec lowering's own per-state body
    (``_spec_body``), and exactly two ``sort`` equations there, of one
    operand each."""
    eng, name, eqns = _traced(make)
    assert eng._sparse
    own = [
        e for e in eqns
        if str(e.source_info.name_stack).startswith("expand")
        and not _spec_body(str(e.source_info.name_stack))
    ]
    # the scope is there, so an empty list below means none
    assert len(own) > 50, len(own)
    # each group's slice, row gather and parameter selects are under the
    # group's own scope (PR 51), opened outside its kernels' vmap
    groups = {g.name for g in eng.model.sparse_groups()}

    def second(es):
        return {(str(e.source_info.name_stack).split("/") + [""])[1]
                for e in es}

    assert groups == second(own) & groups
    assert groups == second(
        e for e in eqns if _spec_body(str(e.source_info.name_stack))
    ) - {"vmap()"}
    scatters = [
        (e.primitive.name, str(e.source_info.name_stack)) for e in own
        if e.primitive.name.startswith("scatter")]
    assert not scatters, f"scatter in the {name} program under: {scatters}"
    sorts = [e for e in own if e.primitive.name == "sort"]
    assert [len(e.invars) for e in sorts] == [1, 1], sorts
    assert all(str(v.aval.dtype) == "int32" for e in sorts for v in e.invars)


@pytest.mark.parametrize(
    "make", [_device_wave, _sharded_chunk], ids=["device", "sharded"])
def test_wave_program_builds_a_group_in_tiles_under_its_count(make):
    """The engagement check of the apply pass's loops (PR 56): a group's
    rows are built a tile a trip under a trip count that comes from the
    group's own count, so the program a wave dispatches holds ONE
    ``while`` a group under ``expand/<Group>``, the group's kernel is
    traced once, in that loop's body, at the tile's width
    (``models/base.py::apply_tile`` of the group's budget) and not at
    the budget, nothing concatenates the groups' blocks, and the last
    gather is one more ``while`` under the bare ``expand``. The group's
    scope is opened outside the loop and the vmap, so the benchmark's
    rule (``xplane.scope_path``) names an op of the loop's body by its
    group."""
    from benchmark import xplane
    from raft_tpu.models.base import apply_tile

    eng, name, eqns = _traced(make)
    groups = eng.model.sparse_groups()
    stack = lambda e: str(e.source_info.name_stack)
    whiles = collections.Counter(
        stack(e) for e in eqns
        if e.primitive.name == "while" and stack(e).startswith("expand"))
    assert whiles == {
        "expand": 1, **{f"expand/{g.name}": 1 for g in groups}}, whiles
    for g, eb in zip(groups, eng._plan):
        T = apply_tile(eb, eng.chunk)
        body = f"expand/{g.name}/while/body"
        kernel = [e for e in eqns if stack(e).startswith(body + "/vmap()")]
        assert kernel, g.name
        assert not [e for e in eqns
                    if stack(e).startswith(f"expand/{g.name}/vmap()")]
        # every row the loop's body touches is a tile's, never the budget's
        lead = {v.aval.shape[0] for e in kernel for v in e.outvars
                if v.aval.shape}
        assert T in lead and (eb == T or eb not in lead), (g.name, lead)
        (write,) = [e for e in eqns if stack(e) == body
                    and e.primitive.name == "dynamic_update_slice"]
        assert write.invars[1].aval.shape == (T, eng.W)
        assert xplane.scope_path(
            f"jit(_wave_step)/while/body/{body}/vmap()/select_n:"
        ) == ("expand", g.name)
    assert not [e for e in eqns if e.primitive.name == "concatenate"
                and stack(e).startswith("expand")
                and e.outvars[0].aval.shape[1:] == (eng.W,)
                and e.outvars[0].aval.shape[0] > eng.VC]


@pytest.mark.parametrize(
    "make", [_device_wave, _sharded_chunk], ids=["device", "sharded"])
def test_wave_program_emit_reads_only_rows_by_index(make):
    """The engagement check of ``emit``'s index work (PR 52). A 1-D
    gather or an ``.at[dst].set`` by a traced index is a serial pass on
    the TPU (7.1 and 4.6 ns a lane: PERF.md section 6, PR 52; three
    such gathers and the scatter were 26 ns for every lane of VC in
    every chunk-step), so the program a wave dispatches holds no
    ``scatter`` under ``emit`` (the invariants' ``scatter-min`` writes a
    static index of the violations vector), the action rank of a
    compacted lane is nowhere gathered out of the [C * A] guard grid (it
    rides in the compaction's sort key), and ``emit/append`` holds one
    sort of int32 lanes, by one key. In the device wave program
    ``emit/coverage`` gathers nothing, and ``emit/append`` gathers
    once, the survivors' [VC, W] rows, beside that sort: the survivors'
    lanes the key and their ``sel``, for the journal, the payload (the
    cheaper form by ``scripts/emit_micro.py --journal``); the sharded
    chunk program keeps its four reads through ``esel`` (ROADMAP S13)
    and its sort is ``util.dense_prefix_sel``, of one operand."""
    eng, name, eqns = _traced(make)
    assert eng.n_actions > 0

    def under(scope, prim):
        return [e for e in eqns if e.primitive.name == prim
                and str(e.source_info.name_stack).startswith(scope)]

    # the scopes are there, so an empty list below means none
    assert len(under("emit/append", "dynamic_update_slice")) >= 3
    assert not under("emit", "scatter"), name
    grid = eng.chunk * eng.A + 1  # the guard grid and its drop lane
    assert not [e for e in eqns if e.primitive.name == "gather"
                and e.invars[0].aval.shape == (grid,)], name
    (sort,) = [e for e in under("emit/append", "sort")
               if str(e.invars[0].aval.dtype) == "int32"]
    assert sort.params["num_keys"] == 1
    assert all(str(v.aval.dtype) == "int32" for v in sort.invars)
    if name == "wave":
        assert under("emit/coverage", "eq")
        assert not under("emit/coverage", "gather")
        (rows,) = under("emit/append", "gather")
        assert rows.invars[0].aval.shape == (eng.VC + 1, eng.W)
        assert len(sort.invars) == 2
    else:
        assert len(sort.invars) == 1


@pytest.mark.parametrize(
    "make,names",
    [(_device_wave, ["wave", "seen_merge"]), (_sharded_chunk, ["chunk"])],
    ids=["device", "sharded"])
def test_engine_declares_only_what_it_dispatches(make, names):
    """An engine has one way to run a wave: its audit surface lists the
    programs ``run()`` dispatches and no other. A second implementation
    of the wave (a host-driven stage mirror, a per-chunk twin) would have
    to declare its donations here to pass the lint, and then fails
    this."""
    eng, _ = make()
    assert [p["name"] for p in eng.audit_programs()] == names
