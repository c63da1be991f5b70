"""The device programs of the two device engines, pinned.

``checker/engine.py`` holds what the three BFS engines say and do alike;
two of its functions (``expand_chunk``, ``compact_chunk``) are traced
into ``DeviceBFS``'s wave program and ``ShardedBFS``'s chunk program.
A plain function called under the caller's ``obs.stage`` scope adds no
scope and no equation, so neither program may change: this file holds
``(equations, conftest.jaxpr_digest)`` of every entry of both engines'
``audit_programs()`` (the production jit objects and their abstract
arguments). PR 46 pinned them at PR 45's tree, ``8ace3e6``, computed
there on a ``git archive`` copy before the functions were lifted out;
PR 50 re-pinned every wave and chunk program on purpose: the two stream
compactions of ``expand`` became sorts of one int32 key (31 equations
fewer in ``raft``'s wave, 11 in its dense arm, where only
``compact_chunk``'s changed), every successor row, ``sel`` and
fingerprint bit-equal to that tree's (``tests/test_expand_compaction.py``'s
``_reference_*``; ``scripts/stage_diff.py`` against the parent). PR 52
re-pinned them again, on purpose: the compaction's key carries the
lane's action rank, so the rank's gather through ``sel`` left both
programs, ``util.dense_prefix_sel`` is a sort of one int32 key, and
the wave program's survivors' lanes and journal blocks come out of one
such sort with ``sel`` its payload (eleven equations fewer in every
wave program, seven in every chunk program); every row, journal entry,
fingerprint, count and coverage cell bit-equal to PR 51's tree
(``tests/test_emit_sorts.py``'s ``_reference_*``; ``stage_diff.py``
against the parent). PR 54 re-pinned them once more, on purpose: the
canon stage's in-chunk dedup (``ops/symmetry.py``
``fingerprints_by_raw_view``) hands a fingerprint to the first lane of
each distinct raw view and masks every other lane, so its fill gather,
``rank``'s ``cumsum``, ``argsort``'s payload sort and the loop's index
gather left both programs and one sort of one int32 key came (eleven
equations fewer in every wave and chunk program); which lane is new, and
every row, journal entry, fingerprint appended, count and coverage cell,
bit-equal to PR 53's tree (``tests/test_symmetry_v3.py``'s ``first_new``
property; ``stage_diff.py``'s wave stage against the parent's dump).
PR 56 re-pinned them, on purpose: ``sparse_apply`` builds each group's
rows a tile a trip under a loop whose trip count is the group's own
count, into one block (no ``concatenate``), gathers the compacted block
in tiles of the worklist under its count, and counts the rows it built;
the device engine's stats vector has an eleventh lane for that count
(which is the four equations the dense arm's wave gained; the dense
chunk program is as it was). A loop's body adds its own slice, gather
and ``dynamic_update_slice`` and the loop's counter a group: 205 to 345
equations more in a wave program, five fewer than that in a chunk
program, which drops the count. Every row of every in-budget worklist
lane, the overflow bits and every count bit-equal to PR 55's tree
(``tests/test_expand_compaction.py``'s ``_reference_sparse_apply``;
``stage_diff.py``'s stages against the parent's dump). PR 57 re-pinned
the seven wave programs, on purpose, and no chunk program: the device
engine's stats vector has a twelfth lane, the chunk-steps whose dedup
stage searched the seen run (``dedup_search_steps``: one compare of the
step's searched query lanes with 0, its convert, a third operand of the
dedup stage's stack, and the slices and the add that fold it, eleven
equations more in every wave program); lanes 0 to 10, every row and
every count as they were (``stage_diff.py`` on the chip, ALL STAGES
EQUAL; the lowered text of ``raft3-wide``'s and ``pull3-full``'s wave
programs against the parent's differs in those lines alone, PERF.md
section 6).
Nothing is compiled or run.

A PR that means to change a program re-pins its digest on purpose, says
so, and checks the benchmark's cells; a PR that does not must leave
every line here as it is. The models are ``tests/test_expand_sparse.py``
``FAMILIES`` (the lowerings behind the benchmark's cells at test size),
at that file's engine capacities; ``raft-dense`` is ``raft`` behind its
``DenseShim``, the dense arm of the same two functions.
"""

import jax
import pytest

from conftest import jaxpr_digest
from raft_tpu.checker.device_bfs import DeviceBFS
from raft_tpu.parallel.sharded import ShardedBFS
from test_expand_sparse import _HEAVY, DenseShim, FAMILIES

KW = dict(symmetry=True, chunk=128, frontier_cap=1 << 12, seen_cap=1 << 15)
ENGINES = {"device": DeviceBFS, "sharded": ShardedBFS}

# the seen merge has no model in it: one digest for every family
SEEN_MERGE = (12, "19ac660b935d83db")

# {family: {engine: {program: (equations, digest)}}} at PR 56's tree,
# the wave programs at PR 57's
PARENT_PROGRAMS = {
    "raft": {
        "device": {"wave": (5092, "01c2e2601ae85420"),
                   "seen_merge": SEEN_MERGE},
        "sharded": {"chunk": (5473, "2e2723c1286523e4")}},
    "raft-dense": {
        "device": {"wave": (3622, "4befee39e8dab41f"),
                   "seen_merge": SEEN_MERGE},
        "sharded": {"chunk": (4004, "0543045755cbcf6f")}},
    "pull_raft": {
        "device": {"wave": (5426, "89c616504dc1e531"),
                   "seen_merge": SEEN_MERGE},
        "sharded": {"chunk": (5807, "a923a619c5d67983")}},
    "kraft": {
        "device": {"wave": (6765, "e08b738292c434b5"),
                   "seen_merge": SEEN_MERGE},
        "sharded": {"chunk": (7146, "958c32a38d91af06")}},
    "joint_raft": {
        "device": {"wave": (11210, "f02504aecf396e2e"),
                   "seen_merge": SEEN_MERGE},
        "sharded": {"chunk": (11591, "b134551dbdaa8f1a")}},
    "kraft_reconfig": {
        "device": {"wave": (15218, "d38aac2e96b2eea1"),
                   "seen_merge": SEEN_MERGE},
        "sharded": {"chunk": (15599, "7c18ccfab891ea11")}},
    "reconfig_raft": {
        "device": {"wave": (10863, "7203cf21b208655b"),
                   "seen_merge": SEEN_MERGE},
        "sharded": {"chunk": (11244, "b2a6225c29a55675")}},
}


def _model(family):
    if family == "raft-dense":
        return DenseShim(FAMILIES["raft"]())
    return FAMILIES[family]()


@pytest.mark.parametrize("engine", sorted(ENGINES))
@pytest.mark.parametrize("family", [
    pytest.param(f, marks=pytest.mark.slow) if f in _HEAVY else f
    for f in PARENT_PROGRAMS])
def test_device_programs_are_the_parents(family, engine):
    model = _model(family)
    eng = ENGINES[engine](
        model, invariants=tuple(list(model.invariants)[:1]), **KW)
    assert eng._sparse == (family != "raft-dense")
    found = {
        entry["name"]: jaxpr_digest(
            jax.make_jaxpr(entry["fn"])(*entry["args"]))
        for entry in eng.audit_programs()}
    assert found == PARENT_PROGRAMS[family][engine]


def test_stages_one_and_two_are_written_once():
    """The valid lanes' compaction (one sort of a key that holds the
    lane's index over its action rank) is written in one place under ``raft_tpu/`` (``engine.compact_chunk``),
    and both device programs trace it."""
    import inspect
    import pathlib

    import raft_tpu
    from raft_tpu.checker import engine

    needle = "valid.reshape(-1), flat, (C * A) << bits))[:VC]"
    root = pathlib.Path(raft_tpu.__file__).parent
    assert [p.relative_to(root).as_posix() for p in sorted(root.rglob("*.py"))
            if needle in p.read_text()] == ["checker/engine.py"]
    assert needle in inspect.getsource(engine.compact_chunk)
    for fn in (DeviceBFS._st_expand, ShardedBFS._cs_pre):
        src = inspect.getsource(fn)
        assert "expand_chunk(" in src and "compact_chunk(" in src


def test_one_definition_each():
    """What the engines say alike has one author under ``raft_tpu/``:
    the row's derived keys are keys of a dict literal in
    ``checker/engine.py`` alone (``obs/events.py`` holds the schema's
    tuples, not dicts), the ``hashv=`` fragment of an ident is formatted
    there alone, and the fleet's queue arm and its supervised run are
    defined once, there, for both device engines."""
    import ast
    import pathlib

    import raft_tpu
    from raft_tpu.checker import engine

    root = pathlib.Path(raft_tpu.__file__).parent
    keys = {"dedup_hit_rate", "enabled_density", "host_s"}
    literal_in, hashv_in, defs_in = {}, set(), {}
    for path in sorted(root.rglob("*.py")):
        rel = path.relative_to(root).as_posix()
        text = path.read_text()
        if "/hashv={" in text:
            hashv_in.add(rel)
        for node in ast.walk(ast.parse(text)):
            if isinstance(node, ast.Dict):
                for k in node.keys:
                    if isinstance(k, ast.Constant) and k.value in keys:
                        literal_in.setdefault(k.value, set()).add(rel)
            elif (isinstance(node, ast.FunctionDef)
                  and node.name == "_run_supervised"):
                defs_in.setdefault(node.name, set()).add(rel)
    assert literal_in == {k: {"checker/engine.py"} for k in keys}
    assert hashv_in == {"checker/engine.py"}
    assert defs_in == {"_run_supervised": {"checker/engine.py"}}
    for cls in (DeviceBFS, ShardedBFS):
        assert cls.run_fleet is engine.FleetQueue.run_fleet
        assert cls._run_supervised is engine.FleetQueue._run_supervised
