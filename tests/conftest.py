"""Test env: force CPU with 8 virtual devices so multi-chip sharding code
paths are exercised without TPU hardware.

The suite pins counts, not speed, and is sized for the CPU: the platform
is forced here (after importing jax, before any computation) so that it
runs the same way on a machine that has a chip. XLA_FLAGS must be set
before the backend starts.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def collect_states(oracle, max_depth, cap=150):
    """Deterministic sample of reachable FULL states (dedup on full state),
    via the oracle's successor function. Shared by the differential tests."""
    seen = {}
    frontier = [oracle.init_state()]
    seen[oracle.serialize_full(frontier[0])] = frontier[0]
    for _ in range(max_depth):
        nxt = []
        for st in frontier:
            for _label, s2 in oracle.successors(st):
                k = oracle.serialize_full(s2)
                if k not in seen:
                    seen[k] = s2
                    nxt.append(s2)
            if len(seen) >= cap:
                break
        frontier = nxt
        if len(seen) >= cap:
            break
    return list(seen.values())


def eqns(jaxpr, under=None):
    """Every equation of a jaxpr and of the jaxprs nested in it. An
    equation inside control flow or a call that was traced under a named
    scope carries that scope's path in front of its own name stack, as
    the lowering composes it: a group's kernels under ``sparse_apply``'s
    loop read ``expand/Restart/while/body/vmap()``, where the body's own
    jaxpr says ``vmap()`` alone. Control flow under no scope (a wave
    program's loop over its chunks) adds nothing, so a stage's path
    starts a stack as it does in the program's own jaxpr."""
    for eqn in jaxpr.eqns:
        if under is not None:
            info = eqn.source_info
            eqn = eqn.replace(source_info=info.replace(
                name_stack=under + info.name_stack))
        yield eqn
        stack = eqn.source_info.name_stack
        for key, val in eqn.params.items():
            subs = jax.core.jaxprs_in_params({key: val})
            for i, sub in enumerate(subs):
                inner = None
                if str(stack):
                    inner = stack.extend(eqn.primitive.name)
                    if eqn.primitive.name == "while":
                        inner = inner.extend(key.partition("_")[0])
                    elif eqn.primitive.name == "cond":
                        inner = inner.extend(f"branch_{i}_fun")
                yield from eqns(sub, inner)


def _kernel_primitives(model, batch=None):
    """(binding name, primitive names) of each per-action kernel, from
    its jaxpr as the sparse apply calls it: one state row and scalar
    bindings, or with ``batch`` under the worklist's vmap (where a read
    by a traced index is a gather); nothing compiled."""
    import numpy as np

    lead = () if batch is None else (batch,)
    row = jax.ShapeDtypeStruct(lead + (model.layout.W,), np.int32)
    arg = jax.ShapeDtypeStruct(lead, np.int32)
    groups = model.sparse_groups()
    assert sum(g.n for g in groups) == model.A
    for g in groups:
        kern = model.kernel_for(g.name)
        closed = jax.make_jaxpr(kern if batch is None else jax.vmap(kern))(
            row, *[arg] * g.params.shape[1])
        names = [e.primitive.name for e in eqns(closed.jaxpr)]
        assert "select_n" in names  # the walk sees in
        yield g.name, names


def scatter_kernels(model):
    """{binding name: scatter equations} of the per-action kernels that
    write through a scatter."""
    found = {}
    for kernel, names in _kernel_primitives(model):
        n = sum(name.startswith("scatter") for name in names)
        if n:
            found[kernel] = n
    return found


def gather_kernels(model, batch=8):
    """{binding name: gather equations} of the per-action kernels that
    read through a gather under the worklist's vmap, and under "guards"
    those of the guard pass as the engines run it, ``vmap(guards1)``
    over a chunk (a read by the inner vmap's iota is a gather there all
    the same)."""
    import numpy as np

    found = {}
    for kernel, names in _kernel_primitives(model, batch):
        n = names.count("gather")
        if n:
            found[kernel] = n
    closed = jax.make_jaxpr(jax.vmap(model.guards1))(
        jax.ShapeDtypeStruct((batch, model.layout.W), np.int32))
    n = [e.primitive.name for e in eqns(closed.jaxpr)].count("gather")
    if n:
        found["guards"] = n
    return found


def scope_paths(lowered):
    """The stage scope paths (``benchmark.xplane.scope_path``: what a
    trace's reduction names an op by, control flow and transforms left
    out) of every name stack in a lowered text with debug info."""
    import re

    from benchmark.xplane import scope_path

    return {scope_path(stack)
            for stack in re.findall(r'"(jit\([^"]*)"', lowered)}


def jaxpr_digest(closed):
    """(equations, sha256 prefix) of a closed jaxpr's equations, nested
    ones included, in order: each one's primitive name and the dtypes
    and shapes of what it puts out."""
    import hashlib

    digest, n = hashlib.sha256(), 0
    for eqn in eqns(closed.jaxpr):
        outs = ",".join(f"{v.aval.dtype}{list(v.aval.shape)}"
                        for v in eqn.outvars)
        digest.update(f"{eqn.primitive.name}:{outs};".encode())
        n += 1
    return n, digest.hexdigest()[:16]


def indexed_ops(lowered):
    """[(kind, operand dims, name stack)] of every gather and scatter in
    a lowered text with debug info: ``kind`` is "gather" or "scatter",
    the dims are those of the array read from or written into (one
    entry: a 1-D gather or scatter, a serial pass over its lanes on the
    chip when the index is traced), the name stack the op's own (bare,
    "gather", inside a function the lowering outlined)."""
    import re

    stacks = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', lowered, re.M))
    found = []
    for kind in ("gather", "scatter"):
        # a scatter's signature follows its update region's closing brace
        for dims, loc in re.findall(
                r'"stablehlo\.%s"\([^\n]*?(?:\n[^\n]*?)*?: \(tensor<([^>]*)>'
                r'[^\n]*loc\((#loc\d+)\)' % kind, lowered):
            found.append((kind, dims.split("x")[:-1], stacks.get(loc, "")))
    return found


def first_lane_masked(canon, rows, valid):
    """What a canon's ``fingerprints_dedup`` returns for ``rows`` under
    ``valid``, worked out on the host from the plain per-lane entry:
    ``(fps, n_dup)``, the lane's own ``canon.fingerprints`` on the first
    valid lane of every distinct raw view, U64_MAX on every other lane,
    and the valid lanes that are not such a first lane."""
    import numpy as np

    from raft_tpu.ops.hashing import U64_MAX

    valid = np.asarray(valid, bool)
    raw = np.asarray(canon.raw_fingerprints(rows))
    lanes = np.flatnonzero(valid)
    _u, first = np.unique(raw[lanes], return_index=True)
    head = np.zeros(len(raw), bool)
    head[lanes[first]] = True
    fps = np.where(head, np.asarray(canon.fingerprints(rows)),
                   np.uint64(U64_MAX))
    return fps, int(valid.sum() - head.sum())


def lower_dedup_canon(model):
    """Lowered text, with debug info, of the engines' canon (in-chunk
    dedup, then the tiers) of ``model`` over a 256-lane batch; nothing
    compiled or run."""
    import numpy as np

    from raft_tpu.ops.symmetry import Canonicalizer

    canon = Canonicalizer.for_model(model, symmetry=True)
    return jax.jit(canon.fingerprints_dedup).lower(
        jax.ShapeDtypeStruct((256, model.layout.W), np.int32),
        jax.ShapeDtypeStruct((256,), bool),
    ).as_text(debug_info=True)
