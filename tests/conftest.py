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


def eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from eqns(sub)


def scatter_kernels(model):
    """{binding name: scatter equations} of the per-action kernels that
    write through a scatter, from their jaxprs as the sparse apply
    calls them (one state row, scalar bindings); nothing compiled."""
    import numpy as np

    row = jax.ShapeDtypeStruct((model.layout.W,), np.int32)
    scalar = jax.ShapeDtypeStruct((), np.int32)
    groups = model.sparse_groups()
    assert sum(g.n for g in groups) == model.A
    found = {}
    for g in groups:
        closed = jax.make_jaxpr(model.kernel_for(g.name))(
            row, *[scalar] * g.params.shape[1])
        names = [e.primitive.name for e in eqns(closed.jaxpr)]
        assert "select_n" in names  # the walk sees in
        n = sum(name.startswith("scatter") for name in names)
        if n:
            found[g.name] = n
    return found


def lower_memo_canon(model):
    """Lowered text, with debug info, of the memoized canon of
    ``model`` over a 256-lane batch; nothing compiled or run."""
    import numpy as np

    from raft_tpu.checker.lsm import CanonMemo
    from raft_tpu.ops.symmetry import Canonicalizer

    canon = Canonicalizer.for_model(model, symmetry=True)
    return jax.jit(canon.fingerprints_memo).lower(
        jax.ShapeDtypeStruct((256, model.layout.W), np.int32),
        jax.ShapeDtypeStruct((256,), bool),
        CanonMemo(1 << 8).reset(),
    ).as_text(debug_info=True)
