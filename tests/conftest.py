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
