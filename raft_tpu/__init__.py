"""raft_tpu — a TPU-native explicit-state model checker for the Raft TLA+ suite.

This package re-provides, TPU-first, the full model-checking capability that
the reference repo (Vanlightly/raft-tlaplus) obtains from TLC: per-variant
`Next` relations hand-lowered to vectorized JAX transition kernels over a
packed fixed-width state encoding, BFS frontier expansion via `vmap`,
VIEW/SYMMETRY-aware 64-bit fingerprint dedup, batched invariant predicates,
counterexample trace reconstruction, and frontier sharding across a
`jax.sharding.Mesh`.

Layout of the package:
  models/    per-variant spec lowerings (state layout + action kernels +
             invariants), e.g. models/raft.py for
             reference specifications/standard-raft/Raft.tla
  ops/       spec-agnostic device ops: bit packing, message-bag ops,
             symmetry canonicalization, 64-bit fingerprint hashing
  checker/   BFS driver, dedup, trace reconstruction, simulation mode
  parallel/  sharded-frontier expansion over a device mesh (ICI all-to-all)
  oracle/    independent pure-Python interpreters of the TLA+ semantics,
             used for differential testing (TLC itself is not vendored)
  utils/     TLC `.cfg` parser, pretty printers
"""

import os
import re

import jax

# 64-bit fingerprints (TLC uses 64-bit state fingerprints; parity requires
# the same collision budget). Must run before any jax arrays are created.
jax.config.update("jax_enable_x64", True)

def enable_compcache() -> None:
    """Persistent compilation cache, placeable from outside.

    A BFS run compiles one wave program per seen-ladder size plus the
    LSM merge programs — a dozen shapes before the first wave — so a
    cold process pays all of them and a warm one reads them back. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already taken the
    directory from it and none is set here. Otherwise the cache lives
    at the fixed ``<checkout>/.jax_cache`` — never a temporary name: a
    cache that moves between runs is never found again. Either way it
    takes every program however fast it compiled (a threshold on
    compile time makes the set of cached programs depend on the
    machine's load). The CPU backend gets no default cache: XLA:CPU's
    loader logs two error lines per entry it reads back (it rejects its
    own ``+prefer-no-gather``/``+prefer-no-scatter`` tuning flags as
    features the host lacks), which buries a run's stderr. Called once
    the backend is known, from Canonicalizer.for_model/__init__,
    Simulator and LivenessChecker — the chokepoints every checker and
    simulation path goes through — so the process's compile counters
    (obs/compiles.py) are switched on here too, cache or no cache.

    The cache key takes the programs' metadata in: the stage scopes of
    obs/trace.py are metadata and nothing else, and a key that strips it
    (JAX's default) would serve a profile the op names of whichever
    commit filled the cache — a trace must never show another commit's
    names. The metadata names source files, so the checkout's own path
    is cut from them: the same commit in another directory (a cache
    placed from outside, shared by two checkouts) still hits."""
    from .obs.compiles import COMPILES

    COMPILES.install()
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        if jax.default_backend() == "cpu":
            return
        jax.config.update(
            "jax_compilation_cache_dir", os.path.join(checkout, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update(
        "jax_hlo_source_file_canonicalization_regex",
        "^" + re.escape(checkout + os.sep))


__version__ = "0.1.0"
