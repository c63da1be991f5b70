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
import time

_T_FIRST = time.perf_counter()  # the program's first line


def _process_age() -> float | None:
    """Wall seconds since this process started: the machine's uptime
    less the process's start time, which ``/proc/self/stat`` gives in
    clock ticks since boot (field 22; good to 10 ms. ``/proc/stat``'s
    ``btime`` is whole seconds and will not do). None where the platform
    has no ``/proc``."""
    try:
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        with open("/proc/self/stat") as f:
            # the command's name, field 2, may hold spaces and brackets
            start = int(f.read().rsplit(")", 1)[1].split()[19])
        return uptime - start / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


# The process's record of its set-up, in seconds (obs/trace.py
# ``setup_phase`` adds to it; obs/compiles.py ``run_stats`` puts it on
# every result as ``setup_<phase>_s``). ``pre`` is what no bracket can
# give: process start to the line above — the interpreter and whatever
# the caller imported and started first (in the benchmark ``import
# jax``, the backend and its own imports; in the CLI almost nothing).
# ``import`` is this file's own imports, stamped below.
SETUP_S: dict = {
    "pre": _process_age(), "import": 0.0, "backend": 0.0, "cfg": 0.0,
    "model": 0.0, "engine": 0.0,
}

import re  # noqa: E402

import jax  # noqa: E402

# 64-bit fingerprints (TLC uses 64-bit state fingerprints; parity requires
# the same collision budget). Must run before any jax arrays are created.
jax.config.update("jax_enable_x64", True)

SETUP_S["import"] = time.perf_counter() - _T_FIRST


def start_backend() -> None:
    """The first touch of the backend, as the set-up phase ``backend``:
    once a process, from ``enable_compcache`` or, earlier, from a caller
    that wants its profiler session open before the cfg is read (the
    CLI). Reads ~0 where the caller had started the backend already (the
    benchmark), and says so by being ~0. The process's compile records
    (obs/compiles.py) are switched on here, before the first program."""
    from .obs.compiles import COMPILES
    from .obs.trace import setup_phase

    if COMPILES.install():
        with setup_phase("backend"):
            jax.devices()


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
    simulation path goes through — so the backend's start is bracketed
    and the process's compile records are switched on from here too
    (``start_backend``), cache or no cache.

    The cache key takes the programs' metadata in: the stage scopes of
    obs/trace.py are metadata and nothing else, and a key that strips it
    (JAX's default) would serve a profile the op names of whichever
    commit filled the cache — a trace must never show another commit's
    names. The metadata names source files, so the checkout's own path
    is cut from them: the same commit in another directory (a cache
    placed from outside, shared by two checkouts) still hits."""
    start_backend()
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
