"""The ``--platform`` flag shared by the two CLIs (``python -m raft_tpu``
and ``raft_tpu sweep``): which JAX backend a run may use."""

from __future__ import annotations

import os
import sys

PLATFORMS = ("auto", "cpu", "tpu")


def add_platform_arg(ap) -> None:
    # no argparse ``choices``: the value may come from the environment,
    # which argparse does not validate, and a bad one must exit 64 (the
    # CLI's usage code), not argparse's 2 (which means "violation found")
    ap.add_argument(
        "--platform",
        default=os.environ.get("RAFT_TPU_PLATFORM", "auto"),
        metavar="{" + ",".join(PLATFORMS) + "}",
        help="JAX backend: auto leaves JAX's own choice (and JAX_PLATFORMS) "
        "alone; cpu and tpu mean exactly that backend, and a machine "
        "without it is an error (default: $RAFT_TPU_PLATFORM, else auto)",
    )


def select_platform(name: str) -> int:
    """Pin the JAX platform a CLI run asked for and return an exit code:
    0, 64 for a name that is not a platform, 5 where the named backend
    cannot start. ``cpu`` and ``tpu`` start the backend here, before any
    work, so a ``--platform tpu`` run can never land on a CPU."""
    if name not in PLATFORMS:
        print(
            f"error: --platform {name!r}: choose from {', '.join(PLATFORMS)}",
            file=sys.stderr,
        )
        return 64
    if name == "auto":
        return 0
    import jax

    jax.config.update("jax_platforms", name)
    try:
        got = jax.devices()[0].platform
    except RuntimeError as e:
        print(f"error: --platform {name}: {e}", file=sys.stderr)
        return 5
    if got != name:
        # only reachable in-process: a backend started before this call
        # keeps serving jax.devices() whatever jax_platforms says now
        print(
            f"error: --platform {name}: this process already runs on the "
            f"{got} backend",
            file=sys.stderr,
        )
        return 5
    return 0
