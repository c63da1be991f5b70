"""Static analysis: the kernel contract auditor behind ``raft_tpu lint``.

The survey's north star — every variant's ``Next`` relation hand-lowered
to fused, donated, fixed-signature device programs — rests on contracts
no type system sees: wave programs must alias their capacity-shaped
carries, deep runs must stay on a closed, declared set of signatures,
guard passes must write no W-wide successor rows, wave loops must stay
zero-extra-sync, and fleet-packable guards must reach dynamic constants
through the ``_cv`` lane indirection. Each pass in this package proves
one of those contracts across the model registry WITHOUT executing a
wave, and anchors every violation to a ``file:line`` so a refactor that
breaks a contract is named before it is benchmarked.

Passes (see ``cli.PASSES``):

  donation        input-output aliasing of every wave/stage/merge jit
  signatures      retrace-closure of the geometry state machine
  guard-purity    DCE-derived guard passes write no W-wide rows
  hidden-sync     no device syncs inside chunk/wave loops
  lane-discipline ``_cv`` constant reads + ACTION_NAMES lock-step
  events-drift    metrics schema rules vs DECLARED_EVENTS

Entry point: ``python -m raft_tpu lint [--strict] [--json] [--pass NAME]``
(exit 0 clean, 3 findings under --strict, 64 usage — the repo's stable
exit-code contract). ``--mutate NAME`` applies one seeded contract
violation and re-runs the targeted pass: the self-test that proves each
auditor actually fires.
"""

from .findings import Finding, PassResult  # noqa: F401
