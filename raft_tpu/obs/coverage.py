"""TLC-style action coverage: table rendering and dead-action detection.

The input everywhere is the cumulative per-action counter block the
engines accumulate on device — ``actions[rank] = [enabled, fired,
new_distinct]`` with ``rank`` indexing the model's ``ACTION_NAMES``
(the Next-disjunct order). The device engines make each chunk-step's
counts by comparing the lanes' ranks against 0..K-1 and summing the
one-hot in int32 (``checker/util.py`` ``rank_onehot``/``rank_counts``;
no scatter-add), then add them into the cumulative i64 block; the host
engine counts with ``np.bincount``, and the counts are equal:

  enabled       (state, action) pairs where the disjunct's guard held
                on a live frontier state — i.e. at least one candidate
                of that rank was valid for the state
  fired         successor generations attributed to the rank (every
                valid candidate lane, pre-dedup)
  new_distinct  distinct states the rank contributed first (post-dedup,
                post-symmetry; first-writer-wins under TLC tie order)

TLC's ``-coverage`` prints fired/distinct per action; ``enabled`` is
the extra column our lowering needs, because a dead disjunct whose
guard also never holds is a *model-scale* artifact, while a disjunct
that is enabled but never fires is a *lowering bug*.

Dependency-free (no jax/numpy): the CLI table, scripts/obs_report.py
and scripts/check_metrics_schema.py all render from plain lists.
"""

from __future__ import annotations

COLUMNS = ("enabled", "fired", "new distinct")


def _rows(action_names, actions) -> list[tuple[str, int, int, int]]:
    names = list(action_names)
    out = []
    for r, row in enumerate(actions):
        name = names[r] if r < len(names) else f"action[{r}]"
        e, f, n = (int(row[0]), int(row[1]), int(row[2]))
        out.append((name, e, f, n))
    return out


def dead_actions(action_names, actions) -> list[str]:
    """Names of actions that never fired (fired == 0), in rank order."""
    return [name for name, _e, f, _n in _rows(action_names, actions) if f == 0]


def render_coverage_table(action_names, actions, title: str | None = None) -> str:
    """The end-of-run ``--coverage`` table (TLC -coverage analog), one
    row per Next disjunct, with an explicit WARNING line per action
    that never fired."""
    rows = _rows(action_names, actions)
    lines = [title or "Action coverage (cumulative over the run):"]
    if not rows:
        lines.append("  (no per-action coverage recorded)")
        return "\n".join(lines)
    wname = max(len("action"), max(len(r[0]) for r in rows))
    head = f"  {'action':<{wname}}"
    for c in COLUMNS:
        head += f"  {c:>12}"
    lines.append(head)
    for name, e, f, n in rows:
        lines.append(f"  {name:<{wname}}  {e:>12}  {f:>12}  {n:>12}")
    for name in dead_actions(action_names, actions):
        lines.append(f"WARNING: action {name} never fired")
    return "\n".join(lines)
