"""TLC-style live progress line on stderr.

TLC's killer usability feature is the periodic progress report ("N
states generated, M distinct states, queue depth D") — the reference
workflow assumes you watch it for hours. This renderer is the
equivalent, fed from the telemetry wave-event stream:

    Progress (depth 7): 1.2M generated, 310k distinct, 2,648/s, dup 53%

Throttled by wall clock (``every_s``); the first wave always prints so a
short run is not silent. Stall events render immediately — a watchdog
warning you cannot see is worthless.
"""

from __future__ import annotations

import sys
import time


def format_count(n) -> str:
    """Humanized count: 1234 -> '1,234', 310000 -> '310k', 1.2e6 -> '1.2M'."""
    n = int(n)
    if n >= 1_000_000_000:
        return f"{n / 1e9:.1f}B"
    if n >= 1_000_000:
        return f"{n / 1e6:.1f}M"
    if n >= 10_000:
        return f"{n / 1e3:.0f}k"
    return f"{n:,}"


class ProgressRenderer:
    """Wave-event listener rendering the TLC-style progress line."""

    # wave-event keys the renderer reads; the tier-1 smoke test asserts
    # these stay inside events.WAVE_KEYS so the renderer and the schema
    # cannot drift apart
    CONSUMES = (
        "depth", "generated_total", "distinct", "distinct_per_s",
        "canon_dup_rate", "hbm_frac", "hbm_bytes",
        "generated", "canon_tier3_local", "canon_tier3_full",
    )

    def __init__(self, every_s: float = 10.0, stream=None):
        self.every_s = float(every_s)
        self.stream = stream if stream is not None else sys.stderr
        self._last: float | None = None

    def render_wave(self, ev: dict) -> str:
        line = (
            f"Progress (depth {ev['depth']}): "
            f"{format_count(ev['generated_total'])} generated, "
            f"{format_count(ev['distinct'])} distinct, "
            f"{ev['distinct_per_s']:,.0f}/s, "
            f"dup {ev['canon_dup_rate']:.0%}"
        )
        # observatory gauges render only when present and non-zero so
        # the base line (pinned by tests) is unchanged on engines /
        # waves that don't carry them
        tier3 = (ev.get("canon_tier3_local") or 0) + (
            ev.get("canon_tier3_full") or 0)
        if tier3:
            line += f", tier3 {tier3 / max(1, ev['generated']):.0%}"
        # the device's memory by its allocator; the geometry's plan
        # where the device reports nothing (a CPU dry run)
        if ev.get("hbm_frac"):
            gauge = "plan" if ev.get("hbm_bytes") is None else "hbm"
            line += f", {gauge} {ev['hbm_frac']:.0%}"
        return line

    def loaded(self, rec: dict) -> None:
        """One line for a program that took long to load (a ``load``
        record of obs/compiles.py): which, how, and the span it was
        loaded in."""
        cause = rec["cause"]
        where = [cause["top"] or "outside a run"]
        if cause["depth"] is not None:
            where[0] += f" {cause['depth']}"
        if cause["bracket"]:
            where.append(cause["bracket"])
        how = "read from the cache" if rec["cache_hit"] else "compiled"
        print(
            f"loading {rec['fun_name']}: {how} in {rec['seconds']:.1f} s "
            f"({', '.join(where)})",
            file=self.stream, flush=True,
        )

    def __call__(self, ev: dict) -> None:
        etype = ev.get("event")
        if etype == "stall":
            print(
                f"Warning: wave {ev['wave']} (depth {ev['depth']}) took "
                f"{ev['wave_s']:.1f}s — {ev['factor']:.1f}x the rolling "
                f"median of {ev['median_wave_s']:.1f}s",
                file=self.stream, flush=True,
            )
            return
        if etype == "summary":
            print(
                f"Finished (depth {ev['depth']}): "
                f"{format_count(ev['total'])} generated, "
                f"{format_count(ev['distinct'])} distinct, "
                f"{ev['terminal']} terminal, {ev['seconds']:.1f}s "
                f"({ev['exit_cause']})",
                file=self.stream, flush=True,
            )
            return
        if etype == "retry":
            print(
                f"Recovery: attempt {ev['attempt']} failed ({ev['cause']}); "
                f"retrying in {ev['backoff_s']:.1f}s"
                + ("" if ev.get("growth") in (None, "-")
                   else f", growing {ev['growth']}"),
                file=self.stream, flush=True,
            )
            return
        if etype == "resume":
            print(
                f"Resumed from {ev['path']} (generation "
                f"{ev['generation']}) at depth {ev['depth']}, "
                f"{format_count(ev['distinct'])} distinct",
                file=self.stream, flush=True,
            )
            return
        if etype == "ckpt_generation":
            print(
                f"Warning: {len(ev['skipped'])} corrupt checkpoint "
                f"generation(s) skipped; loaded generation "
                f"{ev['generation']} of {ev['path']}",
                file=self.stream, flush=True,
            )
            return
        if etype == "preempt":
            print(
                f"Preempted ({ev['signame']}): checkpoint written to "
                f"{ev['checkpoint']} at depth {ev['depth']}",
                file=self.stream, flush=True,
            )
            return
        if etype != "wave":
            return
        now = time.monotonic()
        if self._last is not None and now - self._last < self.every_s:
            return
        self._last = now
        print(self.render_wave(ev), file=self.stream, flush=True)
