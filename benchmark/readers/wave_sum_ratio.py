"""{"kind": "wave_sum_ratio", "num": ..., "den": ...}: one field of the
traced verdict's wave rows, summed, over another."""


def read(spec, ctx):
    rows = ctx["waves"]
    if not rows or any(spec["num"] not in r or spec["den"] not in r
                       for r in rows):
        return None
    den = sum(r[spec["den"]] for r in rows)
    return sum(r[spec["num"]] for r in rows) / den if den else None
