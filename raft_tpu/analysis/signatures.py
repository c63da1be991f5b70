"""Signature-closure auditor: prove a deep run dispatches only the
program signatures its engine declares — the retrace-cliff class,
symbolically.

Round 5's depth-32 wave-time cliff was one mid-run compile: a seen
merge whose target outgrew the concat total left a non-ladder-size run,
and the next wave retraced the whole wave program at a shape off the
seen ladder (most of that wave's wall time). The engine declares the
set a run can dispatch, ``DeviceBFS.signature_inventory()``; this pass
independently recomputes the REACHABLE signature set from the geometry
primitives and proves the two are equal:

  * ladder well-formedness — ``_seen_sizes`` strictly increasing powers
    of two ending at TOPSZ (= pow2 ceiling of max_seen_cap);
  * dispatch closure — ``_seen_size_for`` (the runtime target chooser)
    probed at every ladder boundary +/-1 must return exactly the
    first-size-at-least member the ladder implies, always inside the
    declared wave set, and overflow past TOPSZ must raise;
  * merge closure — the declared merge keys must cover every
    (size, target >= size) pair at the shape of the wave's fingerprint
    buffer (FCAP lanes: what the wave program hands the merge);
  * pad-up proof — ``eval_shape`` of every merge spec body returns
    EXACTLY ``(target,)`` u64 (the shape invariant whose violation
    caused the cliff);
  * growth chain — ``next_cap`` frontier/journal growth from the
    current capacity terminates at the cap ceiling in finitely many
    chunk-aligned steps (growth retraces are bounded);
  * sharded arity — RunLSM pre-creates its full ladder, so the chunk
    program's run-tuple arity can never change mid-run;
  * fleet grouping — FLEET_DYN names resolve to real params fields
    (a renamed field would silently split or mis-merge fleet groups).
"""

from __future__ import annotations

import time

from .findings import Finding, PassResult, site_of

PASS_ID = "signatures"


def _expected_first_geq(n: int, sizes) -> int | None:
    for s in sizes:
        if n <= s:
            return s
    return None


def _check_device(fam: str, eng, findings: list) -> int:
    import jax
    import jax.numpy as jnp

    checked = 0
    cls = type(eng)
    path, line = site_of(cls._seen_size_for)
    sizes = tuple(eng._seen_sizes)

    # ladder well-formedness
    checked += 1
    ok = (
        len(sizes) > 0
        and all(s > 0 and (s & (s - 1)) == 0 for s in sizes)
        and all(a < b for a, b in zip(sizes, sizes[1:]))
        and sizes[-1] == eng.TOPSZ
    )
    if not ok:
        findings.append(Finding(
            PASS_ID, "error", path, line,
            f"device:{fam}: malformed seen ladder {sizes} "
            f"(TOPSZ={eng.TOPSZ}) — must be strictly increasing powers "
            f"of two ending at TOPSZ",
            {"sizes": list(sizes), "topsz": eng.TOPSZ},
        ))
        return checked  # downstream checks assume the ladder

    inv = list(eng.signature_inventory())
    wave_set = [s for tag, *rest in inv if tag == "wave" for s in rest]
    merge_set = {tuple(sig[1:]) for sig in inv if sig[0] == "merge"}

    # declared wave set == the ladder, exactly
    checked += 1
    if wave_set != list(sizes):
        findings.append(Finding(
            PASS_ID, "error", path, line,
            f"device:{fam}: declared wave signatures {wave_set} != "
            f"seen ladder {list(sizes)}",
            {"inventory": wave_set, "ladder": list(sizes)},
        ))

    # dispatch closure: probe the runtime target chooser at every
    # boundary; it must agree with the independent first-geq rule and
    # stay inside the declared set
    probes = {1}
    for s in sizes:
        probes.update(x for x in (s - 1, s, s + 1) if 1 <= x <= eng.TOPSZ)
    for n in sorted(probes):
        checked += 1
        got = eng._seen_size_for(n)
        want = _expected_first_geq(n, sizes)
        if got != want or got not in wave_set:
            findings.append(Finding(
                PASS_ID, "error", path, line,
                f"device:{fam}: _seen_size_for({n}) -> {got}, outside "
                f"the set a run can dispatch (expected {want}) — a deep "
                f"run dispatching this target retraces mid-run",
                {"n": n, "got": got, "expected": want,
                 "declared": wave_set},
            ))
    checked += 1
    try:
        eng._seen_size_for(eng.TOPSZ + 1)
        findings.append(Finding(
            PASS_ID, "error", path, line,
            f"device:{fam}: _seen_size_for(TOPSZ+1) did not raise — the "
            f"capacity guard would dispatch an undeclared signature",
        ))
    except OverflowError:
        pass

    # merge closure at the wave buffer's shape
    lshapes = (eng.FCAP,)
    expect_merges = {
        (s, lshapes, t) for si, s in enumerate(sizes)
        for t in sizes[si:]
    }
    checked += 1
    if merge_set != expect_merges:
        mpath, mline = site_of(cls.signature_inventory)
        findings.append(Finding(
            PASS_ID, "error", mpath, mline,
            f"device:{fam}: declared merge signatures differ from "
            f"the reachable (size, target>=size) closure at the wave "
            f"buffer's shape {lshapes}",
            {"missing": sorted(
                str(k) for k in expect_merges - merge_set),
             "extra": sorted(str(k) for k in merge_set - expect_merges)},
        ))

    # pad-up proof: the merge body's output shape is EXACTLY (target,)
    spath, sline = site_of(cls._seen_merge_spec)
    for key in sorted(merge_set):
        checked += 1
        size, lsh, target = key
        body, _donate = eng._seen_merge_spec(key)
        out = jax.eval_shape(*(
            (body,)
            + (jax.ShapeDtypeStruct((size,), jnp.uint64),)
            + tuple(jax.ShapeDtypeStruct((n,), jnp.uint64) for n in lsh)
        ))
        if out.shape != (target,) or out.dtype != jnp.uint64:
            findings.append(Finding(
                PASS_ID, "error", spath, sline,
                f"device:{fam}: merge {key} produces shape {out.shape} "
                f"instead of exactly ({target},) — the next wave would "
                f"retrace at a seen size off the ladder",
                {"key": str(key), "out_shape": list(out.shape)},
            ))

    # growth chains terminate at the ceiling in chunk-aligned steps
    gpath, gline = site_of(cls._maybe_grow)
    for what, cur, ceil in (
        ("frontier", eng.FCAP, eng.MAX_FCAP),
        ("journal", eng.JCAP, eng.MAX_JCAP),
    ):
        checked += 1
        steps = 0
        bad = None
        while cur < ceil:
            new = eng._next_cap(cur * eng.GROWTH, cur, ceil, eng.GROWTH,
                                eng.chunk)
            if new <= cur or new > ceil or new % eng.chunk:
                bad = f"step {cur} -> {new}"
                break
            cur = new
            steps += 1
            if steps > 64:
                bad = f"no convergence after {steps} steps"
                break
        if bad:
            findings.append(Finding(
                PASS_ID, "error", gpath, gline,
                f"device:{fam}: {what} growth chain is not a finite "
                f"chunk-aligned ascent to the cap ceiling ({bad})",
                {"what": what, "ceiling": ceil},
            ))
    checked += 1
    if eng.FCAP % eng.chunk:
        findings.append(Finding(
            PASS_ID, "error", path, line,
            f"device:{fam}: FCAP {eng.FCAP} not a multiple of chunk "
            f"{eng.chunk} — the chunk schedule would dispatch a ragged "
            f"tail signature",
        ))
    return checked


def _check_sharded(fam: str, sh, findings: list) -> int:
    lsm = sh._lsm
    path, line = site_of(type(lsm).add_level)
    checked = 1
    n = lsm.n_levels()
    if n != lsm._init_levels or lsm.lv_size(n - 1) < lsm.TOPSZ:
        findings.append(Finding(
            PASS_ID, "error", path, line,
            f"sharded:{fam}: LSM ladder of {n} levels does not reach "
            f"TOPSZ={lsm.TOPSZ} at construction — add_level mid-run "
            f"changes the chunk program arity (a whole retrace)",
            {"levels": n, "top": lsm.lv_size(n - 1), "topsz": lsm.TOPSZ},
        ))
    return checked


def _check_fleet(findings: list) -> int:
    import dataclasses
    import importlib

    from ..fleet import grouping

    path, line = site_of(grouping._group_key)
    checked = 0
    for cls_name, names in grouping.FLEET_DYN.items():
        checked += 1
        mod = "raft" if cls_name == "RaftParams" else "pull_raft"
        params_cls = getattr(
            importlib.import_module(f"raft_tpu.models.{mod}"), cls_name)
        fields = {f.name for f in dataclasses.fields(params_cls)}
        missing = [n for n in names if n not in fields]
        if missing:
            findings.append(Finding(
                PASS_ID, "error", path, line,
                f"FLEET_DYN[{cls_name}] names {missing} are not fields "
                f"of {cls_name} — fleet grouping would mis-merge jobs",
                {"class": cls_name, "missing": missing},
            ))
    return checked


def run(families=None) -> PassResult:
    from . import registry

    t0 = time.time()
    families = tuple(families) if families else registry.FAMILIES
    findings: list[Finding] = []
    checked = 0
    for fam in families:
        checked += _check_device(fam, registry.device_engine(fam),
                                 findings)
    checked += _check_sharded(
        families[0], registry.sharded_engine(families[0]), findings)
    checked += _check_fleet(findings)
    notes = [f"{len(families)} device ladders + sharded arity + "
             f"fleet grouping"]
    return PassResult(PASS_ID, findings, checked, time.time() - t0, notes)
