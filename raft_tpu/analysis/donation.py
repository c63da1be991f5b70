"""Donation auditor: prove every capacity-shaped loop carry aliases an
output of the lowered program that rebinds it.

The engines declare their dispatch surface via ``audit_programs()``
(DeviceBFS: the fused wave program + the end-of-wave seen merge;
ShardedBFS: the shard_map chunk program; RunLSM: the
cascade merge closure). Each entry carries an INDEPENDENT ``carries``
map — written out separately from the ``*_DONATE`` tuples the jits
consume — so dropping an argnum from a donate tuple (the classic
regression: PR 9 found an undonated stage dispatch costing 74.2 s vs
0.105 s) diverges the declaration from the lowering and is reported
here with the analytic bytes copied per wave.

The proof reads the LOWERED computation, not the python: jax marks
input-output aliasing in the StableHLO ``@main`` signature as
``{tf.aliasing_output = K}`` arg attributes. A carry must carry that
attribute whenever a shape/dtype-compatible output slot exists for it
(a carry whose shape matches no remaining output — e.g. the wave's
fingerprint buffer folded into the seen merge — cannot alias anything
and is exempt: the engines build such inputs undonated by declaration,
see checker/util.py jit_with_donation).

Coverage vs budget: the full device + sharded + LSM surface is lowered
for one family (raft); for the other five families the fused wave
program — the only per-wave dispatch on the hot path — is lowered and
audited, so a model whose lowering defeats aliasing is still caught.
"""

from __future__ import annotations

import re
import time

from .findings import Finding, PassResult

PASS_ID = "donation"

# the family whose complete program surface is lowered; the rest get
# the wave program only (lowering is the entire cost of this pass)
FULL_FAMILY = "raft"

_ARG_RE = re.compile(r"%arg(\d+): tensor<([^>]+)>")
_ALIAS_RE = re.compile(r"tf\.aliasing_output\s*=\s*(\d+)")
_DTYPE_BYTES = {
    "i1": 1, "i8": 1, "ui8": 1, "i16": 2, "ui16": 2, "i32": 4,
    "ui32": 4, "i64": 8, "ui64": 8, "f32": 4, "bf16": 2, "f16": 2,
    "f64": 8,
}


def parse_main_aliasing(txt: str):
    """Parse the ``@main`` signature of lowered StableHLO text into
    ``(args, results)``: ``args`` maps argnum -> (type, aliased output
    index or None), ``results`` is the list of output type strings.
    Type strings are the tensor bodies, e.g. ``"5120x82xi32"``."""
    i = txt.index("@main(")
    j = txt.index(") -> ", i)
    argstr = txt[i + len("@main("):j]
    resstr = txt[j:txt.index("\n", j)]
    args = {}
    for part in re.split(r"(?=%arg\d+)", argstr):
        m = _ARG_RE.match(part)
        if not m:
            continue
        am = _ALIAS_RE.search(part)
        args[int(m.group(1))] = (
            m.group(2), int(am.group(1)) if am else None)
    results = re.findall(r"tensor<([^>]+)>", resstr)
    return args, results


def tensor_bytes(type_str: str) -> int:
    """Byte size of a StableHLO tensor type body ('5120x82xi32')."""
    parts = type_str.split("x")
    dtype = parts[-1]
    n = 1
    for p in parts[:-1]:
        n *= int(p)
    return n * _DTYPE_BYTES.get(dtype, 8)


def audit_entry(entry: dict, scope: str, findings: list) -> None:
    """Lower one audit entry and check its declared carries/pins
    against the ``tf.aliasing_output`` attributes in the result."""
    txt = entry["fn"].lower(*entry["args"]).as_text()
    args, results = parse_main_aliasing(txt)
    path, line = entry["site"]
    # output slots by type, minus the slots aliased args already consume
    avail: dict[str, int] = {}
    for ty in results:
        avail[ty] = avail.get(ty, 0) + 1
    for ty, tgt in args.values():
        if tgt is not None:
            avail[ty] = avail.get(ty, 0) - 1
    for argnum, name in sorted(entry["carries"].items()):
        if argnum not in args:
            findings.append(Finding(
                PASS_ID, "error", path, line,
                f"{scope} program '{entry['name']}': declared carry "
                f"'{name}' (arg {argnum}) is missing from the lowered "
                f"signature — audit surface out of date",
                {"program": entry["name"], "arg": argnum},
            ))
            continue
        ty, tgt = args[argnum]
        if tgt is not None:
            continue  # aliased: the contract holds
        if avail.get(ty, 0) <= 0:
            # no compatible output slot remains — aliasing is
            # impossible for this carry (e.g. the wave's buffer folded
            # into the seen merge), declared undonated by the engine
            continue
        avail[ty] -= 1
        per_wave = entry.get("per_wave", 1)
        findings.append(Finding(
            PASS_ID, "error", path, line,
            f"{scope} program '{entry['name']}': carry '{name}' "
            f"(arg {argnum}, tensor<{ty}>) is NOT donated — every "
            f"dispatch copies it through the output",
            {
                "program": entry["name"], "arg": argnum,
                "tensor": ty,
                "bytes_per_wave": tensor_bytes(ty) * per_wave,
            },
        ))
    for argnum, name in sorted(entry.get("pinned", {}).items()):
        if argnum in args and args[argnum][1] is not None:
            findings.append(Finding(
                PASS_ID, "error", path, line,
                f"{scope} program '{entry['name']}': pinned buffer "
                f"'{name}' (arg {argnum}) IS donated — the host reuses "
                f"it after the dispatch (use-after-donate)",
                {"program": entry["name"], "arg": argnum},
            ))


def run(families=None, scopes=("device", "sharded", "lsm")) -> PassResult:
    from . import registry

    t0 = time.time()
    families = tuple(families) if families else registry.FAMILIES
    findings: list[Finding] = []
    notes: list[str] = []
    checked = 0

    full = FULL_FAMILY if FULL_FAMILY in families else families[0]
    if "device" in scopes:
        for fam in families:
            eng = registry.device_engine(fam)
            for entry in eng.audit_programs():
                if fam != full and entry["name"] != "wave":
                    continue
                audit_entry(entry, f"device:{fam}", findings)
                checked += 1
        notes.append(
            f"device: full surface for {full}, wave program for "
            f"{len(families) - 1} other families")
    if "sharded" in scopes:
        sh = registry.sharded_engine(full)
        for entry in sh.audit_programs():
            audit_entry(entry, f"sharded:{full}", findings)
            checked += 1
        if "lsm" in scopes:
            for entry in sh._lsm.audit_programs():
                audit_entry(entry, f"lsm:{full}", findings)
                checked += 1
        notes.append(f"sharded+lsm surface for {full} (D=1 mesh)")

    return PassResult(
        PASS_ID, findings, checked, time.time() - t0, notes)
