"""Crash-safe checkpoint I/O shared by all three BFS engines.

TLC's durability contract (SURVEY.md §5.4) is that a long run survives a
crash at ANY instant and resumes bit-identically. The bare ``np.savez``
the engines used before this module had three holes:

  * a crash mid-write left a half-written file AT the final path on
    filesystems where the tmp rename raced the flush — and even with
    tmp+rename, a crash between write and fsync could surface an empty
    file after power loss;
  * nothing detected a truncated/corrupt file at load time: resume
    failed with a numpy ``KeyError``/``BadZipFile`` deep in the loader,
    or worse, loaded stale bytes silently;
  * one file was the only generation — a corruption cost the whole run.

``save_npz`` therefore writes tmp + flush + ``os.fsync`` + ``os.replace``
(+ best-effort directory fsync), embeds ``format_version`` and a
content hash over every array's name/dtype/shape/bytes, and rotates the
previous file through ``path.gen1 .. path.gen{keep-1}`` before the
replace. ``load_npz`` verifies the hash and falls back to the newest
intact generation, reporting what it skipped, so one truncated write
costs at most one checkpoint interval of progress.

Format versions:
  1  pre-resilience (no hash, no coverage field on old files): still
     accepted on load — verification is skipped, engines zero-fill the
     missing fields (pinned by tests/test_resilience.py back-compat).
  2  this module: + format_version, + content_hash, written atomically.

The hash covers the PAYLOAD (sorted field name, dtype, shape, raw
bytes), not the zip container, so it survives numpy/zlib container
differences across versions while still catching any flipped or missing
payload byte.
"""

from __future__ import annotations

import hashlib
import os
import re

import numpy as np

from .errors import CheckpointCorrupt, CheckpointMismatch

FORMAT_VERSION = 2
HASH_KEY = "content_hash"
DEFAULT_KEEP = 3


def generation_path(path: str, gen: int) -> str:
    """On-disk name of generation ``gen`` (0 = the live file)."""
    return path if gen == 0 else f"{path}.gen{gen}"


def content_hash(payload: dict) -> str:
    """Deterministic digest of a checkpoint payload: every field's name,
    dtype, shape and raw bytes, in sorted-name order (the zip member
    order np.savez uses is an implementation detail; this is not)."""
    h = hashlib.blake2b(digest_size=16)
    for key in sorted(payload):
        if key == HASH_KEY:
            continue
        arr = np.asarray(payload[key])
        h.update(key.encode())
        h.update(str(arr.dtype).encode())
        h.update(repr(arr.shape).encode())
        h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()


def _fsync_dir(dirname: str) -> None:
    """Durably record the rename in the directory entry (best effort:
    not every filesystem/platform allows opening a directory)."""
    try:
        fd = os.open(dirname or ".", os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def save_npz(path: str, payload: dict, keep: int = DEFAULT_KEEP,
             chaos=None) -> None:
    """Atomically persist ``payload`` at ``path`` with hash + rotation.

    Write order is crash-safe at every step: (1) tmp file written,
    flushed and fsynced — a crash here leaves the old generations
    untouched; (2) existing generations rotate path -> path.gen1 -> ...
    (oldest dropped) — each rename is atomic, and a crash mid-rotation
    leaves every file intact under SOME candidate name the loader
    tries; (3) ``os.replace(tmp, path)`` publishes the new file;
    (4) directory fsync (best effort) makes the renames durable.

    ``chaos``: a ChaosInjector whose ``checkpoint_written`` hook may
    truncate the published file — the deterministic stand-in for a
    crash mid-write that tests drive the generation-fallback path with.
    """
    keep = max(1, int(keep))
    payload = dict(payload)
    payload["format_version"] = np.int64(FORMAT_VERSION)
    payload[HASH_KEY] = content_hash(payload)
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    tmp = f"{path}.tmp.npz"  # .npz suffix stops savez appending one
    with open(tmp, "wb") as fh:
        # uncompressed: multi-GB checkpoints on a 1-core host must not
        # stall the device loop for minutes of zlib
        np.savez(fh, **payload)
        fh.flush()
        os.fsync(fh.fileno())
    # rotate: path -> .gen1 -> .gen2 ... (newest-first numbering)
    for gen in range(keep - 1, 0, -1):
        older = generation_path(path, gen)
        newer = generation_path(path, gen - 1)
        if os.path.exists(newer):
            os.replace(newer, older)
    os.replace(tmp, path)
    _fsync_dir(parent)
    if chaos is not None:
        chaos.checkpoint_written(path)


def _read_verify(path: str) -> dict:
    """Load one candidate file fully and verify it. Raises CheckpointCorrupt
    (truncated/unreadable/hash mismatch) or returns the payload dict.
    Version-1 files (no hash) load unverified for back-compat."""
    try:
        with np.load(path, allow_pickle=False) as ck:
            payload = {k: np.asarray(ck[k]) for k in ck.files}
    except Exception as e:  # zipfile.BadZipFile, OSError, ValueError ...
        raise CheckpointCorrupt(
            f"{path}: unreadable ({type(e).__name__}: {e})"
        ) from e
    # hash verification is keyed on the CONTAINER revision
    # (format_version, stamped by save_npz) — not on the engines' own
    # payload-layout "version" field, which revs independently (the
    # sharded engine's mesh-portable layout is payload v2 but any
    # container may carry it)
    version = (
        int(payload["format_version"]) if "format_version" in payload else 1
    )
    if version >= 2:
        stored = str(payload.get(HASH_KEY, ""))
        if not stored:
            raise CheckpointCorrupt(f"{path}: format v{version} but no hash")
        if content_hash(payload) != stored:
            raise CheckpointCorrupt(
                f"{path}: content hash mismatch (truncated or corrupt write)"
            )
    return payload


def load_npz(path: str, keep: int = DEFAULT_KEEP) -> tuple[dict, int, list[str]]:
    """Load the newest intact generation of ``path``.

    Tries ``path``, then ``path.gen1`` .. ``path.gen{keep-1}``; the
    first candidate whose content hash verifies wins. Returns
    ``(payload, generation, skipped)`` where ``skipped`` holds one
    diagnostic line per rejected newer candidate (for the
    ``ckpt_generation`` telemetry event and the operator's log).
    Raises CheckpointCorrupt when no generation is intact and
    FileNotFoundError when no candidate exists at all.
    """
    skipped: list[str] = []
    tried_any = False
    for gen in range(max(1, int(keep))):
        cand = generation_path(path, gen)
        if not os.path.exists(cand):
            continue
        tried_any = True
        try:
            payload = _read_verify(cand)
        except CheckpointCorrupt as e:
            skipped.append(str(e))
            continue
        return payload, gen, skipped
    if not tried_any:
        raise FileNotFoundError(
            f"no checkpoint at {path} (or any .gen* generation)"
        )
    raise CheckpointCorrupt(
        f"no intact checkpoint generation at {path}",
        problems=tuple(skipped),
    )


def format_version_of(payload: dict) -> int:
    """The payload's checkpoint-format version (1 for pre-resilience
    files that only carried the engine's own ``version=1`` field)."""
    if "format_version" in payload:
        return int(payload["format_version"])
    return int(payload.get("version", 1))


# The sharded engine's ident embeds its mesh size as /D=<n>/ — that D
# is PROVENANCE (which mesh wrote the file), not identity: the payload
# is a set of per-shard sorted-fingerprint segments that reshard onto
# any mesh by fp mod D_new. These helpers strip/extract it so check_spec
# can tell "different model" from "same model, different mesh".
_MESH_D_RE = re.compile(r"/D=(\d+)")


def mesh_d_of(spec: str) -> int | None:
    """Mesh size recorded in a checkpoint ident, or None when the ident
    has no /D=<n>/ component (host and single-device engines)."""
    m = _MESH_D_RE.search(spec)
    return int(m.group(1)) if m else None


def mesh_neutral(spec: str) -> str:
    """The ident with its /D=<n> provenance component removed — two
    specs with equal neutral forms differ only in mesh size."""
    return _MESH_D_RE.sub("", spec)


def lineage_name(name: str, index: int) -> str:
    """Per-job checkpoint filename inside a fleet's checkpoint_dir.

    Sanitizing alone is ambiguous — "a/b" and "a_b" both sanitize to
    "a_b" — so the job's position in the fleet disambiguates the
    lineage (job order is part of the packed layout, hence stable)."""
    safe = re.sub(r"[^A-Za-z0-9._=-]", "_", name)
    return f"{safe}.j{int(index)}.ckpt.npz"


def check_spec(payload: dict, expect_ident: str, path: str,
               allow_reshard: bool = False) -> None:
    """Refuse a checkpoint whose identity or format this build cannot
    soundly resume. The messages are load-bearing: the "checkpoint is
    for spec" prefix is a documented contract (tests match it), and a
    future format version must fail HERE with a clear sentence, not
    later with a numpy KeyError.

    ``allow_reshard``: accept a checkpoint whose ident differs from
    ``expect_ident`` ONLY in its /D=<n> mesh-size component — the
    sharded engine re-routes the segments by fp mod D_new at load time.
    When False, a pure mesh mismatch still fails, but with a message
    naming both mesh sizes and the reshard path instead of the generic
    spec mismatch."""
    version = format_version_of(payload)
    if version > FORMAT_VERSION:
        raise CheckpointMismatch(
            f"{path}: checkpoint format v{version} is newer than this "
            f"build's v{FORMAT_VERSION}; upgrade raft_tpu to resume it"
        )
    spec = str(payload.get("spec", "<missing spec field>"))
    if spec == expect_ident:
        return
    d_ck, d_run = mesh_d_of(spec), mesh_d_of(expect_ident)
    if (d_ck is not None and d_run is not None and d_ck != d_run
            and mesh_neutral(spec) == mesh_neutral(expect_ident)):
        if allow_reshard:
            return
        raise CheckpointMismatch(
            f"{path}: checkpoint was written on a D={d_ck} mesh, this run "
            f"is on D={d_run} — the payload is mesh-portable; drop "
            f"--no-reshard to re-route the shards by fp mod {d_run} on "
            f"resume"
        )
    raise CheckpointMismatch(
        f"checkpoint is for spec {spec}, model is {expect_ident}"
    )


def validate_resume(path: str, expect_ident: str,
                    keep: int = DEFAULT_KEEP,
                    allow_reshard: bool = False) -> tuple[int, int]:
    """Fail-fast --resume validation: prove the checkpoint exists, loads
    (falling back through generations), and matches the model identity —
    BEFORE the caller pays the run's first compiles. Returns
    ``(generation, depth)`` of the checkpoint that will be used."""
    payload, gen, _skipped = load_npz(path, keep=keep)
    check_spec(payload, expect_ident, path, allow_reshard=allow_reshard)
    return gen, int(payload.get("depth", 0))
