"""LSM of sorted fingerprint runs — the seen-set of the sharded checker
(ShardedBFS). DeviceBFS has its own: one sorted run, and a wave's new
fingerprints in one append buffer whose written prefix is sorted with
the chunk (device_bfs.py), where every level here is sorted at its
capacity.

Level i holds at most one sorted u64 run of ``min(R0 << i, TOPSZ)`` lanes
(tail-padded with U64_MAX). Each chunk's new fingerprints enter at level
0; two runs at the same level merge (sort-concat — measured faster than
scatter-merges on this TPU) into the next level, exactly a binary
counter; the TOPSZ top level absorbs by truncate-merge (sound only while
the engine's capacity guard holds, see the callers). A chunk looks its
fingerprints up by sorting them together with every level short enough
to sort once a chunk, occupied or not, and binary-searches only the
OCCUPIED levels above that crossover (checker/util.py first_new); per-
chunk dedup cost is therefore independent of the total state count.

Lanes live on the LAST axis: a single device would use [lanes] arrays,
ShardedBFS uses [D, lanes] sharded arrays — the per-row sorts/concats are
identical code, ShardedBFS just pins shardings via ``jit_kw``/``put``. The cascade is
deterministic (occupancy-driven), so hosts can enqueue merges without
syncing on run contents.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.trace import stage
from ..ops.hashing import U64_MAX, sort_u64
from .util import jit_with_donation


def pow2_at_least(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


class RunLSM:
    """``r0``: level-0 run lanes (a chunk's emission width, pow2);
    ``topsz``: top-level lane cap (>= the engine's max seen capacity);
    ``lead_shape``: leading batch axes of every run array (() or (D,));
    ``put``: host->device placement for empties (defaults to
    jnp.asarray); ``jit_kw``: extra jax.jit kwargs for merge programs
    (e.g. out_shardings)."""

    def __init__(self, r0: int, topsz: int,
                 lead_shape: tuple[int, ...] = (), put=None, jit_kw=None):
        assert r0 and (r0 & (r0 - 1)) == 0, "r0 must be a power of two"
        self.R0 = r0
        self.TOPSZ = pow2_at_least(max(topsz, r0))
        self._lead = lead_shape
        self._put = put if put is not None else jnp.asarray
        self._jit_kw = dict(jit_kw or {})
        # Pre-create the FULL ladder up to TOPSZ: empty levels all alias
        # one cached sentinel constant per size (no HBM until occupied),
        # while creating a level later changes the engine's chunk-program
        # ARITY — a whole retrace and compile mid-run.
        self._init_levels = 1
        while self.lv_size(self._init_levels - 1) < self.TOPSZ:
            self._init_levels += 1
        self._merge_cache: dict = {}
        self._empty_cache: dict[int, object] = {}
        self.runs: list = []
        self.occ: list[bool] = []
        self.reset()

    # ---------------- geometry ----------------

    def lv_size(self, level: int) -> int:
        return min(self.R0 << level, self.TOPSZ)

    def lanes(self) -> int:
        """Occupied lanes (padding included) — the waste metric."""
        return sum(
            self.lv_size(i) for i in range(len(self.runs)) if self.occ[i]
        )

    def n_levels(self) -> int:
        return len(self.runs)

    # ---------------- internals ----------------

    def _empty_of(self, size: int):
        """Cached read-only all-U64_MAX run. Levels share it and probing
        it is harmless, but it must NEVER reach a merge: merge inputs are
        donated (round 6), and a donated shared sentinel would be deleted
        out from under every other level aliasing it. The cascade never
        does (it only merges occupied runs, which are real buffers);
        warmup/probes use _fresh throwaways."""
        if size not in self._empty_cache:
            self._empty_cache[size] = self._put(
                np.full(self._lead + (size,), np.uint64(U64_MAX))
            )
        return self._empty_cache[size]

    def _fresh(self, size: int):
        """A fresh, never-shared all-sentinel run for donation probes and
        warmup merges (both CONSUME their inputs when donation sticks)."""
        return self._put(np.full(self._lead + (size,), np.uint64(U64_MAX)))

    def _jit(self, key, builder):
        fn = self._merge_cache.get(key)
        if fn is None:
            fn = jax.jit(builder(), **self._jit_kw)
            self._merge_cache[key] = fn
        return fn

    @staticmethod
    def merge_spec(na: int, nb: int, out: int | None = None):
        """The merge program SPEC at a (na, nb, out) signature: the
        traced body plus its donate argnums — the single source both the
        production ``_merge`` and the static donation auditor
        (analysis/donation.py) build from. An input is donated only
        where the output can alias it (same lane count): that is run
        ``a`` of the top truncate-merge (na == nb == out). The
        equal-size merges below the top double their lanes, so neither
        input can alias and they are built undonated by declaration."""
        if out is None:
            def body(x, y):
                return sort_u64(jnp.concatenate([x, y], axis=-1), axis=-1)
        else:
            def body(x, y):
                return sort_u64(
                    jnp.concatenate([x, y], axis=-1), axis=-1
                )[..., :out]
        return stage("seen_merge")(body), ((0,) if na == out else ())

    def _merge(self, a, b, out: int | None = None):
        """Per-row sort-concat merge along the lane axis (2-key u32 sort,
        ops/hashing.py). The cascade only merges runs that are dead
        afterwards (the occupied run is replaced by the merge output or
        an empty sentinel, the carry is consumed), so the top
        truncate-merge donates run ``a`` and sorts into its HBM
        (merge_spec); jit_with_donation compiles and runs each program
        once on throwaway runs and fails if the donation is refused."""
        key = (a.shape[-1], b.shape[-1], out)
        fn = self._merge_cache.get(key)
        if fn is None:
            na, nb = a.shape[-1], b.shape[-1]
            body, donate = self.merge_spec(na, nb, out)
            fn = jit_with_donation(
                body, donate, f"lsm_merge{key}",
                lambda: (self._fresh(na), self._fresh(nb)),
                **self._jit_kw,
            )
            self._merge_cache[key] = fn
        return fn(a, b)

    # ---------------- static audit surface ----------------

    def audit_programs(self):
        """The cascade's complete merge-signature set (the same closure
        argument as ``warmup``: carries double exactly, so only
        equal-size merges per level plus the top truncate-merge exist),
        as audit entries for the static donation auditor — same schema
        as the engines' ``audit_programs``. ``_pad_run`` is absent by
        policy: its output is strictly larger than its input, so
        aliasing is impossible and the program is exempt from the
        donation contract."""
        import inspect as _inspect

        sds = jax.ShapeDtypeStruct
        _, line = _inspect.getsourcelines(RunLSM.merge_spec)
        site = (__file__, line)
        for i in range(len(self.runs)):
            size = self.lv_size(i)
            top = size >= self.TOPSZ
            body, donate = self.merge_spec(
                size, size, size if top else None)
            run = sds(self._lead + (size,), jnp.uint64)
            yield {
                "name": (f"lsm_merge[L{i}:top]" if top
                         else f"lsm_merge[L{i}]"),
                "fn": jax.jit(body, donate_argnums=donate,
                              **self._jit_kw),
                "args": (run, run),
                "carries": {0: "run_a", 1: "run_b"},
                "pinned": {},
                "site": site, "per_wave": 1,
            }
            if top:
                break

    def _pad_run(self, run, size: int):
        have = run.shape[-1]
        if have == size:
            return run
        assert have < size

        def build():
            pad = size - have
            return lambda r: jnp.concatenate(
                [r, jnp.full(r.shape[:-1] + (pad,), U64_MAX, jnp.uint64)],
                axis=-1)

        return self._jit(("pad", have, size), build)(run)

    # ---------------- operations ----------------

    def reset(self, n_levels: int | None = None):
        n = n_levels if n_levels is not None else self._init_levels
        self.runs = [self._empty_of(self.lv_size(i)) for i in range(n)]
        self.occ = [False] * n

    def add_level(self) -> None:
        """NOTE: changes the engine's chunk-program arg count (retrace)."""
        self.runs.append(self._empty_of(self.lv_size(len(self.runs))))
        self.occ.append(False)

    def insert(self, run) -> None:
        """Binary-counter insert of a sorted run (async device ops only —
        the cascade is occupancy-driven, no host sync on run contents)."""
        self.insert_at(run, 0)

    def insert_at(self, run, level: int) -> None:
        """Insert a sorted run whose lane count equals ``lv_size(level)``
        starting the cascade at that level."""
        assert run.shape[-1] == self.lv_size(level), (
            run.shape, self.lv_size(level))
        lv = level
        carry = run
        while True:
            if lv == len(self.runs):
                self.add_level()
            size = self.lv_size(lv)
            if not self.occ[lv]:
                self.runs[lv] = self._pad_run(carry, size)
                self.occ[lv] = True
                return
            if size >= self.TOPSZ:
                # absorb at the top: truncate-merge. Sound because the
                # engine's pre-wave capacity guard ensures all real lanes
                # fit in TOPSZ.
                self.runs[lv] = self._merge(self.runs[lv], carry, out=size)
                return
            carry = self._merge(self.runs[lv], carry)
            self.runs[lv] = self._empty_of(size)
            self.occ[lv] = False
            lv += 1

    def consolidate(self, bound: int) -> None:
        """Repack every occupied run into one right-sized run, dropping
        sentinel padding (bounds probe count and lane waste). `bound`
        must be an upper bound on the real fingerprints held per row; the
        truncation is then safe (the engine's capacity guard keeps it
        sound at TOPSZ).

        HOST-side (round 5): a device repack needs one program per
        (occupied-shapes, target) signature — an open-ended set, each
        compiled mid-run inside some wave's wall time. A numpy sort of a
        few tens of MB plus one H2D upload compiles NOTHING; seeding
        pads on the host so no pad program is needed either."""
        if sum(self.occ) <= 1:
            return
        rows = self.export_real()
        if self._lead:
            n = max((len(r) for r in rows), default=0)
            target = min(max(self.R0, pow2_at_least(max(1, n))), self.TOPSZ)
            host = np.full(self._lead + (target,), np.uint64(U64_MAX))
            for d, r in enumerate(rows):
                host[d, : len(r)] = r[:target]
        else:
            target = min(
                max(self.R0, pow2_at_least(max(1, len(rows)))), self.TOPSZ
            )
            host = np.full((target,), np.uint64(U64_MAX))
            host[: min(len(rows), target)] = rows[:target]
        self.seed(host)

    def seed(self, host_rows: np.ndarray) -> None:
        """Start from a host array [*lead, n] of per-row sorted real
        fingerprints padded with U64_MAX (Init seeding / resume).

        Padding to the level size happens on the HOST: a device pad
        program is one more compile per (n, size) signature, a numpy
        concatenate is none."""
        n = host_rows.shape[-1]
        if n > self.TOPSZ:
            raise OverflowError(
                f"seen-set seed of {n} lanes exceeds the {self.TOPSZ}-lane "
                "capacity; raise max_seen_cap to at least the checkpoint's "
                "seen size"
            )
        lv = 0
        while self.lv_size(lv) < n:
            lv += 1
        size = self.lv_size(lv)
        host_rows = np.asarray(host_rows, dtype=np.uint64)
        if n < size:
            pad = np.full(
                host_rows.shape[:-1] + (size - n,), np.uint64(U64_MAX)
            )
            host_rows = np.concatenate([host_rows, pad], axis=-1)
        self.reset(max(self._init_levels, lv + 1))
        self.runs[lv] = self._put(host_rows)
        self.occ[lv] = True

    def warmup(self) -> None:
        """Execute one sentinel merge per ladder level so every merge
        signature a run can need is compiled (and lands in the
        persistent compile cache) BEFORE the timed region. The cascade
        only ever merges equal-size runs (carries double exactly), so
        this is the complete signature set. Fresh throwaway runs, never
        the shared _empty_of sentinels: merges donate their inputs."""
        for i in range(len(self.runs)):
            size = self.lv_size(i)
            if size >= self.TOPSZ:
                self._merge(self._fresh(size), self._fresh(size), out=size)
                break
            self._merge(self._fresh(size), self._fresh(size))

    def export_host(self) -> list[np.ndarray]:
        """Occupied runs fetched to host (raw, sentinel-padded)."""
        return [
            np.asarray(jax.device_get(self.runs[i]))
            for i in range(len(self.runs))
            if self.occ[i]
        ]

    def export_real(self):
        """Real fingerprints, sentinel-filtered and sorted: a flat [n]
        array for lead_shape (), a list of per-row arrays for (D,)
        (the checkpoint format both engines share)."""
        parts = self.export_host()
        sent = np.uint64(U64_MAX)

        def pack(arrs):
            cat = (np.concatenate(arrs) if arrs
                   else np.empty(0, np.uint64))
            cat = cat[cat != sent]
            cat.sort()
            return cat

        if not self._lead:
            return pack(parts)
        return [pack([p[d] for p in parts]) for d in range(self._lead[0])]
