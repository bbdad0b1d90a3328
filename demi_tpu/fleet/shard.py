"""Digest-range-sharded coordinator host half (ROADMAP item 1(b)).

The fleet sharded the *device* half of exploration across preemptible
workers, but every round still funnels through ONE single-threaded host
pipeline — the racing scan, static/sleep filtering, and digest dedup in
``DeviceDPOR._process_round`` — which caps rounds/sec at high worker
counts. This module partitions that pipeline by prescription
**content-digest range** across N admission shards:

- **Phase A (parallel)** — the round's lanes split into N contiguous
  slices; each shard thread runs the native batch scan (+ static/sleep
  filters) over its slice. The ctypes call into
  ``demi_racing_prescriptions*`` releases the GIL, so the C++ scans
  genuinely overlap; the NumPy-twin fallback rides the same slicing.
  Per-lane scans are independent and the packed stream is lane-major,
  so concatenating the slices in order reproduces the sequential
  scan's candidate stream bit-for-bit.
- **Phase B (parallel)** — each shard checks the candidates whose
  digests land in ITS range against its private slice of the
  explored/suppressed digest sets (``DigestShards``): a disjoint
  membership partition, since equal digests route to the same shard.
- **Phase C (parallel)** — each shard precomputes the Mazurkiewicz
  class keys (``canonical_class_key`` — the host half's dominant cost
  on class-tracked runs) for the admissible candidates it owns; the
  key is a pure function of one candidate, so precomputation is
  unobservable.
- **Canonical merge (serial)** — ``DeviceDPOR._admit_stream`` then
  applies the surviving candidates in the exact sequential round
  order: known duplicates are skipped in bulk, and every
  order-dependent effect (explored-log append order, frontier order,
  class-ledger admission, wakeup guides) happens serially. Explored /
  class / violation sets, frontier contents, and the first-found
  record are therefore **bit-identical** to the 1-shard path at any
  shard count — the fleet's canonical-round-order trick applied one
  level up.

Phases A/B precompute only order-INdependent facts (the scan stream,
content digests, pre-round membership), which is the whole argument:
nothing a shard computes depends on what another shard admits.

Checkpoints stay shard-count-free: ``persist/`` serializes the digest
sets FLAT (sorted byte join), so restoring an N-shard checkpoint into M
shards just re-partitions the ranges (``DigestShards.__init__`` routes
every key). The prune-note ledgers (``StaticIndependence`` /
``SleepSets`` counters + audit lists) are kept deterministic by
buffering each shard's notes (``_NoteBuffer``) and replaying them
serially in slice order after the join.

Knobs: ``DeviceDPOR(host_shards=N)`` / ``demi_tpu dpor --host-shards N``;
``tune.calibrate_host_shards`` makes N a measured, TuningCache-persisted
decision.
``DEMI_HOST_SHARD_SERIALIZE=1`` runs the shard tasks sequentially on
the calling thread — the bench's *uncontended* busy-seconds convention
(each shard timed as if it owned its core, the config-13 analog of
``max_outstanding=1``), and a determinism bisect tool.
"""

from __future__ import annotations

import os
import sys
from concurrent.futures import ThreadPoolExecutor
from time import perf_counter
from typing import Iterable, List, Optional, Sequence, Set

import numpy as np

__all__ = [
    "DigestShards",
    "HostHalfTimer",
    "ShardedAdmission",
    "resolve_host_shards",
    "shard_ids_of_digests",
    "shard_of_key",
]


def resolve_host_shards(explicit: Optional[int] = None) -> int:
    """Admission shard count: what the caller says, at least 1; None is
    1 (the plain sequential pipeline)."""
    return max(1, int(explicit or 1))


def shard_of_key(key: bytes, n: int) -> int:
    """Owning shard of one 16-byte digest key: a contiguous range
    partition on the top 32 bits of the first digest lane —
    ``(hi32 * n) >> 32`` — exact for any n < 2^32 and recomputable
    from the key alone, which is what makes an N-shard checkpoint
    restorable into M shards by pure re-partitioning. Byte order
    follows the digest matrix's native layout (``digest_keys`` packs
    ``tobytes()``), mirrored by ``shard_ids_of_digests``."""
    word = int.from_bytes(key[:8], sys.byteorder)
    return ((word >> 32) * n) >> 32


def shard_ids_of_digests(digests: np.ndarray, n: int) -> np.ndarray:
    """Vectorized ``shard_of_key`` over a [k, 2] uint64 digest matrix
    (the scan's output, before keys are ever materialized)."""
    d0 = np.asarray(digests, np.uint64)[:, 0]
    return (((d0 >> np.uint64(32)) * np.uint64(n)) >> np.uint64(32)).astype(
        np.int64
    )


class DigestShards:
    """The explored/suppressed digest set, partitioned into N disjoint
    range slices. Drop-in for the plain ``set[bytes]`` on every surface
    the search uses — ``add``/``in``/``len``/iteration — while exposing
    ``slices[s]`` so shard s's dedup thread touches only its own set.
    Iteration yields a flat stream (slice-major), so ``set(...)`` /
    ``sorted(...)`` snapshots and the persist codec's flat pack work
    unchanged; construction from any iterable re-partitions, which IS
    the N→M re-shard path."""

    __slots__ = ("n", "slices")

    def __init__(self, n: int, items: Iterable[bytes] = ()):
        self.n = max(1, int(n))
        self.slices: List[Set[bytes]] = [set() for _ in range(self.n)]
        for key in items:
            self.slices[shard_of_key(key, self.n)].add(key)

    def add(self, key: bytes) -> None:
        self.slices[shard_of_key(key, self.n)].add(key)

    def update(self, keys: Iterable[bytes]) -> None:
        for key in keys:
            self.add(key)

    def __contains__(self, key: bytes) -> bool:
        return key in self.slices[shard_of_key(key, self.n)]

    def __len__(self) -> int:
        return sum(len(s) for s in self.slices)

    def __iter__(self):
        for s in self.slices:
            yield from s

    def __eq__(self, other) -> bool:
        if isinstance(other, DigestShards):
            if other.n == self.n:
                return self.slices == other.slices
            return set(self) == set(other)
        if isinstance(other, (set, frozenset)):
            return set(self) == other
        return NotImplemented

    def __repr__(self) -> str:
        return f"DigestShards(n={self.n}, len={len(self)})"


class _NoteBuffer:
    """Buffering proxy over a prune ledger (``StaticIndependence`` or
    ``SleepSets``): shard threads read attributes/oracles straight
    through, but the mutating note calls — ``note_pruned`` (counter
    totals) and ``note_pruned_prescription`` (audit lists) — are
    buffered and replayed serially in slice order after the join, so
    concurrent scans never race on the ledger dicts and the audit
    lists keep the sequential stream order. (Counts are
    order-independent sums; replay order only matters for the lists.)
    """

    __slots__ = ("_target", "_notes")

    _BUFFERED = ("note_pruned", "note_pruned_prescription")

    def __init__(self, target):
        self._target = target
        self._notes: list = []

    def __getattr__(self, name):
        if name in _NoteBuffer._BUFFERED:
            notes = self._notes

            def buffered(*args, __name=name, **kwargs):
                notes.append((__name, args, kwargs))

            return buffered
        return getattr(self._target, name)

    def replay(self) -> None:
        for name, args, kwargs in self._notes:
            getattr(self._target, name)(*args, **kwargs)
        self._notes.clear()


class ShardScan:
    """One round's sharded scan + dedup, re-assembled into the exact
    sequential candidate stream plus per-candidate verdicts."""

    __slots__ = (
        "rows", "offsets", "lanes", "keys", "known_dup", "shard_ids",
        "stats", "wall_s",
    )

    def __init__(self, rows, offsets, lanes, keys, known_dup, shard_ids,
                 stats, wall_s):
        self.rows = rows
        self.offsets = offsets
        self.lanes = lanes
        self.keys = keys
        self.known_dup = known_dup
        self.shard_ids = shard_ids
        self.stats = stats
        self.wall_s = wall_s


class ShardedAdmission:
    """N-shard executor for the admission pipeline's parallel phases,
    plus the per-shard accounting the journal/top/bench read.

    Owns one ``ScanBuffers`` per shard (the satellite-1 per-(instance,
    shard) size-hint home), a lazily-built thread pool, cumulative
    per-shard busy seconds, and the last round's per-shard stats. The
    digest sets themselves live on the DeviceDPOR (as ``DigestShards``)
    — passed per call, so checkpoint restores that swap the sets never
    leave a stale reference here."""

    def __init__(self, n: int, serialize: Optional[bool] = None):
        from ..native import ScanBuffers

        self.n = max(1, int(n))
        if serialize is None:
            serialize = os.environ.get(
                "DEMI_HOST_SHARD_SERIALIZE", ""
            ).strip().lower() in ("1", "true", "yes", "on")
        self.serialize = bool(serialize)
        self.buffers = [ScanBuffers() for _ in range(self.n)]
        self._pool: Optional[ThreadPoolExecutor] = None
        # Cumulative accounting: per-shard busy seconds (scan + dedup),
        # their total, the wall of the parallel sections, and rounds —
        # the inputs to the uncontended-seconds convention
        # (HostHalfTimer) and the fleet.host_shard journal record.
        self.busy_seconds = [0.0] * self.n
        self.busy_total = 0.0
        self.section_seconds = 0.0
        self.rounds = 0
        self.last_stats: List[dict] = []

    def close(self) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None

    def _run(self, tasks: Sequence) -> list:
        """Run thunks across the shard pool — or sequentially under the
        serialize convention (uncontended per-shard timing; also a
        determinism bisect mode). Results keep task order either way."""
        if self.serialize or len(tasks) <= 1:
            return [t() for t in tasks]
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=self.n, thread_name_prefix="demi-host-shard"
            )
        return [f.result() for f in [self._pool.submit(t) for t in tasks]]

    def scan_round(
        self, traces, lens, n_lanes: int, recw: int, *,
        independence=None, sleep=None, sleep_ctx=None,
        explored: DigestShards, suppressed: DigestShards,
    ) -> ShardScan:
        """Phases A + B for one round (see module doc): lane-sliced
        scans, slice-order reassembly of the sequential candidate
        stream, then digest-range-sliced membership against the
        pre-round explored/suppressed sets. The caller (the canonical
        merge) is the only mutator of those sets, and it runs after
        this returns — so every shard reads frozen state."""
        from ..native import digest_keys, racing_prescriptions_batch

        t_section = perf_counter()
        n = self.n
        cuts = [(s * n_lanes) // n for s in range(n + 1)]
        stats = [
            {
                "shard": s, "lanes": cuts[s + 1] - cuts[s], "rows": 0,
                "candidates": 0, "owned": 0, "dup": 0, "fresh": 0,
                "scan_s": 0.0, "dedup_s": 0.0, "wall_s": 0.0,
            }
            for s in range(n)
        ]

        def scan_task(s: int):
            lo, hi = cuts[s], cuts[s + 1]
            t0 = perf_counter()
            ind = _NoteBuffer(independence) if independence is not None else None
            slp = _NoteBuffer(sleep) if sleep is not None else None
            ctx = (
                tuple(np.asarray(x)[lo:hi] for x in sleep_ctx)
                if sleep_ctx is not None
                else None
            )
            rows, offsets, lanes, digests = racing_prescriptions_batch(
                traces[lo:hi], lens[lo:hi], recw,
                independence=ind, sleep=slp, sleep_ctx=ctx,
                buffers=self.buffers[s], shard=s,
            )
            keys = digest_keys(digests)
            return (rows, offsets, lanes, digests, keys, ind, slp,
                    perf_counter() - t0)

        slices = self._run([
            (lambda s=s: scan_task(s)) for s in range(n)
        ])
        # Replay the buffered prune notes serially: every slice's
        # static notes first, then every slice's sleep notes — the
        # sequential path's grouping, in the sequential stream order.
        for part in slices:
            if part[5] is not None:
                part[5].replay()
        for part in slices:
            if part[6] is not None:
                part[6].replay()

        # Reassemble the sequential candidate stream (slice-major ==
        # lane-major == the unsharded scan's order).
        rows_parts, lanes_parts, dig_parts, keys_all = [], [], [], []
        off_parts = [np.zeros(1, np.int64)]
        row_base = 0
        for s, part in enumerate(slices):
            rows_s, offsets_s, lanes_s, digests_s, keys_s = part[:5]
            stats[s]["rows"] = int(len(rows_s))
            stats[s]["candidates"] = len(keys_s)
            stats[s]["scan_s"] = part[7]
            if len(keys_s):
                rows_parts.append(rows_s)
                off_parts.append(np.asarray(offsets_s, np.int64)[1:] + row_base)
                lanes_parts.append(
                    np.asarray(lanes_s, np.int64) + cuts[s]
                )
                dig_parts.append(digests_s)
                keys_all.extend(keys_s)
                row_base += int(offsets_s[-1])
        if keys_all:
            rows_all = np.concatenate(rows_parts, axis=0)
            offsets_all = np.concatenate(off_parts)
            lanes_all = np.concatenate(lanes_parts)
            digests_all = np.concatenate(dig_parts, axis=0)
            shard_ids = shard_ids_of_digests(digests_all, n)
        else:
            w = int(np.asarray(traces).shape[2]) if n_lanes else recw
            rows_all = np.zeros((0, min(w, recw)), np.int32)
            offsets_all = np.zeros(1, np.int64)
            lanes_all = np.zeros(0, np.int64)
            shard_ids = np.zeros(0, np.int64)

        # Phase B: disjoint membership against the pre-round sets,
        # each shard over its own digest-range slice.
        known_dup = np.zeros(len(keys_all), bool)

        def dedup_task(s: int):
            t0 = perf_counter()
            exp = explored.slices[s]
            sup = suppressed.slices[s]
            owned = np.flatnonzero(shard_ids == s).tolist()
            dups = 0
            for i in owned:
                k = keys_all[i]
                if k in exp or k in sup:
                    known_dup[i] = True
                    dups += 1
            return s, len(owned), dups, perf_counter() - t0

        if len(keys_all):
            for s, owned, dups, dt in self._run([
                (lambda s=s: dedup_task(s)) for s in range(n)
            ]):
                stats[s]["owned"] = owned
                stats[s]["dup"] = dups
                stats[s]["dedup_s"] = dt

        wall_s = perf_counter() - t_section
        for s in range(n):
            busy = stats[s]["scan_s"] + stats[s]["dedup_s"]
            stats[s]["wall_s"] = round(busy, 6)
            self.busy_seconds[s] += busy
            self.busy_total += busy
        self.section_seconds += wall_s
        self.rounds += 1
        self.last_stats = stats
        return ShardScan(
            rows_all, offsets_all, lanes_all, keys_all, known_dup,
            shard_ids.tolist(), stats, wall_s,
        )

    def class_round(self, scan: ShardScan, traces, lens, recw: int, sleep):
        """Phase C (parallel): Mazurkiewicz class keys for this round's
        admissible candidates. ``canonical_class_key`` is a pure
        function of one candidate's rows, its lane's delivery
        positions, and the static commute matrix — no explored state —
        so each digest-range shard precomputes the keys for the
        candidates it OWNS and the canonical merge just looks them up.
        This is the host half's dominant cost on class-tracked runs
        (the greedy-topo-sort canonicalization), which is exactly what
        makes the serial merge fraction small at high shard counts.
        Keys for candidates the merge later drops as same-round
        duplicates are computed wastefully — bounded by the same-round
        duplicate count, and never observable (the key is pure)."""
        keys = scan.keys
        if not len(keys) or sleep is None:
            return {}
        survivors = np.flatnonzero(~scan.known_dup)
        if not len(survivors):
            return {}
        from ..device.core import REC_DELIVERY, REC_TIMER

        n = self.n
        offs = scan.offsets
        lanes = scan.lanes
        rows = scan.rows
        shard_ids = scan.shard_ids
        owned = [[] for _ in range(n)]
        for k in survivors.tolist():
            owned[shard_ids[k]].append(k)
        t_section = perf_counter()
        out: dict = {}

        def class_task(s: int):
            t0 = perf_counter()
            lane_pos: dict = {}
            res = []
            for k in owned[s]:
                lo, hi = int(offs[k]), int(offs[k + 1])
                b = int(lanes[k])
                pos = lane_pos.get(b)
                if pos is None:
                    recs = traces[b, : int(lens[b]), :recw]
                    pos = np.nonzero(
                        np.isin(recs[:, 0], (REC_DELIVERY, REC_TIMER))
                    )[0]
                    lane_pos[b] = pos
                m = hi - lo
                res.append((k, sleep.class_key(
                    rows[lo:hi], list(pos[: m - 1]) + [None], recw
                )))
            return s, res, perf_counter() - t0

        for s, res, dt in self._run([
            (lambda s=s: class_task(s)) for s in range(n)
        ]):
            out.update(res)
            self.last_stats[s]["class_s"] = dt
            self.last_stats[s]["wall_s"] = round(
                self.last_stats[s]["wall_s"] + dt, 6
            )
            self.busy_seconds[s] += dt
            self.busy_total += dt
        self.section_seconds += perf_counter() - t_section
        return out


class HostHalfTimer:
    """Wall-times one DeviceDPOR's ``_process_round`` (the host half of
    every round) and converts the total to the **uncontended**
    shared-core convention the bench and the host-shard calibration
    measure: the parallel sections count as ``busy_total / n`` — each
    shard billed as if it owned its core — while everything serial
    (including the canonical merge) counts at wall. At 1 shard this is
    exactly the measured wall, so A/B curves share one metric.
    Wrap BEFORE exploring; deltas are taken from construction time."""

    def __init__(self, dpor):
        self.dpor = dpor
        self.seconds = 0.0
        self.rounds = 0
        sharder = getattr(dpor, "_sharder", None)
        self._busy0 = sharder.busy_total if sharder is not None else 0.0
        self._section0 = (
            sharder.section_seconds if sharder is not None else 0.0
        )
        inner = dpor._process_round

        def timed(*args, **kwargs):
            t0 = perf_counter()
            try:
                return inner(*args, **kwargs)
            finally:
                self.seconds += perf_counter() - t0
                self.rounds += 1

        dpor._process_round = timed

    def uncontended_seconds(self) -> float:
        sharder = getattr(self.dpor, "_sharder", None)
        if sharder is None:
            return max(1e-9, self.seconds)
        busy = sharder.busy_total - self._busy0
        section = sharder.section_seconds - self._section0
        return max(1e-9, self.seconds - section + busy / sharder.n)

    def rounds_per_sec(self) -> float:
        return self.rounds / self.uncontended_seconds()
