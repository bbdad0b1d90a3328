"""Racing analysis: ctypes bindings to the C++ analyzer
(native/trace_analysis.cpp) with semantics-identical NumPy fallbacks.

This is the host-side hot loop of batched device DPOR: every round scans
every lane's parent-tracked trace for co-enabled same-receiver pairs
(reference: DPORwHeuristics.scala:1122-1139). At batch 32 x ~100-record
traces the O(n^2) Python scan dominates frontier turnaround; the native
path runs it over raw int32 buffers with per-record ancestor bitsets.

Two tiers:

- ``racing_pair_scan`` — one lane's (i, j) racing pairs (the original
  per-lane surface, kept for the legacy host path and parity tests).
- ``racing_prescriptions_batch`` — a whole round's stacked lane records
  in ONE call, returning fully-assembled backtrack prescriptions as
  packed int32 rows + per-prescription offsets + owning lanes. This is
  the frontier hot path: one ctypes crossing (or one vectorized NumPy
  pass) per round instead of a scan per lane and a Python tuple loop
  per racing pair.
- ``prescription_digests`` — order-sensitive 128-bit content digests
  over the packed rows, computed in one vectorized NumPy pass; the
  explored-set membership check dedups on these instead of
  materializing a Python tuple per (mostly redundant) prescription.

Build (native/build.py): the library is rebuilt whenever the source's
content hash changes; the compiler is ``$CXX`` when set, else the first of
g++ / clang++ / cc that links. When no native library can be built the
NumPy fallback is used and a ONE-TIME log line + ``native.analysis_fallback``
obs counter fire, so a silent native-miss perf regression shows up in
telemetry instead of only in wall clocks.
"""

from __future__ import annotations

import ctypes
import logging
import os
from typing import Optional, Tuple

import numpy as np

from .build import build_library, native_source

_SRC = native_source("trace_analysis.cpp")

_log = logging.getLogger("demi_tpu.native")

_lib: Optional[ctypes.CDLL] = None
_lib_tried = False
_fallback_noted = False


def _delivery_kinds():
    # Single source of truth for record kinds (the C++ is_delivery must
    # mirror these; see native/trace_analysis.cpp header comment).
    from ..device.core import REC_DELIVERY, REC_TIMER

    return (REC_DELIVERY, REC_TIMER)


def note_fallback(reason: str) -> None:
    """One-time marker that the Python/NumPy path is serving a hot loop
    the native analyzer exists for: a log line (visible regardless of
    telemetry) plus the ``native.analysis_fallback`` counter (visible in
    every obs snapshot), so a silent native-miss regression is
    diagnosable from either surface."""
    global _fallback_noted
    if _fallback_noted:
        return
    _fallback_noted = True
    from .. import obs

    # Direct series write (the Counter analog of Gauge.force_set): this
    # rare, load-bearing fact must reach every snapshot even when the
    # first fallback happens before obs.enable() — a gated inc would be
    # silently dropped and the one-time latch never fires again.
    counter = obs.counter("native.analysis_fallback")
    key = f"reason={reason}"
    counter.series[key] = counter.series.get(key, 0) + 1
    _log.warning(
        "demi_tpu native analysis unavailable (%s): racing analysis runs "
        "on the NumPy fallback — correct, but slower per frontier round",
        reason,
    )


def _load_native() -> Optional[ctypes.CDLL]:
    global _lib, _lib_tried
    if _lib_tried:
        return _lib
    _lib_tried = True
    if not os.path.exists(_SRC):
        note_fallback("source missing")
        return None
    so = build_library(_SRC, "libdemi_analysis")
    if so is None:
        note_fallback("no working C++ compiler")
        return None
    try:
        lib = ctypes.CDLL(so)
        lib.demi_racing_pairs.restype = ctypes.c_int64
        lib.demi_racing_pairs.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int64,
        ]
        lib.demi_racing_prescriptions.restype = ctypes.c_int64
        lib.demi_racing_prescriptions.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p,
            ctypes.c_void_p,
        ]
        lib.demi_racing_prescriptions_static.restype = ctypes.c_int64
        lib.demi_racing_prescriptions_static.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p,
        ]
        lib.demi_racing_prescriptions_sleep.restype = ctypes.c_int64
        lib.demi_racing_prescriptions_sleep.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p,
        ]
        _lib = lib
    except (OSError, AttributeError) as exc:
        note_fallback(f"load failed: {type(exc).__name__}")
        _lib = None
    return _lib


def analysis_native_available() -> bool:
    return _load_native() is not None


def scan_backend() -> str:
    """Which implementation serves the racing scan in this process:
    ``"native"`` (the C++ library built from native/trace_analysis.cpp)
    or ``"numpy"`` (no library could be built or loaded, or the launch
    supervisor degraded the surface)."""
    from ..persist.supervisor import SUPERVISOR

    if _load_native() is None or SUPERVISOR.degraded("native.analysis"):
        return "numpy"
    return "native"


def _py_racing_pairs(recs: np.ndarray) -> np.ndarray:
    """Same semantics as the C++ scan: (i, j) both deliveries, same
    receiver, j's message already created at i (parent(j) < i), and the
    race is IMMEDIATE under the two-edge happens-before closure (creation
    `parent` + program-order `prev` columns): no k with i in past(k) and
    k in past(j). See native/trace_analysis.cpp's header for why pruning
    non-immediate pairs keeps violation recall."""
    n, w = recs.shape
    parent_col, prev_col = w - 2, w - 1
    words = (n + 63) // 64
    past = np.zeros((n, words), np.uint64)
    interp = np.zeros((n, words), np.uint64)
    for p in range(n):
        for q in (int(recs[p, parent_col]), int(recs[p, prev_col])):
            if 0 <= q < p:
                interp[p] |= past[q] | interp[q]
                past[p] |= past[q]
                past[p, q // 64] |= np.uint64(1) << np.uint64(q % 64)
    is_delivery = np.isin(recs[:, 0], _delivery_kinds())
    positions = np.nonzero(is_delivery)[0]
    out = []
    for jj, j in enumerate(positions):
        cj = int(recs[j, parent_col])
        for i in positions[:jj]:
            if recs[i, 2] != recs[j, 2]:
                continue
            if cj >= int(i):
                continue
            if (interp[j, i // 64] >> np.uint64(i % 64)) & np.uint64(1):
                continue  # interposed: not an immediate race
            out.append((int(i), int(j)))
    return np.asarray(out, np.int32).reshape(-1, 2)


def racing_pair_scan(recs: np.ndarray) -> np.ndarray:
    """All racing (i, j) record-position pairs of one lane's trace
    ([k, 2] int32). Native when available, Python otherwise."""
    recs = np.ascontiguousarray(recs, np.int32)
    n, w = recs.shape
    from ..persist.supervisor import SUPERVISOR

    # Shares the batch entry's degradation label: one poisoned library
    # makes every symbol suspect, so a degraded analyzer routes ALL
    # native scans to their Python/NumPy twins.
    lib = None if SUPERVISOR.degraded("native.analysis") else _load_native()
    if lib is None or n == 0:
        if lib is None and not SUPERVISOR.degraded("native.analysis"):
            note_fallback("no native library")
        return _py_racing_pairs(recs)

    def native_pairs(_attempt: int):
        cap = max(64, n * 4)
        while True:
            out = np.empty((cap, 2), np.int32)
            count = lib.demi_racing_pairs(
                recs.ctypes.data, n, w, out.ctypes.data, cap
            )
            if count <= cap:
                return out[:count].copy()
            cap = int(count)

    return SUPERVISOR.run(
        native_pairs, label="native.analysis",
        fallback=lambda: _py_racing_pairs(recs),
    )


# ---------------------------------------------------------------------------
# Batch-native prescription assembly (one call per frontier round)
# ---------------------------------------------------------------------------

class ScanBuffers:
    """Reusable output buffers (+ their adaptive capacities) for ONE
    caller of ``racing_prescriptions_batch`` — one instance per
    (DeviceDPOR instance, admission shard), NOT per call, so concurrent
    shard scans each grow a private hint instead of regrowing and
    contending on one shared ``size_hint``, and a steady-state round
    allocates nothing.

    Capacities only grow (an overflowed round ratchets them up); the
    arrays returned by the scan are VIEWS over these buffers, valid
    until the owner's next scan — exactly the lifetime the frontier
    round's admission loop needs."""

    __slots__ = ("cap_presc", "cap_rows", "width",
                 "rows", "offsets", "lanes", "digests")

    def __init__(self, size_hint: Optional[Tuple[int, int]] = None):
        self.cap_presc = 0 if size_hint is None else max(64, int(size_hint[0]))
        self.cap_rows = 0 if size_hint is None else max(256, int(size_hint[1]))
        self.width = 0
        self.rows = None
        self.offsets = None
        self.lanes = None
        self.digests = None

    def ensure(self, cap_presc: int, cap_rows: int, w: int):
        """Arrays of at least the requested capacities (allocating only
        on growth or a record-width change). The native scan writes
        ``offsets[0..n]`` itself, so reuse needs no re-zeroing."""
        if self.rows is None or w != self.width or cap_rows > self.cap_rows:
            self.cap_rows = max(cap_rows, self.cap_rows)
            self.width = w
            self.rows = np.empty((self.cap_rows, w), np.int32)
        if self.offsets is None or cap_presc > self.cap_presc:
            self.cap_presc = max(cap_presc, self.cap_presc)
            self.offsets = np.zeros(self.cap_presc + 1, np.int64)
            self.lanes = np.empty(self.cap_presc, np.int32)
            self.digests = np.empty((self.cap_presc, 2), np.uint64)
        return self.rows, self.offsets, self.lanes, self.digests


def racing_prescriptions_batch(
    records: np.ndarray, lens: np.ndarray, rec_width: int,
    size_hint: Optional[Tuple[int, int]] = None,
    independence=None,
    sleep=None,
    sleep_ctx: Optional[Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]] = None,
    buffers: Optional[ScanBuffers] = None,
    shard: Optional[int] = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Batch racing analysis over one round's stacked lane records.

    ``records`` is [batch, rmax, >=rec_width] int32 (trailing padding
    columns are sliced off — the scan derives the parent/prev columns
    from the LAST two of ``rec_width``); ``lens`` the per-lane trace
    lengths. Returns ``(rows, offsets, lanes, digests)``:

    - ``rows``    — [n_rows, rec_width] int32, every prescription's
      records packed back to back (a VIEW over the scan buffer — no
      copy of what can be megabytes per round);
    - ``offsets`` — [n_presc + 1] int64, prescription k's rows are
      ``rows[offsets[k]:offsets[k+1]]``;
    - ``lanes``   — [n_presc] int32, the lane each prescription came
      from;
    - ``digests`` — [n_presc, 2] uint64 content digests of each block
      (the ``prescription_digests`` key space; computed in C at O(1)
      per pair via running prefix digests, or by the vectorized NumPy
      pass on the fallback path).

    Prescription k is a backtrack point of its lane: the delivery records
    strictly before the race's first delivery, plus the flipped record,
    lane-major and in ``racing_pair_scan``'s pair order (the tests hold
    it to a per-lane assembly they keep: ``_legacy_prescriptions``).
    One native call (or one NumPy pass) serves the whole round. ``size_hint=(n_presc, n_rows)`` (e.g. the previous
    round's totals) sizes the output buffers; an overflow retries once
    with exact sizes.

    ``independence`` (an analysis.StaticIndependence or None) prunes
    racing pairs whose flip is provably a no-op: content-identical
    ("fungible") records, and tag pairs the static field-effect matrix
    proves commuting. The native scan consults the fixed-shape matrix
    per pair (``demi_racing_prescriptions_static``); the NumPy twin —
    also used for ``independence.audit`` runs, which must materialize
    what was pruned — post-filters with identical placement and counts.
    Pruned counts report via ``independence.note_pruned``.

    ``sleep`` (an analysis.SleepSets or None) + ``sleep_ctx`` =
    ``(sleep_rows [B, S, w] int32, wake [B, S] int32, slept [B] int32,
    presc_deliv [B] int32)`` additionally refuse reversals whose flip is
    asleep at the branch (sleep-set membership — the reversal's subtree
    is covered by an earlier-admitted sibling's) or whose branch lies
    beyond the lane's redundant-suffix marker. Native entry
    ``demi_racing_prescriptions_sleep``; the NumPy twin
    (``_apply_sleep_filter``) is bit-identical and serves audit runs.
    Applied AFTER the static filter (the shared counter contract);
    counts report via ``sleep.note_pruned``.

    ``buffers`` (a ``ScanBuffers`` or None) supplies caller-owned output
    buffers whose capacities persist across calls — the per-(instance,
    shard) home of the adaptive size hint. Returned arrays then view the
    caller's buffers and stay valid until that caller's next scan.
    ``shard`` labels the ``native.scan_seconds`` wall counter so the
    sharded admission pipeline's per-shard scan cost is attributable
    (distinct labels write distinct series keys — safe from concurrent
    shard threads)."""
    from time import perf_counter

    _t_scan = perf_counter()

    def _note_scan_seconds():
        from .. import obs

        dt = perf_counter() - _t_scan
        if shard is not None:
            obs.counter("native.scan_seconds").inc(
                round(dt, 9), shard=str(shard)
            )
        else:
            obs.counter("native.scan_seconds").inc(round(dt, 9))

    records = np.ascontiguousarray(
        np.asarray(records)[:, :, :rec_width], np.int32
    )
    batch, rmax, w = records.shape
    lens = np.clip(np.asarray(lens, np.int32), 0, rmax)
    if batch == 0 or rmax == 0:
        return (
            np.zeros((0, w), np.int32), np.zeros(1, np.int64),
            np.zeros(0, np.int32), np.zeros((0, 2), np.uint64),
        )
    sleep_on = (
        sleep is not None and sleep.prune and sleep_ctx is not None
    )

    def numpy_path():
        """The semantics-identical host twin — also the launch
        supervisor's degradation target when the native scan keeps
        failing (persist/supervisor.py)."""
        rows, offsets, lanes = _np_racing_prescriptions(records, lens)
        out = (rows, offsets, lanes, prescription_digests(rows, offsets))
        if independence is not None:
            out = _apply_static_filter(records, lens, *out,
                                       independence=independence)
        if sleep_on:
            out = _apply_sleep_filter(*out, sleep=sleep, sleep_ctx=sleep_ctx)
        return out

    from ..persist.supervisor import SUPERVISOR

    if SUPERVISOR.degraded("native.analysis"):
        out = numpy_path()
        _note_scan_seconds()
        return out
    lib = _load_native()
    if lib is None:
        note_fallback("no native library")
        out = numpy_path()
        _note_scan_seconds()
        return out
    lens = np.ascontiguousarray(lens)
    # The native per-pair filter serves the hot path; audit runs (which
    # must materialize every pruned prescription) post-filter the
    # unfiltered native stream with the identically-placed NumPy twin.
    native_filter = independence is not None and not independence.audit
    matrix = fungible = None
    if native_filter:
        matrix = independence.device_matrix()
        fungible = independence.fungible
        if matrix is None and not fungible:
            native_filter = False
            independence = None  # nothing to prune
    # The native sleep filter composes with the static one in a single
    # scan; an audit-mode SleepSets (which must materialize what it
    # pruned) or an audit-mode independence falls back to the NumPy
    # twins so both filters stay identically placed.
    native_sleep = (
        sleep_on and not sleep.audit
        and (independence is None or native_filter)
    )
    if native_sleep:
        s_rows = np.ascontiguousarray(sleep_ctx[0], np.int32)
        s_wake = np.ascontiguousarray(sleep_ctx[1], np.int32)
        s_slept = np.ascontiguousarray(sleep_ctx[2], np.int32)
        s_presc = np.ascontiguousarray(sleep_ctx[3], np.int32)
        scap = s_rows.shape[1] if s_rows.ndim == 3 else 0
        if scap == 0:
            native_sleep = False
    if size_hint is not None:
        cap_presc = max(64, int(size_hint[0]))
        cap_rows = max(256, int(size_hint[1]))
    elif buffers is not None and buffers.cap_presc:
        # The caller's persistent buffers ARE the size hint: their
        # capacities ratcheted up on every past overflow, so a
        # steady-state round reuses them without a single allocation.
        cap_presc, cap_rows = buffers.cap_presc, buffers.cap_rows
    else:
        cap_presc = max(64, 4 * int(lens.sum()))
        cap_rows = max(256, cap_presc * max(8, rmax // 4))

    def native_scan(_attempt: int):
        return _native_scan_loop()

    def _native_scan_loop():
        nonlocal cap_presc, cap_rows
        while True:
            out = _native_scan_once()
            if out is not None:
                return out

    def _native_scan_once():
        nonlocal cap_presc, cap_rows
        if buffers is not None:
            rows, offsets, lanes, digests = buffers.ensure(
                cap_presc, cap_rows, w
            )
            cap_presc, cap_rows = buffers.cap_presc, buffers.cap_rows
        else:
            rows = np.empty((cap_rows, w), np.int32)
            offsets = np.zeros(cap_presc + 1, np.int64)
            lanes = np.empty(cap_presc, np.int32)
            digests = np.empty((cap_presc, 2), np.uint64)
        total_rows = ctypes.c_int64(0)
        if native_sleep:
            pruned = np.zeros(3, np.int64)
            n = lib.demi_racing_prescriptions_sleep(
                records.ctypes.data, lens.ctypes.data,
                batch, rmax, w,
                matrix.ctypes.data if matrix is not None else None,
                len(matrix) if matrix is not None else 0,
                1 if fungible else 0,
                s_rows.ctypes.data, scap,
                s_wake.ctypes.data, s_slept.ctypes.data,
                s_presc.ctypes.data,
                rows.ctypes.data, cap_rows,
                offsets.ctypes.data, lanes.ctypes.data, cap_presc,
                digests.ctypes.data,
                ctypes.byref(total_rows),
                pruned.ctypes.data,
            )
        elif native_filter:
            pruned = np.zeros(2, np.int64)
            n = lib.demi_racing_prescriptions_static(
                records.ctypes.data, lens.ctypes.data,
                batch, rmax, w,
                matrix.ctypes.data if matrix is not None else None,
                len(matrix) if matrix is not None else 0,
                1 if fungible else 0,
                rows.ctypes.data, cap_rows,
                offsets.ctypes.data, lanes.ctypes.data, cap_presc,
                digests.ctypes.data,
                ctypes.byref(total_rows),
                pruned.ctypes.data,
            )
        else:
            n = lib.demi_racing_prescriptions(
                records.ctypes.data, lens.ctypes.data,
                batch, rmax, w,
                rows.ctypes.data, cap_rows,
                offsets.ctypes.data, lanes.ctypes.data, cap_presc,
                digests.ctypes.data,
                ctypes.byref(total_rows),
            )
        if n <= cap_presc and total_rows.value <= cap_rows:
            out = (
                rows[: total_rows.value],
                offsets[: n + 1],
                lanes[:n],
                digests[:n],
            )
            return out, (pruned if (native_filter or native_sleep) else None)
        cap_presc = max(cap_presc, int(n))
        cap_rows = max(cap_rows, int(total_rows.value))
        return None  # buffers grown; the loop retries with exact sizes

    # Bounded retry + permanent degradation to the NumPy twin: a native
    # analyzer that segfault-adjacently raises (bad library rebuild,
    # corrupted .so) must not kill an hours-long soak — the twin is
    # bit-identical, just slower. --strict-io turns this into an error.
    # The supervised region is the PURE scan (local buffers only):
    # pruning-ledger notes and the host post-filters run once, after,
    # so a retried attempt can never double-count pruning stats.
    result = SUPERVISOR.run(
        lambda attempt: ("native", native_scan(attempt)),
        label="native.analysis",
        fallback=lambda: ("host", numpy_path()),
    )
    if result[0] == "host":
        _note_scan_seconds()
        return result[1]
    out, pruned = result[1]
    if native_filter:
        if independence is not None:
            independence.note_pruned(
                int(pruned[0]), int(pruned[1]), tier="device"
            )
    elif independence is not None:
        out = _apply_static_filter(records, lens, *out,
                                   independence=independence)
    if native_sleep:
        sleep.note_pruned(sleep=int(pruned[2]), tier="device")
    elif sleep_on:
        out = _apply_sleep_filter(*out, sleep=sleep, sleep_ctx=sleep_ctx)
    _note_scan_seconds()
    return out


def _apply_static_filter(
    records: np.ndarray, lens: np.ndarray,
    rows: np.ndarray, offsets: np.ndarray, lanes: np.ndarray,
    digests: np.ndarray, independence,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """NumPy twin of the native static-independence filter: drop
    prescriptions whose racing pair is a provable no-op flip. Same
    predicate, same ordering (fungible counted before commute), bit-
    identical surviving stream — pinned by tests/test_lint.py. Under
    ``independence.audit`` every pruned prescription is materialized
    into ``independence.pruned_prescriptions`` (the bench's exact-no-op
    assertion reads it)."""
    n = len(lanes)
    if n == 0:
        return rows, offsets, lanes, digests
    w = rows.shape[1]
    offsets = np.asarray(offsets, np.int64)
    lanes = np.asarray(lanes)
    mlen = offsets[1:] - offsets[:-1]
    rows_j = rows[offsets[1:] - 1]
    # The flipped-past record: a prescription with m rows flips past its
    # lane's (m-1)-th delivery (0-based, position order).
    rows_i = np.empty_like(rows_j)
    for b in np.unique(lanes):
        recs = records[b, : int(lens[b])]
        pos = np.nonzero(np.isin(recs[:, 0], _delivery_kinds()))[0]
        sel = lanes == b
        rows_i[sel] = recs[pos][mlen[sel] - 1]
    fung = np.zeros(n, bool)
    if independence.fungible:
        rec_timer = _delivery_kinds()[1]
        fung = (
            (rows_i[:, 0] == rows_j[:, 0])
            & (rows_i[:, 2] == rows_j[:, 2])
            & np.all(rows_i[:, 3: w - 2] == rows_j[:, 3: w - 2], axis=1)
            & ((rows_i[:, 0] == rec_timer) | (rows_i[:, 1] == rows_j[:, 1]))
        )
    comm = np.zeros(n, bool)
    matrix = independence.device_matrix()
    if matrix is not None:
        m_sz = len(matrix)
        ti = rows_i[:, 3].astype(np.int64)
        tj = rows_j[:, 3].astype(np.int64)
        ia = np.where((ti >= 0) & (ti < m_sz - 1), ti, m_sz - 1)
        ib = np.where((tj >= 0) & (tj < m_sz - 1), tj, m_sz - 1)
        comm = matrix[ia, ib].astype(bool) & ~fung
    prune = fung | comm
    independence.note_pruned(
        int(fung.sum()), int(comm.sum()), tier="device"
    )
    if not prune.any():
        return rows, offsets, lanes, digests
    if independence.audit:
        for k in np.flatnonzero(prune):
            lo, hi = int(offsets[k]), int(offsets[k + 1])
            independence.note_pruned_prescription(
                tuple(tuple(int(x) for x in r) for r in rows[lo:hi])
            )
    keep = ~prune
    row_keep = np.repeat(keep, mlen)
    new_mlen = mlen[keep]
    new_offsets = np.zeros(len(new_mlen) + 1, np.int64)
    np.cumsum(new_mlen, out=new_offsets[1:])
    return (
        np.ascontiguousarray(rows[row_keep]),
        new_offsets,
        lanes[keep],
        np.asarray(digests)[keep],
    )


def _apply_sleep_filter(
    rows: np.ndarray, offsets: np.ndarray, lanes: np.ndarray,
    digests: np.ndarray, sleep, sleep_ctx,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """NumPy twin of the native sleep-set filter (placement: AFTER the
    static filter — the shared counter contract): drop prescriptions
    whose flip is content-identical to a sleeping row still asleep at
    the branch ordinal (``mlen - 1``, at/after the lane's node), or
    whose branch lies beyond the lane's redundant-suffix marker. Bit-
    identical surviving stream vs ``demi_racing_prescriptions_sleep``
    (tests/test_sleep_sets.py); under ``sleep.audit`` every pruned
    prescription is materialized into ``sleep.pruned_prescriptions``."""
    n = len(lanes)
    if n == 0:
        return rows, offsets, lanes, digests
    sleep_rows, wake, slept, presc_deliv = (
        np.asarray(x) for x in sleep_ctx
    )
    w = rows.shape[1]
    offsets = np.asarray(offsets, np.int64)
    lanes = np.asarray(lanes)
    mlen = offsets[1:] - offsets[:-1]
    branch = mlen - 1  # deliveries strictly before the flipped race
    flips = rows[offsets[1:] - 1]
    scap = sleep_rows.shape[1] if sleep_rows.ndim == 3 else 0
    prune = branch > slept[lanes]
    if scap:
        s = sleep_rows[lanes]  # [n, scap, w]
        valid = s[:, :, 0] != 0
        rec_timer = _delivery_kinds()[1]
        fung = (
            (s[:, :, 0] == flips[:, None, 0])
            & (s[:, :, 2] == flips[:, None, 2])
            & np.all(s[:, :, 3: w - 2] == flips[:, None, 3: w - 2], axis=2)
            & ((flips[:, None, 0] == rec_timer)
               | (s[:, :, 1] == flips[:, None, 1]))
        )
        asleep = wake[lanes] >= branch[:, None]
        at_node = branch >= presc_deliv[lanes]
        prune = prune | (
            at_node & ~prune
            & np.any(valid & fung & asleep, axis=1)
        )
    sleep.note_pruned(sleep=int(prune.sum()), tier="device")
    if not prune.any():
        return rows, offsets, lanes, digests
    if sleep.audit:
        for k in np.flatnonzero(prune):
            lo, hi = int(offsets[k]), int(offsets[k + 1])
            sleep.note_pruned_prescription(
                tuple(tuple(int(x) for x in r) for r in rows[lo:hi])
            )
    keep = ~prune
    row_keep = np.repeat(keep, mlen)
    new_mlen = mlen[keep]
    new_offsets = np.zeros(len(new_mlen) + 1, np.int64)
    np.cumsum(new_mlen, out=new_offsets[1:])
    return (
        np.ascontiguousarray(rows[row_keep]),
        new_offsets,
        lanes[keep],
        np.asarray(digests)[keep],
    )


def _np_racing_prescriptions(
    records: np.ndarray, lens: np.ndarray
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Semantics-identical NumPy fallback for the batch entry point:
    per-lane pair scans (native pair scan when only the batch symbol is
    missing, pure Python otherwise) with prescription rows assembled by
    array gathers — no per-record Python tuple loop."""
    batch, rmax, w = records.shape
    blocks = []
    counts = [0]
    lanes = []
    for b in range(batch):
        recs = records[b, : int(lens[b])]
        pairs = racing_pair_scan(recs)
        if len(pairs) == 0:
            continue
        is_delivery = np.isin(recs[:, 0], _delivery_kinds())
        positions = np.nonzero(is_delivery)[0]
        deliv_rows = recs[positions]
        for i, j in pairs:
            k = int(np.searchsorted(positions, i))
            blocks.append(deliv_rows[:k])
            blocks.append(recs[int(j)][None, :])
            counts.append(k + 1)
            lanes.append(b)
    if not lanes:
        return (
            np.zeros((0, w), np.int32),
            np.zeros(1, np.int64),
            np.zeros(0, np.int32),
        )
    rows = np.concatenate(blocks, axis=0).astype(np.int32, copy=False)
    offsets = np.cumsum(np.asarray(counts, np.int64))
    return rows, offsets, np.asarray(lanes, np.int32)


# ---------------------------------------------------------------------------
# Vectorized prescription digests (explored-set membership keys)
# ---------------------------------------------------------------------------

# Order-sensitive polynomial digest over uint64 wraparound arithmetic,
# two independent lanes => 128 bits. The block multiplier is ODD, hence
# invertible mod 2^64: a block [s, e)'s hash
#     h = OFF * P^(e-s) + sum_t mix(r[t]) * P^(e-1-t)
# rewrites as OFF * P^(e-s) + P^(e-1) * (S[e] - S[s]) with
# S = cumsum(mix(r) * Pinv^t), so every block of the packed stream is
# digested from ONE pass of cumulative products/sums — no per-
# prescription Python work.
_COL_MULT = np.uint64(0x100000001B3)  # odd (FNV prime)
_BLOCK_P = (np.uint64(0x9E3779B97F4A7C15), np.uint64(0xC2B2AE3D27D4EB4F))
_BLOCK_OFF = (np.uint64(0xCBF29CE484222325), np.uint64(0x84222325CBF29CE4))
_SALTS = (np.uint64(0xA0761D6478BD642F), np.uint64(0xE7037ED1A0B428DB))
_BLOCK_PINV = tuple(
    np.uint64(pow(int(p), -1, 1 << 64)) for p in _BLOCK_P
)


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer, vectorized (uint64 wraparound)."""
    x = x + np.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def _row_values(rows: np.ndarray) -> np.ndarray:
    """Per-row value: polynomial over the columns (uint64 wraparound)."""
    rows = np.asarray(rows)
    n, w = rows.shape
    if not n:
        return np.zeros(0, np.uint64)
    col_pow = np.ones(w, np.uint64)
    if w > 1:
        col_pow[1:] = _COL_MULT
    col_pow = np.cumprod(col_pow)[::-1]
    r64 = rows.astype(np.uint32).astype(np.uint64)
    return (r64 * col_pow[None, :]).sum(axis=1, dtype=np.uint64)


def prescription_digests(rows: np.ndarray, offsets: np.ndarray) -> np.ndarray:
    """[n_presc, 2] uint64 content digests of the packed prescription
    stream (``rows``/``offsets`` as returned by
    ``racing_prescriptions_batch``). Equal digests <=> equal row blocks
    (up to 128-bit collision odds — the same trust level as the
    blake2b-16 prefix digests that key the fork trunk cache). One
    vectorized pass for the whole round."""
    offsets = np.asarray(offsets, np.int64)
    n_presc = len(offsets) - 1
    out = np.empty((n_presc, 2), np.uint64)
    if n_presc == 0:
        return out
    rv = _row_values(rows)
    n = len(rv)
    starts, ends = offsets[:-1], offsets[1:]
    mlen = ends - starts
    for lane, (P, OFF, SALT, PINV) in enumerate(
        zip(_BLOCK_P, _BLOCK_OFF, _SALTS, _BLOCK_PINV)
    ):
        m = _mix64(rv ^ SALT)
        # P^t and Pinv^t for t in [0, n].
        ppow = np.ones(n + 1, np.uint64)
        pinv_pow = np.ones(n, np.uint64) if n else np.ones(0, np.uint64)
        if n:
            ppow[1:] = P
            ppow = np.cumprod(ppow)
            pinv_pow[1:] = PINV
            pinv_pow = np.cumprod(pinv_pow)
        csum = np.zeros(n + 1, np.uint64)
        if n:
            csum[1:] = np.cumsum(m * pinv_pow, dtype=np.uint64)
        seg = csum[ends] - csum[starts]
        h = OFF * ppow[mlen] + ppow[np.maximum(ends, 1) - 1] * seg
        out[:, lane] = h
    return out


def prefixed_digests(
    rows: np.ndarray, starts: np.ndarray, lengths: np.ndarray,
    flips: np.ndarray,
) -> np.ndarray:
    """[n, 2] uint64 digests of prescriptions held as a shared prefix
    plus one row of their own: prescription k is ``rows[starts[k] :
    starts[k] + lengths[k] - 1]`` followed by ``flips[k]``
    (``lengths[k] == 0`` is the empty prescription) — the explored
    log's columnar form. Same key space as ``prescription_digests``,
    from the same one pass of cumulative products over ``rows``."""
    starts = np.asarray(starts, np.int64)
    mlen = np.asarray(lengths, np.int64)
    out = np.empty((len(starts), 2), np.uint64)
    if not len(starts):
        return out
    rv, fv = _row_values(rows), _row_values(flips)
    n = len(rv)
    ends = starts + np.maximum(mlen - 1, 0)
    top = max(n, int(mlen.max())) + 1
    for lane, (P, OFF, SALT, PINV) in enumerate(
        zip(_BLOCK_P, _BLOCK_OFF, _SALTS, _BLOCK_PINV)
    ):
        ppow = np.ones(top, np.uint64)
        ppow[1:] = P
        ppow = np.cumprod(ppow)
        csum = np.zeros(n + 1, np.uint64)
        if n:
            pinv_pow = np.ones(n, np.uint64)
            pinv_pow[1:] = PINV
            csum[1:] = np.cumsum(
                _mix64(rv ^ SALT) * np.cumprod(pinv_pow), dtype=np.uint64
            )
        prefix = ppow[np.maximum(ends, 1) - 1] * (csum[ends] - csum[starts])
        own = prefix * P + _mix64(fv ^ SALT)
        out[:, lane] = OFF * ppow[mlen] + np.where(
            mlen > 0, own, np.uint64(0)
        )
    return out


def prescription_digest(prescription) -> bytes:
    """Digest of ONE prescription given as a tuple of record tuples (the
    frontier's materialized form) — same key space as
    ``prescription_digests`` over packed rows; used to key seeded and
    root prescriptions into the explored-digest set."""
    if len(prescription) == 0:
        rows = np.zeros((0, 1), np.int32)
    else:
        rows = np.asarray(prescription, np.int32).reshape(
            len(prescription), -1
        )
    offs = np.asarray([0, len(prescription)], np.int64)
    return prescription_digests(rows, offs)[0].tobytes()


def digest_keys(digests: np.ndarray) -> list:
    """The [n, 2] uint64 digest matrix as a list of 16-byte keys — what
    the explored-set membership check hashes on. One bulk ``tobytes``
    plus fixed-width slicing (NOT a numpy 'S16' view, whose bytes_
    conversion strips trailing NULs and would alias distinct digests)."""
    n = len(digests)
    if n == 0:
        return []
    buf = np.ascontiguousarray(digests, np.uint64).tobytes()
    return [buf[i: i + 16] for i in range(0, 16 * n, 16)]
