"""Span-based structured tracing with Chrome/Perfetto trace_event export.

    with span("ddmin.iteration", externals=12):
        ...

Spans nest per thread (strict stack discipline — the context manager
enforces it), record wall-clock microseconds from a process epoch, and
export two ways:

  - ``write_jsonl(path)``: one finished span per line
    ({"name", "ts", "dur", "tid", "args"}) for ad-hoc grepping;
  - ``export_perfetto(path)``: Chrome ``trace_event`` JSON (matched B/E
    duration pairs, monotonic timestamps) loadable in ``ui.perfetto.dev``
    or ``chrome://tracing`` — the fuzz -> minimize -> replay pipeline on
    one timeline.

A span is live when telemetry is on (``demi_tpu.obs.enable()`` /
DEMI_OBS=1) or while a ``jax.profiler`` session is recording. Under a
recording session a live span also opens a
``TraceAnnotation("demi.<name>")``, so it lands on the host plane of the
``.xplane.pb`` on the same clock as the device's operations. Off, a
``span(...)`` costs that check and allocates nothing. A layer above
whose numbers are span durations keeps spans live while it is on
(``live_while``): the launch profiler does (obs/profiler.py), so
DEMI_PROFILE=1 / ``--profile-rounds`` also hold finished spans in TRACER
(up to ``max_spans``) and record the collector's passes.

Every job keeps a row of its own stages (``job_ledger()``): a span that
carries ``job=`` while no row is open on its thread opens one, every
span, slice and count that closes under it folds into the row as it
folds into the tables above, and the row is kept (the last
``_ROWS_MAX``) when that span exits. A process in which a recording
session has been seen keeps *folding* once the session has ended
(``folding()``): a span then stamps its clock pair and adds to the open
row, and nothing else; it records nothing, annotates nothing and leaves
the totals and counts tables alone, so those hold what was live (the
benchmark's traced job) and the rows hold every job after it too (the
window's), ``recorded: false``. A process that never saw a session, with
telemetry off, folds nothing: a ``span(...)`` reads one more bool.

Every span carries the span that caused it (``parent``: the enclosing
span's ``op_b``) and the request it belongs to (``job``: a number from
``new_job()`` given to a root span and inherited below it). At exit a
span folds into a per-name totals table (``stage_totals()``: count,
seconds, and self seconds = duration less what child spans cover), which
is bounded by the number of names; ``stage_count`` keeps plain counts
beside it (``stage_counts()``). While any span is open, each pass of
CPython's collector is a ``gc.pause`` span nested in the stage that
triggered it, so a stage's self time leaves the collector out.

Set-up is measured with no switch at all (``stage`` and the compile
ledger, at the end of this file): it happens once a process, before any
flag has been read, and has a few dozen stages. ``setup_ledger()`` and
``compile_ledger()`` say where the seconds before a command's first job
went, and which jitted functions were traced, lowered, compiled or
loaded from the persistent cache in them.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import gc
import itertools
import json
import os
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from . import metrics as _metrics

# Re-entrant: a collector pass can start at an allocation made while the
# lock is held, and its gc.pause span records from the same thread.
_lock = threading.RLock()
_local = threading.local()
_EPOCH_NS = time.perf_counter_ns()
# Wall-clock anchor of the span timebase: the unix microsecond that span
# ts 0 corresponds to. Captured in the same instant as _EPOCH_NS so
# cross-process stitching (obs/distributed.py) can place every process's
# spans on one absolute timeline: wall_us = _EPOCH_UNIX_US + span.ts.
_EPOCH_UNIX_US = time.time_ns() // 1000
# Global operation counter ticked at every span enter AND exit: within a
# thread it orders B/E events exactly as they happened, which is the only
# tie-break that stays correct for zero-width (sub-microsecond) spans.
_ops = itertools.count()
_jobs = itertools.count(1)
_annotation = None      # jax.profiler.TraceAnnotation, once jax is imported
_gc_hooked = False
_switches: List[Callable[[], bool]] = []    # see live_while
_session_seen = False   # a jax.profiler session has recorded in this process
_ROWS_MAX = 256
# Finished job rows, oldest first (``job_ledger``), under ``_lock``.
_rows: "collections.deque[Dict[str, Any]]" = collections.deque(maxlen=_ROWS_MAX)


def _now_us() -> int:
    return (time.perf_counter_ns() - _EPOCH_NS) // 1000


def new_job() -> int:
    """The next request number of this process: ``DeviceDPOR.explore``
    and ``SweepDriver.sweep`` take one at entry and give it to their
    root span (``span(..., job=n)``); every span below inherits it."""
    return next(_jobs)


def _profiling() -> bool:
    """Whether a ``jax.profiler`` session is recording. The class is
    looked up only once jax is imported: obs/ imports without it."""
    global _annotation
    if _annotation is None:
        prof = sys.modules.get("jax.profiler")
        _annotation = getattr(prof, "TraceAnnotation", None)
        if _annotation is None:
            return False
    return _annotation.is_enabled()


def live_while(switch: Callable[[], bool]) -> None:
    """Keep spans live while ``switch()`` is true: for a layer above
    that reads span durations (the launch profiler's ledger), so that
    this module need not know it."""
    _switches.append(switch)


def live() -> bool:
    """Whether a span entered now records."""
    global _session_seen
    if _metrics.enabled():
        return True
    if _profiling():
        _session_seen = True
        return True
    for switch in _switches:
        if switch():
            return True
    return False


def folding() -> bool:
    """Whether a span entered now folds into its job's row: it is live,
    or a recording session has been seen in this process. What costs
    the device nothing is counted under this; what buys device work (a
    sample, a pull) stays under ``live()``."""
    return _session_seen or live()


def now_us() -> int:
    """Current time in the span timebase (µs since the process epoch)."""
    return _now_us()


def epoch_unix_us() -> int:
    """Unix µs corresponding to span timestamp 0 in this process."""
    return _EPOCH_UNIX_US


def record_span(name: str, ts: int, dur: int, tid: int, **args: Any) -> None:
    """Record one already-finished span directly into TRACER — for spans
    whose begin and end happen on different threads (a fleet lease is
    issued on one handler thread and drained on another), where the
    stack-disciplined ``span(...)`` context manager cannot apply. The
    B/E operation ids are allocated here, so the export tie-break still
    orders the pair correctly against zero-width neighbours. Such a span
    has no stack, so it is not folded into the totals table."""
    TRACER.record(name, ts, max(0, dur), tid, next(_ops), next(_ops), args)


def _export_args(s: Dict[str, Any]) -> Dict[str, Any]:
    """A span's args as exported: its own, plus ``parent`` and ``job``
    where it has them (a root has no parent; a span outside any
    ``explore()`` / ``sweep()`` has no job)."""
    args = s["args"]
    extra = {k: s[k] for k in ("parent", "job") if s.get(k) is not None}
    return {**args, **extra} if extra else args


def _fold(totals: Dict[str, List[int]], name: str, dur_ns: int,
          self_ns: int) -> None:
    t = totals.get(name)
    if t is None:
        t = totals[name] = [0, 0, 0]
    t[0] += 1
    t[1] += dur_ns
    t[2] += self_ns


class Tracer:
    """In-memory collector of finished spans.

    Bounded: a DEMI_OBS=1 soak that nobody exports must not grow memory
    forever, so past ``max_spans`` new spans are counted in ``dropped``
    instead of stored (the prefix of the timeline is kept — B/E pairing
    stays valid because whole spans, not events, are dropped). The
    totals and counts tables are bounded by the number of names, so
    they keep folding past that point."""

    def __init__(self, max_spans: int = 200_000):
        self.spans: List[Dict[str, Any]] = []
        self.max_spans = max_spans
        self.dropped = 0
        # name -> [count, duration ns, self ns]; name -> count
        self.totals: Dict[str, List[int]] = {}
        self.counts: Dict[str, int] = {}

    def record(self, name: str, ts: int, dur: int, tid: int, op_b: int,
               op_e: int, args: Dict[str, Any],
               parent: Optional[int] = None,
               job: Optional[int] = None) -> None:
        rec = {
            "name": name,
            "ts": ts,
            "dur": dur,
            "tid": tid,
            "op_b": op_b,
            "op_e": op_e,
            "args": args,
            "parent": parent,
            "job": job,
        }
        with _lock:
            if len(self.spans) >= self.max_spans:
                self.dropped += 1
                return
            self.spans.append(rec)

    def fold(self, name: str, dur_ns: int, self_ns: int) -> None:
        with _lock:
            _fold(self.totals, name, dur_ns, self_ns)

    def clear(self) -> None:
        with _lock:
            self.spans.clear()
            self.dropped = 0
            self.totals.clear()
            self.counts.clear()
            _rows.clear()

    # -- exports ------------------------------------------------------------
    def write_jsonl(self, path: str) -> None:
        with open(path, "a") as f:
            for s in self.spans:
                f.write(json.dumps({
                    "name": s["name"], "ts": s["ts"], "dur": s["dur"],
                    "tid": s["tid"], "args": _export_args(s),
                }) + "\n")

    def to_trace_events(self) -> List[Dict[str, Any]]:
        """Matched B/E pairs sorted by (ts, operation order). Within a
        thread timestamps are non-decreasing in operation order, so the
        sort preserves the exact enter/exit sequence — begin/end events
        nest properly for any span durations, including zero-width."""
        pid = os.getpid()
        events = []
        for s in self.spans:
            base = {"name": s["name"], "pid": pid, "tid": s["tid"],
                    "cat": "demi"}
            events.append(
                {**base, "ph": "B", "ts": s["ts"], "args": _export_args(s),
                 "_ord": (s["ts"], s["op_b"])}
            )
            events.append(
                {**base, "ph": "E", "ts": s["ts"] + s["dur"],
                 "_ord": (s["ts"] + s["dur"], s["op_e"])}
            )
        events.sort(key=lambda e: e.pop("_ord"))
        return events

    def export_perfetto(self, path: str, process: str = None) -> None:
        """Write the Chrome trace_event document. With ``process`` set,
        the event stream is prefixed with a ``process_name`` metadata
        ("M") event so multi-process viewers label this pid — the
        single-process export stays metadata-free (its event count is a
        pinned contract)."""
        events = self.to_trace_events()
        if process is not None:
            events = process_metadata_events(os.getpid(), process) + events
        doc = {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "producer": "demi_tpu.obs",
                "dropped_spans": self.dropped,
            },
        }
        with open(path, "w") as f:
            json.dump(doc, f)


def process_metadata_events(pid: int, process: str,
                            sort_index: int = None) -> List[Dict[str, Any]]:
    """Perfetto process-metadata ("M") events naming one pid's track —
    what makes a stitched multi-process timeline readable."""
    events: List[Dict[str, Any]] = [{
        "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
        "cat": "__metadata", "args": {"name": process},
    }]
    if sort_index is not None:
        events.append({
            "name": "process_sort_index", "ph": "M", "pid": pid, "tid": 0,
            "cat": "__metadata", "args": {"sort_index": sort_index},
        })
    return events


#: The process-wide tracer (CLI --trace-out exports it on exit).
TRACER = Tracer()


def _as_table(totals) -> Dict[str, Dict[str, float]]:
    return {
        name: {"count": c, "seconds": d / 1e9, "self_seconds": s / 1e9}
        for name, (c, d, s) in totals.items()
    }


def stage_totals() -> Dict[str, Dict[str, float]]:
    """Per span name, over every span finished since ``TRACER.clear()``:
    ``count``, ``seconds`` and ``self_seconds`` (duration less what
    child spans cover, ``gc.pause`` among them)."""
    with _lock:
        return _as_table(TRACER.totals)


def stage_counts() -> Dict[str, int]:
    """The counts ``stage_count`` took since ``TRACER.clear()``."""
    with _lock:
        return dict(TRACER.counts)


def stage_count(name: str, n: int = 1) -> None:
    """Add ``n`` to the count ``name``, at the boundary where the work
    happens; kept only while spans are live, and in the open job row
    while they fold."""
    if live():
        with _lock:
            TRACER.counts[name] = TRACER.counts.get(name, 0) + int(n)
    elif not _session_seen:
        return
    job_count(name, n)


def job_count(name: str, n: int = 1) -> None:
    """Add ``n`` to the count ``name`` of the job row open on this
    thread, and to nothing else (``stage_counts()`` never sees it);
    nothing without an open row."""
    row = getattr(_local, "row", None)
    if row is not None:
        row.counts[name] = row.counts.get(name, 0) + int(n)


class _JobRow:
    """The row a job's root span keeps open on its thread."""

    __slots__ = ("root", "recorded", "profiled", "totals", "counts")

    def __init__(self, root: "span", recorded: bool, profiled: bool):
        self.root = root
        self.recorded = recorded
        self.profiled = profiled
        self.totals: Dict[str, List[int]] = {}
        self.counts: Dict[str, int] = {}

    def finish(self, dur_ns: int) -> None:
        root = self.root
        row = {
            "job": root._job,
            "root": root.name,
            "args": dict(root.args),
            "start_s": (root._ts - _EPOCH_NS) / 1e9,
            "seconds": dur_ns / 1e9,
            "recorded": self.recorded,
            "profiled": self.profiled,
            "stages": _as_table(self.totals),
            "counts": dict(self.counts),
        }
        with _lock:
            _rows.append(row)


def job_ledger() -> List[Dict[str, Any]]:
    """A row for each of the last ``_ROWS_MAX`` jobs that ran while
    spans folded, oldest first: ``job``, ``root`` (the span that carried
    ``job=``: ``sweep.job``, ``dpor.search``) with its ``args``,
    ``start_s`` on the span clock and ``seconds``; ``recorded`` (spans
    were live at its entry: TRACER and the totals table hold it too) and
    ``profiled`` (a ``jax.profiler`` session was recording then);
    ``stages``, shaped as ``stage_totals()`` and holding what closed
    under the root on its thread, the root itself and ``gc.pause``
    among them, so the self seconds sum to ``seconds``; ``counts``, as
    ``stage_counts()`` and what ``job_count`` added."""
    with _lock:
        return [
            dict(row, args=dict(row["args"]), counts=dict(row["counts"]),
                 stages={k: dict(v) for k, v in row["stages"].items()})
            for row in _rows
        ]


def _gc_hook(phase: str, info: Dict[str, Any]) -> None:
    """``gc.callbacks`` entry: a collector pass that starts while a span
    is open on this thread becomes a ``gc.pause`` span under it. With no
    span open (spans off) it is one branch."""
    if phase == "start":
        stack = getattr(_local, "stack", None)
        if stack:
            pause = span("gc.pause", generation=info.get("generation"))
            pause._enter(stack[-1]._rec)
            _local.gc_pause = pause
    else:
        pause = getattr(_local, "gc_pause", None)
        if pause is not None:
            _local.gc_pause = None
            pause.__exit__(None, None, None)


class span:
    """Context manager recording one nested span into TRACER. A span
    entered while nothing makes it live (see the module doc) records
    nothing; a span already open when that ends still records on exit,
    keeping the per-thread stack discipline intact. ``job=n`` names the
    request a root span belongs to; below a span it is inherited.
    ``seconds`` is the duration once a live span has exited (0.0 for one
    that never was live). A span entered while spans only fold (see
    ``folding``) keeps the stack, its child time and ``seconds`` the
    same way and adds to its job's row at exit; it records nothing."""

    __slots__ = ("name", "args", "seconds", "_ts", "_op", "_live", "_rec",
                 "_job", "_parent", "_child_ns", "_ann")

    def __init__(self, name: str, **args):
        self.name = name
        self.args = args
        self.seconds = 0.0
        self._live = False

    def __enter__(self) -> "span":
        if live():
            self._enter(True)
        elif _session_seen:
            self._enter(False)
        return self

    def _enter(self, recording: bool) -> None:
        """Open the span on this thread's stack; ``recording`` says
        whether it is live or only folds."""
        global _gc_hooked
        if not _gc_hooked:
            with _lock:
                if not _gc_hooked:
                    _gc_hooked = True
                    gc.callbacks.append(_gc_hook)
        self._live = True
        self._rec = recording
        self._op = next(_ops)
        self._child_ns = 0
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        job = self.args.pop("job", None)
        if stack:
            self._parent = stack[-1]._op
            self._job = stack[-1]._job if job is None else job
        else:
            self._parent = None
            self._job = job
        # (a span that only folds was entered with no session recording)
        profiling = recording and _profiling()
        if job is not None and getattr(_local, "row", None) is None:
            _local.row = _JobRow(self, recording, profiling)
        self._ann = None
        if profiling:
            self._ann = _annotation("demi." + self.name)
            self._ann.__enter__()
        # Pushed and stamped last: a collector pass that starts during
        # the lines above falls to the parent, whose interval holds it.
        stack.append(self)
        self._ts = time.perf_counter_ns()

    def _close(self, end: int, stack) -> None:
        """Finish one span whose stack entry is already popped: end its
        annotation, hand its duration to its parent, fold (into the open
        job row, which ends with its root) and record."""
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        dur = max(0, end - self._ts)
        self.seconds = dur / 1e9
        if stack:
            stack[-1]._child_ns += dur
        row = getattr(_local, "row", None)
        if row is not None:
            _fold(row.totals, self.name, dur, dur - self._child_ns)
            if row.root is self:
                _local.row = None
                row.finish(dur)
        if not self._rec:
            return
        TRACER.fold(self.name, dur, dur - self._child_ns)
        ts = (self._ts - _EPOCH_NS) // 1000
        TRACER.record(
            self.name, ts, (end - _EPOCH_NS) // 1000 - ts,
            threading.get_ident() & 0xFFFF, self._op, next(_ops), self.args,
            self._parent, self._job,
        )

    def __exit__(self, exc_type, exc, tb) -> None:
        if not self._live:
            return
        end = time.perf_counter_ns()
        self._live = False
        if exc_type is not None:
            self.args["error"] = exc_type.__name__
        stack = getattr(_local, "stack", None)
        try:
            # Stack repair instead of an assert: a stage that raised
            # past a manually-entered inner span (or any misnested
            # usage) must not trade the real exception for an
            # AssertionError — and must not leave the inner span's B
            # event orphaned in the export. Pop down to self, closing
            # every abandoned inner span with an end event at 'now'.
            if stack and self in stack:
                while stack:
                    top = stack.pop()
                    if top is self:
                        break
                    top._live = False
                    top.args.setdefault("error", "orphaned")
                    top._close(end, stack)
        finally:
            # The end event is emitted from a finally so a raising
            # handler/stage can never orphan this span's B/E pair —
            # Perfetto trace validity under exceptions is pinned by
            # tests/test_obs.py.
            self._close(end, stack)

    def set(self, **args) -> None:
        """Attach result attributes discovered mid-span."""
        self.args.update(args)

    def slice(self, name: str, ns: int) -> None:
        """Hand ``ns`` nanoseconds of this open span's interval to the
        stage ``name``, as a child would take them: for work interleaved
        per item inside the span (summed by the caller from a clock pair
        per item), where a span per item would be a span in a per-item
        loop. It folds into the totals table (and the open job row) and
        has no interval of its own, so it is in no export."""
        if self._live:
            self._child_ns += ns
            row = getattr(_local, "row", None)
            if row is not None:
                _fold(row.totals, name, ns, ns)
            if self._rec:
                TRACER.fold(name, ns, ns)


def current_depth() -> int:
    """Testing hook: open-span depth on this thread."""
    return len(getattr(_local, "stack", ()))


# ---------------------------------------------------------------------------
# Set-up stages and the compile ledger: always on, bounded
# ---------------------------------------------------------------------------

_TIMELINE_MAX = 512     # stages kept with their intervals; totals fold on
_FUNCTIONS_MAX = 1024   # rows of the compile table; the rest share one
_COVER_MAX = 256        # disjoint compile intervals remembered a thread
_SETUP_PREFIXES = ("setup.", "compile.")

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_KINDS = {_TRACE_EVENT: "trace", _LOWER_EVENT: "lower",
          _BACKEND_EVENT: "backend"}
# kind -> (count column, seconds column) of a compile-table row
_COLUMNS = {
    "trace": ("traces", "trace_s"),
    "lower": ("lowerings", "lower_s"),
    "backend": ("compiles", "compile_s"),
    "cache_load": ("cache_hits", "cache_load_s"),
}


@functools.lru_cache(maxsize=None)
def _process_start_ns() -> Optional[int]:
    """This process's start as a ``perf_counter_ns`` reading, from the
    kernel's start time (``/proc/self/stat``, in clock ticks since boot)
    and ``/proc/uptime``; None where there is no such ``/proc``. Read
    when first asked for: the answer does not depend on when."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        now = time.perf_counter_ns()
        age = uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None
    return now - int(max(0.0, age) * 1e9)


def _new_row() -> Dict[str, float]:
    return {
        "traces": 0, "trace_s": 0.0, "lowerings": 0, "lower_s": 0.0,
        "compiles": 0, "compile_s": 0.0, "cache_hits": 0,
        "cache_load_s": 0.0, "retrieval_s": 0.0, "saved_s": 0.0, "late": 0,
    }


class _Setup:
    """What the stages and the compile listeners keep, under ``_lock``."""

    def __init__(self):
        self.timeline: List[Dict[str, Any]] = []
        self.handed = 0             # timeline entries already in TRACER
        self.dropped = 0
        self.first_stage_ns: Optional[int] = None
        self.functions: Dict[str, Dict[str, float]] = {}
        self.total = dict(_new_row(), covered_s=0.0, cache_misses=0)
        # Job 1: its verb, its clock pair, and the set-up totals and the
        # compile total as they stood at each end of it.
        self.verb: Optional[str] = None
        self.job_start_ns: Optional[int] = None
        self.job_end_ns: Optional[int] = None
        self.before_job: Optional[Dict[str, tuple]] = None
        self.at_job_end: Optional[Dict[str, tuple]] = None
        self.compile_at_job_end: Optional[Dict[str, float]] = None


_SETUP = _Setup()
_listening = False      # the listener pair is registered once a process


def _setup_totals() -> Dict[str, tuple]:
    return {
        name: tuple(t) for name, t in TRACER.totals.items()
        if name.startswith(_SETUP_PREFIXES)
    }


def _hand(rec: Dict[str, Any]) -> None:
    """One finished stage into TRACER, for ``--trace-out``. A stage that
    began before the span epoch (the import of this package) is drawn
    from 0: the export's timestamps stay non-negative."""
    start = max(0, rec["ts_ns"] - _EPOCH_NS)
    end = max(start, rec["ts_ns"] + rec["dur_ns"] - _EPOCH_NS)
    TRACER.record(
        rec["name"], start // 1000, end // 1000 - start // 1000, rec["tid"],
        rec["op_b"], rec["op_e"], rec["args"],
    )


class stage:
    """A span of set-up: it records whether or not spans are live,
    because it runs a bounded number of times a process (an import, a
    native build, a driver's constructor, the first job). It folds into
    the totals table under its name, a child stage taking its time out of
    its parent's self seconds, and the first ``_TIMELINE_MAX`` are kept
    with their intervals (``setup_ledger()["timeline"]``). Once spans are
    live those kept are handed to TRACER, so ``--trace-out`` draws set-up
    before the run's own spans.

    Stages keep a stack of their own: an open stage does not make
    ``live()`` true, does not turn the collector's ``gc.pause`` spans on,
    and takes no time out of a span's self seconds, nor a span out of
    its own. ``start_ns`` is for a stage whose first lines ran before
    this module could be imported."""

    __slots__ = ("name", "args", "seconds", "_ts", "_op", "_child_ns")

    def __init__(self, name: str, start_ns: Optional[int] = None, **args):
        self.name = name
        self.args = args
        self.seconds = 0.0
        self._ts = start_ns

    def __enter__(self) -> "stage":
        stack = getattr(_local, "stages", None)
        if stack is None:
            stack = _local.stages = []
        self._op = next(_ops)
        self._child_ns = 0
        stack.append(self)
        if self._ts is None:
            self._ts = time.perf_counter_ns()
        if _SETUP.first_stage_ns is None:
            _SETUP.first_stage_ns = self._ts
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        end = time.perf_counter_ns()
        stack = _local.stages
        if self in stack:
            del stack[stack.index(self):]
        if exc_type is not None:
            self.args["error"] = exc_type.__name__
        dur = max(0, end - self._ts)
        self.seconds = dur / 1e9
        if stack:
            stack[-1]._child_ns += dur
        TRACER.fold(self.name, dur, dur - self._child_ns)
        rec = {
            "name": self.name, "ts_ns": self._ts, "dur_ns": dur,
            "tid": threading.get_ident() & 0xFFFF, "op_b": self._op,
            "op_e": next(_ops), "args": self.args,
        }
        on = live()
        with _lock:
            if len(_SETUP.timeline) < _TIMELINE_MAX:
                _SETUP.timeline.append(rec)
            else:
                _SETUP.dropped += 1
            if on:
                for kept in _SETUP.timeline[_SETUP.handed:]:
                    _hand(kept)
                _SETUP.handed = len(_SETUP.timeline)

    def set(self, **args) -> None:
        """Attach result attributes discovered mid-stage."""
        self.args.update(args)


def staged(name: str, **args):
    """Decorator: the call is a ``stage(name, **args)``. An arg that is
    callable is what it returns of the call's own arguments."""

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*a, **kw):
            said = {
                key: value(*a, **kw) if callable(value) else value
                for key, value in args.items()
            }
            with stage(name, **said):
                return fn(*a, **kw)

        return inner

    return wrap


class _FirstJob(stage):
    """``setup.first_job``: besides the stage, the set-up totals as they
    stand at each end of it, so that set-up can be read cut at the end of
    job 1 whatever the process runs afterwards."""

    __slots__ = ()

    def __enter__(self) -> "stage":
        with _lock:
            _SETUP.verb = self.args.get("verb")
            _SETUP.before_job = _setup_totals()
        super().__enter__()
        _SETUP.job_start_ns = self._ts
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        super().__exit__(exc_type, exc, tb)
        with _lock:
            _SETUP.at_job_end = _setup_totals()
            _SETUP.compile_at_job_end = dict(_SETUP.total)
            _SETUP.job_end_ns = self._ts + int(self.seconds * 1e9)


_NO_STAGE = contextlib.nullcontext()


def first_job(job: int, verb: str):
    """The root stage of the process's first job (the first number
    ``new_job()`` handed out: a command's only job, the benchmark's warm
    job), for ``SweepDriver.sweep`` / ``DeviceDPOR.explore`` to enter
    around the whole job; nothing for any later job."""
    if job != 1:
        return _NO_STAGE
    return _FirstJob("setup.first_job", verb=verb)


def _own_ns(start: int, end: int) -> int:
    """The nanoseconds of ``[start, end]`` that no earlier compile event
    of this thread covers. A function traced inside another's tracing
    reports both, the inner one first: the outer keeps what is left of
    its interval, so the seconds add up to what they cover together.
    Events arrive in the order of their ends."""
    cover = getattr(_local, "cover", None)
    if cover is None:
        cover = _local.cover = []
    lo, inside = start, 0
    while cover and cover[-1][1] > start:
        s, e = cover.pop()
        inside += min(e, end) - max(s, start)
        lo = min(lo, s)
    cover.append((lo, end))
    if len(cover) > _COVER_MAX:
        del cover[0]
    return max(0, end - start - inside)


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT_EVENT:
        _local.cache_hit = True
    elif event == _CACHE_MISS_EVENT:
        with _lock:
            _SETUP.total["cache_misses"] += 1


def _on_duration(event: str, seconds: float, **kw) -> None:
    kind = _KINDS.get(event)
    if kind is None:
        # A hit reports what it saved and what the read took just
        # before the compile's own duration, on the same thread.
        if event == _RETRIEVAL_EVENT:
            _local.retrieval_s = seconds
        elif event == _SAVED_EVENT:
            _local.saved_s = seconds
        return
    end = time.perf_counter_ns()
    own = _own_ns(end - int(seconds * 1e9), end)
    retrieval = saved = 0.0
    if kind == "backend" and getattr(_local, "cache_hit", False):
        kind = "cache_load"
        _local.cache_hit = False
        retrieval = getattr(_local, "retrieval_s", 0.0)
        saved = getattr(_local, "saved_s", 0.0)
    fun = str(kw.get("fun_name", "?"))
    if kind != "trace" and fun.startswith("jit(") and fun.endswith(")"):
        fun = fun[4:-1]     # tracing says f, the module is jit(f)
    count, secs = _COLUMNS[kind]
    with _lock:
        row = _SETUP.functions.get(fun)
        if row is None:
            if len(_SETUP.functions) >= _FUNCTIONS_MAX:
                fun = "(other)"
            row = _SETUP.functions.setdefault(fun, _new_row())
        late = _SETUP.job_end_ns is not None
        for r in (row, _SETUP.total):
            r[count] += 1
            r[secs] += seconds
            r["retrieval_s"] += retrieval
            r["saved_s"] += saved
            r["late"] += late
        _SETUP.total["covered_s"] += own / 1e9
    stack = getattr(_local, "stages", None)
    if stack:
        stack[-1]._child_ns += own
    TRACER.fold("compile." + kind, own, own)


def listen_to_compiles(monitoring) -> None:
    """Register the compile ledger's listener pair with
    ``jax.monitoring``, once a process, never unregistered:
    ``demi_tpu.device`` calls it where it configures the compile cache."""
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


def compile_ledger() -> Dict[str, Any]:
    """What JAX traced, lowered, compiled and loaded in this process, by
    the function's name (``functions``) and summed (``total``). A row:
    ``traces`` / ``trace_s``, ``lowerings`` / ``lower_s``, ``compiles`` /
    ``compile_s`` (backend compiles the persistent cache did not
    serve), ``cache_hits`` / ``cache_load_s`` (the compile request's
    whole duration when the cache served it; ``retrieval_s`` the read
    alone, ``saved_s`` what JAX says the hit saved), and ``late``:
    events after the first job had ended, which a warm process should
    not have. A row's seconds are each event's own, so a function traced
    inside another's tracing is in both rows; the total's ``covered_s``
    and the ``compile.*`` stages count such seconds once."""
    with _lock:
        return {
            "total": dict(_SETUP.total),
            "functions": {f: dict(r) for f, r in _SETUP.functions.items()},
        }


def setup_ledger() -> Dict[str, Any]:
    """Where the time before the end of the process's first job went.

    ``stages`` is the totals table's ``setup.*`` and ``compile.*`` rows
    cut at the end of job 1 (as they stand now while it has not ended),
    ``before_first_job`` the same rows at its start, ``compile`` the
    compile total at its end. Times are seconds; ``start_s`` and ``end_s``
    count from the process's start (``process_start_s`` on the span
    clock, negative; from the span epoch where the platform does not
    say), so ``first_job["end_s"]`` is the process's age at job 1's end.
    ``pre_program_s`` is what came before the program's first stage: the
    interpreter and whatever the caller imported first."""
    started = _process_start_ns()
    with _lock:
        origin = _EPOCH_NS if started is None else started
        first_job = None
        if _SETUP.job_start_ns is not None:
            first_job = {
                "verb": _SETUP.verb,
                "start_s": (_SETUP.job_start_ns - origin) / 1e9,
                "end_s": None if _SETUP.job_end_ns is None
                else (_SETUP.job_end_ns - origin) / 1e9,
            }
        ended = _SETUP.at_job_end is not None
        return {
            "process_start_s": None if started is None
            else (started - _EPOCH_NS) / 1e9,
            "pre_program_s": None
            if started is None or _SETUP.first_stage_ns is None
            else (_SETUP.first_stage_ns - started) / 1e9,
            "first_job": first_job,
            "stages": _as_table(
                _SETUP.at_job_end if ended else _setup_totals()
            ),
            "before_first_job": None if _SETUP.before_job is None
            else _as_table(_SETUP.before_job),
            "compile": dict(
                _SETUP.compile_at_job_end if ended else _SETUP.total
            ),
            "timeline": [
                {"name": r["name"], "start_s": (r["ts_ns"] - origin) / 1e9,
                 "seconds": r["dur_ns"] / 1e9, "args": dict(r["args"])}
                for r in _SETUP.timeline
            ],
            "timeline_dropped": _SETUP.dropped,
        }


def _reset_setup() -> None:
    """Testing hook: forget every stage, the compile table, job 1 and
    the job rows, and hand out job numbers from 1 again. The listeners
    stay."""
    global _SETUP, _jobs
    with _lock:
        _SETUP = _Setup()
        _jobs = itertools.count(1)
        _rows.clear()
        for name in [n for n in TRACER.totals if n.startswith(_SETUP_PREFIXES)]:
            del TRACER.totals[name]
    _local.__dict__.pop("cover", None)
