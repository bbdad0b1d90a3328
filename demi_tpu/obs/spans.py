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

Every span carries the span that caused it (``parent``: the enclosing
span's ``op_b``) and the request it belongs to (``job``: a number from
``new_job()`` given to a root span and inherited below it). At exit a
span folds into a per-name totals table (``stage_totals()``: count,
seconds, and self seconds = duration less what child spans cover), which
is bounded by the number of names; ``stage_count`` keeps plain counts
beside it (``stage_counts()``). While any span is open, each pass of
CPython's collector is a ``gc.pause`` span nested in the stage that
triggered it, so a stage's self time leaves the collector out.
"""

from __future__ import annotations

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
    if _metrics.enabled() or _profiling():
        return True
    for switch in _switches:
        if switch():
            return True
    return False


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
            t = self.totals.get(name)
            if t is None:
                t = self.totals[name] = [0, 0, 0]
            t[0] += 1
            t[1] += dur_ns
            t[2] += self_ns

    def clear(self) -> None:
        with _lock:
            self.spans.clear()
            self.dropped = 0
            self.totals.clear()
            self.counts.clear()

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


def stage_totals() -> Dict[str, Dict[str, float]]:
    """Per span name, over every span finished since ``TRACER.clear()``:
    ``count``, ``seconds`` and ``self_seconds`` (duration less what
    child spans cover, ``gc.pause`` among them)."""
    with _lock:
        return {
            name: {"count": c, "seconds": d / 1e9, "self_seconds": s / 1e9}
            for name, (c, d, s) in TRACER.totals.items()
        }


def stage_counts() -> Dict[str, int]:
    """The counts ``stage_count`` took since ``TRACER.clear()``."""
    with _lock:
        return dict(TRACER.counts)


def stage_count(name: str, n: int = 1) -> None:
    """Add ``n`` to the count ``name``, at the boundary where the work
    happens; kept only while spans are live."""
    if not live():
        return
    with _lock:
        TRACER.counts[name] = TRACER.counts.get(name, 0) + int(n)


def _gc_hook(phase: str, info: Dict[str, Any]) -> None:
    """``gc.callbacks`` entry: a collector pass that starts while a span
    is open on this thread becomes a ``gc.pause`` span under it. With no
    span open (spans off) it is one branch."""
    if phase == "start":
        if getattr(_local, "stack", None):
            pause = span("gc.pause", generation=info.get("generation"))
            pause._enter()
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
    that never was live)."""

    __slots__ = ("name", "args", "seconds", "_ts", "_op", "_live", "_job",
                 "_parent", "_child_ns", "_ann")

    def __init__(self, name: str, **args):
        self.name = name
        self.args = args
        self.seconds = 0.0
        self._live = False

    def __enter__(self) -> "span":
        if live():
            self._enter()
        return self

    def _enter(self) -> None:
        global _gc_hooked
        if not _gc_hooked:
            with _lock:
                if not _gc_hooked:
                    _gc_hooked = True
                    gc.callbacks.append(_gc_hook)
        self._live = True
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
        self._ann = None
        if _profiling():
            self._ann = _annotation("demi." + self.name)
            self._ann.__enter__()
        # Pushed and stamped last: a collector pass that starts during
        # the lines above falls to the parent, whose interval holds it.
        stack.append(self)
        self._ts = time.perf_counter_ns()

    def _close(self, end: int, op_e: int, tid: int, stack) -> None:
        """Finish one span whose stack entry is already popped: end its
        annotation, hand its duration to its parent, fold and record."""
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        dur = max(0, end - self._ts)
        self.seconds = dur / 1e9
        if stack:
            stack[-1]._child_ns += dur
        TRACER.fold(self.name, dur, dur - self._child_ns)
        ts = (self._ts - _EPOCH_NS) // 1000
        TRACER.record(
            self.name, ts, (end - _EPOCH_NS) // 1000 - ts, tid, self._op,
            op_e, self.args, self._parent, self._job,
        )

    def __exit__(self, exc_type, exc, tb) -> None:
        if not self._live:
            return
        end = time.perf_counter_ns()
        self._live = False
        if exc_type is not None:
            self.args["error"] = exc_type.__name__
        tid = threading.get_ident() & 0xFFFF
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
                    top._close(end, next(_ops), tid, stack)
        finally:
            # The end event is emitted from a finally so a raising
            # handler/stage can never orphan this span's B/E pair —
            # Perfetto trace validity under exceptions is pinned by
            # tests/test_obs.py.
            self._close(end, next(_ops), tid, stack)

    def set(self, **args) -> None:
        """Attach result attributes discovered mid-span."""
        self.args.update(args)

    def slice(self, name: str, ns: int) -> None:
        """Hand ``ns`` nanoseconds of this open span's interval to the
        stage ``name``, as a child would take them: for work interleaved
        per item inside the span (summed by the caller from a clock pair
        per item), where a span per item would be a span in a per-item
        loop. It folds into the totals table and has no interval of its
        own, so it is in no export."""
        if self._live:
            self._child_ns += ns
            TRACER.fold(name, ns, ns)


def current_depth() -> int:
    """Testing hook: open-span depth on this thread."""
    return len(getattr(_local, "stack", ()))
