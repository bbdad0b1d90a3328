"""Launch profiler: wall-time attribution per kernel launch, keyed by
launch shape, with TuningCache-compatible evidence.

Everything the launch-economy cost model (ROADMAP item 5) needs to
decide "does a trunk launch pay here?" is a function of MEASURED launch
shapes: how long a dispatch takes (tracing + enqueue, the async-visible
cost) vs how long a harvest blocks (the device actually computing), per
kernel kind (trunk vs lane vs harvest) and per shape (batch width,
segment/variant). This module collects exactly that ledger:

  - ``PROFILER.dispatch(kernel, batch, seconds)`` — timed around the
    jitted call itself (device/explore.py's ``_counted_kernel``, the one
    wrapper every lane kernel already passes through);
  - ``PROFILER.trunk(...)`` — the single-lane trunk builds of the
    prefix-fork paths (DeviceDPOR._dispatch_forked);
  - ``PROFILER.block(...)`` — the ``block_until_ready`` harvest waits
    (DeviceDPOR._supervised_harvest, SweepDriver._harvest_chunk).

Where a stage span (obs/spans.py) brackets the same interval — DPOR's
``dpor.dispatch``, ``dpor.block`` and ``dpor.scan`` — the ledger takes
the span's duration instead of a clock pair of its own, so an enabled
profiler keeps spans live (``spans.live_while`` below). That has a cost
the ledger alone had not: finished spans are held in ``TRACER`` (up to
its ``max_spans``, 200,000: some tens of MB) and each pass of CPython's
collector under an open span is recorded as a ``gc.pause`` span.

Evidence is exported in the same decision-dict shape the autotuner
persists (``evidence()`` / ``persist_evidence``): one
``TuningCache``-keyed entry per workload, so the future cost model is a
CONSUMER of this ledger, not a rewrite — the measured launch shapes ARE
its calibration input (tune/cache.py's get/put contract).

Off by default (``DEMI_PROFILE=1`` or ``--profile-rounds N``); disabled
call sites pay one attribute load + branch, the same contract as the
metrics registry. ``--profile-rounds N`` additionally opens a
``jax.profiler`` trace window over the first N rounds (start/stop around
round boundaries) for op-level TPU/XLA attribution next to this module's
launch-level ledger.
"""

from __future__ import annotations

import os
import sys
import threading
from typing import Any, Dict, List, Optional

from . import spans

_enabled = os.environ.get("DEMI_PROFILE", "").strip().lower() in (
    "1", "true", "yes", "on"
)


def profile_enabled() -> bool:
    return _enabled


class LaunchProfiler:
    """Per-(kernel, kind, shape) wall-time ledger. ``kind`` is the
    launch's role: 'dispatch' (async kernel call), 'trunk' (single-lane
    prefix build), 'block' (harvest wait)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.enabled = _enabled
        # (kernel, kind, shape) -> [launches, seconds, lanes]
        self.ledger: Dict[tuple, List[float]] = {}
        # jax.profiler trace window state (--profile-rounds)
        self._trace_rounds = 0
        self._trace_dir: Optional[str] = None
        self._trace_open = False

    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def reset(self) -> None:
        with self._lock:
            self.ledger.clear()

    def _note(
        self, kernel: str, kind: str, shape: str, seconds: float, lanes: int
    ) -> None:
        key = (kernel, kind, shape)
        with self._lock:
            s = self.ledger.get(key)
            if s is None:
                s = self.ledger[key] = [0, 0.0, 0]
            s[0] += 1
            s[1] += seconds
            s[2] += lanes

    # The three call-site flavors. ``shape`` is the launch-shape
    # discriminator the cost model keys on — "b=<batch>" plus whatever
    # the driver knows (seg=, variant=).
    def dispatch(
        self, kernel: str, batch: int, seconds: float, shape: str = ""
    ) -> None:
        if not self.enabled:
            return
        self._note(kernel, "dispatch", shape or f"b={batch}", seconds, batch)

    def trunk(
        self, kernel: str, batch: int, seconds: float, shape: str = ""
    ) -> None:
        if not self.enabled:
            return
        self._note(kernel, "trunk", shape or f"b={batch}", seconds, batch)

    def block(
        self, kernel: str, batch: int, seconds: float, shape: str = ""
    ) -> None:
        if not self.enabled:
            return
        self._note(kernel, "block", shape or f"b={batch}", seconds, batch)

    def host_scan(
        self, kernel: str, batch: int, seconds: float, shape: str = ""
    ) -> None:
        """Host-half analysis wall per launch shape — the racing scan +
        filter + dedup section of a frontier round. Device launches
        alone undercount a round's cost (ROADMAP item 5's cost-model
        evidence gap); persisting this kind under the same
        ``profile=launch`` TuningCache key closes it."""
        if not self.enabled:
            return
        self._note(kernel, "host", shape or f"b={batch}", seconds, batch)

    # -- evidence -----------------------------------------------------------
    def evidence(self) -> Dict[str, Any]:
        """TuningCache-compatible decision dict: the measured launch
        shapes, sorted heaviest-first. ``source: 'measured'`` mirrors
        the calibration decisions' provenance field."""
        with self._lock:
            rows = [
                {
                    "kernel": kernel,
                    "kind": kind,
                    "shape": shape,
                    "launches": int(s[0]),
                    "seconds": round(s[1], 6),
                    "lanes": int(s[2]),
                    "mean_ms": round(1000.0 * s[1] / s[0], 4) if s[0] else 0,
                }
                for (kernel, kind, shape), s in self.ledger.items()
            ]
        rows.sort(key=lambda r: -r["seconds"])
        return {
            "profile": "launch",
            "source": "measured",
            "launches": rows,
        }

    def persist_evidence(self, cache, key: str) -> None:
        """Persist the ledger under a ``tune.workload_key``-derived key
        (callers pass ``profile='launch'`` as the extra discriminator)
        so ``TuningCache.get(key)`` hands the cost model its measured
        launch economics with zero new plumbing."""
        ev = self.evidence()
        if ev["launches"]:
            cache.put(key, ev)

    # -- jax.profiler trace window (--profile-rounds N) ---------------------
    def start_trace_window(self, logdir: str, rounds: int) -> bool:
        """Open a jax.profiler trace capturing the next ``rounds`` round
        boundaries (``tick_round`` closes it). Degrades with a warning
        when the profiler backend is unavailable — a bench window must
        never die for want of a trace."""
        self.enabled = True
        try:
            import jax

            jax.profiler.start_trace(logdir)
        except Exception as exc:  # pragma: no cover - backend-specific
            print(
                f"demi_tpu.obs: jax.profiler trace unavailable ({exc}); "
                "launch-ledger profiling continues without it",
                file=sys.stderr,
            )
            return False
        self._trace_rounds = max(1, rounds)
        self._trace_dir = logdir
        self._trace_open = True
        return True

    def tick_round(self) -> None:
        """Round-boundary hook (drivers call it unconditionally — one
        branch when no window is open): closes the trace window after
        its budgeted rounds."""
        if not self._trace_open:
            return
        self._trace_rounds -= 1
        if self._trace_rounds <= 0:
            self.stop_trace_window()

    def stop_trace_window(self) -> None:
        if not self._trace_open:
            return
        self._trace_open = False
        try:
            import jax

            jax.profiler.stop_trace()
            print(
                f"demi_tpu.obs: profiler trace written to "
                f"{self._trace_dir} (load in TensorBoard / xprof)",
                file=sys.stderr,
            )
        except Exception:  # pragma: no cover - backend-specific
            pass


#: Process-wide profiler every instrumented launch site reports into.
PROFILER = LaunchProfiler()
# The ledger's DPOR rows are span durations: spans are live while it is on.
spans.live_while(lambda: PROFILER.enabled)
