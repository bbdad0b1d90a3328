"""Launch supervision and preemption tolerance.

``LaunchSupervisor`` wraps the two host↔accelerator I/O surfaces that
can fail outside the program's control — device kernel launches (a
poisoned buffer, a wedged runtime) and native ctypes calls (a crashed
analyzer) — with bounded retry + exponential backoff. Rounds are pure
functions of (frontier state, rng round keys), so a retry simply
re-executes the round from the last harvested state; nothing is lost
and nothing double-counts in the search state. When a NATIVE surface
keeps failing and a semantics-identical NumPy twin exists, the
supervisor degrades that surface permanently (one-time warning +
``persist.degradations``) — correct, slower, alive. ``--strict-io`` /
``DEMI_STRICT_IO=1`` turns exhausted retries and degradations into
``StrictIOError`` so CI fails loudly instead of limping.

``PreemptionGuard`` converts SIGTERM/SIGINT into a checkpoint REQUEST:
the first signal sets a flag the round loop consults at its next
generation-frozen boundary (where a snapshot resumes bit-identically);
a second signal raises ``KeyboardInterrupt`` for operators who really
mean it. Handlers are restored on exit, and installation degrades to a
no-op guard off the main thread (tests, embedded use).
"""

from __future__ import annotations

import os
import signal
import sys
import threading
import time
from typing import Any, Callable, Dict, Optional

from .. import obs


class StrictIOError(RuntimeError):
    """A launch kept failing (or would have degraded) under strict-io."""


def strict_io_enabled(explicit: Optional[bool] = None) -> bool:
    """Resolve the strict-io switch: explicit arg wins, else
    ``DEMI_STRICT_IO``. Off by default — a long soak should survive a
    flaky launch, not die of it; CI opts into loud failure."""
    if explicit is not None:
        return bool(explicit)
    return os.environ.get("DEMI_STRICT_IO", "").strip().lower() in (
        "1", "true", "yes", "on", "strict"
    )


class LaunchSupervisor:
    """Bounded retry/backoff with per-surface permanent degradation.

    ``run(fn, label=..., fallback=...)`` calls ``fn(attempt)`` (attempt 0
    first); each raised exception is counted and retried up to
    ``retries`` times with exponential backoff. Exhausted retries:
    strict-io raises ``StrictIOError``; otherwise ``fallback()`` (when
    given) serves the call and the surface named ``label`` is degraded
    PERMANENTLY — every later ``run`` for it goes straight to the
    fallback (one warning, ever). No fallback ⇒ the last error
    re-raises (device kernels have no host twin; retry is the whole
    remedy there)."""

    def __init__(
        self,
        retries: Optional[int] = None,
        backoff: float = 0.05,
        strict: Optional[bool] = None,
    ):
        self.retries = (
            retries
            if retries is not None
            else max(0, int(os.environ.get("DEMI_LAUNCH_RETRIES", "2")))
        )
        self.backoff = backoff
        self._strict = strict
        self._degraded: Dict[str, str] = {}
        self.stats: Dict[str, int] = {
            "failures": 0, "retries": 0, "degradations": 0
        }

    @property
    def strict(self) -> bool:
        return strict_io_enabled(self._strict)

    def degraded(self, label: str) -> bool:
        return label in self._degraded

    def reset(self) -> None:
        """Forget degradations + stats (test isolation)."""
        self._degraded.clear()
        for k in self.stats:
            self.stats[k] = 0

    def _degrade(self, label: str, reason: str) -> None:
        self.stats["degradations"] += 1
        obs.counter("persist.degradations").force_inc(label=label)
        if label not in self._degraded:
            self._degraded[label] = reason
            print(
                f"demi_tpu.persist: {label} degraded permanently to its "
                f"host twin after repeated failures ({reason}); results "
                "stay correct, rounds run slower",
                file=sys.stderr,
            )

    def run(
        self,
        fn: Callable[[int], Any],
        *,
        label: str,
        fallback: Optional[Callable[[], Any]] = None,
    ) -> Any:
        if fallback is not None and label in self._degraded:
            return fallback()
        attempt = 0
        while True:
            try:
                return fn(attempt)
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as exc:
                self.stats["failures"] += 1
                obs.counter("persist.launch_failures").force_inc(label=label)
                if attempt < self.retries:
                    attempt += 1
                    self.stats["retries"] += 1
                    obs.counter("persist.launch_retries").force_inc(
                        label=label
                    )
                    time.sleep(self.backoff * (2 ** (attempt - 1)))
                    continue
                if self.strict:
                    # str(exc), not repr: a compiler's diagnostics live
                    # in the message, and some reprs (MLIRError) drop it.
                    raise StrictIOError(
                        f"{label} failed {attempt + 1}x under strict-io: "
                        f"{type(exc).__name__}: {exc}"
                    ) from exc
                if fallback is not None:
                    self._degrade(label, repr(exc))
                    return fallback()
                raise


#: Process-wide supervisor every wrapped surface shares (degradation is
#: a process-level fact: once the native analyzer is poisoned, every
#: caller should stop touching it).
SUPERVISOR = LaunchSupervisor()


class PreemptionGuard:
    """Context manager turning SIGTERM/SIGINT into a boundary-checkpoint
    request (see module doc). ``requested`` flips on the first signal;
    callers poll it at round boundaries. Off the main thread the guard
    installs nothing and ``requested`` stays False."""

    SIGNALS = (signal.SIGTERM, signal.SIGINT)

    def __init__(self):
        self.requested = False
        self.signum: Optional[int] = None
        self._previous: Dict[int, Any] = {}
        self._installed = False

    def _handle(self, signum, frame):
        if self.requested:
            # Second signal: the operator is done waiting for a boundary.
            raise KeyboardInterrupt
        self.requested = True
        self.signum = signum
        obs.counter("persist.preemptions_requested").force_inc()
        print(
            "demi_tpu.persist: preemption requested "
            f"(signal {signum}); checkpointing at the next round boundary "
            "(signal again to abort immediately)",
            file=sys.stderr,
        )

    def __enter__(self) -> "PreemptionGuard":
        if threading.current_thread() is threading.main_thread():
            for sig in self.SIGNALS:
                self._previous[sig] = signal.signal(sig, self._handle)
            self._installed = True
        return self

    def __exit__(self, *exc) -> None:
        if self._installed:
            for sig, prev in self._previous.items():
                signal.signal(sig, prev)
            self._previous.clear()
            self._installed = False
