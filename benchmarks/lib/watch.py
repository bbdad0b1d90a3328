"""What the harness counts inside the window from the host: compilations
(``jax.monitoring``, as ``chip_smoke.py``'s ``Smoke`` listens) and the
garbage collector's time (``gc.callbacks``)."""

from __future__ import annotations

import gc
import time

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    BACKEND_COMPILE,
)
CACHE_HIT = "/jax/compilation_cache/cache_hits"

TRACE_LOWER = COMPILE_EVENTS[:2]


class CompileWatch:
    """Counts, between ``start`` and ``stop``: every trace/lower/compile
    event; the seconds of tracing and lowering (``trace_lower_s``: a verb
    that builds fresh jitted closures for every job traces and lowers them
    again each time, which no cache keeps); and the backend compilations
    that the persistent cache did not serve (``compiles`` and
    ``compile_s``), however short. ``<verb>.window_compiles`` is that
    count. The cache is left as the program sets it: JAX's default keeps
    only programs that took a second or more to compile."""

    def __init__(self):
        import jax

        self._jax = jax
        self.on = False
        self.events = 0
        self.trace_lower_s = 0.0
        self.compiles = 0
        self.compile_s = 0.0
        self._hit = False
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT:
            self._hit = True

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == BACKEND_COMPILE:
            hit, self._hit = self._hit, False
            if self.on and not hit:
                self.compiles += 1
                self.compile_s += seconds
        if self.on and event in COMPILE_EVENTS:
            self.events += 1
            if event in TRACE_LOWER:
                self.trace_lower_s += seconds

    def start(self) -> None:
        self.on = True

    def stop(self) -> None:
        self.on = False

    def close(self) -> None:
        m = self._jax.monitoring
        m.unregister_event_duration_listener(self._duration)
        m.unregister_event_listener(self._event)


class GcWatch:
    """Seconds the collector ran between ``start`` and ``stop``."""

    def __init__(self):
        self.seconds = 0.0
        self.collections = 0
        self.longest = 0.0
        self._t0 = None

    def _callback(self, phase: str, _info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            pause = time.perf_counter() - self._t0
            self.seconds += pause
            self.longest = max(self.longest, pause)
            self.collections += 1
            self._t0 = None

    def start(self) -> None:
        gc.callbacks.append(self._callback)

    def stop(self) -> None:
        gc.callbacks.remove(self._callback)
