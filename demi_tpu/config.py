"""SchedulerConfig: the one config object threaded into every scheduler.

Reference: src/main/scala/verification/SchedulerConfig.scala (37 LoC).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Optional

from .fingerprints import FingerprintFactory, default_fingerprint_factory

# An invariant maps (externals, checkpoint: {actor -> state-or-None}) to an
# optional ViolationFingerprint (reference: TestOracle.scala:27).
Invariant = Callable[[Any, dict], Optional[Any]]


@dataclass
class SchedulerConfig:
    fingerprinter: FingerprintFactory = field(default_factory=default_fingerprint_factory)
    enable_failure_detector: bool = False
    enable_checkpointing: bool = True
    should_shutdown_actor_system: bool = True
    filter_known_absents: bool = True
    invariant_check: Optional[Invariant] = None
    ignore_timers: bool = False
    store_event_traces: bool = False
    abort_upon_divergence: bool = False
    abort_upon_divergence_lax: bool = False
    original_dep_graph: Optional[Any] = None

    @property
    def quiescence_invariant(self) -> bool:
        """Whether ``invariant_check`` may be judged at quiescence only
        (it says so itself: ``apps.common.make_host_invariant`` marks the
        one it makes from ``DSLApp.invariant_at``). A run then ends at
        quiescence or has no verdict, as on the device."""
        return bool(getattr(self.invariant_check, "at_quiescence", False))
