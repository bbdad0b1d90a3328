"""demi_tpu: a TPU-native framework for fuzzing and minimizing
message-delivery schedules of distributed (actor-model) systems.

Capability-equivalent re-design of NetSys/demi (DEMi, NSDI'16) — see
SURVEY.md for the structural map. Two tiers:

  - Host tier: event/trace model, controlled sequential actor runtime
    (the oracle), schedulers, minimization logic, persistence.
  - Device tier (demi_tpu.device / demi_tpu.parallel): actor state and
    pending-message pools as tensors; vmapped jitted transition kernels
    advance thousands of candidate schedules in lockstep, sharded over a
    TPU mesh.
"""

import sys as _sys
import time as _time

_T0 = _time.perf_counter_ns()   # the set-up ledger's first stage starts here

__version__ = "0.1.0"

from .obs import spans as _spans

with _spans.stage("setup.import", start_ns=_T0, module=__name__):
    if "jax" not in _sys.modules:
        # dsl.py is the first of the program to want jax: a stage of its
        # own, absent where the caller imported jax first.
        with _spans.stage("setup.import", module="jax"):
            import jax as _jax  # noqa: F401
    from . import events, external_events, fingerprints, trace, config, dsl  # noqa: F401
    from .config import SchedulerConfig
    from .trace import EventTrace
    from .events import Unique

__all__ = ["SchedulerConfig", "EventTrace", "Unique", "__version__"]


def __getattr__(name):
    # Lazy top-level conveniences (keep `import demi_tpu` light — the
    # runner/apps pull in jax).
    if name in ("fuzz", "run_the_gamut", "print_minimization_stats"):
        from . import runner

        return getattr(runner, name)
    raise AttributeError(f"module 'demi_tpu' has no attribute {name!r}")
