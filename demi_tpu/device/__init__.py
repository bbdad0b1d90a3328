import os as _os

import jax as _jax

from ..obs import spans as _spans


def compile_cache_dir(environ=_os.environ):
    """Where this program points JAX's persistent compilation cache, or
    None when it leaves JAX's configuration alone.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and the
    program sets nothing. ``JAX_PLATFORMS=cpu`` (the test boot): no
    cache — XLA:CPU AOT reloads warn about machine-feature mismatches,
    and the suite must not fill the checkout. Otherwise one fixed,
    git-ignored path inside the checkout: the path is part of the cache
    key, so it is never built from a temp name, a pid or a time."""
    if environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    if environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return None
    return _os.path.join(
        _os.path.dirname(
            _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
        ),
        ".jax_cache",
    )


# The CLI builds the same kernel configs run after run (and the batch
# oracle re-buckets to a handful of shapes): compiled executables kept on
# disk turn repeat compiles into loads.
_cache_dir = compile_cache_dir()
if _cache_dir is not None:
    _jax.config.update("jax_compilation_cache_dir", _cache_dir)
# The compile ledger (obs/spans.py) hears every trace, lowering, compile
# and cache load from here on: the process's one listener pair.
_spans.listen_to_compiles(_jax.monitoring)

with _spans.stage("setup.import", module=__name__):
    from .continuous import ContinuousSweepDriver
    from .core import DeviceConfig, ScheduleState
    from .explore import make_explore_kernel, make_single_lane_trace_kernel
    from .fork import (
        PrefixCache,
        PrefixPlanner,
        PrefixSnapshot,
        fork_lanes,
        make_dpor_prefix_runner,
        make_explore_prefix_runner,
        make_replay_prefix_runner,
        prefix_fork_enabled,
    )
    from .replay import make_replay_kernel

__all__ = [
    "compile_cache_dir",
    "ContinuousSweepDriver",
    "DeviceConfig",
    "PrefixCache",
    "PrefixPlanner",
    "PrefixSnapshot",
    "ScheduleState",
    "fork_lanes",
    "make_dpor_prefix_runner",
    "make_explore_kernel",
    "make_explore_prefix_runner",
    "make_replay_prefix_runner",
    "make_single_lane_trace_kernel",
    "make_replay_kernel",
    "prefix_fork_enabled",
]
