"""Test configuration: tests force the CPU and an 8-device virtual mesh,
so multi-device sharding is exercised without hardware. The chip is
exercised by ``chip_smoke.py`` through the builder's chip tool, never by
this suite."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()


# ---------------------------------------------------------------------------
# Tier-1 scheduling: cheap modules first.
#
# The tier-1 gate (ROADMAP.md) runs this suite under a hard wall-clock cap,
# and the full suite is slower than the cap on small CPU boxes — whatever
# runs last gets truncated. Alphabetical order put the kernel-compiling
# device/continuous modules mid-run, so a timeout used to cut the
# *breadth* tests behind them. Scheduling the dozens of fast host-tier
# modules first makes a truncation cost the fewest tests: the expensive
# kernel-parity modules run at the end, each still whole (module fixtures
# and jit caches stay contiguous). Order within a cost bucket stays stable
# (alphabetical), and a full untimed run is identical either way.
_HEAVY_TEST_MODULES = {
    # Rough ascending per-module wall cost, measured on the 2-core CPU
    # box (pytest --durations); anything unlisted runs first.
    "test_batched_min": 1,
    "test_minimization": 1,
    "test_replay_minimize": 1,
    "test_synoptic": 1,
    "test_scale64": 1,
    "test_native_sweep": 1,
    "test_parallel": 2,
    "test_dpor": 2,
    "test_distributed": 2,
    "test_raft_case_studies": 3,
    "test_rounds": 3,
    "test_raft": 3,
    "test_async_min": 4,
    "test_bench_smoke": 4,
    "test_fork": 5,
    "test_differential": 5,
    "test_device_srcdst": 5,
    "test_device_dpor": 6,
    "test_device": 6,
    "test_continuous": 6,
    # Subprocess-heavy (each fleet run spawns worker processes that
    # import jax + compile): last, so a tier-1 time-cap truncation cuts
    # these new tests before any of the breadth suite.
    "test_fleet": 7,
}


def pytest_collection_modifyitems(config, items):
    items.sort(
        key=lambda item: _HEAVY_TEST_MODULES.get(item.module.__name__, 0)
    )
