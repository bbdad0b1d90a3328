"""Device-lane telemetry: per-sweep counters accumulated on-device.

A sweep's verdict arrays (``LaneResult.status/violation/deliveries``)
live on the accelerator; pulling them per *lane* for bookkeeping would
serialize the host against the device. ``LaneStats`` is a tiny pytree of
scalar totals reduced ON-DEVICE over a whole round's lane batch — one
jitted reduction per round, one host transfer of ~8 int32s — which the
sweep drivers thread through their round loops and fold into the
process metrics registry (``demi_tpu.obs.metrics``).

Counters (the exploration-efficiency signals arXiv:2405.11128 names as
the primary tuning inputs for a schedule explorer):

  - lanes / done: lanes harvested, lanes that completed a verdict
  - deliveries: messages delivered across the round's lanes
  - violations: lanes ending in an invariant violation
  - overflow: lanes aborted on pool overflow (no verdict — these are
    also the lanes the dedup path skips, so overflow == dedup-skipped)
  - invariant_checks: invariant evaluations implied by the config
    (``deliveries // interval`` interval checks + one finalization
    check per finished lane — the exact count the kernels perform)

Unique-schedule accounting stays with the drivers' existing host-side
``sched_hash`` dedup (cross-round dedup needs host memory anyway); the
drivers record it next to these totals so the registry carries the
unique-schedule fraction too.

This module imports jax and is therefore NOT re-exported from
``demi_tpu.obs`` (which stays import-light); device drivers import it
directly.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import metrics as _metrics


class LaneStats(NamedTuple):
    """Scalar totals for one round of device lanes (int32/int64 leaves —
    a pytree, so it rides jit/device boundaries like any kernel value)."""

    lanes: jnp.ndarray
    done: jnp.ndarray
    violations: jnp.ndarray
    overflow: jnp.ndarray
    deliveries: jnp.ndarray
    invariant_checks: jnp.ndarray

    def __add__(self, other: "LaneStats") -> "LaneStats":
        return LaneStats(*(a + b for a, b in zip(self, other)))

    def to_host(self) -> dict:
        """ONE device->host pull for the whole pytree."""
        return {
            k: int(v) for k, v in zip(self._fields, jax.device_get(self))
        }


def zero() -> LaneStats:
    return LaneStats(*(jnp.int32(0) for _ in LaneStats._fields))


@functools.partial(jax.jit, static_argnames=("invariant_interval",))
def reduce_lanes(status, violation, deliveries, lanes,
                 invariant_interval: int = 0) -> LaneStats:
    """Reduction of one round's per-lane verdict arrays to LaneStats
    totals — THE definition of every ``device.lane.*`` counter, shared by
    all drivers (chunked sweep, continuous refill, DPOR rounds) so the
    fields cannot drift between them.

    ``lanes`` selects which of the batch to count: a scalar keeps the
    first N (pad-lane exclusion — mesh-alignment duplicates), a bool [B]
    mask keeps exactly those lanes (the continuous driver's
    finished-this-round set). Called on device arrays this runs as one
    on-device reduction with a single host pull; host numpy arrays work
    too (the continuous driver's already-pulled harvest vectors)."""
    from ..device.core import ST_DONE, ST_OVERFLOW, ST_VIOLATION

    lanes = jnp.asarray(lanes)
    if lanes.ndim == 0:
        real = jnp.arange(status.shape[0]) < lanes
    else:
        real = lanes
    finished = real & (status >= ST_DONE)
    overflow = real & (status == ST_OVERFLOW)
    # A verdict: neither overflowed nor cut unfinished (ST_UNFINISHED).
    counted = finished & (status <= ST_VIOLATION)
    deliv = jnp.sum(jnp.where(real, deliveries, 0))
    if invariant_interval:
        checks = (
            jnp.sum(jnp.where(real, deliveries // invariant_interval, 0))
            + jnp.sum(counted.astype(jnp.int32))
        )
    else:
        checks = jnp.sum(counted.astype(jnp.int32))
    return LaneStats(
        lanes=jnp.sum(real.astype(jnp.int32)),
        done=jnp.sum(counted.astype(jnp.int32)),
        violations=jnp.sum((real & (violation != 0)).astype(jnp.int32)),
        overflow=jnp.sum(overflow.astype(jnp.int32)),
        deliveries=deliv,
        invariant_checks=checks,
    )


def record(stats: "LaneStats | dict", driver: str,
           unique_schedules: int = None) -> None:
    """Fold a round's LaneStats into the process registry (one transfer
    when given the device pytree). No-op while telemetry is disabled."""
    if not _metrics.enabled():
        return
    host = stats.to_host() if isinstance(stats, LaneStats) else dict(stats)
    for field, value in host.items():
        _metrics.counter(f"device.lane.{field}").inc(value, driver=driver)
    if unique_schedules is not None:
        _metrics.counter("device.lane.unique_schedules").inc(
            unique_schedules, driver=driver
        )
