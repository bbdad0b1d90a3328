"""Mesh sharding of schedule sweeps: the distributed backend.

The reference's "distributed communication" is interposed Akka messaging in
one JVM (SURVEY.md §2.9); its only scale-out is shell-looped experiments.
Here the scale-out axes are real (SURVEY.md §2.8, BASELINE north star):

  - ``lanes`` — the schedule batch. Embarrassingly parallel: each lane's
    state (actor states + pool) lives resident on its device; XLA inserts
    no collectives inside a lane. Sharding the batch over ICI scales
    schedules/sec linearly with chips in a slice.
  - multi-slice sweeps (DCN) are plain program-level splits: each slice
    takes a disjoint seed/program range (see sweep.py); only violation
    summaries return to host, so DCN traffic is O(batch), not O(state).

A 2-D mesh (``replica`` × ``shard``) is supported by collapsing both axes
onto the lane batch — the natural layout when embedding sweeps inside a
larger job's mesh. Cross-lane reductions (e.g. "any violation in batch",
violation histograms) are jnp reductions over the sharded axis, which XLA
lowers to psum-style collectives over ICI.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..dsl import DSLApp
from ..device.core import DeviceConfig
from ..device.explore import make_run_lane


LANES = "lanes"


def make_mesh(devices: Optional[Sequence] = None, axis: str = LANES) -> Mesh:
    devices = list(devices) if devices is not None else jax.devices()
    return Mesh(np.asarray(devices), (axis,))


def local_lane_mesh(batch: Optional[int] = None) -> Optional[Mesh]:
    """The mesh a one-process driver shards its lane batch over: every
    local device when this process has more than one — and, for kernels
    that need an even split, when ``batch`` divides by their count. None
    selects the single-device kernels. The CLI verbs and the fleet worker
    all choose here, from what the process can observe, with no flag."""
    n = jax.local_device_count()
    if n <= 1 or (batch is not None and batch % n):
        return None
    return make_mesh(jax.local_devices())


def device_fields() -> dict:
    """The devices this process's numbers come from, as JAX reports
    them — carried by every sweep/dpor/minimize summary, every fleet
    worker and every rehearsal rank, so a run that lost its chip does not
    read as a slow run and a child can be held to its device."""
    devices = jax.local_devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "devices": len(devices),
    }


def lane_sharding_summary(x) -> dict:
    """How a kernel output's lane axis is laid out: the devices its
    sharding spans and the lanes each holds (what a run on several chips
    shows to prove they were used)."""
    shards = x.addressable_shards
    return {
        "devices": len(x.sharding.device_set),
        "lanes_per_device": int(shards[0].data.shape[0]),
    }


def sweep_sharding(mesh: Mesh, axis: str = LANES) -> Tuple[NamedSharding, NamedSharding]:
    """(batch-axis sharding, fully-replicated sharding) for a sweep."""
    return NamedSharding(mesh, P(axis)), NamedSharding(mesh, P())


def _shard_lane_kernel(
    run_lane, mesh: Mesh, axis: str, n_in: int = 2, start_state: bool = False
):
    """vmap a single-lane fn and shard its lane batch over the mesh: all
    ``n_in`` inputs and the outputs are sharded on their leading (lane)
    dimension; each device advances its lane shard independently — the
    pjit/ICI scale-out.

    ``start_state=True`` appends a trailing PrefixSnapshot argument
    (device/fork.py) broadcast over the lane axis (vmap in_axes=None) and
    fully replicated over the mesh: every device forks its lane shard
    from the same trunk state."""
    batch_sharding = NamedSharding(mesh, P(axis))
    if start_state:
        replicated = NamedSharding(mesh, P())
        return jax.jit(
            jax.vmap(
                lambda *args: run_lane(*args),
                in_axes=(0,) * n_in + (None,),
            ),
            in_shardings=(batch_sharding,) * n_in + (replicated,),
            out_shardings=batch_sharding,
        )
    return jax.jit(
        jax.vmap(run_lane),
        in_shardings=(batch_sharding,) * n_in,
        out_shardings=batch_sharding,
    )


def shard_explore_kernel(
    app: DSLApp,
    cfg: DeviceConfig,
    mesh: Mesh,
    axis: str = LANES,
    start_state: bool = False,
):
    """Explore sweep with the lane batch sharded over the mesh."""
    return _shard_lane_kernel(
        make_run_lane(app, cfg), mesh, axis, start_state=start_state
    )


def shard_replay_kernel(
    app: DSLApp,
    cfg: DeviceConfig,
    mesh: Mesh,
    axis: str = LANES,
    start_state: bool = False,
):
    """Batched replay (minimization trials) sharded over the mesh: one
    DDMin level's candidate subsequences spread across chips."""
    from ..device.replay import make_replay_run_lane

    return _shard_lane_kernel(
        make_replay_run_lane(app, cfg), mesh, axis, start_state=start_state
    )


def shard_dpor_kernel(
    app: DSLApp,
    cfg: DeviceConfig,
    mesh: Mesh,
    axis: str = LANES,
    start_state: bool = False,
):
    """DPOR frontier batches sharded over the mesh: each device replays
    its shard of the round's prescriptions (prescription-guided explore
    lanes are independent, so no collectives inside a round — the
    frontier/backtrack analysis stays on the host)."""
    from ..device.dpor_sweep import make_dpor_run_lane

    return _shard_lane_kernel(
        make_dpor_run_lane(app, cfg), mesh, axis, n_in=3,
        start_state=start_state,
    )


def shard_dpor_sleep_kernel(
    app: DSLApp,
    cfg: DeviceConfig,
    mesh: Mesh,
    sleep_cap: int,
    commute_matrix=None,
    axis: str = LANES,
    start_state: bool = False,
):
    """The sleep-set DPOR twin sharded over the mesh — the fleet's
    intra-slice ring with optimal-DPOR tracking on: per-lane sleep rows
    ([B, sleep_cap, recw]) and node ordinals shard with the lane batch
    (``n_in=5``), the optional trunk snapshot stays replicated, and the
    per-lane wake observations come back sharded like every other
    result field. Lane semantics are bit-identical to the unsharded
    sleep kernel (lanes have no cross-lane ops; sharding is placement
    only)."""
    from ..device.dpor_sweep import make_dpor_sleep_run_lane

    return _shard_lane_kernel(
        make_dpor_sleep_run_lane(app, cfg, sleep_cap, commute_matrix),
        mesh, axis, n_in=5, start_state=start_state,
    )


def pad_batch_to_devices(n: int, mesh: Mesh, axis: str = LANES) -> int:
    """Round a batch size up to a multiple of the mesh axis size."""
    size = mesh.shape[axis]
    return ((n + size - 1) // size) * size
