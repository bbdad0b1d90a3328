"""The bytes a step kernel cannot avoid moving, from the configuration's
shapes alone (``configs/<config>.json``'s ``shapes``).

One lane's schedule state is the actors' state rows, their liveness bits,
the partition matrix, the pending pool (flags, source, destination,
payload, arrival order, creating record) and the timer memory, plus a
dozen scalars and the lane's random key. The pool's payload dominates.

Between two launches the state lives in the device's main memory, because
the host reads each lane's status there before it decides what to refill.
So a segment of ``seg_steps`` steps has to read every resident lane's
state once and write it once, whatever the kernel does in between: that is
the floor. A kernel that round-trips the state on every step (the XLA
build today) moves ``seg_steps`` times as much; one that keeps it in fast
memory for the whole segment approaches the floor. The floor is what the
roofline share is taken against, so no kernel of these semantics can pass
100%.
"""

from __future__ import annotations

INT32 = 4
BOOL = 1


def state_bytes_per_lane(shapes: dict) -> int:
    n = shapes["num_actors"]
    s = shapes["state_width"]
    w = shapes["msg_width"]
    p = shapes["pool_capacity"]
    payload = {"int32": 4, "int16": 2}[shapes.get("msg_dtype", "int32")]
    actors = INT32 * n * s + 3 * BOOL * n + BOOL * n * n
    pool = p * (3 * BOOL + 4 * INT32 + payload * w)
    timers = payload * n * w + BOOL * n + INT32 * n
    scalars = 10 * INT32 + BOOL + 2 * INT32  # counters and status, final_seg, key
    return actors + pool + timers + scalars


def least_bytes_per_lane_step(shapes: dict, seg_steps: int) -> float:
    """One read and one write of the lane's state for each segment."""
    return 2.0 * state_bytes_per_lane(shapes) / seg_steps
