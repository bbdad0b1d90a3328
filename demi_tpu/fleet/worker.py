"""Fleet worker: the device half of leased frontier rounds.

A worker is deliberately stateless about the search: it connects to the
coordinator (bounded retry under the launch supervisor — racing the
coordinator's startup must not kill the fleet), rebuilds the workload
from the config message (the SAME builder the coordinator ran; the
handler fingerprint is checked so same-shape-different-bug workloads
can never cross), compiles its DPOR kernel once (warm-up launch before
the first lease, so lease busy time measures rounds, not XLA
compilation), then loops: lease → execute → ship the raw lane records
back. All admission, dedup, and class bookkeeping stay on the
coordinator, which is what makes any worker count bit-identical to the
single-process loop.

Intra-slice ring: with more than one local device (the launcher's
``devices_per_worker`` sets ``--xla_force_host_platform_device_count``
on CPU; real chips on TPU), the worker builds the MESH-sharded kernel
twin (parallel/mesh.py) and each leased round's lane batch shards
across its local devices — ICI-scale parallelism inside the round,
DCN-scale across workers.

``DEMI_FLEET_DIE_AFTER=N`` makes the worker die abruptly (``os._exit``)
upon receiving its N-th lease, holding it un-executed — the preemption
hook the revocation tests use: the coordinator re-leases the round and
coverage is unchanged.
"""

from __future__ import annotations

import json
import os
import socket
import sys
import time
from typing import Any, Dict, Optional


def _send(f, obj: Dict[str, Any]) -> None:
    f.write((json.dumps(obj) + "\n").encode())
    f.flush()


def _recv(f) -> Optional[Dict[str, Any]]:
    line = f.readline()
    if not line:
        return None
    return json.loads(line)


def run_worker(addr: str, worker_id: str) -> int:
    from ..obs import distributed as dtrace
    from ..persist.supervisor import SUPERVISOR

    host, _, port = addr.rpartition(":")
    sock = SUPERVISOR.run(
        lambda _attempt: socket.create_connection(
            (host or "127.0.0.1", int(port)), timeout=30
        ),
        label="fleet.connect",
    )
    f = sock.makefile("rwb")
    # Per-connection clock sync: every request is sender-stamped, every
    # coordinator reply is server-stamped, and the NTP midpoint of the
    # tightest exchange estimates (coordinator clock - local clock) —
    # what `trace stitch` shifts this worker's spans by.
    sync = dtrace.ClockSync()

    def rpc(msg: Dict[str, Any]) -> Optional[Dict[str, Any]]:
        msg["t_sent_us"] = dtrace.wall_us()
        _send(f, msg)
        reply = _recv(f)
        if reply is not None:
            sync.observe(msg["t_sent_us"], reply.get("t_server_us"))
        return reply

    cfg_msg = rpc({"op": "hello", "worker": worker_id})
    if cfg_msg is None or cfg_msg.get("op") != "config":
        print(f"fleet worker {worker_id}: bad config {cfg_msg!r}",
              file=sys.stderr)
        return 4
    trace_parent = dtrace.TraceContext.from_wire(cfg_msg.get("trace"))
    span_dir = cfg_msg.get("span_dir")

    import jax
    import numpy as np

    from .. import obs
    from ..persist.checkpoint import (
        handler_fingerprint,
        pack_array,
        unpack_array,
    )
    from .coordinator import build_fleet_workload

    if cfg_msg.get("obs"):
        obs.enable()
    app, cfg, program = build_fleet_workload(cfg_msg["workload"])
    fp = handler_fingerprint(app)
    if fp != cfg_msg.get("fp"):
        # Same-shape different-handler workloads must never exchange
        # prescriptions (the persist/ cross-restore argument).
        print(
            f"fleet worker {worker_id}: workload fingerprint mismatch "
            f"(coordinator {cfg_msg.get('fp')}, local {fp})",
            file=sys.stderr,
        )
        return 5

    from ..device.dpor_sweep import build_dpor_kernel, lane_keys
    from ..device.encoding import lower_program
    from ..device.explore import broadcast_program
    from ..parallel.mesh import device_fields, local_lane_mesh

    batch = int(cfg_msg["batch"])
    sleep = bool(cfg_msg.get("sleep"))
    sleep_cap = int(cfg_msg.get("sleep_cap", 0)) if sleep else 0
    matrix = None
    if sleep:
        from ..analysis import StaticIndependence

        matrix = StaticIndependence.for_app(app).device_matrix()
    kernel = build_dpor_kernel(
        app, cfg, mesh=local_lane_mesh(batch), sleep_cap=sleep_cap,
        commute_matrix=matrix,
    )
    prog = lower_program(app, cfg, list(program))
    progs = broadcast_program(prog, batch)

    def execute(prescs, keys, sleeps, sfrom):
        if sleeps is None:
            res = kernel(progs, prescs, keys)
        else:
            res = kernel(progs, prescs, keys, sleeps, sfrom)
        jax.block_until_ready(res.violation)
        return res

    # Warm-up: compile outside any lease so busy_s measures execution.
    warm_prescs = np.zeros(
        (batch, cfg.max_steps, cfg.rec_width), np.int32
    )
    warm_keys = lane_keys(np.arange(batch, dtype=np.uint32))
    execute(
        warm_prescs, warm_keys,
        np.zeros((batch, sleep_cap, cfg.rec_width), np.int32)
        if sleep else None,
        np.zeros((batch,), np.int32) if sleep else None,
    )

    # What this worker's runtime came up on, sent with its first poll:
    # the coordinator never asks JAX itself.
    first_poll: Dict[str, Any] = {
        "device": {
            **device_fields(),
            "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        }
    }
    die_after = int(os.environ.get("DEMI_FLEET_DIE_AFTER", "0") or 0)
    served = 0
    while True:
        msg = rpc({"op": "next", "worker": worker_id, **first_poll})
        first_poll = {}
        if msg is None or msg.get("op") == "shutdown":
            break
        if msg.get("op") == "wait":
            time.sleep(float(msg.get("s", 0.05)))
            continue
        if msg.get("op") != "lease":
            print(f"fleet worker {worker_id}: unexpected {msg!r}",
                  file=sys.stderr)
            return 6
        served += 1
        if die_after and served >= die_after:
            # Preemption hook: die upon RECEIVING the Nth lease, i.e.
            # holding it un-executed — the coordinator must revoke and
            # re-lease the round bit-identically.
            os._exit(17)
        prescs = unpack_array(msg["prescs"])
        keys = lane_keys(unpack_array(msg["seeds"]))
        sleeps = unpack_array(msg["sleeps"]) if "sleeps" in msg else None
        sfrom = unpack_array(msg["sfrom"]) if "sfrom" in msg else None
        # Child span under the propagated lease context: the stitched
        # timeline shows this execute inside the coordinator's
        # fleet.lease span, linked by trace_id/parent_span.
        lease_ctx = (
            dtrace.TraceContext.from_wire(msg.get("trace")) or trace_parent
        )
        span_args = lease_ctx.span_args() if lease_ctx is not None else {}
        t0 = time.perf_counter()
        with obs.span(
            "fleet.execute", worker=worker_id, lease=msg["lease"],
            round=msg.get("round"), **span_args,
        ):
            res = execute(prescs, keys, sleeps, sfrom)
        busy = time.perf_counter() - t0
        obs.counter("fleet.worker_rounds").inc(worker=worker_id)
        obs.gauge("fleet.worker_busy_seconds").set(
            round(busy, 6), worker=worker_id
        )
        ack = rpc({
            "op": "result",
            "worker": worker_id,
            "lease": msg["lease"],
            "busy_s": busy,
            "res": {
                field: pack_array(getattr(res, field))
                for field in type(res)._fields
            },
        })
        if ack is None:
            break
    if obs.enabled() and span_dir:
        # Span sidecar for `demi_tpu trace stitch`, clock-shifted onto
        # the coordinator's timeline by the measured offset.
        try:
            dtrace.export_process(
                span_dir, f"worker-{worker_id}",
                clock_offset_us=sync.offset_us(),
            )
        except OSError:
            pass
    bye: Dict[str, Any] = {"op": "bye", "worker": worker_id}
    if sync.samples:
        bye["clock_offset_us"] = round(sync.offset_us(), 3)
    if obs.enabled():
        bye["obs"] = obs.REGISTRY.snapshot()
    try:
        _send(f, bye)
        _recv(f)
    except OSError:
        pass
    sock.close()
    return 0


def main(argv) -> int:
    if len(argv) < 2:
        print("usage: python -m demi_tpu.fleet.worker <host:port> <id>",
              file=sys.stderr)
        return 2
    return run_worker(argv[0], argv[1])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
