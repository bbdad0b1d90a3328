"""Multi-process sweep: the DCN half of SURVEY §5.8 as a real
``jax.distributed`` deployment (not the in-process slice simulation).

Topology: each process initializes the shared jax.distributed runtime
(coordination service over TCP — the DCN stand-in on one host, the actual
DCN on a multi-slice pod), sweeps its own partition of the seed space on
its LOCAL devices, and the per-slice violation summaries — O(counters),
never schedule state — are aggregated with a cross-process allgather over
the distributed runtime's collectives (Gloo on CPU, ICI/DCN on TPU).

Two entry points:
  - ``run_slice(...)``: what ONE process runs (importable; also the
    ``python -m demi_tpu.parallel.distributed`` worker main).
  - ``launch_distributed_sweep(...)``: single-host CPU rehearsal that
    spawns N worker processes with virtual CPU devices and returns rank
    0's aggregated summary — it proves the deployment shape without a
    pod and never touches a chip (``sweep --processes N`` says so).
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
from typing import Optional


#: The fault plane's knobs (crash-recovery, link partitions, bounded
#: waits, the raft log's capacity, the spark job's shape) with the values
#: that were literals in ``cli.build_fuzzer`` / ``cli.build_app`` before
#: they became flags: the CLI's flags and ``DEFAULT_WORKLOAD`` default to
#: these.
FAULT_PLANE_DEFAULTS = {
    "send_weight": 0.6,
    "wait_weight": 0.15,
    "hard_kill_weight": 0.0,
    "restart_weight": 0.0,
    "max_kills": 1,
    "max_sends": None,  # client sends a program may hold; None = unlimited
    "wait_budget": None,  # (lo, hi) deliveries of a generated wait; None = drain
    "log_cap": 8,
    # raft_reconfig: applied entries above its snapshot at which a server
    # compacts (etcd's --snapshot-count); None = half of ``log_cap``.
    "snapshot_every": None,
    # App-shape keys the other apps ignore, as they ignore ``log_cap``:
    # spark's stages a job and tasks a stage.
    "stages": 2,
    "tasks": 4,
    # The datagram discipline (``DSLApp.channels == "datagram"``; any
    # other app refuses a non-zero weight): the share of dispatch steps
    # that deliver an actor's message and keep it pending, the share that
    # lose it undelivered, and how many of each a schedule may hold.
    "dup_weight": 0.0,
    "drop_weight": 0.0,
    "max_dups": 0,
    "max_drops": 0,
}

DEFAULT_WORKLOAD = {
    "app": "broadcast",
    "nodes": 4,
    "bug": "x",
    "seed": 0,
    "num_events": 10,
    "max_messages": 96,
    "timer_weight": 0.2,
    "kill_weight": 0.05,
    "partition_weight": 0.0,
    "pool": 64,
    **FAULT_PLANE_DEFAULTS,
}


def workload_args(workload: Optional[dict]):
    """CLI-args-shaped namespace over DEFAULT_WORKLOAD + overrides — the
    shared front half of every multi-process workload builder (this
    module's sweep slices AND the fleet's coordinator/worker pair), so
    a flag means the same thing in every process."""
    import argparse

    return argparse.Namespace(**{**DEFAULT_WORKLOAD, **(workload or {})})


def build_workload(workload: Optional[dict], record: bool = False):
    """Build (app, DeviceConfig, fuzzer) from a CLI-args-shaped dict,
    reusing the CLI's own builders. ``record=True`` turns on trace +
    parent recording (the DPOR/fleet shape; sweeps keep it off)."""
    from ..obs import spans

    with spans.stage("setup.build", what="workload"):
        from ..cli import build_app, build_fuzzer
        from ..device.core import DeviceConfig

        args = workload_args(workload)
        app = build_app(args)
        cfg = DeviceConfig.for_workload(
            app, args, record_trace=record, record_parents=record
        )
        fuzzer = build_fuzzer(app, args)
    return app, cfg, fuzzer


_build_workload = build_workload  # back-compat alias


def run_slice(
    coordinator: str,
    num_processes: int,
    process_id: int,
    total_lanes: int,
    chunk_size: int,
    workload: Optional[dict] = None,
) -> dict:
    """One slice's work: initialize the distributed runtime, sweep this
    process's seed partition, allgather the summaries. ``workload`` is a
    CLI-args-shaped dict (see DEFAULT_WORKLOAD)."""
    import jax

    from ..persist.supervisor import SUPERVISOR

    def _connect(attempt: int):
        # A worker that races the coordination-service startup (rank 0
        # not listening yet, a slow DNS, a recycled port) used to fail
        # the whole launch on its first refused connection; bounded
        # retry/backoff rides the same LaunchSupervisor as every other
        # I/O surface (DEMI_LAUNCH_RETRIES; --strict-io raises
        # StrictIOError on exhaustion instead of limping).
        if attempt:
            try:
                jax.distributed.shutdown()
            except Exception:
                pass  # a half-initialized runtime blocks re-initialize
        jax.distributed.initialize(
            coordinator, num_processes=num_processes, process_id=process_id
        )

    SUPERVISOR.run(_connect, label="distributed.connect")
    import jax.numpy as jnp
    from jax.experimental import multihost_utils

    from .mesh import device_fields
    from .sweep import SweepDriver

    app, cfg, fuzzer = _build_workload(workload or {})
    driver = SweepDriver(
        app, cfg, lambda s: fuzzer.generate_fuzz_test(seed=s)
    )
    # Seed partition: rank r takes seeds r, r+P, r+2P, ... (disjoint).
    seeds = list(range(process_id, total_lanes, num_processes))
    mode = (workload or {}).get("sweep_mode") or "continuous"
    if mode == "continuous":
        # Lane compaction composes with the multi-process deployment:
        # each rank runs the refill driver over its OWN strided seed
        # partition (same per-seed keys as run_chunk -> identical
        # verdicts either mode).
        import time as _time

        from ..device.core import ST_OVERFLOW

        drv = driver._continuous_driver(chunk_size)
        lanes = violations = overflow = 0
        t0 = _time.perf_counter()
        for _seed, st, code, _h in drv._run(0, seeds=seeds):
            lanes += 1
            violations += code != 0
            overflow += st == ST_OVERFLOW
        seconds = _time.perf_counter() - t0
    else:
        chunks = []
        for i in range(0, len(seeds), chunk_size):
            chunks.append(
                driver.run_chunk(
                    seeds[i : i + chunk_size], slice_index=process_id
                )
            )
        lanes = sum(c.lanes for c in chunks)
        violations = sum(c.violations for c in chunks)
        overflow = sum(c.overflow_lanes for c in chunks)
        seconds = sum(c.seconds for c in chunks)
    # Only summaries cross the wire (O(counters) per slice).
    local = jnp.asarray([lanes, violations, overflow], jnp.int32)
    gathered = multihost_utils.process_allgather(local)
    device = device_fields()
    return {
        "process_id": process_id,
        "num_processes": num_processes,
        "platform": device["platform"],
        "device_kind": device["device_kind"],
        "global_devices": jax.device_count(),
        "local_devices": device["devices"],
        "per_slice": [[int(x) for x in row] for row in gathered],
        "total_lanes": int(gathered[:, 0].sum()),
        "total_violations": int(gathered[:, 1].sum()),
        "total_overflow": int(gathered[:, 2].sum()),
        "local_seconds": round(seconds, 3),
    }


_SUMMARY_MARK = "DEMI_SUMMARY:"


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def launch_distributed_sweep(
    num_processes: int = 2,
    total_lanes: int = 64,
    chunk_size: int = 16,
    workload: Optional[dict] = None,
    devices_per_process: int = 2,
    timeout: float = 600.0,
) -> dict:
    """Single-host CPU REHEARSAL of the multi-process deployment: N worker
    processes on virtual CPU devices, one shared distributed runtime.
    It forces ``JAX_PLATFORMS=cpu`` in the workers and never uses a chip
    — each rank's summary names the platform it ran on. Returns rank 0's
    aggregated summary."""
    port = _free_port()
    coordinator = f"127.0.0.1:{port}"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices_per_process}"
    )
    env.pop("JAX_NUM_PROCESSES", None)
    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    from ..persist.supervisor import SUPERVISOR

    procs = [
        # Spawn under the launch supervisor: a transient fork/exec
        # failure (EAGAIN under memory pressure, a racing fd limit)
        # retries with backoff instead of failing the whole launch.
        SUPERVISOR.run(
            lambda _attempt, rank=rank: subprocess.Popen(
                [
                    sys.executable, "-m", "demi_tpu.parallel.distributed",
                    coordinator, str(num_processes), str(rank),
                    str(total_lanes), str(chunk_size),
                    json.dumps(workload or {}),
                ],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
                env=env,
            ),
            label="distributed.spawn",
        )
        for rank in range(num_processes)
    ]
    # Drain all workers CONCURRENTLY: sequential communicate() deadlocks if
    # a later-drained worker fills its pipe buffer before the collective.
    import threading

    outs: list = [None] * num_processes
    errs: list = [None] * num_processes

    def _drain(i, p):
        try:
            out, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            out, err = p.communicate()
        outs[i] = out
        errs[i] = err

    threads = [
        threading.Thread(target=_drain, args=(i, p), daemon=True)
        for i, p in enumerate(procs)
    ]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout + 30)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    outs = [
        (p.returncode, outs[i] or "", errs[i] or "")
        for i, p in enumerate(procs)
    ]
    for rc, out, err in outs:
        if rc != 0:
            from ..persist.supervisor import StrictIOError, strict_io_enabled

            msg = (
                f"worker failed rc={rc}: stdout={out[-300:]!r} "
                f"stderr={err[-800:]!r}"
            )
            # --strict-io (env DEMI_STRICT_IO, inherited by the workers)
            # makes a dead slice the loud CI failure class it is.
            if strict_io_enabled(None):
                raise StrictIOError(msg)
            raise RuntimeError(msg)
    # Every rank prints its summary; rank 0's carries the aggregate. The
    # sentinel + raw_decode survives collective backends (Gloo) writing
    # status text onto the same stdout, even mid-line.
    def rank_summary(out: str) -> dict:
        pos = out.rfind(_SUMMARY_MARK)
        if pos < 0:
            raise RuntimeError(f"no summary in worker stdout: {out[-500:]!r}")
        summary, _ = json.JSONDecoder().raw_decode(
            out[pos + len(_SUMMARY_MARK):]
        )
        return summary

    return rank_summary(outs[0][1])


def main(argv) -> int:
    coordinator, n, rank, lanes, chunk, workload_json = argv[:6]
    summary = run_slice(
        coordinator, int(n), int(rank), int(lanes), int(chunk),
        json.loads(workload_json),
    )
    print("\n" + _SUMMARY_MARK + json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
