"""Cross-host fleet coordinator: generation-frozen round leases (DCN).

The coordinator owns the ENTIRE host half of one DeviceDPOR search —
frontier, explored tuple/digest sets, sleep/class ledgers, wakeup
guides, admission order — and farms out only the device half: a *lease*
is one frontier round's pure kernel inputs (packed prescriptions,
per-lane rng keys, sleep rows — the delta/zlib payloads persist/ already
defines), and a worker's result is the raw lane records the host half
derives the next generation from.

Why this is BIT-IDENTICAL to the single-process loop, at any worker
count: rounds select from the generation frozen at the last boundary,
and a lane's execution is a function of its prescription content and
its rng key alone — never of admissions made by other rounds — so
concurrent rounds commute. The coordinator plans rounds with exactly
the sequential loop's selection rule (`DeviceDPOR._select_batch` over
the frozen remainder, `_merge_generations` only at the drain tail, key
bases advanced round-by-round) and processes results in canonical round
order through the very same `DeviceDPOR._process_round`, so the
explored set, Mazurkiewicz class set, violation-code set, and even the
first-found record are byte-identical to `DeviceDPOR.explore`
(tests/test_fleet.py and bench --config 13 pin it at 1/2/4 workers).

Leases are revocable and workers preemptible: a dead connection or a
missed deadline moves the lease back to the head of the queue and any
worker re-executes it — round inputs are pure, so the re-execution is
bit-identical (the PR 10 resume argument applied per round). A late
result from a presumed-dead worker is accepted if its lease has not
been re-served, and ignored otherwise.

The class ledger is global by construction (all admission runs through
the coordinator's SleepSets) and persists ACROSS runs via the
content-addressed ``ClassStore``: with ``warm_start`` the prior class
frontier loads at startup, covered classes suppress at admission
(``fleet.warm_skips``), and the updated ledger publishes one segment at
shutdown.
"""

from __future__ import annotations

import hashlib
import json
import os
import socketserver
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from .. import obs
from ..obs import distributed as dtrace
from ..obs import spans as ospans
from .ledger import ClassLedger, ClassStore


def build_fleet_workload(workload: Optional[dict]):
    """(app, DeviceConfig, program) from a CLI-args-shaped workload dict
    — the ONE builder both the coordinator and every worker run
    (parallel/distributed.py's shared builder with recording on), so a
    lease's prescription rows mean the same thing on every host. The
    config message's handler fingerprint double-checks it.

    ``workload["commands"]`` (raft only) appends that many client
    commands to the program — the deep seeded-frontier fixture shape
    bench configs 9/13 explore."""
    from ..apps.common import dsl_start_events
    from ..external_events import WaitQuiescence
    from ..parallel.distributed import build_workload

    app, cfg, _fuzzer = build_workload(workload, record=True)
    program = dsl_start_events(app)
    commands = int((workload or {}).get("commands", 0) or 0)
    if commands:
        from ..apps.raft import T_CLIENT
        from ..external_events import MessageConstructor, Send

        if (workload or {}).get("app", "broadcast") != "raft":
            raise ValueError("workload 'commands' is raft-only")
        program += [
            Send(
                app.actor_name(i % app.num_actors),
                MessageConstructor(
                    lambda v=10 + i: (T_CLIENT, 0, v, 0, 0, 0, 0)
                ),
            )
            for i in range(commands)
        ]
    program += [WaitQuiescence()]
    return app, cfg, program


def set_digest(items) -> str:
    """Order-free content digest of a set of row-tuple sequences
    (explored prescriptions, class keys): sha256 over the sorted packed
    frame — the cross-process coverage-parity comparator."""
    from ..persist.checkpoint import pack_prescriptions

    payload = pack_prescriptions(sorted(items))
    return hashlib.sha256(
        json.dumps(payload, sort_keys=True).encode()
    ).hexdigest()[:16]


def _no_local_kernel(*_args, **_kwargs):
    raise RuntimeError(
        "the fleet coordinator launches no kernels; rounds run on workers"
    )


class Lease(NamedTuple):
    """One generation-frozen frontier round, leased as pure kernel
    inputs. ``batch`` keeps the identities (a ``PrescList`` over the
    coordinator's explored log, items reading as tuples) for host-side
    processing; ``n_real`` counts the non-padding entries (what a
    revoked-and-never-run lease returns to the frontier)."""

    lease_id: int
    round_no: int
    batch: Any
    n_real: int
    prescs: np.ndarray
    # Per-lane rng SEEDS (uint32): the worker folds them into keys
    # (dpor_sweep.lane_keys), so planning a round runs no JAX here.
    seeds: np.ndarray
    sleeps: Optional[np.ndarray]
    sfrom: Optional[np.ndarray]


class _FleetHandler(socketserver.StreamRequestHandler):
    def handle(self):  # one persistent connection per worker
        co = self.server.coordinator  # type: ignore[attr-defined]
        worker = None
        try:
            while True:
                line = self.rfile.readline()
                if not line:
                    break
                msg = json.loads(line)
                op = msg.get("op")
                if op == "hello":
                    worker = str(msg.get("worker", "w?"))
                    reply = co.worker_hello(worker)
                elif op == "next":
                    if "device" in msg:
                        co.worker_device(worker, msg["device"])
                    reply = co.next_lease(worker)
                elif op == "result":
                    reply = co.submit(worker, msg)
                elif op == "bye":
                    co.worker_bye(worker, msg)
                    self._send({"op": "ok", "t_server_us": dtrace.wall_us()})
                    worker = None  # clean exit — nothing to revoke
                    break
                else:
                    reply = {"op": "error", "error": f"unknown op {op!r}"}
                # Every reply is server-timestamped so the worker's
                # per-connection ClockSync can feed its NTP midpoint
                # from the verbs that already exist.
                reply["t_server_us"] = dtrace.wall_us()
                self._send(reply)
        except (OSError, ValueError):
            pass  # dead peer / torn frame: the finally-revoke handles it
        finally:
            if worker is not None:
                co.worker_gone(worker)

    def _send(self, obj: Dict[str, Any]) -> None:
        self.wfile.write((json.dumps(obj) + "\n").encode())
        self.wfile.flush()


class FleetCoordinator:
    """See module doc. Construct, optionally ``dpor.seed(...)``, then
    ``serve()`` for the address and wait on ``done`` while workers
    connect; ``finalize()`` returns the summary."""

    def __init__(
        self,
        app,
        cfg,
        program,
        *,
        workload: Optional[dict] = None,
        batch_size: int = 16,
        max_rounds: int = 20,
        sleep: bool = True,
        prune: bool = False,
        static_prune: bool = False,
        class_store_dir: Optional[str] = None,
        warm_start: bool = False,
        delta: bool = False,
        stop_on_violation: bool = False,
        target_code: Optional[int] = None,
        lease_timeout: float = 120.0,
        max_outstanding: Optional[int] = None,
        min_ready: int = 1,
        journal_dir: Optional[str] = None,
        straggler_factor: float = 4.0,
        span_dir: Optional[str] = None,
        host_shards: Optional[int] = None,
    ):
        from ..analysis import SleepSets, StaticIndependence, sleep_cap
        from ..device.dpor_sweep import DeviceDPOR
        from ..parallel.distributed import DEFAULT_WORKLOAD
        from ..persist.checkpoint import handler_fingerprint

        self.app = app
        self.cfg = cfg
        self.workload = {**DEFAULT_WORKLOAD, **(workload or {})}
        self.max_rounds = max_rounds
        self.stop_on_violation = stop_on_violation
        self.target_code = target_code
        self.lease_timeout = lease_timeout
        self.max_outstanding = max_outstanding
        # Ready gate: hold the first lease until ``min_ready`` workers
        # have finished their warm-up compile and polled (or 60s pass).
        # Keeps per-worker busy attribution comparable — and lease
        # distribution deterministic enough for the preemption tests —
        # instead of letting the fastest-starting worker drain the
        # round budget while the others are still compiling.
        self.min_ready = min_ready
        self._ready: set = set()
        self._gate_open = min_ready <= 1
        self._first_ready_t: Optional[float] = None
        self.fp = handler_fingerprint(app)
        self.sleep_cap = sleep_cap() if sleep else 0
        rel = StaticIndependence.for_app(app) if (sleep or static_prune) else None
        sleep_obj: Any = (
            SleepSets(
                independence=rel, prune=prune, cap=self.sleep_cap,
                # Guides are retained only when a store is in play: they
                # are what makes a published class re-seedable by a
                # later differential run.
                retain_guides=class_store_dir is not None,
            )
            if sleep
            else False
        )
        # The coordinator's DeviceDPOR is the host half only: it gets a
        # kernel that refuses to launch (every round executes on a
        # worker), so this process builds no kernel, reads no backend
        # and initialises none — on a TPU host the chips stay free for
        # the workers, one process per chip.
        self.dpor = DeviceDPOR(
            app, cfg, program, batch_size=batch_size,
            kernel=_no_local_kernel,
            prefix_fork=False, double_buffer=False,
            sleep_sets=sleep_obj,
            static_independence=rel if static_prune else False,
            host_shards=host_shards,
        )
        self.store: Optional[ClassStore] = (
            ClassStore(class_store_dir, self.fp) if class_store_dir else None
        )
        # Journal is attached before the warm/delta block so the
        # ``dpor.delta`` record lands in it.
        self._journal_attached_here = False
        if journal_dir and not obs.journal.attached():
            obs.journal.attach(journal_dir)
            self._journal_attached_here = True
        self.warm = ClassLedger()
        self.delta_stats: Optional[Dict[str, Any]] = None
        if self.store is not None and self.dpor.sleep is not None:
            if delta:
                from ..analysis.delta import delta_warm_start

                self.delta_stats = delta_warm_start(
                    self.dpor, self.store, app
                )
            elif warm_start:
                self.warm = self.store.load()
                if self.warm.classes:
                    self.dpor.sleep.seed_covered(
                        self.warm.classes, meta=self.warm.meta
                    )
        # Distributed-trace root: every lease and config reply carries a
        # context derived from it, and finalize() exports the
        # coordinator's spans next to the journal for `trace stitch`.
        self.trace = dtrace.TraceContext.root("coordinator")
        self.span_dir = span_dir or journal_dir or (
            obs.journal.JOURNAL.root if obs.journal.attached() else None
        )
        # Straggler policy: an outstanding lease older than
        # ``straggler_factor`` x the median completed lease wall is
        # re-leased early (0 disables). Safe for bit-identity: the first
        # result in wins and the duplicate is dropped, exactly the
        # revoke/re-lease path.
        self.straggler_factor = float(straggler_factor)

        self._lock = threading.Lock()
        self.done = threading.Event()
        self._server: Optional[socketserver.ThreadingTCPServer] = None
        # The frozen generation and the next one: lists over the
        # explorer's explored log (device/explored_log.py).
        self._gen = self.dpor._list([])
        self._pending = self.dpor._list([])
        self._planned = 0
        self._processed = 0
        self._next_lease_id = 0
        # lease_id -> (lease, worker, deadline, issue monotonic time)
        self._outstanding: Dict[int, Tuple[Lease, str, float, float]] = {}
        self._requeue: List[Lease] = []
        self._results: Dict[int, Tuple[Lease, Any, float, str]] = {}
        self._found: Optional[Tuple[np.ndarray, int]] = None
        self._stop = False
        self._violating_rounds = 0
        self._releases = 0  # revoked-and-re-leased rounds
        self._stragglers = 0  # early re-leases from straggler detection
        self._lease_walls: List[float] = []  # completed issue->result walls
        self._lease_spans: Dict[int, str] = {}  # lease_id -> span id
        self._lease_issue_ts: Dict[int, int] = {}  # lease_id -> span-us
        self.workers: Dict[str, Dict[str, Any]] = {}
        self._started = False
        self.wall_t0 = 0.0

    # -- server ------------------------------------------------------------
    def serve(self, host: str = "127.0.0.1") -> str:
        """Start the lease server; returns ``host:port``. Also freezes
        the starting generation (call after any ``dpor.seed``)."""
        self._gen = self.dpor.frontier.copy()
        self._pending = self.dpor._list([])
        self._started = True
        self.wall_t0 = time.perf_counter()

        class _Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._server = _Server((host, 0), _FleetHandler)
        self._server.coordinator = self  # type: ignore[attr-defined]
        t = threading.Thread(target=self._server.serve_forever, daemon=True)
        t.start()
        port = self._server.server_address[1]
        return f"{host}:{port}"

    def close(self) -> None:
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server = None
        sharder = getattr(self.dpor, "_sharder", None)
        if sharder is not None:
            sharder.close()

    # -- worker lifecycle --------------------------------------------------
    def worker_hello(self, worker: str) -> Dict[str, Any]:
        with self._lock:
            self.workers.setdefault(worker, {
                "rounds": 0, "busy_s": 0.0, "interleavings": 0,
                "alive": True, "reconnects": 0,
            })
            self.workers[worker]["alive"] = True
            alive = sum(1 for w in self.workers.values() if w["alive"])
        obs.journal.emit(
            "fleet.worker", worker=worker, event="hello",
            workers_alive=alive,
        )
        return {
            "op": "config",
            "workload": self.workload,
            "fp": self.fp,
            "batch": self.dpor.batch_size,
            "sleep": self.dpor.sleep is not None,
            "sleep_cap": self.sleep_cap,
            "obs": obs.enabled(),
            # Distributed tracing: the root context this pod's spans
            # hang under, and where the worker should export its span
            # sidecar for `demi_tpu trace stitch`.
            "trace": self.trace.to_wire(),
            "span_dir": self.span_dir,
        }

    def worker_device(self, worker: Optional[str], device: Dict[str, Any]) -> None:
        """What a worker's JAX runtime came up on (platform, device kind,
        device count, the chip the launcher made visible) — reported
        once, after its warm-up launch, and kept in the summary so a
        worker that lost its chip does not read as a slow worker."""
        with self._lock:
            if worker in self.workers:
                self.workers[worker]["device"] = dict(device)

    def worker_bye(self, worker: Optional[str], msg: Dict[str, Any]) -> None:
        snap = msg.get("obs")
        if worker and snap:
            # Per-worker telemetry survives aggregation as labeled
            # series: `demi_tpu stats`/`--prom` render worker="w0" like
            # any other label.
            obs.REGISTRY.load(obs.relabel_snapshot(snap, worker=worker))
        with self._lock:
            if worker in self.workers:
                self.workers[worker]["alive"] = False
        if worker is not None:
            obs.journal.emit(
                "fleet.worker", worker=worker, event="bye",
                clock_offset_us=msg.get("clock_offset_us"),
            )

    def worker_gone(self, worker: str) -> None:
        """Connection died (crash, preemption, kill): revoke the
        worker's outstanding leases — the rounds re-lease bit-identically
        to whoever asks next."""
        with self._lock:
            if worker in self.workers:
                self.workers[worker]["alive"] = False
            revoked = [
                lid for lid, entry in self._outstanding.items()
                if entry[1] == worker
            ]
            for lid in revoked:
                lease = self._outstanding.pop(lid)[0]
                self._requeue.append(lease)
                self._releases += 1
            alive = sum(1 for w in self.workers.values() if w["alive"])
        if revoked:
            obs.counter("fleet.leases_revoked").force_inc(len(revoked))
        obs.journal.emit(
            "fleet.worker", worker=worker, event="gone",
            revoked=len(revoked), workers_alive=alive,
        )

    # -- lease planning ----------------------------------------------------
    def _check_expired_locked(self) -> None:
        now = time.monotonic()
        expired = [
            lid for lid, entry in self._outstanding.items()
            if entry[2] < now
        ]
        for lid in expired:
            lease = self._outstanding.pop(lid)[0]
            self._requeue.append(lease)
            self._releases += 1
            obs.counter("fleet.leases_expired").force_inc()
        self._check_stragglers_locked(now)

    def _check_stragglers_locked(self, now: float) -> None:
        """Early re-lease for stragglers: an outstanding lease whose
        wall already exceeds ``straggler_factor`` x the median completed
        lease wall goes back to the queue (journaled as
        ``fleet.straggler``) WITHOUT waiting for the full lease timeout.
        The canonical-order merge stays bit-identical because this is
        the existing revoke path: whichever result arrives first is
        accepted and the other is dropped as a duplicate — round inputs
        are pure, so both results are the same bytes."""
        if self.straggler_factor <= 0 or len(self._lease_walls) < 5:
            return
        walls = sorted(self._lease_walls)
        median = walls[len(walls) // 2]
        # Floor the limit: sub-100ms medians on warm CPU rounds must not
        # turn ordinary scheduling jitter into a re-lease storm.
        limit = max(self.straggler_factor * median, 0.25)
        slow = [
            (lid, entry) for lid, entry in self._outstanding.items()
            if now - entry[3] > limit
        ]
        for lid, (lease, w, _deadline, t_issue) in slow:
            del self._outstanding[lid]
            self._requeue.append(lease)
            self._releases += 1
            self._stragglers += 1
            obs.counter("fleet.stragglers").force_inc()
            obs.journal.emit(
                "fleet.straggler",
                worker=w,
                lease=lid,
                round=lease.round_no,
                wall_s=round(now - t_issue, 6),
                median_s=round(median, 6),
                factor=self.straggler_factor,
                leases_outstanding=len(self._outstanding),
            )

    def _finished_locked(self) -> bool:
        if self.done.is_set():
            return True
        if self._stop:
            self.done.set()
            return True
        idle = (
            self._planned == self._processed
            and not self._outstanding
            and not self._requeue
            and not self._results
        )
        if idle and self._planned >= self.max_rounds:
            self.done.set()
            return True
        if idle and not self._gen and not self._pending:
            self.done.set()
            return True
        return False

    def next_lease(self, worker: Optional[str]) -> Dict[str, Any]:
        if worker is None:
            return {"op": "error", "error": "hello first"}
        wait = {"op": "wait", "s": 0.05}
        with self._lock:
            self._check_expired_locked()
            if self._finished_locked():
                return {"op": "shutdown"}
            if not self._gate_open:
                self._ready.add(worker)
                now = time.monotonic()
                if self._first_ready_t is None:
                    self._first_ready_t = now
                if (
                    len(self._ready) >= self.min_ready
                    or now - self._first_ready_t > 60.0
                ):
                    self._gate_open = True
                else:
                    return wait
            if self._requeue:
                lease = self._requeue.pop(0)
                return self._issue_locked(lease, worker)
            if (
                self.max_outstanding is not None
                and len(self._outstanding) >= self.max_outstanding
            ):
                return wait
            if self._planned >= self.max_rounds:
                return wait  # round budget spent; drain what's in flight
            take = max(
                1, min(self.dpor.round_batch, self.dpor.batch_size)
            )
            if len(self._gen) < take:
                # Drain tail: the next round may pull the pending
                # generation forward, which is only deterministic once
                # every earlier round of this generation is processed —
                # the same order the sequential loop sees.
                if (
                    self._planned != self._processed
                    or self._outstanding
                    or self._requeue
                ):
                    return wait
                self._gen, self._pending = self.dpor._merge_generations(
                    self._gen, self._pending
                )
                if not self._gen:
                    if self._finished_locked():
                        return {"op": "shutdown"}
                    return wait
            n_before = len(self._gen)
            batch, self._gen = self.dpor._select_batch(self._gen)
            base = self.dpor.interleavings + (
                (self._planned - self._processed) * self.dpor.batch_size
            )
            lease = Lease(
                lease_id=self._next_lease_id,
                round_no=self._planned,
                batch=batch,
                n_real=min(take, n_before),
                prescs=self.dpor._pack(batch),
                seeds=self.dpor._round_seeds(len(batch), base, batch=batch),
                sleeps=(
                    self.dpor._pack_sleep(batch)
                    if self.dpor.sleep is not None
                    else None
                ),
                sfrom=(
                    self.dpor._sleep_from(batch)
                    if self.dpor.sleep is not None
                    else None
                ),
            )
            self._next_lease_id += 1
            self._planned += 1
            return self._issue_locked(lease, worker)

    def _issue_locked(self, lease: Lease, worker: str) -> Dict[str, Any]:
        from ..persist.checkpoint import pack_array

        now = time.monotonic()
        self._outstanding[lease.lease_id] = (
            lease, worker, now + self.lease_timeout, now
        )
        # One span id per lease (kept across re-issues): the worker's
        # fleet.execute child span links to it via parent_span, and the
        # coordinator records the covering fleet.lease span at drain.
        sid = self._lease_spans.setdefault(lease.lease_id, dtrace.new_id(4))
        self._lease_issue_ts.setdefault(lease.lease_id, ospans.now_us())
        msg = {
            "op": "lease",
            "lease": lease.lease_id,
            "round": lease.round_no,
            "trace": {"id": self.trace.trace_id, "span": sid,
                      "actor": "coordinator"},
            "prescs": pack_array(lease.prescs),
            "seeds": pack_array(lease.seeds),
        }
        if lease.sleeps is not None:
            msg["sleeps"] = pack_array(lease.sleeps)
            msg["sfrom"] = pack_array(lease.sfrom)
        return msg

    # -- results -----------------------------------------------------------
    def _unpack_result(self, msg: Dict[str, Any]):
        from ..device.dpor_sweep import DporSleepResult
        from ..device.explore import LaneResult
        from ..persist.checkpoint import unpack_array

        res_type = (
            DporSleepResult if self.dpor.sleep is not None else LaneResult
        )
        fields = {
            f: unpack_array(msg["res"][f]) for f in res_type._fields
        }
        return res_type(**fields)

    def submit(self, worker: Optional[str], msg: Dict[str, Any]) -> Dict[str, Any]:
        lid = msg.get("lease")
        with self._lock:
            if self._stop:
                # Stopped at a violation: late results are dropped and
                # their leases stay outstanding, so finalize returns the
                # un-processed rounds to the frontier intact.
                return {"op": "ok", "late": True}
            entry = self._outstanding.pop(lid, None)
            lease = entry[0] if entry is not None else None
            lease_wall = (
                time.monotonic() - entry[3] if entry is not None else None
            )
            if lease is None:
                # Revoked but not yet re-served? The result is the same
                # pure computation — accept it and cancel the re-lease.
                for i, rl in enumerate(self._requeue):
                    if rl.lease_id == lid:
                        lease = rl
                        del self._requeue[i]
                        break
            if lease is None:
                # Already served by a re-lease (or unknown): drop.
                return {"op": "ok", "duplicate": True}
            res = self._unpack_result(msg)
            busy = float(msg.get("busy_s", 0.0))
            w = str(worker or msg.get("worker", "w?"))
            self._results[lease.round_no] = (lease, res, busy, w)
            ws = self.workers.setdefault(w, {
                "rounds": 0, "busy_s": 0.0, "interleavings": 0,
                "alive": True, "reconnects": 0,
            })
            ws["rounds"] += 1
            ws["busy_s"] += busy
            ws["interleavings"] += len(lease.batch)
            if lease_wall is not None:
                # Per-worker lease latency: the straggler median's input
                # and the per-WORKER top panel's series.
                self._lease_walls.append(lease_wall)
                if len(self._lease_walls) > 512:
                    del self._lease_walls[:-256]
                obs.histogram("fleet.lease_seconds").observe(
                    lease_wall, worker=w
                )
            obs.counter("fleet.lease_rounds").inc(worker=w)
            self._drain_locked()
        return {"op": "ok"}

    def _drain_locked(self) -> None:
        """Process buffered results in canonical round order through the
        coordinator DPOR's own host half — the step that makes any
        arrival order converge to the sequential loop's state."""
        while self._processed in self._results:
            lease, res, busy, worker = self._results.pop(self._processed)
            t0 = time.perf_counter()
            hit = self.dpor._process_round(
                res, lease.batch, self.target_code, self._pending,
                frontier_extra=len(self._gen),
            )
            host_s = time.perf_counter() - t0
            self._processed += 1
            if self.dpor._last_round.get("violations"):
                self._violating_rounds += 1
            # Worker execution is the fleet's device half; coordinator
            # derivation is its host half — the same split the
            # dpor.host_share gauge reports for single-process runs.
            self.dpor._account_device(busy)
            self.dpor._account_host(host_s)
            self.dpor.round_index += 1
            # Coordinator half of the distributed lease span: issue to
            # drain, on a per-lease track (issue and drain happen on
            # different handler threads, so the stack-disciplined
            # context manager cannot cover it). The worker's
            # fleet.execute child links back via parent_span.
            sid = self._lease_spans.pop(lease.lease_id, None)
            issue_ts = self._lease_issue_ts.pop(lease.lease_id, None)
            if obs.enabled() and issue_ts is not None:
                ospans.record_span(
                    "fleet.lease", issue_ts,
                    ospans.now_us() - issue_ts,
                    0x4000 | (lease.lease_id & 0x3FFF),
                    worker=worker, lease=lease.lease_id,
                    round=lease.round_no, trace_id=self.trace.trace_id,
                    span_id=sid, parent_span=self.trace.span_id,
                )
            # Per-node ledger/frontier byte footprints (packed int32
            # wire form): the fleet-frontier growth alarm for runs where
            # prescription counts reach millions.
            frontier_bytes = ledger_bytes = None
            if obs.enabled() or obs.journal.JOURNAL is not None:
                row_bytes = 4 * self.cfg.rec_width
                frontier_bytes = row_bytes * int(
                    self.dpor._list(self._gen).lengths().sum()
                    + self.dpor._list(self._pending).lengths().sum()
                )
                obs.gauge("fleet.frontier_bytes").force_set(frontier_bytes)
                if self.dpor.sleep is not None:
                    ledger_bytes = row_bytes * sum(
                        len(c) for c in self.dpor.sleep.classes
                    )
                    obs.gauge("fleet.ledger_bytes").force_set(ledger_bytes)
            if obs.journal.JOURNAL is not None:
                lr = self.dpor._last_round
                obs.journal.emit(
                    "fleet.round",
                    round=self.dpor.round_index,
                    worker=worker,
                    lease=lease.lease_id,
                    wall_s=round(busy + host_s, 6),
                    busy_s=round(busy, 6),
                    host_s=round(host_s, 6),
                    batch=lr.get("batch", 0),
                    fresh=lr.get("fresh", 0),
                    redundant=lr.get("redundant", 0),
                    violations=lr.get("violations", []),
                    frontier=len(self._gen) + len(self._pending),
                    explored=len(self.dpor.explored),
                    interleavings=self.dpor.interleavings,
                    classes=(
                        len(self.dpor.sleep.classes)
                        if self.dpor.sleep is not None
                        else None
                    ),
                    warm_skips=(
                        self.dpor.sleep.warm_hits
                        if self.dpor.sleep is not None
                        else 0
                    ),
                    workers_alive=sum(
                        1 for w in self.workers.values() if w["alive"]
                    ),
                    leases_outstanding=len(self._outstanding),
                    frontier_bytes=frontier_bytes,
                    ledger_bytes=ledger_bytes,
                )
                # Per-shard host-half attribution: one record per
                # admission shard per round, the FLEET panel's shard
                # utilization series (balance skew across digest ranges
                # shows up here before it shows up as host_s drift).
                for st in lr.get("host_shards") or ():
                    obs.journal.emit(
                        "fleet.host_shard",
                        round=self.dpor.round_index,
                        shard=st.get("shard"),
                        lanes=st.get("lanes"),
                        rows=st.get("rows"),
                        candidates=st.get("candidates"),
                        dup=st.get("dup"),
                        fresh=st.get("fresh"),
                        wall_s=st.get("wall_s"),
                        scan_s=st.get("scan_s"),
                    )
            if hit is not None:
                if self._found is None:
                    self._found = (np.asarray(hit[0]).copy(), int(hit[1]))
                obs.counter("dpor.violations_found").inc()
                if self.stop_on_violation:
                    self._stop = True
        self._finished_locked()

    # -- completion --------------------------------------------------------
    def finalize(self) -> Dict[str, Any]:
        """Restore un-executed rounds to the frontier, publish the class
        ledger, and return the run summary."""
        with self._lock:
            leftovers = sorted(
                [entry[0] for entry in self._outstanding.values()]
                + self._requeue,
                key=lambda l: l.round_no,
            )
            front = self.dpor._list([])
            for lease in leftovers:
                front = front + lease.batch[: lease.n_real]
            self.dpor.frontier = front + self._gen + self._pending
            self._outstanding.clear()
            self._requeue.clear()
        wall_s = time.perf_counter() - self.wall_t0 if self._started else 0.0
        if obs.enabled() and self.span_dir:
            # The stitcher's coordinator input (offset 0: the
            # coordinator IS the fleet's reference clock).
            dtrace.export_process(self.span_dir, "coordinator")
        if self._journal_attached_here:
            obs.journal.detach()
            self._journal_attached_here = False
        store_info = None
        if self.store is not None and self.dpor.sleep is not None:
            from ..analysis.delta import build_run_ledger

            ledger = build_run_ledger(
                self.dpor, self.app, inherited=self.delta_stats
            )
            self.store.publish(ledger)
            store_info = {
                "dir": self.store.dir,
                "segments": len(self.store.segments()),
                **self.store.stats,
            }
        per_worker = {
            w: {
                "rounds": ws["rounds"],
                "busy_s": round(ws["busy_s"], 4),
                "interleavings": ws["interleavings"],
                "interleavings_per_sec": (
                    round(ws["interleavings"] / ws["busy_s"], 2)
                    if ws["busy_s"] > 0
                    else None
                ),
                "device": ws.get("device"),
            }
            for w, ws in sorted(self.workers.items())
        }
        n_workers = max(1, len(self.workers))
        total_busy = sum(ws["busy_s"] for ws in self.workers.values())
        # Aggregate capacity at one device set per worker: useful
        # interleavings over the MEAN per-worker busy time. Duplicated
        # work (a failed dedup) inflates total busy and pulls this down;
        # perfect partitioning scales it by the worker count.
        aggregate = (
            self.dpor.interleavings / (total_busy / n_workers)
            if total_busy > 0
            else None
        )
        sleep = self.dpor.sleep
        summary: Dict[str, Any] = {
            "workers": len(self.workers),
            "per_worker": per_worker,
            "rounds": self._processed,
            "interleavings": self.dpor.interleavings,
            "explored": len(self.dpor.explored),
            "frontier": len(self.dpor.frontier),
            "violation_codes": sorted(self.dpor.violation_codes),
            "violating_rounds": self._violating_rounds,
            "violation_found": self._found is not None,
            "first_found_sha": (
                hashlib.sha256(
                    self._found[0][: self._found[1]].tobytes()
                ).hexdigest()[:16]
                if self._found is not None
                else None
            ),
            "explored_sha": set_digest(self.dpor.explored),
            "busy_seconds": round(total_busy, 4),
            "wall_seconds": round(wall_s, 4),
            "host_seconds": round(self.dpor.host_seconds, 4),
            "host_share": (
                round(self.dpor.host_share, 4)
                if self.dpor.host_share is not None
                else None
            ),
            "aggregate_interleavings_per_sec": (
                round(aggregate, 2) if aggregate is not None else None
            ),
            "leases_reissued": self._releases,
            "stragglers": self._stragglers,
        }
        if sleep is not None:
            summary["classes"] = len(sleep.classes)
            summary["classes_sha"] = set_digest(sleep.classes)
            summary["warm_skips"] = sleep.warm_hits
            summary["warm_covered"] = len(self.warm.classes)
            # Effective verdict (live + warm-inherited, min-sha merged):
            # emitted for scratch runs too, so a --diff-audit scratch
            # leg compares the same keys.
            from ..analysis.delta import effective_violations

            codes, shas = effective_violations(self.dpor, self.delta_stats)
            summary["violation_codes_effective"] = codes
            summary["witness_shas"] = shas
        if self.delta_stats is not None:
            summary["delta"] = {
                k: v for k, v in self.delta_stats.items()
                if k != "inherited_witnesses"
            }
        if store_info is not None:
            summary["store"] = store_info
        return summary


# ---------------------------------------------------------------------------
# Single-host launcher: coordinator in-process (no JAX backend), workers as
# subprocesses — one chip each on an accelerator host, virtual CPU devices
# when the caller pinned the CPU.
# ---------------------------------------------------------------------------

def worker_environment(
    base_env, index: int, workers: int, devices_per_worker: int = 1
) -> Dict[str, str]:
    """The environment fleet worker ``index`` of ``workers`` starts in.

    Workers go to the CPU only when the CALLER's environment says
    ``JAX_PLATFORMS=cpu`` (the test and rehearsal shape): then each gets
    ``devices_per_worker`` virtual CPU devices. Otherwise the platform is
    JAX's choice in the worker, and the launcher hands each worker
    exactly ONE chip of the host through libtpu's visible-chips
    environment — a chip belongs to one process at a time, so without
    this the first worker would take every chip and the rest would fail
    or hang. A lone worker may instead hold every chip
    (``devices_per_worker`` > 1: its rounds shard over its local mesh)."""
    env = dict(base_env)
    repo = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    if env.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        # Pin the virtual device count (replacing any inherited
        # setting): a worker with >1 local device builds the
        # mesh-sharded kernel twin, and the launcher must be
        # deterministic about which.
        flags = [
            f for f in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f
        ]
        flags.append(
            f"--xla_force_host_platform_device_count={devices_per_worker}"
        )
        env["XLA_FLAGS"] = " ".join(flags)
        return env
    if devices_per_worker > 1:
        if workers > 1:
            raise ValueError(
                "on an accelerator host a fleet worker holds one chip or, "
                "alone, all of them: use --devices-per-worker 1, or "
                "--workers 1"
            )
        return env  # the lone worker sees every chip
    # One standalone single-chip libtpu runtime per worker: its own chip,
    # a 1x1x1 topology (under libtpu's current and legacy names, so a
    # host-wide setting inherited from the caller cannot contradict it),
    # and its own ports.
    from ..parallel.distributed import _free_port

    port = str(_free_port())
    env.update({
        "TPU_VISIBLE_CHIPS": str(index),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_CHIPS_PER_HOST_BOUNDS": "1,1,1",
        "TPU_HOST_BOUNDS": "1,1,1",
        "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
        "TPU_PROCESS_PORT": port,
        "CLOUD_TPU_TASK_ID": "0",
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    })
    metrics_ports = [
        p for p in env.get("TPU_RUNTIME_METRICS_PORTS", "").split(",") if p
    ]
    if metrics_ports:
        env["TPU_RUNTIME_METRICS_PORTS"] = metrics_ports[
            index % len(metrics_ports)
        ]
    return env


def run_fleet(
    workload: Optional[dict] = None,
    workers: int = 2,
    batch: int = 16,
    rounds: int = 20,
    *,
    sleep: bool = True,
    prune: bool = False,
    class_store_dir: Optional[str] = None,
    warm_start: bool = False,
    delta: bool = False,
    stop_on_violation: bool = False,
    target_code: Optional[int] = None,
    journal_dir: Optional[str] = None,
    max_outstanding: Optional[int] = None,
    devices_per_worker: int = 1,
    seed_prescription=None,
    lease_timeout: float = 120.0,
    straggler_factor: float = 4.0,
    worker_env: Optional[Dict[str, Dict[str, str]]] = None,
    timeout: float = 900.0,
    host_shards: Optional[int] = None,
) -> Dict[str, Any]:
    """Run a fleet on this host: serve leases in-process — this process
    initialises no JAX backend — and spawn ``workers`` worker processes,
    each with its own JAX runtime on its own chip (or, under the
    caller's ``JAX_PLATFORMS=cpu``, ``devices_per_worker`` virtual CPU
    devices; see ``worker_environment``). >1 device in a worker shards
    each leased round over its local mesh, the intra-slice ring.
    Returns the coordinator summary. ``worker_env`` maps worker ids to
    extra env vars (the preemption tests inject
    ``DEMI_FLEET_DIE_AFTER``)."""
    from ..persist.supervisor import SUPERVISOR, StrictIOError, strict_io_enabled

    if devices_per_worker > 1 and batch % devices_per_worker:
        raise ValueError(
            f"batch {batch} must be a multiple of devices_per_worker "
            f"{devices_per_worker}"
        )
    app, cfg, program = build_fleet_workload(workload)
    co = FleetCoordinator(
        app, cfg, program,
        workload=workload, batch_size=batch, max_rounds=rounds,
        sleep=sleep, prune=prune, class_store_dir=class_store_dir,
        warm_start=warm_start, delta=delta,
        stop_on_violation=stop_on_violation,
        target_code=target_code, lease_timeout=lease_timeout,
        max_outstanding=max_outstanding, min_ready=workers,
        journal_dir=journal_dir, straggler_factor=straggler_factor,
        host_shards=host_shards,
    )
    if seed_prescription is not None:
        co.dpor.seed(tuple(tuple(r) for r in seed_prescription))
    addr = co.serve()
    procs: List[subprocess.Popen] = []
    try:
        for i in range(workers):
            wid = f"w{i}"
            wenv = worker_environment(
                os.environ, i, workers, devices_per_worker
            )
            wenv.update((worker_env or {}).get(wid, {}))
            procs.append(
                SUPERVISOR.run(
                    lambda _attempt, wid=wid, wenv=wenv: subprocess.Popen(
                        [
                            sys.executable, "-m", "demi_tpu.fleet.worker",
                            addr, wid,
                        ],
                        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                        text=True, env=wenv,
                    ),
                    label="fleet.spawn",
                )
            )
        t0 = time.monotonic()
        while not co.done.wait(0.2):
            if time.monotonic() - t0 > timeout:
                raise RuntimeError(f"fleet timed out after {timeout}s")
            if procs and all(p.poll() is not None for p in procs):
                with co._lock:
                    unfinished = not co._finished_locked()
                if unfinished:
                    errs = "; ".join(
                        f"w{i} rc={p.returncode}" for i, p in enumerate(procs)
                    )
                    tail = ""
                    for p in procs:
                        try:
                            _out, err = p.communicate(timeout=5)
                            if err:
                                tail = err[-800:]
                        except Exception:
                            pass
                    msg = (
                        f"every fleet worker exited with rounds left "
                        f"({errs}); last stderr: {tail!r}"
                    )
                    if strict_io_enabled(None):
                        raise StrictIOError(msg)
                    raise RuntimeError(msg)
    finally:
        deadline = time.monotonic() + 30
        for p in procs:
            if p.poll() is None:
                try:
                    p.wait(timeout=max(0.1, deadline - time.monotonic()))
                except subprocess.TimeoutExpired:
                    p.kill()
        for p in procs:
            try:
                p.communicate(timeout=5)
            except Exception:
                pass
        co.close()
    summary = co.finalize()
    summary["worker_returncodes"] = [p.returncode for p in procs]
    # One process per chip: this process must have initialised no JAX
    # backend (it would hold a chip its workers need).
    from jax._src import xla_bridge

    summary["coordinator_backend_initialized"] = (
        xla_bridge.backends_are_initialized()
    )
    return summary
