"""Command-line interface: fuzz / minimize / replay / interactive / sweep.

Reference: the coarse CLI mode strings of RunnerUtils.getExecutionMode
(RunnerUtils.scala:40-60: --fuzz/--minimize/--interactive) — grown into a
real subcommand CLI over the built-in apps.

    python -m demi_tpu fuzz --app raft --nodes 3 --bug multivote -o exp/
    python -m demi_tpu minimize -e exp/ --app raft --nodes 3 --bug multivote
    python -m demi_tpu replay -e exp/ --app raft --nodes 3 --bug multivote
    python -m demi_tpu sweep --app raft --nodes 3 --bug multivote --batch 1024
    python -m demi_tpu interactive --app broadcast --nodes 3
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Optional

from . import obs

with obs.stage("setup.import", module=__name__):
    from .apps.broadcast import broadcast_send_generator, make_broadcast_app
    from .apps.common import dsl_start_events, make_host_invariant
    from .apps.raft import make_raft_app, raft_send_generator
    from .config import SchedulerConfig
    from .dsl import DSLApp
    from .external_events import WaitQuiescence
    from .fuzzing import Fuzzer, FuzzerWeights
    from .parallel.distributed import FAULT_PLANE_DEFAULTS


def build_app(args) -> DSLApp:
    """The app a verb runs. ``--dup-weight`` and ``--drop-weight`` are a
    datagram network's: given to an app with any other channels, every
    verb ends here, with a sentence."""
    from .device.core import datagram_flags_refusal, datagram_weight_given

    app = _make_app(args)
    if app.channels != "datagram" and datagram_weight_given(args):
        raise SystemExit(datagram_flags_refusal(app))
    return app


def _make_app(args) -> DSLApp:
    if args.app == "broadcast":
        return make_broadcast_app(args.nodes, reliable=args.bug is None)
    if args.app == "raft":
        return make_raft_app(
            args.nodes, log_cap=args.log_cap, bug=args.bug,
            handler_edit=getattr(args, "handler_edit", None),
        )
    if args.app == "spark":
        from .apps.spark_dag import make_spark_app

        return make_spark_app(
            num_workers=max(1, args.nodes - 1), num_stages=args.stages,
            tasks_per_stage=args.tasks, bug=args.bug,
        )
    if args.app == "twopc":
        from .apps.twopc import make_twopc_app

        return make_twopc_app(args.nodes, bug=args.bug)
    if args.app == "vsr":
        from .apps.vsr import make_vsr_app

        return make_vsr_app(args.nodes, log_cap=args.log_cap, bug=args.bug)
    if args.app == "chain":
        from .apps.chain import make_chain_app

        return make_chain_app(args.nodes, log_cap=args.log_cap, bug=args.bug)
    if args.app == "paxos":
        from .apps.paxos import make_paxos_app

        try:
            return make_paxos_app(
                args.nodes, log_cap=args.log_cap, bug=args.bug
            )
        except ValueError as exc:
            raise SystemExit(f"--app paxos: {exc}")
    if args.app == "raft_reconfig":
        from .apps.raft_reconfig import make_raft_reconfig_app

        try:
            return make_raft_reconfig_app(
                args.nodes, log_cap=args.log_cap,
                snapshot_every=args.snapshot_every, bug=args.bug,
            )
        except ValueError as exc:
            raise SystemExit(f"--app raft_reconfig: {exc}")
    if args.app == "kafka":
        from .apps.kafka import make_kafka_app

        try:
            return make_kafka_app(
                args.nodes, log_cap=args.log_cap, bug=args.bug
            )
        except ValueError as exc:
            raise SystemExit(f"--app kafka: {exc}")
    raise SystemExit(
        f"unknown app {args.app!r} (choices: broadcast, chain, kafka, paxos, "
        "raft, raft_reconfig, spark, twopc, vsr)"
    )


def build_fuzzer(app: DSLApp, args) -> Fuzzer:
    if args.app == "spark":
        from .apps.spark_dag import spark_send_generator

        gen = spark_send_generator(app)
    elif args.app == "twopc":
        from .apps.twopc import twopc_send_generator

        gen = twopc_send_generator(app)
    elif args.app == "vsr":
        from .apps.vsr import vsr_send_generator

        gen = vsr_send_generator(app)
    elif args.app == "chain":
        from .apps.chain import chain_send_generator

        gen = chain_send_generator(app)
    elif args.app == "paxos":
        from .apps.paxos import paxos_send_generator

        gen = paxos_send_generator(app)
    elif args.app == "raft_reconfig":
        from .apps.raft_reconfig import reconfig_send_generator

        gen = reconfig_send_generator(app)
    elif args.app == "kafka":
        from .apps.kafka import kafka_send_generator

        gen = kafka_send_generator(app)
    elif args.app == "broadcast":
        gen = broadcast_send_generator(app)
    else:
        gen = raft_send_generator(app)
    weights = FuzzerWeights(
        kill=args.kill_weight,
        send=args.send_weight,
        wait_quiescence=args.wait_weight,
        partition=args.partition_weight,
        unpartition=args.partition_weight,
        hard_kill=args.hard_kill_weight,
        restart=args.restart_weight,
    )
    return Fuzzer(
        num_events=args.num_events,
        weights=weights,
        message_gen=gen,
        prefix=dsl_start_events(app),
        max_kills=args.max_kills,
        max_sends=args.max_sends,
        wait_budget=(
            None if args.wait_budget is None else tuple(args.wait_budget)
        ),
        unkillable=[app.actor_name(i) for i in app.unkillable],
    )


def _network_flags(app: DSLApp, args) -> dict:
    """The datagram discipline's weights and budgets for the host
    ``RandomScheduler`` (``--dup-weight``, ``--drop-weight``,
    ``--max-dups``, ``--max-drops``); nothing for any other app
    (``build_app`` has refused a non-zero weight)."""
    from .device.core import datagram_knobs

    return datagram_knobs(args) if app.channels == "datagram" else {}


def _workload_dict(args) -> dict:
    """The CLI-args-shaped workload dict ``build_workload`` takes: what
    the distributed launcher, the fleet and the service ship to their
    processes, so a flag means the same thing in every one of them."""
    return {
        "app": args.app,
        "nodes": args.nodes,
        "bug": args.bug,
        "seed": args.seed,
        "num_events": args.num_events,
        "max_messages": args.max_messages,
        "timer_weight": args.timer_weight,
        "kill_weight": args.kill_weight,
        "partition_weight": args.partition_weight,
        "pool": args.pool,
        **{k: getattr(args, k) for k in FAULT_PLANE_DEFAULTS},
    }


def _workload_discriminator(args) -> dict:
    """Extra tuning-cache key fields beyond the static kernel shapes:
    ``DSLApp.name`` is only the actor-name prefix ('n'/'r'/...), so two
    workloads with the same shapes but different handlers (raft with and
    without a seeded bug, reliable vs unreliable broadcast) would
    otherwise collide on one cache entry and inherit each other's
    calibrated rates."""
    workload = f"{args.app}:{args.bug or 'none'}"
    if args.app == "spark":
        # The job's shape is the handler's: a 4 x 200 job's launch is
        # 401 rows where the 2 x 4 one's is 9.
        workload += f":{args.stages}x{args.tasks}"
    return {"workload": workload}


#: How many violating lanes the sweep summary names (``violating_seeds``).
_VIOLATING_SEEDS_KEPT = 32


def _device_fields() -> dict:
    from .parallel.mesh import device_fields

    return device_fields()


def _sweep_driver(app, cfg, gen, prefix_fork=None):
    """The sweep verb's driver: lane-sharded over every local device
    when this process has more than one (parallel/mesh.local_lane_mesh)."""
    from .parallel.mesh import local_lane_mesh
    from .parallel.sweep import SweepDriver

    mesh = local_lane_mesh()
    return SweepDriver(
        app, cfg, gen, mesh=mesh, use_mesh=mesh is not None,
        prefix_fork=prefix_fork,
    )


def _flag_or_unset(args, name: str):
    """A store_true flag as a constructor's switch: True when given,
    else None, which leaves the switch to the constructor's own default
    (a verb tells what it builds; it writes nothing into os.environ)."""
    return True if getattr(args, name, False) else None


def _autotune_requested(args) -> bool:
    """``--autotune`` or ``DEMI_AUTOTUNE=1``. Process state is never
    mutated: the commands thread the answer explicitly to everything
    they build, so one --autotune ``main()`` call cannot leak autotuning
    into later calls in the same process."""
    from .tune import autotune_enabled

    return bool(getattr(args, "autotune", False)) or autotune_enabled()


#: Live --metrics-port server for the current main() call (module
#: state so _obs_end can shut it down — a leaked bound port would fail
#: the next in-process invocation with EADDRINUSE).
_METRICS_SERVER = None


def _obs_begin(args) -> bool:
    """Turn telemetry on when the run asked for an observability artifact
    (--trace-out / --stats-out; DEMI_OBS=1 enables it regardless).
    ``--metrics-port`` additionally serves the live registry over HTTP
    (Prometheus text at /metrics), and ``--journal DIR`` attaches the
    continuous round journal for runs without a checkpoint dir (a
    ``--checkpoint-dir`` run journals into that dir automatically)."""
    if getattr(args, "trace_out", None) or getattr(args, "stats_out", None):
        obs.enable()
    if getattr(args, "metrics_port", None) is not None:
        from .obs import timeseries

        obs.enable()
        global _METRICS_SERVER
        _METRICS_SERVER = timeseries.serve(args.metrics_port)
        print(
            "metrics: serving http://127.0.0.1:"
            f"{_METRICS_SERVER.server_address[1]}/metrics",
            flush=True,
        )
    if getattr(args, "journal", None) and not getattr(
        args, "checkpoint_dir", None
    ):
        obs.journal.attach(args.journal)
    return obs.enabled()


def _cleanup_continuous() -> None:
    """Idempotent teardown of the continuous-obs resources one
    ``main()`` call must not leak into the next: shut down the
    ``--metrics-port`` server (a leaked bound port fails the next
    invocation with EADDRINUSE), flush the time-series delta next to
    the journal, detach the journal. Shared by ``_obs_end`` (normal
    exit) and ``main``'s finally (exception exit) so the two paths can
    never drift."""
    global _METRICS_SERVER
    if _METRICS_SERVER is not None:
        _METRICS_SERVER.shutdown()
        _METRICS_SERVER.server_close()
        _METRICS_SERVER = None
    if obs.journal.attached():
        from .obs import timeseries

        timeseries.SERIES.flush_jsonl(obs.journal.JOURNAL.root)
        obs.journal.detach()


def _obs_end(args, experiment_dir: Optional[str] = None) -> None:
    """Export the run's observability artifacts: Perfetto trace and/or
    registry snapshot, plus obs_snapshot.json into the experiment dir so
    `demi_tpu report` / `demi_tpu stats` can pick it up later; the
    continuous-obs resources (journal, time series, metrics server) are
    torn down."""
    _cleanup_continuous()
    if not obs.enabled():
        return
    if getattr(args, "trace_out", None):
        obs.TRACER.export_perfetto(args.trace_out)
        print(
            f"trace written to {args.trace_out} "
            "(load in ui.perfetto.dev or chrome://tracing)"
        )
    snap = obs.REGISTRY.snapshot()
    if getattr(args, "stats_out", None):
        # With the registry: where the seconds before the first job went
        # (the setup.* stages), the per-function compile table, and a
        # row of its own stages and counts for each of the run's jobs.
        doc = dict(
            snap, setup=obs.setup_ledger(), compile=obs.compile_ledger(),
            jobs=obs.job_ledger(),
        )
        with open(args.stats_out, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
        print(f"metrics snapshot written to {args.stats_out}")
    if experiment_dir and os.path.isdir(experiment_dir):
        with open(os.path.join(experiment_dir, "obs_snapshot.json"), "w") as f:
            json.dump(snap, f, indent=2, sort_keys=True)


def _device_confirm_sweep(app, args, program, lanes: int = 32):
    """Telemetry-time device sweep: with a violating ``program``, re-sweep
    it on the device explore kernel (RNG-varied lanes) as a cross-check;
    without one, sweep the fuzzer's own seed space — either way the traced
    run records device sweep spans + LaneStats next to the host tiers."""
    from .device import DeviceConfig
    from .parallel.sweep import SweepDriver

    overrides = {}
    if program is not None:
        overrides["max_external_ops"] = max(16, len(program) + 2)
    cfg = DeviceConfig.for_workload(app, args, **overrides)
    if program is not None:
        gen = lambda s: program  # noqa: E731
    else:
        fuzzer = build_fuzzer(app, args)
        gen = lambda s: fuzzer.generate_fuzz_test(seed=args.seed + s)  # noqa: E731
    driver = SweepDriver(app, cfg, gen)
    with obs.span(
        "fuzz.device_confirm", lanes=lanes, confirm=program is not None
    ):
        result = driver.sweep(lanes, lanes, mode="chunked")
    obs.counter("fuzz.device_confirm_violations").inc(result.violations)
    return result


def _sanitize_begin(args, strict: bool = False):
    """Arm the runtime replay sanitizer for this run when ``--sanitize``
    was passed (DEMI_SANITIZE=1/strict does the same without the flag).
    Same env-switch contract as --prefix-fork/--async-min — the runtime
    reads the env at each delivery, so the flag reaches every stage —
    but the previous value is restored by ``_sanitize_end`` so one
    --sanitize invocation cannot leak strictness into later ``main()``
    calls in the same process (or into child processes)."""
    prev = os.environ.get("DEMI_SANITIZE")
    changed = False
    if getattr(args, "sanitize", False):
        os.environ["DEMI_SANITIZE"] = "strict" if strict else "1"
        changed = True
    from .analysis import sanitize

    return (sanitize.enabled(), changed, prev)


def _sanitize_end(token) -> None:
    enabled, changed, prev = token
    if changed:
        if prev is None:
            os.environ.pop("DEMI_SANITIZE", None)
        else:
            os.environ["DEMI_SANITIZE"] = prev
    if not enabled:
        return
    from .analysis import sanitize

    print(f"sanitizer: {json.dumps(sanitize.stats())}")


def _profile_begin(args) -> bool:
    """``--profile-rounds N``: arm the launch profiler (per-launch wall
    attribution keyed by launch shape — obs/profiler.py) and open a
    jax.profiler trace window over the first N round boundaries, written
    to ``--profile-trace`` (default ./demi_profile). The stage spans are
    live for the whole run (the ledger is fed from them) and land in the
    trace as ``demi.<name>`` events beside the device's operations."""
    rounds = getattr(args, "profile_rounds", 0) or 0
    if not rounds:
        return False
    from .obs.profiler import PROFILER

    PROFILER.enable()
    logdir = getattr(args, "profile_trace", None) or "demi_profile"
    PROFILER.start_trace_window(logdir, rounds)
    return True


def _profile_end(args, summary: dict, app, cfg) -> None:
    """Close the trace window, fold the launch ledger into the summary,
    and persist it in TuningCache-compatible form under the workload key
    (extra discriminator ``profile=launch``) so the launch-economy cost
    model consumes measured evidence instead of re-profiling."""
    if not (getattr(args, "profile_rounds", 0) or 0):
        return
    import jax

    from .obs.profiler import PROFILER, profile_enabled
    from .tune import TuningCache, workload_key

    PROFILER.stop_trace_window()
    evidence = PROFILER.evidence()
    summary["launch_profile"] = evidence
    # The stage table of the same run (obs/spans.py): the launch ledger
    # arms the spans, and the trace holds them as demi.<name> events.
    summary["stages"] = obs.stage_totals()
    cache = TuningCache()
    key = workload_key(
        app.name, app.num_actors, cfg, jax.devices()[0].platform,
        profile="launch", **_workload_discriminator(args),
    )
    PROFILER.persist_evidence(cache, key)
    summary["launch_profile_cache"] = {"key": key, "path": cache.path}
    # One main() call must not leak profiling into the next (tests run
    # the CLI in-process); the env switch re-arms it when set.
    PROFILER.reset()
    PROFILER.enabled = profile_enabled()


def _strict_io_begin(args) -> None:
    """``--strict-io``: degradations (native analyzer → NumPy twin,
    exhausted launch retries) become hard errors. Same env-switch
    contract as --prefix-fork — the launch supervisor reads the env at
    each failure, so the flag reaches every wrapped surface."""
    if getattr(args, "strict_io", False):
        os.environ["DEMI_STRICT_IO"] = "1"


#: Argparse fields a resumed run must reconstruct, per command (the
#: checkpoint manifest stores their values; `demi_tpu resume` rebuilds
#: the namespace from them — keep in sync with what each cmd_* reads).
_RESUME_COMMON = (
    "app", "nodes", "bug", "seed", "num_events", "max_messages",
    "timer_weight", "kill_weight", "partition_weight",
    "trace_out", "stats_out", "checkpoint_every", "strict_io",
    *FAULT_PLANE_DEFAULTS,
)
_RESUME_FIELDS = {
    "dpor": _RESUME_COMMON + (
        "batch", "pool", "rounds", "static_prune", "sleep_sets",
        "prefix_fork", "async_min", "autotune",
    ),
    "sweep": _RESUME_COMMON + (
        "batch", "pool", "chunk", "sweep_mode", "processes",
        "prefix_fork", "autotune",
    ),
    "fuzz": _RESUME_COMMON + ("max_executions", "output", "autotune",
                              "sanitize", "streaming", "split", "chunk",
                              "pool", "prefix_fork", "async_min"),
}


def _resume_args(args, command: str) -> dict:
    return {
        f: getattr(args, f, None) for f in _RESUME_FIELDS[command]
    }


def _attach_checkpoint_journal(args, ckpt, kind: str, cursor: int) -> int:
    """The ONE resume-continuity contract for every checkpointed
    command: attach the round journal to the checkpoint dir with the
    next incarnation, and on a resume drop what the dead run wrote past
    the restored generation — ``kind`` records beyond ``cursor`` (those
    rounds/chunks/executions re-execute and re-journal) plus flushed
    time-series samples newer than the generation (by its MANIFEST
    mtime). Returns the incarnation for the checkpoint meta."""
    incarnation = (
        int(ckpt.meta.get("incarnation", 0)) + 1 if ckpt is not None else 0
    )
    journal = obs.journal.attach(
        args.checkpoint_dir, incarnation=incarnation
    )
    if ckpt is not None:
        journal.truncate_from(kind, cursor)
        from .obs import timeseries

        try:
            cutoff = os.path.getmtime(
                os.path.join(ckpt.path, "MANIFEST.json")
            )
        except OSError:
            return incarnation
        timeseries.truncate_after(args.checkpoint_dir, cutoff)
    return incarnation


def _flush_samples(root: str) -> None:
    """Flush the time-series delta next to the journal (called at the
    same cadence as checkpoint saves, so the export's loss window is
    bounded by the snapshot cadence)."""
    from .obs import timeseries

    timeseries.SERIES.flush_jsonl(root)


def _restore_obs(ckpt) -> None:
    """Merge the dead run's obs registry into this process (counters
    add, gauges last-write-win) so cumulative telemetry spans the kill."""
    snap = ckpt.sections.get("obs")
    if snap:
        obs.REGISTRY.load(snap)


def _restore_or_exit(restore_fn, ckpt) -> None:
    """Apply a digest-valid checkpoint payload, turning a schema-level
    failure (a payload written by an incompatible build) into a clear
    SystemExit instead of a raw traceback — the store's digests catch
    corruption; this catches staleness."""
    try:
        restore_fn(ckpt)
    except Exception as exc:
        raise SystemExit(
            f"resume: checkpoint at {ckpt.path!r} is valid but not "
            f"restorable by this build ({type(exc).__name__}: {exc}); "
            "delete the directory to start fresh"
        )


def _report_completed(ckpt, args) -> int:
    """A resumed run whose checkpoint records terminal status reports
    the saved summary instead of re-exploring past the recorded
    result."""
    summary = dict(ckpt.meta.get("summary", {}))
    summary.update({"resumed": True, "already_complete": True})
    print(json.dumps(summary))
    _obs_end(args)
    return 0 if summary.get("violation_found") else 1


def _preempted_exit(args, store, extra: dict) -> int:
    print(
        json.dumps(
            {
                "preempted": True,
                "checkpoint_dir": args.checkpoint_dir,
                "generations": store.generations(),
                "resume": f"python -m demi_tpu resume {args.checkpoint_dir}",
                **extra,
            }
        )
    )
    _obs_end(args)
    return 3


def _dpor_checkpoint_run(args, app, cfg) -> int:
    """Durable DPOR search: a single-round frontier loop (rounds are
    generation-frozen and deterministic, so every loop iteration is a
    valid resume point) with periodic atomic checkpoints, SIGTERM/SIGINT
    checkpointing at the next round boundary (exit code 3), and
    bit-identical resume via ``demi_tpu resume`` — the kill-and-resume
    parity tests/test_persist.py pins ride exactly this loop."""
    import hashlib

    from .device.dpor_sweep import DeviceDPOR
    from .persist import CheckpointStore, PreemptionGuard

    store = CheckpointStore(args.checkpoint_dir)
    program = dsl_start_events(app) + [WaitQuiescence()]
    ckpt = getattr(args, "_resume_checkpoint", None)
    # On a FRESH run the flags resolve as usual (flag wins, else env);
    # a RESUMED run pins the RESOLVED booleans recorded at save time
    # (below) so the checkpoint restores regardless of the new
    # environment's DEMI_SLEEP_SETS/DEMI_STATIC_PRUNE.
    from .parallel.mesh import local_lane_mesh

    dpor = DeviceDPOR(
        app, cfg, program, batch_size=args.batch,
        mesh=local_lane_mesh(args.batch),
        static_independence=(
            bool(getattr(args, "static_prune", False))
            if ckpt is not None
            else (True if getattr(args, "static_prune", False) else None)
        ),
        sleep_sets=(
            bool(getattr(args, "sleep_sets", False))
            if ckpt is not None
            else (True if getattr(args, "sleep_sets", False) else None)
        ),
        # Single-round explore() calls make every speculative in-flight
        # launch expire unharvested (pure waste, ~2x launches under
        # --async-min on non-CPU platforms) — same reason bench
        # config 10's loop pins it off.
        double_buffer=False,
        prefix_fork=_flag_or_unset(args, "prefix_fork"),
        host_shards=getattr(args, "host_shards", 0) or None,
    )
    autotune_on = (
        bool(getattr(args, "autotune", False))
        if ckpt is not None
        else _autotune_requested(args)
    )
    if autotune_on:
        from .tune import DporBudgetTuner

        dpor.tuner = DporBudgetTuner(batch=args.batch)
    rounds_done = 0
    resumed = False
    if ckpt is not None:
        if ckpt.meta.get("completed"):
            return _report_completed(ckpt, args)
        _restore_or_exit(
            lambda c: dpor.restore_state(c.sections["dpor"]), ckpt
        )
        rounds_done = int(ckpt.meta.get("rounds_done", 0))
        _restore_obs(ckpt)
        resumed = True
    every = max(1, getattr(args, "checkpoint_every", None) or 5)
    # Continuous observability: the round journal lives IN the
    # checkpoint dir (one artifact to point `demi_tpu top` at), and a
    # resume continues it round-contiguously — pinned by
    # tests/test_persist.py and the kill-resume soak. Older checkpoints
    # carry no round_index; pin it to the restored round count either
    # way.
    incarnation = _attach_checkpoint_journal(
        args, ckpt, "dpor.round", rounds_done
    )
    dpor.round_index = rounds_done
    _profile_begin(args)

    def save_ckpt(extra_meta=None) -> None:
        store.save(
            {"dpor": dpor.checkpoint_state(),
             "obs": obs.REGISTRY.snapshot()},
            meta={
                "command": "dpor",
                "cli_args": {
                    **_resume_args(args, "dpor"),
                    # RESOLVED values (flag-or-env at save time), so a
                    # resume in a fresh environment reconstructs the
                    # identical explorer shape.
                    "sleep_sets": dpor.sleep is not None,
                    "static_prune": dpor.static_independence is not None,
                    "autotune": dpor.tuner is not None,
                },
                "rounds_done": rounds_done,
                "checkpoint_every": every,
                "incarnation": incarnation,
                **(extra_meta or {}),
            },
        )
        _flush_samples(args.checkpoint_dir)

    found = None
    with PreemptionGuard() as guard:
        # Announce readiness only with the guard INSTALLED: the line is
        # the "SIGTERM now checkpoints" contract (tests and operators
        # signal the moment they see it), and printing first loses that
        # race on a busy one-core host.
        print(
            f"dpor: checkpointing to {args.checkpoint_dir} every {every} "
            "round(s)"
            + (f"; resumed at round {rounds_done}" if resumed else ""),
            flush=True,
        )
        while rounds_done < args.rounds and dpor.frontier and found is None:
            found = dpor.explore(max_rounds=1)
            rounds_done += 1
            done = (
                found is not None
                or rounds_done >= args.rounds
                or not dpor.frontier
            )
            # Work completed in the very round the signal interrupted
            # — a found violation, the last budgeted round, a drained
            # frontier — still reports normally (the terminal
            # generation below records the final state + summary;
            # there is nothing to resume). Only a mid-search
            # preemption checkpoints and exits early.
            if guard.requested and not done:
                save_ckpt()
                return _preempted_exit(
                    args, store,
                    {"rounds_done": rounds_done,
                     "interleavings": dpor.interleavings},
                )
            if not done and rounds_done % every == 0:
                save_ckpt()
    summary = {
        "rounds_done": rounds_done,
        "interleavings": dpor.interleavings,
        "explored": len(dpor.explored),
        "frontier": len(dpor.frontier),
        "violation_found": found is not None,
        "violation_codes": sorted(dpor.violation_codes),
        "resumed": resumed,
    }
    if found is not None:
        recs, n = found
        # Content digest of the first-found violating lane — the
        # kill-and-resume parity surface (resumed == uninterrupted).
        summary["first_found"] = [
            hashlib.sha256(recs[:n].tobytes()).hexdigest(), int(n)
        ]
    if dpor.host_share is not None:
        summary["host_share"] = round(dpor.host_share, 3)
    if dpor.sleep_stats is not None:
        summary["sleep_sets"] = dpor.sleep_stats
    _profile_end(args, summary, app, cfg)
    # Terminal generation: final state + summary + completed marker, so
    # a resume of a finished run reports instead of re-exploring.
    save_ckpt({"completed": True, "summary": summary})
    summary["checkpoints"] = dict(store.stats)
    print(json.dumps(summary))
    _obs_end(args)
    return 0 if found is not None else 1


def _sweep_checkpoint_run(args, app, cfg, fuzzer) -> int:
    """Durable fuzz sweep: chunked rounds (each chunk a pure function of
    its seed range) with the merged codes / dedup set / seed cursor
    checkpointed every N chunks; SIGTERM checkpoints at the next chunk
    boundary and ``demi_tpu resume`` continues at the next seed."""
    from .persist import CheckpointStore, PreemptionGuard

    if _autotune_requested(args):
        raise SystemExit(
            "--checkpoint-dir does not compose with --autotune on sweep "
            "yet (the fuzz command checkpoints its controller)"
        )
    if getattr(args, "sweep_mode", None) == "continuous":
        raise SystemExit(
            "--checkpoint-dir sweeps run chunked rounds (chunk "
            "boundaries are the snapshot points); drop --sweep-mode "
            "continuous"
        )
    store = CheckpointStore(args.checkpoint_dir)
    gen = lambda s: fuzzer.generate_fuzz_test(seed=args.seed + s)  # noqa: E731
    driver = _sweep_driver(
        app, cfg, gen, prefix_fork=_flag_or_unset(args, "prefix_fork")
    )
    chunk = min(args.batch, getattr(args, "chunk", None) or args.batch)
    state = {
        "seeds_done": 0, "chunks": 0, "violations": 0, "codes": {},
        "overflow_lanes": 0, "unfinished_lanes": 0,
        "first_violating_seed": None,
        "unique_hashes": [],
    }
    resumed = False
    ckpt = getattr(args, "_resume_checkpoint", None)
    if ckpt is not None:
        def _apply(c):
            state.update(c.sections["sweep"])
            fuzzer.restore_state(c.sections["fuzzer"])

        _restore_or_exit(_apply, ckpt)
        _restore_obs(ckpt)
        resumed = True
    hashes = set(int(h) for h in state["unique_hashes"])
    every = max(1, getattr(args, "checkpoint_every", None) or 5)
    # Round journal in the checkpoint dir, chunk-contiguous across
    # resumes (same contract as the DPOR loop; the driver continues the
    # restored chunk numbering).
    incarnation = _attach_checkpoint_journal(
        args, ckpt, "sweep.chunk", int(state["chunks"])
    )
    driver.chunk_index = int(state["chunks"])

    def save_ckpt() -> None:
        state["unique_hashes"] = sorted(hashes)
        store.save(
            {"sweep": state, "fuzzer": fuzzer.checkpoint_state(),
             "obs": obs.REGISTRY.snapshot()},
            meta={
                "command": "sweep",
                "cli_args": _resume_args(args, "sweep"),
                "seeds_done": state["seeds_done"],
                "checkpoint_every": every,
                "incarnation": incarnation,
            },
        )
        _flush_samples(args.checkpoint_dir)

    with PreemptionGuard() as guard:
        # Readiness line with the guard installed (see the dpor loop).
        print(
            f"sweep: checkpointing to {args.checkpoint_dir} every {every} "
            "chunk(s) (chunked rounds)"
            + (f"; resumed at seed {state['seeds_done']}" if resumed else ""),
            flush=True,
        )
        while state["seeds_done"] < args.batch:
            n = min(chunk, args.batch - state["seeds_done"])
            c = driver.run_chunk(
                range(state["seeds_done"], state["seeds_done"] + n)
            )
            state["seeds_done"] += n
            state["chunks"] += 1
            state["violations"] += c.violations
            for code, k in c.codes.items():
                key = str(code)
                state["codes"][key] = state["codes"].get(key, 0) + k
            state["overflow_lanes"] += c.overflow_lanes
            state["unfinished_lanes"] += c.unfinished_lanes
            if (
                state["first_violating_seed"] is None
                and c.first_violating_seed is not None
            ):
                state["first_violating_seed"] = c.first_violating_seed
            if c.unique_hashes is not None:
                hashes.update(int(h) for h in c.unique_hashes)
            done = state["seeds_done"] >= args.batch
            if guard.requested or done or state["chunks"] % every == 0:
                save_ckpt()
            # A signal during the FINAL chunk leaves nothing to resume:
            # report the completed sweep normally.
            if guard.requested and not done:
                return _preempted_exit(
                    args, store, {"seeds_done": state["seeds_done"]}
                )
    summary = {
        "lanes": state["seeds_done"],
        "unique_schedules": len(hashes),
        "violations": state["violations"],
        "codes": dict(state["codes"]),
        "first_violating_seed": state["first_violating_seed"],
        "overflow_lanes": state["overflow_lanes"],
        "unfinished_lanes": state["unfinished_lanes"],
        "resumed": resumed,
        "checkpoints": dict(store.stats),
        **_device_fields(),
    }
    if driver.host_share is not None:
        summary["host_share"] = round(driver.host_share, 3)
    print(json.dumps(summary))
    _obs_end(args)
    return 0


def _fuzz_checkpoint_run(args, app, config, fuzzer, controller) -> int:
    """Durable host fuzz: executions are pure functions of (seed, i)
    plus the controller's restored tuner state, so the checkpoint is
    just the execution cursor + controller/fuzzer weights; SIGTERM
    checkpoints after the in-flight execution."""
    from .persist import CheckpointStore, PreemptionGuard
    from .runner import fuzz
    from .serialization import ExperimentSerializer

    store = CheckpointStore(args.checkpoint_dir)
    start = 0
    resumed = False
    ckpt = getattr(args, "_resume_checkpoint", None)
    if ckpt is not None:
        if ckpt.meta.get("completed"):
            return _report_completed(ckpt, args)
        def _apply(c):
            nonlocal start
            sec = c.sections["fuzz"]
            start = int(sec["executions_done"])
            fuzzer.restore_state(sec["fuzzer"])
            if controller is not None and sec.get("controller") is not None:
                controller.restore_state(sec["controller"])

        _restore_or_exit(_apply, ckpt)
        _restore_obs(ckpt)
        resumed = True
    every = max(1, getattr(args, "checkpoint_every", None) or 25)
    # Round journal in the checkpoint dir, execution-contiguous across
    # resumes (runner.fuzz numbers records from start_execution).
    incarnation = _attach_checkpoint_journal(
        args, ckpt, "fuzz.execution", start
    )

    def save_ckpt(done: int, extra_meta=None) -> None:
        store.save(
            {
                "fuzz": {
                    "executions_done": done,
                    "fuzzer": fuzzer.checkpoint_state(),
                    "controller": (
                        controller.checkpoint_state()
                        if controller is not None
                        else None
                    ),
                },
                "obs": obs.REGISTRY.snapshot(),
            },
            meta={
                "command": "fuzz",
                "cli_args": _resume_args(args, "fuzz"),
                "executions_done": done,
                "checkpoint_every": every,
                "incarnation": incarnation,
                **(extra_meta or {}),
            },
        )
        _flush_samples(args.checkpoint_dir)

    executions_done = start
    with PreemptionGuard() as guard:
        # Readiness line with the guard installed (see the dpor loop).
        print(
            f"fuzz: checkpointing to {args.checkpoint_dir} every {every} "
            "execution(s)"
            + (f"; resumed at execution {start}" if resumed else ""),
            flush=True,
        )

        def hook(done: int) -> bool:
            nonlocal executions_done
            executions_done = done
            if guard.requested or done % every == 0:
                save_ckpt(done)
            return guard.requested

        result = fuzz(
            config, fuzzer,
            max_executions=args.max_executions,
            seed=args.seed, max_messages=args.max_messages,
            invariant_check_interval=app.invariant_interval,
            strategy=app.random_strategy, **_network_flags(app, args),
            timer_weight=args.timer_weight,
            validate_replay=True, controller=controller,
            start_execution=start, round_hook=hook,
        )
        # A violation found in the interrupted execution, or a budget
        # exhausted during it, is completed work — report it normally;
        # only a mid-search preemption exits early.
        if (
            guard.requested and result is None
            and executions_done < args.max_executions
        ):
            return _preempted_exit(
                args, store, {"executions_done": executions_done}
            )
    if result is None:
        summary = {
            "violation_found": False,
            "executions": args.max_executions,
            "resumed": resumed,
        }
        save_ckpt(
            args.max_executions,
            {"completed": True, "summary": summary},
        )
        print(json.dumps({**summary, "checkpoints": dict(store.stats)}))
        _obs_end(args)
        return 1
    print(
        f"violation {result.violation} after {result.executions} "
        f"executions; {len(result.program)} externals, "
        f"{len(result.trace.deliveries())} deliveries"
    )
    save_ckpt(
        result.executions,
        {"completed": True,
         "summary": {"violation_found": True,
                     "executions": result.executions,
                     "violation": repr(result.violation),
                     "resumed": resumed}},
    )
    if args.output:
        ExperimentSerializer.save(
            args.output, result.program, result.trace, result.violation,
            app_name=args.app,
        )
        print(f"experiment saved to {args.output}")
    _obs_end(args, args.output)
    return 0


def _streaming_device_cfg(args, app):
    """Device sweep shapes for the streaming fuzz pipeline — the same
    sizing rule as the telemetry confirm sweep (the lanes re-execute the
    fuzzer's own programs)."""
    from .device import DeviceConfig

    return DeviceConfig.for_workload(app, args)


def _resolve_split(args, app, cfg) -> float:
    """--split wins; under --autotune the TuningCache axis decides
    (cache hit or recorded default — calibrate_pipeline_split); plain
    runs take the lane-for-lane default without touching the cache."""
    from .pipeline.budget import DEFAULT_SPLIT

    if getattr(args, "split", None):
        return args.split
    if _autotune_requested(args):
        import jax

        from .tune import TuningCache, calibrate_pipeline_split

        decision = calibrate_pipeline_split(
            app, cfg, platform=jax.devices()[0].platform,
            cache=TuningCache(), extra_key=_workload_discriminator(args),
        )
        return decision.split
    return DEFAULT_SPLIT


def _fuzz_streaming_run(args, app, config, fuzzer) -> int:
    """The streaming fuzz→minimize→replay pipeline (demi_tpu/pipeline/):
    a device fuzz sweep whose violating lanes hand off to the gamut
    minimizer while the sweep keeps running. With --checkpoint-dir the
    queue frames + sweep cursor snapshot at chunk/frame boundaries
    (SIGTERM exits 3; `demi_tpu resume` continues mid-queue, no
    violation lost or minimized twice)."""
    from .pipeline import StreamingPipeline
    from .serialization import ExperimentSerializer

    cfg = _streaming_device_cfg(args, app)
    gen = lambda s: fuzzer.generate_fuzz_test(seed=args.seed + s)  # noqa: E731
    total = args.max_executions
    chunk = min(total, getattr(args, "chunk", None) or max(8, min(64, total // 4)))
    split = _resolve_split(args, app, cfg)
    ckpt = getattr(args, "_resume_checkpoint", None)
    checkpointed = bool(getattr(args, "checkpoint_dir", None))
    pipe = StreamingPipeline(
        app, cfg, config, gen,
        base_key=0, chunk=chunk, split=split,
        checkpoint_dir=getattr(args, "checkpoint_dir", None),
    )
    store = None
    incarnation = 0
    if checkpointed:
        from .persist import CheckpointStore

        store = CheckpointStore(args.checkpoint_dir)
        if ckpt is not None:
            if ckpt.meta.get("completed"):
                return _report_completed(ckpt, args)

            def _apply(c):
                pipe.restore_state(c.sections["pipeline"])
                fuzzer.restore_state(c.sections["fuzzer"])

            _restore_or_exit(_apply, ckpt)
            _restore_obs(ckpt)
        incarnation = _attach_checkpoint_journal(
            args, ckpt, "sweep.chunk", int(pipe.state["chunks"])
        )
        if ckpt is not None:
            # The dead incarnation's post-checkpoint pipeline records
            # re-execute and re-journal (frames re-minimize from their
            # stage files, lanes re-enqueue) — drop them like the
            # sweep.chunk rounds so frame/enqueue numbering stays
            # contiguous across the resume.
            obs.journal.JOURNAL.truncate_from(
                "pipeline.frame", int(pipe.state["frames_done"])
            )
            obs.journal.JOURNAL.truncate_from(
                "pipeline.enqueue", int(pipe.state["enqueued"])
            )

    def save_ckpt(extra_meta=None) -> None:
        store.save(
            {
                "pipeline": pipe.checkpoint_state(),
                "fuzzer": fuzzer.checkpoint_state(),
                "obs": obs.REGISTRY.snapshot(),
            },
            meta={
                "command": "fuzz",
                "cli_args": _resume_args(args, "fuzz"),
                "chunks_done": int(pipe.state["chunks"]),
                "incarnation": incarnation,
                **(extra_meta or {}),
            },
        )
        _flush_samples(args.checkpoint_dir)

    result = None
    if checkpointed:
        from .persist import PreemptionGuard

        every = max(1, getattr(args, "checkpoint_every", None) or 5)
        boundaries = [0]
        with PreemptionGuard() as guard:
            # Readiness line with the guard installed (see the dpor
            # loop).
            print(
                f"fuzz --streaming: checkpointing to "
                f"{args.checkpoint_dir} every {every} chunk/frame "
                "boundary(ies)"
                + (
                    f"; resumed at chunk {pipe.state['chunks']}"
                    if ckpt is not None else ""
                ),
                flush=True,
            )

            def hook(kind: str) -> bool:
                boundaries[0] += 1
                if guard.requested or boundaries[0] % every == 0:
                    # The in-flight elapsed time is folded in at save so
                    # a resumed run's ttf/mcs-rate clocks stay honest.
                    save_ckpt()
                return guard.requested

            result = pipe.run(total, boundary_hook=hook)
        if result.preempted:
            save_ckpt()
            return _preempted_exit(
                args, store,
                {"chunks_done": int(pipe.state["chunks"]),
                 "queue": result.queue},
            )
    else:
        result = pipe.run(total)
    summary = pipe.summary(result)
    summary["resumed"] = ckpt is not None
    if args.output:
        for frame in pipe.queue.done_frames():
            gr = pipe.results.get(frame.seed)
            if gr is None:
                continue  # minimized by a previous incarnation
            out_dir = os.path.join(args.output, f"seed-{frame.seed}")
            ExperimentSerializer.save(
                out_dir,
                gr.final_trace.original_externals or gr.mcs_externals,
                gr.final_trace,
                None,
                app_name=args.app,
                mcs=gr.mcs_externals,
                minimized_trace=gr.final_trace,
            )
        summary["output"] = args.output
    if checkpointed:
        save_ckpt({"completed": True, "summary": {
            # violation_found keys _report_completed's exit code — a
            # resume of this finished run must report success iff MCSes
            # were produced, like the other checkpointed commands.
            "violation_found": bool(summary["mcs_count"]),
            **{k: v for k, v in summary.items() if k != "mcs"},
        }})
        summary["checkpoints"] = dict(store.stats)
    print(json.dumps(summary))
    _obs_end(args, args.output)
    return 0 if summary["mcs_count"] else 1


def cmd_resume(args) -> int:
    """Resume a checkpointed dpor/sweep/fuzz run: load the newest valid
    snapshot generation (corrupt ones degrade to the previous good one),
    rebuild the original command's arguments from the manifest, and
    continue at the recorded boundary."""
    from .persist import CheckpointStore

    store = CheckpointStore(args.dir)
    ckpt = store.load_latest()
    if ckpt is None:
        raise SystemExit(
            f"resume: no loadable checkpoint under {args.dir!r}"
        )
    command = ckpt.meta.get("command")
    fns = {"dpor": cmd_dpor, "sweep": cmd_sweep, "fuzz": cmd_fuzz}
    if command not in fns:
        raise SystemExit(
            f"resume: checkpoint names unknown command {command!r}"
        )
    # A manifest written before the fault plane's knobs were flags has
    # none of them: it meant the literals. One written while the verbs
    # took a kernel backend carries an ``impl`` key nothing reads: the
    # backends were held bit-identical, so the search continues the same.
    ns = argparse.Namespace(
        **{**FAULT_PLANE_DEFAULTS, **ckpt.meta.get("cli_args", {})}
    )
    ns.checkpoint_dir = args.dir
    ns._resume_checkpoint = ckpt
    print(
        f"resuming {command} from {ckpt.path} "
        f"(generation {ckpt.generation})",
        flush=True,
    )
    return fns[command](ns)


def cmd_lint(args) -> int:
    """Determinism lint over app modules/files (default: the bundled
    zoo). Exit code 1 when any error-level finding survives
    suppression — the CI contract."""
    from .analysis import has_errors, lint_targets, render_json, render_text

    try:
        findings = lint_targets(args.targets or None)
    except (FileNotFoundError, SyntaxError) as exc:
        raise SystemExit(f"lint: {exc}")
    if args.format == "json":
        print(json.dumps(render_json(findings), indent=2, sort_keys=True))
    else:
        print(render_text(findings), end="")
    return 1 if has_errors(findings) else 0


def cmd_fuzz(args) -> int:
    from .runner import fuzz
    from .serialization import ExperimentSerializer

    _obs_begin(args)
    _strict_io_begin(args)
    if getattr(args, "streaming", False):
        # Streaming pipeline: device fuzz sweep → violation queue →
        # gamut minimizer, interleaved in flight (demi_tpu/pipeline/).
        # Same env-switch contract as minimize for the oracle flags.
        if getattr(args, "sanitize", False):
            # Refuse loudly rather than silently not sanitizing: the
            # streaming tiers run device lanes + guided lifts, not the
            # host RandomScheduler executions the sanitizer instruments.
            raise SystemExit(
                "--sanitize does not compose with --streaming yet "
                "(strict-sanitize the saved experiments via "
                "`demi_tpu replay --sanitize` instead)"
            )
        if getattr(args, "prefix_fork", False):
            os.environ["DEMI_PREFIX_FORK"] = "1"
        if getattr(args, "async_min", False):
            os.environ["DEMI_ASYNC_MIN"] = "1"
        app = build_app(args)
        config = SchedulerConfig(invariant_check=make_host_invariant(app))
        return _fuzz_streaming_run(args, app, config, build_fuzzer(app, args))
    sanitizing = _sanitize_begin(args)
    # The device sweep is extra WORK, not just bookkeeping: run it only
    # when this invocation explicitly asked for observability artifacts
    # (a global DEMI_OBS=1 must observe the run, not change it).
    confirm_sweep = bool(args.trace_out or args.stats_out)
    app = build_app(args)
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    fuzzer = build_fuzzer(app, args)
    controller = None
    if _autotune_requested(args):
        from .tune import ExplorationController

        controller = ExplorationController(fuzzer)
    if getattr(args, "checkpoint_dir", None):
        rc = _fuzz_checkpoint_run(args, app, config, fuzzer, controller)
        _sanitize_end(sanitizing)
        return rc
    with obs.span("cli.fuzz", app=args.app, seed=args.seed):
        result = fuzz(
            config,
            fuzzer,
            max_executions=args.max_executions,
            seed=args.seed,
            max_messages=args.max_messages,
            invariant_check_interval=app.invariant_interval,
            strategy=app.random_strategy, **_network_flags(app, args),
            timer_weight=args.timer_weight,
            validate_replay=True,
            controller=controller,
        )
        if confirm_sweep:
            confirm = _device_confirm_sweep(
                app, args, None if result is None else result.program
            )
            print(
                f"device {'confirm ' if result is not None else ''}sweep: "
                f"{confirm.violations}/{confirm.lanes} lanes violate"
            )
    if controller is not None:
        weights = controller.final_weights()
        print(
            "autotune: "
            + json.dumps(
                {
                    "rounds": controller.rounds,
                    "weights": {
                        k: round(v, 4) for k, v in (weights or {}).items()
                        if v > 0
                    },
                }
            )
        )
    _sanitize_end(sanitizing)
    if result is None:
        _obs_end(args)
        print("no violation found")
        return 1
    print(
        f"violation {result.violation} after {result.executions} executions; "
        f"{len(result.program)} externals, {len(result.trace.deliveries())} deliveries"
    )
    if args.output:
        ExperimentSerializer.save(
            args.output, result.program, result.trace, result.violation,
            app_name=args.app,
        )
        print(f"experiment saved to {args.output}")
    _obs_end(args, args.output)
    return 0


def cmd_minimize(args) -> int:
    # --peek is a device-replay feature (the host bookkeeping replay
    # follows the device kernel's setting): reject combinations that
    # would silently drop it rather than minimize a different space.
    if args.peek < 0:
        raise SystemExit("--peek must be >= 0")
    if args.peek and args.host:
        raise SystemExit(
            "--peek requires the device-batched oracle (drop --host)"
        )
    if args.peek and args.strategy == "incddmin":
        raise SystemExit(
            "--peek applies to the gamut's replay oracle; incddmin "
            "replays exact DPOR prescriptions and never peeks"
        )
    _strict_io_begin(args)
    if getattr(args, "prefix_fork", False):
        # The env switch is what the checker / DPOR constructors read,
        # so the flag reaches every stage.
        os.environ["DEMI_PREFIX_FORK"] = "1"
    if getattr(args, "async_min", False):
        # The checker and every minimizer read DEMI_ASYNC_MIN, so the
        # whole gamut pipelines without threading a parameter through.
        os.environ["DEMI_ASYNC_MIN"] = "1"
    from .runner import FuzzResult, print_minimization_stats, run_the_gamut
    from .serialization import ExperimentDeserializer, ExperimentSerializer

    _obs_begin(args)
    # Launch profiler on the minimizer tier: BatchedDDMin levels /
    # internal rounds are this command's "rounds" — dispatches and
    # harvest blocks land in the per-shape ledger exactly like dpor
    # rounds, persisted under the same profile=launch TuningCache key.
    profiling = _profile_begin(args)
    sanitizing = _sanitize_begin(args)
    app = build_app(args)
    if app.channels == "datagram":
        from .device.core import datagram_refusal

        raise SystemExit(
            "minimize: " + datagram_refusal("the replay that judges a candidate")
        )
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    de = ExperimentDeserializer(args.experiment, app)
    externals = de.get_externals()
    trace = de.get_trace(externals)
    violation = de.get_violation()
    fr = FuzzResult(program=externals, trace=trace, violation=violation, executions=0)

    def profile_end() -> None:
        if not profiling:
            return
        from .device.batch_oracle import default_device_config

        prof = {}
        _profile_end(
            args, prof, app, default_device_config(app, trace, externals)
        )
        print("profile: " + json.dumps(
            {k: prof[k] for k in ("launch_profile_cache",) if k in prof}
        ))

    if args.strategy == "incddmin":
        from .runner import edit_distance_dpor_ddmin

        # Device probes explore batch_size lanes per round: map the user's
        # interleaving budget onto rounds so --max-interleavings works on
        # both paths.
        device_batch = 32
        mcs = edit_distance_dpor_ddmin(
            config, trace, externals, violation,
            dpor_kwargs=(
                {
                    "batch_size": device_batch,
                    "max_rounds": max(
                        1, args.max_interleavings // device_batch
                    ),
                }
                if not args.host
                else {"max_interleavings": args.max_interleavings}
            ),
            checkpoint_dir=args.experiment, resume=args.resume,
            app=None if args.host else app,
        )
        kept = mcs.get_all_events()
        print(f"IncDDMin MCS: {len(externals)} -> {len(kept)} externals")
        profile_end()
        _sanitize_end(sanitizing)
        ExperimentSerializer.save(
            args.experiment, externals, trace, violation, app_name=args.app,
            mcs=kept,
        )
        _obs_end(args, args.experiment)
        return 0
    # Device-batched trials are the default for DSL apps (the BASELINE
    # north-star pipeline); --host selects the sequential STS oracle.
    # The checker is built here (not inside the gamut) so each level's
    # candidate batch shards over every local device when this process
    # has more than one, and so the summary can say how it was laid out.
    checker = None
    if not args.host:
        from .device.batch_oracle import (
            DeviceReplayChecker,
            default_device_config,
        )
        from .parallel.mesh import local_lane_mesh

        checker = DeviceReplayChecker(
            app,
            default_device_config(
                app, trace, externals,
                **({"replay_peek": args.peek} if args.peek else {}),
            ),
            config,
            mesh=local_lane_mesh(),
        )
    with obs.span("cli.minimize", app=args.app):
        if getattr(args, "streaming", False):
            # Single-frame streaming drive: the SAME generator the
            # orchestrator steps (run_the_gamut drains it), exercised
            # level-by-level here so the run journals/spans like one
            # pipeline frame — useful for watching a lone minimization
            # in `demi_tpu top` and for A/B-ing the generator path.
            import time as _time

            from .runner import run_the_gamut_streaming

            from .minimization.pipeline import drain_stream

            t_frame = _time.perf_counter()
            result = drain_stream(run_the_gamut_streaming(
                config, fr, wildcards=not args.no_wildcards,
                app=None if args.host else app,
                checker=checker,
                checkpoint_dir=args.experiment, resume=args.resume,
                stage_budget_seconds=args.stage_budget,
            ))
            obs.journal.emit(
                "pipeline.frame",
                round=1,
                seed=args.seed,
                code=getattr(violation, "code", None),
                wall_s=round(_time.perf_counter() - t_frame, 6),
                mcs_externals=len(result.mcs_externals),
                deliveries=len(result.final_trace.deliveries()),
                stages=len(result.stages),
                queue_depth=0,
                ttf_mcs_s=round(_time.perf_counter() - t_frame, 6),
            )
        else:
            result = run_the_gamut(
                config, fr, wildcards=not args.no_wildcards,
                app=None if args.host else app,
                checker=checker,
                checkpoint_dir=args.experiment, resume=args.resume,
                stage_budget_seconds=args.stage_budget,
            )
    print_minimization_stats(result)
    profile_end()
    _sanitize_end(sanitizing)
    ExperimentSerializer.save(
        args.experiment, externals, trace, violation, app_name=args.app,
        mcs=result.mcs_externals, minimized_trace=result.final_trace,
        stats=result.stats,
    )
    print(f"MCS + minimized trace saved to {args.experiment}")
    # The MCS is re-checked on the host STS oracle against the ORIGINAL
    # trace — the plain reference, independent of the device trials.
    from .schedulers.replay import sts_oracle

    verified = sts_oracle(config, trace).test(
        list(result.mcs_externals), violation
    )
    summary = {
        "externals": len(externals),
        "mcs_externals": len(result.mcs_externals),
        "deliveries": len(trace.deliveries()),
        "minimized_deliveries": len(result.final_trace.deliveries()),
        "replays": result.stats.total_replays,
        "mcs_verified": verified is not None,
        "oracle": "host" if args.host else "device",
        **_device_fields(),
    }
    if checker is not None and checker.lane_sharding is not None:
        summary["lane_sharding"] = checker.lane_sharding
    print(json.dumps(summary))
    _obs_end(args, args.experiment)
    return 0


def cmd_replay(args) -> int:
    from .schedulers.replay import ReplayScheduler
    from .serialization import ExperimentDeserializer

    # Strict replay is exactly where handler nondeterminism invalidates
    # the run silently, so --sanitize here arms the STRICT mode: a
    # wall-clock read / global random draw / message mutation raises
    # instead of just counting.
    sanitizing = _sanitize_begin(args, strict=True)
    app = build_app(args)
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    de = ExperimentDeserializer(args.experiment, app)
    externals = de.get_externals()
    trace = de.get_trace(externals)
    result = ReplayScheduler(config).replay(trace, externals)
    print(
        f"replayed {result.deliveries} deliveries; violation: {result.violation}"
    )
    _sanitize_end(sanitizing)
    return 0 if result.violation is not None else 1


def cmd_sweep(args) -> int:
    _obs_begin(args)
    if args.processes > 1:
        if getattr(args, "checkpoint_dir", None):
            # Refuse loudly up front: the distributed branch returns
            # before the single-process checkpoint loop, so the flag
            # would otherwise be dropped silently — and a preempted
            # multi-process sweep would have nothing to resume.
            raise SystemExit(
                "--checkpoint-dir is single-process (drop --processes)"
            )
        if _autotune_requested(args):
            # The weight loop and calibration run in THIS process; the
            # distributed launcher's workers sweep in their own. Closing
            # the loop across ranks is future work — say so rather than
            # silently dropping the flag.
            print(
                "sweep: --autotune is single-process for now; ignoring it "
                "for the distributed launcher",
                file=sys.stderr,
            )
        from .parallel.distributed import launch_distributed_sweep

        summary = launch_distributed_sweep(
            num_processes=args.processes,
            total_lanes=args.batch,
            chunk_size=max(1, args.batch // (4 * args.processes)),
            workload=_workload_dict(args),
        )
        summary["rehearsal"] = True
        print(json.dumps(summary))
        _obs_end(args)
        return 0

    _strict_io_begin(args)
    prefix_fork = _flag_or_unset(args, "prefix_fork")
    from .device import DeviceConfig
    from .parallel.sweep import SweepDriver

    app = build_app(args)
    cfg = DeviceConfig.for_workload(app, args)
    fuzzer = build_fuzzer(app, args)
    if getattr(args, "checkpoint_dir", None):
        return _sweep_checkpoint_run(args, app, cfg, fuzzer)
    gen = lambda s: fuzzer.generate_fuzz_test(seed=args.seed + s)  # noqa: E731
    chunk = min(args.batch, getattr(args, "chunk", None) or args.batch)
    violating: list = []

    def note_violations(seeds, codes) -> None:
        for seed, code in zip(seeds, codes):
            if len(violating) >= _VIOLATING_SEEDS_KEPT:
                return
            violating.append([int(seed), int(code)])

    autotune_summary = None
    if _autotune_requested(args):
        # Closed loop: calibrate (variant, chunk) — cache hit skips the
        # measurement reps entirely — then run chunked rounds with the
        # fuzzer-weight bandit scoring each chunk's fresh fingerprints.
        import jax

        from .tune import (
            ExplorationController,
            TuningCache,
            calibrate_sweep,
            sweep_axes,
        )

        platform = jax.devices()[0].platform
        axes = sweep_axes(cfg, chunk)
        # Never calibrate a chunk the sweep can't run: the decision must
        # describe the configuration that actually executes (and gets
        # cached), so cap the axis at the sweep's own lane budget.
        axes["chunk"] = [c for c in axes["chunk"] if c <= args.batch] or [
            chunk
        ]
        decision = calibrate_sweep(
            app, cfg, gen, chunk=chunk, platform=platform,
            cache=TuningCache(), axes=axes,
            extra_key=_workload_discriminator(args),
        )
        chunk = min(args.batch, int(decision.params.get("chunk", chunk)))
        driver = SweepDriver(
            app, cfg, gen, variant=decision.params.get("variant"),
            prefix_fork=prefix_fork,
        )
        driver.violation_hook = note_violations
        controller = ExplorationController(fuzzer)
        # --sweep-mode continuous rides the lane-compacted continuous
        # driver with segment-boundary reward attribution (lanes tagged
        # by the proposal epoch that generated them); chunked keeps the
        # original one-proposal-per-chunk loop.
        result = driver.sweep_autotuned(
            args.batch, chunk, controller, mode=args.sweep_mode
        )
        autotune_summary = {
            "decision": decision.to_json(),
            "rounds": controller.rounds,
            "weights": {
                k: round(v, 4)
                for k, v in (controller.final_weights() or {}).items()
                if v > 0
            },
        }
    else:
        driver = _sweep_driver(app, cfg, gen, prefix_fork=prefix_fork)
        driver.violation_hook = note_violations
        # Default: lane-compacted continuous sweep (finished lanes are
        # harvested and refilled at segment boundaries). --sweep-mode
        # chunked launches fixed whole-batch kernels instead.
        result = driver.sweep(args.batch, chunk, mode=args.sweep_mode)
    summary = {
        "lanes": result.lanes,
        "unique_schedules": result.unique_schedules,
        "violations": result.violations,
        "codes": {str(c): n for c, n in result.codes.items()},
        "first_violating_seed": result.first_violating_seed,
        # The first few violating lanes as [seed, code]: what a caller
        # needs to re-run them traced and lift them to the host oracle
        # (runner.lift_lane_to_host) without sweeping again.
        "violating_seeds": violating,
        "overflow_lanes": result.overflow_lanes,
        # Lanes cut by --max-messages before quiescence under an
        # invariant judged at quiescence only: no verdict.
        "unfinished_lanes": result.unfinished_lanes,
        # Order-free digest of every lane's (seed, status, code,
        # sched_hash): equal across modes, chunkings and device counts
        # for the same seeds (parallel/sweep.lanes_digest).
        "lanes_digest": f"{result.lanes_digest:016x}",
        # Wall-clock aggregate (per-chunk seconds overlap under async
        # dispatch; this one never double-counts).
        "schedules_per_sec": round(result.schedules_per_sec_wall, 1),
        **_device_fields(),
    }
    if result.lane_sharding is not None:
        summary["lane_sharding"] = result.lane_sharding
    if result.occupancy is not None:
        summary["occupancy"] = round(result.occupancy, 3)
    if driver.host_share is not None:
        # Host-vs-device wall split (the vectorized-host-path health
        # number; also the sweep.host_share gauge under DEMI_OBS).
        summary["host_share"] = round(driver.host_share, 3)
    if autotune_summary is not None:
        summary["autotune"] = autotune_summary
    if driver.fork_stats is not None:
        summary["prefix_fork"] = driver.fork_stats
    print(json.dumps(summary))
    _obs_end(args)
    return 0


def cmd_dpor(args) -> int:
    """Systematic batched DPOR search (BASELINE config 2 shape)."""
    _obs_begin(args)
    _strict_io_begin(args)
    prefix_fork = _flag_or_unset(args, "prefix_fork")
    host_shards = getattr(args, "host_shards", 0) or None
    from .device import DeviceConfig
    from .device.dpor_sweep import (
        DATAGRAM_REFUSAL, FIFO_REFUSAL, DeviceDPOROracle,
    )

    app = build_app(args)
    if app.channels == "fifo":
        raise SystemExit(f"dpor: {FIFO_REFUSAL}")
    if app.channels == "datagram":
        raise SystemExit(f"dpor: {DATAGRAM_REFUSAL}")
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    cfg = DeviceConfig.for_workload(
        app, args, record_trace=True, record_parents=True
    )
    if getattr(args, "checkpoint_dir", None):
        return _dpor_checkpoint_run(args, app, cfg)
    autotune = _autotune_requested(args)
    program = dsl_start_events(app) + [WaitQuiescence()]
    inflight_decision = None
    double_buffer = None
    if autotune and getattr(args, "async_min", False):
        # The double-buffer axis is a real trade on CPU (a mispredicted
        # in-flight launch burns host cores), so under --autotune the
        # decision is measured — and cached, a second run launches
        # nothing. Non-CPU platforms decide "on" without measuring.
        import jax

        from .tune import calibrate_dpor_inflight, make_dpor_inflight_measure

        platform = jax.devices()[0].platform
        inflight_decision = calibrate_dpor_inflight(
            app, cfg, batch=args.batch,
            measure=(
                make_dpor_inflight_measure(
                    app, cfg, program, batch=args.batch,
                    prefix_fork=prefix_fork,
                )
                if platform == "cpu"
                else None
            ),
        )
        double_buffer = inflight_decision.enabled
    host_shard_decision = None
    if autotune and host_shards is None:
        # Measured host-shard axis: how many digest-range shards the
        # admission pipeline fans out over (bit-identical at any count,
        # so the only question is rounds/sec). A cache hit costs no
        # measurements; the decision reaches DeviceDPOROracle as the
        # explicit flag does.
        from .tune import calibrate_host_shards, make_host_shard_measure

        host_shard_decision = calibrate_host_shards(
            app, cfg, batch=args.batch,
            measure=make_host_shard_measure(
                app, cfg, program, batch=args.batch,
                prefix_fork=prefix_fork,
            ),
        )
        host_shards = host_shard_decision.shards
    from .parallel.mesh import local_lane_mesh

    oracle = DeviceDPOROracle(
        app, cfg, config, batch_size=args.batch, max_rounds=args.rounds,
        autotune=autotune, double_buffer=double_buffer,
        prefix_fork=prefix_fork,
        # The oracle turns it into the frontier's double-buffered
        # in-flight rounds (off on a CPU — see
        # tune.calibrate_dpor_inflight) and the test_window surface.
        async_min=_flag_or_unset(args, "async_min"),
        host_shards=host_shards,
        mesh=local_lane_mesh(args.batch),
        static_independence=_flag_or_unset(args, "static_prune"),
        sleep_sets=_flag_or_unset(args, "sleep_sets"),
    )
    _profile_begin(args)
    with obs.span("cli.dpor", app=args.app):
        trace = oracle.test(program, None)
    from .native import scan_backend

    summary = {
        "interleavings": oracle.last_interleavings,
        "violation_found": trace is not None,
        "deliveries": len(trace.deliveries()) if trace is not None else None,
        # A found interleaving is returned only after GuidedScheduler
        # re-executed it on the host oracle and the violation matched
        # (DeviceDPOROracle.test), so "found" means host-verified.
        "host_verified": trace is not None,
        # Which implementation served the host-half racing scan: the C++
        # library built from native/trace_analysis.cpp, or its NumPy twin
        # (no compiler, or a degraded surface).
        "racing_scan": scan_backend(),
        **_device_fields(),
    }
    if oracle.lane_sharding is not None:
        summary["lane_sharding"] = oracle.lane_sharding
    _profile_end(args, summary, app, cfg)
    if oracle.host_share() is not None:
        # Host-vs-device wall split across the frontier rounds (also the
        # dpor.host_share gauge under DEMI_OBS).
        summary["host_share"] = round(oracle.host_share(), 3)
    if autotune:
        summary["autotune"] = oracle.tuner_summaries()
    if inflight_decision is not None:
        summary["inflight_decision"] = inflight_decision.to_json()
    if host_shard_decision is not None:
        summary["host_shard_decision"] = host_shard_decision.to_json()
    if oracle.fork_stats is not None:
        summary["prefix_fork"] = oracle.fork_stats
    if oracle.supports_async:
        # In-flight round economics (speculative launches used/discarded).
        summary["async"] = oracle.async_stats()
    if oracle.static_stats is not None:
        # Racing pairs skipped as provably-no-op flips (static
        # commutativity analysis; also the analysis.static_pruned
        # counters under DEMI_OBS).
        summary["static_pruned"] = oracle.static_stats
        summary["static_relation"] = oracle.static_independence.summary()
    if oracle.sleep_stats is not None:
        # Sleep-set / race-reversal pruning ledger + the redundancy
        # ratio (explored over the Mazurkiewicz-class lower bound; also
        # the analysis.sleep_pruned counters and the
        # dpor.redundancy_ratio gauge under DEMI_OBS).
        summary["sleep_sets"] = oracle.sleep_stats
    print(json.dumps(summary))
    _obs_end(args)
    return 0 if trace is not None else 1


def cmd_fleet(args) -> int:
    """Sharded exploration fleet (demi_tpu/fleet): coordinator +
    worker processes over generation-frozen round leases, global
    class-key dedup, optional cross-run warm start via the
    content-addressed class store. Coverage is bit-identical to the
    single-process `demi_tpu dpor` loop at any worker count."""
    _obs_begin(args)
    _strict_io_begin(args)
    from .fleet import run_fleet

    workload = {
        **_workload_dict(args),
        "handler_edit": getattr(args, "handler_edit", None),
    }
    delta = bool(getattr(args, "delta", False)) or bool(
        getattr(args, "diff_audit", False)
    )
    fleet_kwargs = dict(
        workers=args.workers,
        batch=args.batch,
        rounds=args.rounds,
        # --class-store implies the global class dedup (a covered
        # class must suppress, or the warm start cannot skip it);
        # --sleep-sets turns the same pruning on without a store.
        prune=bool(args.sleep_sets) or args.class_store is not None or delta,
        class_store_dir=args.class_store,
        warm_start=args.class_store is not None and not delta,
        delta=delta,
        stop_on_violation=args.stop_on_violation,
        journal_dir=getattr(args, "journal", None),
        max_outstanding=1 if args.serialize_leases else None,
        devices_per_worker=args.devices_per_worker,
        lease_timeout=args.lease_timeout,
        straggler_factor=args.straggler_factor,
        host_shards=getattr(args, "host_shards", 0) or None,
    )
    with obs.span("cli.fleet", app=args.app, workers=args.workers):
        summary = run_fleet(workload, **fleet_kwargs)
    audit_ok = True
    if getattr(args, "diff_audit", False):
        # Soundness audit: a full scratch exploration of the SAME
        # (changed) app must agree with the differential run on the
        # class set, the effective violation-code set, and the per-code
        # canonical witness digests. Needs a round budget that drains
        # the frontier on both sides, or equality is meaningless.
        scratch_kwargs = dict(
            fleet_kwargs, class_store_dir=None, warm_start=False,
            delta=False, journal_dir=None,
        )
        with obs.span("cli.fleet_audit", app=args.app):
            scratch = run_fleet(workload, **scratch_kwargs)
        audit = {
            "classes_match": summary.get("classes_sha")
            == scratch.get("classes_sha"),
            "codes_match": summary.get("violation_codes_effective")
            == scratch.get("violation_codes_effective"),
            "witnesses_match": summary.get("witness_shas")
            == scratch.get("witness_shas"),
            "scratch_explored": scratch.get("explored"),
            "delta_explored": summary.get("explored"),
        }
        audit["sound"] = bool(
            audit["classes_match"]
            and audit["codes_match"]
            and audit["witnesses_match"]
        )
        audit_ok = audit["sound"]
        summary["audit"] = audit
    print(json.dumps(summary))
    _obs_end(args)
    if not audit_ok:
        return 2
    if args.stop_on_violation:
        return 0 if summary.get("violation_found") else 1
    return 0


def cmd_store(args) -> int:
    """Class-store maintenance. ``compact`` merges a store's
    accumulated per-run segments into one deduped segment per workload
    fingerprint (atomic tmp+fsync+rename publish; old segments removed
    only after the merged segment is durable; corrupt segments skipped
    with ``persist.corrupt_fallbacks`` and left in place)."""
    from .fleet.ledger import compact_store

    if args.action == "compact":
        print(json.dumps(compact_store(args.dir)))
        return 0
    raise SystemExit(f"unknown store action {args.action!r}")


def cmd_shiviz(args) -> int:
    """Export a saved experiment's trace for the ShiViz visualizer
    (reference: RunnerUtils.visualizeDeliveries, RunnerUtils.scala:1341)."""
    from .serialization import ExperimentDeserializer
    from .utils.shiviz import trace_to_shiviz, write_shiviz

    app = build_app(args)
    de = ExperimentDeserializer(args.experiment, app)
    externals = de.get_externals()
    trace = de.get_trace(externals)
    if args.output:
        write_shiviz(trace, args.output)
        print(f"ShiViz log written to {args.output}")
    else:
        print(trace_to_shiviz(trace))
    return 0


def cmd_dot(args) -> int:
    """Export a saved experiment as Graphviz DOT: the delivery chain, plus
    the happens-before forest when a dep graph was saved (reference:
    schedulers/Util.scala getDot:580-618)."""
    from .fingerprints import FingerprintFactory
    from .serialization import ExperimentDeserializer, load_dep_graph
    from .utils.dot import dep_tracker_to_dot, event_trace_to_dot

    app = build_app(args)
    de = ExperimentDeserializer(args.experiment, app)
    externals = de.get_externals()
    trace = de.get_trace(externals)
    out = event_trace_to_dot(trace)
    tracker = load_dep_graph(args.experiment, FingerprintFactory())
    if tracker is not None:
        out += "\n" + dep_tracker_to_dot(tracker)
    if args.output:
        with open(args.output, "w") as f:
            f.write(out + "\n")
        print(f"DOT written to {args.output}")
    else:
        print(out)
    return 0


def cmd_report(args) -> int:
    """Markdown report of a saved experiment (summary of the artifacts the
    reference spreads over stats printing + graphing, RunnerUtils.scala:1200)."""
    from .tools.report import render_report

    text = render_report(args.experiment)
    if args.output:
        with open(args.output, "w") as f:
            f.write(text)
        print(f"report written to {args.output}")
    else:
        print(text)
    return 0


def cmd_bridge_fuzz(args) -> int:
    """Fuzz an EXTERNAL app over the bridge protocol: spawn the launcher,
    start every registered actor, inject randomized sends, flag quiescent
    ask-deadlock (bridge_invariant), and minimize the external program on
    a violation. Works for hand-written bridge apps and unmodified
    asyncio apps behind the adapter alike."""
    import random as _random
    import shlex

    from .bridge import BridgeSession, bridge_invariant
    from .bridge.session import _normalize
    from .external_events import (
        MessageConstructor,
        Send,
        Start,
        atomic_block,
    )
    from .runner import sts_sched_ddmin
    from .schedulers import RandomScheduler

    if args.atomic_batch < 0 or args.atomic_batch > args.num_sends:
        raise SystemExit(
            f"--atomic-batch must be in [0, --num-sends]; got "
            f"{args.atomic_batch} with --num-sends {args.num_sends}"
        )
    payloads = [_normalize(json.loads(s)) for s in args.send]
    if not payloads and args.num_sends > 0:
        raise SystemExit(
            "at least one --send JSON payload is required "
            "(or pass --num-sends 0 for apps driven purely by Starts)"
        )
    predicate = None
    if args.invariant:
        # App-specific safety predicate from the app's integration
        # surface: "module:function" over the checkpoint-states dict.
        import importlib

        mod_name, _, fn_name = args.invariant.partition(":")
        predicate = getattr(importlib.import_module(mod_name), fn_name)
    with BridgeSession(
        shlex.split(args.launcher), transport=args.transport
    ) as session:
        names = session.actor_names
        targets = args.to or names
        print(f"registered actors: {', '.join(names)}")
        config = SchedulerConfig(
            invariant_check=bridge_invariant(predicate=predicate)
        )
        for i in range(args.max_executions):
            rng = _random.Random(args.seed + i)
            sends = [
                Send(
                    rng.choice(targets),
                    MessageConstructor(lambda p=rng.choice(payloads): p),
                )
                for _ in range(args.num_sends)
            ]
            if args.atomic_batch and len(sends) >= args.atomic_batch:
                # Mark a random contiguous run of sends as one external
                # atomic block (minimizes all-or-nothing, unignorable).
                k = args.atomic_batch
                j = rng.randrange(len(sends) - k + 1)
                atomic_block(sends[j:j + k])
            program = [
                Start(n, ctor=session.actor_factory(n)) for n in names
            ] + sends + [WaitQuiescence(budget=args.wait_budget)]
            result = RandomScheduler(
                config, seed=args.seed + i, max_messages=args.max_messages,
                invariant_check_interval=1, timer_weight=args.timer_weight,
            ).execute(program)
            if result.violation is None:
                continue
            print(
                f"violation {result.violation} after {i + 1} executions; "
                f"{result.deliveries} deliveries"
            )
            mcs, verified = sts_sched_ddmin(
                config, result.trace, program, result.violation
            )
            kept = mcs.get_all_events()
            print(f"minimized: {len(program)} -> {len(kept)} externals"
                  + ("" if verified is None else " (MCS verified)"))
            for ev in kept:
                print(f"  {ev!r}")
            return 0
        print("no violation found")
        return 1


def cmd_tune(args) -> int:
    """Calibrate the sweep schedule (kernel variant, chunk size) for a
    workload and persist the decision to the tuning cache.

    ``--dry-run`` resolves the candidate axes and prints any cached
    decision WITHOUT launching a kernel — the smoke path CI exercises.
    A second non-dry run of the same workload hits the cache and also
    launches nothing (``source: "cached"``)."""
    import jax

    from .device import DeviceConfig
    from .tune import TuningCache, calibrate_sweep, sweep_axes, workload_key

    _obs_begin(args)
    app = build_app(args)
    cfg = DeviceConfig.for_workload(app, args)
    fuzzer = build_fuzzer(app, args)
    gen = lambda s: fuzzer.generate_fuzz_test(seed=args.seed + s)  # noqa: E731
    cache = TuningCache(args.cache)
    platform = jax.devices()[0].platform
    chunk = args.chunk or args.batch
    if args.dry_run:
        key = workload_key(
            app.name, app.num_actors, cfg, platform, chunk=chunk,
            **_workload_discriminator(args),
        )
        print(
            json.dumps(
                {
                    "dry_run": True,
                    "key": key,
                    "axes": sweep_axes(cfg, chunk),
                    "cached": cache.get(key),
                    "cache_path": cache.path,
                }
            )
        )
        _obs_end(args)
        return 0
    decision = calibrate_sweep(
        app, cfg, gen, chunk=chunk, platform=platform, cache=cache,
        reps=args.reps, extra_key=_workload_discriminator(args),
    )
    out = decision.to_json()
    out["cache_path"] = cache.path
    print(json.dumps(out))
    _obs_end(args)
    return 0


def cmd_stats(args) -> int:
    """Print a metrics-registry snapshot.

    With ``-i/--input`` (or an experiment dir's obs_snapshot.json via
    ``-e``), saved snapshots are merged (counters/histograms add) and
    printed. Without inputs it runs an instrumented smoke workload —
    host fuzz executions plus a small device sweep on the selected app —
    and prints the live registry, device ``LaneStats`` totals included."""
    inputs = list(args.input)
    if args.experiment:
        path = os.path.join(args.experiment, "obs_snapshot.json")
        if not os.path.exists(path):
            raise SystemExit(
                f"no obs_snapshot.json in {args.experiment!r} (re-run "
                "fuzz/minimize with --stats-out or --trace-out)"
            )
        inputs.append(path)
    if inputs:
        snaps = []
        for path in inputs:
            with open(path) as f:
                snaps.append(json.load(f))
        merged = obs.merge_snapshots(*snaps)
        if getattr(args, "prom", False):
            from .obs.timeseries import prom_text

            print(prom_text(merged), end="")
        else:
            print(json.dumps(merged, indent=2, sort_keys=True))
        return 0

    obs.enable()
    from .runner import fuzz

    app = build_app(args)
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    with obs.span("cli.stats", app=args.app):
        fuzz(
            config,
            build_fuzzer(app, args),
            max_executions=args.max_executions,
            seed=args.seed,
            max_messages=args.max_messages,
            invariant_check_interval=app.invariant_interval,
            strategy=app.random_strategy, **_network_flags(app, args),
            timer_weight=args.timer_weight,
        )
        from .device import DeviceConfig
        from .parallel.sweep import SweepDriver

        cfg = DeviceConfig.for_workload(app, args)
        fuzzer = build_fuzzer(app, args)
        driver = SweepDriver(
            app, cfg, lambda s: fuzzer.generate_fuzz_test(seed=args.seed + s)
        )
        driver.sweep(args.batch, args.batch, mode="chunked")
    if getattr(args, "prom", False):
        from .obs.timeseries import prom_text

        print(prom_text(obs.REGISTRY.snapshot()), end="")
    else:
        print(obs.REGISTRY.to_json())
    return 0


def cmd_top(args) -> int:
    """Live terminal dashboard over a run's round journal (demi_tpu.obs
    journal wire format; `--once` renders a single frame for CI/pipes)."""
    from .tools.top import run_top

    return run_top(
        args.dir, once=args.once, interval=args.interval,
        window=args.window,
    )


def cmd_trace(args) -> int:
    """Stitch N processes' span sidecars + journals into one clock-
    aligned Perfetto timeline (obs/distributed.py). Point it at the
    directories a fleet/service run exported into — typically one
    shared journal dir — and load the output in ui.perfetto.dev."""
    from .obs import distributed as dtrace

    if args.action == "stitch":
        summary = dtrace.stitch(args.dirs, args.output)
        print(json.dumps(summary))
        return 0 if summary.get("spans") else 1
    print(f"unknown trace action {args.action!r}", file=sys.stderr)
    return 2


def _service_workload(args) -> dict:
    """CLI-args-shaped workload dict for the service wire — the same
    fields the fleet ships, so a submission means the same thing on any
    daemon host."""
    w = _workload_dict(args)
    if getattr(args, "commands", 0):
        w["commands"] = args.commands
    return w


def cmd_serve(args) -> int:
    """Multi-tenant exploration service daemon (demi_tpu/service):
    accepts tenant job submissions over the fleet's TCP JSON wire and
    batches their fuzz→minimize work into shared device launches.
    Announces `{"op": "listening", "addr": ...}` on stdout; SIGTERM
    checkpoints mid-queue and exits 3 (`serve --resume` continues)."""
    _obs_begin(args)
    from .service import run_service

    rc = run_service(
        args.state_dir,
        host=args.host,
        port=args.port,
        split=args.split,
        depth=args.depth,
        default_chunk=args.chunk,
        stage_budget_seconds=args.stage_budget,
        resume=args.resume,
        drain_when_idle=args.drain,
    )
    _obs_end(args)
    return rc


def cmd_submit(args) -> int:
    """Submit one tenant job (app spec + seed range) to a running
    `demi_tpu serve` daemon; prints the admitted job summary JSON."""
    from .service import ServiceClient, ServiceError

    try:
        with ServiceClient(args.addr) as client:
            reply = client.submit(
                args.tenant,
                _service_workload(args),
                lanes=args.lanes,
                chunk=args.chunk,
                base_key=args.base_key,
                max_frames=args.max_frames,
                weight=args.weight,
                wildcards=not args.no_wildcards,
            )
    except ServiceError as exc:
        print(json.dumps({"error": str(exc), "refused": exc.refused}))
        return 2 if exc.refused else 1
    print(json.dumps(reply))
    return 0


def cmd_jobs(args) -> int:
    """List/poll a daemon's jobs, or fetch one job's minimization
    artifacts (`--job ID --fetch [--out DIR]`)."""
    from .service import ServiceClient, ServiceError

    try:
        with ServiceClient(args.addr) as client:
            if args.job and args.fetch:
                frames = client.fetch(args.job)
                if args.out:
                    os.makedirs(args.out, exist_ok=True)
                    path = os.path.join(
                        args.out, f"{args.job}-artifacts.json"
                    )
                    with open(path, "w") as f:
                        json.dump(frames, f, indent=2, sort_keys=True)
                    print(json.dumps({
                        "job": args.job, "frames": len(frames),
                        "out": path,
                    }))
                else:
                    print(json.dumps(frames))
            elif args.job:
                print(json.dumps(client.poll(args.job)))
            elif args.status:
                print(json.dumps(client.status()))
            else:
                print(json.dumps(client.jobs(args.tenant)))
    except ServiceError as exc:
        print(json.dumps({"error": str(exc)}))
        return 1
    return 0


def cmd_interactive(args) -> int:
    from .schedulers.interactive import InteractiveScheduler

    app = build_app(args)
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    sched = InteractiveScheduler(config)
    program = dsl_start_events(app) + [WaitQuiescence()]
    result = sched.run_session(program)
    print(f"session over: {result.deliveries} deliveries, violation {result.violation}")
    return 0


def main(argv: Optional[list] = None) -> int:
    parser = argparse.ArgumentParser(prog="demi_tpu")
    sub = parser.add_subparsers(dest="command", required=True)

    def common(p):
        p.add_argument("--app", default="broadcast")
        p.add_argument("--nodes", type=int, default=3)
        p.add_argument("--bug", default=None)
        p.add_argument("--seed", type=int, default=0)
        p.add_argument("--num-events", type=int, default=12, dest="num_events")
        p.add_argument("--max-messages", type=int, default=400, dest="max_messages")
        p.add_argument("--timer-weight", type=float, default=0.2, dest="timer_weight")
        p.add_argument("--kill-weight", type=float, default=0.05, dest="kill_weight")
        p.add_argument(
            "--partition-weight", type=float, default=0.0, dest="partition_weight"
        )
        # The rest of the fault plane (crash-recovery, healing links,
        # bounded waits); each default is what was a literal before.
        knobs = FAULT_PLANE_DEFAULTS
        p.add_argument("--send-weight", type=float,
                       default=knobs["send_weight"], dest="send_weight")
        p.add_argument("--wait-weight", type=float,
                       default=knobs["wait_weight"], dest="wait_weight",
                       help="weight of a generated WaitQuiescence")
        p.add_argument("--hard-kill-weight", type=float,
                       default=knobs["hard_kill_weight"],
                       dest="hard_kill_weight",
                       help="weight of HardKill: the node stops, its state "
                            "and its pending messages are gone")
        p.add_argument("--restart-weight", type=float,
                       default=knobs["restart_weight"], dest="restart_weight",
                       help="weight of restarting a killed node (recovery)")
        p.add_argument("--max-kills", type=int, default=knobs["max_kills"],
                       dest="max_kills",
                       help="kills of either kind a program may hold; a "
                            "restart gives none back")
        p.add_argument("--max-sends", type=int, default=knobs["max_sends"],
                       dest="max_sends",
                       help="client sends a program may hold (one flood a "
                            "schedule keeps a broadcast's pool bounded); "
                            "default: unlimited")
        p.add_argument("--wait-budget", type=int, nargs=2,
                       default=knobs["wait_budget"], dest="wait_budget",
                       metavar=("LO", "HI"),
                       help="deliveries a generated wait lasts, drawn from "
                            "LO..HI, so later events land mid-flood "
                            "(default: every wait drains)")
        p.add_argument("--log-cap", type=int, default=knobs["log_cap"],
                       dest="log_cap",
                       help="raft: log entries a node holds")
        p.add_argument("--snapshot-every", type=int,
                       default=knobs["snapshot_every"], dest="snapshot_every",
                       help="raft_reconfig: applied entries above its "
                            "snapshot at which a server compacts "
                            "(default: half of --log-cap)")
        # Datagram channels (an app whose DSLApp.channels say so: paxos).
        p.add_argument("--dup-weight", type=float,
                       default=knobs["dup_weight"], dest="dup_weight",
                       help="share of dispatch steps that deliver an "
                            "actor's message and keep it pending, so that "
                            "it may be delivered again")
        p.add_argument("--drop-weight", type=float,
                       default=knobs["drop_weight"], dest="drop_weight",
                       help="share of dispatch steps that lose an actor's "
                            "pending message undelivered")
        p.add_argument("--max-dups", type=int, default=knobs["max_dups"],
                       dest="max_dups",
                       help="kept deliveries a schedule may hold")
        p.add_argument("--max-drops", type=int, default=knobs["max_drops"],
                       dest="max_drops",
                       help="lost messages a schedule may hold")
        p.add_argument("--stages", type=int, default=knobs["stages"],
                       help="spark: stages a job has")
        p.add_argument("--tasks", type=int, default=knobs["tasks"],
                       help="spark: tasks a stage has (each launched "
                            "twice; a SQL shuffle's default is 200)")
        p.add_argument(
            "--handler-edit", default=None, dest="handler_edit",
            metavar="KIND[:TAG]",
            help="apply a synthetic handler edit before building the app "
                 "(raft only): 'refactor[:tag]' = behavior- and "
                 "effect-identical rewrite of one branch, "
                 "'opaque[:tag]' = an edit the static effects analyzer "
                 "cannot see through (differential exploration then "
                 "degrades to full re-exploration)",
        )

    def obs_flags(p):
        p.add_argument(
            "--trace-out", default=None, dest="trace_out", metavar="PATH",
            help="enable telemetry and write a Chrome/Perfetto "
                 "trace_event JSON of this run (ui.perfetto.dev)",
        )
        p.add_argument(
            "--stats-out", default=None, dest="stats_out", metavar="PATH",
            help="enable telemetry and write the metrics-registry "
                 "snapshot JSON (readable via `demi_tpu stats -i`)",
        )
        p.add_argument(
            "--journal", default=None, metavar="DIR",
            help="continuous observability: append one JSONL record per "
                 "round/chunk/level to DIR/journal.jsonl (crash-safe, "
                 "rotation-bounded; tail it with `demi_tpu top DIR`). "
                 "Runs with --checkpoint-dir journal there automatically",
        )
        p.add_argument(
            "--metrics-port", type=int, default=None, dest="metrics_port",
            metavar="PORT",
            help="enable telemetry and serve the live registry over "
                 "HTTP: Prometheus text at /metrics, snapshot JSON at "
                 "/metrics.json (0 binds an ephemeral port)",
        )

    def tune_flags(p):
        p.add_argument(
            "--autotune", action="store_true",
            help="close the measurement feedback loop: adapt fuzzer "
                 "weights / DPOR budgets / sweep shapes online from the "
                 "obs counters (DEMI_AUTOTUNE=1 does the same)",
        )

    def fork_flags(p):
        p.add_argument(
            "--prefix-fork", action="store_true", dest="prefix_fork",
            help="prefix-fork replay: snapshot device state at shared-"
                 "prefix branch points and fork lane batches instead of "
                 "re-executing prefixes (bit-identical results; "
                 "DEMI_PREFIX_FORK=1 does the same; off by default)",
        )

    def async_min_flags(p):
        p.add_argument(
            "--async-min", action="store_true", dest="async_min",
            help="async minimization pipeline: lower-once/gather-many "
                 "candidate lowering, dispatch/harvest split, and "
                 "speculative next-level dispatch into idle padded lanes "
                 "(bit-identical verdicts and MCS; DEMI_ASYNC_MIN=1 does "
                 "the same; off by default)",
        )

    def checkpoint_flags(p, default_every: int, unit: str):
        p.add_argument(
            "--checkpoint-dir", default=None, dest="checkpoint_dir",
            metavar="DIR",
            help="durable exploration state: write atomic, versioned "
                 "snapshots of the search state under DIR (SIGTERM/"
                 "SIGINT checkpoint at the next round boundary and exit "
                 "3; continue with `demi_tpu resume DIR`)",
        )
        p.add_argument(
            "--checkpoint-every", type=int, default=default_every,
            dest="checkpoint_every", metavar="N",
            help=f"snapshot every N {unit} (default {default_every}; "
                 "boundaries are generation-frozen, so a snapshot "
                 "resumes bit-identically)",
        )

    def strict_io_flags(p):
        p.add_argument(
            "--strict-io", action="store_true", dest="strict_io",
            help="launch supervisor strictness: exhausted kernel-launch "
                 "retries and native-analyzer degradations (NumPy-twin "
                 "fallbacks) raise instead of limping — the CI mode "
                 "(DEMI_STRICT_IO=1 does the same; off by default)",
        )

    def sanitize_flags(p, strict: bool = False):
        p.add_argument(
            "--sanitize", action="store_true",
            help="runtime replay sanitizer: digest messages before/after "
                 "delivery (catches in-place mutation) and trap "
                 "wall-clock/global-random calls in handlers "
                 + ("— STRICT here: a trip aborts the replay "
                    if strict else "(counts + warnings) ")
                 + "(DEMI_SANITIZE=1/strict does the same; off by default)",
        )

    p = sub.add_parser(
        "lint",
        help="determinism lint over app modules/files (default: the "
             "bundled zoo); exits 1 on error-level findings",
    )
    p.add_argument(
        "targets", nargs="*",
        help="dotted module names, files, or directories "
             "(default: demi_tpu.apps + demi_tpu.bridge.demo_app)",
    )
    p.add_argument("--format", choices=("text", "json"), default="text")
    p.set_defaults(fn=cmd_lint)

    p = sub.add_parser("fuzz", help="random fuzzing until a violation")
    common(p)
    obs_flags(p)
    tune_flags(p)
    sanitize_flags(p)
    checkpoint_flags(p, 25, "executions")
    strict_io_flags(p)
    fork_flags(p)
    async_min_flags(p)
    p.add_argument("--max-executions", type=int, default=200, dest="max_executions")
    p.add_argument("-o", "--output", default=None)
    p.add_argument(
        "--streaming", action="store_true",
        help="streaming fuzz→minimize→replay pipeline: a device fuzz "
             "sweep over --max-executions lanes whose violating lanes "
             "hand off to the gamut minimizer WHILE the sweep keeps "
             "running (one shared in-flight launch budget; "
             "time-to-first-MCS / MCSes-per-hour in the summary). Off "
             "by default; the staged fuzz-then-minimize path is the "
             "pinned bit-identical baseline (bench --config 12)",
    )
    p.add_argument(
        "--split", type=float, default=None,
        help="streaming budget split: the minimizer's share of each "
             "in-flight turn (0<split<1; default 0.5 = lane-for-lane; "
             "under --autotune the TuningCache pipeline_split axis "
             "decides)",
    )
    p.add_argument(
        "--chunk", type=int, default=None,
        help="streaming sweep chunk lanes per launch (default: "
             "max_executions/4 clamped to [8, 64])",
    )
    p.add_argument(
        "--pool", type=int, default=256,
        help="streaming device pool capacity (pending-event slots)",
    )
    p.set_defaults(fn=cmd_fuzz)

    p = sub.add_parser("minimize", help="run the minimization gamut on an experiment")
    common(p)
    obs_flags(p)
    fork_flags(p)
    async_min_flags(p)
    sanitize_flags(p)
    strict_io_flags(p)
    p.add_argument("-e", "--experiment", required=True)
    p.add_argument("--no-wildcards", action="store_true")
    p.add_argument(
        "--host", action="store_true",
        help="sequential host STS oracle instead of device-batched trials",
    )
    p.add_argument(
        "--strategy", choices=["gamut", "incddmin"], default="gamut",
        help="gamut (default) or IncrementalDDMin over a resumable DPOR oracle",
    )
    p.add_argument(
        "--max-interleavings", type=int, default=64, dest="max_interleavings",
        help="DPOR interleaving budget per incddmin probe",
    )
    p.add_argument(
        "--resume", action="store_true",
        help="restart after the last completed pipeline stage "
             "(stage checkpoints live in the experiment dir)",
    )
    p.add_argument(
        "--stage-budget", type=float, default=None, dest="stage_budget",
        metavar="SECONDS",
        help="wall-clock cap per minimizer stage (best-so-far kept, "
             "exhaustion recorded in stats; reference caps each gamut "
             "minimizer the same way)",
    )
    p.add_argument(
        "--peek", type=int, default=0, metavar="K",
        help="replay peek budget: absent expected deliveries may be "
             "enabled by delivering up to K pending entries "
             "(device kernel + host bookkeeping replay both peek)",
    )
    p.add_argument(
        "--streaming", action="store_true",
        help="drive the gamut through its streaming generator (one "
             "pipeline frame: level-stepped, journaled as pipeline.* "
             "records for `demi_tpu top`); results bit-identical to the "
             "staged drive — same code path",
    )
    p.add_argument(
        "--profile-rounds", type=int, default=0, dest="profile_rounds",
        metavar="N",
        help="launch profiler on the minimizer tier: attribute wall "
             "time per replay launch (dispatch vs harvest block, keyed "
             "by launch shape), open a jax.profiler trace window over "
             "the first N BatchedDDMin/internal levels, and persist the "
             "evidence to the tuning cache under the same "
             "profile=launch key the dpor profiler uses",
    )
    p.add_argument(
        "--profile-trace", default=None, dest="profile_trace",
        metavar="DIR",
        help="jax.profiler trace output dir for --profile-rounds "
             "(default ./demi_profile)",
    )
    p.set_defaults(fn=cmd_minimize)

    p = sub.add_parser("replay", help="strict-replay an experiment")
    common(p)
    sanitize_flags(p, strict=True)
    p.add_argument("-e", "--experiment", required=True)
    p.set_defaults(fn=cmd_replay)

    p = sub.add_parser("sweep", help="device-batched fuzz sweep")
    common(p)
    obs_flags(p)
    tune_flags(p)
    fork_flags(p)
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--pool", type=int, default=256)
    p.add_argument(
        "--sweep-mode", choices=("continuous", "chunked"), default=None,
        help="continuous (default): lane-compacted sweep with mid-flight "
             "refill; chunked: fixed whole-batch kernel launches",
    )
    p.add_argument(
        "--chunk", type=int, default=None,
        help="device batch size per launch (default: --batch)",
    )
    p.add_argument(
        "--processes", type=int, default=1,
        help=">1: CPU REHEARSAL of the multi-process jax.distributed "
             "sweep: N processes on virtual CPU devices (seed-space "
             "partition per process, summaries aggregated over the "
             "distributed runtime). It never uses a chip; the summary "
             "says platform cpu. On a multi-chip host plain `sweep` "
             "already shards over every local chip in one process",
    )
    checkpoint_flags(p, 5, "chunks")
    strict_io_flags(p)
    p.set_defaults(fn=cmd_sweep)

    p = sub.add_parser("dpor", help="systematic batched DPOR search")
    common(p)
    obs_flags(p)
    tune_flags(p)
    fork_flags(p)
    async_min_flags(p)
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--pool", type=int, default=256)
    p.add_argument("--rounds", type=int, default=10)
    p.add_argument(
        "--static-prune", action="store_true", dest="static_prune",
        help="static commutativity pruning: skip racing pairs whose flip "
             "is provably a no-op (content-identical records, or tags "
             "the AST field-effect analysis proves commuting); "
             "DEMI_STATIC_PRUNE=1 does the same; off by default",
    )
    p.add_argument(
        "--sleep-sets", action="store_true", dest="sleep_sets",
        help="sleep-set + race-reversal pruning (optimal DPOR): admitted "
             "reversals follow wakeup-sequence guides, carry device-"
             "encoded sleep rows, and dedup on Mazurkiewicz class keys "
             "so already-reversed races are not re-explored; "
             "DEMI_SLEEP_SETS=1 does the same; off by default",
    )
    checkpoint_flags(p, 5, "rounds")
    strict_io_flags(p)
    p.add_argument(
        "--profile-rounds", type=int, default=0, dest="profile_rounds",
        metavar="N",
        help="launch profiler: attribute wall time per kernel launch "
             "(trunk vs lane vs harvest, dispatch vs block, keyed by "
             "launch shape), open a jax.profiler trace window over the "
             "first N rounds, and persist the evidence to the tuning "
             "cache (profile=launch) for the launch-economy cost model",
    )
    p.add_argument(
        "--host-shards", type=int, default=0, dest="host_shards",
        metavar="N",
        help="partition the host-half admission pipeline (scan, "
             "filters, digest dedup) into N digest-range shards run "
             "concurrently, with a canonical merge that keeps results "
             "bit-identical to 1 shard; under --autotune the measured "
             "host_shards axis decides; default 1",
    )
    p.add_argument(
        "--profile-trace", default=None, dest="profile_trace",
        metavar="DIR",
        help="jax.profiler trace output dir for --profile-rounds "
             "(default ./demi_profile; load in TensorBoard/xprof)",
    )
    p.set_defaults(fn=cmd_dpor)

    p = sub.add_parser(
        "fleet",
        help="sharded exploration fleet: a coordinator assigns "
             "generation-frozen DPOR round leases to worker processes; "
             "admissions dedup globally on content digests and "
             "Mazurkiewicz class keys (coverage bit-identical to a "
             "single-process dpor run at any worker count)",
    )
    common(p)
    obs_flags(p)
    p.add_argument("--workers", type=int, default=2,
                   help="worker processes to spawn (default 2)")
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--pool", type=int, default=256)
    p.add_argument("--rounds", type=int, default=10,
                   help="frontier-round budget across the whole fleet")
    p.add_argument(
        "--class-store", default=None, dest="class_store", metavar="DIR",
        help="content-addressed class store: load prior runs' covered "
             "Mazurkiewicz classes (warm start — covered classes are "
             "never re-explored) and publish this run's ledger at exit",
    )
    p.add_argument(
        "--sleep-sets", action="store_true", dest="sleep_sets",
        help="class-dedup pruning without a store (implied by "
             "--class-store); off = observe mode, classes tracked only",
    )
    p.add_argument(
        "--delta", action="store_true",
        help="differential warm start against --class-store: diff the "
             "stored effect-signature manifest vs the current app, "
             "transfer every stored class whose delivery-tag footprint "
             "avoids the contaminated cone, re-explore only inside it "
             "(unknown effects degrade soundly to full scratch)",
    )
    p.add_argument(
        "--diff-audit", action="store_true", dest="diff_audit",
        help="after the --delta run, full-explore the same app from "
             "scratch and assert the skip set was sound (class set, "
             "violation codes, canonical witness digests bit-identical; "
             "exit 2 on mismatch). Implies --delta",
    )
    p.add_argument(
        "--stop-on-violation", action="store_true",
        dest="stop_on_violation",
        help="stop the fleet at the first violating round (default: "
             "coverage mode — drain the round budget)",
    )
    p.add_argument(
        "--devices-per-worker", type=int, default=1,
        dest="devices_per_worker", metavar="N",
        help="virtual (CPU) or local (TPU) devices per worker; >1 "
             "shards each leased round over the worker's mesh (the "
             "intra-slice ring; batch must divide by N)",
    )
    p.add_argument(
        "--serialize-leases", action="store_true", dest="serialize_leases",
        help="at most one lease in flight (uncontended per-worker "
             "timing on a shared-core host — what bench config 13 "
             "measures); default overlaps leases across workers",
    )
    p.add_argument(
        "--host-shards", type=int, default=0, dest="host_shards",
        metavar="N",
        help="digest-range shards for the coordinator's host-half "
             "admission pipeline (bit-identical at any N; default 1)",
    )
    p.add_argument(
        "--lease-timeout", type=float, default=120.0, dest="lease_timeout",
        metavar="S",
        help="revoke and re-lease a round not returned within S seconds "
             "(re-execution is bit-identical — round inputs are pure)",
    )
    p.add_argument(
        "--straggler-factor", type=float, default=4.0,
        dest="straggler_factor", metavar="K",
        help="early re-lease a round outstanding longer than K× the "
             "median completed lease wall (journaled as fleet.straggler; "
             "0 disables; re-execution is bit-identical)",
    )
    strict_io_flags(p)
    p.set_defaults(fn=cmd_fleet)

    p = sub.add_parser(
        "store",
        help="class-store maintenance: `store compact DIR` merges "
             "accumulated per-run segments into one deduped segment "
             "per workload fingerprint (long-lived stores otherwise "
             "grow one file per run forever)",
    )
    p.add_argument("action", choices=["compact"],
                   help="maintenance action")
    p.add_argument("dir",
                   help="store root (one fingerprint subdir per "
                        "workload) or a single fingerprint directory")
    p.set_defaults(fn=cmd_store)

    p = sub.add_parser(
        "serve",
        help="multi-tenant exploration service daemon: tenants submit "
             "fuzz→minimize jobs over the fleet's TCP JSON wire; the "
             "service batches many tenants' lanes into shared device "
             "launches (per-tenant results bit-identical to solo runs); "
             "SIGTERM drains — checkpoint mid-queue, exit 3 — and "
             "`serve --resume` continues with no job lost",
    )
    obs_flags(p)
    p.add_argument("--state-dir", default=None, dest="state_dir",
                   metavar="DIR",
                   help="durable tenant/job/artifact state + journal "
                        "(omit for an ephemeral in-memory service)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0,
                   help="TCP port (0 = ephemeral; the bound address is "
                        "announced as a JSON line on stdout)")
    p.add_argument("--split", type=float, default=0.5,
                   help="minimizer share of each in-flight turn "
                        "(pipeline/budget.py split knob)")
    p.add_argument("--depth", type=int, default=2,
                   help="sweep chunks kept in flight per shared group")
    p.add_argument("--chunk", type=int, default=64,
                   help="default lanes per shared sweep chunk")
    p.add_argument("--stage-budget", type=float, default=None,
                   dest="stage_budget", metavar="S",
                   help="per-minimizer-stage wall-clock cap, seconds")
    p.add_argument("--resume", action="store_true",
                   help="continue from --state-dir's newest checkpoint "
                        "(after a SIGTERM drain or a SIGKILL)")
    p.add_argument("--drain", action="store_true",
                   help="exit 0 once every submitted job is done "
                        "(default: keep serving until shutdown)")
    p.set_defaults(fn=cmd_serve)

    p = sub.add_parser(
        "submit",
        help="submit one tenant fuzz→minimize job (app spec + seed "
             "range) to a running `demi_tpu serve` daemon",
    )
    common(p)
    p.add_argument("--addr", required=True, metavar="HOST:PORT",
                   help="the daemon's announced address")
    p.add_argument("--tenant", required=True,
                   help="tenant account name (handler fingerprint pinned "
                        "on first submission)")
    p.add_argument("--pool", type=int, default=64)
    p.add_argument("--commands", type=int, default=0,
                   help="raft only: fixed program with N client commands "
                        "(the multi-violation bench shape) instead of "
                        "per-seed fuzzer programs")
    p.add_argument("--lanes", type=int, default=256,
                   help="seed range to sweep: seeds 0..lanes")
    p.add_argument("--chunk", type=int, default=None,
                   help="lanes per sweep chunk (default: the daemon's)")
    p.add_argument("--base-key", type=int, default=0, dest="base_key",
                   help="rng base key (distinct per tenant by "
                        "convention — same seeds, different schedules)")
    p.add_argument("--max-frames", type=int, default=None,
                   dest="max_frames",
                   help="minimize at most K violations (enqueue order)")
    p.add_argument("--weight", type=float, default=1.0,
                   help="fair-share weight of this tenant's account")
    p.add_argument("--no-wildcards", action="store_true",
                   dest="no_wildcards",
                   help="skip the wildcard minimization stage")
    p.set_defaults(fn=cmd_submit)

    p = sub.add_parser(
        "jobs",
        help="list/poll a serve daemon's jobs or fetch artifacts "
             "(--job ID [--fetch [--out DIR]])",
    )
    p.add_argument("--addr", required=True, metavar="HOST:PORT")
    p.add_argument("--tenant", default=None,
                   help="restrict the listing to one tenant")
    p.add_argument("--job", default=None, help="poll one job by id")
    p.add_argument("--fetch", action="store_true",
                   help="with --job: fetch the violation frames + "
                        "minimization artifacts")
    p.add_argument("--out", default=None, metavar="DIR",
                   help="with --fetch: write artifacts JSON under DIR")
    p.add_argument("--status", action="store_true",
                   help="print the service summary (tenants, queue, "
                        "shared-launch savings) instead of a job list")
    p.set_defaults(fn=cmd_jobs)

    p = sub.add_parser(
        "resume",
        help="resume a checkpointed dpor/sweep/fuzz run from its "
             "--checkpoint-dir (newest valid snapshot generation; "
             "corrupt ones fall back to the previous good one)",
    )
    p.add_argument("dir", help="the original run's --checkpoint-dir")
    p.set_defaults(fn=cmd_resume)

    p = sub.add_parser(
        "tune",
        help="calibrate sweep kernel variant/chunk for a workload "
             "(decision persisted to the tuning cache)",
    )
    common(p)
    obs_flags(p)
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--pool", type=int, default=256)
    p.add_argument(
        "--chunk", type=int, default=None,
        help="device batch size per launch to calibrate around "
             "(default: --batch)",
    )
    p.add_argument(
        "--reps", type=int, default=3,
        help="timed reps per candidate (first rep is always an extra "
             "dropped warm-up)",
    )
    p.add_argument(
        "--cache", default=None, metavar="PATH",
        help="tuning cache file (default: DEMI_TUNE_CACHE or "
             "~/.cache/demi_tpu/tune.json)",
    )
    p.add_argument(
        "--dry-run", action="store_true", dest="dry_run",
        help="print candidate axes + any cached decision without "
             "launching kernels",
    )
    p.set_defaults(fn=cmd_tune)

    p = sub.add_parser(
        "stats",
        help="print a metrics-registry snapshot (saved or live smoke run)",
    )
    common(p)
    p.add_argument(
        "-i", "--input", action="append", default=[], metavar="PATH",
        help="saved snapshot JSON (repeatable; merged and printed "
             "instead of running the smoke workload)",
    )
    p.add_argument(
        "-e", "--experiment", default=None,
        help="experiment dir whose obs_snapshot.json to print",
    )
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--pool", type=int, default=128)
    p.add_argument(
        "--max-executions", type=int, default=8, dest="max_executions",
        help="host fuzz executions in the smoke workload",
    )
    p.add_argument(
        "--prom", action="store_true",
        help="print the Prometheus text exposition instead of JSON "
             "(the format --metrics-port serves at /metrics)",
    )
    p.set_defaults(fn=cmd_stats)

    p = sub.add_parser(
        "top",
        help="live dashboard tailing a run's round journal "
             "(checkpoint dir or --journal dir); --once for one frame",
    )
    p.add_argument("dir", help="directory being journaled")
    p.add_argument(
        "--once", action="store_true",
        help="render a single frame and exit (no TTY needed)",
    )
    p.add_argument("--interval", type=float, default=1.0, metavar="SECONDS")
    p.add_argument(
        "--window", type=int, default=30, metavar="N",
        help="sliding window (records) for the rate numbers",
    )
    p.set_defaults(fn=cmd_top)

    p = sub.add_parser(
        "trace",
        help="distributed-trace tooling: `trace stitch <dirs...>` merges "
             "every process's span sidecar (spans-*.jsonl) and journal "
             "into ONE clock-aligned Perfetto timeline",
    )
    p.add_argument("action", choices=["stitch"],
                   help="stitch: merge span sidecars + journals")
    p.add_argument("dirs", nargs="+",
                   help="directories holding spans-*.jsonl sidecars "
                        "(journal records in the same dirs become "
                        "instant events)")
    p.add_argument("-o", "--output", default="trace-stitched.json",
                   help="Perfetto JSON output path "
                        "(default trace-stitched.json)")
    p.set_defaults(fn=cmd_trace)

    p = sub.add_parser("report", help="markdown report of a saved experiment")
    p.add_argument("-e", "--experiment", required=True)
    p.add_argument("-o", "--output", default=None)
    p.set_defaults(fn=cmd_report)

    p = sub.add_parser("dot", help="export an experiment as Graphviz DOT")
    common(p)
    p.add_argument("-e", "--experiment", required=True)
    p.add_argument("-o", "--output", default=None)
    p.set_defaults(fn=cmd_dot)

    p = sub.add_parser("shiviz", help="export an experiment trace for ShiViz")
    common(p)
    p.add_argument("-e", "--experiment", required=True)
    p.add_argument("-o", "--output", default=None)
    p.set_defaults(fn=cmd_shiviz)

    p = sub.add_parser(
        "bridge-fuzz",
        help="fuzz an external (bridge/adapter) app; deadlock invariant",
    )
    p.add_argument("--launcher", required=True,
                   help="shell command spawning the bridge app")
    p.add_argument("--transport", choices=("pipe", "socket"), default="pipe")
    p.add_argument("--send", action="append", default=[],
                   help="JSON message payload (repeatable)")
    p.add_argument("--to", action="append", default=[],
                   help="target actor (repeatable; default: all registered)")
    p.add_argument("--num-sends", type=int, default=3, dest="num_sends")
    p.add_argument("--wait-budget", type=int, default=60, dest="wait_budget")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--max-executions", type=int, default=50,
                   dest="max_executions")
    p.add_argument("--max-messages", type=int, default=200,
                   dest="max_messages")
    p.add_argument("--timer-weight", type=float, default=0.3,
                   dest="timer_weight")
    p.add_argument(
        "--atomic-batch", type=int, default=0, dest="atomic_batch",
        metavar="K",
        help="mark a random K-run of the generated sends as one external "
             "atomic block (all-or-nothing under minimization)",
    )
    p.add_argument(
        "--invariant", default=None, metavar="MODULE:FUNCTION",
        help="app-specific safety predicate (states dict -> violation "
             "code or None) layered on the deadlock invariant; import "
             "path resolved from PYTHONPATH",
    )
    p.set_defaults(fn=cmd_bridge_fuzz)

    p = sub.add_parser("interactive", help="hand-drive a schedule")
    common(p)
    p.set_defaults(fn=cmd_interactive)

    args = parser.parse_args(argv)
    try:
        return args.fn(args)
    finally:
        # Commands that finish normally already ran _obs_end; this
        # catches the exception exits (idempotent).
        _cleanup_continuous()


if __name__ == "__main__":
    sys.exit(main())
