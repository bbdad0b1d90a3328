#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that demi_tpu's main path still
starts on the chip.

Drives sweep -> lift -> DPOR -> fuzz/minimize/replay once, through the
``demi_tpu.cli`` verbs a user would call (and, for the lift, the public
``demi_tpu.runner.lift_lane_to_host``), in ONE process: the process that
holds the chip. Each phase checks its answer by the repo's own means (the
host oracle is the plain reference), and any phase's failure is the
script's failure: no phase is wrapped in an ``except``.

    python chip_smoke.py                       # on a TPU, at SIZES["chip"]
    python chip_smoke.py --size tiny --expect-platform cpu   # CPU tests

It fails, before anything else, unless JAX's first device is the expected
platform. The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import shutil
import sys
import tempfile
import time

#: One table of sizes, one code path. "chip" is what the repo calls its
#: accelerator size (bench.py's headline shape): 5-node raft, pool 96, 144
#: steps, invariant checked on every delivery, 8,192 lanes per launch.
#: "tiny" is the same path cut down for the CPU tests.
SIZES = {
    "chip": dict(
        nodes=5, pool=96, steps=144, lanes=32768, chunk=8192, lifts=8,
        dpor_batch=256, dpor_rounds=8,
        min_nodes=3, min_events=12, min_steps=400, fuzz_executions=200,
        paxos_lanes=512, paxos_log_cap=8, paxos_steps=2048, paxos_pool=256,
        paxos_events=96, paxos_violating=1,
        reconfig_lanes=1024, reconfig_log_cap=32, reconfig_steps=2048,
        reconfig_pool=512, reconfig_events=96, reconfig_commits=30,
        kafka_lanes=1024, kafka_nodes=6, kafka_log_cap=24,
        kafka_steps=2048, kafka_pool=256, kafka_events=96, kafka_elections=40,
    ),
    "tiny": dict(
        nodes=3, pool=48, steps=64, lanes=64, chunk=32, lifts=2,
        dpor_batch=16, dpor_rounds=2,
        min_nodes=3, min_events=6, min_steps=96, fuzz_executions=200,
        paxos_lanes=32, paxos_log_cap=4, paxos_steps=384, paxos_pool=128,
        paxos_events=24, paxos_violating=0,
        reconfig_lanes=32, reconfig_log_cap=8, reconfig_steps=256,
        reconfig_pool=128, reconfig_events=48, reconfig_commits=3,
        kafka_lanes=32, kafka_nodes=5, kafka_log_cap=8,
        kafka_steps=256, kafka_pool=128, kafka_events=48, kafka_elections=6,
    ),
}

class _Tee(io.TextIOBase):
    """Standard output that also keeps what a verb printed, so its JSON
    summary can be read back."""

    def __init__(self, stream):
        self.stream = stream
        self.kept = io.StringIO()

    def write(self, text):
        self.kept.write(text)
        return self.stream.write(text)

    def flush(self):
        self.stream.flush()


class Smoke:
    def __init__(self, size: dict, device: dict):
        import demi_tpu.device  # noqa: F401  (the compile ledger listens from here)

        self.size = size
        self.device = device
        self.phases: dict = {}

    # -- measurement -------------------------------------------------------
    def _peak_bytes(self):
        import jax

        peaks = [
            (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.local_devices()
        ]
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None

    @contextlib.contextmanager
    def phase(self, name: str):
        """Time one phase: wall seconds, compile seconds apart from run
        seconds (JAX's own trace/lower/compile duration events), the
        persistent cache's hits and misses, and the peak device memory so
        far. The compile numbers are the program's own ledger's
        (``demi_tpu.obs.compile_ledger``: one listener in the process),
        whose ``covered_s`` counts a function traced inside another's
        tracing once, so it cannot pass the wall's. The record is kept
        only if the body did not raise."""
        from demi_tpu.obs import compile_ledger

        before = compile_ledger()["total"]
        record: dict = {}
        t0 = time.perf_counter()
        yield record
        wall = time.perf_counter() - t0
        total = compile_ledger()["total"]
        took = {k: total[k] - before[k] for k in total}
        record.update(
            wall_s=round(wall, 3),
            compile_s=round(took["covered_s"], 3),
            backend_compile_s=round(
                took["compile_s"] + took["cache_load_s"], 3
            ),
            run_s=round(wall - took["covered_s"], 3),
            cache_hits=took["cache_hits"],
            cache_misses=took["cache_misses"],
            peak_bytes_in_use=self._peak_bytes(),
        )
        self.phases[name] = record
        print(f"[smoke] {name}: {json.dumps(record)}", flush=True)

    # -- verbs -------------------------------------------------------------
    def verb(self, argv, want_rc=0):
        """Run one ``demi_tpu`` verb in this process and return the JSON
        summary it printed (None for verbs that print none)."""
        from demi_tpu.cli import main

        print(f"[smoke] $ demi_tpu {' '.join(argv)}", flush=True)
        tee = _Tee(sys.stdout)
        with contextlib.redirect_stdout(tee):
            rc = main(list(argv))
        if rc != want_rc:
            raise RuntimeError(
                f"demi_tpu {argv[0]} exited {rc}, expected {want_rc}"
            )
        summary = None
        for line in tee.kept.getvalue().splitlines():
            if line.startswith("{"):
                summary = json.loads(line)
        return summary

    def check_device(self, summary: dict, lanes_per_launch=None) -> None:
        """Hold a verb's summary to this process's device, and to every
        local device when there are several."""
        want = (
            self.device["platform"], self.device["kind"],
            self.device["count"],
        )
        got = (
            summary["platform"], summary["device_kind"], summary["devices"]
        )
        check(got == want, f"summary names device {got}, smoke holds {want}")
        if lanes_per_launch is not None and not (
            lanes_per_launch % self.device["count"]
        ):
            sharding = summary["lane_sharding"]
            check(
                sharding["devices"] == self.device["count"],
                f"lanes span {sharding['devices']} device(s), "
                f"{self.device['count']} present",
            )
            check(
                sharding["lanes_per_device"] * sharding["devices"]
                >= lanes_per_launch,
                f"sharding {sharding} does not hold {lanes_per_launch} lanes",
            )


def check(ok: bool, message: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke: {message}")


def _raft(nodes: int, bug=True) -> list:
    return ["--app", "raft", "--nodes", str(nodes)] + (
        ["--bug", "multivote"] if bug else []
    )


def _sweep_workload(z: dict) -> dict:
    """The sweep verb's workload (its flag defaults spelled out), for the
    shared builder the multi-process verbs use."""
    return {
        "app": "raft", "nodes": z["nodes"], "bug": "multivote", "seed": 0,
        "num_events": 12, "max_messages": z["steps"], "pool": z["pool"],
        "timer_weight": 0.2, "kill_weight": 0.05, "partition_weight": 0.0,
    }


def phase_native(smoke: Smoke) -> None:
    """Build both native libraries from native/*.cpp, here, so a machine
    without a compiler is seen and not guessed."""
    from demi_tpu.native.build import build_library, native_source

    with smoke.phase("native") as rec:
        for src, stem in (
            ("trace_analysis.cpp", "libdemi_analysis"),
            ("record_codec.cpp", "libdemi_records"),
        ):
            so = build_library(native_source(src), stem, rebuild=True)
            rec[stem] = "built" if so is not None else "no compiler"


def phase_sweep(smoke: Smoke) -> dict:
    """BASELINE config 1/5 shape: the fuzz sweep, in the default
    (continuous) mode and as chunked whole-batch launches over the same
    seeds. The two modes must agree lane for lane."""
    z = smoke.size
    base = ["sweep"] + _raft(z["nodes"]) + [
        "--batch", str(z["lanes"]), "--chunk", str(z["chunk"]),
        "--pool", str(z["pool"]), "--max-messages", str(z["steps"]),
        "--strict-io",
    ]
    summaries = {}
    for name, extra in (
        ("sweep_continuous", []),
        ("sweep_chunked", ["--sweep-mode", "chunked"]),
        # The same kernels built a second time in the same process: what
        # the persistent compile cache gives back.
        ("sweep_chunked_warm", ["--sweep-mode", "chunked"]),
    ):
        with smoke.phase(name) as rec:
            s = smoke.verb(base + extra)
            smoke.check_device(s, lanes_per_launch=z["chunk"])
            check(s["lanes"] == z["lanes"], f"{name}: {s['lanes']} lanes")
            check(
                s["overflow_lanes"] == 0,
                f"{name}: {s['overflow_lanes']} overflow lanes (pool too small)",
            )
            check(s["violations"] > 0, f"{name}: no violation found")
            check(s["unique_schedules"] > 0, f"{name}: no schedules counted")
            rec.update(
                lanes=s["lanes"], lanes_per_launch=z["chunk"],
                unique_schedules=s["unique_schedules"],
                violations=s["violations"], overflow_lanes=0,
                lanes_digest=s["lanes_digest"],
                lane_sharding=s.get("lane_sharding"),
                verb_schedules_per_sec=s["schedules_per_sec"],
            )
        summaries[name] = s
    first = summaries["sweep_continuous"]
    for name, s in summaries.items():
        for key in ("lanes_digest", "unique_schedules", "violations", "codes"):
            check(
                s[key] == first[key],
                f"{name} disagrees with sweep_continuous on {key}: "
                f"{s[key]} vs {first[key]}",
            )
    return first


def _cache_configured() -> bool:
    import jax

    return bool(jax.config.jax_compilation_cache_dir)


def check_warm_compile(smoke: Smoke) -> None:
    """A cache that never hits must be visible: where a persistent cache
    is configured, the second build of the same kernels loads them."""
    cold = smoke.phases["sweep_chunked"]
    warm = smoke.phases["sweep_chunked_warm"]
    print(
        f"[smoke] compile seconds cold {cold['backend_compile_s']} "
        f"warm {warm['backend_compile_s']} (persistent cache "
        f"{'on' if _cache_configured() else 'off'})",
        flush=True,
    )
    if _cache_configured():
        check(
            warm["cache_hits"] > 0 and warm["cache_misses"] == 0,
            f"persistent compile cache did not hit on the second build: {warm}",
        )


def phase_lift(smoke: Smoke, sweep_summary: dict) -> None:
    """Violating lanes of that sweep, re-run traced on the device and
    lifted through GuidedScheduler to the host oracle: each must
    reproduce the same violation code. This is the check that the branch
    the device takes computes what the host tier computes; a
    GuideDivergence propagates."""
    import jax
    import numpy as np

    from demi_tpu.apps.common import make_host_invariant
    from demi_tpu.config import SchedulerConfig
    from demi_tpu.device.encoding import lower_program, stack_programs
    from demi_tpu.parallel.distributed import build_workload
    from demi_tpu.runner import lift_lane_to_host

    z = smoke.size
    with smoke.phase("lift") as rec:
        picked = sweep_summary["violating_seeds"][: z["lifts"]]
        check(
            len(picked) >= z["lifts"],
            f"sweep named {len(picked)} violating lanes, need {z['lifts']}",
        )
        # The sweep verb's own workload builder and per-lane key scheme.
        app, cfg, fuzzer = build_workload(_sweep_workload(z))
        seeds = np.asarray([s for s, _ in picked], np.uint32)
        progs = stack_programs([
            lower_program(app, cfg, fuzzer.generate_fuzz_test(seed=int(s)))
            for s in seeds
        ])
        keys = jax.vmap(
            lambda s: jax.random.fold_in(jax.random.PRNGKey(0), s)
        )(seeds)
        config = SchedulerConfig(invariant_check=make_host_invariant(app))
        for lane, (seed, code) in enumerate(picked):
            single, host = lift_lane_to_host(
                app, cfg, progs, keys, lane, config
            )
            check(
                int(single.violation) == code,
                f"seed {seed}: traced re-run gave code "
                f"{int(single.violation)}, sweep gave {code}",
            )
            check(
                host.violation is not None and host.violation.code == code,
                f"seed {seed}: host oracle gave {host.violation}, "
                f"device gave code {code}",
            )
        rec.update(lanes_lifted=len(picked), host_agrees=True)


def _paxos_workload(z: dict) -> dict:
    """The datagram sweep's workload: Multi-Paxos with its seeded bug over
    a network that re-delivers and loses messages."""
    return {
        "app": "paxos", "nodes": 11, "bug": "count_replies", "seed": 0,
        "log_cap": z["paxos_log_cap"], "num_events": z["paxos_events"],
        "max_messages": z["paxos_steps"], "pool": z["paxos_pool"],
        "timer_weight": 0.2, "send_weight": 0.6, "wait_weight": 0.28,
        "hard_kill_weight": 0.12, "kill_weight": 0.0,
        "partition_weight": 0.0, "max_kills": 4, "wait_budget": [1, 40],
        "dup_weight": 0.25, "drop_weight": 0.02, "max_dups": 256,
        "max_drops": 16,
    }


def phase_datagram(smoke: Smoke) -> None:
    """The datagram discipline outside the benchmark: one sweep of
    Multi-Paxos (``--app paxos``, ``count_replies``) through the CLI's
    normal path with ``--dup-weight`` and ``--drop-weight``; at the chip
    size it holds both verdicts. Violating and clean lanes are re-run
    traced and lifted: the host oracle follows the kept and discarded
    deliveries to the same code and the same ``sched_hash``."""
    import jax
    import numpy as np

    from demi_tpu.device.core import REC_DISCARDED, REC_KEPT
    from demi_tpu.device.encoding import (
        host_sched_hash, lower_program, stack_programs,
    )
    from demi_tpu.parallel.distributed import build_workload
    from demi_tpu.runner import lift_lane_to_host

    z = smoke.size
    w = _paxos_workload(z)
    with smoke.phase("datagram_sweep") as rec:
        s = smoke.verb([
            "sweep", "--app", "paxos", "--nodes", "11",
            "--bug", "count_replies", "--log-cap", str(w["log_cap"]),
            "--batch", str(z["paxos_lanes"]), "--pool", str(w["pool"]),
            "--max-messages", str(w["max_messages"]),
            "--num-events", str(w["num_events"]),
            "--send-weight", "0.6", "--wait-weight", "0.28",
            "--hard-kill-weight", "0.12", "--kill-weight", "0",
            "--max-kills", "4", "--wait-budget", "1", "40",
            "--dup-weight", "0.25", "--drop-weight", "0.02",
            "--max-dups", "256", "--max-drops", "16", "--strict-io",
        ])
        smoke.check_device(s)
        check(s["lanes"] == z["paxos_lanes"], f"datagram: {s['lanes']} lanes")
        check(s["overflow_lanes"] == 0, "datagram: overflow lanes")
        check(
            z["paxos_violating"] <= s["violations"] < s["lanes"],
            f"datagram: {s['violations']} violating lanes of {s['lanes']}: "
            "the sweep should hold both verdicts",
        )
        violating = dict(s["violating_seeds"])
        picked = sorted(violating)[:2]
        picked += [x for x in range(z["paxos_lanes"]) if x not in violating][:2]
        app, cfg, fuzzer = build_workload(w)
        seeds = np.asarray(picked, np.uint32)
        progs = stack_programs([
            lower_program(app, cfg, fuzzer.generate_fuzz_test(seed=int(x)))
            for x in seeds
        ])
        keys = jax.vmap(
            lambda x: jax.random.fold_in(jax.random.PRNGKey(0), x)
        )(seeds)
        kept = dropped = 0
        for lane, seed in enumerate(picked):
            single, host = lift_lane_to_host(app, cfg, progs, keys, lane)
            code = violating.get(seed, 0)
            host_code = host.violation.code if host.violation else 0
            check(
                int(single.violation) == host_code == code,
                f"datagram seed {seed}: sweep {code}, traced "
                f"{int(single.violation)}, host {host_code}",
            )
            check(
                host_sched_hash(app, host.trace) == int(single.sched_hash),
                f"datagram seed {seed}: the host delivered another sequence",
            )
            kinds = np.asarray(single.trace)[: int(single.trace_len), 0]
            kept += int((kinds == REC_KEPT).sum())
            dropped += int((kinds == REC_DISCARDED).sum())
        check(kept > 0, "datagram: no lifted lane kept a delivery")
        rec.update(
            lanes=s["lanes"], violations=s["violations"],
            lanes_lifted=len(picked), kept=kept, discarded=dropped,
            host_agrees=True, lanes_digest=s["lanes_digest"],
        )


def _lifted_rows(w: dict, picked, violating: dict, label: str):
    """``lift_lane_to_host``'s ritual on the seeds ``picked`` of workload
    ``w``, keeping the host's actors: each lane is re-run traced and
    replayed on the host oracle, which must give the sweep's code and the
    traced lane's deliveries; yields the host's final rows, ``[actors, S]``."""
    import jax
    import numpy as np

    from demi_tpu.apps.common import make_host_invariant
    from demi_tpu.config import SchedulerConfig
    from demi_tpu.device.encoding import (
        device_trace_to_guide, lower_program, stack_programs,
    )
    from demi_tpu.device.explore import make_single_lane_trace_kernel
    from demi_tpu.parallel.distributed import build_workload
    from demi_tpu.schedulers.guided import GuidedScheduler

    app, cfg, fuzzer = build_workload(w)
    seeds = np.asarray(picked, np.uint32)
    progs = stack_programs([
        lower_program(app, cfg, fuzzer.generate_fuzz_test(seed=int(x)))
        for x in seeds
    ])
    keys = jax.vmap(
        lambda x: jax.random.fold_in(jax.random.PRNGKey(0), x)
    )(seeds)
    kernel = make_single_lane_trace_kernel(app, cfg)
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    for lane, seed in enumerate(picked):
        single = kernel(
            jax.tree_util.tree_map(lambda x: x[lane], progs), keys[lane]
        )
        sched = GuidedScheduler(config, app)
        host = sched.execute_guide(device_trace_to_guide(
            app, np.asarray(single.trace), int(single.trace_len)
        ))
        code = violating.get(seed, 0)
        host_code = host.violation.code if host.violation else 0
        check(
            int(single.violation) == host_code == code,
            f"{label} seed {seed}: sweep {code}, traced "
            f"{int(single.violation)}, host {host_code}",
        )
        check(
            int(single.deliveries) == host.deliveries,
            f"{label} seed {seed}: the host delivered another sequence",
        )
        yield np.stack([
            np.asarray(sched.system.actors[app.actor_name(i)].state)
            for i in range(app.num_actors)
            if app.actor_name(i) in sched.system.actors
        ])


def _fault_plane_flags(w: dict) -> list:
    """A workload's mix as the CLI's flags."""
    return [
        "--pool", str(w["pool"]), "--max-messages", str(w["max_messages"]),
        "--num-events", str(w["num_events"]),
        "--timer-weight", str(w["timer_weight"]),
        "--send-weight", str(w["send_weight"]),
        "--wait-weight", str(w["wait_weight"]),
        "--hard-kill-weight", str(w["hard_kill_weight"]),
        "--restart-weight", str(w["restart_weight"]),
        "--partition-weight", str(w["partition_weight"]),
        "--kill-weight", "0", "--max-kills", str(w["max_kills"]),
        "--wait-budget", *map(str, w["wait_budget"]), "--strict-io",
    ]


def phase_reconfig(smoke: Smoke) -> None:
    """Row-scale ``DSLApp.durable`` outside the benchmark: one sweep of the
    reconfiguring raft (``--app raft_reconfig``, ``snapshot_keeps_config``)
    through the CLI's normal path under crash-recovery and partitions. A
    violating lane where there is one, and clean ones, are re-run traced and
    lifted: the host oracle restarts servers from their durable rows to the
    same code, the same ``sched_hash`` and the same final rows, which hold
    compactions, installed snapshots and restarts."""
    from demi_tpu.apps import raft_reconfig as rr

    z = smoke.size
    w = {
        "app": "raft_reconfig", "nodes": 7, "bug": "snapshot_keeps_config",
        "seed": 0, "log_cap": z["reconfig_log_cap"],
        "snapshot_every": z["reconfig_log_cap"] // 2,
        "num_events": z["reconfig_events"],
        "max_messages": z["reconfig_steps"], "pool": z["reconfig_pool"],
        "timer_weight": 0.1, "send_weight": 0.5, "wait_weight": 0.28,
        "hard_kill_weight": 0.08, "restart_weight": 0.1,
        "partition_weight": 0.04, "kill_weight": 0.0, "max_kills": 4,
        "wait_budget": [1, 40],
    }
    with smoke.phase("reconfig_sweep") as rec:
        s = smoke.verb([
            "sweep", "--app", "raft_reconfig", "--nodes", "7",
            "--bug", "snapshot_keeps_config",
            "--log-cap", str(w["log_cap"]),
            "--snapshot-every", str(w["snapshot_every"]),
            "--batch", str(z["reconfig_lanes"]),
        ] + _fault_plane_flags(w))
        smoke.check_device(s)
        check(s["lanes"] == z["reconfig_lanes"], f"reconfig: {s['lanes']} lanes")
        check(s["overflow_lanes"] == 0, "reconfig: overflow lanes")
        check(s["violations"] < s["lanes"] // 8,
              f"reconfig: {s['violations']} violating lanes of {s['lanes']}")
        violating = dict(s["violating_seeds"])
        picked = sorted(violating)[:1]
        picked += [x for x in range(z["reconfig_lanes"]) if x not in violating][:3]
        done = dict.fromkeys(
            ("COMMIT", "COMPACTIONS", "SNAP_INSTALLED", "RESTORES"), 0
        )
        for rows in _lifted_rows(w, picked, violating, "reconfig"):
            done["COMMIT"] = max(done["COMMIT"], int(rows[:, rr.COMMIT].max()))
            for name in ("COMPACTIONS", "SNAP_INSTALLED"):
                done[name] += int(rows[:, getattr(rr, name)].sum())
            done["RESTORES"] += int((rows[:, rr.RESTORES] - 1).clip(min=0).sum())
        check(done["COMMIT"] >= z["reconfig_commits"],
              f"reconfig: the lifted lanes commit {done['COMMIT']} at most")
        check(done["COMPACTIONS"] > 0 and done["RESTORES"] > 0,
              f"reconfig: the lifted lanes did {done}")
        rec.update(
            lanes=s["lanes"], violations=s["violations"],
            lanes_lifted=len(picked), host_agrees=True,
            lanes_digest=s["lanes_digest"],
            **{k.lower(): v for k, v in done.items()},
        )


def phase_kafka(smoke: Smoke) -> None:
    """Per-pair FIFO links under hard kills, restarts and cuts, and a row
    with a partition axis, outside the benchmark: one sweep of Kafka's
    partition replication (``--app kafka``, ``truncate_to_hw``) through the
    CLI's normal path. A violating lane where there is one, and clean ones,
    are re-run traced and lifted: the host oracle, which refuses a delivery
    that is not its channel's oldest, gives the same code, the same
    deliveries and final rows that hold elections, ISR changes, fenced
    fetches and restarts from the durable rows."""
    from demi_tpu.apps import kafka as kf

    z = smoke.size
    nodes = z["kafka_nodes"]
    w = {
        "app": "kafka", "nodes": nodes, "bug": "truncate_to_hw", "seed": 0, "log_cap": z["kafka_log_cap"],
        "num_events": z["kafka_events"], "max_messages": z["kafka_steps"],
        "pool": z["kafka_pool"], "timer_weight": 0.3, "send_weight": 0.4,
        "wait_weight": 0.28, "hard_kill_weight": 0.08,
        "restart_weight": 0.12, "partition_weight": 0.04, "kill_weight": 0.0,
        "max_kills": 4, "wait_budget": [1, 40],
    }
    lay = kf.state_layout(nodes, w["log_cap"])
    with smoke.phase("kafka_sweep") as rec:
        s = smoke.verb([
            "sweep", "--app", "kafka", "--nodes", str(nodes),
            "--bug", "truncate_to_hw",
            "--log-cap", str(w["log_cap"]), "--batch", str(z["kafka_lanes"]),
        ] + _fault_plane_flags(w))
        smoke.check_device(s)
        check(s["lanes"] == z["kafka_lanes"], f"kafka: {s['lanes']} lanes")
        check(s["overflow_lanes"] == 0, "kafka: overflow lanes")
        check(s["violations"] < s["lanes"] // 8,
              f"kafka: {s['violations']} violating lanes of {s['lanes']}")
        violating = dict(s["violating_seeds"])
        picked = sorted(violating)[:1]
        picked += [x for x in range(z["kafka_lanes"]) if x not in violating][:3]
        done = dict.fromkeys(("ELECTED", "ISR_SHRUNK", "FENCED", "ACKED"), 0)
        restores = 0
        for rows in _lifted_rows(w, picked, violating, "kafka"):
            brokers = rows[: nodes - 1]
            for name in done:
                start, length = lay[name]
                done[name] += int(brokers[:, start : start + length].sum())
            restores += int((brokers[:, kf.RESTORES] - 1).clip(min=0).sum())
        check(done["ELECTED"] >= z["kafka_elections"],
              f"kafka: the lifted lanes elect {done['ELECTED']} times")
        check(done["FENCED"] > 0 and restores > 0,
              f"kafka: the lifted lanes did {done}, {restores} restarts")
        rec.update(
            lanes=s["lanes"], violations=s["violations"],
            lanes_lifted=len(picked), host_agrees=True,
            lanes_digest=s["lanes_digest"], restores=restores,
            **{k.lower(): v for k, v in done.items()},
        )


def phase_dpor(smoke: Smoke) -> None:
    """BASELINE config 2 shape. First the full round budget on the
    correct protocol (a violating run stops at its first hit, so this is
    the run that drives prescribed frontier rounds and the racing scan),
    then the seeded bug, whose found interleaving the verb re-executes on
    the host oracle."""
    z = smoke.size
    common = [
        "--batch", str(z["dpor_batch"]), "--rounds", str(z["dpor_rounds"]),
        "--pool", str(z["pool"]), "--max-messages", str(z["steps"]),
        "--strict-io",
    ]
    with smoke.phase("dpor_rounds") as rec:
        s = smoke.verb(["dpor"] + _raft(z["nodes"], bug=False) + common, 1)
        smoke.check_device(s, lanes_per_launch=z["dpor_batch"])
        check(not s["violation_found"], "correct raft reported a violation")
        check(
            s["interleavings"] == z["dpor_batch"] * z["dpor_rounds"],
            f"ran {s['interleavings']} interleavings, budget was "
            f"{z['dpor_batch']} x {z['dpor_rounds']}",
        )
        rec.update(
            interleavings=s["interleavings"], racing_scan=s["racing_scan"],
            lane_sharding=s.get("lane_sharding"),
        )
    with smoke.phase("dpor_find") as rec:
        s = smoke.verb(["dpor"] + _raft(z["nodes"]) + common)
        smoke.check_device(s, lanes_per_launch=z["dpor_batch"])
        check(
            s["violation_found"] and s["host_verified"],
            "DPOR did not find a host-verified violation",
        )
        rec.update(
            interleavings=s["interleavings"], deliveries=s["deliveries"],
            host_verified=True, racing_scan=s["racing_scan"],
        )


def phase_minimize(smoke: Smoke, workdir: str) -> None:
    """BASELINE config 3 shape: fuzz -> minimize (device-batched trials)
    -> strict replay on the seeded 3-node raft bug."""
    z = smoke.size
    app = _raft(z["min_nodes"]) + [
        "--num-events", str(z["min_events"]),
        "--max-messages", str(z["min_steps"]),
    ]
    with smoke.phase("fuzz"):
        smoke.verb(
            ["fuzz"] + app + [
                "--max-executions", str(z["fuzz_executions"]),
                "-o", workdir, "--strict-io",
            ]
        )
    with smoke.phase("minimize") as rec:
        s = smoke.verb(["minimize"] + app + ["-e", workdir, "--strict-io"])
        smoke.check_device(s)
        if smoke.device["count"] > 1:
            check(
                s["lane_sharding"]["devices"] == smoke.device["count"],
                f"minimize trials span {s['lane_sharding']}",
            )
        check(s["oracle"] == "device", "minimize did not use device trials")
        check(s["mcs_verified"], "the MCS does not verify on the host oracle")
        check(
            0 < s["mcs_externals"] <= s["externals"],
            f"MCS of {s['mcs_externals']} from {s['externals']} externals",
        )
        rec.update(
            externals=s["externals"], mcs_externals=s["mcs_externals"],
            deliveries=s["deliveries"],
            minimized_deliveries=s["minimized_deliveries"],
            replays=s["replays"], mcs_verified=True,
            lane_sharding=s.get("lane_sharding"),
        )
    with smoke.phase("replay"):
        # rc 0 == the strict replay reproduced the violation.
        smoke.verb(["replay"] + app + ["-e", workdir])


def phase_mesh_parity(smoke: Smoke) -> None:
    """With several devices: the same seeds through the lane-sharded
    kernel and through the one-device kernel give the same status,
    violation and sched_hash for every lane."""
    from demi_tpu.parallel.distributed import build_workload
    from demi_tpu.parallel.sweep import SweepDriver

    z = smoke.size
    with smoke.phase("mesh_parity") as rec:
        app, cfg, fuzzer = build_workload(_sweep_workload(z))
        gen = lambda s: fuzzer.generate_fuzz_test(seed=s)  # noqa: E731
        seeds = range(z["chunk"])
        one = SweepDriver(app, cfg, gen).run_chunk(seeds)
        many = SweepDriver(app, cfg, gen, use_mesh=True).run_chunk(seeds)
        check(
            one.lane_sharding["devices"] == 1
            and many.lane_sharding["devices"] == smoke.device["count"],
            f"layouts {one.lane_sharding} / {many.lane_sharding}",
        )
        check(
            one.lanes_digest == many.lanes_digest
            and one.violations == many.violations,
            "lane-sharded results differ from the one-device results",
        )
        rec.update(
            lanes=z["chunk"], one_device=one.lane_sharding,
            all_devices=many.lane_sharding,
            lanes_digest=f"{many.lanes_digest:016x}",
        )


def run(size: dict, device: dict) -> dict:
    smoke = Smoke(size, device)
    workdir = tempfile.mkdtemp(prefix="demi_smoke_")
    try:
        phase_native(smoke)
        sweep_summary = phase_sweep(smoke)
        check_warm_compile(smoke)
        phase_lift(smoke, sweep_summary)
        phase_datagram(smoke)
        phase_reconfig(smoke)
        phase_kafka(smoke)
        phase_dpor(smoke)
        phase_minimize(smoke, workdir)
        if device["count"] > 1:
            phase_mesh_parity(smoke)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    return smoke.phases


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--size", choices=sorted(SIZES), default="chip")
    parser.add_argument(
        "--expect-platform", default="tpu", dest="expect_platform",
        help="fail unless jax.devices()[0].platform is this (default tpu)",
    )
    args = parser.parse_args(argv)

    # The device, before anything else.
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    print(
        f"[smoke] device: platform={device['platform']} "
        f"device_kind={device['kind']!r} count={device['count']}",
        flush=True,
    )
    if device["platform"] != args.expect_platform:
        print(
            f"chip_smoke: expected a {args.expect_platform!r} device, JAX "
            f"found {device['platform']!r}; nothing was run",
            file=sys.stderr,
        )
        return 2
    t0 = time.perf_counter()
    phases = run(SIZES[args.size], device)
    print(json.dumps({
        "smoke": {
            "size": args.size, "device": device,
            "wall_s": round(time.perf_counter() - t0, 3), "phases": phases,
        },
        "claim": None,
    }))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
