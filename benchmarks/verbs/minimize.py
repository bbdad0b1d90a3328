"""The minimize verb's jobs: from a violating experiment on disk to a
host-verified MCS, as ``cli.cmd_minimize`` does it (device-batched trials,
the whole gamut, wildcards on, a fresh replay checker for every command)."""

from __future__ import annotations

import os
import shutil
import tempfile
from dataclasses import dataclass, field

from lib import spans
from lib.harness import Check
from verbs_common import build, build_native, host_config, lane_mesh

STEP_KERNEL = "jit_run_lane"
FUZZ_STRIDE = 1000  # panel entry s is fuzzed from fuzz seed 1000*s


@dataclass
class Ctx:
    cell: object
    app: object
    fuzzer: object
    config: object
    mesh: object
    devices: list
    workdir: str
    pristine: dict = field(default_factory=dict)   # sub-seed -> experiment dir
    runs: int = 0


def setup(cell, devices) -> Ctx:
    from demi_tpu.runner import fuzz
    from demi_tpu.serialization import ExperimentSerializer

    build_native()
    app, _cfg, fuzzer = build(cell)
    w = cell.config["workload"]
    ctx = Ctx(
        cell=cell, app=app, fuzzer=fuzzer, config=host_config(app),
        mesh=lane_mesh(devices), devices=list(devices),
        workdir=tempfile.mkdtemp(prefix="bench_mcs_"),
    )
    # The panel: violating executions fuzzed on the host, as the `fuzz`
    # verb finds and saves them.
    for s in cell.traffic["panel"]["seeds"]:
        found = fuzz(
            ctx.config, fuzzer,
            max_executions=cell.traffic["job"]["fuzz_executions"],
            seed=FUZZ_STRIDE * s, max_messages=w["max_messages"],
            invariant_check_interval=1, timer_weight=w["timer_weight"],
            validate_replay=True,
        )
        if found is None:
            raise RuntimeError(f"panel seed {s}: fuzzing found no violation")
        path = os.path.join(ctx.workdir, f"panel_{s}")
        ExperimentSerializer.save(
            path, found.program, found.trace, found.violation,
            app_name=w["app"],
        )
        ctx.pristine[s] = path
    return ctx


def run_job(ctx: Ctx, job) -> dict:
    from demi_tpu.device.batch_oracle import DeviceReplayChecker, default_device_config
    from demi_tpu.runner import FuzzResult, run_the_gamut
    from demi_tpu.schedulers.replay import sts_oracle
    from demi_tpu.serialization import ExperimentDeserializer, ExperimentSerializer

    ctx.runs += 1
    experiment = os.path.join(ctx.workdir, f"run_{ctx.runs}")
    shutil.copytree(ctx.pristine[job.sub_seed], experiment)
    with spans.span("bench.minimize.job"):
        with spans.span("bench.minimize.read"):
            de = ExperimentDeserializer(experiment, ctx.app)
            externals = de.get_externals()
            trace = de.get_trace(externals)
            violation = de.get_violation()
        fr = FuzzResult(
            program=externals, trace=trace, violation=violation, executions=0
        )
        with spans.span("bench.minimize.build_checker"):
            device_cfg = default_device_config(ctx.app, trace, externals)
            checker = DeviceReplayChecker(
                ctx.app, device_cfg, ctx.config, mesh=ctx.mesh
            )
        with spans.span("bench.minimize.gamut"):
            result = run_the_gamut(
                ctx.config, fr, wildcards=True, app=ctx.app, checker=checker,
                checkpoint_dir=experiment, resume=False,
            )
        with spans.span("bench.minimize.save"):
            ExperimentSerializer.save(
                experiment, externals, trace, violation,
                app_name=ctx.cell.config["workload"]["app"],
                mcs=result.mcs_externals, minimized_trace=result.final_trace,
                stats=result.stats,
            )
        with spans.span("bench.minimize.verify"):
            verified = sts_oracle(ctx.config, trace).test(
                list(result.mcs_externals), violation
            )
    shutil.rmtree(experiment, ignore_errors=True)
    replays = result.stats.total_replays
    return {
        "work": 1,
        "replays": replays,
        "externals": len(externals),
        "mcs_externals": len(result.mcs_externals),
        "deliveries": len(trace.deliveries()),
        "minimized_deliveries": len(result.final_trace.deliveries()),
        "verified": verified is not None,
        "violation": violation,
        "mcs": list(result.mcs_externals),
        "final_trace": result.final_trace,
        "trace": trace,
        "sub_seed": job.sub_seed,
        "max_steps": device_cfg.max_steps,
        "lane_steps": replays * device_cfg.max_steps,
    }


def instrument(ctx: Ctx, on: bool) -> None:
    pass  # the job's spans are always there; they cost nothing untraced


def counters(ctx: Ctx, records) -> dict:
    outs = [r.out for r in records]
    return {
        "replays": sum(o["replays"] for o in outs),
        "job_s": sum(r.seconds for r in records),
        "lane_steps": sum(o["lane_steps"] for o in outs),
        "chips": len(ctx.devices),
    }


def check(ctx: Ctx, records, warm, rng):
    """On the window's own outputs: every job's MCS verified on the host
    STS oracle, is no larger than the recorded externals, and a strict
    replay of the minimized trace with the MCS's externals reproduces the
    violation; each panel entry's MCS size and minimized length are the
    ones the traffic file states; the first job repeats its warm run's MCS size and replay count
    exactly."""
    from demi_tpu.schedulers.replay import ReplayException, ReplayScheduler

    outs = [r.out for r in records]
    unverified = sum(1 for o in outs if not o["verified"])
    oversize = sum(1 for o in outs if not 0 < o["mcs_externals"] <= o["externals"])
    unreplayed = 0
    for o in outs:
        try:
            replayed = ReplayScheduler(ctx.config).replay(o["final_trace"], o["mcs"])
        except ReplayException:
            unreplayed += 1
            continue
        if replayed.violation is None or not o["violation"].matches(replayed.violation):
            unreplayed += 1
    # The panel is fixed, so its answers are too: the traffic file
    # states each entry's MCS size and minimized length.
    expect = ctx.cell.traffic.get("expect", {})
    unexpected = sum(
        1 for o in outs
        if str(o["sub_seed"]) in expect and any(
            o[k] != v for k, v in expect[str(o["sub_seed"])].items()
        )
    )
    first, again = outs[0], warm[records[0].job.sub_seed]
    repeat = int(
        (first["mcs_externals"], first["replays"], first["minimized_deliveries"])
        != (again["mcs_externals"], again["replays"], again["minimized_deliveries"])
    )
    checks = [
        Check("minimize.mcs_unverified_on_host", unverified, 0),
        Check("minimize.mcs_larger_than_externals", oversize, 0),
        Check("minimize.strict_replays_not_reproducing", unreplayed, 0),
        Check("minimize.jobs_off_the_panels_stated_answers", unexpected, 0),
        Check("minimize.repeat_counts_differ", repeat, 0),
    ]
    failed = unverified + oversize + unreplayed + unexpected
    return checks, len(outs), failed


def close(ctx: Ctx) -> None:
    shutil.rmtree(ctx.workdir, ignore_errors=True)
