"""The sweep verb's jobs: one continuous `sweep` of fresh fuzzed schedules
through ``SweepDriver`` (lane-sharded over the cell's chips when there are
several), as ``cli.cmd_sweep`` drives it."""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from lib import spans
from lib.harness import Check
from verbs_common import build, build_native, host_config, lane_mesh

STEP_KERNEL = "jit_seg_lane"
LANE_SHIFT = 20  # lane s of a job is fuzz seed (sub_seed << 20) + s


@dataclass
class Ctx:
    cell: object
    app: object
    cfg: object
    fuzzer: object
    driver: object
    resident: int
    devices: list
    base: int = 0                              # the running job's fuzz-seed base
    vio: list = field(default_factory=list)    # its violating (seeds, codes)


def setup(cell, devices) -> Ctx:
    from demi_tpu.parallel.sweep import SweepDriver

    build_native()
    app, cfg, fuzzer = build(cell)
    mesh = lane_mesh(devices)
    ctx = Ctx(
        cell=cell, app=app, cfg=cfg, fuzzer=fuzzer, driver=None,
        resident=cell.traffic["job"]["resident_lanes_per_chip"] * len(devices),
        devices=list(devices),
    )
    ctx.driver = SweepDriver(
        app, cfg, lambda s: fuzzer.generate_fuzz_test(seed=ctx.base + s),
        mesh=mesh, use_mesh=mesh is not None,
    )
    ctx.driver.violation_hook = lambda seeds, codes: ctx.vio.append(
        (np.array(seeds), np.array(codes))
    )
    return ctx


def _continuous(ctx: Ctx):
    # The driver's one continuous-driver constructor; cached per batch,
    # so this is the instance the sweep itself runs.
    return ctx.driver._continuous_driver(ctx.resident)


def run_job(ctx: Ctx, job) -> dict:
    ctx.base = job.sub_seed << LANE_SHIFT
    ctx.vio = []
    host0, dev0 = ctx.driver.host_seconds, ctx.driver.device_seconds
    with spans.span("bench.sweep.job"):
        result = ctx.driver.sweep(
            ctx.cell.traffic["job"]["schedules"], ctx.resident,
            mode=ctx.cell.traffic["job"]["mode"],
        )
    seeds = np.concatenate([s for s, _ in ctx.vio]) if ctx.vio else np.zeros(0, np.int64)
    codes = np.concatenate([c for _, c in ctx.vio]) if ctx.vio else np.zeros(0, np.int64)
    return {
        "work": result.lanes,
        "base": ctx.base,
        "digest": result.lanes_digest,
        "overflow": result.overflow_lanes,
        "violations": result.violations,
        "unique_hashes": result.chunks[0].unique_hashes,
        "vio_seeds": seeds,
        "vio_codes": codes,
        "host_s": ctx.driver.host_seconds - host0,
        "device_s": ctx.driver.device_seconds - dev0,
        "lane_steps": _continuous(ctx).last_total_lane_steps,
        "lane_sharding": result.lane_sharding,
    }


_SPANNED = ("segment", "refill", "init", "finalize")


def instrument(ctx: Ctx, on: bool) -> None:
    drv = _continuous(ctx)
    if not on:
        spans.unwrap(drv, "_run_batches", *_SPANNED)
        return
    spans.wrap_generator(drv, "_run_batches", "bench.sweep.harvest_round")
    for attr in _SPANNED:
        spans.wrap(drv, attr, f"bench.sweep.{attr}_dispatch")


def counters(ctx: Ctx, records) -> dict:
    outs = [r.out for r in records]
    return {
        "host_s": sum(o["host_s"] for o in outs),
        "device_s": sum(o["device_s"] for o in outs),
        "lane_steps": sum(o["lane_steps"] for o in outs),
        "seg_steps": _continuous(ctx).seg_steps,
        "chips": len(ctx.devices),
    }


def check(ctx: Ctx, records, warm, rng):
    """On the window's own outputs: no lane overflowed; the first job's
    digest repeats its warm run; a seeded sample of the last job's lanes,
    violating and clean, re-run traced on one device and lifted to the
    host oracle, agrees code for code and each delivered sequence is one
    the window counted; on several chips a one-chip chunk of the same
    seeds delivers only sequences the sharded job delivered."""
    import jax
    from demi_tpu.device.encoding import lower_program, stack_programs
    from demi_tpu.runner import lift_lane_to_host
    from demi_tpu.schedulers.guided import GuideDivergence

    outs = [r.out for r in records]
    attempted = sum(o["work"] for o in outs)
    overflow = sum(o["overflow"] for o in outs)
    first = outs[0]
    repeat = int(first["digest"] != warm[records[0].job.sub_seed]["digest"])

    last = outs[-1]
    want = ctx.cell.traffic["check"]
    n = ctx.cell.traffic["job"]["schedules"]
    code_of = dict(zip(last["vio_seeds"].tolist(), last["vio_codes"].tolist()))
    violating = sorted(code_of)
    picked = rng.sample(violating, min(want["lift_violating"], len(violating)))
    clean = []
    while len(clean) < want["lift_clean"]:
        s = rng.randrange(n)
        if s not in code_of and s not in clean:
            clean.append(s)
    picked += clean
    ctx.base = last["base"]
    gen = ctx.driver.program_gen
    progs = stack_programs(
        [lower_program(ctx.app, ctx.cfg, gen(s)) for s in picked]
    )
    keys = jax.vmap(lambda s: jax.random.fold_in(jax.random.PRNGKey(0), s))(
        np.asarray(picked, np.uint32)
    )
    config = host_config(ctx.app)
    known = set(last["unique_hashes"].tolist())
    disagree = 0
    for lane, s in enumerate(picked):
        code = code_of.get(s, 0)
        try:
            single, host = lift_lane_to_host(
                ctx.app, ctx.cfg, progs, keys, lane, config
            )
        except GuideDivergence:
            disagree += 1
            continue
        host_code = host.violation.code if host.violation is not None else 0
        if (
            int(single.violation) != code
            or host_code != code
            or int(single.sched_hash) not in known
        ):
            disagree += 1
    checks = [
        Check("sweep.overflow_lanes", overflow, 0),
        Check("sweep.repeat_digest_differs", repeat, 0),
        Check("sweep.lifted_lanes_disagreeing", disagree, 0),
    ]
    failed = overflow + disagree
    if len(ctx.devices) > 1:
        from demi_tpu.parallel.sweep import SweepDriver

        width = want["one_chip_slice"]
        start = rng.randrange(n - width)
        chunk = SweepDriver(ctx.app, ctx.cfg, gen).run_chunk(
            range(start, start + width)
        )
        stray = int(np.setdiff1d(chunk.unique_hashes, last["unique_hashes"]).size)
        in_slice = (last["vio_seeds"] >= start) & (last["vio_seeds"] < start + width)
        miscount = abs(int(in_slice.sum()) - chunk.violations)
        spread = last["lane_sharding"] or {}
        checks += [
            Check("sweep.one_chip_sequences_not_in_sharded_job", stray, 0),
            Check("sweep.one_chip_violations_miscount", miscount, 0),
            Check("sweep.devices_not_spanned",
                  len(ctx.devices) - spread.get("devices", 0), 0),
        ]
        failed += stray + miscount
    return checks, attempted, failed


def close(ctx: Ctx) -> None:
    pass
