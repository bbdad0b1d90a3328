"""The dpor verb's jobs: what ``demi_tpu dpor --batch B --rounds R`` does
once traced, in coverage mode. The program is the verb's own
(``dsl_start_events(app) + [WaitQuiescence()]``, cli.py's ``cmd_dpor``), the
search a fresh ``DeviceDPOR`` over the kernel built once in set-up (as
``DeviceDPOROracle`` shares one), with the verb's defaults: no prefix fork,
no double buffer, no sleep sets, position lane keys. The verb takes no
seed and the search is deterministic, so every job is the same search and
its counts are stated in the traffic file; ``--seed`` draws only what the
check samples."""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from lib import racing, spans
from lib.harness import Check
from verbs_common import build, build_native, host_config, lane_mesh

STEP_KERNEL = "jit_run_lane"


@dataclass
class Ctx:
    cell: object
    app: object
    cfg: object
    program: list
    kernel: object
    mesh: object
    devices: list
    spanned: bool = False
    last: object = None     # the newest job's DeviceDPOR, for the check


def setup(cell, devices) -> Ctx:
    from demi_tpu.device.dpor_sweep import build_dpor_kernel
    from demi_tpu.apps.common import dsl_start_events
    from demi_tpu.external_events import WaitQuiescence

    build_native()
    app, cfg, _fuzzer = build(cell, record=True)
    mesh = lane_mesh(devices)
    return Ctx(
        cell=cell, app=app, cfg=cfg,
        program=dsl_start_events(app) + [WaitQuiescence()],
        kernel=build_dpor_kernel(app, cfg, mesh=mesh), mesh=mesh,
        devices=list(devices),
    )


def run_job(ctx: Ctx, job) -> dict:
    from demi_tpu.device.dpor_sweep import DeviceDPOR

    spec = ctx.cell.traffic["job"]
    # The job before is freed here, as a command's is when its process
    # ends; only the newest search is kept, for the check.
    ctx.last = None
    dpor = DeviceDPOR(
        ctx.app, ctx.cfg, ctx.program, batch_size=spec["batch"],
        kernel=ctx.kernel, mesh=ctx.mesh,
    )
    if ctx.spanned:
        spans.wrap(dpor, "_dispatch_round", "bench.dpor.dispatch")
        spans.wrap(dpor, "_supervised_harvest", "bench.dpor.block")
        spans.wrap(dpor, "_process_round", "bench.dpor.host_half")
    with spans.span("bench.dpor.job"):
        found = dpor.explore(max_rounds=spec["rounds"], stop_on_violation=False)
    ctx.last = dpor
    return {
        "work": dpor.interleavings,
        "explored": len(dpor.explored),
        "frontier": len(dpor.frontier),
        "rounds": dpor.round_index,
        "found": found,
        "codes": sorted(dpor.violation_codes),
        "host_s": dpor.host_seconds,
        "device_s": dpor.device_seconds,
        "lane_steps": dpor.interleavings * ctx.cfg.max_steps,
    }


def instrument(ctx: Ctx, on: bool) -> None:
    ctx.spanned = on


def counters(ctx: Ctx, records) -> dict:
    outs = [r.out for r in records]
    return {
        "host_s": sum(o["host_s"] for o in outs),
        "device_s": sum(o["device_s"] for o in outs),
        "lane_steps": sum(o["lane_steps"] for o in outs),
        "chips": len(ctx.devices),
    }


def counts_of(out: dict) -> dict:
    """A job's answer in the traffic file's ``expect`` form."""
    return {
        "interleavings": int(out["work"]), "explored": int(out["explored"]),
        "frontier": int(out["frontier"]), "rounds": int(out["rounds"]),
        "violation_codes": [int(c) for c in out["codes"]],
        "found_trace_len": None if out["found"] is None else int(out["found"][1]),
    }


def check(ctx: Ctx, records, warm, rng):
    """On the window's own outputs. Every job: its counts (interleavings,
    explored set, frontier, rounds, violation codes, the found lane's
    length) are the traffic file's, exactly: a search that drops, repeats
    or never admits a prescription explores another set. Every job's found
    lane, lifted to the host oracle, reproduces a code the device
    reported. The window's last search, whole: every prescription the
    plain racing reference derives from the found lane is in its explored
    set, and a sample of its pending prescriptions, drawn from the seed,
    runs on the host oracle: the part the device ran is clean there too
    and the reversed delivery is deliverable."""
    from demi_tpu.device.encoding import device_trace_to_guide
    from demi_tpu.schedulers.guided import GuidedScheduler, GuideDivergence

    outs = [r.out for r in records]
    expect = ctx.cell.traffic["expect"]
    sample = ctx.cell.traffic["check"]["host_executed_prescriptions"]
    attempted = sum(o["work"] for o in outs)
    off = sum(1 for o in outs if counts_of(o) != expect)
    first, again = outs[0], warm[records[0].job.sub_seed]
    repeat = int(counts_of(first) != counts_of(again))
    config = host_config(ctx.app)
    recw = ctx.cfg.rec_width

    def host(rows):
        guide = device_trace_to_guide(ctx.app, np.asarray(rows, np.int32), len(rows))
        return GuidedScheduler(config, ctx.app).execute_guide(guide)

    disagree = 0
    for o in outs:
        if o["found"] is None:
            disagree += int(bool(o["codes"]))   # codes without a lane to show
            continue
        rows = racing.rows_of(o["found"][0], o["found"][1], recw)
        try:
            violation = host(rows).violation
        except GuideDivergence:
            disagree += 1
            continue
        if violation is None or violation.code not in o["codes"]:
            disagree += 1

    dpor, last = ctx.last, outs[-1]
    unexplored = diverging = 0
    externals = []
    if last["found"] is not None:
        rows = racing.rows_of(last["found"][0], last["found"][1], recw)
        externals = [r for r in rows if r[0] >= racing.KIND_EXT_BASE]
        derived = racing.prescriptions(rows)
        unexplored = sum(1 for p in derived if p not in dpor.explored)
        print(f"[bench] reference: {len(derived)} races in the found lane of "
              f"{len(rows)} records")
    pending = dpor.frontier
    for k in sorted(rng.sample(range(len(pending)), min(sample, len(pending)))):
        presc = [tuple(int(x) for x in row) for row in pending[k]]
        try:
            clean = host(externals + presc[:-1]).violation is None
            host(externals + presc)
        except GuideDivergence:
            clean = False
        diverging += int(not clean)
    checks = [
        Check("dpor.jobs_off_the_stated_counts", off, 0),
        Check("dpor.repeat_counts_differ", repeat, 0),
        Check("dpor.found_lanes_disagreeing_with_host", disagree, 0),
        Check("dpor.reference_races_not_explored", unexplored, 0),
        Check("dpor.pending_prescriptions_diverging_on_host", diverging, 0),
    ]
    if off:
        print(f"[bench] stated {expect}; first job off: "
              f"{next(counts_of(o) for o in outs if counts_of(o) != expect)}")
    return checks, attempted, off + disagree + unexplored + diverging


def close(ctx: Ctx) -> None:
    ctx.last = None
