#!/usr/bin/env python3
"""The plain reference (``lib/dag_reference.py``: a Spark driver and its
executors in sets of (stage, task)) against the timed path's own lanes, at
the cell's own size, on the chip (PR 33 ran this):

    chiprun -- python3 benchmarks/tests/dag_reference_on_chip.py spark17-shuffle200-sweep 11 16
    chiprun -- python3 benchmarks/tests/dag_reference_on_chip.py spark17-shuffle200-sweep 11 64 --control

One whole job of the cell through the verb module's ``setup`` and
``run_job`` (the sweep the window times); then ``lanes`` of its lanes, the
violating ones first and seeded clean ones after, are run again twice:
through the job's own compiled segment kernel to their end, for the final
actor states, and traced on one device with the creation links on, as the
cell's own check lifts them. The reference replays each recorded sequence.
A lane agrees when the reference accepts every delivery under the record
that sent it, gives the verdict the job gave that lane at the step the
lane stopped, ends quiescent if clean, holds the driver's stage, done flag
and credited set and every executor's executed set as the timed kernel's
final state has them, and the re-runs' delivered sequence is one the job
counted. Prints one JSON object; exits 1 on any disagreement.

``--control`` replays with the reference's epoch check taken out (the
protocol's ``stale_task`` bug) and exits 0 only if that reference
DISAGREES on at least one lane: a late duplicate changes which delivery
completes a stage in about one lane in eight, so give it 64 lanes. Not a
test (``test_spark_cell.py`` has its tiny twin)."""

import json
import os
import random
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH, HERE]

MASKS = 2        # state words 0, 1: the driver's stage and done flag
WORD_BITS = 32


def mask_sets(words, stages, tasks):
    """The (stage, task) set a node's mask words stand for: stage s's
    words at MASKS + s * per_stage, task t bit t % 32 of word t // 32."""
    per_stage = (len(words) - MASKS) // stages
    return {
        (s, WORD_BITS * k + bit)
        for s in range(stages) for k in range(per_stage)
        for bit in range(WORD_BITS)
        if (int(words[MASKS + s * per_stage + k]) >> bit) & 1
    } & {(s, t) for s in range(stages) for t in range(tasks)}


def final_states(ctx, seeds):
    """``seeds`` run to their end through the sweep's own compiled
    kernels (the resident set's shapes, so nothing compiles): per seed
    the final ``(code, deliveries, sched_hash, actor_state)``."""
    import jax.numpy as jnp
    import numpy as np

    from demi_tpu.device.encoding import empty_programs, lower_into

    drv = ctx.driver._continuous_driver(ctx.resident)
    b = ctx.resident
    lane_seed = list(seeds) + [seeds[0]] * (b - len(seeds))
    progs = empty_programs(ctx.cfg, b)
    for lane, s in enumerate(lane_seed):
        lower_into(ctx.app, ctx.cfg, ctx.driver.program_gen(s), progs, lane)
    state = drv.init(drv._vkeys(jnp.asarray(lane_seed, jnp.uint32)))
    for steps in range(0, ctx.cfg.max_steps, drv.seg_steps):
        state = drv.segment(state, progs, jnp.full(b, steps, jnp.int32))
    state = drv.finalize(state)
    k = len(seeds)
    return (
        np.asarray(state.violation)[:k], np.asarray(state.deliveries)[:k],
        np.asarray(state.sched_hash)[:k], np.asarray(state.actor_state)[:k],
    )


def reference_check(
    bench, workload, seed, lanes=16, require_tpu=True, control=False
) -> dict:
    import dataclasses

    import jax
    import numpy as np

    from lib import cells, dag_reference, jobs
    from demi_tpu.device.encoding import lower_program
    from demi_tpu.device.explore import make_single_lane_trace_kernel

    cell = cells.load_cell(bench, workload)
    devices = jax.local_devices()[: cell.chips]
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit("dag_reference_on_chip.py: no TPU; nothing was run")
    stages = cell.config["workload"]["stages"]
    tasks = cell.config["workload"]["tasks"]
    verb = cells.load_verb(cell)
    ctx = verb.setup(cell, devices)
    try:
        out = verb.run_job(ctx, jobs.warm_jobs(cell.traffic["panel"], seed)[0])
        code_of = dict(zip(out["vio_seeds"].tolist(), out["vio_codes"].tolist()))
        rng = random.Random(seed)
        picked = rng.sample(sorted(code_of), min(lanes // 2, len(code_of)))
        while len(picked) < lanes:
            s = rng.randrange(cell.traffic["job"]["schedules"])
            if s not in code_of and s not in picked:
                picked.append(s)
        known = set(out["unique_hashes"].tolist())
        ctx.base = out["base"]
        codes, delivered, hashes, actors = final_states(ctx, picked)
        kernel = make_single_lane_trace_kernel(
            ctx.app, dataclasses.replace(ctx.cfg, record_parents=True)
        )
        n = ctx.cfg.num_actors
        disagreeing, unfinished, peak, deliveries, done_jobs, notes = 0, 0, 0, 0, 0, []
        for lane, s in enumerate(picked):
            prog = lower_program(ctx.app, ctx.cfg, ctx.driver.program_gen(s))
            key = jax.random.fold_in(jax.random.PRNGKey(0), np.uint32(s))
            single = kernel(prog, key)
            want = code_of.get(s, 0)
            try:
                ref = dag_reference.replay(
                    n, stages, tasks, np.asarray(single.trace).tolist(),
                    int(single.trace_len), epoch_check=not control,
                )
            except dag_reference.Diverged as e:
                disagreeing += 1
                notes.append(f"seed {s}: {e}")
                continue
            unfinished += not (ref.quiescent or ref.code)
            peak = max(peak, ref.peak_pending)
            deliveries += ref.deliveries
            done_jobs += ref.done
            driver = actors[lane][0]
            faults = [
                name for name, bad in (
                    ("not quiescent", not (ref.quiescent or ref.code)),
                    ("verdict", not (ref.code == want == int(single.violation)
                                     == int(codes[lane]))),
                    ("step", not (ref.step == ref.deliveries
                                  == int(single.deliveries)
                                  == int(delivered[lane]))),
                    ("sequence", int(single.sched_hash) not in known
                     or int(single.sched_hash) != int(hashes[lane])),
                    ("driver", (ref.stage, int(ref.done))
                     != (int(driver[0]), int(driver[1]))
                     or ref.credited != mask_sets(driver, stages, tasks)),
                    ("executors", any(
                        ref.executed[i] != mask_sets(actors[lane][i], stages, tasks)
                        for i in range(1, n)
                    )),
                ) if bad
            ]
            if faults:
                disagreeing += 1
                notes.append(
                    f"seed {s}: {', '.join(faults)} (job code {want}, traced "
                    f"{int(single.violation)}, reference {ref.code} at {ref.step})"
                )
        return {
            "workload": workload, "seed": seed, "lanes": len(picked),
            "control": control, "violating": sum(s in code_of for s in picked),
            "disagreeing": disagreeing, "unfinished": unfinished,
            "jobs_done": done_jobs,
            "job_violations": int(out["violations"]),
            "job_overflow": int(out["overflow"]),
            "peak_pending": peak, "deliveries": deliveries,
            "device": devices[0].platform, "notes": notes[:8],
        }
    finally:
        verb.close(ctx)


def main(argv) -> int:
    control = "--control" in argv
    argv = [a for a in argv if a != "--control"]
    workload, seed = argv[0], int(argv[1])
    lanes = int(argv[2]) if len(argv) > 2 else 16
    report = reference_check(
        os.path.join(ROOT, "BENCHMARK.json"), workload, seed, lanes,
        control=control,
    )
    print(json.dumps(report), flush=True)
    if report["job_overflow"]:
        return 1
    return int(not report["disagreeing"]) if control else int(bool(report["disagreeing"]))


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
