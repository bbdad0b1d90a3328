#!/usr/bin/env python3
"""The plain reference (``lib/flood_reference.py``: eager reliable
broadcast in sets and lists) against the timed path's own lanes, at the
cell's own size, on the chip (PR 31 ran this):

    chiprun -- python3 benchmarks/tests/flood_reference_on_chip.py bcast64-flood-sweep 11 16

One whole job of the cell through the verb module's ``setup`` and
``run_job`` (the sweep the window times); then ``lanes`` of its lanes,
the violating ones first and seeded clean ones after, are run again
traced on one device, as the cell's own check lifts them, and each
recorded delivered sequence is replayed by the reference. A lane agrees
when the reference accepts every delivery, ends quiescent, and gives
the verdict the job gave that lane, and the traced re-run's delivered
sequence is one the job counted. Prints one JSON object; exits 1 on any
disagreement. Not a test (``test_flood_cell.py`` has its tiny twin)."""

import json
import os
import random
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH, HERE]


def reference_check(bench, workload, seed, lanes=16, require_tpu=True) -> dict:
    import jax
    import numpy as np

    from lib import cells, flood_reference, jobs
    from demi_tpu.device.encoding import lower_program
    from demi_tpu.device.explore import make_single_lane_trace_kernel

    cell = cells.load_cell(bench, workload)
    devices = jax.local_devices()[: cell.chips]
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit("flood_reference_on_chip.py: no TPU; nothing was run")
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
        kernel = make_single_lane_trace_kernel(ctx.app, ctx.cfg)
        ctx.base = out["base"]
        disagreeing, unfinished, peak, deliveries, notes = 0, 0, 0, 0, []
        for s in picked:
            prog = lower_program(ctx.app, ctx.cfg, ctx.driver.program_gen(s))
            key = jax.random.fold_in(jax.random.PRNGKey(0), np.uint32(s))
            single = kernel(prog, key)
            want = code_of.get(s, 0)
            try:
                ref = flood_reference.replay(
                    ctx.cfg.num_actors, np.asarray(single.trace).tolist(),
                    int(single.trace_len),
                )
            except flood_reference.Diverged as e:
                disagreeing += 1
                notes.append(f"seed {s}: {e}")
                continue
            unfinished += not ref.quiescent
            peak = max(peak, ref.peak_pending)
            deliveries += ref.deliveries
            if (
                not ref.quiescent
                or ref.code != want
                or int(single.violation) != want
                or int(single.sched_hash) not in known
                or ref.deliveries != int(single.deliveries)
            ):
                disagreeing += 1
                notes.append(
                    f"seed {s}: job code {want}, traced {int(single.violation)}, "
                    f"reference {ref.code} quiescent {ref.quiescent}"
                )
        return {
            "workload": workload, "seed": seed, "lanes": len(picked),
            "violating": sum(s in code_of for s in picked),
            "disagreeing": disagreeing, "unfinished": unfinished,
            "job_violations": int(out["violations"]),
            "job_overflow": int(out["overflow"]),
            "peak_pending": peak, "deliveries": deliveries,
            "device": devices[0].platform, "notes": notes[:8],
        }
    finally:
        verb.close(ctx)


def main(argv) -> int:
    workload, seed = argv[0], int(argv[1])
    lanes = int(argv[2]) if len(argv) > 2 else 16
    report = reference_check(
        os.path.join(ROOT, "BENCHMARK.json"), workload, seed, lanes
    )
    print(json.dumps(report), flush=True)
    return 1 if report["disagreeing"] or report["job_overflow"] else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
