#!/usr/bin/env python3
"""The plain reference (``lib/vsr_reference.py``: Viewstamped Replication
Revisited in dicts and lists) against the timed path's own lanes, at the
cell's own size, on the chip (PR 38 ran this):

    chiprun -- python3 benchmarks/tests/vsr_reference_on_chip.py vsr5-recovery-sweep 11 32
    chiprun -- python3 benchmarks/tests/vsr_reference_on_chip.py vsr5-recovery-sweep 11 64 --control

One whole job of the cell through the verb module's ``setup`` and
``run_job`` (the sweep the window times); then ``lanes`` of its lanes, half
of them violating and seeded clean ones after, are run again twice:
through the job's own compiled segment kernel to their end, for the final
actor rows (``dag_reference_on_chip.final_states``), and traced on one device with the creation links on, as the
cell's own check lifts them. The reference replays each recorded sequence
with the configuration's ``bug``. A lane agrees when the reference accepts
every delivery under the record that sent it, gives the verdict the job
gave that lane at the step the lane stopped, holds every replica's view,
status, commit-number, log and spawn count as the timed kernel's final
rows have them, counts the log-bearing rows the kernel's ghost word
counted, and the re-runs' delivered sequence is one the job counted.
Prints one JSON object; exits 1 on any disagreement.

``--control`` replays with ``bug=None``, the protocol as published, and
exits 0 only if that reference DISAGREES on at least one lane: it refuses
or judges otherwise the lanes in which the program's seeded bug fired, so
give it 64 lanes, the violating ones first. Not a test
(``test_vsr_cell.py`` has its tiny twin)."""

import json
import os
import random
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH, HERE]

# The state words the comparison reads (apps/vsr.py's layout; the log
# follows the 20 scalars).
VIEW, STATUS, OPN, COMMIT, INCARN, LOG_ROWS_SENT, LOG = 0, 1, 2, 3, 5, 18, 20


def reference_check(
    bench, workload, seed, lanes=32, require_tpu=True, control=False
) -> dict:
    import dataclasses

    import jax
    import numpy as np

    from dag_reference_on_chip import final_states
    from lib import cells, jobs, vsr_reference
    from demi_tpu.device.encoding import lower_program
    from demi_tpu.device.explore import make_single_lane_trace_kernel

    cell = cells.load_cell(bench, workload)
    devices = jax.local_devices()[: cell.chips]
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit("vsr_reference_on_chip.py: no TPU; nothing was run")
    log_cap = cell.config["workload"]["log_cap"]
    bug = None if control else cell.config["workload"]["bug"]
    verb = cells.load_verb(cell)
    ctx = verb.setup(cell, devices)
    try:
        out = verb.run_job(ctx, jobs.warm_jobs(cell.traffic["panel"], seed)[0])
        code_of = dict(zip(out["vio_seeds"].tolist(), out["vio_codes"].tolist()))
        rng = random.Random(seed)
        # Half of them violating where the job holds as many (a lane
        # stops at its verdict, so the clean half holds the long runs);
        # the control wants violating lanes alone.
        want = lanes if control else lanes // 2
        picked = rng.sample(sorted(code_of), min(want, len(code_of)))
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
        disagreeing, peak, deliveries, views, notes = 0, 0, 0, 0, []
        for lane, s in enumerate(picked):
            prog = lower_program(ctx.app, ctx.cfg, ctx.driver.program_gen(s))
            key = jax.random.fold_in(jax.random.PRNGKey(0), np.uint32(s))
            single = kernel(prog, key)
            want = code_of.get(s, 0)
            try:
                ref = vsr_reference.replay(
                    n, log_cap, np.asarray(single.trace).tolist(),
                    int(single.trace_len), bug=bug,
                )
            except vsr_reference.Diverged as e:
                disagreeing += 1
                notes.append(f"seed {s}: {e}")
                continue
            peak = max(peak, ref.peak_pending)
            deliveries += ref.deliveries
            views += max(ref.views)
            rows = actors[lane]
            faults = [
                name for name, bad in (
                    ("verdict", not (ref.code == want == int(single.violation)
                                     == int(codes[lane]))),
                    ("step", not (ref.step == ref.deliveries
                                  == int(single.deliveries)
                                  == int(delivered[lane]))),
                    ("sequence", int(single.sched_hash) not in known
                     or int(single.sched_hash) != int(hashes[lane])),
                    ("replicas", any(
                        (ref.views[i], ref.statuses[i], ref.commits[i],
                         ref.spawns[i]) != tuple(
                            int(rows[i][w]) for w in (VIEW, STATUS, COMMIT, INCARN)
                        ) for i in range(n)
                    )),
                    ("logs", any(
                        ref.logs[i]
                        != rows[i][LOG : LOG + int(rows[i][OPN])].tolist()
                        for i in range(n)
                    )),
                    ("log rows", ref.log_rows
                     != int(rows[:, LOG_ROWS_SENT].sum())),
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
            "disagreeing": disagreeing,
            "job_violations": int(out["violations"]),
            "job_codes": sorted(set(code_of.values())),
            "job_overflow": int(out["overflow"]),
            "peak_pending": peak, "deliveries": deliveries,
            "views": views,
            "device": devices[0].platform, "notes": notes[:8],
        }
    finally:
        verb.close(ctx)


def main(argv) -> int:
    control = "--control" in argv
    argv = [a for a in argv if a != "--control"]
    workload, seed = argv[0], int(argv[1])
    lanes = int(argv[2]) if len(argv) > 2 else 32
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
