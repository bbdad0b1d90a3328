#!/usr/bin/env python3
"""The plain reference (``lib/chain_reference.py``: chain replication as
objects with ``hist`` and ``sent`` lists over per-pair FIFO queues) against
the timed path's own lanes, at the cell's own size, on the chip (PR 40 ran
this):

    chiprun -- python3 benchmarks/tests/chain_reference_on_chip.py chain7-fifo-sweep 11 32
    chiprun -- python3 benchmarks/tests/chain_reference_on_chip.py chain7-fifo-sweep 11 32 --control
    chiprun -- python3 benchmarks/tests/chain_reference_on_chip.py chain7-fifo-sweep 11 --any

One whole job of the cell through the verb module's ``setup`` and
``run_job`` (the sweep the window times); then ``lanes`` of its lanes, half
of them violating and seeded clean ones after, are run again twice: through
the job's own compiled segment kernel to their end, for the final actor
rows (``dag_reference_on_chip.final_states``), and traced on one device, as
the cell's own check lifts them. The reference replays each recorded
sequence with the configuration's ``bug`` and **fails where a delivery is
not the head of its (sender, receiver) queue**. A lane agrees when the
reference accepts every delivery, gives the verdict the job gave that lane
at the step the lane stopped, holds every server's status, Hist,
acknowledged count and spawn count as the timed kernel's final rows have
them, counts the resent rows and configuration changes the kernel's ghost
words counted, and the re-runs' delivered sequence is one the job counted.
Prints one JSON object; exits 1 on any disagreement.

``--control`` replays with ``bug=None``, the protocol as published, and
exits 0 only if that reference parts on EVERY violating lane picked (it
refuses or judges otherwise each lane in which the program's seeded bug
fired). ``--any`` is the control of the discipline: one job of the cell's
shape with ``bug=None`` over channels that keep no order
(``channels="any"``, the kernel built without ``srcdst_fifo``); exits 0
only if lanes violate, and says how many of how many. Not a test
(``test_chain_cell.py`` has its tiny twin)."""

import json
import os
import random
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH, HERE]

# The state words the comparison reads (apps/chain.py's layout; Hist
# follows the 15 scalars).
STATUS, OPN, ACKED, SPAWNS, AWAKE, RESENT_ROWS, RECONFIGS, HIST = (
    0, 5, 6, 8, 9, 10, 11, 15
)


def reference_check(
    bench, workload, seed, lanes=32, require_tpu=True, control=False
) -> dict:
    import jax
    import numpy as np

    from dag_reference_on_chip import final_states
    from lib import cells, chain_reference, jobs
    from demi_tpu.device.encoding import lower_program
    from demi_tpu.device.explore import make_single_lane_trace_kernel

    cell = cells.load_cell(bench, workload)
    devices = jax.local_devices()[: cell.chips]
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit("chain_reference_on_chip.py: no TPU; nothing was run")
    log_cap = cell.config["workload"]["log_cap"]
    bug = None if control else cell.config["workload"]["bug"]
    verb = cells.load_verb(cell)
    ctx = verb.setup(cell, devices)
    try:
        out = verb.run_job(ctx, jobs.warm_jobs(cell.traffic["panel"], seed)[0])
        code_of = dict(zip(out["vio_seeds"].tolist(), out["vio_codes"].tolist()))
        rng = random.Random(seed)
        # Half of them violating where the job holds as many (a lane
        # stops at its verdict, so the clean half holds the long runs).
        picked = rng.sample(sorted(code_of), min(lanes // 2, len(code_of)))
        while len(picked) < lanes:
            s = rng.randrange(cell.traffic["job"]["schedules"])
            if s not in code_of and s not in picked:
                picked.append(s)
        known = set(out["unique_hashes"].tolist())
        ctx.base = out["base"]
        codes, delivered, hashes, actors = final_states(ctx, picked)
        kernel = make_single_lane_trace_kernel(ctx.app, ctx.cfg)
        n = ctx.cfg.num_actors
        disagreeing = parted = peak = deliveries = 0
        notes = []
        for lane, s in enumerate(picked):
            prog = lower_program(ctx.app, ctx.cfg, ctx.driver.program_gen(s))
            key = jax.random.fold_in(jax.random.PRNGKey(0), np.uint32(s))
            single = kernel(prog, key)
            want = code_of.get(s, 0)
            try:
                ref = chain_reference.replay(
                    n, log_cap, np.asarray(single.trace).tolist(),
                    int(single.trace_len), bug=bug,
                )
            except chain_reference.Diverged as e:
                disagreeing += 1
                parted += bool(want)
                notes.append(f"seed {s}: {e}")
                continue
            peak = max(peak, ref.peak_pending)
            deliveries += ref.deliveries
            rows = actors[lane]
            awake = [i for i in range(n) if rows[i][AWAKE]]
            faults = [
                name for name, bad in (
                    ("verdict", not (ref.code == want == int(single.violation)
                                     == int(codes[lane]))),
                    ("step", not (ref.step == ref.deliveries
                                  == int(single.deliveries)
                                  == int(delivered[lane]))),
                    ("sequence", int(single.sched_hash) not in known
                     or int(single.sched_hash) != int(hashes[lane])),
                    ("spawns", ref.spawns != rows[:, SPAWNS].tolist()),
                    ("servers", any(
                        (ref.statuses[i], ref.acked[i]) != (
                            int(rows[i][STATUS]), int(rows[i][ACKED])
                        ) for i in awake
                    )),
                    ("hists", any(
                        ref.hists[i]
                        != rows[i][HIST : HIST + int(rows[i][OPN])].tolist()
                        for i in awake
                    )),
                    ("ghost counts", (ref.resent, ref.reconfigs) != (
                        int(rows[:, RESENT_ROWS].sum()),
                        int(rows[:, RECONFIGS].sum()),
                    )),
                ) if bad
            ]
            if faults:
                disagreeing += 1
                parted += bool(want)
                notes.append(
                    f"seed {s}: {', '.join(faults)} (job code {want}, traced "
                    f"{int(single.violation)}, reference {ref.code} at {ref.step})"
                )
        return {
            "workload": workload, "seed": seed, "lanes": len(picked),
            "control": control, "violating": sum(s in code_of for s in picked),
            "disagreeing": disagreeing, "violating_parted": parted,
            "job_violations": int(out["violations"]),
            "job_codes": sorted(set(code_of.values())),
            "job_overflow": int(out["overflow"]),
            "peak_pending": peak, "deliveries": deliveries,
            "device": devices[0].platform, "notes": notes[:8],
        }
    finally:
        verb.close(ctx)


def any_channels_control(bench, workload, seed, require_tpu=True) -> dict:
    """One job of the cell's shape, the protocol as published, over
    channels that keep no order."""
    import dataclasses

    import jax

    from lib import cells, jobs
    from demi_tpu.parallel.distributed import build_workload
    from demi_tpu.parallel.sweep import SweepDriver

    cell = cells.load_cell(bench, workload)
    devices = jax.local_devices()[: cell.chips]
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit("chain_reference_on_chip.py: no TPU; nothing was run")
    app, cfg, fuzzer = build_workload(dict(cell.config["workload"], bug=None))
    app = dataclasses.replace(app, channels="any")
    cfg = dataclasses.replace(cfg, srcdst_fifo=False)
    base = jobs.warm_jobs(cell.traffic["panel"], seed)[0].sub_seed << 20
    driver = SweepDriver(
        app, cfg, lambda s: fuzzer.generate_fuzz_test(seed=base + s)
    )
    job = cell.traffic["job"]
    result = driver.sweep(
        job["schedules"], job["resident_lanes_per_chip"], mode=job["mode"]
    )
    return {
        "workload": workload, "seed": seed, "channels": "any", "bug": None,
        "lanes": int(result.lanes), "violations": int(result.violations),
        "overflow": int(result.overflow_lanes),
        "device": devices[0].platform,
    }


def main(argv) -> int:
    flags = {a for a in argv if a.startswith("--")}
    argv = [a for a in argv if a not in flags]
    workload, seed = argv[0], int(argv[1])
    bench = os.path.join(ROOT, "BENCHMARK.json")
    if "--any" in flags:
        report = any_channels_control(bench, workload, seed)
        print(json.dumps(report), flush=True)
        return int(not report["violations"])
    control = "--control" in flags
    lanes = int(argv[2]) if len(argv) > 2 else 32
    report = reference_check(bench, workload, seed, lanes, control=control)
    print(json.dumps(report), flush=True)
    if report["job_overflow"]:
        return 1
    if control:
        return int(
            not report["violating"]
            or report["violating_parted"] != report["violating"]
        )
    return int(bool(report["disagreeing"]))


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
