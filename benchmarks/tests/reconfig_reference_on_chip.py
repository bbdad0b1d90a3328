#!/usr/bin/env python3
"""The plain reference (``lib/reconfig_reference.py``: the dissertation's
Raft as a class a server, a list for the log, a dict for the snapshot, sets
for configurations) against the timed path's own lanes, at the cell's own
size, on the chip (PR 47 ran this):

    chiprun -- python3 benchmarks/tests/reconfig_reference_on_chip.py raft7-reconfig-sweep 11 32
    chiprun -- python3 benchmarks/tests/reconfig_reference_on_chip.py raft7-reconfig-sweep 11 32 --control
    chiprun -- python3 benchmarks/tests/reconfig_reference_on_chip.py raft7-reconfig-sweep 11 --fixed

One whole job of the cell through the verb module's ``setup`` and
``run_job`` (the sweep the window times); then ``lanes`` of its lanes, half
of them violating where there are that many and seeded clean ones after,
are run again twice: through the job's own compiled segment kernel to their
end, for the final actor rows (``dag_reference_on_chip.final_states``), and
traced on one device, as the cell's own check lifts them. The reference
replays each recorded sequence, kills, restarts from disk and link cuts
included, with the configuration's ``bug``. A lane agrees when the
reference accepts every record, gives the verdict the job gave that lane at
the delivery the lane stopped at, holds every server's disk (term, vote,
window, snapshot), role, commit and applied index, state machine and
configuration as the timed kernel's final rows have them, counts the
configurations committed, compactions, snapshots sent and installed and
restarts the kernel counted, and the re-runs' delivered sequence is one the
job counted. Prints one JSON object (with the lanes' means of the six
progress counts and the fullest pending set); exits 1 on any disagreement.

``--control`` replays with ``bug=None``, the protocol as published and
fixed, and exits 0 only if that reference parts on EVERY violating lane
picked. ``--fixed`` runs one job of the cell's shape with ``bug=None``;
exits 0 only if NO lane violates, and says how many of how many ran. Not a
test (``test_reconfig_cell.py`` has its tiny twin)."""

import json
import os
import random
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH, HERE]


def row_digest(row, n, log_cap):
    """A device row as plain values: what the reference's ``server_digest``
    gives a server."""
    from demi_tpu.apps import raft_reconfig as rr

    lay = rr.state_layout(n, log_cap)
    row = [int(x) for x in row]

    def array(name):
        start, length = lay[name]
        return row[start : start + length]

    held = row[rr.LOG_LEN]
    return (
        row[rr.TERM], row[rr.VOTED_FOR], row[rr.LOG_BASE],
        tuple(zip(array("LOG_T")[:held], array("LOG_K")[:held],
                  array("LOG_V")[:held])),
        (row[rr.SNAP_TERM], row[rr.SNAP_CFG], row[rr.SNAP_DIGEST],
         tuple(array("SNAP_REG"))),
        row[rr.ROLE], row[rr.COMMIT], row[rr.APPLIED], row[rr.DIGEST],
        tuple(array("REG")), row[rr.CFG], row[rr.CFG_IDX], row[rr.RESTORES],
    )


def server_digest(reference, server, spawns):
    snap = server.snapshot
    return (
        server.term, -1 if server.voted_for is None else server.voted_for,
        server.base, tuple(tuple(e) for e in server.log),
        (snap["term"], reference.mask_of(snap["config"]), snap["digest"],
         tuple(snap["reg"])),
        server.role, server.commit, server.applied, server.digest,
        tuple(server.reg), reference.mask_of(server.config), server.config_at,
        spawns,
    )


def reference_check(
    bench, workload, seed, lanes=32, require_tpu=True, control=False
) -> dict:
    import jax
    import numpy as np

    from dag_reference_on_chip import final_states
    from lib import cells, jobs, reconfig_reference
    from demi_tpu.device.encoding import lower_program
    from demi_tpu.device.explore import make_single_lane_trace_kernel

    cell = cells.load_cell(bench, workload)
    devices = jax.local_devices()[: cell.chips]
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit("reconfig_reference_on_chip.py: no TPU; nothing was run")
    spec = cell.config["workload"]
    log_cap, every = spec["log_cap"], spec["snapshot_every"]
    bug = None if control else spec["bug"]
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
        kernel = make_single_lane_trace_kernel(ctx.app, ctx.cfg)
        n = ctx.cfg.num_actors
        disagreeing = parted = peak = deliveries = 0
        progress = dict(ctx.app.progress)   # the counts the sweep sums
        totals = dict.fromkeys(progress, 0)
        notes = []
        for lane, s in enumerate(picked):
            prog = lower_program(ctx.app, ctx.cfg, ctx.driver.program_gen(s))
            key = jax.random.fold_in(jax.random.PRNGKey(0), np.uint32(s))
            single = kernel(prog, key)
            want = code_of.get(s, 0)
            try:
                ref = reconfig_reference.replay(
                    n, log_cap, every, np.asarray(single.trace).tolist(),
                    int(single.trace_len), bug=bug,
                )
            except reconfig_reference.Diverged as e:
                disagreeing += 1
                parted += bool(want)
                notes.append(f"seed {s}: {e}")
                continue
            peak = max(peak, ref.peak_pending)
            deliveries += ref.deliveries
            rows = np.asarray(actors[lane])
            counted = {name: int(fn(rows)) for name, fn in progress.items()}
            for name, count in counted.items():
                totals[name] += count
            faults = [
                name for name, bad in (
                    ("verdict", not (ref.code == want == int(single.violation)
                                     == int(codes[lane]))),
                    ("step", not (ref.step == ref.deliveries
                                  == int(single.deliveries)
                                  == int(delivered[lane]))),
                    ("sequence", int(single.sched_hash) not in known
                     or int(single.sched_hash) != int(hashes[lane])),
                    ("servers", [
                        i for i in range(n)
                        if ref.spawns[i] and server_digest(
                            reconfig_reference, ref.servers[i], ref.spawns[i]
                        ) != row_digest(rows[i], n, log_cap)
                    ]),
                    ("counts", {
                        k: (ref.counts[k], counted[k]) for k in counted
                        if ref.counts[k] != counted[k]
                    }),
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
            "job_lanes": int(out["work"]),
            "job_codes": sorted(set(code_of.values())),
            "job_overflow": int(out["overflow"]),
            "peak_pending": peak, "deliveries": deliveries,
            "per_lane": {k: v / len(picked) for k, v in totals.items()},
            "device": devices[0].platform, "notes": notes[:8],
        }
    finally:
        verb.close(ctx)


def fixed_control(bench, workload, seed, require_tpu=True) -> dict:
    """One job of the cell's shape by the fixed protocol (``bug=None``)."""
    import jax

    from lib import cells, jobs
    from demi_tpu.parallel.distributed import build_workload
    from demi_tpu.parallel.sweep import SweepDriver

    cell = cells.load_cell(bench, workload)
    devices = jax.local_devices()[: cell.chips]
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit("reconfig_reference_on_chip.py: no TPU; nothing was run")
    app, cfg, fuzzer = build_workload(dict(cell.config["workload"], bug=None))
    base = jobs.warm_jobs(cell.traffic["panel"], seed)[0].sub_seed << 20
    driver = SweepDriver(
        app, cfg, lambda s: fuzzer.generate_fuzz_test(seed=base + s)
    )
    job = cell.traffic["job"]
    result = driver.sweep(
        job["schedules"], job["resident_lanes_per_chip"], mode=job["mode"]
    )
    return {
        "workload": workload, "seed": seed, "bug": None,
        "lanes": int(result.lanes), "violations": int(result.violations),
        "overflow": int(result.overflow_lanes),
        "device": devices[0].platform,
    }


def main(argv) -> int:
    flags = {a for a in argv if a.startswith("--")}
    argv = [a for a in argv if a not in flags]
    workload, seed = argv[0], int(argv[1])
    bench = os.path.join(ROOT, "BENCHMARK.json")
    if "--fixed" in flags:
        report = fixed_control(bench, workload, seed)
        print(json.dumps(report), flush=True)
        return int(bool(report["violations"] or report["overflow"]))
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
