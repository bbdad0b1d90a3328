#!/usr/bin/env python3
"""The plain reference (``lib/paxos_reference.py``: Multi-Paxos as objects
with real sets and dicts over a bag of pending messages) against the timed
path's own lanes, at the cell's own size, on the chip (PR 44 ran this):

    chiprun -- python3 benchmarks/tests/paxos_reference_on_chip.py paxos11-datagram-sweep 11 32
    chiprun -- python3 benchmarks/tests/paxos_reference_on_chip.py paxos11-datagram-sweep 11 32 --control
    chiprun -- python3 benchmarks/tests/paxos_reference_on_chip.py paxos11-datagram-sweep 11 --reliable

One whole job of the cell through the verb module's ``setup`` and
``run_job`` (the sweep the window times); then ``lanes`` of its lanes, half
of them violating and seeded clean ones after, are run again twice: through
the job's own compiled segment kernel to their end, for the final actor
rows (``dag_reference_on_chip.final_states``), and traced on one device, as
the cell's own check lifts them. The reference replays each recorded
sequence with the configuration's ``bug``, **kept and discarded deliveries
included, and fails where a message that was consumed is delivered again,
where a timer or a client's send is kept or discarded, or where a schedule
holds more of either than the configuration's budgets**. A lane agrees
when the reference accepts every record, gives the verdict the job gave
that lane at the step the lane stopped, holds every actor's state (replica:
slot_in, slot_out, requests, proposals, decisions; leader: ballot, active,
scout, pmax, proposals, commanders; acceptor: ballot, accepted) as the
timed kernel's final rows have it, counts the kept and discarded
deliveries, the adoptions and preemptions the kernel counted, and the
re-runs' delivered sequence is one the job counted. Prints one JSON object;
exits 1 on any disagreement.

``--control`` replays with ``bug=None``, the protocol as published, and
exits 0 only if that reference parts on EVERY violating lane picked.
``--reliable`` is the control of the network: one job of the cell's shape
with the configuration's bug and both weights 0 (no message is repeated or
lost); exits 0 only if NO lane violates, and says how many of how many
ran. Not a test (``test_paxos_cell.py`` has its tiny twin)."""

import json
import os
import random
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH, HERE]

# The state words the comparison reads (apps/paxos.py's layout: eight
# scalars, then tables of ``log_cap`` words).
BASE = 8


def row_digest(node, row, num_actors, log_cap, bug):
    """A device row as the reference's ``digest`` gives an actor: plain
    values by role (``lib/paxos_reference.py: _Cluster.digest``)."""
    f = (num_actors - 3) // 4
    L = log_cap
    row = [int(x) for x in row]
    t0, t1, t2, t3 = (BASE + k * L for k in range(4))
    every = range(1, L + 1)

    def table(start, keep=lambda v: v != 0):
        return tuple(
            (s, row[start + s - 1]) for s in every if keep(row[start + s - 1])
        )

    if node <= f:
        return (
            "replica", row[0], row[1],
            tuple(c for c in every if row[t0 + c - 1]),
            table(t1), table(t2), row[2], row[3],
        )
    acceptors = 2 * f + 1
    if node >= 2 * f + 2:
        return (
            "acceptor", row[0],
            tuple((s, b, row[t1 + s - 1]) for s, b in table(t0, lambda v: v >= 0)),
        )
    def answered(word):
        if bug == "count_replies":
            return word & ((1 << acceptors) - 1)
        return tuple(a for a in range(acceptors) if word >> a & 1)

    return (
        "leader", row[0], row[1], row[2], answered(row[3]),
        tuple((s, b, row[t2 + s - 1]) for s, b in table(t1, lambda v: v >= 0)),
        table(t0),
        tuple((s, (w >> acceptors) - 1, answered(w)) for s, w in table(t3)),
        row[4], row[5],
    )


def reference_check(
    bench, workload, seed, lanes=32, require_tpu=True, control=False
) -> dict:
    import jax
    import numpy as np

    from dag_reference_on_chip import final_states
    from lib import cells, jobs, paxos_reference
    from demi_tpu.device.encoding import lower_program
    from demi_tpu.device.explore import make_single_lane_trace_kernel

    cell = cells.load_cell(bench, workload)
    devices = jax.local_devices()[: cell.chips]
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit("paxos_reference_on_chip.py: no TPU; nothing was run")
    spec = cell.config["workload"]
    log_cap = spec["log_cap"]
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
        disagreeing = parted = peak = deliveries = kept = discarded = 0
        notes = []
        for lane, s in enumerate(picked):
            prog = lower_program(ctx.app, ctx.cfg, ctx.driver.program_gen(s))
            key = jax.random.fold_in(jax.random.PRNGKey(0), np.uint32(s))
            single = kernel(prog, key)
            want = code_of.get(s, 0)
            try:
                ref = paxos_reference.replay(
                    n, log_cap, np.asarray(single.trace).tolist(),
                    int(single.trace_len), bug=bug,
                    max_dups=spec["max_dups"], max_drops=spec["max_drops"],
                )
            except paxos_reference.Diverged as e:
                disagreeing += 1
                parted += bool(want)
                notes.append(f"seed {s}: {e}")
                continue
            peak = max(peak, ref.peak_pending)
            deliveries += ref.deliveries
            kept += ref.kept
            discarded += ref.discarded
            rows = actors[lane]
            digests = [row_digest(i, rows[i], n, log_cap, bug) for i in range(n)]
            faults = [
                name for name, bad in (
                    ("verdict", not (ref.code == want == int(single.violation)
                                     == int(codes[lane]))),
                    ("step", not (ref.step == ref.deliveries
                                  == int(single.deliveries)
                                  == int(delivered[lane]))),
                    ("sequence", int(single.sched_hash) not in known
                     or int(single.sched_hash) != int(hashes[lane])),
                    ("actors", [
                        i for i in range(n) if ref.digests[i] != digests[i]
                    ]),
                    ("ghost counts", (ref.adoptions, ref.preempts) != (
                        sum(d[-2] for d in digests if d[0] == "leader"),
                        sum(d[-1] for d in digests if d[0] == "leader"),
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
            "peak_pending": peak, "deliveries": deliveries, "kept": kept,
            "discarded": discarded,
            "device": devices[0].platform, "notes": notes[:8],
        }
    finally:
        verb.close(ctx)


def reliable_control(bench, workload, seed, require_tpu=True) -> dict:
    """One job of the cell's shape, the configuration's bug, over a network
    that repeats and loses nothing (both weights 0)."""
    import jax

    from lib import cells, jobs
    from demi_tpu.parallel.distributed import build_workload
    from demi_tpu.parallel.sweep import SweepDriver

    cell = cells.load_cell(bench, workload)
    devices = jax.local_devices()[: cell.chips]
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit("paxos_reference_on_chip.py: no TPU; nothing was run")
    app, cfg, fuzzer = build_workload(
        dict(cell.config["workload"], dup_weight=0.0, drop_weight=0.0)
    )
    base = jobs.warm_jobs(cell.traffic["panel"], seed)[0].sub_seed << 20
    driver = SweepDriver(
        app, cfg, lambda s: fuzzer.generate_fuzz_test(seed=base + s)
    )
    job = cell.traffic["job"]
    result = driver.sweep(
        job["schedules"], job["resident_lanes_per_chip"], mode=job["mode"]
    )
    return {
        "workload": workload, "seed": seed, "dup_weight": 0.0,
        "drop_weight": 0.0, "bug": cell.config["workload"]["bug"],
        "lanes": int(result.lanes), "violations": int(result.violations),
        "overflow": int(result.overflow_lanes),
        "device": devices[0].platform,
    }


def main(argv) -> int:
    flags = {a for a in argv if a.startswith("--")}
    argv = [a for a in argv if a not in flags]
    workload, seed = argv[0], int(argv[1])
    bench = os.path.join(ROOT, "BENCHMARK.json")
    if "--reliable" in flags:
        report = reliable_control(bench, workload, seed)
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
