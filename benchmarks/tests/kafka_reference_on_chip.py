#!/usr/bin/env python3
"""The plain reference (``lib/kafka_reference.py``: Kafka's partition
replication as a class a replica, lists for logs and epoch caches, sets for
in-sync sets, per-pair queues for the links) against the timed path's own
lanes, at the cell's own size, on the chip (PR 52 ran this):

    chiprun -- python3 benchmarks/tests/kafka_reference_on_chip.py kafka5-acks-all-sweep 11 32
    chiprun -- python3 benchmarks/tests/kafka_reference_on_chip.py kafka5-acks-all-sweep 11 32 --control
    chiprun -- python3 benchmarks/tests/kafka_reference_on_chip.py kafka5-acks-all-sweep 11 --fixed

One whole job of the cell through the verb module's ``setup`` and
``run_job`` (the sweep the window times); then ``lanes`` of its lanes, half
of them violating where there are that many and seeded clean ones after,
are run again twice: through the job's own compiled segment kernel to their
end, for the final actor rows (``dag_reference_on_chip.final_states``), and
traced on one device, as the cell's own check lifts them. The reference
replays each recorded sequence, kills, restarts from disk and link cuts
included, with the configuration's ``bug``, and refuses a delivery that is
not the head of its (sender, receiver) queue. A lane agrees when the
reference accepts every record, gives the verdict the job gave that lane at
the delivery the lane stopped at, holds every broker's disk (logs, epoch
caches, checkpoints), roles, epochs, leaders, high watermarks, a leader's
ISR and version, the ghost counts and the controller's table as the timed
kernel's final rows have them, counts what the kernel counted, and the
re-runs' delivered sequence is one the job counted. Prints one JSON object
(with the lanes' means of the six progress counts, the fullest pending set
and the epochs that found a cache full); exits 1 on any disagreement.

``--control`` replays with ``bug=None``, KIP-101 with KIP-279's reply, and
exits 0 only if that reference parts on EVERY violating lane picked.
``--fixed`` runs one job of the cell's shape with ``bug=None``; exits 0
only if NO lane violates, and says how many of how many ran. Not a test
(``test_kafka_cell.py`` has its tiny twin)."""

import json
import os
import random
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH, HERE]


def row_digest(row, node, n, log_cap):
    """A device row as plain values: what ``node_digest`` gives the
    reference's broker or controller."""
    from demi_tpu.apps import kafka as kf

    lay = kf.state_layout(n, log_cap)
    slots = lay["slots"][0]
    _, held = kf.assignment(n - 1, n)
    row = [int(x) for x in row]

    def words(name):
        start, length = lay[name]
        return row[start : start + length]

    if node == n - 1:
        return tuple(
            tuple(words(name))
            for name in ("C_LEADER", "C_EPOCH", "C_ISR", "C_ZKV", "C_MISSED",
                         "LIVE", "HEARD")
        )

    def at(name, j):
        got = words(name)
        k = len(got) // slots
        return got[j * k : (j + 1) * k]

    out = [row[kf.RESTORES]]
    for j, _p in enumerate(held[node]):
        one = lambda name: at(name, j)[0]  # noqa: E731
        leo, cached = one("LEO"), one("EP_LEN")
        # a restarted broker takes its checkpoint at its first delivery
        hw = one("HW") if row[kf.BOOTED] else min(one("HW_CKPT"), leo)
        leads = one("ROLE") == kf.LEADER
        out.append((
            tuple(zip(at("LOG_V", j)[:leo], at("LOG_E", j)[:leo])),
            tuple(zip(at("EP_E", j)[:cached], at("EP_S", j)[:cached])),
            one("HW_CKPT"), hw, one("EXPOSED"), one("EXPOSED_AT"),
            one("ROLE"), one("EPOCH"), one("LEADER"),
            (one("ISR"), one("ZKV"), one("PEND_ADD"), one("PEND_DEL"))
            if leads else None,
            tuple(one(name) for name in (
                "ACKED", "REJECTED", "ELECTED", "ISR_SHRUNK", "ISR_GROWN",
                "TRUNCATED", "FENCED", "EPOCH_OVERFLOW",
            )),
        ))
    return tuple(out)


def node_digest(reference, ref, node, n):
    if node == n - 1:
        c = ref.controller
        brokers = range(n - 1)
        return (
            tuple(-1 if x is None else x for x in c.leader), tuple(c.epoch),
            tuple(reference.mask_of(x) for x in c.isr), tuple(c.version),
            tuple(c.missed.get(b, 0) for b in brokers),
            (reference.mask_of(c.live),), (reference.mask_of(c.heard),),
        )
    out = [ref.spawns[node]]
    for _p, r in sorted(ref.brokers[node].items()):
        leads = r.role == reference.LEADER
        out.append((
            tuple(tuple(x) for x in r.log), tuple(tuple(x) for x in r.cache),
            r.checkpoint, r.hw, r.exposed, r.exposed_at, r.role, r.epoch,
            -1 if r.leader is None else r.leader,
            (reference.mask_of(r.isr), r.version, reference.mask_of(r.adding),
             reference.mask_of(r.removing)) if leads else None,
            tuple(r.counts[name] for name in reference.COUNTS),
        ))
    return tuple(out)


def reference_check(
    bench, workload, seed, lanes=32, require_tpu=True, control=False
) -> dict:
    import jax
    import numpy as np

    from dag_reference_on_chip import final_states
    from lib import cells, jobs, kafka_reference
    from demi_tpu.device.encoding import lower_program
    from demi_tpu.device.explore import make_single_lane_trace_kernel

    cell = cells.load_cell(bench, workload)
    devices = jax.local_devices()[: cell.chips]
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit("kafka_reference_on_chip.py: no TPU; nothing was run")
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
        disagreeing = parted = peak = deliveries = overflow = 0
        progress = dict(ctx.app.progress)   # the counts the sweep sums
        totals = dict.fromkeys(progress, 0)
        notes = []
        for lane, s in enumerate(picked):
            prog = lower_program(ctx.app, ctx.cfg, ctx.driver.program_gen(s))
            key = jax.random.fold_in(jax.random.PRNGKey(0), np.uint32(s))
            single = kernel(prog, key)
            want = code_of.get(s, 0)
            try:
                ref = kafka_reference.replay(
                    n, log_cap, np.asarray(single.trace).tolist(),
                    int(single.trace_len), bug=bug,
                )
            except kafka_reference.Diverged as e:
                disagreeing += 1
                parted += bool(want)
                notes.append(f"seed {s}: {e}")
                continue
            peak = max(peak, ref.peak_pending)
            deliveries += ref.deliveries
            overflow += ref.overflow
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
                    ("nodes", [
                        i for i in range(n)
                        if ref.spawns[i] and node_digest(
                            kafka_reference, ref, i, n
                        ) != row_digest(rows[i], i, n, log_cap)
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
            "epoch_overflow": overflow,
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
        raise SystemExit("kafka_reference_on_chip.py: no TPU; nothing was run")
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
