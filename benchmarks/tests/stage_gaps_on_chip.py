#!/usr/bin/env python3
"""The device's idle gaps by the program's own stage, on the chip (PR 24):

    chiprun -- python3 benchmarks/tests/stage_gaps_on_chip.py raft5-dpor 7

Runs the cell's set-up and one warm job, traces whole jobs as
``lib.harness.traced_jobs`` does, and reduces the trace with the span
filter widened from the harness's own ``bench.`` spans to the program's
``demi.<stage>`` annotations (what a later ``benchmark`` change to
``lib/trace.py: SPAN_PREFIX`` will make the ledger's ``idle_gaps`` read).
Prints the idle gaps by innermost ``demi.*`` stage, the stage totals and
counts the traced jobs left, and writes them to
``chiprun_out/stage_gaps.<cell>.json``. Not a test."""

import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH, HERE]


def main(argv) -> int:
    import jax

    from demi_tpu import obs
    from lib import cells, harness, jobs, trace as T

    workload, seed = argv[1], int(argv[2])
    if jax.devices()[0].platform != "tpu":
        print("stage_gaps_on_chip: needs a TPU", file=sys.stderr)
        return 2
    cell = cells.load_cell(os.path.join(ROOT, "BENCHMARK.json"), workload)
    verb = cells.load_verb(cell)
    ctx = verb.setup(cell, jax.local_devices()[: cell.chips])
    warm = jobs.warm_jobs(cell.traffic["panel"], seed)
    for job in warm:
        verb.run_job(ctx, job)
    obs.TRACER.clear()
    trace_dir = tempfile.mkdtemp(prefix="bench_gaps_")
    records = harness.traced_jobs(verb, ctx, cell, warm, trace_dir)
    # startswith takes a tuple: the program's stages and the window span
    T.SPAN_PREFIX = ("demi.", T.WINDOW_SPAN)
    reduced = T.reduce_trace(T.load_xplane(trace_dir), verb.STEP_KERNEL)
    out = {
        "cell": workload, "seed": seed, "traced_jobs": len(records),
        "window_s": reduced["window_s"], "busy_s": reduced["busy_s"],
        "idle_gaps_by_stage": reduced["breakdown"]["idle_gaps"],
        "stage_totals": obs.stage_totals(),
        "stage_counts": obs.stage_counts(),
    }
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out", f"stage_gaps.{workload}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    verb.close(ctx)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
