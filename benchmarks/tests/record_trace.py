#!/usr/bin/env python3
"""How ``data/recorded_trace.json`` was recorded (on the chip, PR 23):

    chiprun -- python3 benchmarks/tests/record_trace.py

Runs the tiny DPOR cell of ``tiny.py`` on the TPU under the profiler, as
``lib.harness.traced_jobs`` does, and writes the first few hundred device
operations of the trace, with the module executions and the benchmark's
spans that cover them, to ``chiprun_out/recorded_trace.json`` in the
neutral form that ``lib.trace.reduce_trace`` reads. Not a test."""

import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH, HERE]

KEEP_OPS = 400


def main() -> int:
    import jax

    import tiny
    from lib import cells, harness, jobs, trace as T

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    work = tempfile.mkdtemp(prefix="bench_rec_")
    cell = cells.load_cell(tiny.write(work), "tiny-dpor")
    verb = cells.load_verb(cell)
    ctx = verb.setup(cell, jax.local_devices()[:1])
    warm = jobs.warm_jobs(cell.traffic["panel"], 23)
    verb.run_job(ctx, warm[0])
    trace_dir = os.path.join(work, "trace")
    harness.traced_jobs(verb, ctx, cell, warm, trace_dir)
    planes = T.load_xplane(trace_dir)
    print(json.dumps({
        p["name"]: {ln["name"]: len(ln["events"]) for ln in p["lines"]}
        for p in planes
    }))
    device = next(p for p in planes if p["name"].startswith(T.DEVICE_PREFIX))
    ops = sorted(T._line(device, T.OPS_LINE), key=lambda e: e[1])[:KEEP_OPS]
    lo = ops[0][1] - 2e6
    hi = max(s + d for _n, s, d in ops) + 2e6

    def inside(e):
        return e[1] >= lo and e[1] + e[2] <= hi

    kept = {
        "name": device["name"],
        "lines": [
            {"name": T.OPS_LINE, "events": ops},
            {"name": T.MODULES_LINE,
             "events": [e for e in T._line(device, T.MODULES_LINE) if inside(e)]},
        ],
    }
    spans = [
        e for e in T.host_spans(planes)
        if e[0] != T.WINDOW_SPAN and e[1] < hi and e[1] + e[2] > lo
    ]
    spans.append([T.WINDOW_SPAN, lo, hi - lo])
    out = [kept, {"name": T.HOST_PLANE, "lines": [{"name": "python3", "events": spans}]}]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out", "recorded_trace.json")
    with open(path, "w") as f:
        json.dump(out, f)
    print("wrote", path, os.path.getsize(path), "bytes;",
          json.dumps(T.reduce_trace(out, verb.STEP_KERNEL))[:1500])
    return 0


if __name__ == "__main__":
    sys.exit(main())
