#!/usr/bin/env python3
"""A ``--trace 1`` run of a cell with the rows of its jobs kept (PR 49):

    chiprun -- python3 benchmarks/tests/window_rows_on_chip.py raft5-dpor 7 [seconds]

Runs the cell as ``benchmarks/run.py --trace 1`` does (the same
``lib.harness.run``, the same result line as the last line of standard
output) and then writes the program's ``obs.job_ledger()`` (the traced
jobs' rows, the window's, the check's) with the harness's own log lines
(a window job's seconds, cpu and collector seconds) to
``chiprun_out/window_rows.<cell>.<seed>.json``: what the
``*.window_*`` per-layer metrics were read from, row by row, for the
questions a share summed over the window cannot answer (the slowest
search beside the median one, stage by stage). Not a test."""

import json
import os
import sys
import time

T_START = time.perf_counter()

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH, HERE]


def main(argv) -> int:
    from lib import harness

    workload, seed = argv[1], int(argv[2])
    seconds = float(argv[3]) if len(argv) > 3 else 40.0
    lines = []

    def log(line: str) -> None:
        lines.append(line)
        print(line, flush=True)

    try:
        result = harness.run(
            os.path.join(ROOT, "BENCHMARK.json"), workload, seed, seconds,
            True, T_START, log=log,
        )
    except harness.NoChip as e:
        print(f"window_rows_on_chip: {e}; nothing was run", file=sys.stderr)
        return 2
    from demi_tpu import obs

    out = {
        "cell": workload, "seed": seed, "result": result,
        "rows": obs.job_ledger() if hasattr(obs, "job_ledger") else None,
        "log": [ln for ln in lines if ln.startswith("[bench] job ")],
    }
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(
        ROOT, "chiprun_out", f"window_rows.{workload}.{seed}.json"
    )
    with open(path, "w") as f:
        json.dump(out, f)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
