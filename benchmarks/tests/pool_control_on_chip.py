#!/usr/bin/env python3
"""``controls.sweep_small_pool`` at a pool you name, at a sweep cell's own
size, on the chip (PR 27 ran this):

    chiprun -- python3 benchmarks/tests/pool_control_on_chip.py raft5-nemesis-sweep 32 11

The stock control takes a quarter of the cell's pool. ``raft5-nemesis``
states a pool of 256 and its fullest schedule of 524,288 held 52 entries
(PERF.md, PR 27), so a quarter (64) overflows nothing and that control
reads ``correct: true`` there. This is the control that can fail on such a
cell: the guarantee "no lane is dropped" broken at a pool under the
fullest schedule. One whole run through ``lib.harness.run`` with a short
window, which has to print ``correct: false``; exits 1 if it does not. Not
a test (``test_nemesis_cell.py`` has its tiny twin)."""

import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH, HERE]


def sweep_pool(pool: int):
    """The control: the sweep's driver rebuilt over a pool of ``pool``."""
    import controls

    return lambda verb: controls._patch_setup(verb, lambda ctx: controls._rebuild_driver(
        ctx, dataclasses.replace(ctx.cfg, pool_capacity=pool)
    ))


def main(argv) -> int:
    from lib import cells, harness

    workload, pool, seed = argv[0], int(argv[1]), int(argv[2])
    bench = os.path.join(ROOT, "BENCHMARK.json")
    verb = cells.load_verb(cells.load_cell(bench, workload))
    undo = sweep_pool(pool)(verb)
    lines = []
    try:
        result = harness.run(
            bench, workload, seed, 1.0, False, time.perf_counter(), log=lines.append
        )
    finally:
        undo()
    print(json.dumps({
        "control": f"sweep_pool {pool}", "seed": seed, "correct": result["correct"],
        "failed_checks": [ln for ln in lines if "FAILED" in ln],
    }), flush=True)
    return 1 if result["correct"] else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
