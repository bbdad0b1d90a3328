#!/usr/bin/env python3
"""The control that can fail on a cell whose invariant is judged at
quiescence only, at the cell's own size, on the chip (PR 31 ran this):

    chiprun -- python3 benchmarks/tests/every_delivery_control_on_chip.py bcast64-flood-sweep 11

``bcast64-flood`` states ``invariant_interval`` 0: agreement is false in
the middle of any flood, so it is judged only once a schedule is
quiescent. The stock ``controls.sweep_invariant_at_end`` sets exactly
that, which the cell already runs, and reads ``correct: true`` there.
This is the guarantee broken the other way: the sweep's kernels judge
after every delivery (``invariant_interval=1``), so every lane stops at
its first delivery with code 1, and the lifted lanes, re-run as the
configuration says, disagree. One whole run through ``lib.harness.run``
with a short window, which has to print ``correct: false``; exits 1 if
it does not. Not a test (``test_flood_cell.py`` has its tiny twin)."""

import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH, HERE]


def every_delivery(verb):
    """The control: the sweep's driver rebuilt to judge after every delivery."""
    import controls

    return controls._patch_setup(verb, lambda ctx: controls._rebuild_driver(
        ctx, dataclasses.replace(ctx.cfg, invariant_interval=1)
    ))


def main(argv) -> int:
    from lib import cells, harness

    workload, seed = argv[0], int(argv[1])
    bench = os.path.join(ROOT, "BENCHMARK.json")
    verb = cells.load_verb(cells.load_cell(bench, workload))
    undo = every_delivery(verb)
    lines = []
    try:
        result = harness.run(
            bench, workload, seed, 1.0, False, time.perf_counter(), log=lines.append
        )
    finally:
        undo()
    print(json.dumps({
        "control": "sweep_every_delivery", "seed": seed, "correct": result["correct"],
        "failed_checks": [ln for ln in lines if "FAILED" in ln],
    }), flush=True)
    return 1 if result["correct"] else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
