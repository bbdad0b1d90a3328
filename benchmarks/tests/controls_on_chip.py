#!/usr/bin/env python3
"""The controls at a cell's own size, on the chip (PR 23 ran this; the
benchmark's own runs never do):

    chiprun -- python3 benchmarks/tests/controls_on_chip.py raft5-dpor 11 12 13

For every seed given: a whole run of the sound program through
``lib.harness.run`` with a short window (one pass of jobs), which has to
print ``correct: true``; then the same with each control of the cell's verb
in the program's place, which has to print ``correct: false``. One
process, so set-up's compilations are shared. ``--sound-only`` leaves the
controls out (more seeds of the sound program for the same chip time).
Exits 1 if a sound run was not correct or a control came out correct. Not
a test."""

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH, HERE]


def main(argv) -> int:
    import controls
    from lib import cells, harness

    sound_only = "--sound-only" in argv
    argv = [a for a in argv if a != "--sound-only"]
    workload, seeds = argv[0], [int(s) for s in argv[1:]]
    bench = os.path.join(ROOT, "BENCHMARK.json")
    cell = cells.load_cell(bench, workload)
    verb = cells.load_verb(cell)
    escaped = 0
    todo = [None] + ([] if sound_only else controls.CONTROLS[cell.traffic["verb"]])
    for control in todo:
        for seed in seeds:
            undo = control(verb) if control else (lambda: None)
            lines = []
            try:
                result = harness.run(
                    bench, workload, seed, 1.0, False, time.perf_counter(),
                    log=lines.append,
                )
                verdict = result["correct"]
                detail = [ln for ln in lines if "FAILED" in ln]
            except Exception as e:  # a control that crashes has failed too
                verdict, detail = False, [f"crashed: {type(e).__name__}: {e}"]
            finally:
                undo()
            escaped += bool(verdict) == (control is not None)
            print(json.dumps({
                "control": control.__name__ if control else "(sound)", "seed": seed,
                "correct": verdict, "failed_checks": detail,
            }), flush=True)
    return 1 if escaped else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
