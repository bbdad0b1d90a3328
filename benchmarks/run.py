#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

run from the root of a checkout on the machine that holds the chip(s). It
runs one cell of ``BENCHMARK.json`` in this one process, which holds the
chip(s), and prints as the last line of standard output one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, when
traced, ``breakdown``. It exits 2 and prints no result unless JAX's first
device is a TPU and there are as many chips as the cell asks for.
"""

import time

T_START = time.perf_counter()  # set-up counts from here: imports included

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "demi_tpu")):
        print(
            "benchmarks/run.py: the program (demi_tpu/) is not in this "
            "checkout; nothing to measure", file=sys.stderr,
        )
        return 3
    sys.path.insert(0, ROOT)   # the program
    sys.path.insert(0, HERE)   # lib/
    from lib import harness

    try:
        result = harness.run(
            os.path.join(ROOT, "BENCHMARK.json"), args.workload, args.seed,
            args.seconds, bool(args.trace), T_START,
        )
    except harness.NoChip as e:
        print(f"benchmarks/run.py: {e}; nothing was run", file=sys.stderr)
        return 2
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
