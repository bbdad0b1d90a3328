#!/usr/bin/env python3
"""The program's set-up and compile ledger of one run of a cell, on the
chip (PR 36):

    chiprun -- python3 benchmarks/tests/setup_ledger_on_chip.py raft5-sweep 7 [seconds] [trace]

One process, as ``benchmarks/run.py`` is one: the clock starts at the top
of this file, ``lib.harness.run`` runs the cell (traced unless ``trace``
is 0, a window of ``seconds``, 40 by default), and the result line is
printed as ``run.py`` prints it. Then what the ``setup.*`` readers cannot
put in a result line: what came before the program split into
``import jax`` and the client coming up (``jax.devices()``), the ledger's
stages and their disjoint parts beside
the harness's own ``setup_s``, the compile table's slowest functions, the
functions the persistent cache did not serve, and the functions of the
events that came after the warm job. The whole of both ledgers goes to
``chiprun_out/setup_ledger/<cell>.<seed>.<pid>.json``. Not a test."""

import time

T_START = time.perf_counter()

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]


def _row_seconds(row: dict) -> float:
    return row["trace_s"] + row["lower_s"] + row["compile_s"] + row["cache_load_s"]


def main(argv) -> int:
    # What the program's ledger can only call pre-program, apart: the
    # harness does these two itself, and finds them done.
    t0 = time.perf_counter()
    import jax

    t1 = time.perf_counter()
    jax.devices()
    t2 = time.perf_counter()
    print(f"[ledger] before the program: this file's top to here "
          f"{t0 - T_START:.3f} s, import jax {t1 - t0:.3f} s, "
          f"jax.devices() {t2 - t1:.3f} s", flush=True)
    from lib import harness, setup_ledger

    workload, seed = argv[1], int(argv[2])
    seconds = float(argv[3]) if len(argv) > 3 else 40.0
    trace = bool(int(argv[4])) if len(argv) > 4 else True
    lines = []

    def log(line: str) -> None:
        lines.append(line)
        print(line, flush=True)

    try:
        result = harness.run(
            os.path.join(ROOT, "BENCHMARK.json"), workload, seed, seconds,
            trace, T_START, log=log,
        )
    except harness.NoChip as e:
        print(f"setup_ledger_on_chip: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)

    from demi_tpu import obs

    found, table = obs.setup_ledger(), obs.compile_ledger()
    setup_s = next(
        (float(l.split()[2]) for l in lines if l.startswith("[bench] setup_s")),
        None,
    )
    age = found["first_job"]["end_s"]
    parts = setup_ledger.disjoint(found)
    summary = {
        "cell": workload, "seed": seed, "traced": trace,
        "harness_setup_s": setup_s,
        "age_at_first_job_end_s": age,
        "disjoint_parts_s": parts,
        "unattributed_s": age - sum(parts.values()),
        "stages": found["stages"],
        "compile_at_first_job_end": found["compile"],
    }
    print("[ledger] " + json.dumps(summary), flush=True)
    for entry in found["timeline"]:
        if entry["seconds"] >= 0.05:
            print(f"[ledger] timeline {entry['start_s']:9.3f} s  "
                  f"{entry['seconds']:8.3f} s  {entry['name']} {entry['args']}")
    rows = sorted(
        table["functions"].items(), key=lambda kv: -_row_seconds(kv[1])
    )
    for fun, row in rows[:6]:
        print(f"[ledger] slowest {fun}: {json.dumps(row)}")
    for fun, row in rows:
        if row["compiles"]:
            print(f"[ledger] not served by the cache: {fun} x{row['compiles']} "
                  f"{row['compile_s']:.3f} s")
    # jitted functions only: one that was only ever traced inside them
    # (jnp's own) has no lowering of its own
    late = sorted(
        ((row["late"], fun) for fun, row in rows
         if row["late"] and row["lowerings"]), reverse=True,
    )
    for n, fun in late[:10]:
        print(f"[ledger] after the warm job: {fun} x{n} events")
    print("[ledger] total " + json.dumps(table["total"]), flush=True)
    out = os.path.join(ROOT, "chiprun_out", "setup_ledger")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"{workload}.{seed}.{os.getpid()}.json"), "w") as f:
        json.dump({"summary": summary, "setup": found, "compile": table}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
