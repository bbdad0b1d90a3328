"""A tiny benchmark in a temporary directory, made the way a later PR
adds cells: a new directory of its own with configuration and traffic
files and new entries, ahead of the real ``benchmarks/`` in ``paths``.
No file of the benchmark is edited or copied."""

from __future__ import annotations

import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

RAFT3 = {
    "app": "raft", "nodes": 3, "bug": "multivote", "seed": 0, "num_events": 12,
    "max_messages": 64, "pool": 48, "timer_weight": 0.2, "kill_weight": 0.05,
    "partition_weight": 0.0,
}
CONFIGS = {
    "tiny-raft3": {
        "workload": RAFT3,
        "shapes": {"num_actors": 3, "state_width": 30, "msg_width": 7,
                   "pool_capacity": 48, "max_steps": 64},
    },
    "tiny-raft3-min": {
        "workload": dict(RAFT3, num_events=6, max_messages=96),
        "shapes": {"num_actors": 3},
    },
}
TRAFFIC = {
    "tiny-fuzz": {
        "verb": "sweep",
        "job": {"schedules": 96, "resident_lanes_per_chip": 32, "mode": "continuous"},
        "panel": {"from": "seed", "size": 1},
        "end_to_end": {"schedules_per_s": "mean_rate"},
        "trace_seconds": 0.01,
        "check": {"lift_violating": 2, "lift_clean": 2, "one_chip_slice": 16},
    },
    "tiny-dpor": {
        "verb": "dpor", "job": {"batch": 32, "rounds": 8},
        "check": {"host_executed_prescriptions": 6},
        "panel": {"from": "seed", "size": 1},
        "expect": {"interleavings": 256, "explored": 2916, "frontier": 2691,
                   "rounds": 8, "violation_codes": [1], "found_trace_len": 33},
        "end_to_end": {"interleavings_per_s": "mean_rate"},
        "trace_seconds": 0.01,
    },
    "tiny-mcs": {
        "verb": "minimize", "job": {"fuzz_executions": 200},
        "panel": {"from": "fixed", "seeds": [0], "warm": 1},
        "expect": {"0": {"mcs_externals": 4}},
        "end_to_end": {"mcs_s": "mean_job_s"},
        "trace_seconds": 0.01,
    },
}
CELLS = [
    ("tiny-sweep", "tiny-raft3", "tiny-fuzz", 1),
    ("tiny-dpor", "tiny-raft3", "tiny-dpor", 1),
    ("tiny-minimize", "tiny-raft3-min", "tiny-mcs", 1),
    ("tiny-sweep-x4", "tiny-raft3", "tiny-fuzz", 4),
]
VERB_OF = {"tiny-sweep": "sweep", "tiny-dpor": "dpor",
           "tiny-minimize": "minimize", "tiny-sweep-x4": "sweep"}
# The minimize verb has no cell in BENCHMARK.json yet (PERF.md, Open
# questions): its metrics are entries that a later PR adds, as here.
MINIMIZE_END_TO_END = [
    {"name": "mcs_s", "unit": "s", "better": "lower", "bound": 0.06,
     "source": "host_clock", "workloads": ["raft3-minimize"]},
]
MINIMIZE_PER_LAYER = [
    {"name": f"minimize.{name}", "unit": unit, "better": better, "source": source,
     "layer": layer, "moves": "mcs_s", "workloads": ["raft3-minimize"]}
    for name, unit, better, source, layer in (
        ("window_compiles", "count", "lower", "program_counter", "entry point / harness"),
        ("trace_lower_share", "%", "lower", "program_counter", "entry point / harness"),
        ("job_cv", "%", "lower", "host_clock", "drivers (host)"),
        ("replays_per_s", "replays/s", "higher", "program_counter", "host oracle"),
        ("kernel_ns_per_lane_step", "ns", "lower", "device_trace", "kernels"),
        ("device_idle_share", "%", "lower", "device_trace", "device"),
        ("device_peak_hbm_mb", "MB", "lower", "program_counter", "device"),
    )
]


def write(tmp: str) -> str:
    """Write the tiny benchmark under ``tmp``; returns its BENCHMARK.json."""
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        real = json.load(f)
    extra = os.path.join(tmp, "extra")
    for sub, table in (("configs", CONFIGS), ("traffic", TRAFFIC)):
        os.makedirs(os.path.join(extra, sub), exist_ok=True)
        for name, body in table.items():
            with open(os.path.join(extra, sub, name + ".json"), "w") as f:
                json.dump(body, f)

    def retarget(metric):
        # a real metric lists the tiny cells of the verbs its own cells run
        if "workloads" not in metric:
            return metric
        verbs = {"minimize" if "minimize" in w else "dpor" if "dpor" in w else "sweep"
                 for w in metric["workloads"]}
        return dict(metric, workloads=[c for c, v in VERB_OF.items() if v in verbs])

    bench = dict(
        real,
        paths=["extra", BENCH],
        configs=[{"name": n, "file": f"extra/configs/{n}.json"} for n in CONFIGS],
        workloads=[
            {"name": n, "config": c, "traffic": t, "chips": k}
            for n, c, t, k in CELLS
        ],
        end_to_end=[retarget(m) for m in real["end_to_end"] + MINIMIZE_END_TO_END],
        per_layer=[retarget(m) for m in real["per_layer"] + MINIMIZE_PER_LAYER],
    )
    path = os.path.join(tmp, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(bench, f)
    return path
