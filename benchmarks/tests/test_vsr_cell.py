"""``vsr5-recovery-sweep`` (PR 38), rehearsed at tiny size on the CPU the way
``tiny.write`` adds cells: the deployment of ``configs/vsr5-recovery.json``
cut to 3 replicas (f = 1), ``log_cap`` 4 (a message is 9 words), 256
deliveries, 24 fuzzed events and a pool of 48, under a traffic file shaped
like ``traffic/fuzz-continuous-vsr.json``, as one more cell of the tiny
benchmark (``tiny-vsr3-sweep``; ``tiny.py`` itself is a file the benchmark
has, so the cell is added here, as ``test_spark_cell.py`` adds its own). The
three per-layer metrics the PR brought are read from the program's counts in
the traced run, on a hand-made counter table, and are absent, with no error,
from a program or an app that keeps no such counts (the PR's parent; raft).
The plain reference replays the cell's own lanes; its control, the protocol
as published, must part from the program's seeded bug. The real files are
held to what ISSUE 38 fixed; nothing here pins the tail of ``per_layer`` or
the full list of sweep cells."""

import json
import os
import time

import pytest

import controls
import tiny
from lib import cells, harness, vsr_reference

CELL = "tiny-vsr3-sweep"
REAL_CELL = "vsr5-recovery-sweep"
METRICS = ("sweep.log_row_share", "sweep.views_per_schedule",
           "sweep.recovered_share")


def real(relative):
    with open(os.path.join(tiny.BENCH, relative), encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("tiny"))
    path = tiny.write(tmp)
    config = real("configs/vsr5-recovery.json")
    config["workload"].update(
        nodes=3, log_cap=4, num_events=24, max_messages=256, pool=48,
        max_kills=1,
    )
    config["shapes"].update(
        num_actors=3, state_width=35, msg_width=9, max_outbox=3,
        pool_capacity=48, max_steps=256, max_external_ops=29,
    )
    traffic = real("traffic/fuzz-continuous-vsr.json")
    traffic["job"].update(schedules=192, resident_lanes_per_chip=64)
    traffic["trace_seconds"] = 0.01
    traffic["check"] = {"lift_violating": 2, "lift_clean": 2}
    for sub, name, body in (
        ("configs", "tiny-vsr3", config),
        ("traffic", "tiny-fuzz-vsr", traffic),
    ):
        with open(os.path.join(tmp, "extra", sub, name + ".json"), "w") as f:
            json.dump(body, f)
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-vsr3", "file": "extra/configs/tiny-vsr3.json",
    })
    bench["workloads"].append({
        "name": CELL, "config": "tiny-vsr3", "traffic": "tiny-fuzz-vsr",
        "chips": 1,
    })
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if metric["name"] in METRICS:
            metric["workloads"] = [CELL]
        elif "tiny-sweep" in metric.get("workloads", ()):
            metric["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return path


def run(bench, trace, cell=CELL, seed=2**31 + 3838):
    lines = []
    result = harness.run(
        bench, cell, seed, 0.3, trace, time.perf_counter(),
        require_tpu=False, log=lines.append,
    )
    return result, lines


def test_the_cell_runs_end_to_end_on_cpu(bench):
    result, lines = run(bench, trace=False)
    assert result["correct"] is True and result["failed"] == 0, lines
    assert result["attempted"] >= 192
    assert set(result["metrics"]) == {"schedules_per_s", "setup_s"}
    assert sum("check " in ln and "(limit " in ln for ln in lines) >= 3


def test_the_traced_run_reports_the_new_metrics(bench):
    from demi_tpu import obs

    obs.TRACER.clear()
    result, lines = run(bench, trace=True)
    assert result["correct"] is True, lines
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    counts = obs.stage_counts()
    assert metrics["sweep.row_lowered_share"] == 100.0
    assert metrics["sweep.log_row_share"] == pytest.approx(
        100.0 * counts["sweep.app.log_rows"] / counts["sweep.rows_inserted"]
    )
    assert metrics["sweep.views_per_schedule"] == pytest.approx(
        counts["sweep.app.views"] / counts["sweep.retired"]
    )
    assert metrics["sweep.recovered_share"] == pytest.approx(
        100.0 * counts["sweep.app.recovered"] / counts["sweep.app.recoveries"]
    )
    assert 0 < metrics["sweep.log_row_share"] < 50
    assert metrics["sweep.views_per_schedule"] >= 1
    assert 0 < metrics["sweep.recovered_share"] <= 100
    assert "sweep.insert_short_share" not in metrics   # an outbox of 3 rows
    assert metrics["sweep.fault_op_share"] > 20


def test_a_raft_cell_reports_none_of_them(bench):
    """The readers on a program whose app names no progress count: absent,
    no error. (The tiny benchmark lists them for the VSR cell alone; here
    each reader is called after a raft cell's traced run.)"""
    from demi_tpu import obs

    obs.TRACER.clear()
    result, lines = run(bench, trace=True, cell="tiny-sweep", seed=2**31 + 3839)
    assert result["correct"] is True, lines
    assert not set(METRICS) & set(result["metrics"])
    cell = cells.load_cell(bench, CELL)
    assert "sweep.job" in obs.stage_totals()
    for name in METRICS:
        assert cells.load_reader(cell, name)(None) is None, name


@pytest.mark.parametrize("name,counts,value", [
    ("sweep.log_row_share",
     {"sweep.app.log_rows": 150, "sweep.rows_inserted": 1000}, 15.0),
    ("sweep.views_per_schedule",
     {"sweep.app.views": 72, "sweep.retired": 8}, 9.0),
    ("sweep.recovered_share",
     {"sweep.app.recovered": 3, "sweep.app.recoveries": 12}, 25.0),
])
def test_a_reader_on_a_hand_made_counter_table(bench, name, counts, value):
    from demi_tpu import obs

    read = cells.load_reader(cells.load_cell(bench, CELL), name)
    obs.TRACER.clear()
    assert read(None) is None   # no tables' root: the parent's untraced run
    obs.enable()
    try:
        with obs.span("sweep.job"):  # the parent's traced job: the root, no count
            obs.stage_count("sweep.retired", 8)
            obs.stage_count("sweep.rows_inserted", 1000)
        assert read(None) is None
        obs.TRACER.clear()
        with obs.span("sweep.job"):
            for key, n in counts.items():
                obs.stage_count(key, n)
        assert read(None) == pytest.approx(value)
    finally:
        obs.disable()
        obs.TRACER.clear()


@pytest.mark.parametrize("control,correct", [
    (controls.sweep_small_pool, False),
    (controls.sweep_corrupt_codes, False),
])
def test_the_stock_controls_on_this_cell(bench, control, correct):
    undo = control(cells.load_verb(cells.load_cell(bench, CELL)))
    try:
        result, lines = run(bench, trace=False)
    finally:
        undo()
    assert result["correct"] is correct, lines


def test_the_plain_reference_agrees_on_the_cells_own_lanes(bench):
    from vsr_reference_on_chip import reference_check

    report = reference_check(bench, CELL, 2**31 + 3840, lanes=16, require_tpu=False)
    assert report["lanes"] == 16 and report["disagreeing"] == 0, report
    assert report["job_overflow"] == 0 and report["violating"] >= 1
    assert report["views"] >= 8 and 12 < report["peak_pending"] <= 48


def test_the_protocol_as_published_parts_from_the_seeded_bug(bench):
    from vsr_reference_on_chip import reference_check

    report = reference_check(
        bench, CELL, 2**31 + 3840, lanes=16, require_tpu=False, control=True
    )
    assert report["control"] is True and report["violating"] >= 1
    assert report["disagreeing"] >= 1, report


def test_the_reference_is_plain():
    with open(vsr_reference.__file__, encoding="utf-8") as f:
        code = f.read().split('"""')[2]
    assert "import jax" not in code and "demi_tpu" not in code
    assert "numpy" not in code


def test_the_real_entries_are_what_the_issue_fixed():
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "vsr5-recovery", "fuzz-continuous-vsr", 1
    )
    assert len(cell["why"]) <= 200
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, unit, layer in zip(
        METRICS, ("%", "views/schedule", "%"),
        ("kernels", "entry point / harness", "entry point / harness"),
    ):
        new = by_name[name]
        assert new["workloads"] == [REAL_CELL], name
        assert (new["moves"], new["source"], new["better"], new["unit"],
                new["layer"]) == (
            "schedules_per_s", "program_counter", "higher", unit, layer
        )
        assert os.path.exists(
            os.path.join(tiny.BENCH, "layer_metrics", name + ".py")
        )
    for name, metric in by_name.items():
        shared = (
            name.startswith(("sweep.", "setup."))
            or name == "explore_segment_roofline"
        )
        if shared and name not in METRICS + ("sweep.insert_short_share",):
            assert REAL_CELL in metric["workloads"], name
    assert REAL_CELL not in by_name["sweep.insert_short_share"]["workloads"]
    rate = next(m for m in bench["end_to_end"] if m["name"] == "schedules_per_s")
    assert REAL_CELL in rate["workloads"]
    entry = next(c for c in bench["configs"] if c["name"] == "vsr5-recovery")
    config = real("configs/vsr5-recovery.json")
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["source"].startswith(
        "Liskov & Cowling, Viewstamped Replication Revisited, MIT-CSAIL-TR-2012-021"
    )
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == [
        "chips", "log_cap", "max_messages", "schedules",
    ]
    assert config["architecture"] is None
    workload = config["workload"]
    assert (workload["app"], workload["nodes"], workload["log_cap"]) == (
        "vsr", 5, 32
    )
    assert workload["bug"] in ("recover_any", "dvc_by_opnum")
    assert (workload["num_events"], workload["max_messages"]) == (48, 1024)
    assert workload["pool"] in (256, 512) and workload["max_kills"] == 2
    assert (workload["timer_weight"], workload["send_weight"],
            workload["wait_weight"], workload["hard_kill_weight"],
            workload["restart_weight"], workload["partition_weight"],
            workload["kill_weight"]) == (0.05, 0.15, 0.35, 0.15, 0.25, 0.1, 0.0)
    assert workload["wait_budget"] == [1, 25]
    assert config["shapes"] == {
        "num_actors": 5, "state_width": 121, "msg_width": 37, "max_outbox": 5,
        "pool_capacity": workload["pool"], "max_steps": 1024,
        "max_external_ops": 55, "invariant_interval": 1, "msg_dtype": "int32",
    }
    assert set(config["assumed"]) <= set(workload)
    assert all(workload[k] == v for k, v in config["assumed"].items())
    assert len(config["departures_from_the_paper"]) >= 5 and config["guarantees"]
    assert any("durable" in g for g in config["guarantees"])
    vsr = real("traffic/fuzz-continuous-vsr.json")
    assert vsr["job"] in (
        {"schedules": 8192, "resident_lanes_per_chip": 4096, "mode": "continuous"},
        {"schedules": 4096, "resident_lanes_per_chip": 2048, "mode": "continuous"},
    )
    assert vsr["check"] == {"lift_violating": 8, "lift_clean": 16}
    deep = real("traffic/fuzz-continuous-deep.json")
    same = ("verb", "panel", "seed_changes", "seed_keeps", "end_to_end",
            "trace_seconds", "check")
    assert all(vsr[k] == deep[k] for k in same)


def test_the_real_shapes_are_what_the_program_builds():
    import dataclasses

    from demi_tpu.parallel.distributed import build_workload

    config = real("configs/vsr5-recovery.json")
    _app, cfg, _fuzzer = build_workload(dict(config["workload"]))
    have = dataclasses.asdict(cfg)
    assert {k: have[k] for k in config["shapes"]} == config["shapes"]
