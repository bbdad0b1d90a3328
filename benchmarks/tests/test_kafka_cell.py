"""``kafka5-acks-all-sweep`` (PR 52), rehearsed at tiny size on the CPU the
way ``tiny.write`` adds cells: the deployment of
``configs/kafka5-acks-all.json`` cut to 4 brokers and the controller (5
partitions), ``log_cap`` 8, 1,024 steps (the seeded bug bites in none of
some hundreds of lanes in 256) and a pool of 128,
under a traffic file shaped like ``traffic/fuzz-continuous-kafka.json``, as
one more cell of the tiny benchmark (``tiny-kafka4-sweep``; ``tiny.py``
itself is a file the benchmark has, so the cell is added here, as
``test_reconfig_cell.py`` adds its own). The three per-layer metrics the PR
brought, and ``sweep.commits_per_schedule`` and ``sweep.fifo_blocked_share``
over this app's counts, are read from the program's counts in the traced
run, on a hand-made counter table, and are absent, with no error, from a
program or an app that keeps no such counts (the PR's parent;
``apps/raft.py``). The plain reference replays the cell's own lanes. The real
files are looked up by name; nothing here pins the tail of ``per_layer`` or
the full list of sweep cells."""

import json
import os
import time

import pytest

import controls
import tiny
from lib import cells, harness, kafka_reference

CELL = "tiny-kafka4-sweep"
REAL_CELL = "kafka5-acks-all-sweep"
METRICS = ("sweep.elections_per_schedule", "sweep.isr_changes_per_schedule",
           "sweep.truncated_per_schedule")
COUNT_OF = dict(zip(METRICS, ("elections", "isr_changes", "truncated")))
SHARED = ("sweep.commits_per_schedule", "sweep.fifo_blocked_share")


def real(relative):
    with open(os.path.join(tiny.BENCH, relative), encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("tiny"))
    path = tiny.write(tmp)
    config = real("configs/kafka5-acks-all.json")
    config["workload"].update(
        nodes=5, log_cap=8, max_messages=1024, pool=128,
    )
    config["shapes"].update(
        num_actors=5, state_width=280, msg_width=50, max_outbox=16,
        pool_capacity=128, max_steps=1024, max_external_ops=103,
    )
    traffic = real("traffic/fuzz-continuous-kafka.json")
    traffic["job"].update(schedules=192, resident_lanes_per_chip=64)
    traffic["trace_seconds"] = 0.01
    traffic["check"] = {"lift_violating": 2, "lift_clean": 2}
    for sub, name, body in (
        ("configs", "tiny-kafka4", config),
        ("traffic", "tiny-fuzz-kafka", traffic),
    ):
        with open(os.path.join(tmp, "extra", sub, name + ".json"), "w") as f:
            json.dump(body, f)
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-kafka4", "file": "extra/configs/tiny-kafka4.json",
    })
    bench["workloads"].append({
        "name": CELL, "config": "tiny-kafka4", "traffic": "tiny-fuzz-kafka",
        "chips": 1,
    })
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if metric["name"] in METRICS + SHARED:
            metric["workloads"] = [CELL]
        elif "tiny-sweep" in metric.get("workloads", ()):
            metric["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return path


def run(bench, trace, cell=CELL, seed=2**31 + 5252):
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
    for name, count in COUNT_OF.items():
        assert metrics[name] == pytest.approx(
            counts["sweep.app." + count] / counts["sweep.retired"]
        ), name
    assert metrics["sweep.commits_per_schedule"] == pytest.approx(
        counts["sweep.app.committed"] / counts["sweep.retired"]
    )
    assert counts["sweep.app.restores"] > 0 and counts["sweep.app.fenced"] > 0
    assert metrics["sweep.elections_per_schedule"] > 3
    assert metrics["sweep.isr_changes_per_schedule"] > 0.05
    assert metrics["sweep.truncated_per_schedule"] > 0
    assert metrics["sweep.commits_per_schedule"] > 0
    assert 0 <= metrics["sweep.fifo_blocked_share"] < 100   # FIFO links
    assert "sweep.insert_short_share" not in metrics   # an outbox of 16 rows
    assert metrics["sweep.fault_op_share"] > 10


def test_a_raft_cell_reports_none_of_them(bench):
    """The readers on a program whose app names no progress count: absent,
    no error. (The tiny benchmark lists them for this cell alone; here
    each reader is called after a traced run of ``apps/raft.py``'s cell.)"""
    from demi_tpu import obs

    obs.TRACER.clear()
    result, lines = run(bench, trace=True, cell="tiny-sweep", seed=2**31 + 5253)
    assert result["correct"] is True, lines
    assert not set(METRICS) & set(result["metrics"])
    cell = cells.load_cell(bench, CELL)
    assert "sweep.job" in obs.stage_totals()
    for name in METRICS:
        assert cells.load_reader(cell, name)(None) is None, name


@pytest.mark.parametrize("name,counts,value", [
    ("sweep.elections_per_schedule",
     {"sweep.app.elections": 220, "sweep.retired": 8}, 27.5),
    ("sweep.isr_changes_per_schedule",
     {"sweep.app.isr_changes": 166, "sweep.retired": 8}, 20.75),
    ("sweep.truncated_per_schedule",
     {"sweep.app.truncated": 74, "sweep.retired": 8}, 9.25),
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
    from kafka_reference_on_chip import reference_check

    report = reference_check(bench, CELL, 2**31 + 5254, lanes=16, require_tpu=False)
    assert report["lanes"] == 16 and report["disagreeing"] == 0, report
    assert report["job_overflow"] == 0 and report["job_lanes"] == 192
    per_lane = report["per_lane"]
    assert per_lane["elections"] > 3 and per_lane["fenced"] > 0.5
    assert per_lane["restores"] > 0.5 and 8 < report["peak_pending"] <= 128
    assert report["epoch_overflow"] == 0


def test_the_fixed_protocol_violates_in_no_lane_of_a_job(bench):
    from kafka_reference_on_chip import fixed_control

    report = fixed_control(bench, CELL, 2**31 + 5254, require_tpu=False)
    assert report == {
        "workload": CELL, "seed": 2**31 + 5254, "bug": None, "lanes": 192,
        "violations": 0, "overflow": 0, "device": "cpu",
    }


def test_the_reference_is_plain():
    with open(kafka_reference.__file__, encoding="utf-8") as f:
        code = f.read().split('"""')[2]
    assert "import jax" not in code and "demi_tpu" not in code
    assert "numpy" not in code


def test_the_real_entries_are_there():
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kafka5-acks-all", "fuzz-continuous-kafka", 1
    )
    assert len(cell["why"]) <= 200
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, layer, unit in zip(
        METRICS, ("entry point / harness", "entry point / harness", "kernels"),
        ("1/schedule", "1/schedule", "records/schedule"),
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
    # every metric that lists the reconfiguring raft's cell as one of the
    # sweep cells (and not as its own) lists this one too
    own = ("sweep.reconfigs_per_schedule", "sweep.snapshot_installs_per_schedule",
           "sweep.compactions_per_schedule")
    for name, metric in by_name.items():
        if "raft7-reconfig-sweep" in metric.get("workloads", ()) and name not in own:
            assert REAL_CELL in metric["workloads"], name
    for name in SHARED:
        assert REAL_CELL in by_name[name]["workloads"], name
    for name in ("sweep.insert_short_share", "sweep.insert_full_lane_share"):
        assert REAL_CELL not in by_name[name]["workloads"]
    rate = next(m for m in bench["end_to_end"] if m["name"] == "schedules_per_s")
    assert REAL_CELL in rate["workloads"]
    entry = next(c for c in bench["configs"] if c["name"] == "kafka5-acks-all")
    config = real("configs/kafka5-acks-all.json")
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert 1 <= len(entry["why"]) <= 200 and entry["why"].isprintable()
    assert entry["source"].startswith("Apache Kafka design doc 4.7 Replication; KIP-101")
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == [
        "chips", "log_cap", "max_messages", "partitions", "schedules",
    ]
    assert config["architecture"] is None
    for key in ("deployment", "workload", "shapes", "state_layout",
                "message_kinds", "rules", "departures_from_the_sources",
                "reduced", "assumed", "guarantees", "chips"):
        assert config[key], key
    workload = config["workload"]
    assert (workload["app"], workload["nodes"], workload["log_cap"],
            workload["bug"]) == ("kafka", 6, 24, "truncate_to_hw")
    assert 0.2 <= workload["timer_weight"] <= 0.5
    assert 0.05 <= workload["hard_kill_weight"] <= 0.12
    assert 0.08 <= workload["restart_weight"] <= 0.15
    assert workload["num_events"] == 96 and workload["pool"] in (256, 512)
    assert workload["max_messages"] == 2048
    assert (workload["send_weight"], workload["wait_weight"],
            workload["partition_weight"], workload["kill_weight"],
            workload["max_kills"], workload["wait_budget"]) == (
        0.4, 0.28, 0.04, 0.0, 4, [1, 40]
    )
    assert any("durable" in g for g in config["guarantees"])
    assert len(config["message_kinds"]) == 8
    job = real("traffic/fuzz-continuous-kafka.json")
    assert job["job"] in (
        {"schedules": 4096, "resident_lanes_per_chip": 2048, "mode": "continuous"},
        {"schedules": 2048, "resident_lanes_per_chip": 1024, "mode": "continuous"},
        {"schedules": 1024, "resident_lanes_per_chip": 512, "mode": "continuous"},
    )
    assert job["check"] == {"lift_violating": 8, "lift_clean": 16}
    reconfig = real("traffic/fuzz-continuous-reconfig.json")
    same = ("verb", "panel", "seed_changes", "seed_keeps", "end_to_end",
            "trace_seconds", "check")
    assert all(job[k] == reconfig[k] for k in same)


def test_the_real_shapes_are_what_the_program_builds():
    import dataclasses

    from demi_tpu.apps import kafka as kf
    from demi_tpu.parallel.distributed import build_workload

    config = real("configs/kafka5-acks-all.json")
    app, cfg, _fuzzer = build_workload(dict(config["workload"]))
    have = dataclasses.asdict(cfg)
    assert {k: have[k] for k in config["shapes"]} == config["shapes"]
    assert len(app.durable) == 308 and app.unkillable == (5,)
    assert kf.state_layout(6, 24)["F_LEO"] == (350, 20)
    assert str(kf.SESSION_MISSES) in config["assumed"]["session_misses"][:2]
    assert str(kf.LAG_MISSES) in config["assumed"]["lag_misses"][:2]
