"""``spark17-shuffle200-sweep`` (PR 33), rehearsed at tiny size on the CPU the
way ``tiny.write`` adds cells: the deployment of
``configs/spark17-shuffle200.json`` cut to 4 executors and 3 stages of 40
tasks (a stage's mask crosses a word; one job is 481 messages), 512 steps and
a pool of 256, under a traffic file shaped like
``traffic/fuzz-continuous-dag.json``, as one more cell of the tiny benchmark
(``tiny-spark5-sweep``). The per-layer metric the PR brought is read from the
program's counts in the traced run, on a hand-made counter table, and is
absent, with no error, from a program that keeps no such counts (the PR's
parent). The plain reference replays the cell's own lanes and holds the timed
kernel's final states; its control, the reference without its epoch check,
must part from the timed path on each of three seeds. The real files are held
to what ISSUE 33 fixed."""

import json
import os
import time

import pytest

import controls
import tiny
from lib import cells, dag_reference, harness

CELL = "tiny-spark5-sweep"
REAL_CELL = "spark17-shuffle200-sweep"
METRIC = "sweep.outbox_fill_share"
SWEEP_CELLS = ["raft5-sweep", "raft5-nemesis-sweep", "raft5-sweep-x4",
               "bcast64-flood-sweep", REAL_CELL]


def real(relative):
    with open(os.path.join(tiny.BENCH, relative), encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("tiny"))
    path = tiny.write(tmp)
    config = real("configs/spark17-shuffle200.json")
    config["workload"].update(
        nodes=5, stages=3, tasks=40, max_messages=512, pool=256,
        wait_budget=[8, 160], hard_kill_weight=0.25, restart_weight=0.3,
    )
    config["shapes"].update(
        num_actors=5, state_width=8, max_outbox=81, pool_capacity=256,
        max_steps=512, max_external_ops=16,
    )
    traffic = real("traffic/fuzz-continuous-dag.json")
    traffic["job"].update(schedules=96, resident_lanes_per_chip=32)
    traffic["trace_seconds"] = 0.01
    traffic["check"] = {"lift_violating": 2, "lift_clean": 2}
    for sub, name, body in (
        ("configs", "tiny-spark5", config),
        ("traffic", "tiny-fuzz-dag", traffic),
    ):
        with open(os.path.join(tmp, "extra", sub, name + ".json"), "w") as f:
            json.dump(body, f)
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-spark5", "file": "extra/configs/tiny-spark5.json",
    })
    bench["workloads"].append({
        "name": CELL, "config": "tiny-spark5", "traffic": "tiny-fuzz-dag",
        "chips": 1,
    })
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "tiny-sweep" in metric.get("workloads", ()):
            metric["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return path


def run(bench, trace, cell=CELL, seed=2**31 + 3333):
    lines = []
    result = harness.run(
        bench, cell, seed, 0.3, trace, time.perf_counter(),
        require_tpu=False, log=lines.append,
    )
    return result, lines


def test_the_cell_runs_end_to_end_on_cpu(bench):
    result, lines = run(bench, trace=False)
    assert result["correct"] is True and result["failed"] == 0, lines
    assert result["attempted"] >= 96
    assert set(result["metrics"]) == {"schedules_per_s", "setup_s"}
    assert sum("check " in ln and "(limit " in ln for ln in lines) >= 3


def test_the_traced_run_reports_the_new_metric(bench):
    from demi_tpu import obs

    obs.TRACER.clear()
    result, lines = run(bench, trace=True)
    assert result["correct"] is True, lines
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    counts = obs.stage_counts()
    assert counts["sweep.unfinished"] == 0
    assert metrics["sweep.quiesced_share"] == 100.0
    assert metrics["sweep.row_lowered_share"] == 100.0
    assert counts["sweep.outbox_rows"] % 81 == 0
    assert metrics[METRIC] == pytest.approx(
        100.0 * counts["sweep.rows_inserted"] / counts["sweep.outbox_rows"]
    )
    # A whole job is 481 deliveries that insert 481 rows through 81-row
    # outboxes (1.23%); a job cut short by a lost driver reads about the same.
    assert 0.8 < metrics[METRIC] < 1.6
    assert 0 < metrics["sweep.pool_peak_share"] <= 100.0 * 200 / 256


def test_the_raft_cell_reads_the_new_metric_too(bench):
    from demi_tpu import obs

    obs.TRACER.clear()
    result, lines = run(bench, trace=True, cell="tiny-sweep", seed=2**31 + 3334)
    assert result["correct"] is True, lines
    # raft3's outbox is 3 rows; a delivery mostly sends one or none
    assert 5 < result["metrics"][METRIC]["value"] < 70


def test_the_reader_on_a_hand_made_counter_table(bench):
    from demi_tpu import obs

    read = cells.load_reader(cells.load_cell(bench, CELL), METRIC)
    obs.TRACER.clear()
    assert read(None) is None   # no tables' root: the parent's untraced run
    obs.enable()
    try:
        with obs.span("sweep.job"):  # the parent's traced job: the root, no count
            pass
        assert read(None) is None
        with obs.span("sweep.job"):
            obs.stage_count("sweep.rows_inserted", 3201)
            obs.stage_count("sweep.rows_inserted", 799)
            obs.stage_count("sweep.outbox_rows", 4000 * 401)
        assert read(None) == pytest.approx(100.0 / 401)
    finally:
        obs.disable()
        obs.TRACER.clear()


def test_the_counts_cost_nothing_while_spans_are_off(bench):
    """Spans off: the retire pulls neither ``seq_counter`` nor
    ``deliveries``, and no count is kept."""
    from demi_tpu import obs
    from demi_tpu.parallel.distributed import build_workload
    from demi_tpu.parallel.sweep import SweepDriver

    cell = cells.load_cell(bench, CELL)
    app, cfg, fuzzer = build_workload(dict(cell.config["workload"]))
    driver = SweepDriver(app, cfg, lambda s: fuzzer.generate_fuzz_test(seed=s))
    obs.TRACER.clear()
    driver.sweep(32, 32, mode="continuous")
    assert "sweep.rows_inserted" not in obs.stage_counts()
    obs.enable()
    try:
        driver.sweep(32, 32, mode="continuous")
        counts = obs.stage_counts()
    finally:
        obs.disable()
        obs.TRACER.clear()
    assert 0 < counts["sweep.rows_inserted"] < counts["sweep.outbox_rows"]


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
    from dag_reference_on_chip import reference_check

    report = reference_check(bench, CELL, 2**31 + 3335, lanes=12, require_tpu=False)
    assert report["lanes"] == 12 and report["disagreeing"] == 0, report
    assert report["unfinished"] == 0 and report["jobs_done"] >= 3
    # a stage leaves at most 40 copies pending; the last launch finds 200
    assert 80 < report["peak_pending"] <= 200


@pytest.mark.parametrize("seed", [2**31 + 3336, 2**31 + 3337, 2**31 + 3338])
def test_without_its_epoch_check_the_reference_parts_from_the_timed_path(bench, seed):
    from dag_reference_on_chip import reference_check

    report = reference_check(
        bench, CELL, seed, lanes=32, require_tpu=False, control=True
    )
    assert report["control"] is True and report["lanes"] == 32
    assert report["disagreeing"] >= 1, report


def test_the_reference_is_plain():
    with open(dag_reference.__file__, encoding="utf-8") as f:
        source = f.read()
    assert "import jax" not in source and "demi_tpu import" not in source
    assert "numpy" not in source and "demi_tpu.apps" not in source.split('"""')[2]


def test_the_real_entries_are_what_the_issue_fixed():
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "spark17-shuffle200", "fuzz-continuous-dag", 1
    )
    assert len(cell["why"]) <= 200
    have = [w["name"] for w in bench["workloads"]]
    sweeps = [c for c in SWEEP_CELLS if c in have]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    new = by_name[METRIC]
    assert new["workloads"] == sweeps and new["moves"] == "schedules_per_s"
    assert (new["layer"], new["source"], new["better"], new["unit"]) == (
        "kernels", "program_counter", "higher", "%"
    )
    assert os.path.exists(os.path.join(tiny.BENCH, "layer_metrics", METRIC + ".py"))
    for name, metric in by_name.items():
        if name.startswith("sweep.") or name == "explore_segment_roofline":
            assert metric["workloads"][: len(sweeps)] == sweeps, name
    rate = next(m for m in bench["end_to_end"] if m["name"] == "schedules_per_s")
    assert rate["workloads"][: len(sweeps)] == sweeps
    entry = next(c for c in bench["configs"] if c["name"] == "spark17-shuffle200")
    config = real("configs/spark17-shuffle200.json")
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == ["chips", "schedules"]
    assert config["architecture"] is None
    workload = config["workload"]
    assert (workload["app"], workload["nodes"], workload["stages"],
            workload["tasks"], workload["bug"]) == ("spark", 17, 4, 200, None)
    assert (workload["num_events"], workload["max_sends"]) == (8, 1)
    assert (workload["max_messages"], workload["pool"]) == (3328, workload["pool"])
    assert workload["pool"] in (1024, 1280)
    assert workload["wait_budget"] == [64, 1600] and workload["max_kills"] == 3
    assert config["shapes"] == {
        "num_actors": 17, "state_width": 30, "msg_width": 3, "max_outbox": 401,
        "pool_capacity": workload["pool"], "max_steps": 3328,
        "max_external_ops": 27, "invariant_interval": 1, "msg_dtype": "int32",
    }
    assert set(config["assumed"]) <= set(workload)
    assert all(workload[k] == v for k, v in config["assumed"].items())
    assert len(config["departures_from_spark"]) >= 3 and config["guarantees"]
    dag = real("traffic/fuzz-continuous-dag.json")
    assert dag["job"] in (
        {"schedules": 1024, "resident_lanes_per_chip": 256, "mode": "continuous"},
        {"schedules": 512, "resident_lanes_per_chip": 128, "mode": "continuous"},
    )
    assert dag["check"] == {"lift_violating": 4, "lift_clean": 4}
    assert dag["end_to_end"] == {"schedules_per_s": "mean_rate"}
    assert dag["trace_seconds"] == 2
    flood = real("traffic/fuzz-continuous-flood.json")
    same = ("verb", "panel", "seed_changes", "seed_keeps", "end_to_end",
            "trace_seconds", "check")
    assert all(dag[k] == flood[k] for k in same)
