"""``bcast64-flood-sweep`` (PR 31), rehearsed at tiny size on the CPU the way
``tiny.write`` adds cells: the deployment of ``configs/bcast64-flood.json``
cut to 8 processes (one flood is 57 messages), 128 steps and a pool of 128,
under a traffic file shaped like ``traffic/fuzz-continuous-flood.json``, as
one more cell of the tiny benchmark (``tiny-bcast8-flood-sweep``). The two
per-layer metrics the PR brought are read from the program's counts in the
traced run, and are absent, with no error, from a program that keeps no such
counts (the PR's parent). The plain reference replays the cell's own lanes.
The controls: a quarter of the pool and corrupted codes read ``correct:
false``; ``sweep_invariant_at_end`` sets what the cell already runs, so it
reads ``true``; the control that can fail here, the same cell judged after
every delivery (``every_delivery_control_on_chip.py``), reads ``false``. The
real files are held to what ISSUE 31 fixed."""

import json
import os
import time

import pytest

import controls
import tiny
from lib import cells, flood_reference, harness

CELL = "tiny-bcast8-flood-sweep"
NEW_METRICS = ("sweep.quiesced_share", "sweep.pool_peak_share")
SWEEP_CELLS = ["raft5-sweep", "raft5-nemesis-sweep", "raft5-sweep-x4",
               "bcast64-flood-sweep"]


def real(relative):
    with open(os.path.join(tiny.BENCH, relative), encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("tiny"))
    path = tiny.write(tmp)
    config = real("configs/bcast64-flood.json")
    config["workload"].update(
        nodes=8, max_messages=128, pool=128, wait_budget=[2, 40]
    )
    config["shapes"].update(
        num_actors=8, max_outbox=8, pool_capacity=128, max_steps=128,
        max_external_ops=16,
    )
    traffic = real("traffic/fuzz-continuous-flood.json")
    traffic["job"].update(schedules=96, resident_lanes_per_chip=32)
    traffic["trace_seconds"] = 0.01
    traffic["check"] = {"lift_violating": 2, "lift_clean": 2}
    for sub, name, body in (
        ("configs", "tiny-bcast8-flood", config),
        ("traffic", "tiny-fuzz-flood", traffic),
    ):
        with open(os.path.join(tmp, "extra", sub, name + ".json"), "w") as f:
            json.dump(body, f)
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-bcast8-flood",
        "file": "extra/configs/tiny-bcast8-flood.json",
    })
    bench["workloads"].append({
        "name": CELL, "config": "tiny-bcast8-flood",
        "traffic": "tiny-fuzz-flood", "chips": 1,
    })
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "tiny-sweep" in metric.get("workloads", ()):
            metric["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return path


def run(bench, trace, cell=CELL, seed=2**31 + 3131):
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


def test_the_traced_run_reports_the_two_new_metrics(bench):
    from demi_tpu import obs

    obs.TRACER.clear()
    result, lines = run(bench, trace=True)
    assert result["correct"] is True, lines
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    counts = obs.stage_counts()
    assert counts["sweep.unfinished"] == 0
    assert counts["sweep.quiesced"] == counts["sweep.retired"] > 0
    assert metrics["sweep.quiesced_share"] == 100.0
    assert metrics["sweep.pool_peak_share"] == pytest.approx(
        100.0 * counts["sweep.pool_peak_rows"] / counts["sweep.pool_rows"]
    )
    # One flood of 8 holds up to 49 messages at once; the pool has 128 rows
    # and the peak is sampled at segment boundaries (32 steps).
    assert 5 < metrics["sweep.pool_peak_share"] <= 100.0 * 57 / 128
    assert metrics["sweep.row_lowered_share"] == 100.0
    for name in ("sweep.block_share", "sweep.live_step_share"):
        assert name in metrics


def test_the_raft_cell_reads_the_new_metrics_too(bench):
    from demi_tpu import obs

    obs.TRACER.clear()
    result, lines = run(bench, trace=True, cell="tiny-sweep", seed=2**31 + 3132)
    assert result["correct"] is True, lines
    assert result["metrics"]["sweep.quiesced_share"]["value"] == 100.0
    assert 0 < result["metrics"]["sweep.pool_peak_share"]["value"] < 100


def test_an_undersized_step_budget_reads_under_100(bench):
    """The counter behind ``sweep.quiesced_share``, where lanes are cut."""
    from demi_tpu import obs
    from demi_tpu.parallel.distributed import build_workload
    from demi_tpu.parallel.sweep import SweepDriver

    cell = cells.load_cell(bench, CELL)
    app, cfg, fuzzer = build_workload(dict(cell.config["workload"], max_messages=24))
    driver = SweepDriver(app, cfg, lambda s: fuzzer.generate_fuzz_test(seed=s))
    obs.TRACER.clear()
    obs.enable()
    try:
        result = driver.sweep(64, 32, mode="continuous")
        read = cells.load_reader(cell, "sweep.quiesced_share")
        share = read(None)
    finally:
        obs.disable()
        obs.TRACER.clear()
    assert result.unfinished_lanes > 0 and result.violations == 0
    assert share == pytest.approx(100.0 * (64 - result.unfinished_lanes) / 64)


@pytest.mark.parametrize("control,correct", [
    (controls.sweep_small_pool, False),
    (controls.sweep_corrupt_codes, False),
    (controls.sweep_invariant_at_end, True),   # what the cell already runs
])
def test_the_stock_controls_on_this_cell(bench, control, correct):
    undo = control(cells.load_verb(cells.load_cell(bench, CELL)))
    try:
        result, lines = run(bench, trace=False)
    finally:
        undo()
    assert result["correct"] is correct, lines


def test_judged_after_every_delivery_reads_not_correct(bench):
    """The control that can fail on this cell
    (``every_delivery_control_on_chip.py`` runs it at the cell's size)."""
    from every_delivery_control_on_chip import every_delivery

    undo = every_delivery(cells.load_verb(cells.load_cell(bench, CELL)))
    try:
        result, lines = run(bench, trace=False)
    finally:
        undo()
    assert result["correct"] is False
    assert any(
        "sweep.lifted_lanes_disagreeing" in ln and "FAILED" in ln for ln in lines
    ), lines


def test_the_plain_reference_agrees_on_the_cells_own_lanes(bench):
    from flood_reference_on_chip import reference_check

    report = reference_check(bench, CELL, 2**31 + 3133, lanes=12, require_tpu=False)
    assert report["lanes"] == 12 and report["disagreeing"] == 0, report
    assert report["unfinished"] == 0
    assert 0 < report["peak_pending"] <= 57


def test_the_reference_is_plain():
    with open(flood_reference.__file__, encoding="utf-8") as f:
        source = f.read()
    assert "import jax" not in source and "demi_tpu import" not in source
    assert "numpy" not in source


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_counts_gives_none_and_no_error(bench, name):
    from demi_tpu import obs

    read = cells.load_reader(cells.load_cell(bench, CELL), name)
    obs.TRACER.clear()
    assert read(None) is None
    obs.enable()
    try:
        with obs.span("sweep.job"):  # the parent's traced job: the root, no count
            pass
        assert read(None) is None
    finally:
        obs.disable()
        obs.TRACER.clear()


def test_the_real_entries_are_what_the_issue_fixed():
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == "bcast64-flood-sweep")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "bcast64-flood", "fuzz-continuous-flood", 1
    )
    have = [w["name"] for w in bench["workloads"]]
    sweeps = [c for c in SWEEP_CELLS if c in have]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-2:]] == list(NEW_METRICS)
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == sweeps
        assert by_name[name]["moves"] == "schedules_per_s"
        assert by_name[name]["layer"] == "kernels"
        assert os.path.exists(os.path.join(tiny.BENCH, "layer_metrics", name + ".py"))
    for name, metric in by_name.items():
        if name.startswith("sweep.") or name == "explore_segment_roofline":
            assert metric["workloads"] == sweeps, name
    entry = next(c for c in bench["configs"] if c["name"] == "bcast64-flood")
    config = real("configs/bcast64-flood.json")
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == ["chips", "schedules"]
    assert config["workload"]["bug"] is None and config["workload"]["max_sends"] == 1
    assert config["shapes"] == {
        "num_actors": 64, "state_width": 1, "msg_width": 2, "max_outbox": 64,
        "pool_capacity": 4608, "max_steps": 4608, "max_external_ops": 72,
        "invariant_interval": 0, "msg_dtype": "int32",
    }
    assert set(config["assumed"]) <= set(config["workload"])
    assert all(config["workload"][k] == v for k, v in config["assumed"].items())
    flood = real("traffic/fuzz-continuous-flood.json")
    assert flood["job"] in (
        {"schedules": 1024, "resident_lanes_per_chip": 256, "mode": "continuous"},
        {"schedules": 512, "resident_lanes_per_chip": 128, "mode": "continuous"},
    )
    assert flood["check"] == {"lift_violating": 4, "lift_clean": 4}
    if "raft5-sweep-x4" in have:
        x4 = next(w for w in bench["workloads"] if w["name"] == "raft5-sweep-x4")
        assert (x4["config"], x4["traffic"], x4["chips"]) == (
            "raft5-multivote", "fuzz-continuous-x4", 4
        )
    job = real("traffic/fuzz-continuous-x4.json")
    assert job["job"] == {"schedules": 131072, "resident_lanes_per_chip": 8192,
                          "mode": "continuous"}
    assert job["check"] == {"lift_violating": 8, "lift_clean": 16,
                            "one_chip_slice": 2048}
