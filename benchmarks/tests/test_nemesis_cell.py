"""``raft5-nemesis-sweep`` (PR 27), rehearsed at tiny size on the CPU the
way ``tiny.write`` adds cells: the deployment of
``configs/raft5-nemesis.json`` cut to ``log_cap`` 8, 160 steps and pool 96,
under a traffic file shaped like ``traffic/fuzz-continuous-deep.json``,
as one more cell of the tiny benchmark. The two per-layer metrics the PR
brought are read from the program's counts in the traced run, and are
absent, with no error, from a program that keeps no such counts (the
PR's parent). The real files are held to what ISSUE 27 fixed."""

import json
import os
import time

import pytest

import tiny
from lib import cells, harness

CELL = "tiny-nemesis-sweep"
NEW_METRICS = ("sweep.live_step_share", "sweep.fault_op_share")


def real(relative):
    with open(os.path.join(tiny.BENCH, relative), encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("tiny"))
    path = tiny.write(tmp)
    config = real("configs/raft5-nemesis.json")
    config["workload"].update(log_cap=8, num_events=24, max_messages=160, pool=96)
    config["shapes"].update(
        state_width=34, pool_capacity=96, max_steps=160, max_external_ops=31
    )
    traffic = real("traffic/fuzz-continuous-deep.json")
    traffic["job"].update(schedules=192, resident_lanes_per_chip=64)
    traffic["trace_seconds"] = 0.01
    traffic["check"] = {"lift_violating": 2, "lift_clean": 2}
    for sub, name, body in (
        ("configs", "tiny-raft5-nemesis", config),
        ("traffic", "tiny-fuzz-deep", traffic),
    ):
        with open(os.path.join(tmp, "extra", sub, name + ".json"), "w") as f:
            json.dump(body, f)
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-raft5-nemesis",
        "file": "extra/configs/tiny-raft5-nemesis.json",
    })
    bench["workloads"].append({
        "name": CELL, "config": "tiny-raft5-nemesis",
        "traffic": "tiny-fuzz-deep", "chips": 1,
    })
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "tiny-sweep" in metric.get("workloads", ()):
            metric["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return path


def run(bench, trace):
    lines = []
    result = harness.run(
        bench, CELL, 2**31 + 2727, 0.3, trace, time.perf_counter(),
        require_tpu=False, log=lines.append,
    )
    return result, lines


def test_the_cell_runs_end_to_end_on_cpu(bench):
    result, lines = run(bench, trace=False)
    assert result["correct"] is True and result["failed"] == 0, lines
    assert result["attempted"] >= 192
    assert set(result["metrics"]) == {"schedules_per_s", "setup_s"}
    assert sum("check " in ln and "(limit " in ln for ln in lines) >= 3


def test_the_traced_run_reports_the_two_new_metrics(bench):
    from demi_tpu import obs

    obs.TRACER.clear()
    result, lines = run(bench, trace=True)
    assert result["correct"] is True, lines
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    counts = obs.stage_counts()
    assert metrics["sweep.live_step_share"] == pytest.approx(
        100.0 * counts["sweep.live_lane_steps"] / counts["sweep.lane_steps"]
    )
    assert 0 < metrics["sweep.live_step_share"] <= 100
    lowered = sum(n for k, n in counts.items() if k.startswith("sweep.ops."))
    faults = sum(
        counts[f"sweep.ops.{k}"]
        for k in ("hard_kill", "restart", "partition", "unpartition")
    )
    assert metrics["sweep.fault_op_share"] == pytest.approx(100.0 * faults / lowered)
    assert 10 < metrics["sweep.fault_op_share"] < 100
    assert counts["sweep.ops.kill"] == 0 and counts["sweep.ops.start"] > 0
    for name in ("sweep.fuzz_share", "sweep.lower_share", "sweep.block_share"):
        assert name in metrics


def test_the_plain_sweep_cell_engages_next_to_none_of_the_fault_plane(bench):
    from demi_tpu import obs

    obs.TRACER.clear()
    lines = []
    result = harness.run(
        bench, "tiny-sweep", 2**31 + 2728, 0.3, True, time.perf_counter(),
        require_tpu=False, log=lines.append,
    )
    assert result["correct"] is True, lines
    assert result["metrics"]["sweep.fault_op_share"]["value"] == 0.0
    assert 0 < result["metrics"]["sweep.live_step_share"]["value"] <= 100


def test_a_pool_under_the_fullest_schedule_reads_not_correct(bench):
    """The control that can fail on this cell (``pool_control_on_chip.py``
    runs it at pool 32 on the chip): the stock ``sweep_small_pool`` takes
    a quarter of a pool that is five times the fullest schedule."""
    from pool_control_on_chip import sweep_pool

    undo = sweep_pool(8)(cells.load_verb(cells.load_cell(bench, CELL)))
    try:
        result, lines = run(bench, trace=False)
    finally:
        undo()
    assert result["correct"] is False
    assert any("sweep.overflow_lanes" in ln and "FAILED" in ln for ln in lines), lines


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
    cell = next(w for w in bench["workloads"] if w["name"] == "raft5-nemesis-sweep")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "raft5-nemesis", "fuzz-continuous-deep", 1
    )
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == ["raft5-sweep", "raft5-nemesis-sweep"]
        assert by_name[name]["moves"] == "schedules_per_s"
        assert os.path.exists(os.path.join(tiny.BENCH, "layer_metrics", name + ".py"))
    for name, metric in by_name.items():
        if name.startswith("sweep.") or name == "explore_segment_roofline":
            assert "raft5-nemesis-sweep" in metric["workloads"], name
    config = real("configs/raft5-nemesis.json")
    assert config["workload"]["bug"] is None
    assert config["shapes"]["state_width"] == 7 + 2 * 32 + 2 * 5 + 1
    assert set(config["reduced"]) == {"log_cap", "max_messages"}
    assert list(config["chips"]) == ["1"]
    job = real("traffic/fuzz-continuous-deep.json")["job"]
    assert job == {"schedules": 16384, "resident_lanes_per_chip": 4096,
                   "mode": "continuous"}
