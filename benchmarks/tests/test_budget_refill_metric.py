"""``sweep.budget_refill_share`` (``layer_metrics/sweep.budget_refill_share.py``,
PR 48): an entry of ``per_layer`` listed for the sweep cells, read from the
count the continuous driver keeps at the retire (``sweep.budget_retired``:
lanes the host knew spent when it dispatched their last segment, refilled or
parked behind it with no frozen segment) over ``sweep.retired``, in a
``--trace 1`` run: 100 in a tiny cell whose every schedule runs to its step
budget (a correct raft that nobody kills), 0 in one whose schedules all stop
on their own (the tiny flood cell), between the two in the tiny raft cell;
and absent, with no error, from a program that keeps no such count (the PR's
parent)."""

import json
import os
import time

import pytest

import tiny
from lib import cells, harness
from test_flood_cell import CELL as FLOOD, bench as flood_bench  # noqa: F401

NAME = "sweep.budget_refill_share"
QUEUED = "sweep.queued_segment_share"
BUDGET = "tiny-raft3-correct-sweep"


def test_the_metric_is_an_entry_with_a_reader():
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    (entry,) = [m for m in per_layer if m["name"] == NAME]
    (queued,) = [m for m in per_layer if m["name"] == QUEUED]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "drivers (host)",
        "moves": "schedules_per_s",
        # every sweep cell: one loop runs them all
        "workloads": queued["workloads"],
    }
    assert os.path.exists(
        os.path.join(tiny.BENCH, "layer_metrics", NAME + ".py")
    )


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """The tiny benchmark with one more cell: the tiny raft without its
    bug and without kills, whose timers keep every schedule going to its
    64th step (4 segments of 16: the lag engages)."""
    tmp = str(tmp_path_factory.mktemp("tiny"))
    path = tiny.write(tmp)
    config = json.loads(json.dumps(tiny.CONFIGS["tiny-raft3"]))
    config["workload"].update(bug=None, kill_weight=0.0)
    with open(
        os.path.join(tmp, "extra", "configs", "tiny-raft3-correct.json"), "w"
    ) as f:
        json.dump(config, f)
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-raft3-correct",
        "file": "extra/configs/tiny-raft3-correct.json",
    })
    bench["workloads"].append({
        "name": BUDGET, "config": "tiny-raft3-correct",
        "traffic": "tiny-fuzz", "chips": 1,
    })
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "tiny-sweep" in metric.get("workloads", ()):
            metric["workloads"].append(BUDGET)
    with open(path, "w") as f:
        json.dump(bench, f)
    return path


def _traced(bench_file, cell, seed):
    from demi_tpu import obs

    obs.TRACER.clear()
    lines = []
    result = harness.run(
        bench_file, cell, seed, 0.3, True, time.perf_counter(),
        require_tpu=False, log=lines.append,
    )
    assert result["correct"] is True and result["failed"] == 0, lines
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    return metrics, obs.stage_counts()


def test_schedules_that_run_to_their_budget_read_100(bench):
    metrics, counts = _traced(bench, BUDGET, 2**31 + 4848)
    assert counts["sweep.budget_retired"] == counts["sweep.retired"] == 96
    assert metrics[NAME] == 100.0
    # no frozen segment, and no segment behind the last wave
    assert metrics["sweep.live_step_share"] == 100.0
    assert counts["sweep.segments"] == (96 // 32) * 4


def test_schedules_that_stop_on_their_own_read_0(flood_bench):  # noqa: F811
    metrics, counts = _traced(flood_bench, FLOOD, 2**31 + 4849)
    assert counts["sweep.retired"] >= 96
    assert counts["sweep.budget_retired"] == 0
    assert metrics[NAME] == 0.0


def test_the_tiny_raft_cell_reads_its_lanes_that_do_not_violate(bench):
    metrics, counts = _traced(bench, "tiny-sweep", 2**31 + 4850)
    assert 0 < counts["sweep.budget_retired"] < counts["sweep.retired"] == 96
    assert metrics[NAME] == pytest.approx(
        100.0 * counts["sweep.budget_retired"] / 96
    )


def test_a_program_without_the_count_gives_none_and_no_error(bench):
    from demi_tpu import obs

    read = cells.load_reader(cells.load_cell(bench, "tiny-sweep"), NAME)
    obs.TRACER.clear()
    assert read(None) is None
    obs.enable()
    try:
        # the parent's traced job: the root span and its retire count
        with obs.span("sweep.job"):
            obs.stage_count("sweep.retired", 8)
        assert read(None) is None
        obs.stage_count("sweep.budget_retired", 0)
        assert read(None) == 0.0
        obs.stage_count("sweep.budget_retired", 6)
        assert read(None) == pytest.approx(75.0)
    finally:
        obs.disable()
        obs.TRACER.clear()
