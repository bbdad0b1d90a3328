"""``sweep.insert_full_lane_share`` (``layer_metrics/sweep.insert_full_lane_share.py``,
PR 45): an entry of ``per_layer``, read from the count the continuous driver
pulls at the retire where the pool insert has a short pass: the steps in which
a lane's own rows went through the full ``[K, P]`` pass, in a ``--trace 1``
run of the tiny spark cell with the one-hot lowering a TPU takes forced on the
CPU; and absent, with no error, where the program keeps no such count: the
same cell in the CPU's own lowering, the raft cells, and the PR's parent
(whose lanes count the batch's full steps only)."""

import json
import os

import pytest

import tiny
from lib import cells
from test_spark_cell import CELL, bench, run  # noqa: F401  (bench: a fixture)

NAME = "sweep.insert_full_lane_share"
SHORT = "sweep.insert_short_share"


def test_the_metric_is_an_entry_with_a_reader():
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    (entry,) = [m for m in per_layer if m["name"] == NAME]
    (short,) = [m for m in per_layer if m["name"] == SHORT]
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "kernels",
        "moves": "schedules_per_s",
        # the cells whose insert has the short pass
        "workloads": short["workloads"],
    }
    assert os.path.exists(
        os.path.join(tiny.BENCH, "layer_metrics", NAME + ".py")
    )


def test_the_traced_spark_cell_reports_it_on_the_one_hot_path(bench, monkeypatch):
    from demi_tpu import obs
    from demi_tpu.device.core import DeviceConfig

    # What 'auto' resolves to on a TPU; steered here, not by an option.
    monkeypatch.setattr(DeviceConfig, "use_onehot", property(lambda self: True))
    obs.TRACER.clear()
    result, lines = run(bench, trace=True, seed=2**31 + 4545)
    assert result["correct"] is True, lines
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    counts = obs.stage_counts()
    assert (
        0 < counts["sweep.insert_full_lane_steps"]
        < counts["sweep.insert_full_steps"] < counts["sweep.insert_steps"]
    )
    assert metrics[NAME] == pytest.approx(
        100.0 * counts["sweep.insert_full_lane_steps"]
        / counts["sweep.insert_steps"]
    )
    # a lane launches three 80-row stages in some 500 steps; 32 resident
    # lanes make that a step in three or so for the batch
    assert 0 < metrics[NAME] < 2
    assert metrics[NAME] < 100.0 - metrics[SHORT]


@pytest.mark.parametrize("cell", [CELL, "tiny-sweep"])
def test_a_program_without_the_count_leaves_it_out(bench, cell):
    from demi_tpu import obs

    obs.TRACER.clear()
    result, lines = run(bench, trace=True, cell=cell, seed=2**31 + 4546)
    assert result["correct"] is True, lines
    assert NAME not in result["metrics"]
    assert "sweep.outbox_fill_share" in result["metrics"]


def test_the_reader_on_a_hand_made_counter_table(bench):
    from demi_tpu import obs

    read = cells.load_reader(cells.load_cell(bench, CELL), NAME)
    obs.TRACER.clear()
    assert read(None) is None   # no tables' root: an untraced run
    obs.enable()
    try:
        with obs.span("sweep.job"):
            # the parent's traced job: the batch's count, not the lanes'
            obs.stage_count("sweep.insert_steps", 3200)
            obs.stage_count("sweep.insert_full_steps", 800)
        assert read(None) is None
        obs.stage_count("sweep.insert_full_lane_steps", 0)
        assert read(None) == 0.0   # no lane burst
        obs.stage_count("sweep.insert_full_lane_steps", 16)
        assert read(None) == pytest.approx(0.5)
    finally:
        obs.disable()
        obs.TRACER.clear()
