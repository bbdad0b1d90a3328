"""``sweep.insert_short_share`` (``layer_metrics/sweep.insert_short_share.py``,
PR 37): an appended entry of ``per_layer``, read from the counts the
continuous driver pulls at the retire where the pool insert has a short pass
(the flood's and the spark DAG's outboxes), in a ``--trace 1`` run of the tiny
spark cell with the one-hot lowering a TPU takes forced on the CPU; and
absent, with no error, where the insert has none: the same cell in the CPU's
own lowering, the raft cells, and the PR's parent."""

import json
import os

import pytest

import tiny
from lib import cells
from test_spark_cell import CELL, bench, run  # noqa: F401  (bench: a fixture)

NAME = "sweep.insert_short_share"


def test_the_metric_is_an_entry_with_a_reader():
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        entries = [m for m in json.load(f)["per_layer"] if m["name"] == NAME]
    assert entries == [{
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "schedules_per_s",
        "workloads": ["bcast64-flood-sweep", "spark17-shuffle200-sweep"],
    }]
    assert os.path.exists(
        os.path.join(tiny.BENCH, "layer_metrics", NAME + ".py")
    )


def test_the_traced_spark_cell_reports_it_on_the_one_hot_path(bench, monkeypatch):
    from demi_tpu import obs
    from demi_tpu.device.core import DeviceConfig

    # What 'auto' resolves to on a TPU; steered here, not by an option.
    monkeypatch.setattr(DeviceConfig, "use_onehot", property(lambda self: True))
    obs.TRACER.clear()
    result, lines = run(bench, trace=True, seed=2**31 + 3737)
    assert result["correct"] is True, lines
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    counts = obs.stage_counts()
    # every step a retired lane was scanned, whole segments of 64
    assert counts["sweep.insert_steps"] % 64 == 0
    assert 0 < counts["sweep.insert_full_steps"] < counts["sweep.insert_steps"]
    assert metrics[NAME] == pytest.approx(
        100.0
        - 100.0 * counts["sweep.insert_full_steps"] / counts["sweep.insert_steps"]
    )
    # 32 resident lanes, three 80-row launches in some 500 steps each
    assert 50 < metrics[NAME] < 99


@pytest.mark.parametrize("cell", [CELL, "tiny-sweep"])
def test_a_program_without_the_short_pass_leaves_it_out(bench, cell):
    from demi_tpu import obs

    obs.TRACER.clear()
    result, lines = run(bench, trace=True, cell=cell, seed=2**31 + 3738)
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
        with obs.span("sweep.job"):  # the parent's traced job: no such count
            obs.stage_count("sweep.lane_steps", 100)
        assert read(None) is None
        with obs.span("sweep.job"):
            obs.stage_count("sweep.insert_steps", 3200)
        assert read(None) == 100.0   # no step took the full pass
        obs.stage_count("sweep.insert_full_steps", 800)
        assert read(None) == pytest.approx(75.0)
    finally:
        obs.disable()
        obs.TRACER.clear()
