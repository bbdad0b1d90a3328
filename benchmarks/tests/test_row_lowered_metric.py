"""``sweep.row_lowered_share`` (``layer_metrics/sweep.row_lowered_share.py``,
PR 30): an appended entry of ``per_layer``, read from the program's own
counts in a ``--trace 1`` run of the tiny sweep cell (100: every program
a fuzzed one, lowered from its op rows), and absent, with no error, from
a program that keeps no such count (the PR's parent)."""

import json
import os
import time

import pytest

import tiny
from lib import cells, harness

NAME = "sweep.row_lowered_share"


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny.write(str(tmp_path_factory.mktemp("tiny")))


def test_the_metric_is_an_entry_with_a_reader():
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        entries = [m for m in json.load(f)["per_layer"] if m["name"] == NAME]
    assert entries == [{
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "drivers (host)",
        "moves": "schedules_per_s",
        "workloads": ["raft5-sweep", "raft5-nemesis-sweep"],
    }]
    assert os.path.exists(
        os.path.join(tiny.BENCH, "layer_metrics", NAME + ".py")
    )


def test_the_traced_sweep_cell_reports_a_hundred(bench):
    from demi_tpu import obs

    obs.TRACER.clear()
    lines = []
    result = harness.run(
        bench, "tiny-sweep", 2**31 + 3030, 0.3, True, time.perf_counter(),
        require_tpu=False, log=lines.append,
    )
    assert result["correct"] is True, lines
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    counts = obs.stage_counts()
    # one fuzzed program a schedule of the traced job, each lowered from
    # its rows, at the refill or ahead of it
    assert counts["sweep.programs"] == 96
    assert counts["sweep.row_lowered"] == 96
    assert metrics[NAME] == 100.0


def test_a_program_without_the_count_gives_none_and_no_error(bench):
    from demi_tpu import obs

    read = cells.load_reader(cells.load_cell(bench, "tiny-sweep"), NAME)
    obs.TRACER.clear()
    assert read(None) is None
    # the parent's traced job: the root span and its other counts only
    obs.enable()
    try:
        with obs.span("sweep.job"):
            obs.stage_count("sweep.lane_steps", 100)
        assert read(None) is None
        obs.stage_count("sweep.programs", 8)
        obs.stage_count("sweep.prefetched", 2)
        assert read(None) is None
        # a sweep fed hand-written lists keeps the count, at nothing
        obs.stage_count("sweep.row_lowered", 0)
        assert read(None) == 0.0
        obs.stage_count("sweep.row_lowered", 6)
        assert read(None) == pytest.approx(75.0)
    finally:
        obs.disable()
        obs.TRACER.clear()
