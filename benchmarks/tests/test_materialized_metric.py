"""``dpor.materialized_share`` (``layer_metrics/dpor.materialized_share.py``):
the last entry of ``per_layer``, read from the program's own counts in a
``--trace 1`` run of the tiny DPOR cell, and absent, with no error, from a
program that keeps no such count (the parent of the PR that brought it)."""

import json
import os
import time

import pytest

import tiny
from lib import cells, harness

NAME = "dpor.materialized_share"


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny.write(str(tmp_path_factory.mktemp("tiny")))


def reader(bench):
    return cells.load_reader(cells.load_cell(bench, "tiny-dpor"), NAME)


def test_the_metric_is_an_appended_entry_with_a_reader():
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        entry = json.load(f)["per_layer"][-1]
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "drivers (host)",
        "moves": "interleavings_per_s", "workloads": ["raft5-dpor"],
    }
    assert os.path.exists(
        os.path.join(tiny.BENCH, "layer_metrics", NAME + ".py")
    )


def test_the_traced_dpor_cell_reports_it_under_100(bench):
    from demi_tpu import obs

    obs.TRACER.clear()
    lines = []
    result = harness.run(
        bench, "tiny-dpor", 2**31 + 78, 0.3, True, time.perf_counter(),
        require_tpu=False, log=lines.append,
    )
    assert result["correct"] is True, lines
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert 0 <= metrics[NAME] < 100
    counts = obs.stage_counts()
    assert metrics[NAME] == pytest.approx(
        100.0 * counts["dpor.materialized"] / counts["dpor.fresh"]
    )
    assert not [n for n in result["metrics"] if n.startswith("sweep.")]


def test_a_program_without_the_count_gives_none_and_no_error(bench):
    from demi_tpu import obs

    read = reader(bench)
    obs.TRACER.clear()
    assert read(None) is None
    # the parent's traced job: the root span and its two counts, no third
    obs.enable()
    try:
        with obs.span("dpor.search"):
            obs.stage_count("dpor.candidates", 10)
            obs.stage_count("dpor.fresh", 4)
        assert read(None) is None
        obs.stage_count("dpor.materialized", 1)
        assert read(None) == pytest.approx(25.0)
    finally:
        obs.disable()
        obs.TRACER.clear()
