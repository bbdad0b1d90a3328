"""``sweep.produced_share`` and ``sweep.starve_share`` (PR 42): appended
entries of ``per_layer`` with a reader each, read from the program's own
counts and its ``sweep.starve`` stage in a ``--trace 1`` run of the tiny
sweep cell, and absent, with no error, from a program that has no
producer processes (the PR's parent)."""

import json
import os
import time

import pytest

import tiny
from lib import cells, harness

PRODUCED, STARVE = "sweep.produced_share", "sweep.starve_share"
SWEEP_CELLS = [
    "raft5-sweep", "raft5-nemesis-sweep", "raft5-sweep-x4",
    "bcast64-flood-sweep", "spark17-shuffle200-sweep",
    "vsr5-recovery-sweep", "chain7-fifo-sweep",
]


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny.write(str(tmp_path_factory.mktemp("tiny")))


def test_each_metric_is_an_entry_with_a_reader():
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    common = {
        "unit": "%", "layer": "drivers (host)", "moves": "schedules_per_s",
        "workloads": SWEEP_CELLS,
    }
    assert per_layer[PRODUCED] == dict(
        common, name=PRODUCED, better="higher", source="program_counter"
    )
    assert per_layer[STARVE] == dict(
        common, name=STARVE, better="lower", source="program_span"
    )
    for name in (PRODUCED, STARVE):
        assert os.path.exists(
            os.path.join(tiny.BENCH, "layer_metrics", name + ".py")
        )


def _traced(bench, seed):
    from demi_tpu import obs

    obs.TRACER.clear()
    lines = []
    result = harness.run(
        bench, "tiny-sweep", seed, 0.3, True, time.perf_counter(),
        require_tpu=False, log=lines.append,
    )
    assert result["correct"] is True, lines
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    return metrics, obs.stage_counts(), obs.stage_totals()


def test_a_job_too_short_for_a_fork_reads_zero(bench):
    """96 programs are worth no producer: both metrics are there, at 0."""
    metrics, counts, totals = _traced(bench, 2**31 + 4242)
    assert counts["sweep.programs"] == 96
    assert counts["sweep.produced"] == counts["sweep.producers"] == 0
    assert metrics[PRODUCED] == 0.0 and metrics[STARVE] == 0.0
    assert "sweep.starve" not in totals


def test_the_traced_sweep_cell_reports_them_where_producers_engage(
    bench, monkeypatch
):
    """The rule itself, with a fork made worth a microsecond of making
    and a device that is never the slower of the two: the warm job forks
    mid-way, at its first refill that makes a program on the spot, and
    the traced job, the driver's next call, at its prime fill: all but
    the probe's 32 programs come out of the ring."""
    from demi_tpu.device import continuous

    if not hasattr(os, "fork") or continuous._cores() < 2:
        pytest.skip("no producer process can be had here")
    monkeypatch.setattr(continuous, "_WORTH_S", 1e-6)
    monkeypatch.setattr(continuous, "_ready", lambda _array: True)
    metrics, counts, totals = _traced(bench, 2**31 + 4243)
    assert counts["sweep.programs"] == 96 and counts["sweep.producers"] >= 1
    assert counts["sweep.produced"] == 96 - 32
    assert metrics[PRODUCED] == pytest.approx(100.0 * 64 / 96)
    starved = totals.get("sweep.starve", {"self_seconds": 0.0})["self_seconds"]
    assert metrics[STARVE] == pytest.approx(
        100.0 * starved / totals["sweep.job"]["seconds"]
    )
    assert 0.0 <= metrics[STARVE] < 100.0
    with pytest.raises(ChildProcessError):
        os.waitpid(-1, os.WNOHANG)      # no child outlives the run


def test_a_program_without_the_counts_gives_none_and_no_error(bench):
    from demi_tpu import obs

    cell = cells.load_cell(bench, "tiny-sweep")
    produced = cells.load_reader(cell, PRODUCED)
    starve = cells.load_reader(cell, STARVE)
    obs.TRACER.clear()
    assert produced(None) is None and starve(None) is None
    # the parent's traced job: the root span, its fills and its counts
    obs.enable()
    try:
        with obs.span("sweep.job"):
            obs.stage_count("sweep.programs", 8)
            obs.stage_count("sweep.prefetched", 2)
            time.sleep(0.01)
        assert produced(None) is None and starve(None) is None
        # this PR's program, producers not engaged, then engaged
        obs.stage_count("sweep.produced", 0)
        obs.stage_count("sweep.producers", 0)
        assert produced(None) == 0.0 and starve(None) == 0.0
        obs.stage_count("sweep.produced", 6)
        assert produced(None) == pytest.approx(75.0)
        with obs.span("sweep.job"):
            with obs.span("sweep.starve"):
                time.sleep(0.01)
        totals = obs.stage_totals()
        assert starve(None) == pytest.approx(
            100.0 * totals["sweep.starve"]["self_seconds"]
            / totals["sweep.job"]["seconds"]
        )
        assert 0.0 < starve(None) < 100.0
    finally:
        obs.disable()
        obs.TRACER.clear()
