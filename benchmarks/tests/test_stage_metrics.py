"""The per-layer metrics that read the program's stage spans
(``lib/stage_share.py``): on the tiny CPU cells a ``--trace 1`` run gives
every one of them a number, the shares of a cell close on 100, and a
program without the tables gives none of them and no error."""

import json
import os
import sys
import time

import pytest

import tiny
from lib import harness, stage_share

DPOR_SHARES = (
    "dpor.select_share", "dpor.launch_share", "dpor.block_share",
    "dpor.pull_share", "dpor.scan_share", "dpor.admit_share",
    "dpor.gc_pause_share", "dpor.unattributed_share",
)
DPOR_OTHERS = ("dpor.admit_us_per_candidate", "dpor.fresh_share")
SWEEP_SHARES = (
    "sweep.block_share", "sweep.fuzz_share", "sweep.lower_share",
    "sweep.stack_share", "sweep.refill_share", "sweep.retire_share",
    "sweep.unattributed_share",
)


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny.write(str(tmp_path_factory.mktemp("tiny")))


def traced(bench, cell):
    from demi_tpu import obs

    obs.TRACER.clear()   # one process runs several cells here; a run.py has one
    lines = []
    result = harness.run(
        bench, cell, 2**31 + 77, 0.3, True, time.perf_counter(),
        require_tpu=False, log=lines.append,
    )
    assert result["correct"] is True, lines
    return {k: v["value"] for k, v in result["metrics"].items()}


def test_every_new_metric_is_an_appended_entry_with_a_reader():
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    names = [m["name"] for m in per_layer]
    new = DPOR_SHARES + DPOR_OTHERS + SWEEP_SHARES
    assert tuple(names[-len(new):]) == new
    for m in per_layer[-len(new):]:
        assert m["layer"] == "drivers (host)" and m["source"] == "program_counter"
        assert os.path.exists(
            os.path.join(tiny.BENCH, "layer_metrics", m["name"] + ".py")
        )


def test_dpor_cell_reports_every_stage_metric_and_the_shares_close(bench):
    from demi_tpu import obs

    metrics = traced(bench, "tiny-dpor")
    for name in DPOR_SHARES + DPOR_OTHERS:
        assert name in metrics, sorted(metrics)
    assert sum(metrics[n] for n in DPOR_SHARES) == pytest.approx(100.0, abs=0.01)
    assert 0 < metrics["dpor.fresh_share"] <= 100
    assert metrics["dpor.admit_us_per_candidate"] > 0
    assert metrics["dpor.block_share"] > 0 and metrics["dpor.scan_share"] > 0
    totals = obs.stage_totals()
    rounds = tiny.TRAFFIC["tiny-dpor"]["job"]["rounds"]
    assert totals["dpor.round"]["count"] == rounds * totals["dpor.search"]["count"]
    assert not [n for n in metrics if n.startswith("sweep.")]


def test_sweep_cell_reports_every_stage_metric_and_the_shares_close(bench):
    metrics = traced(bench, "tiny-sweep")
    for name in SWEEP_SHARES:
        assert name in metrics, sorted(metrics)
    assert sum(metrics[n] for n in SWEEP_SHARES) == pytest.approx(100.0, abs=0.01)
    assert metrics["sweep.block_share"] > 0 and metrics["sweep.lower_share"] > 0


def test_a_program_without_the_tables_gives_no_metric_and_no_error(monkeypatch):
    from demi_tpu import obs

    obs.TRACER.clear()
    assert stage_share.share(stage_share.DPOR_ROOT, ("dpor.scan",)) is None
    assert stage_share.count_ratio("dpor.fresh", "dpor.candidates",
                                   stage_share.DPOR_ROOT) is None
    assert stage_share.seconds_per_count("dpor.admit", "dpor.candidates",
                                         stage_share.DPOR_ROOT) is None
    # the parent commit: demi_tpu.obs has no stage_totals
    monkeypatch.delattr(sys.modules["demi_tpu.obs"], "stage_totals")
    assert stage_share.tables() is None
    assert stage_share.share(stage_share.SWEEP_ROOT, ("sweep.block",)) is None
