"""The per-layer metrics that read the rows of the window's jobs
(``lib/job_rows.py`` over ``demi_tpu.obs.job_ledger()``, PR 49): thirteen
entries of ``per_layer`` looked up by name (never as the list's tail: a
later PR appends), each with a reader; the window picked out of recorded
rows (after the last profiled row, the first ``stats.jobs`` of them),
None on too few rows, on a row longer than its job, on sums that part,
and on a program without the ledger (the PR's parent); and a ``--trace
1`` run of the tiny sweep and dpor cells in which they all print, from
rows the totals table never saw."""

import json
import os
import time
from types import SimpleNamespace

import pytest

import tiny
from lib import cells, harness, job_rows

SWEEP = (
    "sweep.traced_job_stretch", "sweep.window_device_idle_share",
    "sweep.window_prime_share", "sweep.window_fork_share",
    "sweep.window_starve_share", "sweep.window_dispatch_share",
    "sweep.window_wait_share", "sweep.window_queued_segment_share",
    "sweep.window_unattributed_share",
)
DPOR = (
    "dpor.traced_job_stretch", "dpor.window_block_share",
    "dpor.window_scan_share", "dpor.window_gc_pause_share",
)
LAYER = {
    "sweep.traced_job_stretch": "entry point / harness",
    "dpor.traced_job_stretch": "entry point / harness",
    "sweep.window_device_idle_share": "device",
}
HIGHER = {
    "sweep.window_wait_share", "sweep.window_queued_segment_share",
    "dpor.window_block_share",
}


@pytest.mark.parametrize("name", SWEEP + DPOR)
def test_each_metric_is_an_entry_with_a_reader(name):
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    rate = "interleavings_per_s" if name in DPOR else "schedules_per_s"
    (moved,) = [m for m in bench["end_to_end"] if m["name"] == rate]
    assert entry == {
        "name": name,
        "unit": "ratio" if name.endswith("stretch") else "%",
        "better": "higher" if name in HIGHER else "lower",
        "source": "program_counter"
        if name == "sweep.window_queued_segment_share" else "program_span",
        "layer": LAYER.get(name, "drivers (host)"),
        "moves": rate,
        # every cell of the verb: one loop runs them all
        "workloads": moved["workloads"],
    }
    assert os.path.exists(
        os.path.join(tiny.BENCH, "layer_metrics", name + ".py")
    )


# -- the window, out of recorded rows -----------------------------------------

def _row(root, seconds, *, profiled=False, recorded=None, **stages):
    return {
        "job": 0, "root": root, "args": {}, "start_s": 0.0,
        "seconds": seconds, "profiled": profiled,
        "recorded": profiled if recorded is None else recorded,
        "stages": {
            name.replace("_", ".", 1): {
                "count": 1, "seconds": s, "self_seconds": s,
            }
            for name, s in stages.items()
        },
        "counts": {},
    }


def _obs(job_seconds, **kw):
    per_job = [(i, i, 1, s) for i, s in enumerate(job_seconds)]
    stats = SimpleNamespace(jobs=len(per_job), per_job=per_job)
    return SimpleNamespace(stats=stats, **kw)


@pytest.fixture
def rows(monkeypatch):
    held = []
    monkeypatch.setattr(job_rows, "ledger", lambda: list(held))
    return held


def test_the_window_follows_the_last_profiled_row(rows):
    sweep = job_rows.SWEEP_ROOT
    rows += [
        _row(sweep, 9.0),                       # before the session: not the window's
        _row(sweep, 3.0, profiled=True),
        _row(job_rows.DPOR_ROOT, 5.0, profiled=True),
        _row(sweep, 1.0, sweep_block=0.4),
        _row("device.sweep.chunk", 7.0),        # another root between two jobs
        _row(sweep, 1.2, recorded=True),        # telemetry on: not a folded row
        _row(sweep, 2.0, sweep_block=1.0),
        _row(sweep, 4.0),                       # the check's job, past the window
    ]
    obs = _obs([1.01, 2.02])
    assert [r["seconds"] for r in job_rows.window(obs, sweep)] == [1.0, 2.0]
    assert job_rows.stretch(obs, sweep) == pytest.approx(3.0 / 1.5)
    assert job_rows.stage_share(obs, sweep, ("sweep.block",)) == pytest.approx(
        100.0 * 1.4 / 3.0
    )
    assert job_rows.stage_share(obs, sweep, ("sweep.fork",)) == 0.0
    assert job_rows.unattributed_share(obs, sweep, ("sweep.block",)) == 0.0
    # the searches' rows are another window
    assert job_rows.window(obs, job_rows.DPOR_ROOT) is None
    assert job_rows.stretch(obs, job_rows.DPOR_ROOT) is None


@pytest.mark.parametrize("why, seconds, jobs", [
    ("too few rows", [1.0], [1.01, 2.02]),
    ("a row longer than its job", [1.0, 2.1], [1.01, 2.02]),
    ("sums more than the tolerance apart", [1.0, 1.8], [1.01, 2.02]),
    ("no row at all", [], [1.0]),
])
def test_rows_that_do_not_match_the_windows_jobs_give_none(rows, why, seconds, jobs):
    sweep = job_rows.SWEEP_ROOT
    rows += [_row(sweep, 3.0, profiled=True)] + [_row(sweep, s) for s in seconds]
    trace = {"busy_s": 1.2, "counters": {"lane_steps": 1000}}
    obs = _obs(jobs, on_chip=True, trace=trace, counters={"lane_steps": 2000})
    assert job_rows.window(obs, sweep) is None, why
    for read in (
        lambda: job_rows.stretch(obs, sweep),
        lambda: job_rows.stage_share(obs, sweep, ("sweep.block",)),
        lambda: job_rows.unattributed_share(obs, sweep, ()),
        lambda: job_rows.ns_share(obs, sweep, "sweep.wait_ns"),
        lambda: job_rows.count_ratio(obs, sweep, "a", "b"),
        lambda: job_rows.device_idle_share(obs, sweep),
    ):
        assert read() is None


def test_counts_are_summed_over_the_windows_rows(rows):
    sweep = job_rows.SWEEP_ROOT
    rows += [_row(sweep, 3.0, profiled=True), _row(sweep, 1.0), _row(sweep, 3.0)]
    rows[1]["counts"] = {"sweep.wait_ns": 250_000_000, "sweep.segments": 4,
                         "sweep.segments_queued": 3}
    rows[2]["counts"] = {"sweep.wait_ns": 750_000_000, "sweep.segments": 4,
                         "sweep.segments_queued": 1}
    obs = _obs([1.0, 3.0])
    assert job_rows.ns_share(obs, sweep, "sweep.wait_ns") == pytest.approx(25.0)
    assert job_rows.ns_share(obs, sweep, "sweep.dispatch_ns") is None
    assert job_rows.count_ratio(
        obs, sweep, "sweep.segments_queued", "sweep.segments"
    ) == pytest.approx(50.0)
    assert job_rows.count_ratio(obs, sweep, "sweep.produced", "sweep.programs") is None


def test_the_windows_idle_chip_is_the_traced_busy_seconds_a_lane_step(rows):
    sweep = job_rows.SWEEP_ROOT
    rows += [_row(sweep, 3.0, profiled=True), _row(sweep, 2.0), _row(sweep, 2.0)]
    trace = {"busy_s": 1.2, "counters": {"lane_steps": 1000}}
    obs = _obs([2.0, 2.0], on_chip=True, trace=trace,
               counters={"lane_steps": 2000})
    # busy 1.2 s a traced job of 1,000 lane-steps: 2.4 s of the window's 4
    assert job_rows.device_idle_share(obs, sweep) == pytest.approx(40.0)
    obs.on_chip = False
    assert job_rows.device_idle_share(obs, sweep) is None
    obs.on_chip, obs.trace = True, None
    assert job_rows.device_idle_share(obs, sweep) is None


def test_a_program_without_the_ledger_gives_none_and_no_error(monkeypatch):
    import demi_tpu.obs

    monkeypatch.delattr(demi_tpu.obs, "job_ledger")
    assert job_rows.ledger() is None
    obs = _obs([1.0], on_chip=True, trace=None, counters={})
    bench = os.path.join(tiny.ROOT, "BENCHMARK.json")
    for cell, names in (("raft5-sweep", SWEEP), ("raft5-dpor", DPOR)):
        for name in names:
            read = cells.load_reader(cells.load_cell(bench, cell), name)
            assert read(obs) is None, name


# -- a --trace 1 run of the tiny cells -----------------------------------------

@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny.write(str(tmp_path_factory.mktemp("tiny")))


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
    return metrics, obs.job_ledger(), obs.stage_totals()


def test_a_traced_run_of_the_sweep_cell_prints_them_from_the_windows_rows(bench):
    metrics, rows, totals = _traced(bench, "tiny-sweep", 2**31 + 4901)
    traced = [r for r in rows if r["profiled"]]
    folded = [r for r in rows if not r["recorded"]]
    assert traced and all(r["recorded"] for r in traced)
    assert len(folded) >= 1 and rows.index(folded[0]) > rows.index(traced[-1])
    # the totals table holds the traced jobs alone, as before
    assert totals["sweep.job"]["count"] == len(traced)
    assert totals["sweep.job"]["seconds"] == pytest.approx(
        sum(r["seconds"] for r in traced)
    )
    # all print but the chip's (no device trace on the CPU)
    assert set(SWEEP) - set(metrics) == {"sweep.window_device_idle_share"}
    assert metrics["sweep.traced_job_stretch"] > 0
    for name in SWEEP[2:]:
        assert 0.0 <= metrics[name] <= 100.0, name
    assert metrics["sweep.window_fork_share"] == 0.0        # 96 programs fork none
    assert metrics["sweep.window_starve_share"] == 0.0
    assert metrics["sweep.window_prime_share"] > 0
    # the round's two clock pairs hold the stages they bracket
    assert metrics["sweep.window_dispatch_share"] + metrics[
        "sweep.window_wait_share"
    ] >= 100.0 * sum(
        r["stages"]["sweep.block"]["seconds"] for r in folded
    ) / sum(r["seconds"] for r in folded) - 1e-6
    # the metrics that read the traced job still print beside them
    assert {"sweep.block_share", "sweep.queued_segment_share",
            "sweep.unattributed_share", "sweep.starve_share"} <= set(metrics)


def test_a_traced_run_of_the_dpor_cell_prints_them_from_the_windows_rows(
    bench, monkeypatch
):
    # a tiny search is a few rounds: building its DeviceDPOR, which the
    # verb does before ``dpor.search`` opens, is a larger part of the job
    # than of the cell's 64-round one
    monkeypatch.setitem(job_rows.TOLERANCE, job_rows.DPOR_ROOT, 0.5)
    metrics, rows, totals = _traced(bench, "tiny-dpor", 2**31 + 4902)
    traced = [r for r in rows if r["profiled"]]
    assert totals["dpor.search"]["count"] == len(traced) >= 1
    assert set(DPOR) <= set(metrics)
    assert metrics["dpor.traced_job_stretch"] > 0
    assert 0 < metrics["dpor.window_block_share"] < 100
    assert 0 < metrics["dpor.window_scan_share"] < 100
    assert 0 <= metrics["dpor.window_gc_pause_share"] < 100
    assert {"dpor.block_share", "dpor.gc_pause_share", "dpor.gc_share"} <= set(metrics)
