"""A row of its own stages for every job (``obs.job_ledger()``,
obs/spans.py): one row a ``sweep()`` / ``explore()``, closed on its
root; the ``recorded`` / ``profiled`` flags; rows that keep coming once a
``jax.profiler`` session has ended while the totals and counts tables
and TRACER stand still; nothing at all where no session was ever seen
and telemetry is off; a raising stage; the bound; the producers' stages;
the counts that are the row's alone."""

import os
import threading

import pytest

from demi_tpu import obs
from demi_tpu.obs import spans as obs_spans

from test_stage_spans import (  # noqa: F401  (fixtures)
    _explore,
    _run_rows,
    _sweep,
    clean,
    reversal,
    sweeper,
)

ROOTS = {"dpor": "dpor.search", "sweep": "sweep.job"}


@pytest.fixture
def unseen(clean, monkeypatch):
    """A process that has seen no profiler session, whatever ran before
    this test in it."""
    monkeypatch.setattr(obs_spans, "_session_seen", False)


@pytest.fixture
def seen(clean, monkeypatch):
    """A process whose profiler session has ended: spans fold and do
    not record."""
    monkeypatch.setattr(obs_spans, "_session_seen", True)


def _run(driver, reversal, sweeper):
    if driver == "dpor":
        return _explore(reversal, rounds=3)
    return _sweep(sweeper)


def _selfs(row) -> float:
    return sum(s["self_seconds"] for s in row["stages"].values())


# -- a row a job --------------------------------------------------------------

@pytest.mark.parametrize("driver", ["dpor", "sweep"])
def test_a_job_leaves_one_row_that_closes_on_its_root(
    unseen, reversal, sweeper, driver
):
    obs.enable()
    _run(driver, reversal, sweeper)
    _run(driver, reversal, sweeper)
    obs.disable()
    first, second = obs.job_ledger()
    root = ROOTS[driver]
    assert first["root"] == second["root"] == root
    assert second["job"] == first["job"] + 1
    assert second["start_s"] >= first["start_s"] + first["seconds"]
    assert first["recorded"] and not first["profiled"]
    for row in (first, second):
        assert row["stages"][root]["count"] == 1
        assert row["stages"][root]["seconds"] == row["seconds"]
        # every stage that closed under the root, the collector's
        # passes among them: the self seconds are the root's seconds
        assert _selfs(row) == pytest.approx(row["seconds"], abs=1e-6)
    if driver == "dpor":
        assert first["args"] == {"max_rounds": 3}
        assert first["stages"]["dpor.round"]["count"] == 3
        assert first["counts"]["dpor.candidates"] >= first["counts"]["dpor.fresh"] > 0
    else:
        assert first["args"] == {"lanes": 24}
        assert first["counts"]["sweep.programs"] == 24
        assert first["counts"]["sweep.retired"] == 24
    # the two rows together are what the totals table folded
    totals = _run_rows(obs.stage_totals())
    for name, total in totals.items():
        assert total["count"] == sum(
            row["stages"].get(name, {"count": 0})["count"]
            for row in (first, second)
        ), name
    counts = obs.stage_counts()
    for name in first["counts"].keys() & counts.keys():
        assert counts[name] == first["counts"][name] + second["counts"][name]


def test_a_root_under_another_span_opens_the_row(unseen):
    """``cli.dpor`` wraps the search under ``--stats-out``: the row is
    the span's that carries ``job=``, not the outermost's."""
    obs.enable()
    with obs.span("t.verb"):
        with obs.span("t.root", job=7, lanes=2):
            with obs.span("t.stage", job=8):    # a job under a job: a stage
                pass
    obs.disable()
    (row,) = obs.job_ledger()
    assert (row["job"], row["root"], row["args"]) == (7, "t.root", {"lanes": 2})
    assert set(row["stages"]) == {"t.root", "t.stage"}


# -- the flags, and folding without recording ----------------------------------

def test_rows_keep_coming_after_a_profiler_session_and_nothing_else_does(
    unseen, reversal, sweeper, tmp_path
):
    import jax

    assert not obs_spans.folding()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _explore(reversal, rounds=2)
        _sweep(sweeper)
    finally:
        jax.profiler.stop_trace()
    assert not obs_spans.live() and obs_spans.folding()
    traced = obs.job_ledger()
    assert [r["root"] for r in traced] == ["dpor.search", "sweep.job"]
    assert all(r["recorded"] and r["profiled"] for r in traced)
    # (the set-up stages record with no switch: ``DeviceDPOR.__init__``)
    totals, counts = _run_rows(obs.stage_totals()), obs.stage_counts()
    recorded = len(obs.TRACER.spans)

    _explore(reversal, rounds=2)
    _sweep(sweeper)
    assert _run_rows(obs.stage_totals()) == totals
    assert obs.stage_counts() == counts
    assert len(obs.TRACER.spans) == recorded
    assert obs_spans.current_depth() == 0
    rows = obs.job_ledger()
    assert rows[:2] == traced
    dpor, sweep = rows[2:]
    assert not (dpor["recorded"] or dpor["profiled"])
    assert not (sweep["recorded"] or sweep["profiled"])
    # the same search, the same sweep: the same stages ran, and as often
    # where the device's pace does not say how often (a fill made ahead)
    timed = {"gc.pause", "sweep.fill", "sweep.fuzz", "sweep.lower", "sweep.stack"}
    for was, now in zip(traced, (dpor, sweep)):
        assert set(now["stages"]) - timed == set(was["stages"]) - timed
        for name in set(now["stages"]) - timed:
            assert now["stages"][name]["count"] == was["stages"][name]["count"], name
        assert _selfs(now) == pytest.approx(now["seconds"], abs=1e-6)
    assert dpor["counts"] == traced[0]["counts"]
    # the counts that cost no device work fold; a sample or a pull of
    # the device's (the pool's peak, the rows inserted) stays the
    # traced job's
    folded = {
        "sweep.segments", "sweep.segments_queued", "sweep.retired",
        "sweep.budget_retired", "sweep.programs", "sweep.produced",
        "sweep.prefetched", "sweep.producers", "sweep.lane_steps",
        "sweep.live_lane_steps", "sweep.dispatch_ns", "sweep.wait_ns",
    }
    assert set(sweep["counts"]) == folded
    assert folded < set(traced[1]["counts"])
    assert "sweep.pool_peak_rows" in traced[1]["counts"]
    for name in folded - {"sweep.segments_queued", "sweep.prefetched",
                          "sweep.dispatch_ns", "sweep.wait_ns"}:
        assert sweep["counts"][name] == traced[1]["counts"][name], name


def test_the_clock_pairs_of_a_round_are_the_rows_alone(seen, sweeper):
    _sweep(sweeper)
    obs.enable()
    _sweep(sweeper)
    obs.disable()
    for row in obs.job_ledger():
        counts, stages = row["counts"], row["stages"]
        # the pairs hold the round's two ``sweep.block`` spans and the
        # ``sweep.finalize`` between them, and lie inside the round
        pairs = (counts["sweep.dispatch_ns"] + counts["sweep.wait_ns"]) / 1e9
        inside = sum(
            stages[name]["seconds"]
            for name in ("sweep.block", "sweep.finalize") if name in stages
        )
        assert 0 < inside <= pairs + 1e-6
        assert pairs <= stages["sweep.round"]["seconds"]
    assert "sweep.dispatch_ns" not in obs.stage_counts()
    assert "sweep.wait_ns" not in obs.stage_counts()


def test_off_with_no_session_ever_a_span_keeps_nothing(unseen, sweeper):
    """What a ``--trace 0`` run pays: no row, and not even the thread's
    stack is made."""
    made = {}

    def job():
        with obs.span("t.root", job=1) as sp:
            with obs.span("t.stage"):
                obs.stage_count("t.n")
                obs_spans.job_count("t.m")
            sp.slice("t.part", 5)
        made.update(vars(obs_spans._local), seconds=sp.seconds)

    worker = threading.Thread(target=job)
    worker.start()
    worker.join(30)
    assert made == {"seconds": 0.0}
    _sweep(sweeper)
    assert obs.job_ledger() == [] and obs.TRACER.spans == []
    assert not obs_spans.folding()


# -- a row ends with its root, however that ends --------------------------------

def test_a_raising_stage_still_closes_its_row(seen):
    with pytest.raises(ValueError):
        with obs.span("t.root", job=3):
            with obs.span("t.stage"):
                obs.span("t.orphan").__enter__()
                raise ValueError("stage blew up")
    (row,) = obs.job_ledger()
    assert row["args"] == {"error": "ValueError"}
    assert {n: s["count"] for n, s in row["stages"].items()} == {
        "t.root": 1, "t.stage": 1, "t.orphan": 1,
    }
    assert _selfs(row) == pytest.approx(row["seconds"], abs=1e-9)
    assert obs_spans.current_depth() == 0
    # the thread's next job gets a row of its own
    with obs.span("t.root", job=4):
        pass
    assert [r["job"] for r in obs.job_ledger()] == [3, 4]
    assert obs.stage_totals() == {} or "t.root" not in obs.stage_totals()


def test_the_rows_are_bounded_and_cleared_with_the_tables(seen):
    for job in range(obs_spans._ROWS_MAX + 40):
        with obs.span("t.root", job=job):
            pass
    rows = obs.job_ledger()
    assert len(rows) == obs_spans._ROWS_MAX
    assert [rows[0]["job"], rows[-1]["job"]] == [40, obs_spans._ROWS_MAX + 39]
    # a copy: the caller's edits stay the caller's
    rows[0]["stages"].clear()
    assert obs.job_ledger()[0]["stages"]
    obs.TRACER.clear()
    assert obs.job_ledger() == []
    with obs.span("t.root", job=1):
        pass
    obs_spans._reset_setup()
    assert obs.job_ledger() == []


# -- the producers' stages ------------------------------------------------------

@pytest.mark.skipif(not hasattr(os, "fork"), reason="producer processes need os.fork")
def test_a_producers_job_has_its_forks_and_its_waits_in_its_row(seen, monkeypatch):
    """The prime fill's race decided as ``test_continuous_producers``
    decides it: no child makes a program before the host thread is
    inside a ``sweep.starve`` wait."""
    import multiprocessing

    from demi_tpu.device import continuous
    from demi_tpu.parallel.sweep import SweepDriver
    from test_continuous_producers import _raft

    app, cfg, fuzzer = _raft()
    host = os.getpid()
    waited_for = multiprocessing.get_context("fork").Event()

    def gen(seed):
        if os.getpid() != host:
            assert waited_for.wait(60)
        return fuzzer.generate_fuzz_test(seed=seed)

    reaped = continuous._Producers._reaped

    def waiting(self, i):
        waited_for.set()
        return reaped(self, i)

    monkeypatch.setattr(continuous._Producers, "_reaped", waiting)
    lanes, batch = 160, 2 * continuous._PROBE
    driver = SweepDriver(app, cfg, gen)
    driver._continuous_driver(batch)._producers = 2
    result = driver.sweep(lanes, batch, mode="continuous")
    assert result.lanes == lanes
    (row,) = obs.job_ledger()
    assert not row["recorded"]
    assert row["stages"]["sweep.fork"]["count"] == 1
    assert row["stages"]["sweep.starve"]["count"] >= 1
    assert row["counts"]["sweep.producers"] == 2
    assert row["counts"]["sweep.produced"] == lanes - continuous._PROBE
    assert row["counts"]["sweep.producer_ns"] > 0
    assert _selfs(row) == pytest.approx(row["seconds"], abs=1e-6)
    assert "sweep.fork" not in obs.stage_totals()


# -- the operator's use ---------------------------------------------------------

@pytest.mark.parametrize("verb", ["sweep", "dpor"])
def test_stats_out_carries_the_runs_jobs(unseen, tmp_path, verb):
    import json

    from demi_tpu.cli import main

    out = tmp_path / "stats.json"
    rc = main({
        "sweep": ["sweep", "--app", "broadcast", "--nodes", "3", "--batch",
                  "16", "--chunk", "8"],
        "dpor": ["dpor", "--app", "broadcast", "--nodes", "3", "--batch",
                 "8", "--rounds", "2"],
    }[verb] + ["--stats-out", str(out)])
    obs.disable()
    assert rc in (0, 1)
    (row,) = json.loads(out.read_text())["jobs"]
    assert row["root"] == ROOTS[verb] and row["recorded"]
    assert not row["profiled"]
    assert _selfs(row) == pytest.approx(row["seconds"], abs=1e-6)
    assert row["stages"][ROOTS[verb]]["count"] == 1
