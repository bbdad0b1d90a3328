"""Stage spans inside the DPOR round and the continuous sweep round
(obs/spans.py + the two host drivers): the totals table's arithmetic,
liveness under a ``jax.profiler`` session with no switch set, silence
when off, closure of the stage shares, determinism with spans on and
off, no span across ``_run_batches``'s yield, job and parent links, and
the attributes the benchmark's harness wraps."""

import gc
import glob
import importlib.util
import json
import os
import time

import pytest

from demi_tpu import obs
from demi_tpu.obs import spans as obs_spans
from demi_tpu.obs.profiler import PROFILER

from test_device_dpor import _setup

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DPOR_STAGES = (
    "dpor.select", "dpor.pack", "dpor.dispatch", "dpor.block", "dpor.pull",
    "dpor.violations", "dpor.scan", "dpor.admit", "dpor.account",
)
SWEEP_STAGES = (
    "sweep.prime", "sweep.round", "sweep.block", "sweep.pull",
    "sweep.retire", "sweep.fill", "sweep.fuzz", "sweep.lower", "sweep.stack",
    "sweep.refill", "sweep.fold", "sweep.finish",
)


SETUP_ROWS = ("setup.", "compile.")


def _run_rows(table):
    """A totals table less the set-up ledger's rows (``setup.*`` stages
    and ``compile.*`` events), which record with no switch: what the
    run's own spans left."""
    return {k: v for k, v in table.items() if not k.startswith(SETUP_ROWS)}


@pytest.fixture
def clean():
    """Spans off and every table empty, before and after."""
    obs.disable()
    PROFILER.disable()
    obs.TRACER.clear()
    yield
    obs.disable()
    PROFILER.disable()
    obs.TRACER.clear()


@pytest.fixture(scope="module")
def reversal():
    """The k=3 reversal app and ONE jitted DPOR kernel for the module."""
    from demi_tpu.device.dpor_sweep import make_dpor_kernel

    app, cfg, program = _setup(3)
    return app, cfg, program, make_dpor_kernel(app, cfg)


@pytest.fixture(scope="module")
def sweeper():
    """One SweepDriver over a 3-node broadcast: its continuous driver
    (and the kernels under it) is cached per batch, so every sweep of
    the module shares them."""
    from demi_tpu.apps.broadcast import (
        broadcast_send_generator,
        make_broadcast_app,
    )
    from demi_tpu.apps.common import dsl_start_events
    from demi_tpu.device import DeviceConfig
    from demi_tpu.fuzzing import Fuzzer, FuzzerWeights
    from demi_tpu.parallel.sweep import SweepDriver

    app = make_broadcast_app(3, reliable=False)
    cfg = DeviceConfig.for_app(
        app, pool_capacity=32, max_steps=48, max_external_ops=16,
        invariant_interval=1,
    )
    fuzzer = Fuzzer(
        num_events=6,
        weights=FuzzerWeights(send=0.7, wait_quiescence=0.15),
        message_gen=broadcast_send_generator(app),
        prefix=dsl_start_events(app),
    )
    return SweepDriver(app, cfg, lambda s: fuzzer.generate_fuzz_test(seed=s))


def _dpor(reversal, batch_size=2):
    from demi_tpu.device.dpor_sweep import DeviceDPOR

    app, cfg, program, kernel = reversal
    return DeviceDPOR(app, cfg, program, batch_size=batch_size, kernel=kernel)


def _explore(reversal, rounds=2):
    d = _dpor(reversal)
    d.explore(target_code=2, max_rounds=rounds)  # code 2 never occurs
    return d


def _sweep(sweeper):
    return sweeper.sweep(24, 8, mode="continuous")


def _tree_closes(root: str) -> None:
    """Self times of every name under ``root`` sum to its seconds."""
    totals = obs.stage_totals()
    family = root.split(".")[0] + "."
    selfs = sum(
        t["self_seconds"] for name, t in totals.items()
        if name.startswith(family) or name == "gc.pause"
    )
    assert selfs == pytest.approx(totals[root]["seconds"], abs=1e-6)


# -- (a) the totals table ----------------------------------------------------

def test_totals_count_seconds_and_self_seconds(clean):
    obs.enable()
    for _ in range(3):
        with obs.span("t.outer"):
            with obs.span("t.inner"):
                time.sleep(0.002)
            with obs.span("t.inner"):
                pass
    totals = obs.stage_totals()
    assert totals["t.outer"]["count"] == 3
    assert totals["t.inner"]["count"] == 6
    assert totals["t.inner"]["seconds"] >= 0.006
    # self = duration - children, to the nanosecond
    assert totals["t.outer"]["self_seconds"] == pytest.approx(
        totals["t.outer"]["seconds"] - totals["t.inner"]["seconds"], abs=1e-9
    )
    assert totals["t.inner"]["self_seconds"] == totals["t.inner"]["seconds"]
    # a slice takes its nanoseconds out of the open span's self time
    with obs.span("t.loop") as sp:
        time.sleep(0.002)
        sp.slice("t.part", 1_500_000)
    totals = obs.stage_totals()
    assert totals["t.part"] == {
        "count": 1, "seconds": 0.0015, "self_seconds": 0.0015,
    }
    assert totals["t.loop"]["self_seconds"] == pytest.approx(
        totals["t.loop"]["seconds"] - 0.0015, abs=1e-9
    )
    assert "t.part" not in {s["name"] for s in obs.TRACER.spans}
    obs.stage_count("t.things", 5)
    obs.stage_count("t.things")
    assert obs.stage_counts() == {"t.things": 6}
    obs.TRACER.clear()
    assert obs.stage_totals() == {} and obs.stage_counts() == {}


def test_gc_pause_is_a_child_the_parent_self_time_excludes(clean):
    obs.enable()
    with obs.span("t.stage") as sp:
        gc.collect()
    obs.disable()
    totals = obs.stage_totals()
    assert totals["gc.pause"]["count"] >= 1
    assert totals["t.stage"]["self_seconds"] == pytest.approx(
        totals["t.stage"]["seconds"] - totals["gc.pause"]["seconds"], abs=1e-9
    )
    stage = next(s for s in obs.TRACER.spans if s["name"] == "t.stage")
    pauses = [s for s in obs.TRACER.spans if s["name"] == "gc.pause"]
    assert all(p["parent"] == stage["op_b"] for p in pauses)
    assert 2 in {p["args"]["generation"] for p in pauses}
    assert sp.seconds == pytest.approx(totals["t.stage"]["seconds"])
    # with no span open a collection records nothing
    gc.collect()
    assert obs.stage_totals()["gc.pause"]["count"] == len(pauses)


def test_abandoned_inner_span_still_folds(clean):
    obs.enable()
    with pytest.raises(ValueError):
        with obs.span("t.outer"):
            obs.span("t.orphan").__enter__()
            raise ValueError("stage blew up")
    totals = obs.stage_totals()
    assert totals["t.orphan"]["count"] == totals["t.outer"]["count"] == 1
    assert totals["t.outer"]["self_seconds"] == pytest.approx(
        totals["t.outer"]["seconds"] - totals["t.orphan"]["seconds"], abs=1e-9
    )
    assert obs_spans.current_depth() == 0


# -- (b) live under the profiler, with DEMI_OBS off --------------------------

def _host_events(trace_dir: str) -> set:
    import jax

    (path,) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    data = jax.profiler.ProfileData.from_file(path)
    return {
        ev.name
        for plane in data.planes if plane.name == "/host:CPU"
        for line in plane.lines for ev in line.events
        if ev.name.startswith("demi.")
    }


def test_live_under_a_profiler_session_with_no_switch(
    clean, reversal, sweeper, tmp_path
):
    import jax

    assert not obs.enabled() and not obs_spans.live()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        assert obs_spans.live()
        _explore(reversal, rounds=2)
        _sweep(sweeper)
    finally:
        jax.profiler.stop_trace()
    assert not obs_spans.live()
    totals = _run_rows(obs.stage_totals())
    assert totals["dpor.round"]["count"] == 2
    assert totals["dpor.search"]["count"] == totals["sweep.job"]["count"] == 1
    names = _host_events(str(tmp_path))
    assert {"demi.dpor.scan", "demi.dpor.block", "demi.sweep.block",
            "demi.sweep.fill", "demi.sweep.job"} <= names
    # once the session has stopped, a further search adds nothing
    _explore(reversal, rounds=2)
    assert _run_rows(obs.stage_totals()) == totals


# -- (c) off -----------------------------------------------------------------

def test_off_records_nothing(clean, reversal, sweeper):
    _explore(reversal)
    _sweep(sweeper)
    assert _run_rows(obs.stage_totals()) == {} and obs.stage_counts() == {}
    assert obs.TRACER.spans == []
    assert obs_spans.current_depth() == 0


# -- (d) closure -------------------------------------------------------------

@pytest.mark.parametrize("driver", ["dpor", "sweep"])
def test_stage_self_times_close_on_the_root(clean, reversal, sweeper, driver):
    obs.enable()
    if driver == "dpor":
        _explore(reversal, rounds=3)
        root, stages = "dpor.search", DPOR_STAGES
    else:
        _sweep(sweeper)
        root, stages = "sweep.job", SWEEP_STAGES
    obs.disable()
    totals = obs.stage_totals()
    assert set(stages) <= set(totals), sorted(totals)
    _tree_closes(root)


def test_counts_at_the_stage_boundaries(clean, reversal, sweeper):
    obs.enable()
    d = _explore(reversal, rounds=3)
    result = _sweep(sweeper)
    obs.disable()
    counts = obs.stage_counts()
    # Every count kept has a reader: the benchmark's dpor.fresh_share,
    # dpor.admit_us_per_candidate and dpor.materialized_share; the sweep's
    # sweep.live_step_share and sweep.fault_op_share (PR 27),
    # sweep.prefetch_share (PR 28), sweep.row_lowered_share (PR 30),
    # sweep.quiesced_share and sweep.pool_peak_share (PR 31),
    # sweep.outbox_fill_share (PR 33), sweep.produced_share and
    # sweep.starve_share (PR 42: ``sweep.producers`` says the program has
    # producers at all; 24 programs are worth none),
    # sweep.queued_segment_share (PR 46), sweep.budget_refill_share (PR 48).
    op_kinds = {
        "start", "send", "wait", "kill", "hard_kill", "restart",
        "partition", "unpartition",
    }
    assert set(counts) == {
        "dpor.candidates", "dpor.fresh", "dpor.materialized",
        "sweep.lane_steps", "sweep.live_lane_steps",
        "sweep.segments", "sweep.segments_queued",
        "sweep.programs", "sweep.prefetched", "sweep.row_lowered",
        "sweep.produced", "sweep.producers",
        "sweep.retired", "sweep.quiesced", "sweep.unfinished",
        "sweep.budget_retired",
        "sweep.pool_peak_rows", "sweep.pool_rows",
        "sweep.rows_inserted", "sweep.outbox_rows",
    } | {f"sweep.ops.{kind}" for kind in op_kinds}
    assert counts["dpor.candidates"] >= counts["dpor.fresh"] > 0
    assert counts["dpor.fresh"] == len(d.explored) - 1    # the root was seeded
    assert result.lanes == 24
    assert 0 < counts["sweep.live_lane_steps"] <= counts["sweep.lane_steps"]
    # a dispatch a segment; 4 segments a life, so the harvest lags one
    # (since PR 48), and a job's first dispatch finds nothing queued
    drv = sweeper._continuous_driver(8)
    assert counts["sweep.lane_steps"] == (
        counts["sweep.segments"] * 8 * drv.seg_steps
    )
    assert drv._lag() == 1
    assert 0 <= counts["sweep.segments_queued"] < counts["sweep.segments"]
    # the lanes the host knew spent when it dispatched their last segment
    assert 0 <= counts["sweep.budget_retired"] <= counts["sweep.retired"]
    # one program a schedule put in a lane; the prime fill's 8 never ahead
    assert counts["sweep.programs"] == 24
    assert 0 <= counts["sweep.prefetched"] <= 24 - 8
    assert counts["sweep.produced"] == counts["sweep.producers"] == 0
    # the sweeper's generator is the fuzzer: every program from its rows
    assert counts["sweep.row_lowered"] == 24
    # 24 programs of the sweeper's: every actor started once in each
    assert counts["sweep.ops.start"] == 24 * sweeper.app.num_actors
    assert counts["sweep.ops.restart"] == 0
    # every lane retired with a verdict; one job's fullest pool, sampled
    assert counts["sweep.retired"] == counts["sweep.quiesced"] == 24
    assert counts["sweep.unfinished"] == 0
    assert counts["sweep.pool_rows"] == sweeper.cfg.pool_capacity
    assert 0 < counts["sweep.pool_peak_rows"] <= counts["sweep.pool_rows"]
    # what the 24 lanes put in their pools, against their deliveries'
    # outbox rows through the insert
    assert counts["sweep.outbox_rows"] % sweeper.cfg.max_outbox == 0
    assert 0 < counts["sweep.rows_inserted"] <= (
        counts["sweep.outbox_rows"] + 24 * sweeper.cfg.max_external_ops
    )


@pytest.mark.parametrize("mode", ["default", "sleep_sets", "max_distance"])
def test_tuples_are_materialized_only_where_a_mode_needs_them(
    clean, reversal, mode
):
    """``dpor.materialized`` counts log entries turned into Python
    tuples: every admitted one where the driver's own configuration
    needs the tuple (sleep sets key their side tables by it, the
    distance gate measures it), and only what a round's selection
    compared otherwise."""
    from demi_tpu.device.dpor_sweep import DeviceDPOR

    app, cfg, program, kernel = reversal
    if mode == "sleep_sets":
        d = DeviceDPOR(app, cfg, program, batch_size=2, sleep_sets=True)
    else:
        d = DeviceDPOR(app, cfg, program, batch_size=2, kernel=kernel)
    if mode == "max_distance":
        d.max_distance = 1 << 20
    obs.enable()
    d.explore(target_code=2, max_rounds=3)
    obs.disable()
    counts = obs.stage_counts()
    assert counts["dpor.fresh"] == len(d.explored) - 1 > 0
    if mode == "default":
        assert counts["dpor.materialized"] < counts["dpor.fresh"]
    else:
        assert counts["dpor.materialized"] == counts["dpor.fresh"]


def test_launch_ledger_is_fed_from_the_stage_spans(clean, reversal):
    PROFILER.reset()
    PROFILER.enable()
    try:
        _explore(reversal, rounds=2)
        rows = PROFILER.evidence()["launches"]
    finally:
        PROFILER.disable()
        PROFILER.reset()
    totals = obs.stage_totals()
    for kind, stage in (
        ("dispatch", "dpor.dispatch"), ("block", "dpor.block"),
        ("host", "dpor.scan"),
    ):
        row = next(
            r for r in rows if r["kind"] == kind and r["kernel"].startswith("dpor")
        )
        assert row["launches"] == totals[stage]["count"] == 2
        assert row["seconds"] == pytest.approx(
            totals[stage]["seconds"], abs=1e-5
        )


# -- (e) determinism ---------------------------------------------------------

@pytest.mark.parametrize("driver", ["dpor", "sweep"])
def test_spans_change_nothing_the_driver_computes(
    clean, reversal, sweeper, driver
):
    def run():
        if driver == "dpor":
            d = _explore(reversal, rounds=3)
            return d.explored, d.frontier, d.interleavings
        return _sweep(sweeper).lanes_digest

    off = run()
    obs.enable()
    on = run()
    obs.disable()
    assert obs.stage_totals()  # the second run was live
    assert on == off


# -- (f) no span across the yield --------------------------------------------

@pytest.fixture
def busy_device(monkeypatch):
    """The segment never reads ready before its pull: every gap between
    a dispatch and the pull makes programs ahead, up to its room."""
    from demi_tpu.device import continuous

    monkeypatch.setattr(continuous, "_ready", lambda _array: False)


def test_programs_made_ahead_are_a_fill_outside_the_block(
    clean, sweeper, busy_device
):
    """``sweep.block`` is the dispatch and the wait at the pull, twice a
    round, and holds no fill; programs made in the gap are a
    ``sweep.fill`` under the round whose slices reach ``sweep.fuzz`` and
    ``sweep.lower`` like any other's; every refill is served from the
    stock when the device stays busy."""
    slept = []

    def slow(seed):
        time.sleep(0.002)
        slept.append(seed)
        return gen(seed)

    drv = sweeper._continuous_driver(8)
    gen, drv.program_gen = drv.program_gen, slow
    obs.enable()
    try:
        result = _sweep(sweeper)
    finally:
        obs.disable()
        drv.program_gen = gen
    assert result.lanes == 24 and slept == list(range(24))
    spans = obs.TRACER.spans
    by_op = {s["op_b"]: s for s in spans}
    fills = [s for s in spans if s["name"] == "sweep.fill"]
    ahead = [s for s in fills if s["args"].get("ahead")]
    assert ahead and sum(s["args"]["programs"] for s in ahead) == 24 - 8
    assert {by_op[s["parent"]]["name"] for s in ahead} == {"sweep.round"}
    assert sum(s["args"]["programs"] for s in fills if s not in ahead) == 24
    totals = obs.stage_totals()
    rounds = totals["sweep.round"]["count"]
    assert totals["sweep.block"]["count"] == 2 * rounds
    # nothing ran under a block but, at most, a collector pass
    blocks = {s["op_b"] for s in spans if s["name"] == "sweep.block"}
    assert {s["name"] for s in spans if s["parent"] in blocks} <= {"gc.pause"}
    # every program made, ahead or not, is in the two slices
    assert totals["sweep.fuzz"]["seconds"] >= 24 * 0.002
    assert totals["sweep.fuzz"]["count"] == len(fills) == totals["sweep.lower"]["count"]
    counts = obs.stage_counts()
    assert counts["sweep.programs"] == 24 and counts["sweep.prefetched"] == 16
    _tree_closes("sweep.job")
    # the driver's own split: what was made in the gap is harvest time
    # (the prime fill is before either clock starts)
    assert drv.last_harvest_seconds >= 16 * 0.002
    assert drv.last_segment_seconds == pytest.approx(
        totals["sweep.block"]["seconds"], abs=5e-3
    )


@pytest.mark.parametrize("device", ["as_it_is", "busy"])
def test_consumer_time_is_not_the_drivers(clean, sweeper, device, request):
    if device == "busy":
        request.getfixturevalue("busy_device")
    drv = sweeper._continuous_driver(8)
    obs.enable()
    t0 = time.perf_counter()
    yields = 0
    for _batch in drv._run_batches(24):
        assert obs_spans.current_depth() == 0
        time.sleep(0.05)
        yields += 1
    wall = time.perf_counter() - t0
    obs.disable()
    totals = obs.stage_totals()
    driver_s = totals["sweep.round"]["seconds"] + totals["sweep.prime"]["seconds"]
    assert yields >= 2
    assert driver_s <= wall - 0.045 * yields


# -- (g) job and parent ------------------------------------------------------

def test_every_span_carries_its_job_and_reaches_the_root(clean, reversal):
    obs.enable()
    _explore(reversal, rounds=2)
    _explore(reversal, rounds=2)
    obs.disable()
    # (a search's set-up stages, its constructor's, are not of its tree)
    spans = [
        s for s in obs.TRACER.spans if not s["name"].startswith(SETUP_ROWS)
    ]
    by_op = {s["op_b"]: s for s in spans}
    roots = [s for s in spans if s["name"] == "dpor.search"]
    assert len(roots) == 2 and roots[0]["job"] != roots[1]["job"]
    assert all(r["parent"] is None for r in roots)
    for s in spans:
        top = s
        while top["parent"] is not None:
            top = by_op[top["parent"]]
        assert top["name"] == "dpor.search" and s["job"] == top["job"]
    # the exports carry both as args, and still pair every B with its E
    events = obs.TRACER.to_trace_events()
    depth = 0
    for e in events:
        depth += 1 if e["ph"] == "B" else -1
        assert depth >= 0
    assert depth == 0
    begins = [
        e for e in events
        if e["ph"] == "B" and not e["name"].startswith(SETUP_ROWS)
    ]
    assert {e["args"]["job"] for e in begins} == {r["job"] for r in roots}
    assert all(
        ("parent" in e["args"]) == (e["name"] != "dpor.search") for e in begins
    )
    line = json.dumps(begins[0]["args"])   # stays JSON
    assert "job" in line


# -- (h) what the benchmark's harness wraps ----------------------------------

def _bench_spans():
    spec = importlib.util.spec_from_file_location(
        "bench_lib_spans", os.path.join(ROOT, "benchmarks", "lib", "spans.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_harness_attributes_exist_and_wrap_round_trips(
    clean, reversal, sweeper
):
    bench = _bench_spans()
    d = _dpor(reversal)
    for attr in ("_dispatch_round", "_supervised_harvest", "_process_round"):
        assert callable(getattr(d, attr))
        bench.wrap(d, attr, "bench.test." + attr)
    d.explore(target_code=2, max_rounds=2)
    assert d.round_index == 2
    for attr in ("host_seconds", "device_seconds", "_continuous_driver"):
        assert hasattr(sweeper, attr)
    drv = sweeper._continuous_driver(8)
    assert drv is sweeper._continuous_driver(8)
    for attr in ("last_total_lane_steps", "seg_steps"):
        assert isinstance(getattr(drv, attr), int)
    kernels = {a: getattr(drv, a) for a in ("segment", "refill", "init", "finalize")}
    plain = _sweep(sweeper).lanes_digest
    bench.wrap_generator(drv, "_run_batches", "bench.test.harvest_round")
    for attr in kernels:
        bench.wrap(drv, attr, "bench.test." + attr)
    assert _sweep(sweeper).lanes_digest == plain
    bench.unwrap(drv, "_run_batches", *kernels)
    assert "_run_batches" not in drv.__dict__
    assert all(getattr(drv, a) is k for a, k in kernels.items())
    assert _sweep(sweeper).lanes_digest == plain
