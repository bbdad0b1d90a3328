"""The set-up ledger (obs/spans.py: ``stage``, ``first_job``, the compile
listeners): stages that record with no switch and leave the spans'
switches alone, the bounded timeline, the compile table fed synthetic
``jax.monitoring`` events and a real ``jax.jit``, the cut at the end of
job 1, and what ``--stats-out`` carries of it."""

import builtins
import gc
import json
import time

import pytest

from demi_tpu import obs
from demi_tpu.obs import spans
from demi_tpu.obs.profiler import PROFILER

from test_stage_spans import _explore, _sweep, busy_device, reversal, sweeper  # noqa: F401

TRACE, LOWER, BACKEND = spans._TRACE_EVENT, spans._LOWER_EVENT, spans._BACKEND_EVENT


@pytest.fixture
def fresh():
    """Spans off, every table empty and job numbers from 1 again,
    before and after."""

    def wipe():
        obs.disable()
        PROFILER.disable()
        obs.TRACER.clear()
        spans._reset_setup()

    wipe()
    yield
    wipe()


def _compile(fun, trace=0.0, lower=0.0, backend=0.0, hit=False):
    """The events one jit call of ``fun`` sends, in JAX's order."""
    if trace:
        spans._on_duration(TRACE, trace, fun_name=fun)
    if lower:
        spans._on_duration(LOWER, lower, fun_name=f"jit({fun})")
    if backend:
        if hit:
            spans._on_event(spans._CACHE_HIT_EVENT)
            spans._on_duration(spans._SAVED_EVENT, 2.0)
            spans._on_duration(spans._RETRIEVAL_EVENT, backend / 2)
        else:
            spans._on_event(spans._CACHE_MISS_EVENT)
        spans._on_duration(BACKEND, backend, fun_name=f"jit({fun})")


# -- stages ------------------------------------------------------------------

def test_a_stage_records_with_telemetry_off_and_turns_nothing_on(fresh):
    assert not obs.enabled() and not spans.live()
    with obs.stage("setup.build", what="t") as st:
        assert not spans.live() and spans.current_depth() == 0
        with obs.span("t.dead"):      # a span under a stage stays dead
            gc.collect()              # and the collector's pass unrecorded
        time.sleep(0.002)
    assert st.seconds >= 0.002
    totals = obs.stage_totals()
    assert totals["setup.build"]["count"] == 1
    assert totals["setup.build"]["seconds"] == pytest.approx(st.seconds)
    assert "gc.pause" not in totals and "t.dead" not in totals
    assert obs.TRACER.spans == []     # nothing handed over while off
    (entry,) = obs.setup_ledger()["timeline"]
    assert entry["name"] == "setup.build" and entry["args"] == {"what": "t"}
    assert entry["seconds"] == pytest.approx(st.seconds)


def test_a_child_stage_takes_its_time_out_of_its_parent(fresh):
    with obs.stage("setup.import", module="outer"):
        time.sleep(0.002)
        with obs.stage("setup.native_build", stem="x", compiled=False):
            time.sleep(0.004)
        with obs.stage("setup.import", module="inner"):
            time.sleep(0.002)
    t = obs.stage_totals()
    imp, nat = t["setup.import"], t["setup.native_build"]
    assert imp["count"] == 2 and nat["count"] == 1
    assert nat["self_seconds"] == pytest.approx(nat["seconds"])
    # both imports' durations, less the inner one counted inside the
    # outer and the native build: what the two imports ran themselves
    outer = max(e["seconds"] for e in obs.setup_ledger()["timeline"])
    assert imp["self_seconds"] == pytest.approx(outer - nat["seconds"], abs=1e-6)
    assert imp["self_seconds"] < imp["seconds"] - 0.004


def test_the_timeline_is_bounded_and_the_totals_keep_folding(fresh, monkeypatch):
    monkeypatch.setattr(spans, "_TIMELINE_MAX", 5)
    for i in range(12):
        with obs.stage("setup.build", what=str(i)):
            pass
    led = obs.setup_ledger()
    assert [e["args"]["what"] for e in led["timeline"]] == list("01234")
    assert led["timeline_dropped"] == 7
    assert obs.stage_totals()["setup.build"]["count"] == 12


def test_a_stage_that_raises_says_so_and_leaves_the_stack_clean(fresh):
    with pytest.raises(KeyError):
        with obs.stage("setup.build", what="outer"):
            with obs.stage("setup.build", what="inner"):
                raise KeyError("x")
    assert getattr(spans._local, "stages") == []
    errors = [e["args"].get("error") for e in obs.setup_ledger()["timeline"]]
    assert errors == ["KeyError", "KeyError"]


def test_staged_wraps_a_call_in_a_stage(fresh):
    @spans.staged("setup.build", what="make_thing")
    def make_thing(a, b=2):
        """doc"""
        return a + b

    assert make_thing(1, b=3) == 4
    assert make_thing.__name__ == "make_thing" and make_thing.__doc__ == "doc"
    (entry,) = obs.setup_ledger()["timeline"]
    assert entry["args"] == {"what": "make_thing"}


def test_a_staged_arg_that_is_callable_reads_the_calls_arguments(fresh):
    @spans.staged("setup.build", what="make_thing", size=lambda a, b=2: a * b)
    def make_thing(a, b=2):
        return a + b

    assert make_thing(3) == 5 and make_thing(3, b=4) == 7
    said = [e["args"] for e in obs.setup_ledger()["timeline"]]
    assert said == [
        {"what": "make_thing", "size": 6}, {"what": "make_thing", "size": 12},
    ]


def test_stages_are_handed_to_the_tracer_once_spans_are_live(fresh):
    with obs.stage("setup.import", module="early"):
        pass
    assert obs.TRACER.spans == []
    obs.enable()
    with obs.stage("setup.build", what="late"):
        pass
    names = [(s["name"], s["args"]) for s in obs.TRACER.spans]
    assert names == [
        ("setup.import", {"module": "early"}),
        ("setup.build", {"what": "late"}),
    ]
    assert all(s["ts"] >= 0 and s["dur"] >= 0 for s in obs.TRACER.spans)
    with obs.stage("setup.build", what="again"):
        pass
    assert len(obs.TRACER.spans) == 3     # each stage once
    events = obs.TRACER.to_trace_events()
    assert [e["ph"] for e in events].count("B") == 3


def test_the_process_start_anchor(fresh, monkeypatch):
    with obs.stage("setup.import", module="m"):
        pass
    led = obs.setup_ledger()
    # Linux: the process started before this module's epoch, and the
    # first stage began after both.
    assert led["process_start_s"] < 0
    assert led["pre_program_s"] > -led["process_start_s"]
    assert led["timeline"][0]["start_s"] == pytest.approx(led["pre_program_s"])

    def no_proc(path, *a, **kw):
        if str(path).startswith("/proc/"):
            raise FileNotFoundError(path)
        return real_open(path, *a, **kw)

    real_open = builtins.open
    monkeypatch.setattr(builtins, "open", no_proc)
    assert spans._process_start_ns.__wrapped__() is None


# -- the compile ledger -------------------------------------------------------

def _listeners():
    """``jax.monitoring``'s duration and event listeners, as they stand."""
    from jax._src import monitoring

    return (list(monitoring.get_event_duration_listeners()),
            list(monitoring.get_event_listeners()))


def test_a_cache_hit_is_told_from_a_miss_and_rows_go_by_function(fresh):
    _compile("seg_lane", trace=0.5, lower=0.25, backend=3.0)
    _compile("seg_lane", trace=0.5, lower=0.25, backend=0.4, hit=True)
    _compile("fin", trace=0.1, lower=0.1, backend=0.2, hit=True)
    led = obs.compile_ledger()
    assert set(led["functions"]) == {"seg_lane", "fin"}   # jit(f) is f's row
    seg = led["functions"]["seg_lane"]
    assert (seg["traces"], seg["lowerings"]) == (2, 2)
    assert (seg["compiles"], seg["compile_s"]) == (1, 3.0)
    assert (seg["cache_hits"], seg["cache_load_s"]) == (1, 0.4)
    assert (seg["retrieval_s"], seg["saved_s"]) == (0.2, 2.0)
    total = led["total"]
    assert (total["compiles"], total["cache_hits"], total["cache_misses"]) == (1, 2, 1)
    assert total["trace_s"] == pytest.approx(1.1)
    t = obs.stage_totals()
    assert t["compile.backend"]["count"] == 1
    assert t["compile.cache_load"]["count"] == 2
    assert t["compile.trace"]["count"] == 3 and t["compile.lower"]["count"] == 3


def test_nested_tracings_are_covered_not_summed(fresh):
    t0 = time.perf_counter()
    time.sleep(0.03)
    # the inner function's tracing ends first and lies inside the outer's
    spans._on_duration(TRACE, 0.01, fun_name="inner")
    time.sleep(0.01)
    spans._on_duration(TRACE, 0.01, fun_name="inner")
    outer = time.perf_counter() - t0
    spans._on_duration(TRACE, outer, fun_name="outer")
    wall = time.perf_counter() - t0
    led = obs.compile_ledger()
    assert led["functions"]["outer"]["trace_s"] == pytest.approx(outer)
    assert led["total"]["trace_s"] == pytest.approx(outer + 0.02)   # each its own
    covered = obs.stage_totals()["compile.trace"]["seconds"]
    assert covered == pytest.approx(led["total"]["covered_s"])
    assert covered == pytest.approx(outer, abs=2e-3) and covered <= wall
    # a later, disjoint event adds its whole self
    time.sleep(0.005)
    spans._on_duration(LOWER, 0.004, fun_name="jit(outer)")
    assert obs.stage_totals()["compile.lower"]["seconds"] == pytest.approx(0.004, abs=1e-6)


def test_compile_events_land_in_the_open_stage(fresh):
    with obs.stage("setup.build", what="t"):
        time.sleep(0.02)
        spans._on_duration(TRACE, 0.015, fun_name="f")
    t = obs.stage_totals()
    assert t["compile.trace"]["seconds"] == pytest.approx(0.015, abs=1e-6)
    assert t["setup.build"]["self_seconds"] == pytest.approx(
        t["setup.build"]["seconds"] - 0.015, abs=1e-6
    )


def test_events_after_job_one_are_late_and_the_ledger_is_cut_there(fresh):
    with obs.stage("setup.build", what="before"):
        _compile("early", trace=0.001)
    assert obs.setup_ledger()["first_job"] is None
    with spans.first_job(obs.new_job(), "sweep"):
        assert obs.setup_ledger()["first_job"]["end_s"] is None
        _compile("seg_lane", trace=0.002, lower=0.001, backend=0.003)
    cut = obs.setup_ledger()
    assert cut["first_job"]["verb"] == "sweep"
    assert cut["first_job"]["end_s"] > cut["first_job"]["start_s"]
    assert obs.compile_ledger()["total"]["late"] == 0
    # job 2 has no stage, and what follows job 1 changes nothing of the cut
    assert spans.first_job(obs.new_job(), "sweep") is spans._NO_STAGE
    with obs.stage("setup.build", what="after"):
        _compile("seg_lane", trace=0.002, lower=0.001, backend=0.003)
    after = obs.setup_ledger()
    for key in ("stages", "before_first_job", "compile", "first_job"):
        assert after[key] == cut[key], key
    assert set(cut["before_first_job"]) == {"setup.build", "compile.trace"}
    assert cut["stages"]["setup.first_job"]["count"] == 1
    assert cut["stages"]["setup.build"]["count"] == 1
    led = obs.compile_ledger()
    assert led["functions"]["seg_lane"]["late"] == 3
    assert led["functions"]["early"]["late"] == 0
    assert led["total"]["late"] == 3
    assert obs.stage_totals()["setup.build"]["count"] == 2   # still folding


def test_a_real_jit_call_shows_up_under_its_functions_name(fresh):
    import jax
    import jax.numpy as jnp

    import demi_tpu.device  # noqa: F401  (registers the listener pair)

    def ledger_probe_fn(x):
        return x * 3 + 1

    before = _listeners()
    spans.listen_to_compiles(jax.monitoring)     # a second call adds none
    assert _listeners() == before
    assert before[0].count(spans._on_duration) == 1
    assert before[1].count(spans._on_event) == 1
    with obs.stage("setup.build", what="probe") as st:
        jax.jit(ledger_probe_fn)(jnp.arange(4)).block_until_ready()
    row = obs.compile_ledger()["functions"]["ledger_probe_fn"]
    assert row["traces"] == row["lowerings"] == 1
    assert row["compiles"] + row["cache_hits"] == 1
    assert row["trace_s"] > 0 and row["lower_s"] > 0
    t = obs.stage_totals()
    took = sum(t[k]["seconds"] for k in t if k.startswith("compile."))
    assert 0 < took <= st.seconds
    assert t["setup.build"]["self_seconds"] == pytest.approx(
        st.seconds - took, abs=1e-6
    )


def test_the_smoke_hangs_no_listener_of_its_own(fresh):
    import jax

    import chip_smoke

    import demi_tpu.device  # noqa: F401

    before = _listeners()
    smoke = chip_smoke.Smoke(chip_smoke.SIZES["tiny"], {"platform": "cpu"})
    with smoke.phase("p") as record:
        time.sleep(0.01)
        _compile("f", trace=0.004, lower=0.002, backend=0.003, hit=True)
    assert _listeners() == before
    assert not hasattr(smoke, "close")
    assert record["cache_hits"] == 1 and record["cache_misses"] == 0
    assert record["backend_compile_s"] == 0.003
    assert record["run_s"] >= 0


# -- what it must not change --------------------------------------------------

def _counts(prefixes):
    return {
        name: row["count"] for name, row in obs.stage_totals().items()
        if name.startswith(prefixes)
    }


@pytest.mark.parametrize("driver", ["sweep", "dpor"])
def test_a_traced_jobs_own_rows_are_the_same_under_a_set_up_stage(
    fresh, reversal, sweeper, busy_device, driver  # noqa: F811
):
    """The run's spans and the collector's pauses under them count the
    same whether or not a set-up stage is open around the job; the
    collector is driven by hand so that its passes are the same."""
    root = "sweep.job" if driver == "sweep" else "dpor.search"

    def job():
        gc.collect()
        gc.disable()
        try:
            obs.enable()
            if driver == "sweep":
                _sweep(sweeper)
            else:
                _explore(reversal, rounds=3)
            with obs.span(root):
                gc.collect()
            obs.disable()
        finally:
            gc.enable()
        return _counts(("sweep.", "dpor.", "gc.pause"))

    plain = job()
    obs.TRACER.clear()
    with obs.stage("setup.build", what="around"):
        gc.collect()      # spans off: not a gc.pause
        staged = job()
    assert staged == plain
    assert plain["gc.pause"] == 1 and plain[root] == 2


def test_first_job_brackets_job_one_of_either_driver(fresh, reversal, sweeper):  # noqa: F811
    _sweep(sweeper)
    led = obs.setup_ledger()
    assert led["first_job"]["verb"] == "sweep"
    assert led["stages"]["setup.first_job"]["count"] == 1
    _explore(reversal)                      # job 2
    assert obs.stage_totals()["setup.first_job"]["count"] == 1
    spans._reset_setup()
    obs.TRACER.clear()
    _explore(reversal)
    assert obs.setup_ledger()["first_job"]["verb"] == "dpor"
    # the search's constructor ran before job 1 began
    assert obs.setup_ledger()["before_first_job"]["setup.build"]["count"] == 1


def test_stats_out_of_a_tiny_sweep_holds_the_stages_and_the_table(fresh, tmp_path):
    from demi_tpu.cli import main

    out = tmp_path / "stats.json"
    rc = main([
        "sweep", "--app", "broadcast", "--nodes", "3", "--batch", "16",
        "--chunk", "8", "--stats-out", str(out),
    ])
    obs.disable()
    assert rc in (0, 1)
    doc = json.loads(out.read_text())
    assert {"counters", "gauges", "histograms"} <= set(doc)   # as before
    setup, table = doc["setup"], doc["compile"]
    assert setup["first_job"]["verb"] == "sweep"
    assert setup["first_job"]["end_s"] > 0
    assert {"setup.build", "setup.first_job", "compile.trace",
            "compile.lower"} <= set(setup["stages"])
    assert {"SweepDriver", "ContinuousSweepDriver"} <= {
        e["args"].get("what") for e in setup["timeline"]
    }
    assert "seg_lane" in table["functions"]
    assert table["functions"]["seg_lane"]["traces"] >= 1
    # the self-check: what no stage names is what is left of the age
    named = sum(
        r["self_seconds"] for r in setup["before_first_job"].values()
    ) + setup["stages"]["setup.first_job"]["seconds"] + setup["pre_program_s"]
    assert named <= setup["first_job"]["end_s"] + 1e-6
