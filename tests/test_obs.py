"""Observability layer (demi_tpu/obs): registry semantics, snapshot
merge, span nesting, Perfetto export validity, and device LaneStats
agreement with host-side sweep accounting."""

import gc
import json
import os

import numpy as np
import pytest

from demi_tpu import obs
from demi_tpu.obs import spans as obs_spans


@pytest.fixture
def telemetry():
    """Clean, enabled telemetry for one test; always restored to off.
    The collector is held off meanwhile: a pass that starts inside an
    open span is a ``gc.pause`` span of its own (tests/test_stage_spans.py
    pins that), and the tests here count spans exactly."""
    obs.REGISTRY.reset()
    obs.TRACER.clear()
    gc.disable()
    obs.enable()
    yield
    obs.disable()
    gc.enable()
    obs.REGISTRY.reset()
    obs.TRACER.clear()


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_semantics(telemetry):
    c = obs.counter("t.count")
    c.inc()
    c.inc(4)
    c.inc(2, app="raft")
    assert c.value() == 5
    assert c.value(app="raft") == 2
    assert c.total() == 7

    g = obs.gauge("t.gauge")
    g.set(0.25)
    g.set(0.75)  # last write wins
    g.set(3, phase="b")
    assert g.value() == 0.75
    assert g.value(phase="b") == 3.0

    h = obs.histogram("t.hist")
    for v in (0.001, 0.002, 1.5):
        h.observe(v)
    assert h.count() == 3
    assert h.sum() == pytest.approx(1.503)
    snap = obs.REGISTRY.snapshot()
    rec = snap["histograms"]["t.hist"][""]
    assert sum(rec["buckets"]) == 3
    assert rec["min"] == pytest.approx(0.001)
    assert rec["max"] == pytest.approx(1.5)


def test_metric_kind_conflict_raises(telemetry):
    obs.counter("t.kind")
    with pytest.raises(TypeError, match="already registered"):
        obs.gauge("t.kind")


def test_disabled_is_a_noop():
    obs.REGISTRY.reset()
    obs.TRACER.clear()
    obs.disable()
    obs.counter("t.off").inc(100)
    obs.gauge("t.off.g").set(1)
    obs.histogram("t.off.h").observe(1)
    with obs.span("t.off.span"):
        pass
    assert obs.counter("t.off").total() == 0
    assert obs.histogram("t.off.h").count() == 0
    assert obs.TRACER.spans == []
    obs.REGISTRY.reset()


def test_snapshot_merge_round_trip(telemetry):
    obs.counter("m.c").inc(3, k="a")
    obs.gauge("m.g").set(0.5)
    obs.histogram("m.h").observe(2.0)
    snap = json.loads(json.dumps(obs.REGISTRY.snapshot()))  # JSON round trip

    merged = obs.merge_snapshots(snap, snap)
    assert merged["counters"]["m.c"]["k=a"] == 6
    assert merged["gauges"]["m.g"][""] == 0.5
    assert merged["histograms"]["m.h"][""]["count"] == 2
    assert merged["histograms"]["m.h"][""]["sum"] == pytest.approx(4.0)
    assert merged["histograms"]["m.h"][""]["max"] == pytest.approx(2.0)

    # Loading into a fresh registry reproduces the totals.
    reg = obs.MetricsRegistry()
    reg.load(merged)
    assert reg.snapshot() == merged


# ---------------------------------------------------------------------------
# Spans + Perfetto export
# ---------------------------------------------------------------------------

def _check_trace_events(events):
    """B/E pairs must nest like a well-formed bracket sequence per tid,
    and file order must be timestamp-monotonic."""
    last_ts = -1
    stacks = {}
    for e in events:
        assert e["ph"] in ("B", "E")
        assert e["ts"] >= last_ts
        last_ts = e["ts"]
        stack = stacks.setdefault(e["tid"], [])
        if e["ph"] == "B":
            stack.append(e["name"])
        else:
            assert stack, f"E without matching B: {e}"
            assert stack.pop() == e["name"]
    for tid, stack in stacks.items():
        assert stack == [], f"unclosed spans on tid {tid}: {stack}"


def test_span_nesting_and_perfetto_export(telemetry, tmp_path):
    with obs.span("outer", stage="x"):
        assert obs_spans.current_depth() == 1
        with obs.span("inner"):
            assert obs_spans.current_depth() == 2
        with obs.span("inner2"):
            pass
    assert obs_spans.current_depth() == 0
    assert [s["name"] for s in obs.TRACER.spans] == ["inner", "inner2", "outer"]

    out = tmp_path / "t.json"
    obs.TRACER.export_perfetto(str(out))
    doc = json.loads(out.read_text())
    events = doc["traceEvents"]
    assert len(events) == 6
    _check_trace_events(events)
    names = [e["name"] for e in events if e["ph"] == "B"]
    assert names == ["outer", "inner", "inner2"]
    # B events carry the span attributes.
    outer_b = next(e for e in events if e["name"] == "outer" and e["ph"] == "B")
    assert outer_b["args"] == {"stage": "x"}


def test_span_error_annotation_and_jsonl(telemetry, tmp_path):
    with pytest.raises(ValueError):
        with obs.span("boom"):
            raise ValueError("x")
    assert obs.TRACER.spans[-1]["args"]["error"] == "ValueError"
    path = tmp_path / "spans.jsonl"
    obs.TRACER.write_jsonl(str(path))
    lines = [json.loads(line) for line in path.read_text().splitlines()]
    assert lines[-1]["name"] == "boom"


def test_zero_width_spans_still_pair(telemetry):
    # Sub-microsecond spans share begin/end timestamps; the export's
    # operation-order tiebreak must still produce valid bracketing.
    with obs.span("a"):
        for _ in range(5):
            with obs.span("z"):
                pass
    _check_trace_events(obs.TRACER.to_trace_events())


# ---------------------------------------------------------------------------
# Device LaneStats
# ---------------------------------------------------------------------------

def _small_sweep(telemetry_on: bool, mode: str):
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
    driver = SweepDriver(
        app, cfg, lambda s: fuzzer.generate_fuzz_test(seed=s)
    )
    return driver.sweep(16, 8, mode=mode)


def test_lane_stats_agree_with_sweep_results(telemetry):
    result = _small_sweep(True, "chunked")
    assert result.lanes == 16

    def total(name):
        return obs.counter(name).value(driver="sweep")

    assert total("device.lane.lanes") == result.lanes
    assert total("device.lane.violations") == result.violations
    assert total("device.lane.overflow") == result.overflow_lanes
    assert total("device.lane.done") == result.lanes - result.overflow_lanes
    # Per-chunk unique counts upper-bound the cross-chunk dedup.
    assert total("device.lane.unique_schedules") >= result.unique_schedules
    assert total("device.lane.deliveries") > 0
    # interval=1: one check per delivery plus one finalization per lane.
    assert (
        total("device.lane.invariant_checks")
        == total("device.lane.deliveries") + total("device.lane.done")
    )
    assert obs.counter("device.kernel.lanes").value(kernel="explore") == 16


def test_lane_stats_continuous_driver(telemetry):
    result = _small_sweep(True, "continuous")

    def total(name):
        return obs.counter(name).value(driver="continuous")

    assert total("device.lane.lanes") == result.lanes == 16
    assert total("device.lane.violations") == result.violations
    assert total("device.lane.overflow") == result.overflow_lanes
    assert obs.counter("device.continuous.rounds").total() > 0
    occ = obs.gauge("device.continuous.occupancy").value()
    assert occ is not None and 0 < occ <= 1


def test_reduce_lanes_masks_pad_lanes(telemetry):
    from demi_tpu.device.core import ST_DONE, ST_OVERFLOW, ST_VIOLATION
    from demi_tpu.obs import lane_stats as ls

    status = np.asarray(
        [ST_DONE, ST_VIOLATION, ST_OVERFLOW, ST_DONE], np.int32
    )
    violation = np.asarray([0, 7, 0, 0], np.int32)
    deliveries = np.asarray([10, 5, 3, 99], np.int32)
    stats = ls.reduce_lanes(
        status, violation, deliveries, 3, invariant_interval=2
    ).to_host()
    assert stats == {
        "lanes": 3,
        "done": 2,
        "violations": 1,
        "overflow": 1,
        "deliveries": 18,
        # 10//2 + 5//2 + 3//2 interval checks + 2 finalizations
        "invariant_checks": 5 + 2 + 1 + 2,
    }


def test_sweep_records_nothing_when_disabled():
    obs.REGISTRY.reset()
    obs.disable()
    _small_sweep(False, "chunked")
    snap = obs.REGISTRY.snapshot()
    assert snap["counters"] == {}


# ---------------------------------------------------------------------------
# CLI surface
# ---------------------------------------------------------------------------

def test_cli_fuzz_trace_out_and_stats(tmp_path, capsys):
    from demi_tpu.cli import main

    obs.REGISTRY.reset()
    obs.TRACER.clear()
    exp = tmp_path / "exp"
    exp.mkdir()
    trace_path = tmp_path / "t.json"
    try:
        rc = main([
            "fuzz", "--app", "broadcast", "--nodes", "3", "--bug",
            "unreliable", "--max-executions", "50", "--max-messages", "96",
            "-o", str(exp), "--trace-out", str(trace_path),
        ])
    finally:
        obs.disable()
    assert rc == 0

    doc = json.loads(trace_path.read_text())
    events = doc["traceEvents"]
    _check_trace_events(events)
    names = {e["name"] for e in events}
    # The pipeline tiers are all on the timeline: fuzzer, scheduler,
    # device sweep.
    assert "fuzz.execution" in names
    assert "scheduler.execute" in names
    assert "device.sweep.chunk" in names
    assert "fuzz.device_confirm" in names

    # The experiment dir carries the registry snapshot...
    snap = json.loads((exp / "obs_snapshot.json").read_text())
    assert snap["counters"]["device.lane.lanes"]["driver=sweep"] > 0
    # ...including the host-share split of the confirm sweep.
    assert "sweep.host_share" in snap["gauges"]
    assert 0.0 <= snap["gauges"]["sweep.host_share"][""] <= 1.0

    # ...which `demi_tpu stats -e` prints...
    capsys.readouterr()  # drain the fuzz command's output
    rc = main(["stats", "-e", str(exp)])
    assert rc == 0
    printed = json.loads(capsys.readouterr().out)
    assert printed["counters"]["fuzz.programs_generated"][""] >= 1
    assert "device.lane.lanes" in printed["counters"]
    assert "sweep.host_share" in printed["gauges"]

    # ...and `demi_tpu report` renders as a Telemetry section, host
    # share included in the Pipeline block.
    from demi_tpu.tools.report import render_report

    text = render_report(str(exp))
    assert "## Telemetry" in text
    assert "device.lane.lanes" in text
    assert "sweep host share" in text


def test_cli_stats_merges_inputs(tmp_path, capsys):
    from demi_tpu.cli import main

    snap = {"counters": {"x": {"": 2}}, "gauges": {}, "histograms": {}}
    a = tmp_path / "a.json"
    a.write_text(json.dumps(snap))
    rc = main(["stats", "-i", str(a), "-i", str(a)])
    assert rc == 0
    merged = json.loads(capsys.readouterr().out)
    assert merged["counters"]["x"][""] == 4


def test_analysis_counters_and_report_section(telemetry, tmp_path):
    """analysis.* counters: static pruning and the sanitizer both report
    into the registry, and report.py renders them as a 'Static analysis'
    section above the raw counter tables."""
    import time as _time

    from demi_tpu.analysis import StaticIndependence, sanitize
    from demi_tpu.native.analysis import racing_prescriptions_batch
    from demi_tpu.runtime.actor import Actor
    from demi_tpu.runtime.system import ControlledActorSystem

    # Device-tier static pruning on a hand-built fungible race: two
    # identical timer records at one receiver, concurrent and immediate.
    w = 8
    recs = np.zeros((1, 4, w), np.int32)
    recs[0, 0] = [2, 1, 1, 5, 0, -1, -1, -1]
    recs[0, 1] = [2, 1, 1, 5, 0, -1, -1, 0]
    lens = np.asarray([2], np.int32)
    rel = StaticIndependence(app_effects=None, fungible=True)
    rows, offsets, lanes, digests = racing_prescriptions_batch(
        recs, lens, w, independence=rel
    )
    assert rel.pruned_total["fungible"] == 1
    assert len(lanes) == 0

    # Runtime sanitizer counters.
    class Clocky(Actor):
        def receive(self, ctx, snd, msg):
            _time.time()

    sanitize.enable(strict=False)
    sanitize.reset_stats()
    try:
        sys_ = ControlledActorSystem()
        sys_.spawn("a", Clocky)
        sys_.deliver(sys_.inject("a", ("tick",)))
    finally:
        sanitize.reset()
        sanitize.reset_stats()

    snap = obs.REGISTRY.snapshot()
    assert snap["counters"]["analysis.static_pruned"][
        "kind=fungible,tier=device"
    ] == 1
    assert snap["counters"]["analysis.sanitizer_time_reads"][
        "fn=time.time"
    ] == 1

    # The report renders a Static analysis block from the snapshot.
    from demi_tpu.tools.report import render_report

    exp = tmp_path / "exp"
    exp.mkdir()
    (exp / "obs_snapshot.json").write_text(json.dumps(snap))
    text = render_report(str(exp))
    assert "### Static analysis" in text
    assert "static-pruned racing pairs: 1" in text
    assert "sanitizer wall-clock reads: 1" in text


def test_sleep_counters_and_report_section(telemetry, tmp_path):
    """analysis.sleep_pruned counters + the dpor.redundancy_ratio gauge
    render in the Static-analysis block — including for a dpor-only
    snapshot with NO pipe.* series and no other analysis counters (the
    PR 5 guard mirrored), so `demi_tpu dpor --stats-out` reports never
    drop the pruning ledger."""
    from demi_tpu.tools.report import render_report

    obs.counter("analysis.sleep_pruned").inc(3, kind="sleep", tier="device")
    obs.counter("analysis.sleep_pruned").inc(2, kind="class", tier="device")
    obs.gauge("dpor.redundancy_ratio").set(1.05)
    snap = obs.REGISTRY.snapshot()
    assert "pipe.overlap_seconds" not in snap["counters"]  # dpor-only
    exp = tmp_path / "exp"
    exp.mkdir()
    (exp / "obs_snapshot.json").write_text(json.dumps(snap))
    text = render_report(str(exp))
    assert "### Static analysis" in text
    assert "sleep-pruned reversals: 5" in text
    assert "redundancy ratio" in text and "1.05" in text

    # Ratio-only snapshot (sleep on, nothing pruned): the block still
    # renders from the gauge alone.
    exp2 = tmp_path / "exp2"
    exp2.mkdir()
    (exp2 / "obs_snapshot.json").write_text(json.dumps({
        "gauges": {"dpor.redundancy_ratio": {"": 1.0}},
        "counters": {}, "histograms": {},
    }))
    text2 = render_report(str(exp2))
    assert "### Static analysis" in text2
    assert "redundancy ratio" in text2


# ---------------------------------------------------------------------------
# Span end events under exceptions (finally discipline)
# ---------------------------------------------------------------------------

def test_span_end_events_survive_abandoned_inner_span(telemetry, tmp_path):
    """A stage that raises past a manually-entered inner span must not
    trade the real exception for an AssertionError, and the exported
    Perfetto trace must still be valid bracketing — the outer span's end
    event is emitted from a finally, and the abandoned inner span is
    closed as 'orphaned'."""
    with pytest.raises(ValueError, match="stage blew up"):
        with obs.span("outer.stage"):
            inner = obs.span("inner.handler")
            inner.__enter__()  # a handler that never reaches its exit
            raise ValueError("stage blew up")
    names = {s["name"] for s in obs.TRACER.spans}
    assert names == {"outer.stage", "inner.handler"}
    by_name = {s["name"]: s for s in obs.TRACER.spans}
    assert by_name["inner.handler"]["args"]["error"] == "orphaned"
    assert by_name["outer.stage"]["args"]["error"] == "ValueError"
    # Stack fully repaired: nothing leaks into the next span.
    assert obs_spans.current_depth() == 0
    out = tmp_path / "t.json"
    obs.TRACER.export_perfetto(str(out))
    _check_trace_events(json.loads(out.read_text())["traceEvents"])


# ---------------------------------------------------------------------------
# Cross-process merge audit: associative + commutative (fleet prereq)
# ---------------------------------------------------------------------------

def _random_snapshot(seed: int):
    """One simulated per-process registry snapshot with counters,
    stamped gauges, and histograms."""
    rng = np.random.RandomState(seed)
    reg = obs.MetricsRegistry()
    c = reg.counter("p.count")
    g = reg.gauge("p.gauge")
    h = reg.histogram("p.hist")
    for _ in range(rng.randint(1, 6)):
        c.series["k=a"] = c.series.get("k=a", 0) + int(rng.randint(1, 9))
        c.series[""] = c.series.get("", 0) + 1
    g.force_set(float(rng.rand()), node=int(rng.randint(2)))
    g.force_set(float(rng.rand()))
    for _ in range(rng.randint(1, 8)):
        v = float(2.0 ** rng.uniform(-19, 6))
        key = ""
        s = h._series(key)
        b = 0
        from demi_tpu.obs.metrics import _BUCKETS
        while b < len(_BUCKETS) and v > _BUCKETS[b]:
            b += 1
        s[0][b] += 1
        s[1] += 1
        s[2] += v
        s[3] = min(s[3], v)
        s[4] = max(s[4], v)
    return json.loads(json.dumps(reg.snapshot()))


def _snap_eq(a, b):
    """Snapshot equality with float tolerance on the SUM accumulators
    (float addition is not bit-associative; counts, buckets, gauges,
    stamps, and min/max must match exactly)."""
    import copy

    a, b = copy.deepcopy(a), copy.deepcopy(b)
    for snap in (a, b):
        for series in snap.get("histograms", {}).values():
            for rec in series.values():
                rec["sum"] = round(rec["sum"], 6)
    return a == b


def test_merge_is_associative_and_commutative(telemetry):
    """Property test over counters, gauges, and log2 histogram buckets:
    merging per-process snapshots must give ONE answer for any merge
    order or grouping — the prerequisite for fleet aggregation, where
    workers' snapshots arrive in nondeterministic order. (Histogram
    SUM accumulators compare with float tolerance; every discrete
    series — counts, buckets, gauges + stamps, min/max — exactly.)"""
    for seed in range(10):
        a = _random_snapshot(3 * seed)
        b = _random_snapshot(3 * seed + 1)
        c = _random_snapshot(3 * seed + 2)
        # Commutative.
        assert _snap_eq(
            obs.merge_snapshots(a, b), obs.merge_snapshots(b, a)
        )
        # Associative (grouping-independent).
        ab_c = obs.merge_snapshots(obs.merge_snapshots(a, b), c)
        a_bc = obs.merge_snapshots(a, obs.merge_snapshots(b, c))
        abc = obs.merge_snapshots(a, b, c)
        assert _snap_eq(ab_c, a_bc) and _snap_eq(a_bc, abc)
        # And every permutation lands on the same result.
        assert _snap_eq(obs.merge_snapshots(c, a, b), abc)
        assert _snap_eq(obs.merge_snapshots(b, c, a), abc)


def test_histogram_bucket_alignment_drift_rebins_by_value(telemetry):
    """A snapshot written with DIFFERENT bucket boundaries (an older or
    newer build) must merge by VALUE, not by index: every count lands in
    the local bucket covering its recorded bound, drift past the local
    range lands in overflow, and the total count is exact."""
    from demi_tpu.obs.metrics import _BUCKETS

    reg = obs.MetricsRegistry()
    # Foreign build: half the buckets, shifted boundaries, plus values
    # beyond the local range.
    foreign_bounds = [0.001, 0.1, 10.0, 1000.0]
    rec = {
        "le": foreign_bounds,
        "buckets": [2, 3, 4, 5, 6],  # last = foreign overflow
        "count": 20,
        "sum": 12.5,
        "min": 0.0005,
        "max": 2000.0,
    }
    reg.load({"histograms": {"d.h": {"": rec}}})
    snap = reg.snapshot()["histograms"]["d.h"][""]
    assert sum(snap["buckets"]) == 20  # nothing lost, nothing doubled
    assert snap["count"] == 20
    # The 1000.0-bound counts and the foreign overflow exceed the local
    # top bound (128s) and both land in overflow.
    assert snap["buckets"][-1] == 11
    # Each kept bound landed at a local bucket covering it.
    import bisect
    for bound, n in zip(foreign_bounds[:-1], rec["buckets"]):
        b = bisect.bisect_left(_BUCKETS, bound)
        assert snap["buckets"][b] >= n
    # Same-bounds fast path stays exact (index-wise).
    reg2 = obs.MetricsRegistry()
    reg2.load(reg.snapshot())
    assert reg2.snapshot()["histograms"]["d.h"][""]["buckets"] == (
        snap["buckets"]
    )


# ---------------------------------------------------------------------------
# Round journal (obs/journal.py)
# ---------------------------------------------------------------------------

def test_journal_write_read_and_torn_tail(tmp_path):
    from demi_tpu.obs import journal

    j = journal.RoundJournal(str(tmp_path))
    j.emit("dpor.round", round=1, wall_s=0.5)
    j.emit("dpor.round", round=2, wall_s=0.4)
    j.emit("sweep.chunk", round=1, lanes=8)
    j.close()
    # SIGKILL mid-write: a torn trailing line is skipped, not fatal.
    with open(j.path, "a") as f:
        f.write('{"seq": 99, "kind": "dpor.rou')
    recs = journal.read_records(str(tmp_path))
    assert [r["kind"] for r in recs] == [
        "dpor.round", "dpor.round", "sweep.chunk"
    ]
    ok, rounds = journal.contiguous_rounds(recs, "dpor.round")
    assert ok and rounds == [1, 2]


def test_journal_pipeline_record_schema(tmp_path):
    """The streaming pipeline's journal wire format is pinned: one
    pipeline.enqueue record per violating lane handed off, one
    pipeline.frame per minimized violation, with the schema keys `top`
    and the fleet coordinator consume; pipeline.frame is a SAMPLED kind
    (round-grained time-series boundary), pipeline.enqueue is not (it
    can arrive many-per-chunk)."""
    from demi_tpu.apps.broadcast import (
        broadcast_send_generator,
        make_broadcast_app,
    )
    from demi_tpu.apps.common import dsl_start_events, make_host_invariant
    from demi_tpu.config import SchedulerConfig
    from demi_tpu.device import DeviceConfig
    from demi_tpu.fuzzing import Fuzzer, FuzzerWeights
    from demi_tpu.obs import journal
    from demi_tpu.pipeline import StreamingPipeline

    assert "pipeline.frame" in journal._SAMPLED_KINDS
    assert "pipeline.enqueue" not in journal._SAMPLED_KINDS

    app = make_broadcast_app(4, reliable=False)
    fz = Fuzzer(
        num_events=8,
        weights=FuzzerWeights(send=0.6, wait_quiescence=0.25, kill=0.15),
        message_gen=broadcast_send_generator(app),
        prefix=dsl_start_events(app), max_kills=1,
    )
    cfg = DeviceConfig.for_app(
        app, pool_capacity=64, max_steps=96, max_external_ops=24
    )
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    journal.attach(str(tmp_path))
    pipe = StreamingPipeline(
        app, cfg, config, lambda s: fz.generate_fuzz_test(seed=s),
        chunk=8, wildcards=False, max_frames=1,
    )
    result = pipe.run(8)
    journal.detach()
    assert result.frames_done >= 1, "fixture found no violation"

    enq = journal.read_records(str(tmp_path), kind="pipeline.enqueue")
    frames = journal.read_records(str(tmp_path), kind="pipeline.frame")
    assert enq and frames
    for key in ("round", "seed", "code", "queue_depth", "minimize"):
        assert key in enq[0], key
    for key in ("round", "seed", "code", "wall_s", "mcs_externals",
                "deliveries", "stages", "queue_depth", "ttf_mcs_s"):
        assert key in frames[0], key
    assert frames[0]["round"] == 1
    assert frames[0]["ttf_mcs_s"] is not None
    # sweep.chunk and minimize.level records share the same journal —
    # the interleaved-tiers wire `demi_tpu top` renders.
    assert journal.read_records(str(tmp_path), kind="sweep.chunk")
    assert journal.read_records(str(tmp_path), kind="minimize.level")


def test_journal_rotation_bounds_disk(tmp_path):
    from demi_tpu.obs import journal

    j = journal.RoundJournal(str(tmp_path), max_bytes=300)
    for i in range(50):
        j.emit("dpor.round", round=i + 1, pad="x" * 40)
    j.close()
    import os as _os
    live = _os.path.getsize(j.path) if _os.path.exists(j.path) else 0
    rotated = (
        _os.path.getsize(j.path + ".1")
        if _os.path.exists(j.path + ".1") else 0
    )
    # Bounded window: at most ~2x the rotation bound stays on disk.
    assert live + rotated < 4 * 300
    # The kept window is the most recent suffix, in order.
    recs = journal.read_records(str(tmp_path), kind="dpor.round")
    rounds = [r["round"] for r in recs]
    assert rounds == sorted(rounds)
    assert rounds[-1] == 50


def test_journal_truncate_from_resumes_contiguously(tmp_path):
    from demi_tpu.obs import journal

    j = journal.attach(str(tmp_path))
    for i in range(5):
        journal.emit("dpor.round", round=i + 1)
    journal.detach()
    # Resume from the round-3 checkpoint: rounds 4..5 were journaled by
    # the dead run but will re-execute — drop them.
    j = journal.attach(str(tmp_path), incarnation=1)
    dropped = j.truncate_from("dpor.round", 3)
    assert dropped == 2
    journal.emit("dpor.round", round=4)
    journal.emit("dpor.round", round=5)
    journal.emit("dpor.round", round=6)
    recs = journal.read_records(str(tmp_path))
    journal.detach()
    ok, rounds = journal.contiguous_rounds(recs, "dpor.round")
    assert ok and rounds == [1, 2, 3, 4, 5, 6]
    assert [r["inc"] for r in recs] == [0, 0, 0, 1, 1, 1]
    # seq stays strictly monotonic across the truncation.
    seqs = [r["seq"] for r in recs]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)


# ---------------------------------------------------------------------------
# Time series + Prometheus exposition (obs/timeseries.py)
# ---------------------------------------------------------------------------

def test_timeseries_ring_delta_export_and_flush(telemetry, tmp_path):
    from demi_tpu.obs import timeseries

    ts = timeseries.TimeSeries(capacity=4)
    obs.counter("r.c").inc(3)
    ts.sample(kind="dpor.round")
    obs.counter("r.c").inc(2)
    ts.sample(kind="dpor.round")
    delta = ts.export_delta()
    assert [row["v"]["r.c"] for row in delta] == [3.0, 5.0]
    assert ts.export_delta() == []  # nothing new since the export
    ts.sample(kind="dpor.round")
    n = ts.flush_jsonl(str(tmp_path))
    assert n == 1
    rows = timeseries.read_jsonl(str(tmp_path))
    assert len(rows) == 1 and rows[0]["v"]["r.c"] == 5.0
    # The ring is bounded: old samples evict, seq keeps counting.
    for _ in range(10):
        ts.sample()
    assert len(ts.rows()) == 4
    assert ts.seq == 13


def test_prom_text_format_pinned(telemetry):
    """The Prometheus exposition format `stats --prom` prints and
    --metrics-port serves: TYPE lines, _total counters, label blocks,
    cumulative le buckets with +Inf, _sum/_count."""
    from demi_tpu.obs.timeseries import prom_text

    obs.counter("dpor.rounds").inc(7, app="raft")
    obs.gauge("dpor.host_share").set(0.25)
    obs.histogram("dpor.round_seconds").observe(0.002)
    obs.histogram("dpor.round_seconds").observe(3.0)
    text = prom_text(obs.REGISTRY.snapshot())
    lines = text.splitlines()
    assert "# TYPE demi_dpor_rounds_total counter" in lines
    assert 'demi_dpor_rounds_total{app="raft"} 7' in lines
    assert "# TYPE demi_dpor_host_share gauge" in lines
    assert "demi_dpor_host_share 0.25" in lines
    assert "# TYPE demi_dpor_round_seconds histogram" in lines
    assert 'demi_dpor_round_seconds_bucket{le="+Inf"} 2' in lines
    assert "demi_dpor_round_seconds_count 2" in lines
    assert any(
        line.startswith("demi_dpor_round_seconds_sum ") for line in lines
    )
    # Cumulative: bucket counts never decrease along the le axis.
    cums = [
        int(line.rsplit(" ", 1)[1])
        for line in lines
        if line.startswith('demi_dpor_round_seconds_bucket{le="')
    ]
    assert cums == sorted(cums) and cums[-1] == 2


def test_metrics_http_endpoint(telemetry):
    import urllib.request

    from demi_tpu.obs import timeseries

    obs.counter("http.c").inc(4)
    server = timeseries.serve(0)
    try:
        port = server.server_address[1]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ).read().decode()
        assert "demi_http_c_total 4" in body
        snap = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics.json", timeout=10
        ).read().decode())
        assert snap["counters"]["http.c"][""] == 4
    finally:
        server.shutdown()


# ---------------------------------------------------------------------------
# Launch profiler (obs/profiler.py)
# ---------------------------------------------------------------------------

def test_launch_profiler_ledger_and_tuningcache_evidence(tmp_path):
    from demi_tpu.obs.profiler import LaunchProfiler
    from demi_tpu.tune import TuningCache

    p = LaunchProfiler()
    p.enable()
    p.dispatch("dpor", 16, 0.02)
    p.dispatch("dpor", 16, 0.04)
    p.block("dpor", 16, 0.5)
    p.trunk("dpor-trunk", 1, 0.1, shape="p=24")
    ev = p.evidence()
    assert ev["profile"] == "launch" and ev["source"] == "measured"
    rows = {(r["kernel"], r["kind"], r["shape"]): r for r in ev["launches"]}
    disp = rows[("dpor", "dispatch", "b=16")]
    assert disp["launches"] == 2 and disp["lanes"] == 32
    assert disp["seconds"] == pytest.approx(0.06)
    assert rows[("dpor", "block", "b=16")]["seconds"] == pytest.approx(0.5)
    assert ("dpor-trunk", "trunk", "p=24") in rows
    # Heaviest-first ordering (the cost model reads the top shapes).
    secs = [r["seconds"] for r in ev["launches"]]
    assert secs == sorted(secs, reverse=True)
    # TuningCache-compatible persistence: get() returns the evidence.
    cache = TuningCache(str(tmp_path / "tune.json"))
    p.persist_evidence(cache, "wk,profile=launch")
    assert TuningCache(str(tmp_path / "tune.json")).get(
        "wk,profile=launch"
    )["profile"] == "launch"
    # Disabled profiler records nothing (one-branch contract).
    p2 = LaunchProfiler()
    p2.enabled = False
    p2.dispatch("x", 8, 1.0)
    assert p2.evidence()["launches"] == []


# ---------------------------------------------------------------------------
# Distributed tracing (obs/distributed.py)
# ---------------------------------------------------------------------------

def test_trace_context_wire_round_trip():
    from demi_tpu.obs import distributed as dtrace

    root = dtrace.TraceContext.root("coordinator")
    child = root.child("worker")
    assert child.trace_id == root.trace_id
    assert child.parent_span == root.span_id
    # Wire form survives a JSON hop (what the lease/submit verbs carry).
    back = dtrace.TraceContext.from_wire(json.loads(json.dumps(child.to_wire())))
    assert back.trace_id == root.trace_id
    assert back.span_id == child.span_id
    assert back.parent_span == root.span_id
    assert back.actor == "worker"
    args = back.span_args()
    assert args["trace_id"] == root.trace_id
    assert args["parent_span"] == child.span_id
    # Absent/garbage wire contexts degrade to None, never raise.
    assert dtrace.TraceContext.from_wire(None) is None
    assert dtrace.TraceContext.from_wire({}) is None


def test_clock_sync_keeps_min_rtt_midpoint():
    from demi_tpu.obs import distributed as dtrace

    sync = dtrace.ClockSync()
    assert sync.offset_us() == 0.0
    # Loose exchange: rtt 4000us, midpoint offset +1000us.
    sync.observe(10_000, 13_000, t_recv_us=14_000)
    assert sync.offset_us() == pytest.approx(1000.0)
    # Tighter exchange wins: rtt 1000us, offset +2500us.
    sync.observe(20_000, 23_000, t_recv_us=21_000)
    assert sync.offset_us() == pytest.approx(2500.0)
    assert sync.rtt_us() == pytest.approx(1000.0)
    # A looser later sample must not override the best estimate.
    sync.observe(30_000, 99_000, t_recv_us=40_000)
    assert sync.offset_us() == pytest.approx(2500.0)
    assert sync.samples == 3
    # Un-stamped replies (an old peer) are ignored.
    sync.observe(None, None)
    assert sync.samples == 3


def test_export_stitch_clock_aligned_multiprocess(telemetry, tmp_path):
    """Two span sidecars (one with a synthetic clock offset) plus a
    journal stitch into ONE Perfetto doc: per-process metadata events,
    globally monotonic timestamps, bracket-valid B/E per (pid, tid),
    journal records as instant events, offsets applied exactly."""
    from demi_tpu.obs import distributed as dtrace
    from demi_tpu.obs import journal

    d = str(tmp_path)
    with obs.span("fleet.lease", round=1):
        with obs.span("admit"):
            pass
    dtrace.export_process(d, "coordinator")
    obs.TRACER.clear()
    with obs.span("fleet.execute", round=1):
        pass
    raw_exec_ts = obs.TRACER.spans[0]["ts"]
    dtrace.export_process(d, "worker-w0", clock_offset_us=250.0)
    j = journal.RoundJournal(d)
    j.emit("dpor.round", round=1, wall_s=0.01)
    j.close()

    out = str(tmp_path / "stitched.json")
    summary = dtrace.stitch([d], out)
    assert {"coordinator", "worker-w0"} <= set(summary["processes"])
    assert any(p.startswith("journal:") for p in summary["processes"])
    assert summary["spans"] == 3
    assert summary["journal_records"] == 1

    doc = json.loads(open(out).read())
    events = doc["traceEvents"]
    named = {
        e["args"]["name"] for e in events
        if e["ph"] == "M" and e["name"] == "process_name"
    }
    assert {"coordinator", "worker-w0"} <= named
    # Distinct processes from ONE test process get distinct pids.
    pids = {e["pid"] for e in events if e["ph"] in ("B", "E")}
    assert len(pids) == 2
    be = [e for e in events if e["ph"] in ("B", "E")]
    last = -1
    stacks = {}
    for e in be:
        assert e["ts"] >= last
        last = e["ts"]
        st = stacks.setdefault((e["pid"], e["tid"]), [])
        if e["ph"] == "B":
            st.append(e["name"])
        else:
            assert st and st.pop() == e["name"]
    assert all(not st for st in stacks.values())
    # The worker's clock offset is applied to its aligned timestamps.
    exec_b = next(
        e for e in be if e["name"] == "fleet.execute" and e["ph"] == "B"
    )
    assert exec_b["ts"] == int(round(
        raw_exec_ts + obs_spans.epoch_unix_us() + 250.0
    ))
    inst = [e for e in events if e["ph"] == "i"]
    assert len(inst) == 1
    assert inst[0]["s"] == "p" and inst[0]["name"] == "dpor.round"


def test_prom_text_help_lines(telemetry):
    """Satellite: every TYPE line is preceded by a HELP line — curated
    text for described metrics, name-derived fallback otherwise."""
    from demi_tpu.obs.timeseries import prom_text

    obs.counter("dpor.rounds").inc(3)
    obs.gauge("custom.thing").set(1.0)
    obs.describe("custom.described", "words chosen by the caller")
    obs.counter("custom.described").inc()
    obs.histogram("dpor.round_seconds").observe(0.5)
    lines = prom_text(obs.REGISTRY.snapshot()).splitlines()
    assert (
        "# HELP demi_dpor_rounds_total DPOR frontier rounds executed"
        in lines
    )
    assert (
        "# HELP demi_custom_described_total words chosen by the caller"
        in lines
    )
    assert "# HELP demi_custom_thing custom thing (demi_tpu)" in lines
    assert any(
        line.startswith("# HELP demi_dpor_round_seconds ") for line in lines
    )
    for i, line in enumerate(lines):
        if line.startswith("# TYPE"):
            pname = line.split()[2]
            assert lines[i - 1].startswith(f"# HELP {pname} "), (
                lines[i - 1], line,
            )


def test_truncate_from_across_rotated_segments(tmp_path):
    """Satellite: resume truncation when the drop point lies in the
    ROTATED segment — rewrite_segments must rewrite BOTH files, and the
    journal stays contiguous + seq-monotonic after re-emitting."""
    from demi_tpu.obs import journal

    j = journal.RoundJournal(str(tmp_path), max_bytes=700)
    for i in range(10):
        j.emit("dpor.round", round=i + 1, pad="x" * 40)
    j.close()
    # The tiny bound forced exactly one rotation: both segments hold
    # records, and rounds > 4 live in BOTH files.
    assert os.path.exists(j.path + ".1")
    rot_rounds = [
        rec["round"] for _, rec in journal._read_lines(j.path + ".1")
    ]
    live_rounds = [
        rec["round"] for _, rec in journal._read_lines(j.path)
    ]
    assert rot_rounds and live_rounds
    assert max(rot_rounds) > 4 and max(live_rounds) > 4

    dropped = j.truncate_from("dpor.round", 4)
    assert dropped == 6  # rounds 5..10, split across the two segments
    # The rotated segment itself was rewritten, not just the live file.
    assert all(
        rec["round"] <= 4 for _, rec in journal._read_lines(j.path + ".1")
    )
    rounds = [
        r["round"] for r in journal.read_records(str(tmp_path), "dpor.round")
    ]
    assert rounds == [1, 2, 3, 4]
    for r in (5, 6):
        j.emit("dpor.round", round=r)
    j.close()
    recs = journal.read_records(str(tmp_path))
    ok, rounds = journal.contiguous_rounds(recs, "dpor.round")
    assert ok and rounds == [1, 2, 3, 4, 5, 6]
    seqs = [r["seq"] for r in recs]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    # rewrite_segments is the shared machinery: an arbitrary filter
    # applied across both segments reports exactly what it dropped.
    dropped = journal.rewrite_segments(
        j.path, lambda rec: rec.get("round", 0) % 2 == 0
    )
    assert dropped == 3  # rounds 1, 3 from .1 / live split, plus 5
    rounds = [
        r["round"] for r in journal.read_records(str(tmp_path), "dpor.round")
    ]
    assert rounds == [2, 4, 6]


def test_top_narrow_terminal_clamps_width(tmp_path):
    """Satellite: render_frame below 60 columns shrinks the bars and
    truncates every line to the terminal width; wide frames keep the
    full layout, including the fleet health + tenant SLO lines."""
    from demi_tpu.obs import journal
    from demi_tpu.tools.top import render_frame

    d = str(tmp_path / "run")
    j = journal.RoundJournal(d)
    for i in range(6):
        j.emit(
            "dpor.round", round=i + 1, wall_s=0.05, host_s=0.02,
            device_s=0.03, frontier=4, depth=2, fresh=3, redundant=1,
            distance_pruned=0, violations=[], explored=5 + i,
            interleavings=8 * (i + 1), batch=8,
        )
    for i in range(3):
        j.emit(
            "fleet.round", round=i + 1, worker=f"w{i % 2}", wall_s=0.04,
            batch=8, classes=5, explored=9, frontier=3, workers_alive=2,
            leases_outstanding=0, frontier_bytes=2048, ledger_bytes=1024,
        )
    j.emit(
        "fleet.straggler", worker="w0", lease=7, round=9, wall_s=1.5,
        median_s=0.05, factor=4.0, leases_outstanding=0,
    )
    j.emit(
        "service.frame", tenant="acme", job="j1", seed=1, wall_s=0.2,
        ttf_mcs_s=1.25, queue_age_s=0.4, queue_depth=0,
        mcs_externals=2, deliveries=3,
    )
    j.close()

    wide = render_frame(d, window=10, width=72)
    assert "stragglers re-leased 1" in wide
    assert "lease wall by worker" in wide
    assert "footprint: frontier 2.0 KiB" in wide
    assert "class ledger 1.0 KiB" in wide
    assert "SLO by tenant: acme ttf-mcs 1.25s queue-age 0.40s" in wide
    assert any(len(line) > 40 for line in wide.splitlines())

    narrow = render_frame(d, window=10, width=40)
    assert all(len(line) <= 40 for line in narrow.splitlines())
    assert "FLEET" in narrow and "DPOR" in narrow
