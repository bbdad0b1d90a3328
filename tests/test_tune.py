"""Measurement-guided autotuning: deterministic controller tests driven
by synthetic metrics streams, tuning-cache round-trips, the CLI dry-run
smoke path, and the regression pin that DEMI_AUTOTUNE unset leaves
fuzz/sweep/dpor outputs identical to the untuned explorer.

The controller logic is exercised with NO device work wherever possible
(synthetic reward/rate streams); the tests that launch real calibration
kernels are marked ``slow`` and stay out of the tier-1 budget.
"""

import json
import os

import pytest

from demi_tpu.fuzzing import Fuzzer, FuzzerWeights
from demi_tpu.tune import (
    DporBudgetTuner,
    ExplorationController,
    TuningCache,
    WeightTuner,
    autotune_enabled,
    calibrate_dpor_inflight,
    calibrate_fork,
    calibrate_sweep,
    coordinate_descent,
    depth_bucket,
    median_rate,
    workload_key,
)


@pytest.fixture(autouse=True)
def _no_ambient_autotune(monkeypatch, tmp_path):
    """Tests control the switch and the cache location explicitly."""
    monkeypatch.delenv("DEMI_AUTOTUNE", raising=False)
    monkeypatch.setenv("DEMI_TUNE_CACHE", str(tmp_path / "tune.json"))


# ---------------------------------------------------------------------------
# WeightTuner: synthetic reward streams
# ---------------------------------------------------------------------------

def _weight_distance(weights, target):
    return sum(abs(weights[k] - target[k]) for k in target)


def test_weight_tuner_converges_toward_planted_best():
    """Reward = closeness to a planted weight vector: coordinate descent
    must move the incumbent strictly closer over enough rounds."""
    start = {"kill": 0.05, "send": 0.6, "wait_quiescence": 0.15}
    target = {"kill": 0.02, "send": 1.5, "wait_quiescence": 0.1}
    tuner = WeightTuner(dict(start))

    def reward(weights):
        return 1.0 - _weight_distance(weights, target) / 3.0

    for _ in range(60):
        trial = tuner.propose()
        tuner.observe(reward(trial))
    assert tuner.accepted > 0
    assert _weight_distance(tuner.weights(), target) < (
        0.5 * _weight_distance(start, target)
    )


def test_weight_tuner_degenerate_signal_keeps_defaults():
    """All-zero (and flat) rewards must never move the weights: no
    signal => the defaults survive untouched."""
    start = {"kill": 0.05, "send": 0.6, "wait_quiescence": 0.15}
    tuner = WeightTuner(dict(start))
    for _ in range(40):
        tuner.propose()
        tuner.observe(0.0)
    assert tuner.weights() == start
    assert tuner.accepted == 0

    flat = WeightTuner(dict(start))
    for _ in range(40):
        flat.propose()
        flat.observe(0.37)  # constant reward: nudges never beat baseline
    assert flat.weights() == start


def test_weight_tuner_only_tunes_active_kinds():
    """Zero-weight kinds are language, not mix: the tuner must never
    enable an event kind the workload didn't opt into."""
    tuner = WeightTuner({"send": 0.6, "partition": 0.0})
    for _ in range(30):
        trial = tuner.propose()
        assert trial["partition"] == 0.0
        tuner.observe(1.0)
    assert tuner.weights()["partition"] == 0.0


# ---------------------------------------------------------------------------
# DporBudgetTuner: prescription-counter streams
# ---------------------------------------------------------------------------

def test_dpor_tuner_widens_distance_when_pruned_dominates():
    t = DporBudgetTuner(batch=64, max_distance=4, max_distance_cap=32)
    t.observe_round(fresh=1, redundant=1, pruned=8, frontier=10)
    assert t.max_distance == 8
    # A zero budget (IncrementalDDMin's first distance rung) must still
    # widen — 0*2 would pin it forever.
    t0 = DporBudgetTuner(batch=64, max_distance=0, max_distance_cap=32)
    t0.observe_round(fresh=0, redundant=1, pruned=9, frontier=10)
    assert t0.max_distance == 1
    t.observe_round(fresh=1, redundant=1, pruned=8, frontier=10)
    t.observe_round(fresh=1, redundant=1, pruned=8, frontier=10)
    t.observe_round(fresh=1, redundant=1, pruned=8, frontier=10)
    assert t.max_distance == 32  # capped
    t.observe_round(fresh=1, redundant=1, pruned=8, frontier=10)
    assert t.max_distance == 32


def test_dpor_tuner_shrinks_round_batch_on_redundant_saturation():
    t = DporBudgetTuner(batch=64, min_batch=8)
    t.observe_round(fresh=2, redundant=60, pruned=0, frontier=5)
    assert t.round_batch == 32
    for _ in range(5):
        t.observe_round(fresh=0, redundant=40, pruned=0, frontier=2)
    assert t.round_batch == 8  # floored at min_batch


def test_dpor_tuner_grows_round_batch_on_fresh_rich_rounds():
    t = DporBudgetTuner(batch=64)
    t.observe_round(fresh=2, redundant=60, pruned=0, frontier=5)
    assert t.round_batch == 32
    t.observe_round(fresh=40, redundant=2, pruned=0, frontier=50)
    assert t.round_batch == 64
    # Degenerate: an empty round changes nothing.
    t.observe_round(fresh=0, redundant=0, pruned=0, frontier=0)
    assert t.round_batch == 64


# ---------------------------------------------------------------------------
# Coordinate descent + calibration over a synthetic rate table
# ---------------------------------------------------------------------------

def test_median_rate_drops_warmup_rep():
    assert median_rate([5.0, 100.0, 110.0, 120.0]) == 110.0
    assert median_rate([42.0]) == 42.0  # lone rep kept
    assert median_rate([]) == 0.0


def test_coordinate_descent_finds_planted_best():
    rates = {
        ("xla", 32): 100.0, ("xla", 64): 120.0,
        ("xla-trailing", 32): 140.0, ("xla-trailing", 64): 180.0,
    }

    def measure(p):
        return rates[(p["variant"], p["chunk"])]

    best, rate, table = coordinate_descent(
        {"variant": ["xla", "xla-trailing"], "chunk": [32, 64]},
        measure,
        {"variant": "xla", "chunk": 32},
    )
    assert best == {"variant": "xla-trailing", "chunk": 64}
    assert rate == 180.0
    # One walk per axis (start + one alternative per knob): 3 points
    # measured, not the full cross product (the point of coordinate
    # descent).
    assert len(table) == 3


def test_coordinate_descent_measurement_failure_loses():
    def measure(p):
        if p["variant"] == "broken":
            raise RuntimeError("no lowering on this backend")
        return 10.0

    best, rate, _ = coordinate_descent(
        {"variant": ["xla", "broken"]}, measure, {"variant": "xla"}
    )
    assert best == {"variant": "xla"}
    assert rate == 10.0


class _ShapeCfg:
    """Duck-typed DeviceConfig shape fields for cache keys."""

    pool_capacity = 64
    max_steps = 96
    max_external_ops = 16
    invariant_interval = 1
    round_delivery = False
    early_exit = False
    msg_dtype = "int32"


class _App:
    name = "t"
    num_actors = 3


def test_calibrate_sweep_synthetic_and_cache_roundtrip(tmp_path):
    """calibrate_sweep with an injected measure: first call measures and
    persists, second call returns the cached decision WITHOUT calling
    measure again (the warm-start acceptance shape)."""
    cache = TuningCache(str(tmp_path / "cache.json"))
    calls = []

    def measure(p):
        calls.append(dict(p))
        return {"xla": 50.0, "xla-trailing": 80.0}[p["variant"]] + p["chunk"]

    axes = {"variant": ["xla", "xla-trailing"], "chunk": [16, 32]}
    d1 = calibrate_sweep(
        _App(), _ShapeCfg(), None, chunk=16, platform="cpu", cache=cache,
        measure=measure, axes=axes,
    )
    assert d1.source == "calibrated"
    assert d1.params == {"variant": "xla-trailing", "chunk": 32}
    assert calls, "first run must measure"

    calls.clear()
    # Fresh cache object on the same file = a new process reading it.
    cache2 = TuningCache(str(tmp_path / "cache.json"))
    d2 = calibrate_sweep(
        _App(), _ShapeCfg(), None, chunk=16, platform="cpu", cache=cache2,
        measure=measure, axes=axes,
    )
    assert d2.source == "cached"
    assert d2.params == d1.params
    assert calls == [], "cache hit must not re-calibrate"

    # A different workload shape misses the cache.
    d3 = calibrate_sweep(
        _App(), _ShapeCfg(), None, chunk=32, platform="cpu", cache=cache2,
        measure=measure, axes=axes,
    )
    assert d3.source == "calibrated"


def test_calibrate_fork_bucket_axis_and_off_decision(tmp_path):
    """calibrate_fork walks the fork_bucket axis with 0 (= fork off)
    competing on equal terms, persists per (shape, depth-bucket), and a
    same-depth-bucket second call is a cache hit with no measurements."""
    cache = TuningCache(str(tmp_path / "cache.json"))
    calls = []

    def measure(p):
        calls.append(int(p["fork_bucket"]))
        return {0: 100.0, 4: 120.0, 8: 180.0, 16: 140.0, 32: 90.0}[
            int(p["fork_bucket"])
        ]

    d1 = calibrate_fork(
        _App(), _ShapeCfg(), depth=100, platform="cpu", cache=cache,
        measure=measure,
    )
    assert d1.source == "calibrated" and d1.bucket == 8 and d1.enabled
    assert set(calls) == {0, 4, 8, 16, 32}

    calls.clear()
    # depth 120 shares the 128 depth bucket with depth 100: cache hit.
    assert depth_bucket(100) == depth_bucket(120) == 128
    d2 = calibrate_fork(
        _App(), _ShapeCfg(), depth=120, platform="cpu",
        cache=TuningCache(str(tmp_path / "cache.json")), measure=measure,
    )
    assert d2.source == "cached" and d2.bucket == 8 and calls == []

    # A shallow workload where scratch wins calibrates fork OFF.
    d3 = calibrate_fork(
        _App(), _ShapeCfg(), depth=10, platform="cpu", cache=cache,
        measure=lambda p: 100.0 if int(p["fork_bucket"]) == 0 else 60.0,
    )
    assert d3.bucket == 0 and not d3.enabled


def test_calibrate_dpor_inflight_axis_and_platform_gate(tmp_path):
    """calibrate_dpor_inflight walks the 0/1 in-flight axis on CPU with
    an injected measure, persists the decision, and a second call is a
    cache hit with no measurements; non-CPU platforms decide 'enabled'
    without measuring (speculation is free there); a CPU cache miss with
    no measure is a loud error, never a silent guess."""
    cache = TuningCache(str(tmp_path / "cache.json"))
    calls = []

    def measure(p):
        calls.append(int(p["dpor_inflight"]))
        return {0: 100.0, 1: 140.0}[int(p["dpor_inflight"])]

    d1 = calibrate_dpor_inflight(
        _App(), _ShapeCfg(), batch=16, platform="cpu", cache=cache,
        measure=measure,
    )
    assert d1.source == "calibrated" and d1.enabled and d1.rate == 140.0
    assert set(calls) == {0, 1}

    calls.clear()
    d2 = calibrate_dpor_inflight(
        _App(), _ShapeCfg(), batch=16, platform="cpu",
        cache=TuningCache(str(tmp_path / "cache.json")), measure=measure,
    )
    assert d2.source == "cached" and d2.enabled and calls == []

    # A workload where the misprediction waste loses calibrates it OFF.
    d3 = calibrate_dpor_inflight(
        _App(), _ShapeCfg(), batch=32, platform="cpu", cache=cache,
        measure=lambda p: 100.0 if int(p["dpor_inflight"]) == 0 else 70.0,
    )
    assert not d3.enabled

    # Non-CPU: enabled by default, no measure needed, still cached.
    d4 = calibrate_dpor_inflight(
        _App(), _ShapeCfg(), batch=16, platform="tpu", cache=cache,
    )
    assert d4.source == "default" and d4.enabled

    with pytest.raises(ValueError):
        calibrate_dpor_inflight(
            _App(), _ShapeCfg(), batch=64, platform="cpu", cache=cache,
        )


@pytest.mark.slow
def test_calibrate_fork_real_measure(tmp_path):
    """Real fork calibration (slow): make_fork_measure drives actual
    DeviceReplayCheckers over an internal-minimization level and the
    decision persists with its fork-telemetry evidence."""
    from demi_tpu.apps.common import dsl_start_events, make_host_invariant
    from demi_tpu.apps.raft import make_raft_app
    from demi_tpu.config import SchedulerConfig
    from demi_tpu.device.batch_oracle import default_device_config
    from demi_tpu.external_events import WaitQuiescence
    from demi_tpu.minimization.internal import (
        removable_delivery_indices,
        remove_delivery,
    )
    from demi_tpu.schedulers import RandomScheduler
    from demi_tpu.tune import make_fork_measure

    app = make_raft_app(3)
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    program = dsl_start_events(app) + [WaitQuiescence(budget=48)]
    result = RandomScheduler(
        config, seed=0, max_messages=200, invariant_check_interval=1
    ).execute(program)
    trace = result.trace
    trace.set_original_externals(list(program))
    indices = removable_delivery_indices(trace)[:12]
    candidates = [remove_delivery(trace, i) for i in indices]
    device_cfg = default_device_config(app, trace, program)
    measure = make_fork_measure(
        app, device_cfg, config, candidates, list(program), reps=1
    )
    cache = TuningCache(str(tmp_path / "cache.json"))
    decision = calibrate_fork(
        _App(), _ShapeCfg(), depth=len(trace.deliveries()),
        platform="cpu", cache=cache, measure=measure, axis=(0, 8),
    )
    assert decision.source == "calibrated"
    assert decision.bucket in (0, 8)
    assert decision.rates  # both points measured
    d2 = calibrate_fork(
        _App(), _ShapeCfg(), depth=len(trace.deliveries()),
        platform="cpu", cache=cache, measure=measure, axis=(0, 8),
    )
    assert d2.source == "cached"


def test_calibrate_weight_bonus_synthetic_and_default(tmp_path):
    """calibrate_weight_bonus walks the bonus axis with an injected
    measure (distinct violations/sec), persists the winner as the
    TuningCache default the ExplorationController reads, and a second
    call is a cache hit with no measurements; a cache miss with no
    measure is a loud error."""
    from demi_tpu.tune import (
        VIOLATION_BONUS_AXIS,
        VIOLATION_BONUS_DEFAULT_KEY,
        ExplorationController,
        calibrate_weight_bonus,
        default_violation_bonus,
    )

    cache = TuningCache(str(tmp_path / "cache.json"))
    calls = []
    table = {2.0: 0.5, 5.0: 0.9, 10.0: 0.7, 20.0: 0.4}

    def measure(p):
        calls.append(float(p["violation_bonus"]))
        return table[float(p["violation_bonus"])]

    d1 = calibrate_weight_bonus(cache=cache, measure=measure)
    assert d1.source == "calibrated"
    assert d1.bonus == 5.0 and d1.rate == 0.9
    assert set(calls) == set(VIOLATION_BONUS_AXIS)

    calls.clear()
    d2 = calibrate_weight_bonus(
        cache=TuningCache(str(tmp_path / "cache.json")), measure=measure
    )
    assert d2.source == "cached" and d2.bonus == 5.0 and calls == []

    # The persisted winner becomes the controller's reward shape.
    assert default_violation_bonus(cache) == 5.0
    ctl = ExplorationController(violation_bonus=default_violation_bonus(cache))
    assert ctl.violation_bonus == 5.0
    # And an explicit bonus always wins.
    assert ExplorationController(violation_bonus=3.0).violation_bonus == 3.0
    # Never-calibrated caches fall back to the hand-set 10x.
    assert default_violation_bonus(
        TuningCache(str(tmp_path / "empty.json"))
    ) == 10.0

    with pytest.raises(ValueError):
        calibrate_weight_bonus(
            cache=TuningCache(str(tmp_path / "other.json")), key="axis=x"
        )


@pytest.mark.slow
def test_calibrate_weight_bonus_real_measure(tmp_path):
    """Real bonus calibration (slow): make_bonus_measure drives actual
    host fuzz executions on the unreliable-broadcast fixture and
    calibrate_weight_bonus persists a winner from the measured axis."""
    from demi_tpu.apps.broadcast import (
        broadcast_send_generator,
        make_broadcast_app,
    )
    from demi_tpu.apps.common import dsl_start_events, make_host_invariant
    from demi_tpu.config import SchedulerConfig
    from demi_tpu.fuzzing import Fuzzer, FuzzerWeights
    from demi_tpu.tune import calibrate_weight_bonus, make_bonus_measure

    app = make_broadcast_app(3, reliable=False)

    def fuzzer_factory(seed):
        return Fuzzer(
            num_events=10,
            weights=FuzzerWeights(kill=0.05, send=0.6, wait_quiescence=0.15),
            message_gen=broadcast_send_generator(app),
            prefix=dsl_start_events(app),
            max_kills=1,
        )

    def config_factory():
        return SchedulerConfig(invariant_check=make_host_invariant(app))

    measure = make_bonus_measure(
        fuzzer_factory, config_factory, seeds=2, target_distinct=1,
        max_executions=40, timeout_seconds=20.0,
    )
    cache = TuningCache(str(tmp_path / "cache.json"))
    d = calibrate_weight_bonus(
        cache=cache, measure=measure, axis=(5.0, 10.0)
    )
    assert d.source == "calibrated"
    assert d.bonus in (5.0, 10.0)
    assert len(d.rates) == 2


def test_tuning_cache_survives_corrupt_file(tmp_path):
    path = tmp_path / "cache.json"
    path.write_text("{not json")
    cache = TuningCache(str(path))
    assert cache.get("k") is None
    cache.put("k", {"params": {"variant": "xla"}})
    assert TuningCache(str(path)).get("k")["params"]["variant"] == "xla"


def test_workload_key_is_shape_stable():
    k1 = workload_key("app", 4, _ShapeCfg(), "cpu", chunk=16)
    k2 = workload_key("app", 4, _ShapeCfg(), "cpu", chunk=16)
    assert k1 == k2
    assert workload_key("app", 5, _ShapeCfg(), "cpu", chunk=16) != k1
    assert workload_key("app", 4, _ShapeCfg(), "tpu", chunk=16) != k1


# ---------------------------------------------------------------------------
# ExplorationController: reward attribution on a synthetic stream
# ---------------------------------------------------------------------------

def test_controller_rewards_fresh_fingerprints_only():
    ctrl = ExplorationController(fuzzer=None, weight_tuner=None)
    r1 = ctrl.end_round(hashes=[1, 2, 3], violations=0, lanes=3)
    assert r1 == 1.0  # all fresh
    r2 = ctrl.end_round(hashes=[1, 2, 3], violations=0, lanes=3)
    assert r2 == 0.0  # all seen: re-finding old schedules earns nothing
    r3 = ctrl.end_round(hashes=[4], violations=1, lanes=2)
    assert r3 == (1 + ExplorationController.VIOLATION_BONUS) / 2


def test_controller_swaps_fuzzer_weights_between_rounds():
    from demi_tpu.apps.broadcast import broadcast_send_generator, make_broadcast_app
    from demi_tpu.apps.common import dsl_start_events

    app = make_broadcast_app(3, reliable=False)
    fuzzer = Fuzzer(
        num_events=6,
        weights=FuzzerWeights(kill=0.05, send=0.6, wait_quiescence=0.15),
        message_gen=broadcast_send_generator(app),
        prefix=dsl_start_events(app),
        max_kills=1,
    )
    original = fuzzer.weights
    ctrl = ExplorationController(fuzzer)
    for h in range(6):
        ctrl.begin_round()
        # Each round runs under the tuner's live proposal.
        assert fuzzer.weights.as_dict() == ctrl.weight_tuner.weights() or (
            ctrl.weight_tuner._pending is not None
        )
        # Reward stream with variance so proposals get scored.
        ctrl.end_round(hashes=[h * 3, h * 3 + 1], violations=h % 2, lanes=2)
    assert ctrl.rounds == 6
    assert fuzzer.weights is not original  # weights really were swapped
    # Programs still generate and sanity-check under swapped weights.
    prog = fuzzer.generate_fuzz_test(seed=1)
    assert prog


# ---------------------------------------------------------------------------
# Runtime-settable fuzzer weights
# ---------------------------------------------------------------------------

def _shape(program):
    """Structural view of a generated program: eids are a global counter
    and differ between generations of identical programs."""
    return [
        (
            type(e).__name__,
            getattr(e, "name", None),
            getattr(e, "budget", None),
        )
        for e in program
    ]


def test_fuzzer_weights_dict_roundtrip_and_validation():
    w = FuzzerWeights(kill=0.1, send=0.5)
    assert FuzzerWeights.from_dict(w.as_dict()) == w
    with pytest.raises(ValueError):
        FuzzerWeights.from_dict({"sendz": 1.0})


def test_fuzzer_set_weights_applies_to_next_program():
    from demi_tpu.apps.broadcast import broadcast_send_generator, make_broadcast_app
    from demi_tpu.apps.common import dsl_start_events
    from demi_tpu.external_events import Kill

    app = make_broadcast_app(4, reliable=False)

    def make(weights):
        return Fuzzer(
            num_events=12, weights=weights,
            message_gen=broadcast_send_generator(app),
            prefix=dsl_start_events(app), max_kills=2,
        )

    base = FuzzerWeights(kill=0.0, send=1.0)
    heavy = FuzzerWeights(kill=5.0, send=0.2)
    fz = make(base)
    no_kills = fz.generate_fuzz_test(seed=7)
    fz.set_weights(heavy)
    with_kills = fz.generate_fuzz_test(seed=7)
    assert not any(isinstance(e, Kill) for e in no_kills)
    assert any(isinstance(e, Kill) for e in with_kills)
    # Same (weights, seed) => same program shape regardless of swap
    # history (eids are a global counter, so compare structurally).
    assert _shape(with_kills) == _shape(make(heavy).generate_fuzz_test(seed=7))
    with pytest.raises(ValueError):
        fz.set_weights(FuzzerWeights(kill=0.0, send=0.0, wait_quiescence=0.0))


# ---------------------------------------------------------------------------
# Regression: DEMI_AUTOTUNE unset => outputs identical to the untuned path
# ---------------------------------------------------------------------------

def test_autotune_defaults_off_and_sweep_output_unchanged(capsys):
    """With the env unset, (a) the switch reads off, (b) `demi_tpu sweep`
    emits the same verdict fields as a direct untuned SweepDriver run of
    the same workload, and (c) no autotune key appears."""
    from demi_tpu.cli import main
    from demi_tpu.apps.broadcast import broadcast_send_generator, make_broadcast_app
    from demi_tpu.apps.common import dsl_start_events
    from demi_tpu.device import DeviceConfig
    from demi_tpu.parallel.sweep import SweepDriver

    assert not autotune_enabled()
    rc = main([
        "sweep", "--app", "broadcast", "--nodes", "4", "--bug", "unreliable",
        "--batch", "24", "--pool", "64", "--max-messages", "96",
    ])
    assert rc == 0
    data = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "autotune" not in data

    app = make_broadcast_app(4, reliable=False)
    cfg = DeviceConfig.for_app(
        app, pool_capacity=64, max_steps=96,
        max_external_ops=max(16, 12 + app.num_actors + 2),
        # The verb takes the cadence from the app (agreement is judged at
        # quiescence), so the direct run does too.
        invariant_interval=app.invariant_interval, timer_weight=0.2,
    )
    fuzzer = Fuzzer(
        num_events=12,
        weights=FuzzerWeights(
            kill=0.05, send=0.6, wait_quiescence=0.15,
            partition=0.0, unpartition=0.0,
        ),
        message_gen=broadcast_send_generator(app),
        prefix=dsl_start_events(app), max_kills=1,
    )
    driver = SweepDriver(
        app, cfg, lambda s: fuzzer.generate_fuzz_test(seed=s)
    )
    result = driver.sweep(24, 24)
    assert data["lanes"] == result.lanes
    assert data["violations"] == result.violations
    assert data["unique_schedules"] == result.unique_schedules
    assert data["codes"] == {str(c): n for c, n in result.codes.items()}


def test_fuzz_programs_identical_without_controller():
    """The seed behavior pin: constructing tune machinery must not leak
    into an untuned fuzzer — same seeds, same programs."""
    from demi_tpu.apps.broadcast import broadcast_send_generator, make_broadcast_app
    from demi_tpu.apps.common import dsl_start_events

    app = make_broadcast_app(4, reliable=False)

    def make():
        return Fuzzer(
            num_events=10,
            weights=FuzzerWeights(kill=0.05, send=0.6, wait_quiescence=0.15),
            message_gen=broadcast_send_generator(app),
            prefix=dsl_start_events(app), max_kills=1,
        )

    before = [_shape(make().generate_fuzz_test(seed=s)) for s in range(5)]
    # Exercise the tune import + an unrelated controller, then regenerate.
    ExplorationController(make())
    after = [_shape(make().generate_fuzz_test(seed=s)) for s in range(5)]
    assert before == after


def test_device_dpor_untuned_has_no_tuner_and_full_round_batch():
    from demi_tpu.config import SchedulerConfig
    from demi_tpu.apps.common import make_host_invariant
    from demi_tpu.apps.broadcast import make_broadcast_app
    from demi_tpu.device import DeviceConfig
    from demi_tpu.device.dpor_sweep import DeviceDPOROracle

    app = make_broadcast_app(3, reliable=False)
    cfg = DeviceConfig.for_app(
        app, pool_capacity=32, max_steps=32, max_external_ops=12,
        invariant_interval=1, record_trace=True, record_parents=True,
    )
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    oracle = DeviceDPOROracle(app, cfg, config, batch_size=8)
    inst = oracle._instance([])
    assert inst.tuner is None
    assert inst.round_batch == 8


# ---------------------------------------------------------------------------
# CLI: tune --dry-run smoke (fast), full calibration (slow)
# ---------------------------------------------------------------------------

def test_cli_tune_dry_run_smoke(capsys, tmp_path):
    from demi_tpu.cli import main

    rc = main([
        "tune", "--app", "broadcast", "--nodes", "3", "--batch", "16",
        "--pool", "64", "--max-messages", "64",
        "--cache", str(tmp_path / "c.json"), "--dry-run",
    ])
    assert rc == 0
    data = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert data["dry_run"] is True
    assert data["cached"] is None
    assert "variant" in data["axes"] and "chunk" in data["axes"]
    # Agreement is judged at quiescence only (the app says so, the verb
    # builds interval 0 from it): round variants are candidates.
    assert any("-round" in v for v in data["axes"]["variant"])
    rc = main([
        "tune", "--app", "raft", "--nodes", "3", "--batch", "16",
        "--pool", "64", "--max-messages", "64",
        "--cache", str(tmp_path / "c.json"), "--dry-run",
    ])
    assert rc == 0
    data = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # interval=1 workload: round variants are not semantics-preserving
    # candidates.
    assert all("-round" not in v for v in data["axes"]["variant"])


@pytest.mark.slow
def test_cli_tune_real_calibration_and_cache_reuse(capsys, tmp_path):
    """Real kernel calibration (slow): calibrate, then verify the second
    run returns the persisted decision without re-measuring."""
    from demi_tpu.cli import main

    args = [
        "tune", "--app", "broadcast", "--nodes", "3", "--bug", "unreliable",
        "--batch", "16", "--pool", "64", "--max-messages", "64",
        "--reps", "1", "--cache", str(tmp_path / "c.json"),
    ]
    assert main(args) == 0
    first = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert first["source"] == "calibrated"
    assert first["rates"]

    assert main(args) == 0
    second = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert second["source"] == "cached"
    assert second["params"] == first["params"]


@pytest.mark.slow
def test_cli_sweep_autotune_end_to_end(capsys, tmp_path, monkeypatch):
    """--autotune sweep: calibrated decision reported, decisions land in
    the obs snapshot, verdict fields still populated."""
    from demi_tpu import obs
    from demi_tpu.cli import main

    monkeypatch.setenv("DEMI_TUNE_CACHE", str(tmp_path / "t.json"))
    rc = main([
        "sweep", "--app", "broadcast", "--nodes", "4", "--bug", "unreliable",
        "--batch", "32", "--chunk", "16", "--pool", "64",
        "--max-messages", "96", "--autotune",
    ])
    monkeypatch.delenv("DEMI_AUTOTUNE", raising=False)
    assert rc == 0
    data = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert data["lanes"] == 32
    assert data["autotune"]["decision"]["source"] == "calibrated"
    assert data["autotune"]["decision"]["params"]["variant"]
    # Decisions are snapshot-visible even with DEMI_OBS off (force_set).
    snap = obs.REGISTRY.snapshot()
    assert "tune.sweep.variant" in snap["gauges"]
    assert "tune.sweep.rate" in snap["gauges"]
