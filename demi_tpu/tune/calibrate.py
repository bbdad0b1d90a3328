"""Sweep-shape calibration: pick (kernel variant, chunk size, segment
length) from short measured reps, coordinate-descent style.

BENCH_r05 shows the explore-kernel impl variants differ by ~10% on the
same workload with the winner platform-dependent, and rep spread of ±15%
— so calibration (a) drops the first warm-up rep and scores the median,
and (b) walks one knob axis at a time (arXiv:2406.20037's
coordinate-descent schedule search) instead of the full cross product:
sum(len(axis)) measurements, not product.

The measurement function is injectable: production uses a real chunked
kernel launch per candidate; tests drive the same search logic with a
synthetic rate table and zero device work.

Decisions persist to the ``TuningCache`` keyed by workload shape +
platform, so a second run of the same workload warm-starts: cache hit =
no kernel launches at all.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence

from .cache import TuningCache, workload_key
from .controller import record_decision

#: Knob axes walked in order. ``variant`` first: the impl choice shifts
#: the whole rate curve, so later shape knobs should be tuned on the
#: winning kernel.
KNOB_ORDER = ("variant", "chunk", "seg")


@dataclass
class SweepDecision:
    """One calibration outcome: chosen knob values + the evidence."""

    params: Dict[str, Any]
    rate: float  # schedules/sec of the chosen point (median rep)
    source: str  # "calibrated" | "cached" | "default"
    rates: Dict[str, float] = field(default_factory=dict)  # point -> rate
    key: Optional[str] = None
    calibration_seconds: float = 0.0

    def to_json(self) -> Dict[str, Any]:
        return {
            "params": dict(self.params),
            "rate": round(self.rate, 1),
            "source": self.source,
            "rates": {k: round(v, 1) for k, v in self.rates.items()},
            "key": self.key,
            "calibration_seconds": round(self.calibration_seconds, 2),
        }

    @classmethod
    def from_json(cls, obj: Dict[str, Any], source: str) -> "SweepDecision":
        return cls(
            params=dict(obj.get("params", {})),
            rate=float(obj.get("rate", 0.0)),
            source=source,
            rates=dict(obj.get("rates", {})),
            key=obj.get("key"),
        )


def _point_key(params: Dict[str, Any]) -> str:
    return ",".join(f"{k}={params[k]}" for k in sorted(params))


def median_rate(rates: Sequence[float], drop_first: bool = True) -> float:
    """Median of measured reps, first (warm-up) rep dropped when there is
    anything left after dropping — the anti-±15%-spread rule bench.py and
    calibration share."""
    rs = list(rates)
    if drop_first and len(rs) > 1:
        rs = rs[1:]
    if not rs:
        return 0.0
    rs.sort()
    return rs[len(rs) // 2]


def coordinate_descent(
    axes: Dict[str, Sequence[Any]],
    measure: Callable[[Dict[str, Any]], float],
    start: Dict[str, Any],
    order: Sequence[str] = KNOB_ORDER,
) -> "tuple[Dict[str, Any], float, Dict[str, float]]":
    """Walk each axis once, adopting the argmax with other knobs fixed at
    their current best. Returns (best params, best rate, all measured
    rates). Measurement failures (a variant that doesn't lower on this
    backend) score 0 and lose naturally."""
    current = dict(start)
    rates: Dict[str, float] = {}
    best_rate = 0.0

    def score(params: Dict[str, Any]) -> float:
        key = _point_key(params)
        if key not in rates:
            try:
                rates[key] = float(measure(dict(params)))
            except Exception:
                rates[key] = 0.0
        return rates[key]

    best_rate = score(current)
    for knob in order:
        if knob not in axes or knob not in current:
            continue
        for value in axes[knob]:
            if value == current[knob]:
                continue
            trial = dict(current)
            trial[knob] = value
            r = score(trial)
            if r > best_rate:
                best_rate = r
                current = trial
    return current, best_rate, rates


def sweep_axes(
    cfg, chunk: int, continuous: bool = False
) -> Dict[str, List[Any]]:
    """Candidate axes for a sweep on this workload.

    Variants are restricted to the semantics-preserving set: lane-axis
    and early-exit change nothing observable; round-delivery coarsens
    invariant checks to round granularity, so it is only a candidate
    when ``invariant_interval == 0`` (checks only at quiescence — same
    verdicts either way, the bench config-5 equivalence).
    The ``seg`` (segment length) axis only exists for continuous drivers;
    a chunked launch has no segment knob."""
    from ..device.explore import EXPLORE_VARIANTS

    variants = [
        v for v in EXPLORE_VARIANTS
        if cfg.invariant_interval == 0 or "-round" not in v
    ]
    axes: Dict[str, List[Any]] = {
        "variant": variants,
        "chunk": sorted({max(8, chunk // 2), chunk, chunk * 2}),
    }
    if continuous:
        axes["seg"] = sorted({
            max(8, min(64, cfg.max_steps // 8)),
            max(8, min(64, cfg.max_steps // 4)),
            max(8, min(128, cfg.max_steps // 2)),
        })
    return axes


def make_chunked_measure(
    app, cfg, program_gen, *, reps: int = 3, base_key: int = 0
):
    """Real measurement for one candidate point: build the variant
    kernel, run ``reps`` chunk-sized launches (first dropped as warm-up —
    it carries compilation), return median lanes/sec. ``seg`` is ignored
    here (a chunked launch has no segment knob); the axis only moves
    rates for continuous drivers, whose measure fn callers supply."""
    import numpy as np

    import jax

    from ..device.encoding import lower_program, stack_programs
    from ..device.explore import make_explore_kernel_variant

    kernels: Dict[str, Any] = {}
    progs_by_chunk: Dict[int, Any] = {}

    def measure(params: Dict[str, Any]) -> float:
        chunk = int(params["chunk"])
        variant = params["variant"]
        kernel = kernels.get(variant)
        if kernel is None:
            kernel = kernels[variant] = make_explore_kernel_variant(
                app, cfg, variant
            )
        progs = progs_by_chunk.get(chunk)
        if progs is None:
            progs = progs_by_chunk[chunk] = stack_programs(
                [lower_program(app, cfg, program_gen(s)) for s in range(chunk)]
            )
        rates = []
        for rep in range(reps + 1):  # +1: the dropped warm-up rep
            keys = jax.vmap(
                lambda s: jax.random.fold_in(
                    jax.random.PRNGKey(base_key + rep), s
                )
            )(np.arange(chunk, dtype=np.uint32))
            t0 = time.perf_counter()
            res = kernel(progs, keys)
            jax.block_until_ready(res.status)
            rates.append(chunk / (time.perf_counter() - t0))
        return median_rate(rates, drop_first=True)

    return measure


def calibrate_sweep(
    app,
    cfg,
    program_gen=None,
    *,
    chunk: int,
    platform: Optional[str] = None,
    cache: Optional[TuningCache] = None,
    measure: Optional[Callable[[Dict[str, Any]], float]] = None,
    axes: Optional[Dict[str, Sequence[Any]]] = None,
    reps: int = 3,
    extra_key: Optional[Dict[str, Any]] = None,
) -> SweepDecision:
    """The calibration entry point: cache lookup, else coordinate-descent
    over the candidate axes with measured reps, decision recorded in the
    obs registry and persisted back to the cache."""
    if platform is None:
        import jax

        platform = jax.devices()[0].platform
    cache = cache or TuningCache()
    key = workload_key(
        app.name, app.num_actors, cfg, platform, chunk=chunk,
        **(extra_key or {}),
    )
    cached = cache.get(key)
    if cached is not None:
        decision = SweepDecision.from_json(cached, source="cached")
        decision.key = key
        _record_sweep_decision(decision)
        return decision

    axes = dict(axes) if axes is not None else sweep_axes(cfg, chunk)
    defaults = {
        "variant": "xla",
        "chunk": chunk,
        "seg": max(8, min(64, cfg.max_steps // 4)),
    }
    start = {knob: defaults.get(knob) for knob in axes}
    for knob, candidates in axes.items():
        if candidates and start.get(knob) not in candidates:
            start[knob] = candidates[0]
    if measure is None:
        measure = make_chunked_measure(app, cfg, program_gen, reps=reps)
    t0 = time.perf_counter()
    params, rate, rates = coordinate_descent(axes, measure, start)
    decision = SweepDecision(
        params=params,
        rate=rate,
        source="calibrated",
        rates=rates,
        key=key,
        calibration_seconds=time.perf_counter() - t0,
    )
    _record_sweep_decision(decision)
    cache.put(key, decision.to_json())
    return decision


# ---------------------------------------------------------------------------
# Prefix-fork calibration: fork_bucket axis + per-depth on/off decision
# ---------------------------------------------------------------------------

#: fork_bucket candidates; 0 means "prefix-fork off for this workload
#: depth" — the on/off decision falls out of the same argmax that picks
#: the granularity (ROADMAP prefix-fork follow-on: tuner-learned bucket).
FORK_BUCKET_AXIS = (0, 4, 8, 16, 32)


def depth_bucket(depth: int) -> int:
    """Power-of-two bucket of a workload's delivery depth. Fork economics
    scale with prefix length (bench config 6: 192 deliveries -> ~1.85x,
    64 -> ~1.3x), so decisions cache per depth bucket, not per exact
    depth — a 100- and a 120-delivery minimization share one decision."""
    return 1 << max(0, (max(1, depth) - 1).bit_length())


def fork_signals() -> Dict[str, float]:
    """Decision evidence from the already-recorded fork telemetry:
    ``fork.steps_saved`` (prefix work the fork lanes skipped) and the
    mean group sizes of the ``fork.group_size`` / ``dpor.prefix_group_size``
    histograms. A mean group size under 2 means trunks don't amortize and
    the calibrated off-decision is expected; recorded into the decision
    so the cache entry explains itself."""
    from .. import obs

    out: Dict[str, float] = {}
    snap = obs.REGISTRY.snapshot()
    steps = snap.get("counters", {}).get("fork.steps_saved", {})
    if steps:
        out["steps_saved"] = float(sum(steps.values()))
    for name, label in (
        ("fork.group_size", "mean_group_size"),
        ("dpor.prefix_group_size", "mean_dpor_group_size"),
    ):
        series = snap.get("histograms", {}).get(name, {})
        count = sum(rec["count"] for rec in series.values())
        if count:
            total = sum(rec["sum"] for rec in series.values())
            out[label] = round(total / count, 2)
    return out


@dataclass
class ForkDecision:
    """One fork calibration outcome for a (workload shape, depth bucket):
    the chosen bucket (0 = fork off) plus the measured evidence."""

    bucket: int
    rate: float
    source: str  # "calibrated" | "cached" | "default"
    rates: Dict[str, float] = field(default_factory=dict)
    signals: Dict[str, float] = field(default_factory=dict)
    key: Optional[str] = None
    calibration_seconds: float = 0.0

    @property
    def enabled(self) -> bool:
        return self.bucket > 0

    def to_json(self) -> Dict[str, Any]:
        return {
            "bucket": int(self.bucket),
            "enabled": self.enabled,
            "rate": round(self.rate, 1),
            "source": self.source,
            "rates": {k: round(v, 1) for k, v in self.rates.items()},
            "signals": dict(self.signals),
            "key": self.key,
            "calibration_seconds": round(self.calibration_seconds, 2),
        }

    @classmethod
    def from_json(cls, obj: Dict[str, Any], source: str) -> "ForkDecision":
        return cls(
            bucket=int(obj.get("bucket", 0)),
            rate=float(obj.get("rate", 0.0)),
            source=source,
            rates=dict(obj.get("rates", {})),
            signals=dict(obj.get("signals", {})),
            key=obj.get("key"),
        )


def make_fork_measure(
    app, device_cfg, config, candidates, externals, *,
    target_code: int = 1, reps: int = 2
):
    """Real measurement for one fork_bucket candidate: a fresh
    DeviceReplayChecker per point (bucket 0 = fork off), one warm-up
    verdicts pass (compiles kernels + populates the trunk cache — the
    steady state of consecutive minimization rounds), then ``reps`` timed
    passes; returns trials/sec. The winning checker's fork stats land in
    ``measure.signals`` for the decision record."""
    from ..device.batch_oracle import DeviceReplayChecker

    exts = [externals] * len(candidates)

    def measure(params: Dict[str, Any]) -> float:
        bucket = int(params["fork_bucket"])
        checker = DeviceReplayChecker(
            app, device_cfg, config,
            prefix_fork=bucket > 0, fork_bucket=bucket or 8,
        )
        checker.verdicts(candidates, exts, target_code)  # warm-up
        t0 = time.perf_counter()
        for _ in range(reps):
            checker.verdicts(candidates, exts, target_code)
        rate = len(candidates) * reps / (time.perf_counter() - t0)
        if checker.fork_stats is not None:
            st = checker.fork_stats
            lanes = st["forked_lanes"] + st["scratch_lanes"]
            measure.signals[f"bucket={bucket}"] = {
                "steps_saved": st["steps_saved"],
                "forked_fraction": round(
                    st["forked_lanes"] / lanes, 3
                ) if lanes else 0.0,
                "parent_trunks": st["parent_trunks"],
            }
        return rate

    measure.signals = {}
    return measure


def calibrate_fork(
    app,
    cfg,
    *,
    depth: int,
    platform: Optional[str] = None,
    cache: Optional[TuningCache] = None,
    measure: Optional[Callable[[Dict[str, Any]], float]] = None,
    axis: Optional[Sequence[int]] = None,
    extra_key: Optional[Dict[str, Any]] = None,
) -> ForkDecision:
    """Calibrate the prefix-fork bucket (and the fork on/off decision)
    for one workload shape + depth bucket. Caching contract as
    ``calibrate_sweep``: cache hit = no measurements at all; otherwise a
    single-axis coordinate-descent walk over ``FORK_BUCKET_AXIS`` with
    bucket 0 (fork off) competing on equal terms, persisted to the
    TuningCache and recorded as ``tune.fork.*`` decisions. Unlike
    ``calibrate_sweep`` there is no default ``measure`` — a real one
    needs the workload's candidate traces (``make_fork_measure``), which
    this signature does not carry — so a cache miss requires it."""
    if platform is None:
        import jax

        platform = jax.devices()[0].platform
    cache = cache or TuningCache()
    key = workload_key(
        app.name, app.num_actors, cfg, platform,
        axis="fork", depth=depth_bucket(depth), **(extra_key or {}),
    )
    cached = cache.get(key)
    if cached is not None:
        decision = ForkDecision.from_json(cached, source="cached")
        decision.key = key
        _record_fork_decision(decision)
        return decision

    if measure is None:
        raise ValueError(
            "calibrate_fork: cache miss for %r and no measure given — "
            "build one with make_fork_measure(app, device_cfg, config, "
            "candidates, externals)" % (key,)
        )
    candidates = list(axis) if axis is not None else list(FORK_BUCKET_AXIS)
    start = {"fork_bucket": candidates[0]}
    t0 = time.perf_counter()
    params, rate, rates = coordinate_descent(
        {"fork_bucket": candidates}, measure, start, order=("fork_bucket",)
    )
    decision = ForkDecision(
        bucket=int(params["fork_bucket"]),
        rate=rate,
        source="calibrated",
        rates=rates,
        signals={
            **fork_signals(),
            **{
                k: v for k, v in getattr(measure, "signals", {}).items()
                if k == f"bucket={int(params['fork_bucket'])}"
            },
        },
        key=key,
        calibration_seconds=time.perf_counter() - t0,
    )
    _record_fork_decision(decision)
    cache.put(key, decision.to_json())
    return decision


# ---------------------------------------------------------------------------
# DPOR in-flight (double-buffered frontier rounds) calibration
# ---------------------------------------------------------------------------

#: In-flight candidates: 0 = synchronous rounds, 1 = double-buffered
#: (round N+1 dispatched as a full speculative launch before round N's
#: harvest). On TPU speculation is free — host and device are disjoint —
#: so DeviceDPOR defaults it on under DEMI_ASYNC_MIN there; on CPU the
#: "device" lanes run on the host's own cores and a mispredicted launch
#: burns real compute, so the decision must be measured per workload.
DPOR_INFLIGHT_AXIS = (0, 1)


@dataclass
class InflightDecision:
    """One in-flight calibration outcome for a workload shape: the
    on/off decision plus the measured evidence (rounds/sec per point and
    the winning run's speculation economy)."""

    enabled: bool
    rate: float  # frontier interleavings/sec of the chosen point
    source: str  # "calibrated" | "cached" | "default"
    rates: Dict[str, float] = field(default_factory=dict)
    signals: Dict[str, Any] = field(default_factory=dict)
    key: Optional[str] = None
    calibration_seconds: float = 0.0

    def to_json(self) -> Dict[str, Any]:
        return {
            "enabled": bool(self.enabled),
            "rate": round(self.rate, 1),
            "source": self.source,
            "rates": {k: round(v, 1) for k, v in self.rates.items()},
            "signals": dict(self.signals),
            "key": self.key,
            "calibration_seconds": round(self.calibration_seconds, 2),
        }

    @classmethod
    def from_json(cls, obj: Dict[str, Any], source: str) -> "InflightDecision":
        return cls(
            enabled=bool(obj.get("enabled", False)),
            rate=float(obj.get("rate", 0.0)),
            source=source,
            rates=dict(obj.get("rates", {})),
            signals=dict(obj.get("signals", {})),
            key=obj.get("key"),
        )


def make_dpor_inflight_measure(
    app, device_cfg, program, *, batch: int = 16, rounds: int = 3,
    reps: int = 2, target_code: Optional[int] = None,
    prefix_fork: Optional[bool] = None,
):
    """Real measurement for one in-flight candidate: a fresh DeviceDPOR
    per rep (exploration is stateful — reps must start from the same
    frontier), one warm-up round (compiles the kernel and seeds the
    frontier), then ``rounds`` timed frontier rounds; returns median
    interleavings/sec. Kernels are shared across points/reps so the walk
    compiles once. The winning run's in-flight economy lands in
    ``measure.signals``. ``prefix_fork`` is the search's own switch (the
    ``dpor`` verb's flag), so the measured explorers are built as the
    search's are."""
    from ..device.dpor_sweep import DeviceDPOR, make_dpor_kernel
    from ..device.fork import prefix_fork_enabled

    kernel = make_dpor_kernel(app, device_cfg)
    # With prefix-fork on each fresh DeviceDPOR would otherwise jit its
    # own identical start_state kernel — (reps+1) x 2 candidates of
    # redundant compiles polluting the timed rounds.
    fork_kernel = (
        make_dpor_kernel(app, device_cfg, start_state=True)
        if prefix_fork_enabled(prefix_fork)
        else None
    )

    def measure(params: Dict[str, Any]) -> float:
        on = bool(int(params["dpor_inflight"]))
        rates = []
        last = None
        for _ in range(reps + 1):  # +1: the dropped warm-up rep
            dpor = DeviceDPOR(
                app, device_cfg, program, batch_size=batch,
                double_buffer=on, kernel=kernel, fork_kernel=fork_kernel,
                prefix_fork=prefix_fork,
                # The shared kernels are plain ones; pin sleep mode off
                # so an ambient DEMI_SLEEP_SETS cannot mismatch them.
                sleep_sets=False,
            )
            dpor.explore(target_code=target_code, max_rounds=1)
            before = dpor.interleavings
            t0 = time.perf_counter()
            dpor.explore(target_code=target_code, max_rounds=rounds)
            secs = time.perf_counter() - t0
            rates.append((dpor.interleavings - before) / secs if secs else 0.0)
            last = dpor
        if last is not None:
            measure.signals[f"inflight={int(on)}"] = dict(last.async_stats)
        return median_rate(rates, drop_first=True)

    measure.signals = {}
    return measure


def calibrate_dpor_inflight(
    app,
    cfg,
    *,
    batch: int,
    platform: Optional[str] = None,
    cache: Optional[TuningCache] = None,
    measure: Optional[Callable[[Dict[str, Any]], float]] = None,
    axis: Optional[Sequence[int]] = None,
    extra_key: Optional[Dict[str, Any]] = None,
) -> InflightDecision:
    """Calibrate the DeviceDPOR double-buffer decision for one workload
    shape + platform. Caching contract as ``calibrate_fork``: a cache hit
    costs no measurements; a miss requires ``measure`` (a real one needs
    the workload's program — ``make_dpor_inflight_measure``). On non-CPU
    platforms with no measure given, the decision defaults to enabled
    without measuring (host and device are disjoint there, so a wasted
    in-flight launch costs the host nothing); on CPU the axis is walked
    for real. Persisted to the TuningCache, recorded as
    ``tune.dpor.inflight`` decisions."""
    if platform is None:
        import jax

        platform = jax.devices()[0].platform
    cache = cache or TuningCache()
    key = workload_key(
        app.name, app.num_actors, cfg, platform,
        axis="dpor_inflight", batch=batch, **(extra_key or {}),
    )
    cached = cache.get(key)
    if cached is not None:
        decision = InflightDecision.from_json(cached, source="cached")
        decision.key = key
        _record_inflight_decision(decision)
        return decision

    if measure is None:
        if platform != "cpu":
            decision = InflightDecision(
                enabled=True, rate=0.0, source="default", key=key,
                signals={"reason": "non-cpu platform: speculation is free"},
            )
            _record_inflight_decision(decision)
            cache.put(key, decision.to_json())
            return decision
        raise ValueError(
            "calibrate_dpor_inflight: cache miss for %r on cpu and no "
            "measure given — build one with make_dpor_inflight_measure("
            "app, device_cfg, program)" % (key,)
        )
    candidates = list(axis) if axis is not None else list(DPOR_INFLIGHT_AXIS)
    start = {"dpor_inflight": candidates[0]}
    t0 = time.perf_counter()
    params, rate, rates = coordinate_descent(
        {"dpor_inflight": candidates}, measure, start,
        order=("dpor_inflight",),
    )
    enabled = bool(int(params["dpor_inflight"]))
    decision = InflightDecision(
        enabled=enabled,
        rate=rate,
        source="calibrated",
        rates=rates,
        signals={
            k: v for k, v in getattr(measure, "signals", {}).items()
            if k == f"inflight={int(enabled)}"
        },
        key=key,
        calibration_seconds=time.perf_counter() - t0,
    )
    _record_inflight_decision(decision)
    cache.put(key, decision.to_json())
    return decision


#: Host-shard candidates for the admission pipeline (fleet/shard.py):
#: how many digest-range shards the per-round scan + filter + dedup is
#: partitioned into. 1 = the sequential host half. The sweet spot is a
#: property of the host (cores, GIL pressure of the NumPy twin vs the
#: GIL-released native scan) and of the workload's rows-per-round, so
#: the decision is measured and cached per workload shape + platform.
HOST_SHARD_AXIS = (1, 2, 4)


@dataclass
class HostShardDecision:
    """One host-shard calibration outcome for a workload shape: the
    chosen shard count plus measured host-half rounds/sec per point."""

    shards: int
    rate: float  # host-half rounds/sec of the chosen point
    source: str  # "calibrated" | "cached" | "default"
    rates: Dict[str, float] = field(default_factory=dict)
    key: Optional[str] = None
    calibration_seconds: float = 0.0

    def to_json(self) -> Dict[str, Any]:
        return {
            "shards": int(self.shards),
            "rate": round(self.rate, 2),
            "source": self.source,
            "rates": {k: round(v, 2) for k, v in self.rates.items()},
            "key": self.key,
            "calibration_seconds": round(self.calibration_seconds, 2),
        }

    @classmethod
    def from_json(
        cls, obj: Dict[str, Any], source: str
    ) -> "HostShardDecision":
        return cls(
            shards=int(obj.get("shards", 1)),
            rate=float(obj.get("rate", 0.0)),
            source=source,
            rates=dict(obj.get("rates", {})),
            key=obj.get("key"),
        )


def make_host_shard_measure(
    app, device_cfg, program, *, batch: int = 16, rounds: int = 3,
    reps: int = 2, target_code: Optional[int] = None,
    prefix_fork: Optional[bool] = None,
):
    """Real measurement for one host-shard candidate: a fresh DeviceDPOR
    per rep (exploration is stateful), one warm-up round, then
    ``rounds`` timed frontier rounds under a HostHalfTimer; returns
    median host-half rounds/sec. Device time is excluded — the axis only
    moves the admission pipeline, so ranking on host seconds keeps the
    decision stable across device-speed noise. Kernels are shared across
    points/reps so the walk compiles once. ``prefix_fork`` is the
    search's own switch (the ``dpor`` verb's flag)."""
    from ..device.dpor_sweep import DeviceDPOR, make_dpor_kernel
    from ..fleet.shard import HostHalfTimer

    kernel = make_dpor_kernel(app, device_cfg)

    def measure(params: Dict[str, Any]) -> float:
        n = int(params["host_shards"])
        rates = []
        for _ in range(reps + 1):  # +1: the dropped warm-up rep
            dpor = DeviceDPOR(
                app, device_cfg, program, batch_size=batch,
                kernel=kernel, sleep_sets=False, host_shards=n,
                prefix_fork=prefix_fork,
            )
            dpor.explore(target_code=target_code, max_rounds=1)
            timer = HostHalfTimer(dpor)
            dpor.explore(target_code=target_code, max_rounds=rounds)
            rates.append(timer.rounds_per_sec())
            sharder = getattr(dpor, "_sharder", None)
            if sharder is not None:
                sharder.close()
        return median_rate(rates, drop_first=True)

    return measure


def calibrate_host_shards(
    app,
    cfg,
    *,
    batch: int,
    platform: Optional[str] = None,
    cache: Optional[TuningCache] = None,
    measure: Optional[Callable[[Dict[str, Any]], float]] = None,
    axis: Optional[Sequence[int]] = None,
    extra_key: Optional[Dict[str, Any]] = None,
) -> HostShardDecision:
    """Calibrate the admission-pipeline shard count for one workload
    shape + platform. Caching contract as ``calibrate_dpor_inflight``: a
    cache hit costs no measurements; a miss requires ``measure`` (a real
    one needs the workload's program — ``make_host_shard_measure``).
    With no measure given the decision defaults to 1 shard (the
    sequential host half — always correct, never slower than a
    mispredicted fan-out). Persisted to the TuningCache, recorded as
    ``tune.dpor.host_shards`` decisions."""
    if platform is None:
        import jax

        platform = jax.devices()[0].platform
    cache = cache or TuningCache()
    key = workload_key(
        app.name, app.num_actors, cfg, platform,
        axis="host_shards", batch=batch, **(extra_key or {}),
    )
    cached = cache.get(key)
    if cached is not None:
        decision = HostShardDecision.from_json(cached, source="cached")
        decision.key = key
        _record_host_shard_decision(decision)
        return decision

    if measure is None:
        decision = HostShardDecision(
            shards=1, rate=0.0, source="default", key=key,
        )
        _record_host_shard_decision(decision)
        return decision
    candidates = list(axis) if axis is not None else list(HOST_SHARD_AXIS)
    start = {"host_shards": candidates[0]}
    t0 = time.perf_counter()
    params, rate, rates = coordinate_descent(
        {"host_shards": candidates}, measure, start,
        order=("host_shards",),
    )
    decision = HostShardDecision(
        shards=int(params["host_shards"]),
        rate=rate,
        source="calibrated",
        rates=rates,
        key=key,
        calibration_seconds=time.perf_counter() - t0,
    )
    _record_host_shard_decision(decision)
    cache.put(key, decision.to_json())
    return decision


@dataclass
class SplitDecision:
    """One streaming budget-split calibration outcome: the minimizer's
    share of each in-flight turn (demi_tpu/pipeline/budget.py) plus the
    measured MCSes/hour per candidate."""

    split: float
    rate: float  # MCSes/hour of the chosen point (0.0 when defaulted)
    source: str  # "calibrated" | "cached" | "default"
    rates: Dict[str, float] = field(default_factory=dict)
    signals: Dict[str, Any] = field(default_factory=dict)
    key: Optional[str] = None
    calibration_seconds: float = 0.0

    def to_json(self) -> Dict[str, Any]:
        return {
            "split": float(self.split),
            "rate": round(self.rate, 3),
            "source": self.source,
            "rates": {k: round(v, 3) for k, v in self.rates.items()},
            "signals": dict(self.signals),
            "key": self.key,
            "calibration_seconds": round(self.calibration_seconds, 2),
        }

    @classmethod
    def from_json(cls, obj: Dict[str, Any], source: str) -> "SplitDecision":
        return cls(
            split=float(obj.get("split", 0.5)),
            rate=float(obj.get("rate", 0.0)),
            source=source,
            rates=dict(obj.get("rates", {})),
            signals=dict(obj.get("signals", {})),
            key=obj.get("key"),
        )


def make_pipeline_split_measure(
    app, cfg, config, program_gen, *, total_lanes: int, chunk: int,
    max_frames: Optional[int] = None, wildcards: bool = False,
    reps: int = 1,
):
    """Real measurement for one split candidate: a fresh
    ``StreamingPipeline`` per rep over the same (seed-deterministic)
    lane range, scored by MCSes/hour. Expensive relative to the other
    axes — each point runs a whole small streaming pipeline — so the
    production path prefers the cache and the bench measures at its own
    shapes; reps default to 1 with no warm-up drop (kernel compiles are
    shared across points after the first)."""
    from ..pipeline import StreamingPipeline

    def measure(params: Dict[str, Any]) -> float:
        split = float(params["pipeline_split"])
        rates = []
        for _ in range(reps):
            pipe = StreamingPipeline(
                app, cfg, config, program_gen, chunk=chunk, split=split,
                wildcards=wildcards, max_frames=max_frames,
            )
            result = pipe.run(total_lanes)
            rates.append(result.mcs_per_hour or 0.0)
        return median_rate(rates, drop_first=False)

    return measure


def calibrate_pipeline_split(
    app,
    cfg,
    *,
    platform: Optional[str] = None,
    cache: Optional[TuningCache] = None,
    measure: Optional[Callable[[Dict[str, Any]], float]] = None,
    axis: Optional[Sequence[float]] = None,
    extra_key: Optional[Dict[str, Any]] = None,
) -> SplitDecision:
    """Calibrate the streaming pipeline's fuzz/minimize budget split for
    one workload shape + platform — the knob ``LaunchBudget`` applies
    per in-flight turn. Caching contract as the other axes: a cache hit
    costs nothing; a miss with no ``measure`` records the default
    (0.5 — lane-for-lane interleave) as a decided value rather than
    guessing a measurement; a miss with a measure walks the axis by
    MCSes/hour (``make_pipeline_split_measure``). Persisted to the
    TuningCache, recorded as ``tune.pipeline.split`` decisions."""
    from ..pipeline.budget import DEFAULT_SPLIT, PIPELINE_SPLIT_AXIS

    if platform is None:
        import jax

        platform = jax.devices()[0].platform
    cache = cache or TuningCache()
    key = workload_key(
        app.name, app.num_actors, cfg, platform,
        axis="pipeline_split", **(extra_key or {}),
    )
    cached = cache.get(key)
    if cached is not None:
        decision = SplitDecision.from_json(cached, source="cached")
        decision.key = key
        _record_split_decision(decision)
        return decision
    if measure is None:
        decision = SplitDecision(
            split=DEFAULT_SPLIT, rate=0.0, source="default", key=key,
            signals={
                "reason": "no measurement available; lane-for-lane "
                          "interleave until the workload is measured"
            },
        )
        _record_split_decision(decision)
        cache.put(key, decision.to_json())
        return decision
    candidates = list(axis) if axis is not None else list(PIPELINE_SPLIT_AXIS)
    t0 = time.perf_counter()
    params, rate, rates = coordinate_descent(
        {"pipeline_split": candidates}, measure,
        {"pipeline_split": candidates[0]},
        order=("pipeline_split",),
    )
    decision = SplitDecision(
        split=float(params["pipeline_split"]),
        rate=rate,
        source="calibrated",
        rates=rates,
        key=key,
        calibration_seconds=time.perf_counter() - t0,
    )
    _record_split_decision(decision)
    cache.put(key, decision.to_json())
    return decision


def _record_split_decision(decision: SplitDecision) -> None:
    record_decision("pipeline.split", decision.split)
    record_decision("pipeline.split_rate", decision.rate)
    record_decision("pipeline.split_source", decision.source)


#: Candidate violation-bonus weights (the ExplorationController reward's
#: "one violating lane is worth this many fresh schedules" knob — 10.0
#: was hand-set in PR 2; the ROADMAP debt is measuring it).
VIOLATION_BONUS_AXIS = (2.0, 5.0, 10.0, 20.0)

#: Global TuningCache key for the measured default (workload-specific
#: keys coexist; the controller falls back to this one, then to 10.0).
VIOLATION_BONUS_DEFAULT_KEY = "axis=violation_bonus,scope=default"


@dataclass
class BonusDecision:
    """One violation-bonus calibration outcome: the chosen bonus plus
    the measured evidence (per-candidate rates — distinct violations
    per second, i.e. the inverse of time-to-Nth-distinct-violation)."""

    bonus: float
    rate: float  # distinct violations/sec of the chosen point
    source: str  # "calibrated" | "cached" | "default"
    rates: Dict[str, float] = field(default_factory=dict)
    key: Optional[str] = None
    calibration_seconds: float = 0.0

    def to_json(self) -> Dict[str, Any]:
        return {
            "bonus": float(self.bonus),
            "rate": round(self.rate, 4),
            "source": self.source,
            "rates": {k: round(v, 4) for k, v in self.rates.items()},
            "key": self.key,
            "calibration_seconds": round(self.calibration_seconds, 2),
        }

    @classmethod
    def from_json(cls, obj: Dict[str, Any], source: str) -> "BonusDecision":
        return cls(
            bonus=float(obj.get("bonus", 10.0)),
            rate=float(obj.get("rate", 0.0)),
            source=source,
            rates=dict(obj.get("rates", {})),
            key=obj.get("key"),
        )


def default_violation_bonus(cache: Optional[TuningCache] = None) -> float:
    """The persisted violation-bonus default (10.0 when never measured)
    — what ExplorationController reads when built without an explicit
    bonus. One cached-file read; corrupt/absent caches degrade to the
    hand-set PR 2 value."""
    cache = cache or TuningCache()
    cached = cache.get(VIOLATION_BONUS_DEFAULT_KEY)
    if cached is not None:
        try:
            return float(cached.get("bonus", 10.0))
        except (TypeError, ValueError):
            return 10.0
    return 10.0


def make_bonus_measure(
    fuzzer_factory: Callable[[int], Any],
    config_factory: Callable[[], Any],
    *, seeds: int = 3, target_distinct: int = 2,
    max_executions: int = 120, max_messages: int = 300,
    timeout_seconds: float = 60.0,
):
    """Real measurement for one violation-bonus candidate: run the
    autotuned host fuzzer (WeightTuner-driven, reward shaped by the
    candidate bonus) until ``target_distinct`` DISTINCT violations are
    found (by violation identity), per seed; score = distinct
    violations per second, medianed across seeds with the warm-up seed
    dropped. The time-to-Nth-distinct-violation metric the ROADMAP
    names is exactly the reciprocal of the reported rate.
    ``fuzzer_factory(seed)`` builds a fresh Fuzzer (weights reset per
    candidate — the tuner must re-learn under each bonus);
    ``config_factory()`` a fresh SchedulerConfig."""
    import time as _time

    def measure(params: Dict[str, Any]) -> float:
        bonus = float(params["violation_bonus"])
        from ..schedulers import RandomScheduler
        from .controller import ExplorationController, WeightTuner

        rates = []
        for seed in range(seeds):
            fuzzer = fuzzer_factory(seed)
            config = config_factory()
            controller = ExplorationController(
                fuzzer=fuzzer,
                weight_tuner=WeightTuner(fuzzer.weights.as_dict()),
                violation_bonus=bonus,
            )
            distinct = set()
            t0 = _time.perf_counter()
            rng_seed = seed * 1000
            for i in range(max_executions):
                if _time.perf_counter() - t0 > timeout_seconds:
                    break
                controller.begin_round()
                program = fuzzer.generate_fuzz_test(seed=rng_seed + i)
                result = RandomScheduler(
                    config, seed=rng_seed + i, max_messages=max_messages,
                    invariant_check_interval=1,
                ).execute(program)
                violations = 0
                if result.violation is not None:
                    violations = 1
                    distinct.add(repr(result.violation))
                controller.end_round(
                    hashes=[hash(tuple(
                        (u.event.__class__.__name__, getattr(u.event, "rcv", ""))
                        for u in result.trace.events[:64]
                    ))],
                    violations=violations,
                    lanes=1,
                )
                if len(distinct) >= target_distinct:
                    break
            secs = _time.perf_counter() - t0
            rates.append(len(distinct) / secs if secs > 0 else 0.0)
        return median_rate(rates, drop_first=True)

    return measure


def calibrate_weight_bonus(
    *,
    cache: Optional[TuningCache] = None,
    measure: Optional[Callable[[Dict[str, Any]], float]] = None,
    axis: Optional[Sequence[float]] = None,
    key: Optional[str] = None,
    persist_default: bool = True,
) -> BonusDecision:
    """Calibrate the WeightTuner reward's violation bonus against
    time-to-Nth-distinct-violation (ROADMAP debt: the 10x was hand-set).
    Caching contract as the other axes: a cache hit costs no
    measurements; a miss walks ``VIOLATION_BONUS_AXIS`` with the
    injectable ``measure`` (``make_bonus_measure`` builds a real one
    over the raft/broadcast fixtures; tests inject synthetic tables).
    The winner persists under ``key`` (default: the global default key
    the ExplorationController reads) and — with ``persist_default`` —
    under ``VIOLATION_BONUS_DEFAULT_KEY`` too, recorded as
    ``tune.fuzz.violation_bonus``."""
    cache = cache or TuningCache()
    key = key or VIOLATION_BONUS_DEFAULT_KEY
    cached = cache.get(key)
    if cached is not None:
        decision = BonusDecision.from_json(cached, source="cached")
        decision.key = key
        record_decision("fuzz.violation_bonus", decision.bonus)
        return decision
    if measure is None:
        raise ValueError(
            "calibrate_weight_bonus: cache miss for %r and no measure "
            "given — build one with make_bonus_measure(...)" % (key,)
        )
    candidates = list(axis) if axis is not None else list(VIOLATION_BONUS_AXIS)
    start = {"violation_bonus": candidates[0]}
    t0 = time.perf_counter()
    params, rate, rates = coordinate_descent(
        {"violation_bonus": candidates}, measure, start,
        order=("violation_bonus",),
    )
    decision = BonusDecision(
        bonus=float(params["violation_bonus"]),
        rate=rate,
        source="calibrated",
        rates=rates,
        key=key,
        calibration_seconds=time.perf_counter() - t0,
    )
    record_decision("fuzz.violation_bonus", decision.bonus)
    cache.put(key, decision.to_json())
    if persist_default and key != VIOLATION_BONUS_DEFAULT_KEY:
        cache.put(VIOLATION_BONUS_DEFAULT_KEY, decision.to_json())
    return decision


def _record_inflight_decision(decision: InflightDecision) -> None:
    record_decision("dpor.inflight", int(decision.enabled))
    record_decision("dpor.inflight_rate", decision.rate)
    record_decision("dpor.inflight_source", decision.source)


def _record_host_shard_decision(decision: HostShardDecision) -> None:
    record_decision("dpor.host_shards", int(decision.shards))
    record_decision("dpor.host_shards_rate", decision.rate)
    record_decision("dpor.host_shards_source", decision.source)


def _record_fork_decision(decision: ForkDecision) -> None:
    record_decision("fork.bucket", int(decision.bucket))
    record_decision("fork.enabled", int(decision.enabled))
    record_decision("fork.rate", decision.rate)
    record_decision("fork.source", decision.source)


def _record_sweep_decision(decision: SweepDecision) -> None:
    record_decision("sweep.variant", decision.params.get("variant", "xla"))
    for knob in ("chunk", "seg"):
        if knob in decision.params:
            record_decision(f"sweep.{knob}", int(decision.params[knob]))
    record_decision("sweep.rate", decision.rate)
    record_decision("sweep.source", decision.source)
