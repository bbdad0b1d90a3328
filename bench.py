"""Benchmark: unique schedules explored per second per chip.

Prints ONE JSON line. Required keys (driver contract):
  {"metric", "value", "unit", "vs_baseline"}
Extra keys reported for the record:
  - host_schedules_per_sec: the host-tier Python RandomScheduler on the
    SAME 5-node raft program. The JVM reference cannot run in this image
    (BASELINE.md), so host-Python is the measured stand-in denominator for
    the "≥100x the sequential baseline" claim.
  - device_vs_host: value / host_schedules_per_sec.
  - time_to_first_violation_s: wall-clock for the device sweep to find the
    first violation on the unreliable-broadcast fixture (BASELINE.md's
    other headline metric).
  - config2: BASELINE config 2 — DeviceDPOR frontier search on a 3-node
    raft app (interleavings/sec over timed frontier rounds).
  - config3: BASELINE config 3 — batched DDMin replay oracle on the
    unreliable-broadcast fixture (oracle replays/sec; the fuzz that
    produces the violation to minimize is untimed).
  - config4: BASELINE config 4 — Spark DAGScheduler fuzz sweep with the
    job-completion invariant on the seeded stale_task bug
    (schedules/sec + violations found).
  - config6: prefix-fork vs scratch replay-trial throughput on a deep
    raft internal-minimization level (fork speedup, prefix-hit rate,
    steps_saved; DEMI_PREFIX_FORK-independent — both paths are measured).
  - config7: async minimization pipeline vs the synchronous oracle —
    end-to-end wall clock of a deep raft ddmin+internal minimization
    (speedup, speculation hits/waste, lowering-cache hit rate, overlap
    fraction; DEMI_ASYNC_MIN-independent — both paths are measured, and
    verdicts_match / mcs_match pin bit-exactness).
  - config8: async DPOR frontier throughput — double-buffered in-flight
    rounds + prefix forking with prescribed-resume trunks vs the
    synchronous scratch loop on the config-2 raft fixture (frontier
    rounds/sec + speedup; explored_match / frontier_match /
    interleavings_match pin that the async pipeline explores the EXACT
    same schedule space). Also measures the host-vs-device wall split
    (host_share target < 25% async-on).
  - config9: redundancy-ratio A/B — sleep-set + race-reversal DPOR
    (wakeup-sequence guides, device-encoded sleep rows, Mazurkiewicz
    class dedup) vs the observe-only baseline on the config-8 deep
    seeded raft frontier: explored schedules vs. the distinct-class
    optimal lower bound (redundancy ratio), violation set and first
    found records asserted bit-identical, rounds/sec for both sides.
  - config10: durability — checkpoint overhead % (atomic snapshot
    generations written every --checkpoint-every rounds vs the plain
    single-round loop; target < 5% of round wall time) and cold
    time-to-resume on the config-9 seeded raft frontier, restore
    asserted bit-identical to the writer's final state.
  - config11: continuous observability — round-journal + per-round
    time-series overhead % vs the unjournaled loop on the config-9
    seeded raft frontier (target < 1% of round wall — the always-on
    bar), with journal round-contiguity, record schema, time-series
    sample count, and Prometheus exposition asserted.
  - config12: streaming pipeline — time-to-first-MCS and MCSes/hour,
    streaming fuzz→minimize→replay (demi_tpu/pipeline/: violation
    lanes hand off to the minimizer while the sweep keeps running, one
    shared in-flight launch budget) vs the staged tiers on a
    multi-violation raft fixture; MCS artifact + violation-code sets
    asserted bit-identical and the journal tiers interleaved. Target
    >= 1.3x MCSes/hour in the disjoint-host/device (TPU) regime;
    shared-core CPU measures ~1.1-1.2x (~1.2-1.3x ttf-MCS).
  - config5: BASELINE config 5 — 64-actor reliable broadcast sweep
    (schedules/sec + lanes swept; 1M lanes on TPU, smaller on CPU
    fallback; override with DEMI_BENCH_CONFIG5_LANES). Runs in
    round-delivery mode by default (identical invariant semantics for
    this workload — checks only at quiescence; ~6x on CPU);
    DEMI_BENCH_CONFIG5_MODE=seq forces the sequential kernel for
    comparison with pre-round-5 numbers.
  - platform: the JAX platform the numbers were measured on.

Modes: `python bench.py` runs everything; `--config 2` / `--config 3` /
`--config 4` / `--config 5` / `--config 6` / `--config 7` /
`--config 8` / `--config 9` / `--config 10` / `--config 11` /
`--config 12` / `--config 13` / `--config 14` / `--config 15` /
`--config 16` / `--config 17` / `--config rehearsal` run a single
section (same one-line JSON with that key populated). Config 16 A/Bs
the digest-range-sharded coordinator host half (fleet/shard.py) at
1/2/4 admission shards, asserting bit-identity at every point. Config
17 measures differential exploration (analysis/delta.py): after a
one-handler edit, re-verification re-explores only the change cone
(>=3x fewer classes than scratch), with violations and the full-scratch
audit bit-identical.

DEMI_AUTOTUNE=1 lets the measurement-guided tuner (demi_tpu/tune) pick
the rehearsal drive's (kernel variant, batch, segment) from short
calibration reps, persisted to the tuning cache; the decision is
reported under config5_rehearsal.autotune. With it unset, output keys
match the untuned bench exactly.
"""

import argparse
import json
import os
import sys
import time

import numpy as np


def _raft_workload():
    from demi_tpu.apps.common import dsl_start_events
    from demi_tpu.apps.raft import T_CLIENT, make_raft_app
    from demi_tpu.external_events import MessageConstructor, Send, WaitQuiescence

    app = make_raft_app(5)

    def cmd(node, v):
        return Send(
            app.actor_name(node),
            MessageConstructor(lambda vv=v: (T_CLIENT, 0, vv, 0, 0, 0, 0)),
        )

    program = dsl_start_events(app) + [
        cmd(0, 10), cmd(1, 11), cmd(2, 12), WaitQuiescence(budget=60),
        cmd(3, 20), cmd(4, 21), WaitQuiescence(budget=60),
    ]
    return app, program


def bench_device_raft(jax):
    """Device explore throughput on the 5-node raft workload.

    Variants are measured INTERLEAVED (round-robin over reps) so slow
    machine-state drift — allocator warm-up, clock scaling — lands on
    every variant equally; round-3's first-measured-variant penalty was
    ~15%, larger than most lever effects. Per-variant value = unique
    schedules / total measured seconds; rep_spread reports each
    variant's (min, median, max) raw lanes/sec across reps so the reader
    can tell signal from noise (VERDICT r3 weak #7).

    DEMI_BENCH_IMPL forces a single variant: xla | xla-trailing |
    xla-trailing-ee | xla-round-ee | xla-trailing-round-ee ('-ee' =
    early-exit while_loop instead of the fixed-length scan; '-round' = round-delivery mode,
    whose invariant checks are round-granularity — such variants are
    excluded from the per-delivery headline and summarized under
    "round", unless forced alone, which relabels the metric)."""
    from demi_tpu.device import DeviceConfig
    from demi_tpu.device.core import ST_OVERFLOW
    from demi_tpu.device.encoding import lower_program, stack_programs
    from demi_tpu.device.explore import make_explore_kernel_variant

    app, program = _raft_workload()
    # Step budget: 12 injection ops + 2 x 60-delivery wait budgets + slack.
    # Pool 96: step cost is ~linear in pool_capacity and this workload's
    # peak pending stays well under 64 (0 overflow lanes in 5k-lane
    # sweeps at capacity 64); 96 keeps margin.
    cfg = DeviceConfig.for_app(
        app, pool_capacity=96, max_steps=144, max_external_ops=24,
        invariant_interval=1, timer_weight=0.2,
        msg_dtype=os.environ.get("DEMI_BENCH_MSG_DTYPE", "int32"),
    )
    platform = jax.devices()[0].platform
    default_batch = 8192 if platform not in ("cpu",) else 1024
    batch = int(os.environ.get("DEMI_BENCH_BATCH", default_batch))
    progs = stack_programs([lower_program(app, cfg, program)] * batch)

    impl = os.environ.get("DEMI_BENCH_IMPL")
    # Default: measure the whole layout/loop family; headline = the best.
    impls = [impl] if impl else [
        "xla", "xla-trailing", "xla-trailing-ee",
        "xla-round-ee", "xla-trailing-round-ee",
    ]

    def build(name):
        # Round-delivery variants check the invariant at round (not
        # delivery) granularity — reported separately, never as the
        # per-delivery headline (see `round` in the output). The variant
        # grammar itself lives in device/explore.py, shared with the
        # autotuner's calibration so bench and tuner measure the same
        # kernels by the same names.
        return make_explore_kernel_variant(app, cfg, name)

    kernels = {}
    for name in impls:
        try:
            kernel = build(name)
            jax.block_until_ready(
                kernel(progs, jax.random.split(jax.random.PRNGKey(0), batch))
            )
            kernels[name] = kernel
        except Exception as e:  # pragma: no cover - accelerator-dependent
            # A lowering gap on real hardware must not cost the whole
            # benchmark run; record the failure and keep the other
            # variants' numbers.
            kernels[name] = None
            print(f"# bench: {name} backend failed: {e!r}", file=sys.stderr)
    ok_names = [n for n, k in kernels.items() if k is not None]
    if not ok_names:
        raise RuntimeError(
            f"every benchmark backend failed on {platform}: {list(kernels)}"
        )

    reps = int(os.environ.get("DEMI_BENCH_REPS", 5))
    # reps+1 measured passes per variant; the FIRST is a warm-up whose
    # timing and hashes are dropped from every per_impl number. The
    # build-time launch above compiles, but the first timed rep still
    # lands allocator/cache warm-up — r5's ±15% rep spread was dominated
    # by it, too noisy for the autotuner's impl-selection signal.
    rates = {n: [] for n in ok_names}
    dts = {n: [] for n in ok_names}
    hashes = {n: [] for n in ok_names}
    for rep in range(reps + 1):
        keys_r = jax.random.split(jax.random.PRNGKey(rep + 1), batch)
        for name in list(ok_names):
            try:
                t0 = time.perf_counter()
                res = kernels[name](progs, keys_r)
                jax.block_until_ready(res)
                dt = time.perf_counter() - t0
                # Dedup by the device-side schedule fingerprint: "unique
                # schedules explored" per BASELINE.json, not lanes swept.
                # Overflowed lanes' truncated fingerprints are excluded.
                h = np.asarray(res.sched_hash)[
                    np.asarray(res.status) != ST_OVERFLOW
                ]
            except Exception as e:  # pragma: no cover - device-dependent
                # A mid-rep runtime failure (transient device error, OOM)
                # must not cost the whole benchmark run on a scarce TPU
                # window; drop this variant, keep the others.
                kernels[name] = None
                ok_names.remove(name)
                print(f"# bench: {name} rep {rep} failed: {e!r}",
                      file=sys.stderr)
                continue
            rates[name].append(batch / dt)
            dts[name].append(dt)
            hashes[name].append(h)
    if not ok_names:
        raise RuntimeError(
            f"every benchmark backend failed mid-measurement on {platform}"
        )

    def _measured(seq):
        """Drop the warm-up rep (kept only when it's all we have)."""
        return seq[1:] if len(seq) > 1 else seq

    per_impl, per_impl_raw, spread = {}, {}, {}
    uniq_rate_exact = {}
    for name in kernels:
        if kernels[name] is None or not rates[name]:
            per_impl[name] = per_impl_raw[name] = spread[name] = None
            continue
        m_hashes = _measured(hashes[name])
        m_rates = _measured(rates[name])
        uniq = int(np.unique(np.concatenate(m_hashes)).size)
        uniq_rate_exact[name] = uniq / sum(_measured(dts[name]))
        per_impl[name] = round(uniq_rate_exact[name], 1)
        rs = sorted(m_rates)
        per_impl_raw[name] = round(rs[len(rs) // 2], 1)  # median
        spread[name] = [
            round(rs[0], 1), round(rs[len(rs) // 2], 1), round(rs[-1], 1)
        ]
    # Headline = best variant with per-delivery invariant checks; the
    # round-delivery variants (coarser, round-granularity checks) are
    # summarized separately so the metric name stays truthful.
    seq_rates = {
        n: r for n, r in uniq_rate_exact.items() if "-round" not in n
    }
    rnd_rates = {n: r for n, r in uniq_rate_exact.items() if "-round" in n}
    headline_granularity = "per-delivery"
    if not seq_rates:  # every per-delivery variant failed on this backend
        seq_rates = rnd_rates
        headline_granularity = "round"
    best = max(seq_rates, key=seq_rates.get)
    uniq_rate = per_impl[best]
    # Exact duplicate fraction over the best variant's measured lanes
    # (per-rep rate variance must not leak into this metric).
    best_uniq = int(np.unique(np.concatenate(_measured(hashes[best]))).size)
    best_lanes = len(_measured(rates[best])) * batch
    extra = {
        "per_impl": per_impl,
        "per_impl_raw_lanes_per_sec": per_impl_raw,
        # (min, median, max) raw lanes/sec over the measured reps (the
        # extra first warm-up rep is excluded from every number here).
        "per_impl_rep_spread": spread,
        "reps": reps,
        "raw_lanes_per_sec": per_impl_raw[best],
        "unique_fraction": round(best_uniq / best_lanes, 4),
        "impl": best,
        # "round" here = the headline number itself came from a
        # round-granularity variant (only when no per-delivery variant
        # produced a result) — main() relabels the metric string then.
        "headline_invariant_granularity": headline_granularity,
    }
    if rnd_rates:
        rbest = max(rnd_rates, key=rnd_rates.get)
        extra["round"] = {
            "value": per_impl[rbest],
            "impl": rbest,
            "invariant_granularity": "round",
        }
    return uniq_rate, extra


def bench_host_raft(budget_s: float = 6.0):
    """Host-tier Python RandomScheduler on the same raft program — the
    measured stand-in for the JVM denominator (BASELINE.md:31-33)."""
    from demi_tpu.apps.common import make_host_invariant
    from demi_tpu.config import SchedulerConfig
    from demi_tpu.schedulers import RandomScheduler

    app, program = _raft_workload()
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    sched = RandomScheduler(
        config, seed=0, max_messages=132, invariant_check_interval=1,
        timer_weight=0.2,
    )
    n = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < budget_s:
        sched.seed = n
        sched.execute(program)
        n += 1
    return n / (time.perf_counter() - t0)


def bench_time_to_first_violation(jax):
    """Device sweep wall-clock to the first violation (unreliable
    broadcast, fuzzed programs) — BASELINE.md headline #2."""
    from demi_tpu.apps.broadcast import broadcast_send_generator, make_broadcast_app
    from demi_tpu.apps.common import dsl_start_events
    from demi_tpu.device import DeviceConfig
    from demi_tpu.fuzzing import Fuzzer, FuzzerWeights
    from demi_tpu.parallel.sweep import SweepDriver

    app = make_broadcast_app(4, reliable=False)
    cfg = DeviceConfig.for_app(
        app, pool_capacity=64, max_steps=96, max_external_ops=24,
        early_exit=True,  # fuzzed lanes quiesce far below the step cap
    )
    fuzzer = Fuzzer(
        num_events=10,
        weights=FuzzerWeights(kill=0.05, send=0.6, wait_quiescence=0.15),
        message_gen=broadcast_send_generator(app),
        prefix=dsl_start_events(app),
        max_kills=1,
    )
    driver = SweepDriver(app, cfg, lambda s: fuzzer.generate_fuzz_test(seed=s))
    chunk = 256
    # Warm-up: compile the continuous-sweep kernels outside the timed
    # window (sweep() defaults to lane-compacted continuous mode).
    driver.sweep(chunk, chunk)
    # The sweep itself is deterministic after warm-up, so reps measure
    # pure timing noise; report the median (r3 runs drifted 0.1-0.5s on
    # CPU for the same work — VERDICT r3 weak #7).
    times = []
    for _ in range(3):
        secs, result = driver.time_to_first_violation(chunk_size=chunk)
        if secs is None:
            return None
        times.append(secs)
    return sorted(times)[1]


def bench_config4(jax):
    """BASELINE config 4: Spark DAGScheduler fuzz, job-completion
    invariant — device sweep throughput + violation count on the seeded
    stale_task bug."""
    from demi_tpu.apps.common import dsl_start_events
    from demi_tpu.apps.spark_dag import T_SUBMIT, make_spark_app
    from demi_tpu.device import DeviceConfig, make_explore_kernel
    from demi_tpu.device.encoding import lower_program, stack_programs
    from demi_tpu.external_events import MessageConstructor, Send, WaitQuiescence

    app = make_spark_app(
        num_workers=3, num_stages=2, tasks_per_stage=4, bug="stale_task"
    )
    cfg = DeviceConfig.for_app(
        app, pool_capacity=128, max_steps=200, max_external_ops=8,
        invariant_interval=1, early_exit=True,
    )
    program = dsl_start_events(app) + [
        Send(app.actor_name(0), MessageConstructor(lambda: (T_SUBMIT, 0, 0))),
        WaitQuiescence(),
    ]
    platform = jax.devices()[0].platform
    batch = 2048 if platform not in ("cpu",) else 256
    kernel = make_explore_kernel(app, cfg)
    progs = stack_programs([lower_program(app, cfg, program)] * batch)
    warm = kernel(progs, jax.random.split(jax.random.PRNGKey(99), batch))
    jax.block_until_ready(warm)  # async dispatch must not leak into timing
    t0 = time.perf_counter()
    res = kernel(progs, jax.random.split(jax.random.PRNGKey(0), batch))
    violations = int((np.asarray(res.violation) != 0).sum())
    secs = time.perf_counter() - t0
    from demi_tpu.device.core import ST_OVERFLOW

    return {
        "lanes": batch,
        "schedules_per_sec": round(batch / secs, 1),
        "unique_schedules": int(
            np.unique(
                np.asarray(res.sched_hash)[np.asarray(res.status) != ST_OVERFLOW]
            ).size
        ),
        "violations": violations,
        # Overflowed lanes completed no verdict; nonzero means the numbers
        # above undercount (same signal bench_config5 reports).
        "overflow_lanes": int((np.asarray(res.status) == ST_OVERFLOW).sum()),
    }


def _static_prune_ab(app, cfg, program, batch, rounds, kernel, presc=None):
    """Static-commutativity A/B on one DPOR fixture (configs 2/8): run
    the identical frontier search with the static relation disabled vs
    enabled (audit mode, so every pruned prescription is materialized)
    and assert that pruning only removed true no-ops:

      - interleavings bit-identical (pruned entries are leaves the
        deepest-first selection never reached, so round batches match);
      - the pruned run's explored set / frontier are the unpruned run's
        MINUS exactly (a subset of) the audited no-op prescriptions —
        nothing else may move.

    Returns the static_pruned counts for the bench JSON, next to the
    redundant/distance-pruned numbers the obs counters carry."""
    from demi_tpu.analysis import StaticIndependence
    from demi_tpu.device.dpor_sweep import DeviceDPOR

    def run(rel):
        d = DeviceDPOR(
            app, cfg, program, batch_size=batch, prefix_fork=False,
            double_buffer=False, kernel=kernel,
            static_independence=rel if rel is not None else False,
            sleep_sets=False,  # the shared kernel is a plain one
        )
        if presc is not None:
            d.seed(presc)
        d.explore(max_rounds=rounds)
        return d

    base = run(None)
    rel = StaticIndependence.for_app(app, audit=True)
    pruned = run(rel)
    pruned_set = set(rel.pruned_prescriptions)
    assert base.interleavings == pruned.interleavings, (
        base.interleavings, pruned.interleavings
    )
    extra = pruned.explored - base.explored
    removed = base.explored - pruned.explored
    assert not extra, f"static pruning ADDED {len(extra)} prescriptions"
    assert removed <= pruned_set, (
        "static pruning removed a prescription it cannot prove no-op"
    )
    f_removed = set(base.frontier) - set(pruned.frontier)
    f_extra = set(pruned.frontier) - set(base.frontier)
    assert f_removed <= pruned_set and not f_extra
    return {
        "static_pruned": dict(rel.pruned_total),
        "explored_without": len(base.explored),
        "explored_with": len(pruned.explored),
        "removed_prescriptions": len(removed),
        "interleavings_match": True,
        "noop_only": True,
        "commuting_tag_pairs": rel.summary().get("commuting_tag_pairs"),
    }


def bench_config2(jax):
    """BASELINE config 2: DeviceDPOR frontier search on a raft-class app —
    systematic batched backtracking, measured as interleavings/sec over
    timed frontier rounds (warm-up round excluded: it carries kernel
    compilation and the initial frontier seeding)."""
    from demi_tpu.apps.common import dsl_start_events
    from demi_tpu.apps.raft import T_CLIENT, make_raft_app
    from demi_tpu.device import DeviceConfig
    from demi_tpu.device.dpor_sweep import DeviceDPOR
    from demi_tpu.external_events import MessageConstructor, Send, WaitQuiescence

    app = make_raft_app(3)
    cfg = DeviceConfig.for_app(
        app, pool_capacity=96, max_steps=96, max_external_ops=16,
        invariant_interval=1, record_trace=True, record_parents=True,
    )
    # Two racing client commands: enough concurrent deliveries that the
    # racing-pair scan keeps the frontier fed across rounds.
    program = dsl_start_events(app) + [
        Send(app.actor_name(0),
             MessageConstructor(lambda: (T_CLIENT, 0, 7, 0, 0, 0, 0))),
        Send(app.actor_name(1),
             MessageConstructor(lambda: (T_CLIENT, 0, 8, 0, 0, 0, 0))),
        WaitQuiescence(),
    ]
    platform = jax.devices()[0].platform
    batch = 64 if platform not in ("cpu",) else 16
    rounds = int(os.environ.get("DEMI_BENCH_DPOR_ROUNDS", 4))
    dpor = DeviceDPOR(app, cfg, program, batch_size=batch)
    dpor.explore(max_rounds=1)  # warm-up: compile + seed the frontier
    # Host-share ledger starts AFTER the warm-up (kernel compilation
    # lands in the dispatch path and would read as host time).
    dpor.host_seconds = dpor.device_seconds = 0.0
    before = dpor.interleavings
    t0 = time.perf_counter()
    dpor.explore(max_rounds=rounds)
    secs = time.perf_counter() - t0
    measured = dpor.interleavings - before
    share = dpor.host_share
    # Static-commutativity A/B (disabled vs enabled, no-op-only
    # asserted) on the same fixture + compiled kernel.
    static = _static_prune_ab(
        app, cfg, program, batch,
        rounds=int(os.environ.get("DEMI_BENCH_STATIC_ROUNDS", 2)),
        kernel=dpor.kernel,
    )
    return {
        "app": "raft3",
        "batch": batch,
        "rounds": rounds,
        "static": static,
        "interleavings": dpor.interleavings,
        "interleavings_per_sec": round(measured / secs, 1) if secs > 0 else None,
        "frontier": len(dpor.frontier),
        "explored": len(dpor.explored),
        "seconds": round(secs, 2),
        # Host-vs-device wall split of the timed frontier rounds (the
        # vectorized-host-path health number).
        "host_seconds": round(dpor.host_seconds, 3),
        "device_seconds": round(dpor.device_seconds, 3),
        "host_share": round(share, 3) if share is not None else None,
        "device_share": round(1 - share, 3) if share is not None else None,
    }


def bench_config3(jax):
    """BASELINE config 3: the batched DDMin replay oracle — fuzz a
    violation on the unreliable-broadcast fixture (host tier, untimed),
    then time BatchedDDMin minimizing it with every level's candidates
    replayed as one device batch. Throughput = oracle replays/sec (the
    number the device-batched trials exist to maximize)."""
    from demi_tpu.apps.broadcast import broadcast_send_generator, make_broadcast_app
    from demi_tpu.apps.common import dsl_start_events, make_host_invariant
    from demi_tpu.config import SchedulerConfig
    from demi_tpu.device.batch_oracle import (
        DeviceReplayChecker,
        DeviceSTSOracle,
        default_device_config,
    )
    from demi_tpu.fuzzing import Fuzzer, FuzzerWeights
    from demi_tpu.minimization.ddmin import BatchedDDMin, make_dag
    from demi_tpu.minimization.stats import MinimizationStats
    from demi_tpu.runner import fuzz as host_fuzz

    app = make_broadcast_app(4, reliable=False)
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    fuzzer = Fuzzer(
        num_events=12,
        weights=FuzzerWeights(kill=0.05, send=0.6, wait_quiescence=0.15),
        message_gen=broadcast_send_generator(app),
        prefix=dsl_start_events(app),
        max_kills=1,
    )
    fr = host_fuzz(
        config, fuzzer, max_executions=200, seed=0, max_messages=400,
        invariant_check_interval=1, timer_weight=0.2, validate_replay=True,
    )
    if fr is None:  # pragma: no cover - fixture reliably violates
        return {"error": "no violation found to minimize"}
    device_cfg = default_device_config(app, fr.trace, fr.program)
    checker = DeviceReplayChecker(app, device_cfg, config)
    oracle = DeviceSTSOracle(
        app, device_cfg, config, fr.trace, checker=checker
    )
    # Warm-up: one single-candidate batch compiles the replay kernel for
    # the static record shape every level reuses.
    oracle.test_batch([list(fr.program)], fr.violation)
    stats = MinimizationStats()
    ddmin = BatchedDDMin(oracle, stats=stats)
    t0 = time.perf_counter()
    mcs = ddmin.minimize(make_dag(list(fr.program)), fr.violation)
    secs = time.perf_counter() - t0
    replays = stats.total_replays
    return {
        "app": "broadcast4-unreliable",
        "externals": len(fr.program),
        "mcs_externals": len(mcs.get_all_events()),
        "ddmin_levels": ddmin.levels,
        "replays": replays,
        "replays_per_sec": round(replays / secs, 1) if secs > 0 else None,
        "seconds": round(secs, 2),
    }


def bench_config5(jax, total_lanes=None):
    """BASELINE config 5: 64-actor reliable broadcast schedule sweep.
    Since PR 31 the deployment is a configuration of the benchmark
    (benchmarks/configs/bcast64-flood.json, cell bcast64-flood-sweep, on
    the CLI's normal path); this hand-built variant matrix is read by
    nothing there (its deletion is ROADMAP C3's)."""
    from demi_tpu.apps.broadcast import make_broadcast_app
    from demi_tpu.apps.common import dsl_start_events
    from demi_tpu.device import DeviceConfig
    from demi_tpu.external_events import (
        Kill,
        MessageConstructor,
        Send,
        WaitQuiescence,
    )
    from demi_tpu.parallel.sweep import SweepDriver

    n = 64
    app = make_broadcast_app(n, reliable=True)
    # Round-delivery mode by default (DEMI_BENCH_CONFIG5_MODE=seq forces
    # the sequential kernel): with invariant_interval=0 the agreement
    # check runs only at quiescence in BOTH modes, so round mode is
    # apples-to-apples here — same programs, same verdicts, same unique-
    # schedule accounting — at ~1/30th the steps (one round delivers up
    # to one message per receiver; the flood is ~4.5k deliveries/lane).
    mode = os.environ.get("DEMI_BENCH_CONFIG5_MODE", "round")
    if mode not in ("seq", "round"):
        raise ValueError(
            f"DEMI_BENCH_CONFIG5_MODE must be 'seq' or 'round', got {mode!r}"
        )
    # Reliable broadcast floods n*(n-1) relays; pool must hold the peak.
    cfg = DeviceConfig.for_app(
        app,
        pool_capacity=4608,
        max_steps=4608 if mode == "seq" else 224,
        max_external_ops=80,
        invariant_interval=0,  # agreement holds only at quiescence
        early_exit=True,  # the flood quiesces below the step cap
        round_delivery=(mode != "seq"),
    )
    starts = dsl_start_events(app)

    def program_gen(seed):
        # One broadcast; every 3rd schedule also kills a fuzzed receiver
        # mid-flood (exercises the kill/agreement interplay at scale).
        prog = list(starts) + [
            Send(app.actor_name(seed % n),
                 MessageConstructor(lambda: (1, 0))),
        ]
        if seed % 3 == 0:
            prog.append(Kill(app.actor_name((seed + 1) % n)))
        prog.append(WaitQuiescence())
        return prog

    platform = jax.devices()[0].platform
    if total_lanes is None:
        # CPU fallback sizing: sequential runs ~2-3 lanes/sec (4608 steps
        # x 4608-slot pool per lane); round mode ~25-30/sec. The 1M-lane
        # sweep is a TPU workload either way.
        if platform not in ("cpu",):
            default = 1_000_000
        else:
            default = 256 if mode != "seq" else 64
        total_lanes = int(os.environ.get("DEMI_BENCH_CONFIG5_LANES", default))
    chunk = min(2048 if platform not in ("cpu",) else 32, total_lanes)
    driver = SweepDriver(app, cfg, program_gen)
    driver.sweep(chunk, chunk)  # compile (continuous kernels) outside timing
    # Host-share ledger starts after the compile sweep.
    driver.host_seconds = driver.device_seconds = 0.0
    result = driver.sweep(total_lanes, chunk)
    overflow_lanes = sum(c.overflow_lanes for c in result.chunks)
    share = driver.host_share
    return {
        "actors": n,
        "mode": mode,
        "lanes": result.lanes,
        # Driver-recorded wall clock: per-chunk seconds overlap under
        # async dispatch, so the summed-seconds rate would overstate.
        "schedules_per_sec": round(result.schedules_per_sec_wall, 1),
        "unique_schedules": result.unique_schedules,
        "violations": result.violations,
        "seconds": round(result.wall_seconds, 2),
        "overflow_lanes": overflow_lanes,
        "occupancy": (
            round(result.occupancy, 3) if result.occupancy else None
        ),
        # Host-vs-device wall split of the measured sweep (continuous
        # mode splits exactly at the per-segment status sync).
        "host_seconds": round(driver.host_seconds, 3),
        "device_seconds": round(driver.device_seconds, 3),
        "host_share": round(share, 3) if share is not None else None,
        "device_share": round(1 - share, 3) if share is not None else None,
    }


def bench_config6(jax):
    """Config 6: prefix-fork vs scratch trial throughput on a deep raft
    internal-minimization level. The level's candidates (each omitting one
    delivery from a recorded schedule) are identical up to the first
    removed index — the prefix-fork sweet spot: the shared prefix replays
    ONCE per first-divergence bucket on a trunk lane and the candidates
    fork from the snapshot (device/fork.py). Scratch and fork verdicts are
    bit-identical; the section reports the throughput ratio, prefix-hit
    rate, and steps_saved. Depth/size knobs: DEMI_BENCH_CONFIG6_NODES /
    _COMMANDS / _BUDGET / _CANDIDATES / _REPS."""
    from demi_tpu.apps.common import dsl_start_events, make_host_invariant
    from demi_tpu.apps.raft import T_CLIENT, make_raft_app
    from demi_tpu.config import SchedulerConfig
    from demi_tpu.device.batch_oracle import (
        DeviceReplayChecker,
        default_device_config,
    )
    from demi_tpu.external_events import MessageConstructor, Send, WaitQuiescence
    from demi_tpu.minimization.internal import (
        removable_delivery_indices,
        remove_delivery,
    )
    from demi_tpu.schedulers import RandomScheduler

    nodes = int(os.environ.get("DEMI_BENCH_CONFIG6_NODES", 3))
    commands = int(os.environ.get("DEMI_BENCH_CONFIG6_COMMANDS", 3))
    # Depth default measured on CPU: 192 deliveries -> ~1.85x fork
    # speedup (the win grows with prefix length; 64 -> only ~1.3x).
    budget = int(os.environ.get("DEMI_BENCH_CONFIG6_BUDGET", 192))
    app = make_raft_app(nodes)
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    program = dsl_start_events(app) + [
        Send(
            app.actor_name(i % nodes),
            MessageConstructor(lambda v=10 + i: (T_CLIENT, 0, v, 0, 0, 0, 0)),
        )
        for i in range(commands)
    ] + [WaitQuiescence(budget=budget)]
    # The recorded schedule to minimize: depth is what matters here (the
    # win grows with prefix length), not a violation — replay trials cost
    # the same either way.
    result = RandomScheduler(
        config, seed=0, max_messages=4 * budget, invariant_check_interval=1,
        timer_weight=0.2,
    ).execute(program)
    trace = result.trace
    trace.set_original_externals(list(program))
    indices = removable_delivery_indices(trace)
    cap = int(os.environ.get("DEMI_BENCH_CONFIG6_CANDIDATES", 0))
    if cap:
        indices = indices[:cap]
    candidates = [remove_delivery(trace, i) for i in indices]
    if len(candidates) < 2:  # pragma: no cover - fixture is delivery-rich
        return {"error": "too few removable deliveries to measure"}
    device_cfg = default_device_config(app, trace, program)
    target = 1  # arbitrary: throughput does not depend on the verdict
    reps = int(os.environ.get("DEMI_BENCH_CONFIG6_REPS", 3))
    bucket = int(os.environ.get("DEMI_BENCH_CONFIG6_BUCKET", 8))
    exts = [program] * len(candidates)

    def measure(checker):
        # Warm-up pass compiles the kernels (and, for the fork checker,
        # populates the trunk cache — the steady state of consecutive
        # internal-minimization rounds, which reuse trunks).
        verdicts = checker.verdicts(candidates, exts, target)
        t0 = time.perf_counter()
        for _ in range(reps):
            verdicts = checker.verdicts(candidates, exts, target)
        return len(candidates) * reps / (time.perf_counter() - t0), verdicts

    scratch_rate, scratch_verdicts = measure(
        DeviceReplayChecker(app, device_cfg, config, prefix_fork=False)
    )
    fork_checker = DeviceReplayChecker(
        app, device_cfg, config, prefix_fork=True, fork_bucket=bucket
    )
    fork_rate, fork_verdicts = measure(fork_checker)
    st = fork_checker.fork_stats
    probes = st["prefix_hits"] + st["prefix_misses"]
    return {
        "app": f"raft{nodes}",
        "deliveries": len(trace.deliveries()),
        "candidates": len(candidates),
        "reps": reps,
        "scratch_trials_per_sec": round(scratch_rate, 1),
        "fork_trials_per_sec": round(fork_rate, 1),
        "speedup": round(fork_rate / scratch_rate, 2) if scratch_rate else None,
        # Bit-exactness is the contract, so record it next to the rates.
        "verdicts_match": scratch_verdicts == fork_verdicts,
        "prefix_hit_rate": round(st["prefix_hits"] / probes, 3) if probes else 0.0,
        "steps_saved": st["steps_saved"],
        "forked_lanes": st["forked_lanes"],
        "scratch_lanes": st["scratch_lanes"],
        "fork_groups": st["groups"],
    }


def bench_config7(jax):
    """Config 7: the full async minimization pipeline vs the synchronous
    scratch oracle — end-to-end wall clock of a deep raft ddmin +
    internal minimization. Both paths run the SAME minimizers on the
    SAME recorded violation; the pipeline side turns on every PR-4
    feature: lower-once/gather-many candidate lowering, the
    dispatch/harvest split (speculative host execution between dispatch
    and harvest), speculative next-level dispatch into the idle padded
    lanes, and prefix-fork replay with HIERARCHICAL trunks (a trunk-cache
    miss resumes the parent bucket's cached trunk instead of replaying
    its full prefix). The contract keys — verdicts_match / mcs_match —
    assert the pipeline's results are bit-identical; every feature stays
    off by default everywhere (both paths are measured regardless of the
    env). Knobs: DEMI_BENCH_CONFIG7_NODES / _COMMANDS / _BUDGET /
    _SEEDS / _DEPTH_CAP / _REPS / _BUCKET."""
    from demi_tpu.apps.common import dsl_start_events, make_host_invariant
    from demi_tpu.apps.raft import T_CLIENT, make_raft_app
    from demi_tpu.config import SchedulerConfig
    from demi_tpu.device.batch_oracle import (
        DeviceReplayChecker,
        DeviceSTSOracle,
        default_device_config,
        make_batched_internal_check,
    )
    from demi_tpu.external_events import MessageConstructor, Send, WaitQuiescence
    from demi_tpu.minimization.ddmin import BatchedDDMin, make_dag
    from demi_tpu.minimization.internal import BatchedInternalMinimizer
    from demi_tpu.minimization.stats import MinimizationStats
    from demi_tpu.schedulers import RandomScheduler

    nodes = int(os.environ.get("DEMI_BENCH_CONFIG7_NODES", 3))
    commands = int(os.environ.get("DEMI_BENCH_CONFIG7_COMMANDS", 3))
    budget = int(os.environ.get("DEMI_BENCH_CONFIG7_BUDGET", 240))
    seeds = int(os.environ.get("DEMI_BENCH_CONFIG7_SEEDS", 40))
    # Depth cap: a 300-delivery minimization runs ~13s per ROUND on a
    # 2-core CPU box (the pipeline is for exactly that scale, but the
    # bench must finish); default targets the ~120-delivery class.
    depth_cap = int(os.environ.get("DEMI_BENCH_CONFIG7_DEPTH_CAP", 160))
    reps = int(os.environ.get("DEMI_BENCH_CONFIG7_REPS", 3))
    bucket = int(os.environ.get("DEMI_BENCH_CONFIG7_BUCKET", 8))
    app = make_raft_app(nodes, bug="multivote")
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    program = dsl_start_events(app) + [
        Send(
            app.actor_name(i % nodes),
            MessageConstructor(lambda v=10 + i: (T_CLIENT, 0, v, 0, 0, 0, 0)),
        )
        for i in range(commands)
    ] + [WaitQuiescence()]
    # Deepest violating execution under the depth cap: the pipeline's
    # win scales with trace depth (host lowering and bookkeeping
    # executions are O(depth) per candidate), and multivote violations
    # land anywhere from ~15 to ~400 deliveries depending on the seed.
    fr = None
    best = -1
    for seed in range(seeds):
        r = RandomScheduler(
            config, seed=seed, max_messages=budget,
            invariant_check_interval=1,
        ).execute(program)
        if r.violation is None:
            continue
        depth = len(r.trace.deliveries())
        if depth <= depth_cap and depth > best:
            fr, best = r, depth
    if fr is None:  # pragma: no cover - multivote violates reliably
        return {"error": "no violation found to minimize"}
    trace = fr.trace
    trace.set_original_externals(list(program))
    device_cfg = default_device_config(app, trace, program)

    class LoggingChecker(DeviceReplayChecker):
        """Records the verdict stream so sync/async bit-exactness is a
        measured fact, not an assumption: sync logs in verdicts(), async
        logs at harvest (verdicts() routes through dispatch there)."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.vlog = []

        def verdicts(self, *a, **kw):
            v = super().verdicts(*a, **kw)
            if not self.async_enabled:
                self.vlog.append(tuple(v))
            return v

        def dispatch(self, *a, **kw):
            pending = super().dispatch(*a, **kw)
            inner = pending.harvest

            def harvest():
                fresh = pending._verdicts is None
                v = inner()
                if fresh:
                    self.vlog.append(tuple(v))
                return v

            pending.harvest = harvest
            return pending

    def pipeline(checker, speculative):
        stats = MinimizationStats()
        oracle = DeviceSTSOracle(
            app, device_cfg, config, trace, checker=checker
        )
        ddmin = BatchedDDMin(oracle, stats=stats, speculative=speculative)
        mcs = ddmin.minimize(make_dag(list(program)), fr.violation)
        ext = mcs.get_all_events()
        base = ddmin.verified_trace
        if base is None:  # pragma: no cover - MCS host-verifies
            raise RuntimeError("MCS failed host verification")
        minimizer = BatchedInternalMinimizer(
            make_batched_internal_check(checker, list(ext), fr.violation),
            stats=stats,
            speculative=speculative,
        )
        final = minimizer.minimize(base)
        return ext, final, ddmin.levels, minimizer

    # Interleaved reps + medians (the bench_device_raft rule: machine
    # drift must land on both variants equally — single-run wall clocks
    # on a busy 2-core box spread ±15%).
    s_checker = LoggingChecker(
        app, device_cfg, config, prefix_fork=False, async_min=False
    )
    a_checker = LoggingChecker(
        app, device_cfg, config, prefix_fork=True, fork_bucket=bucket,
        async_min=True,
    )
    pipeline(s_checker, False)  # warm-up: compile + steady-state caches
    pipeline(a_checker, True)
    sync_times, async_times = [], []
    for _ in range(reps):
        s_checker.vlog = []
        t0 = time.perf_counter()
        s_out = pipeline(s_checker, False)
        sync_times.append(time.perf_counter() - t0)
        a_checker.vlog = []
        t0 = time.perf_counter()
        a_out = pipeline(a_checker, True)
        async_times.append(time.perf_counter() - t0)
    sync_secs = sorted(sync_times)[len(sync_times) // 2]
    async_secs = sorted(async_times)[len(async_times) // 2]
    s_ext, s_final, s_levels, _ = s_out
    a_ext, a_final, a_levels, a_im = a_out
    from demi_tpu.device.encoding import lower_expected_trace

    s_bytes = lower_expected_trace(
        app, device_cfg, s_final, s_ext, s_checker.max_records
    ).tobytes()
    a_bytes = lower_expected_trace(
        app, device_cfg, a_final, a_ext, a_checker.max_records
    ).tobytes()
    pipe = a_checker.pipeline_snapshot()
    fork = a_checker.fork_stats
    return {
        "app": f"raft{nodes}",
        "deliveries": len(trace.deliveries()),
        "externals": len(program),
        "mcs_externals": len(s_ext),
        "final_deliveries": len(s_final.deliveries()),
        "ddmin_levels": s_levels,
        "reps": reps,
        "sync_seconds": round(sync_secs, 2),
        "async_seconds": round(async_secs, 2),
        "speedup": round(sync_secs / async_secs, 2) if async_secs else None,
        # Bit-exactness contract: identical verdict stream, identical
        # MCS, identical final minimized schedule (record bytes).
        "verdicts_match": s_checker.vlog == a_checker.vlog,
        "mcs_match": (
            [e.eid for e in s_ext] == [e.eid for e in a_ext]
            and s_levels == a_levels
            and s_bytes == a_bytes
        ),
        "speculation_hits": pipe["spec_hits"],
        "speculation_waste": pipe["spec_waste"],
        # Speculative host executions (the predicted adoption, run
        # between dispatch and harvest) from the timed run's minimizer.
        "spec_exec_hits": a_im.spec_exec_hits,
        "spec_exec_waste": a_im.spec_exec_waste,
        "lowering_cache_hit_rate": pipe["lowering_cache_hit_rate"],
        "overlap_fraction": pipe["overlap_fraction"],
        "launches": pipe["launches"],
        "fork": {
            "prefix_hit_rate": round(
                fork["prefix_hits"]
                / max(1, fork["prefix_hits"] + fork["prefix_misses"]),
                3,
            ),
            # Hierarchical trunks: misses served by resuming an ancestor
            # trunk (O(bucket)) instead of a full-prefix replay (O(p)).
            "parent_trunks": fork["parent_trunks"],
            "steps_saved": fork["steps_saved"],
        },
    }


def bench_config8(jax):
    """Config 8: async DPOR frontier throughput — the synchronous
    scratch loop vs the async pipeline (double-buffered in-flight rounds
    + prefix forking with prescribed-resume trunks armed) on a DEEP
    seeded raft fixture, measured as frontier rounds/sec over the SAME
    round budget. The fixture is the oracle-probe shape the pipeline
    exists for (the config-7 recipe): fuzz the deepest multivote
    violation under the depth cap, seed the frontier with its steering
    prescription, and explore uncapped — racing prescriptions then run
    hundreds of records deep, so each round carries a real host share
    (the O(n^2) racing-pair scan) for the in-flight round to overlap.
    Both variants follow the same generation-frozen round policy and
    identical per-lane keys, so the explored set, frontier, and
    interleaving count are asserted EQUAL — the async side may only be
    faster, never different. Every feature stays off by default; the
    bench passes explicit constructor args. Knobs:
    DEMI_BENCH_CONFIG8_ROUNDS / _REPS / _BATCH / _BUCKET / _WARM /
    _BUDGET / _SEEDS / _DEPTH_CAP."""
    from demi_tpu.apps.common import dsl_start_events, make_host_invariant
    from demi_tpu.apps.raft import T_CLIENT, make_raft_app
    from demi_tpu.config import SchedulerConfig
    from demi_tpu.device.batch_oracle import default_device_config
    from demi_tpu.device.dpor_sweep import (
        DeviceDPOR,
        make_dpor_kernel,
        steering_prescription,
    )
    from demi_tpu.external_events import MessageConstructor, Send, WaitQuiescence
    from demi_tpu.schedulers import RandomScheduler

    nodes, commands = 3, 3
    budget = int(os.environ.get("DEMI_BENCH_CONFIG8_BUDGET", 240))
    seeds = int(os.environ.get("DEMI_BENCH_CONFIG8_SEEDS", 40))
    depth_cap = int(os.environ.get("DEMI_BENCH_CONFIG8_DEPTH_CAP", 120))
    app = make_raft_app(nodes, bug="multivote")
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    program = dsl_start_events(app) + [
        Send(
            app.actor_name(i % nodes),
            MessageConstructor(lambda v=10 + i: (T_CLIENT, 0, v, 0, 0, 0, 0)),
        )
        for i in range(commands)
    ] + [WaitQuiescence()]
    fr = None
    best = -1
    for seed in range(seeds):
        r = RandomScheduler(
            config, seed=seed, max_messages=budget,
            invariant_check_interval=1,
        ).execute(program)
        if r.violation is None:
            continue
        depth = len(r.trace.deliveries())
        if depth <= depth_cap and depth > best:
            fr, best = r, depth
    if fr is None:  # pragma: no cover - multivote violates reliably
        return {"error": "no violation found to seed the frontier"}
    trace = fr.trace
    trace.set_original_externals(list(program))
    cfg = default_device_config(
        app, trace, program, record_trace=True, record_parents=True,
    )
    presc = steering_prescription(app, cfg, trace, program)

    platform = jax.devices()[0].platform
    batch = int(os.environ.get(
        "DEMI_BENCH_CONFIG8_BATCH", 64 if platform not in ("cpu",) else 16
    ))
    rounds = int(os.environ.get("DEMI_BENCH_CONFIG8_ROUNDS", 4))
    reps = int(os.environ.get("DEMI_BENCH_CONFIG8_REPS", 3))
    bucket = int(os.environ.get("DEMI_BENCH_CONFIG8_BUCKET", 8))
    # Warm-up rounds: compile the kernels AND saturate the frontier with
    # deep racing prescriptions, so the timed rounds measure the
    # steady-state regime (deep generation, full batches).
    warm = int(os.environ.get("DEMI_BENCH_CONFIG8_WARM", 3))
    # One compiled kernel pair serves every rep (a fresh DeviceDPOR per
    # rep resets the frontier; sharing kernels keeps compilation out of
    # the timed region after the warm-up rep).
    kernel = make_dpor_kernel(app, cfg)
    fork_kernel = make_dpor_kernel(app, cfg, start_state=True)

    def run(variant):
        # 'sync'    — async off;
        # 'async'   — double-buffered rounds + prefix forking with
        #             prescribed-resume trunks.
        if variant == "async":
            # DEMI_BENCH_CONFIG8_MIN_GROUP overrides the platform fork
            # gate (CPU default: half a batch — which zeroes the fork
            # economy at CPU smoke shapes); a permissive value measures
            # the trunk/anchor hit rates the gate normally hides.
            min_group = os.environ.get("DEMI_BENCH_CONFIG8_MIN_GROUP")
            dpor = DeviceDPOR(
                app, cfg, program, batch_size=batch,
                prefix_fork=True, fork_bucket=bucket,
                fork_min_group=int(min_group) if min_group else None,
                double_buffer=True, kernel=kernel, fork_kernel=fork_kernel,
                sleep_sets=False,  # the shared kernels are plain ones
            )
        else:
            dpor = DeviceDPOR(
                app, cfg, program, batch_size=batch,
                prefix_fork=False, double_buffer=False, kernel=kernel,
                sleep_sets=False,
            )
        dpor.seed(presc)
        dpor.explore(max_rounds=warm)
        # Host-share ledger starts AFTER the warm-up (compilation lands
        # in the dispatch path and would read as host time).
        dpor.host_seconds = dpor.device_seconds = 0.0
        before = dpor.interleavings
        t0 = time.perf_counter()
        dpor.explore(max_rounds=rounds)
        secs = time.perf_counter() - t0
        return dpor, dpor.interleavings - before, secs

    run("sync")  # warm-up rep: compilation + trunk-cache steady state
    run("async")
    times = {"sync": [], "async": []}
    dpors = {}
    measured = 0
    for _ in range(reps):
        # Interleaved reps + medians (the config-7 rule: machine drift
        # must land on every variant equally).
        for variant in ("sync", "async"):
            d, m, secs = run(variant)
            times[variant].append(secs)
            dpors[variant] = d
            if measured:
                assert m == measured
            measured = m

    def median(xs):
        return sorted(xs)[len(xs) // 2]

    s_dpor, a_dpor = dpors["sync"], dpors["async"]

    def sibling_clustering(dpor, rounds_to_plan=3):
        # The dpor.prefix_group_size shift, measured directly: plan the
        # next few round batches of the final frontier with a permissive
        # planner (min_group=2) and report multi-member group sizes. The
        # bucketed depth selection turns the structural 2-lane sibling
        # groups into 4-7-lane groups; whether a trunk actually FORKS
        # them is the platform cost model's call (CPU keeps scratch
        # unless groups reach half a batch — see DeviceDPOR).
        from demi_tpu.device.fork import PrefixPlanner

        planner = PrefixPlanner(bucket=bucket, min_group=2)
        rest = dpor._ordered_frontier(dpor.frontier)
        sizes = []
        for r in range(rounds_to_plan):
            batch_p = rest[r * batch: (r + 1) * batch]
            if not batch_p:
                break
            recs = dpor._pack(batch_p)
            lengths = np.asarray([len(p) for p in batch_p])
            groups, _scratch = planner.plan(recs, lengths)
            sizes.extend(len(g.indices) for g in groups if len(g.indices) > 1)
        return {
            "mean_group_size": (
                round(sum(sizes) / len(sizes), 2) if sizes else None
            ),
            "max_group_size": max(sizes) if sizes else None,
            "groups": len(sizes),
        }

    sync_secs = median(times["sync"])
    async_secs = median(times["async"])
    fork = a_dpor._forker.stats_view()
    # Async-on host share: the double-buffered loop never blocks, so its
    # own wall-minus-blocked split degenerates on CPU (overlapped device
    # compute steals the same cores the host segment is timed on). The
    # sync run measures the SAME per-round host work uncontended — its
    # host seconds against the async wall is the honest "host share per
    # round" figure (how much of an async round a single host thread
    # actually needs).
    a_share = (
        min(1.0, s_dpor.host_seconds / async_secs) if async_secs else None
    )
    # Static-commutativity A/B on the SEEDED deep fixture (disabled vs
    # enabled, no-op-only asserted) — static_pruned lands next to the
    # redundant/distance-pruned counters the obs snapshot carries.
    static = _static_prune_ab(
        app, cfg, program, batch,
        rounds=int(os.environ.get("DEMI_BENCH_STATIC_ROUNDS", 2)),
        kernel=kernel, presc=presc,
    )
    return {
        "app": f"raft{nodes}",
        "seed_deliveries": best,
        "batch": batch,
        "rounds": rounds,
        "warm_rounds": warm,
        "reps": reps,
        "static": static,
        "interleavings": measured,
        "sync_seconds": round(sync_secs, 3),
        "async_seconds": round(async_secs, 3),
        "speedup": round(sync_secs / async_secs, 2) if async_secs else None,
        "sync_rounds_per_sec": (
            round(rounds / sync_secs, 2) if sync_secs else None
        ),
        "async_rounds_per_sec": (
            round(rounds / async_secs, 2) if async_secs else None
        ),
        # The equality contract: the async pipeline must explore the
        # EXACT same schedule space, not a faster different one.
        "explored_match": s_dpor.explored == a_dpor.explored,
        "frontier_match": s_dpor.frontier == a_dpor.frontier,
        "interleavings_match": s_dpor.interleavings == a_dpor.interleavings,
        "explored": len(s_dpor.explored),
        "frontier": len(s_dpor.frontier),
        # Host-vs-device wall split with the full async stack on — the
        # acceptance target is host share < 25% on this fixture.
        "host_share": round(a_share, 3) if a_share is not None else None,
        "device_share": (
            round(1 - a_share, 3) if a_share is not None else None
        ),
        # In-flight round economy (the calibrate_dpor_inflight signal).
        "inflight": dict(a_dpor.async_stats),
        "fork": {
            "prefix_hit_rate": round(
                fork["prefix_hits"]
                / max(1, fork["prefix_hits"] + fork["prefix_misses"]),
                3,
            ),
            "parent_trunks": fork["parent_trunks"],
            # Cross-round trunk reuse (the PR 6 ~0%-hit debt): anchors
            # cached at sub-bucket stride boundaries while building
            # trunks, so later rounds' round-unique prefixes resume the
            # deepest shared ancestor (DEMI_FORK_ANCHOR_STRIDE).
            "anchor_trunks": fork.get("anchor_trunks", 0),
            "steps_saved": fork["steps_saved"],
            # Fork-group growth: mean forked-group size (the
            # dpor.prefix_group_size shift the cross-generation merge +
            # equal-depth clustering exist to raise past the structural
            # 2-3 sibling lanes).
            "groups": fork["groups"],
            "forked_lanes": fork["forked_lanes"],
            "mean_group_size": (
                round(fork["forked_lanes"] / fork["groups"], 2)
                if fork["groups"] else None
            ),
        },
        # Planner-view sibling clustering of the final frontier (the
        # dpor.prefix_group_size shift the bucketed selection produces,
        # independent of whether the platform cost model forks them).
        "sibling_groups": sibling_clustering(s_dpor),
    }


def bench_config9(jax):
    """Redundancy-ratio bench: explored schedules vs. the per-fixture
    optimal lower bound (distinct Mazurkiewicz classes among admitted
    prescriptions), A/B'd with sleep-set + race-reversal pruning OFF
    (observe mode — classes tracked, nothing suppressed) vs ON, on the
    config-8 deep seeded raft frontier. Both sides run identically-
    guided wakeup sequences with content-derived lane keys, so a
    prescription explores the same suffix wherever pruning shifts it —
    the property the identity assertions rest on:

      - the FIRST found violating lane's records are bit-identical;
      - the distinct violation-code set over every lane of every round
        is identical;
      - the pruned run admits no more schedules than the baseline
        (STRICTLY fewer at the default depth — DEMI_BENCH_CONFIG9_STRICT=0
        relaxes for tiny smoke shapes), and its redundancy ratio is <=
        the baseline's, with the gap reported.

    Knobs: DEMI_BENCH_CONFIG9_ROUNDS / _BATCH / _BUDGET / _SEEDS /
    _DEPTH_CAP / _STRICT."""
    from demi_tpu.analysis import SleepSets, StaticIndependence, sleep_cap
    from demi_tpu.apps.common import dsl_start_events, make_host_invariant
    from demi_tpu.apps.raft import T_CLIENT, make_raft_app
    from demi_tpu.config import SchedulerConfig
    from demi_tpu.device.batch_oracle import default_device_config
    from demi_tpu.device.dpor_sweep import (
        DeviceDPOR,
        make_dpor_kernel,
        steering_prescription,
    )
    from demi_tpu.external_events import MessageConstructor, Send, WaitQuiescence
    from demi_tpu.schedulers import RandomScheduler

    nodes, commands = 3, 3
    budget = int(os.environ.get("DEMI_BENCH_CONFIG9_BUDGET", 240))
    seeds = int(os.environ.get("DEMI_BENCH_CONFIG9_SEEDS", 40))
    depth_cap = int(os.environ.get("DEMI_BENCH_CONFIG9_DEPTH_CAP", 120))
    strict = os.environ.get("DEMI_BENCH_CONFIG9_STRICT", "1") != "0"
    app = make_raft_app(nodes, bug="multivote")
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    program = dsl_start_events(app) + [
        Send(
            app.actor_name(i % nodes),
            MessageConstructor(lambda v=10 + i: (T_CLIENT, 0, v, 0, 0, 0, 0)),
        )
        for i in range(commands)
    ] + [WaitQuiescence()]
    fr = None
    best = -1
    for seed in range(seeds):
        r = RandomScheduler(
            config, seed=seed, max_messages=budget,
            invariant_check_interval=1,
        ).execute(program)
        if r.violation is None:
            continue
        depth = len(r.trace.deliveries())
        if depth <= depth_cap and depth > best:
            fr, best = r, depth
    if fr is None:  # pragma: no cover - multivote violates reliably
        return {"error": "no violation found to seed the frontier"}
    trace = fr.trace
    trace.set_original_externals(list(program))
    cfg = default_device_config(
        app, trace, program, record_trace=True, record_parents=True,
    )
    presc = steering_prescription(app, cfg, trace, program)

    platform = jax.devices()[0].platform
    batch = int(os.environ.get(
        "DEMI_BENCH_CONFIG9_BATCH", 64 if platform not in ("cpu",) else 16
    ))
    rounds = int(os.environ.get("DEMI_BENCH_CONFIG9_ROUNDS", 16))
    cap = sleep_cap()
    rel = StaticIndependence.for_app(app)
    kernel = make_dpor_kernel(
        app, cfg, sleep_cap=cap, commute_matrix=rel.device_matrix()
    )

    def run(prune):
        d = DeviceDPOR(
            app, cfg, program, batch_size=batch, kernel=kernel,
            prefix_fork=False, double_buffer=False,
            sleep_sets=SleepSets(independence=rel, prune=prune, cap=cap),
        )
        d.seed(presc)
        founds = []
        secs = 0.0
        done = 0
        for r in range(rounds):
            if not d.frontier:
                break
            t0 = time.perf_counter()
            f = d.explore(max_rounds=1)
            dt = time.perf_counter() - t0
            if r > 0:  # round 0 carries kernel compilation
                secs += dt
                done += 1
            if f is not None:
                founds.append((f[0][: f[1]].tobytes(), int(f[1])))
        return d, founds, (done / secs if secs > 0 else None)

    base, founds_base, rps_base = run(False)
    pruned, founds_pruned, rps_pruned = run(True)

    ratio_base = base.sleep.redundancy_ratio(len(base.explored)) or 1.0
    ratio_pruned = (
        pruned.sleep.redundancy_ratio(len(pruned.explored)) or 1.0
    )
    first_base = founds_base[0] if founds_base else None
    first_pruned = founds_pruned[0] if founds_pruned else None
    # The A/B identity contracts: same violations, same first find,
    # never MORE schedules, never a WORSE ratio.
    assert base.violation_codes == pruned.violation_codes, (
        base.violation_codes, pruned.violation_codes
    )
    assert first_base == first_pruned
    assert len(pruned.explored) <= len(base.explored)
    assert ratio_pruned <= ratio_base + 1e-9
    if strict:
        # The headline: at the default depth the deep raft frontier
        # always carries already-reversed races, so pruning must bite.
        assert len(pruned.explored) < len(base.explored), (
            len(pruned.explored), len(base.explored)
        )
    return {
        "app": f"raft{nodes}",
        "seed_deliveries": best,
        "batch": batch,
        "rounds": rounds,
        "sleep_cap": cap,
        "explored_base": len(base.explored),
        "explored_pruned": len(pruned.explored),
        "explored_reduction": len(base.explored) - len(pruned.explored),
        "classes_base": len(base.sleep.classes),
        "classes_pruned": len(pruned.sleep.classes),
        "redundancy_ratio_base": round(ratio_base, 4),
        "redundancy_ratio_pruned": round(ratio_pruned, 4),
        "ratio_gap": round(ratio_base - ratio_pruned, 4),
        "sleep_pruned": dict(pruned.sleep.pruned_total),
        "violations_match": True,
        "found_match": True,
        "violation_codes": sorted(base.violation_codes),
        "rounds_per_sec_base": (
            round(rps_base, 2) if rps_base is not None else None
        ),
        "rounds_per_sec_pruned": (
            round(rps_pruned, 2) if rps_pruned is not None else None
        ),
    }


def bench_config10(jax):
    """Durability bench: checkpoint overhead % and time-to-resume on the
    config-9 deep seeded raft frontier. Three measurements:

      - A plain single-round frontier loop (the checkpointing CLI's loop
        shape) timed with no persistence — the denominator;
      - the same loop writing an atomic snapshot generation every
        ``--checkpoint-every`` rounds (the CLI default, 5) — overhead %
        is the headline, with the acceptance bar at < 5% of round wall
        time;
      - a cold restore: a FRESH DeviceDPOR restored from the newest
        generation, timed, and asserted bit-identical (explored/
        frontier/violation codes) to the writer's final state.

    Knobs: DEMI_BENCH_CONFIG10_ROUNDS / _BATCH / _EVERY / _BUDGET /
    _SEEDS / _DEPTH_CAP."""
    import tempfile

    from demi_tpu.apps.common import dsl_start_events, make_host_invariant
    from demi_tpu.apps.raft import T_CLIENT, make_raft_app
    from demi_tpu.config import SchedulerConfig
    from demi_tpu.device.batch_oracle import default_device_config
    from demi_tpu.device.dpor_sweep import (
        DeviceDPOR,
        make_dpor_kernel,
        steering_prescription,
    )
    from demi_tpu.external_events import (
        MessageConstructor,
        Send,
        WaitQuiescence,
    )
    from demi_tpu.persist import CheckpointStore
    from demi_tpu.schedulers import RandomScheduler

    nodes, commands = 3, 3
    budget = int(os.environ.get("DEMI_BENCH_CONFIG10_BUDGET", 240))
    seeds = int(os.environ.get("DEMI_BENCH_CONFIG10_SEEDS", 40))
    depth_cap = int(os.environ.get("DEMI_BENCH_CONFIG10_DEPTH_CAP", 120))
    app = make_raft_app(nodes, bug="multivote")
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    program = dsl_start_events(app) + [
        Send(
            app.actor_name(i % nodes),
            MessageConstructor(lambda v=10 + i: (T_CLIENT, 0, v, 0, 0, 0, 0)),
        )
        for i in range(commands)
    ] + [WaitQuiescence()]
    fr = None
    best = -1
    for seed in range(seeds):
        r = RandomScheduler(
            config, seed=seed, max_messages=budget,
            invariant_check_interval=1,
        ).execute(program)
        if r.violation is None:
            continue
        depth = len(r.trace.deliveries())
        if depth <= depth_cap and depth > best:
            fr, best = r, depth
    if fr is None:  # pragma: no cover - multivote violates reliably
        return {"error": "no violation found to seed the frontier"}
    trace = fr.trace
    trace.set_original_externals(list(program))
    cfg = default_device_config(
        app, trace, program, record_trace=True, record_parents=True,
    )
    presc = steering_prescription(app, cfg, trace, program)

    platform = jax.devices()[0].platform
    batch = int(os.environ.get(
        "DEMI_BENCH_CONFIG10_BATCH", 64 if platform not in ("cpu",) else 16
    ))
    rounds = int(os.environ.get("DEMI_BENCH_CONFIG10_ROUNDS", 10))
    every = int(os.environ.get("DEMI_BENCH_CONFIG10_EVERY", 5))
    kernel = make_dpor_kernel(app, cfg)

    def run(store):
        d = DeviceDPOR(
            app, cfg, program, batch_size=batch, kernel=kernel,
            prefix_fork=False, double_buffer=False,
        )
        d.seed(presc)
        secs = 0.0
        done = 0
        for r in range(rounds):
            if not d.frontier:
                break
            t0 = time.perf_counter()
            d.explore(max_rounds=1)
            if store is not None and (r + 1) % every == 0:
                store.save(
                    {"dpor": d.checkpoint_state()},
                    meta={"command": "bench10", "rounds_done": r + 1},
                )
            dt = time.perf_counter() - t0
            if r > 0:  # round 0 carries kernel compilation
                secs += dt
                done += 1
        if store is not None:
            # Terminal generation (untimed — the CLI writes one per run
            # too): the newest snapshot always IS the final state, so
            # the cold-restore check below is well-defined for any
            # ROUNDS/EVERY knobs and early frontier drains.
            store.save(
                {"dpor": d.checkpoint_state()},
                meta={"command": "bench10", "completed": True},
            )
        return d, (done / secs if secs > 0 else None)

    plain, rps_plain = run(None)
    with tempfile.TemporaryDirectory() as tmp:
        store = CheckpointStore(tmp)
        ckpt_d, rps_ckpt = run(store)
        # Writing snapshots must not change what was explored: the two
        # loops run identical rounds.
        assert ckpt_d.explored == plain.explored
        assert ckpt_d.violation_codes == plain.violation_codes
        # Cold restore: newest generation into a FRESH explorer.
        t0 = time.perf_counter()
        loaded = store.load_latest()
        fresh = DeviceDPOR(
            app, cfg, program, batch_size=batch, kernel=kernel,
            prefix_fork=False, double_buffer=False,
        )
        fresh.restore_state(loaded.sections["dpor"])
        time_to_resume = time.perf_counter() - t0
        restore_match = (
            fresh.explored == ckpt_d.explored
            and fresh.frontier == ckpt_d.frontier
            and fresh.violation_codes == ckpt_d.violation_codes
            and fresh._explored_digests == ckpt_d._explored_digests
        )
        assert restore_match
        snapshots = dict(store.stats)
    overhead_pct = None
    if rps_plain and rps_ckpt:
        # Overhead of persistence per round, as % of plain round wall
        # time (rounds/sec inverted): the acceptance bar is < 5% at the
        # default --checkpoint-every.
        overhead_pct = round(
            max(0.0, (1.0 / rps_ckpt - 1.0 / rps_plain) * rps_plain) * 100,
            2,
        )
    return {
        "app": f"raft{nodes}",
        "seed_deliveries": best,
        "batch": batch,
        "rounds": rounds,
        "checkpoint_every": every,
        "explored": len(ckpt_d.explored),
        "violation_codes": sorted(ckpt_d.violation_codes),
        "snapshots_written": snapshots["snapshots_written"],
        "snapshot_bytes": snapshots["snapshot_bytes"],
        "rounds_per_sec_plain": (
            round(rps_plain, 2) if rps_plain is not None else None
        ),
        "rounds_per_sec_checkpointed": (
            round(rps_ckpt, 2) if rps_ckpt is not None else None
        ),
        "checkpoint_overhead_pct": overhead_pct,
        "time_to_resume_s": round(time_to_resume, 4),
        "restore_match": restore_match,
    }


def bench_config11(jax):
    """Continuous-observability overhead: the round journal + per-round
    time-series sampling attached (always-on shape) vs detached, on the
    config-9/10 deep seeded raft frontier. The acceptance bar is < 1% of
    round wall — the number that lets the continuous plane default ON
    wherever a checkpoint dir exists (opt-in → measured → default, the
    repo's discipline). Also asserts:

      - attaching the journal changes NOTHING about the search
        (explored set + violation codes bit-identical);
      - the journal is round-contiguous 1..N with the per-round schema
        keys present;
      - the time-series export carries one sample per round and the
        Prometheus exposition of the final registry snapshot renders.

    Knobs: DEMI_BENCH_CONFIG11_ROUNDS / _BATCH / _BUDGET / _SEEDS /
    _DEPTH_CAP."""
    import tempfile

    from demi_tpu import obs
    from demi_tpu.apps.common import dsl_start_events, make_host_invariant
    from demi_tpu.apps.raft import T_CLIENT, make_raft_app
    from demi_tpu.config import SchedulerConfig
    from demi_tpu.device.batch_oracle import default_device_config
    from demi_tpu.device.dpor_sweep import (
        DeviceDPOR,
        make_dpor_kernel,
        steering_prescription,
    )
    from demi_tpu.external_events import (
        MessageConstructor,
        Send,
        WaitQuiescence,
    )
    from demi_tpu.obs import journal as obs_journal
    from demi_tpu.obs import timeseries as obs_ts
    from demi_tpu.schedulers import RandomScheduler

    nodes, commands = 3, 3
    budget = int(os.environ.get("DEMI_BENCH_CONFIG11_BUDGET", 240))
    seeds = int(os.environ.get("DEMI_BENCH_CONFIG11_SEEDS", 40))
    depth_cap = int(os.environ.get("DEMI_BENCH_CONFIG11_DEPTH_CAP", 120))
    app = make_raft_app(nodes, bug="multivote")
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    program = dsl_start_events(app) + [
        Send(
            app.actor_name(i % nodes),
            MessageConstructor(lambda v=10 + i: (T_CLIENT, 0, v, 0, 0, 0, 0)),
        )
        for i in range(commands)
    ] + [WaitQuiescence()]
    fr = None
    best = -1
    for seed in range(seeds):
        r = RandomScheduler(
            config, seed=seed, max_messages=budget,
            invariant_check_interval=1,
        ).execute(program)
        if r.violation is None:
            continue
        depth = len(r.trace.deliveries())
        if depth <= depth_cap and depth > best:
            fr, best = r, depth
    if fr is None:  # pragma: no cover - multivote violates reliably
        return {"error": "no violation found to seed the frontier"}
    trace = fr.trace
    trace.set_original_externals(list(program))
    cfg = default_device_config(
        app, trace, program, record_trace=True, record_parents=True,
    )
    presc = steering_prescription(app, cfg, trace, program)

    platform = jax.devices()[0].platform
    batch = int(os.environ.get(
        "DEMI_BENCH_CONFIG11_BATCH", 64 if platform not in ("cpu",) else 16
    ))
    rounds = int(os.environ.get("DEMI_BENCH_CONFIG11_ROUNDS", 10))
    kernel = make_dpor_kernel(app, cfg)

    def run(journal_dir):
        if journal_dir is not None:
            obs_journal.attach(journal_dir)
            obs_ts.SERIES.clear()
        d = DeviceDPOR(
            app, cfg, program, batch_size=batch, kernel=kernel,
            prefix_fork=False, double_buffer=False,
        )
        d.seed(presc)
        secs = 0.0
        done = 0
        for r in range(rounds):
            if not d.frontier:
                break
            t0 = time.perf_counter()
            d.explore(max_rounds=1)
            dt = time.perf_counter() - t0
            if r > 0:  # round 0 carries kernel compilation
                secs += dt
                done += 1
        if journal_dir is not None:
            obs_ts.SERIES.flush_jsonl(journal_dir)
            obs_journal.detach()
        return d, done, (done / secs if secs > 0 else None)

    # Telemetry off on BOTH sides (the A/B isolates the continuous
    # plane's own cost, not DEMI_OBS bookkeeping; the journal reads the
    # drivers' always-on local stats either way).
    plain, _, rps_plain = run(None)
    with tempfile.TemporaryDirectory() as tmp:
        journaled, done, rps_j = run(tmp)
        # Observing the run must not change the run.
        assert journaled.explored == plain.explored
        assert journaled.violation_codes == plain.violation_codes
        recs = obs_journal.read_records(tmp, kind="dpor.round")
        contiguous, round_ids = obs_journal.contiguous_rounds(
            obs_journal.read_records(tmp), "dpor.round"
        )
        assert contiguous and len(round_ids) == journaled.round_index, (
            round_ids, journaled.round_index,
        )
        schema_ok = all(
            key in recs[-1]
            for key in ("round", "wall_s", "host_s", "device_s", "frontier",
                        "depth", "fresh", "redundant", "distance_pruned",
                        "violations", "explored", "interleavings",
                        "inflight_hits", "inflight_waste")
        )
        ts_rows = obs_ts.read_jsonl(tmp)
        prom = obs_ts.prom_text(obs.REGISTRY.snapshot())
    overhead_pct = None
    if rps_plain and rps_j:
        overhead_pct = round(
            max(0.0, (1.0 / rps_j - 1.0 / rps_plain) * rps_plain) * 100, 3
        )
    return {
        "app": f"raft{nodes}",
        "seed_deliveries": best,
        "batch": batch,
        "rounds": rounds,
        "journal_records": len(recs),
        "journal_contiguous": contiguous,
        "journal_schema_ok": schema_ok,
        "timeseries_samples": len(ts_rows),
        "prom_renders": prom.startswith(("# HELP", "# TYPE")) or prom == "\n",
        "explored": len(journaled.explored),
        "explored_match": journaled.explored == plain.explored,
        "violations_match": (
            journaled.violation_codes == plain.violation_codes
        ),
        "rounds_per_sec_plain": (
            round(rps_plain, 2) if rps_plain is not None else None
        ),
        "rounds_per_sec_journaled": (
            round(rps_j, 2) if rps_j is not None else None
        ),
        "journal_overhead_pct": overhead_pct,
    }


def bench_config12(jax):
    """Streaming fuzz→minimize→replay vs the staged pipeline
    (demi_tpu/pipeline/): a multi-violation raft fixture swept on
    device, every violating lane handed to the gamut minimizer — staged
    runs the tiers in sequence (sweep to completion, then each frame),
    streaming interleaves minimizer levels between chunk dispatch and
    harvest under one launch budget. Headline: time-to-first-MCS and
    MCSes/hour, streaming vs staged, with the MCS artifact sets
    (externals + final traces, eid-insensitive) and violation-code sets
    required bit-identical.

    Also asserts the streaming journal shows the tiers INTERLEAVED
    (minimize.level records between sweep.chunk records) — the span-
    timeline overlap contract at journal granularity.

    Measured reality on shared-core CPU: XLA CPU serializes executable
    executions (two dispatched kernels take the sum, measured), so the
    tiers' DEVICE halves cannot overlap — only host work hides under
    the other tier's kernels. That bounds CPU MCSes/hour at ~1.1-1.2x
    (ttf-MCS ~1.2-1.3x); the >=1.3x target is the disjoint-host/device
    regime (TPU), where the sweep's device time rides entirely under
    the minimizer's host half — the ROADMAP-5 measurement campaign
    covers it with this bench's knobs.

    Knobs: DEMI_BENCH_CONFIG12_LANES / _CHUNK / _MAX_MCS / _SPLIT /
    _DEPTH / _STEPS / _WILDCARDS."""
    import tempfile

    from demi_tpu.apps.common import dsl_start_events, make_host_invariant
    from demi_tpu.apps.raft import T_CLIENT, make_raft_app
    from demi_tpu.config import SchedulerConfig
    from demi_tpu.device import DeviceConfig
    from demi_tpu.external_events import (
        MessageConstructor,
        Send,
        WaitQuiescence,
    )
    from demi_tpu.obs import journal as obs_journal
    from demi_tpu.pipeline import (
        StreamingPipeline,
        frame_signature,
        run_staged,
    )

    nodes, commands = 3, 2
    lanes = int(os.environ.get("DEMI_BENCH_CONFIG12_LANES", 8192))
    chunk = int(os.environ.get("DEMI_BENCH_CONFIG12_CHUNK", 64))
    max_mcs = int(os.environ.get("DEMI_BENCH_CONFIG12_MAX_MCS", 4))
    split = float(os.environ.get("DEMI_BENCH_CONFIG12_SPLIT", 0.5))
    depth = int(os.environ.get("DEMI_BENCH_CONFIG12_DEPTH", 4))
    steps = int(os.environ.get("DEMI_BENCH_CONFIG12_STEPS", 192))
    wildcards = bool(int(os.environ.get("DEMI_BENCH_CONFIG12_WILDCARDS", 0)))
    app = make_raft_app(nodes, bug="multivote")
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    program = dsl_start_events(app) + [
        Send(
            app.actor_name(i % nodes),
            MessageConstructor(lambda v=10 + i: (T_CLIENT, 0, v, 0, 0, 0, 0)),
        )
        for i in range(commands)
    ] + [WaitQuiescence()]
    gen = lambda s: program  # noqa: E731
    cfg = DeviceConfig.for_app(
        app, pool_capacity=96, max_steps=steps, max_external_ops=16,
        invariant_interval=1, timer_weight=0.2,
    )

    # Process warm-up OUTSIDE both measured windows: jax runtime init +
    # first-touch costs would otherwise land in whichever side runs
    # first. (The kernels themselves don't carry over — every driver /
    # checker / lift jits its own closures, so each side pays its own
    # compiles either way; this only evens the process-level start.)
    run_staged(
        app, cfg, config, gen, chunk, chunk=chunk, wildcards=wildcards,
        max_frames=0,
    )
    staged = run_staged(
        app, cfg, config, gen, lanes, chunk=chunk, wildcards=wildcards,
        max_frames=max_mcs,
    )
    if not staged.results:  # pragma: no cover - multivote violates reliably
        return {"error": "no violation found to minimize"}
    with tempfile.TemporaryDirectory() as tmp:
        obs_journal.attach(tmp)
        pipe = StreamingPipeline(
            app, cfg, config, gen, chunk=chunk, split=split, depth=depth,
            wildcards=wildcards, max_frames=max_mcs,
        )
        streaming = pipe.run(lanes)
        recs = obs_journal.read_records(tmp)
        obs_journal.detach()

    # Identity contracts: same frame set, bit-identical artifacts
    # (eid-insensitive — lifts mint fresh ids), same violation codes.
    mcs_match = sorted(staged.results) == sorted(streaming.results) and all(
        frame_signature(staged.results[s])
        == frame_signature(streaming.results[s])
        for s in staged.results
    )
    codes_match = staged.codes == streaming.codes
    assert mcs_match, "streaming MCS artifacts diverged from staged"
    assert codes_match, "violation-code sets diverged"

    # Tier interleave at journal granularity: a minimize.level record
    # between two sweep.chunk records proves minimization ran while the
    # sweep still had chunks in flight.
    sweep_seqs = [r["seq"] for r in recs if r.get("kind") == "sweep.chunk"]
    level_seqs = [
        r["seq"] for r in recs if r.get("kind") == "minimize.level"
    ]
    tiers_interleaved = bool(
        sweep_seqs and level_seqs
        and any(sweep_seqs[0] < s < sweep_seqs[-1] for s in level_seqs)
    )
    enq = [r for r in recs if r.get("kind") == "pipeline.enqueue"]
    frames = [r for r in recs if r.get("kind") == "pipeline.frame"]

    speedup = None
    if staged.mcs_per_hour and streaming.mcs_per_hour:
        speedup = round(streaming.mcs_per_hour / staged.mcs_per_hour, 3)
    return {
        "app": f"raft{nodes}",
        "lanes": lanes,
        "chunk": chunk,
        "max_mcs": max_mcs,
        "split": split,
        "depth": depth,
        "wildcards": wildcards,
        "violations": streaming.violations,
        "mcs_count": streaming.mcs_count,
        "ttf_mcs_staged_s": round(staged.ttf_mcs_s, 3),
        "ttf_mcs_streaming_s": round(streaming.ttf_mcs_s, 3),
        "wall_staged_s": round(staged.wall_s, 3),
        "wall_streaming_s": round(streaming.wall_s, 3),
        "mcs_per_hour_staged": round(staged.mcs_per_hour or 0, 2),
        "mcs_per_hour_streaming": round(streaming.mcs_per_hour or 0, 2),
        "speedup": speedup,
        "mcs_match": mcs_match,
        "codes_match": codes_match,
        "tiers_interleaved": tiers_interleaved,
        "queue": streaming.queue,
        "journal_enqueues": len(enq),
        "journal_frames": len(frames),
        "budget": streaming.budget,
    }


def bench_config13(jax):
    """Sharded exploration fleet scaling curve (demi_tpu/fleet): the
    config-9 deep seeded raft frontier explored by a coordinator +
    worker-process fleet at 1/2/4 workers, leases serialized so each
    worker's busy time is uncontended (1 chip per worker modeled on a
    shared-core CPU host; concurrent virtual workers would time-slice
    the same cores and measure contention, not capacity — the PR 6/12
    CPU-attribution caveat).

    Headline: **aggregate interleavings/sec vs worker count** —
    ``useful interleavings / (total worker busy seconds / workers)``.
    Duplicated exploration (a failed global dedup) would inflate total
    busy and pull the number down, so the curve only scales if the
    frontier partitions evenly AND no worker re-explores another's
    prescriptions. Hard identity contracts, asserted per worker count:

      - explored prescription set, Mazurkiewicz class set,
        violation-code set, and the FIRST found record all bit-identical
        to the single-process DeviceDPOR baseline (sharded exploration
        may differ in order, never in coverage);
      - round count equal to the baseline's (no duplicated rounds).

    Plus the cross-run warm start: the 1-worker run publishes its class
    ledger to a content-addressed store; a second run over the same
    workload loads it and must re-explore ZERO covered classes (only
    the root re-executes), with the skips counted.

    Knobs: DEMI_BENCH_CONFIG13_ROUNDS / _BATCH / _WORKERS ("1,2,4") /
    _BUDGET / _SEEDS / _DEPTH_CAP / _MSGS / _STRICT."""
    import hashlib
    import tempfile

    from demi_tpu.analysis import SleepSets, StaticIndependence, sleep_cap
    from demi_tpu.device.dpor_sweep import DeviceDPOR, steering_prescription
    from demi_tpu.fleet import build_fleet_workload, run_fleet, set_digest
    from demi_tpu.schedulers import RandomScheduler
    from demi_tpu.apps.common import make_host_invariant
    from demi_tpu.config import SchedulerConfig

    nodes, commands = 3, 3
    rounds = int(os.environ.get("DEMI_BENCH_CONFIG13_ROUNDS", 12))
    batch = int(os.environ.get("DEMI_BENCH_CONFIG13_BATCH", 16))
    worker_counts = [
        int(w)
        for w in os.environ.get(
            "DEMI_BENCH_CONFIG13_WORKERS", "1,2,4"
        ).split(",")
    ]
    budget = int(os.environ.get("DEMI_BENCH_CONFIG13_BUDGET", 240))
    seeds = int(os.environ.get("DEMI_BENCH_CONFIG13_SEEDS", 40))
    depth_cap = int(os.environ.get("DEMI_BENCH_CONFIG13_DEPTH_CAP", 120))
    msgs = int(os.environ.get("DEMI_BENCH_CONFIG13_MSGS", 160))
    strict = os.environ.get("DEMI_BENCH_CONFIG13_STRICT", "1") != "0"

    workload = {
        "app": "raft", "nodes": nodes, "bug": "multivote",
        "commands": commands, "max_messages": msgs, "pool": 256,
        "num_events": 12,
    }
    app, cfg, program = build_fleet_workload(workload)
    config = SchedulerConfig(invariant_check=make_host_invariant(app))

    # Seed a deep violating schedule (config-9 shape: deepest violating
    # host execution under the depth cap steers the frontier).
    fr, best = None, -1
    for seed in range(seeds):
        r = RandomScheduler(
            config, seed=seed, max_messages=budget,
            invariant_check_interval=1,
        ).execute(program)
        if r.violation is None:
            continue
        depth = len(r.trace.deliveries())
        if depth <= depth_cap and depth > best:
            fr, best = r, depth
    if fr is None:  # pragma: no cover - multivote violates reliably
        return {"error": "no violation found to seed the frontier"}
    trace = fr.trace
    trace.set_original_externals(list(program))
    presc = steering_prescription(app, cfg, trace, program)

    # Single-process baseline: the same construction the coordinator
    # owns (sleep observe mode tracks classes, content lane keys),
    # drained in coverage mode — the coverage truth every fleet run
    # must match bit-identically.
    rel = StaticIndependence.for_app(app)
    cap = sleep_cap()
    base = DeviceDPOR(
        app, cfg, program, batch_size=batch, prefix_fork=False,
        double_buffer=False,
        sleep_sets=SleepSets(independence=rel, prune=False, cap=cap),
    )
    base.seed(presc)
    t0 = time.perf_counter()
    found = base.explore(max_rounds=rounds, stop_on_violation=False)
    base_wall = time.perf_counter() - t0
    base_explored_sha = set_digest(base.explored)
    base_classes_sha = set_digest(base.sleep.classes)
    base_found_sha = (
        hashlib.sha256(found[0][: found[1]].tobytes()).hexdigest()[:16]
        if found is not None
        else None
    )

    store = tempfile.mkdtemp(prefix="demi_fleet_store_")
    curve = []
    agg1 = None
    for w in worker_counts:
        s = run_fleet(
            workload, workers=w, batch=batch, rounds=rounds,
            seed_prescription=presc, max_outstanding=1,
            # The 1-worker run doubles as the warm-start publisher.
            class_store_dir=store if w == 1 else None,
            timeout=900.0,
        )
        coverage_match = (
            s["explored_sha"] == base_explored_sha
            and s["classes_sha"] == base_classes_sha
        )
        violations_match = s["violation_codes"] == sorted(
            base.violation_codes
        )
        assert coverage_match, (
            f"fleet@{w} coverage diverged from single process"
        )
        assert violations_match, (
            f"fleet@{w} violation codes diverged",
            s["violation_codes"], sorted(base.violation_codes),
        )
        assert s["first_found_sha"] == base_found_sha
        assert s["rounds"] == base.round_index, (
            "fleet executed a different round count",
            s["rounds"], base.round_index,
        )
        agg = s["aggregate_interleavings_per_sec"]
        if w == 1:
            agg1 = agg
        busy_hours = (s["busy_seconds"] / max(1, w)) / 3600.0
        curve.append({
            "workers": w,
            "rounds": s["rounds"],
            "interleavings": s["interleavings"],
            "aggregate_interleavings_per_sec": agg,
            "scaling_x": (
                round(agg / agg1, 3) if agg and agg1 else None
            ),
            "busy_seconds": s["busy_seconds"],
            "wall_seconds": s["wall_seconds"],
            "per_worker": s["per_worker"],
            "violating_rounds": s["violating_rounds"],
            "violations_per_hour": (
                round(s["violating_rounds"] / busy_hours, 1)
                if busy_hours > 0
                else None
            ),
            "coverage_match": coverage_match,
            "violations_match": violations_match,
            "leases_reissued": s["leases_reissued"],
        })
    scaling = {
        str(pt["workers"]): pt["scaling_x"] for pt in curve
    }
    if strict:
        for pt in curve:
            # The acceptance thresholds: >=1.6x at 2 workers, >=2.5x at
            # 4 — the partition is even and dedup global, so the
            # capacity curve tracks the worker count.
            floor = {2: 1.6, 4: 2.5}.get(pt["workers"])
            if floor is not None and pt["scaling_x"] is not None:
                assert pt["scaling_x"] >= floor, (
                    f"scaling at {pt['workers']} workers below target",
                    pt["scaling_x"], floor,
                )

    # Cross-run warm start: the same workload against the published
    # ledger must re-explore ZERO covered classes — only the root round
    # executes, every candidate suppresses as covered.
    warm = run_fleet(
        workload, workers=1, batch=batch, rounds=rounds,
        seed_prescription=presc, max_outstanding=1,
        class_store_dir=store, warm_start=True, prune=True,
        timeout=900.0,
    )
    # Explored beyond the root + seeded entry = classes re-explored
    # (admission is suppressed for covered classes, so this must be 0;
    # the seeded original is pinned into the frontier by seed(), its
    # class was covered by run 1 — count it separately).
    reexplored = max(0, warm["explored"] - 2)
    warm_block = {
        "covered_loaded": warm["warm_covered"],
        "warm_skips": warm["warm_skips"],
        "reexplored_classes": reexplored,
        "explored": warm["explored"],
        "rounds": warm["rounds"],
        "store_segments": warm.get("store", {}).get("segments"),
    }
    assert warm["warm_covered"] > 0
    assert reexplored == 0, warm_block
    if strict:
        assert warm["warm_skips"] > 0, warm_block

    return {
        "app": f"raft{nodes}",
        "batch": batch,
        "rounds": rounds,
        "seed_deliveries": best,
        "sleep_cap": cap,
        "baseline": {
            "interleavings": base.interleavings,
            "explored": len(base.explored),
            "classes": len(base.sleep.classes),
            "violation_codes": sorted(base.violation_codes),
            "rounds": base.round_index,
            "wall_seconds": round(base_wall, 3),
            "device_seconds": round(base.device_seconds, 4),
        },
        "curve": curve,
        "scaling": scaling,
        "coverage_match": all(pt["coverage_match"] for pt in curve),
        "violations_match": all(pt["violations_match"] for pt in curve),
        "warm_start": warm_block,
    }


def bench_config14(jax):
    """Multi-tenant exploration service vs dedicated solo runs
    (demi_tpu/service): N tenants submit the SAME multi-violation raft
    workload (config-12 shape) with per-tenant rng base keys — distinct
    violation sets — and the service batches their fuzz sweeps into
    shared mixed chunks and their minimization frames through pooled
    replay oracles. The baseline runs each tenant as a dedicated solo
    ``StreamingPipeline``, SEQUENTIALLY (serialized uncontended busy
    time — the one-core convention: no wall-clock parallelism claims,
    just fewer compiles and launches for the same artifacts).

    Hard identity contracts, asserted per tenant: MCS artifact
    signatures (eid-insensitive, over the structural-JSON payloads both
    sides persist) and violation-code sets bit-identical between the
    shared-batch service and the solo run. Economy contracts: shared
    compiled executables AND total kernel launches strictly fewer than
    the solo sum (lanes deliberately not a chunk multiple, so solo tail
    chunks pay launches the mixed fill merges away). Headline:
    aggregate MCSes per serialized busy second, service vs
    solo-sequential — the >=1.15x bar is mostly shared-compile economy
    on CPU (each solo run compiles its own sweep kernel, lift kernel,
    and per-shape checkers; the service compiles each once).

    Knobs: DEMI_BENCH_CONFIG14_TENANTS / _LANES / _CHUNK / _MAX_MCS /
    _STEPS / _SPLIT / _WILDCARDS / _STRICT."""
    import tempfile

    from demi_tpu.obs import journal as obs_journal
    from demi_tpu.pipeline import StreamingPipeline
    from demi_tpu.service import (
        ExplorationService,
        artifact_signature,
        build_service_workload,
    )

    nodes, commands = 3, 2
    n_tenants = int(os.environ.get("DEMI_BENCH_CONFIG14_TENANTS", 3))
    lanes = int(os.environ.get("DEMI_BENCH_CONFIG14_LANES", 56))
    chunk = int(os.environ.get("DEMI_BENCH_CONFIG14_CHUNK", 16))
    max_mcs = int(os.environ.get("DEMI_BENCH_CONFIG14_MAX_MCS", 2))
    steps = int(os.environ.get("DEMI_BENCH_CONFIG14_STEPS", 192))
    split = float(os.environ.get("DEMI_BENCH_CONFIG14_SPLIT", 0.5))
    wildcards = bool(
        int(os.environ.get("DEMI_BENCH_CONFIG14_WILDCARDS", 0))
    )
    strict = os.environ.get("DEMI_BENCH_CONFIG14_STRICT", "1") != "0"
    workload = {
        "app": "raft", "nodes": nodes, "bug": "multivote",
        "commands": commands, "max_messages": steps, "pool": 96,
        # num_events keeps max_external_ops at the floor (16) so the
        # solo and service kernels share the config-12 shapes.
        "num_events": 8, "timer_weight": 0.2,
    }
    app, cfg, config, gen, fp = build_service_workload(workload)

    # Process warm-up outside both measured windows (config-12 rule):
    # jax runtime init + first-touch costs land on neither side. Every
    # measured pipeline/service still compiles its own kernels — that
    # asymmetry IS the thing being measured.
    warm = StreamingPipeline(
        app, cfg, config, gen, chunk=chunk, wildcards=wildcards,
        max_frames=0,
    )
    warm.run(chunk)

    # Solo-sequential baseline: one dedicated StreamingPipeline per
    # tenant, run back to back in this process.
    solo = []
    solo_wall = 0.0
    for i in range(n_tenants):
        pipe = StreamingPipeline(
            app, cfg, config, gen, base_key=i, chunk=chunk, split=split,
            wildcards=wildcards, max_frames=max_mcs,
        )
        t0 = time.perf_counter()
        result = pipe.run(lanes)
        wall = time.perf_counter() - t0
        solo_wall += wall
        sigs = {
            f.seed: artifact_signature(f.result)
            for f in pipe.queue.done_frames()
        }
        compiles = (
            1  # the sweep kernel
            + (1 if pipe._lift_kernel is not None else 0)
            + len(pipe._checkers)
        )
        solo.append({
            "tenant": f"t{i}",
            "wall_s": wall,
            "sigs": sigs,
            "codes": {int(s): int(c) for s, c in result.codes.items()},
            "violations": result.violations,
            "mcs": len(sigs),
            "launches": sum(pipe.budget.launches.values()),
            "fuzz_launches": pipe.budget.launches.get("fuzz", 0),
            "compiles": compiles,
        })
    if not any(s["mcs"] for s in solo):  # pragma: no cover
        return {"error": "no violation found to minimize"}

    # Shared-batch service: the same tenants through one engine.
    with tempfile.TemporaryDirectory() as tmp:
        obs_journal.attach(tmp)
        svc = ExplorationService(
            None, split=split, depth=4, default_chunk=chunk,
        )
        job_ids = []
        for i in range(n_tenants):
            job = svc.submit(
                f"t{i}", workload, lanes=lanes, chunk=chunk, base_key=i,
                max_frames=max_mcs, wildcards=wildcards,
            )
            job_ids.append(job["job"])
        t0 = time.perf_counter()
        svc.run_until_idle()
        svc_wall = time.perf_counter() - t0
        recs = obs_journal.read_records(tmp)
        obs_journal.detach()
    savings = svc.savings()

    per_tenant = []
    all_sigs_match = True
    all_codes_match = True
    for i, job_id in enumerate(job_ids):
        job = svc.jobs[job_id]
        frames = svc.job_frames(job_id)
        sigs = {
            int(f["seed"]): artifact_signature(f["result"])
            for f in frames
            if f["status"] == "done"
        }
        sig_match = sigs == solo[i]["sigs"]
        codes_match = job.codes == solo[i]["codes"]
        all_sigs_match &= sig_match
        all_codes_match &= codes_match
        per_tenant.append({
            "tenant": f"t{i}",
            "job": job_id,
            "mcs": len(sigs),
            "violations": job.violations,
            "ttf_mcs_s": job.ttf_mcs_s,
            "artifacts_match": sig_match,
            "codes_match": codes_match,
        })
    assert all_sigs_match, "service MCS artifacts diverged from solo runs"
    assert all_codes_match, "service violation codes diverged from solo"

    solo_launches = sum(s["launches"] for s in solo)
    solo_compiles = sum(s["compiles"] for s in solo)
    svc_launches = sum(savings["launches"].values())
    svc_compiles = savings["compiled_executables"]
    assert svc_compiles < solo_compiles, (
        "service compiled executables not fewer than solo sum",
        svc_compiles, solo_compiles,
    )
    assert svc_launches < solo_launches, (
        "service kernel launches not fewer than solo sum",
        svc_launches, solo_launches,
    )

    mcs_total = sum(s["mcs"] for s in solo)
    rate_solo = mcs_total / solo_wall if solo_wall > 0 else None
    rate_svc = mcs_total / svc_wall if svc_wall > 0 else None
    speedup = (
        round(rate_svc / rate_solo, 3) if rate_solo and rate_svc else None
    )
    if strict and speedup is not None:
        assert speedup >= 1.15, (
            "service MCSes per serialized busy second below the 1.15x "
            "bar vs solo-sequential", speedup,
        )
    svc_frames_recs = [
        r for r in recs if r.get("kind") == "service.frame"
    ]
    svc_chunk_recs = [
        r for r in recs if r.get("kind") == "service.chunk"
    ]
    return {
        "app": f"raft{nodes}",
        "tenants": n_tenants,
        "lanes": lanes,
        "chunk": chunk,
        "max_mcs": max_mcs,
        "split": split,
        "wildcards": wildcards,
        "mcs_total": mcs_total,
        "per_tenant": per_tenant,
        "artifacts_match": all_sigs_match,
        "codes_match": all_codes_match,
        "wall_solo_sequential_s": round(solo_wall, 3),
        "wall_service_s": round(svc_wall, 3),
        "mcs_per_busy_hour_solo": (
            round(rate_solo * 3600.0, 2) if rate_solo else None
        ),
        "mcs_per_busy_hour_service": (
            round(rate_svc * 3600.0, 2) if rate_svc else None
        ),
        "speedup": speedup,
        "solo_launches": solo_launches,
        "service_launches": svc_launches,
        "launches_saved": solo_launches - svc_launches,
        "solo_compiles": solo_compiles,
        "service_compiles": svc_compiles,
        "compiles_saved": solo_compiles - svc_compiles,
        "savings": savings,
        "journal_frames": len(svc_frames_recs),
        "journal_chunks": len(svc_chunk_recs),
        "journal_mixed_chunks": sum(
            1 for r in svc_chunk_recs if r.get("mixed")
        ),
    }


def bench_config15(jax):
    """Pod-wide tracing + health-plane overhead (demi_tpu/obs
    distributed): the SAME 2-worker fleet run twice — once with the full
    observability plane ON (DEMI_OBS spans, round journal, span
    sidecars, per-connection clock sync, straggler scan, byte-footprint
    gauges) and once with everything OFF. The acceptance bar is < 1% of
    per-round busy time — the number that lets fleet tracing default ON
    wherever a journal dir exists (the config-11 discipline applied to
    the distributed plane). Also asserts:

      - tracing changes NOTHING about the search (explored-set digest,
        class digest, violation codes bit-identical across the A/B);
      - `trace stitch` over the traced run's dir produces ONE Perfetto
        timeline containing the coordinator and every worker process,
        with clock-aligned non-negative span durations.

    Knobs: DEMI_BENCH_CONFIG15_ROUNDS / _BATCH / _WORKERS / _MSGS."""
    import tempfile

    from demi_tpu import obs
    from demi_tpu.fleet import run_fleet
    from demi_tpu.obs import distributed as dtrace

    nodes = 3
    rounds = int(os.environ.get("DEMI_BENCH_CONFIG15_ROUNDS", 8))
    batch = int(os.environ.get("DEMI_BENCH_CONFIG15_BATCH", 16))
    workers = int(os.environ.get("DEMI_BENCH_CONFIG15_WORKERS", 2))
    msgs = int(os.environ.get("DEMI_BENCH_CONFIG15_MSGS", 48))
    workload = {
        "app": "raft", "nodes": nodes, "bug": "multivote",
        "max_messages": msgs, "pool": 64, "num_events": 8,
    }

    def run(journal_dir):
        # The obs switch rides the coordinator's config message, so the
        # spawned workers inherit it; busy seconds (worker-side lease
        # execution, compile excluded by the warm-up) are the honest
        # denominator — wall would mostly measure process spawn.
        if journal_dir is not None:
            obs.enable()
        try:
            s = run_fleet(
                workload, workers=workers, batch=batch, rounds=rounds,
                journal_dir=journal_dir, timeout=900.0,
            )
        finally:
            if journal_dir is not None:
                obs.disable()
        rps = (
            s["rounds"] / s["busy_seconds"]
            if s.get("busy_seconds") else None
        )
        return s, rps

    plain, rps_off = run(None)
    with tempfile.TemporaryDirectory() as tmp:
        traced, rps_on = run(tmp)
        # Observing the fleet must not change the fleet.
        assert traced["explored_sha"] == plain["explored_sha"], (
            "tracing changed the explored set"
        )
        assert traced.get("classes_sha") == plain.get("classes_sha"), (
            "tracing changed the class ledger"
        )
        assert traced["violation_codes"] == plain["violation_codes"], (
            "tracing changed the violation codes"
        )
        stitched = dtrace.stitch(
            [tmp], os.path.join(tmp, "trace-stitched.json")
        )
        procs = stitched["processes"]
        assert "coordinator" in procs, procs
        worker_procs = [p for p in procs if p.startswith("worker-")]
        assert len(worker_procs) == workers, procs
        assert stitched["spans"] > 0, stitched
    overhead_pct = None
    if rps_off and rps_on:
        overhead_pct = round(
            max(0.0, (1.0 / rps_on - 1.0 / rps_off) * rps_off) * 100, 3
        )
    return {
        "app": f"raft{nodes}",
        "workers": workers,
        "batch": batch,
        "rounds": traced["rounds"],
        "explored_match": traced["explored_sha"] == plain["explored_sha"],
        "violations_match": (
            traced["violation_codes"] == plain["violation_codes"]
        ),
        "stitched_processes": procs,
        "stitched_spans": stitched["spans"],
        "stitched_journal_records": stitched["journal_records"],
        "stragglers": traced.get("stragglers", 0),
        "rounds_per_busy_sec_plain": (
            round(rps_off, 2) if rps_off is not None else None
        ),
        "rounds_per_busy_sec_traced": (
            round(rps_on, 2) if rps_on is not None else None
        ),
        "tracing_overhead_pct": overhead_pct,
    }


def bench_config16(jax):
    """Sharded coordinator host half (demi_tpu/fleet/shard): the
    config-13 deep seeded raft frontier drained at 1/2/4 admission
    shards — the per-round racing scan + static/sleep filter + digest
    dedup partitioned by prescription content-digest range and run
    concurrently, with a serial canonical merge that keeps every
    explored/class/violation set, the frontier, and the first-found
    record bit-identical to the 1-shard pipeline.

    Headline: **host-half rounds/sec vs shard count** under the
    uncontended shared-core convention (DEMI_HOST_SHARD_SERIALIZE=1:
    each shard's scan+dedup timed sequentially and billed as
    ``busy/n`` — capacity, not time-slicing contention; the serial
    merge always counts at wall; at 1 shard the metric is the plain
    measured wall). Hard contracts, asserted per point:

      - full search identity (explored set AND log order, frontier
        order, digest sets, class ledger, violation codes, wakeup
        guides, first-found bytes) bit-identical to 1 shard;
      - an N→M re-sharded resume: one 2-shard checkpoint restored into
        1/2/4 shards, each continued — all three final states (and the
        source instance's own continuation) bit-identical;
      - a kill-mid-lease fleet run (2 workers x 2 host shards, one
        worker dies after its first lease) bit-identical to the
        single-process baseline, with at least one lease re-issued.

    Knobs: DEMI_BENCH_CONFIG16_ROUNDS / _BATCH / _SHARDS ("1,2,4") /
    _BUDGET / _SEEDS / _DEPTH_CAP / _MSGS / _STRICT / _FLEET /
    _FLEET_ROUNDS."""
    import hashlib

    from demi_tpu.analysis import SleepSets, StaticIndependence, sleep_cap
    from demi_tpu.device.dpor_sweep import (
        DeviceDPOR,
        make_dpor_kernel,
        steering_prescription,
    )
    from demi_tpu.fleet import build_fleet_workload, run_fleet, set_digest
    from demi_tpu.schedulers import RandomScheduler
    from demi_tpu.apps.common import make_host_invariant
    from demi_tpu.config import SchedulerConfig
    from demi_tpu.fleet.shard import HostHalfTimer

    nodes, commands = 3, 3
    rounds = int(os.environ.get("DEMI_BENCH_CONFIG16_ROUNDS", 10))
    batch = int(os.environ.get("DEMI_BENCH_CONFIG16_BATCH", 16))
    shard_counts = [
        int(s)
        for s in os.environ.get(
            "DEMI_BENCH_CONFIG16_SHARDS", "1,2,4"
        ).split(",")
    ]
    budget = int(os.environ.get("DEMI_BENCH_CONFIG16_BUDGET", 240))
    seeds = int(os.environ.get("DEMI_BENCH_CONFIG16_SEEDS", 40))
    depth_cap = int(os.environ.get("DEMI_BENCH_CONFIG16_DEPTH_CAP", 120))
    msgs = int(os.environ.get("DEMI_BENCH_CONFIG16_MSGS", 160))
    strict = os.environ.get("DEMI_BENCH_CONFIG16_STRICT", "1") != "0"
    fleet_on = os.environ.get("DEMI_BENCH_CONFIG16_FLEET", "1") != "0"
    fleet_rounds = int(os.environ.get("DEMI_BENCH_CONFIG16_FLEET_ROUNDS", 6))

    workload = {
        "app": "raft", "nodes": nodes, "bug": "multivote",
        "commands": commands, "max_messages": msgs, "pool": 256,
        "num_events": 12,
    }
    app, cfg, program = build_fleet_workload(workload)
    config = SchedulerConfig(invariant_check=make_host_invariant(app))

    # Seed a deep violating schedule (the config-13 frontier shape).
    fr, best = None, -1
    for seed in range(seeds):
        r = RandomScheduler(
            config, seed=seed, max_messages=budget,
            invariant_check_interval=1,
        ).execute(program)
        if r.violation is None:
            continue
        depth = len(r.trace.deliveries())
        if depth <= depth_cap and depth > best:
            fr, best = r, depth
    if fr is None:  # pragma: no cover - multivote violates reliably
        return {"error": "no violation found to seed the frontier"}
    trace = fr.trace
    trace.set_original_externals(list(program))
    presc = steering_prescription(app, cfg, trace, program)

    rel = StaticIndependence.for_app(app)
    cap = sleep_cap()
    # Shared sleep-mode kernel (cap > 0 builds the sleep variant): every
    # instance in the A/B compiles nothing after the first.
    kernel = make_dpor_kernel(
        app, cfg, sleep_cap=cap, commute_matrix=rel.device_matrix(),
    )

    def make(n):
        return DeviceDPOR(
            app, cfg, program, batch_size=batch, prefix_fork=False,
            double_buffer=False, kernel=kernel,
            sleep_sets=SleepSets(independence=rel, prune=False, cap=cap),
            host_shards=n,
        )

    def identity(d, found):
        # Full bit-identity, not just coverage: log ORDER, frontier
        # ORDER, digest sets, and the found record's bytes all count.
        return (
            frozenset(d.explored), tuple(d._explored_log),
            tuple(d.frontier), frozenset(d._explored_digests),
            frozenset(d._suppressed_digests),
            tuple(sorted(d.violation_codes)),
            frozenset(d.sleep.classes), d.interleavings,
            None if found is None else found[0][: found[1]].tobytes(),
        )

    def close_sharder(d):
        sharder = getattr(d, "_sharder", None)
        if sharder is not None:
            sharder.close()

    # -- the A/B curve: uncontended host-half rounds/sec per shard count
    prev_serialize = os.environ.get("DEMI_HOST_SHARD_SERIALIZE")
    os.environ["DEMI_HOST_SHARD_SERIALIZE"] = "1"
    curve = []
    ident1 = rate1 = None
    try:
        for n in shard_counts:
            d = make(n)
            d.seed(presc)
            # Warm-up round: compiles the kernel and seeds the frontier
            # outside the timed window (the timer only bills the host
            # half, but the first round's allocations are noise too).
            d.explore(max_rounds=1, stop_on_violation=False)
            timer = HostHalfTimer(d)
            found = d.explore(max_rounds=rounds, stop_on_violation=False)
            rate = timer.rounds_per_sec()
            ident = identity(d, found)
            close_sharder(d)
            if ident1 is None:
                ident1, rate1 = ident, rate
            bit_match = ident == ident1
            assert bit_match, (
                f"host shards={n} diverged from the 1-shard pipeline"
            )
            curve.append({
                "shards": n,
                "rounds": timer.rounds,
                "host_seconds": round(timer.uncontended_seconds(), 4),
                "host_rounds_per_sec": round(rate, 2),
                "host_x": round(rate / rate1, 3) if rate1 else None,
                "bit_match": bit_match,
            })
    finally:
        if prev_serialize is None:
            os.environ.pop("DEMI_HOST_SHARD_SERIALIZE", None)
        else:
            os.environ["DEMI_HOST_SHARD_SERIALIZE"] = prev_serialize
    scaling = {str(pt["shards"]): pt["host_x"] for pt in curve}
    if strict:
        for pt in curve:
            # Acceptance floors: >=1.6x at 2 shards, >=2.5x at 4 — the
            # parallel sections dominate the host half and the serial
            # merge stays cheap (dups skip in bulk).
            floor = {2: 1.6, 4: 2.5}.get(pt["shards"])
            if floor is not None and pt["host_x"] is not None:
                assert pt["host_x"] >= floor, (
                    f"host-shard scaling at {pt['shards']} below target",
                    pt["host_x"], floor,
                )

    # -- N -> M re-sharded resume: one 2-shard checkpoint restored into
    # every shard count; all continuations must land bit-identical
    # (checkpoints serialize digests FLAT, so restore re-partitions).
    r1 = max(1, rounds // 2)
    r2 = max(1, rounds - r1)
    src = make(2)
    src.seed(presc)
    src.explore(max_rounds=r1, stop_on_violation=False)
    payload = src.checkpoint_state()
    reshard_ident = None
    for n in shard_counts:
        dm = make(n)
        dm.restore_state(payload)
        found = dm.explore(max_rounds=r2, stop_on_violation=False)
        ident = identity(dm, found)
        close_sharder(dm)
        if reshard_ident is None:
            reshard_ident = ident
        assert ident == reshard_ident, (
            f"2->{n} re-sharded resume diverged"
        )
    found = src.explore(max_rounds=r2, stop_on_violation=False)
    assert identity(src, found) == reshard_ident, (
        "re-sharded resumes diverged from the source instance"
    )
    close_sharder(src)

    # -- kill-mid-lease fleet parity at 2 host shards: the sharded
    # coordinator host half under re-lease churn must still match the
    # single-process baseline bit-for-bit.
    fleet_block = None
    if fleet_on:
        base = make(1)
        base.seed(presc)
        bfound = base.explore(max_rounds=fleet_rounds, stop_on_violation=False)
        s = run_fleet(
            workload, workers=2, batch=batch, rounds=fleet_rounds,
            seed_prescription=presc, max_outstanding=1, host_shards=2,
            worker_env={"w0": {"DEMI_FLEET_DIE_AFTER": "1"}},
            timeout=900.0,
        )
        base_found_sha = (
            hashlib.sha256(
                bfound[0][: bfound[1]].tobytes()
            ).hexdigest()[:16]
            if bfound is not None
            else None
        )
        fleet_block = {
            "workers": 2,
            "host_shards": 2,
            "rounds": s["rounds"],
            "leases_reissued": s["leases_reissued"],
            "worker_returncodes": s["worker_returncodes"],
            "coverage_match": (
                s["explored_sha"] == set_digest(base.explored)
                and s["classes_sha"] == set_digest(base.sleep.classes)
            ),
            "violations_match": (
                s["violation_codes"] == sorted(base.violation_codes)
            ),
            "first_found_match": s["first_found_sha"] == base_found_sha,
        }
        assert fleet_block["coverage_match"], (
            "sharded fleet coverage diverged under kill-mid-lease"
        )
        assert fleet_block["violations_match"]
        assert fleet_block["first_found_match"]
        assert 17 in s["worker_returncodes"], s["worker_returncodes"]
        assert s["leases_reissued"] >= 1, s["leases_reissued"]

    return {
        "app": f"raft{nodes}",
        "batch": batch,
        "rounds": rounds,
        "seed_deliveries": best,
        "sleep_cap": cap,
        "curve": curve,
        "scaling": scaling,
        "bit_identical": all(pt["bit_match"] for pt in curve),
        "reshard_resume_match": True,
        **({"fleet": fleet_block} if fleet_block is not None else {}),
    }


def bench_config17(jax):
    """Differential exploration (analysis/delta.py): re-verification
    cost proportional to the change cone. The config-13 deep seeded
    raft frontier is explored once and its class ledger published with
    an effect-signature manifest; then ONE raft handler is edited
    (``refactor:heartbeat`` — behavior- and effect-identical, code
    digest moves) and the edited app re-verifies two ways:

      - **scratch**: full re-exploration (today's cost of any edit);
      - **delta**: ``delta_warm_start`` diffs the stored manifest vs
        the edited app's, transfers every stored class whose
        reversal-chain tag footprint avoids the change cone (never
        re-executed), and re-seeds only the cone classes onto the
        frontier via their stored guides.

    Headline: **re-explored classes, scratch / delta** — the floor is
    >=3x (the cone must be a minority of the frontier). Hard contracts,
    all asserted: the delta run's effective violation-code set AND
    per-code canonical witness digests bit-identical to scratch; the
    audit (full scratch class set vs the delta run's transferred +
    re-explored + pending set) bit-identical — zero unsoundly skipped
    classes; and an ``opaque`` edit (a while-loop the static effects
    analyzer cannot see through) degrades to FULL scratch
    re-exploration, also bit-identical.

    Knobs: DEMI_BENCH_CONFIG17_ROUNDS / _BATCH / _BUDGET / _SEEDS /
    _DEPTH_CAP / _MSGS / _STRICT / _EDIT / _FLOOR."""
    import tempfile

    from demi_tpu.analysis import SleepSets, StaticIndependence, sleep_cap
    from demi_tpu.analysis.delta import (
        build_run_ledger,
        delta_warm_start,
        effective_violations,
    )
    from demi_tpu.apps.common import make_host_invariant
    from demi_tpu.config import SchedulerConfig
    from demi_tpu.device.dpor_sweep import DeviceDPOR, steering_prescription
    from demi_tpu.fleet import build_fleet_workload, set_digest
    from demi_tpu.fleet.ledger import ClassStore
    from demi_tpu.persist.checkpoint import handler_fingerprint
    from demi_tpu.schedulers import RandomScheduler

    nodes, commands = 3, 3
    rounds = int(os.environ.get("DEMI_BENCH_CONFIG17_ROUNDS", 12))
    batch = int(os.environ.get("DEMI_BENCH_CONFIG17_BATCH", 16))
    budget = int(os.environ.get("DEMI_BENCH_CONFIG17_BUDGET", 240))
    seeds = int(os.environ.get("DEMI_BENCH_CONFIG17_SEEDS", 40))
    depth_cap = int(os.environ.get("DEMI_BENCH_CONFIG17_DEPTH_CAP", 120))
    msgs = int(os.environ.get("DEMI_BENCH_CONFIG17_MSGS", 160))
    strict = os.environ.get("DEMI_BENCH_CONFIG17_STRICT", "1") != "0"
    edit = os.environ.get(
        "DEMI_BENCH_CONFIG17_EDIT", "refactor:heartbeat"
    )
    floor = float(os.environ.get("DEMI_BENCH_CONFIG17_FLOOR", "3.0"))

    base_workload = {
        "app": "raft", "nodes": nodes, "bug": "multivote",
        "commands": commands, "max_messages": msgs, "pool": 256,
        "num_events": 12,
    }
    app1, cfg, program = build_fleet_workload(base_workload)
    config = SchedulerConfig(invariant_check=make_host_invariant(app1))

    # Seed a deep violating schedule (config-13 shape).
    fr, best = None, -1
    for seed in range(seeds):
        r = RandomScheduler(
            config, seed=seed, max_messages=budget,
            invariant_check_interval=1,
        ).execute(program)
        if r.violation is None:
            continue
        depth = len(r.trace.deliveries())
        if depth <= depth_cap and depth > best:
            fr, best = r, depth
    if fr is None:  # pragma: no cover - multivote violates reliably
        return {"error": "no violation found to seed the frontier"}
    trace = fr.trace
    trace.set_original_externals(list(program))
    presc = steering_prescription(app1, cfg, trace, program)

    cap = sleep_cap()

    def run(workload, store_dir=None, delta=False):
        """One exploration of a (possibly edited) workload: sleep-set
        pruning on, guides retained, content lane keys (the sleep-mode
        default) so a re-seeded prescription's execution is a pure
        function of its content — what makes delta-vs-scratch equality
        exact, not statistical."""
        app, cfg_w, program_w = build_fleet_workload(workload)
        sl = SleepSets(
            independence=StaticIndependence.for_app(app), prune=True,
            cap=cap, retain_guides=True,
        )
        d = DeviceDPOR(
            app, cfg_w, program_w, batch_size=batch, prefix_fork=False,
            double_buffer=False, sleep_sets=sl,
        )
        # Closed seeded exploration: padding lanes never admit races, so
        # every class descends from the seed and carries an exact
        # trunk-divergence index — the scratch and delta legs verify the
        # SAME class universe and the transfer test is prescription-
        # granular instead of saturating on random-lane lineage.
        d.pad_exploration = False
        d.seed(presc)
        stats = None
        if delta:
            store = ClassStore(store_dir, handler_fingerprint(app))
            stats = delta_warm_start(d, store, app)
        t0 = time.perf_counter()
        d.explore(max_rounds=rounds, stop_on_violation=False)
        wall = time.perf_counter() - t0
        return d, app, stats, wall

    # v1: explore the original app, publish classes + manifest + guides.
    store = tempfile.mkdtemp(prefix="demi_delta_store_")
    d1, _, _, wall1 = run(base_workload)
    ClassStore(store, handler_fingerprint(app1)).publish(
        build_run_ledger(d1, app1)
    )

    def executed(d):
        # explored counts admissions; subtract what never left the
        # frontier (and the root + seeded original) to get the classes
        # this run actually re-executed.
        return max(0, len(d.explored) - len(d.frontier) - 2)

    # v2 (the one-handler edit), scratch vs differential.
    workload2 = {**base_workload, "handler_edit": edit}
    ds, _, _, wall_scratch = run(workload2)
    dd, app2, stats, wall_delta = run(workload2, store_dir=store, delta=True)
    assert stats is not None and not stats["full"], stats

    scratch_codes, scratch_wits = effective_violations(ds)
    delta_codes, delta_wits = effective_violations(dd, stats)
    violations_match = delta_codes == scratch_codes
    witnesses_match = delta_wits == scratch_wits
    reexplored_scratch = executed(ds)
    reexplored_delta = executed(dd)
    reduction_x = round(
        reexplored_scratch / max(1, reexplored_delta), 3
    )
    # The audit: the differential run's class set (transferred +
    # re-explored + pending) must equal the full scratch exploration's
    # — zero unsoundly skipped classes.
    audit_sound = (
        set_digest(dd.sleep.classes) == set_digest(ds.sleep.classes)
        and violations_match
        and witnesses_match
    )
    assert violations_match, (delta_codes, scratch_codes)
    assert witnesses_match, (delta_wits, scratch_wits)
    assert audit_sound
    if strict:
        assert reduction_x >= floor, (
            f"delta reduction {reduction_x}x below the {floor}x floor",
            reexplored_scratch, reexplored_delta, stats,
        )

    # Unknown-effects leg: an opaque edit (analyzer bails) must degrade
    # to a FULL scratch re-exploration — nothing transferred, coverage
    # still bit-identical to scratch.
    opaque_edit = "opaque:" + (edit.partition(":")[2] or "request_vote")
    workload3 = {**base_workload, "handler_edit": opaque_edit}
    d3, _, stats3, wall_opaque = run(workload3, store_dir=store, delta=True)
    unknown_degrades = (
        stats3 is not None
        and bool(stats3["full"])
        and stats3["transferred"] == 0
        and len(d3.explored) == len(ds.explored)
        and set_digest(d3.sleep.classes) == set_digest(ds.sleep.classes)
    )
    assert unknown_degrades, stats3

    return {
        "app": f"raft{nodes}",
        "batch": batch,
        "rounds": rounds,
        "seed_deliveries": best,
        "sleep_cap": cap,
        "edit": edit,
        "changed_tags": stats["changed_tags"],
        "cone_tags": stats["cone_tags"],
        "cone_size": len(stats["cone_tags"]),
        "stored_classes": stats["stored_classes"],
        "transferred": stats["transferred"],
        "reseeded": stats["reseeded"],
        "pending": stats["pending"],
        "skipped_launches": stats["skipped_launches"],
        "reexplored_scratch": reexplored_scratch,
        "reexplored_delta": reexplored_delta,
        "reduction_x": reduction_x,
        "violation_codes": delta_codes,
        "violations_match": violations_match,
        "witnesses_match": witnesses_match,
        "audit_sound": audit_sound,
        "unknown_degrades": unknown_degrades,
        "opaque_reason": (stats3 or {}).get("reason"),
        "walls": {
            "v1_seconds": round(wall1, 3),
            "scratch_seconds": round(wall_scratch, 3),
            "delta_seconds": round(wall_delta, 3),
            "opaque_seconds": round(wall_opaque, 3),
            "wall_reduction_x": round(
                wall_scratch / max(1e-9, wall_delta), 3
            ),
        },
    }


def bench_config5_rehearsal(jax, total_lanes=None):
    """Config-5 machinery rehearsal at >=1e5 lanes (VERDICT r3 #6): the
    64-actor *reliable* flood runs ~1 lane/sec on CPU, so the full config
    5 sweep is TPU-only — but the parts that must not fall over at 1e5+
    lanes (continuous harvesting, refill, uint32 hash-dedup memory,
    overflow accounting) are workload-independent. This block drives them
    with a 64-actor UNRELIABLE broadcast (same actor count, ~1/70th the
    per-lane step cost) and records occupancy, dedup stats, harvest
    overhead, and peak RSS. DEMI_BENCH_REHEARSAL_LANES overrides."""
    import resource

    from demi_tpu.apps.broadcast import make_broadcast_app
    from demi_tpu.apps.common import dsl_start_events
    from demi_tpu.device import DeviceConfig
    from demi_tpu.device.continuous import ContinuousSweepDriver
    from demi_tpu.device.core import ST_OVERFLOW
    from demi_tpu.external_events import (
        Kill,
        MessageConstructor,
        Send,
        WaitQuiescence,
    )

    n = 64
    # No-relay broadcast, externally fanned out to every node: same actor
    # count and invariant as config 5, ~1/70th the per-lane step cost
    # (the reliable relay flood is O(n^2) deliveries; this is O(n)), and
    # every lane still has 64!-rich delivery orderings for the dedup
    # machinery plus kill-class lanes that strand deliveries into real
    # disagreement violations.
    app = make_broadcast_app(n, reliable=False)
    cfg = DeviceConfig.for_app(
        app, pool_capacity=96, max_steps=224, max_external_ops=136,
        invariant_interval=0, early_exit=True,
    )
    starts = dsl_start_events(app)

    def program_gen(seed):
        prog = list(starts) + [
            Send(app.actor_name(i), MessageConstructor(lambda: (1, 0)))
            for i in range(n)
        ]
        if seed % 3 == 0:
            prog.append(Kill(app.actor_name(seed % n)))
        prog.append(WaitQuiescence())
        return prog

    if total_lanes is None:
        total_lanes = int(
            os.environ.get("DEMI_BENCH_REHEARSAL_LANES", 100_000)
        )
    # The generator is periodic in the seed: skip re-lowering on refill
    # (the honest scale fix — host lowering otherwise dominates at 1e5+
    # lanes). RNG still uses raw seeds, so equal programs keep distinct
    # schedules.
    program_key = lambda s: (s % n) if s % 3 == 0 else -1  # noqa: E731

    batch, seg = 512, 48
    autotune_info = None
    from demi_tpu.tune import autotune_enabled

    if autotune_enabled():
        # Measurement-guided shape selection: short calibration reps over
        # (kernel variant, batch, segment length), warm-up rep dropped,
        # decision persisted to the tuning cache — a second DEMI_AUTOTUNE
        # run reuses it and launches no calibration kernels. Variants:
        # early-exit is already on; round delivery is semantics-equal
        # here (invariant_interval=0 checks only at quiescence); the
        # trailing lane axis is a chunked-kernel knob, not a continuous
        # driver one, so it is not a candidate.
        from demi_tpu.device.explore import variant_config
        from demi_tpu.tune import TuningCache, calibrate_sweep, median_rate

        # Calibration reps must be >= one full batch of lanes: _run
        # specializes its kernels to min(batch, total_lanes), so smaller
        # probes would compile shapes the tuned drive never uses. That
        # makes each point cost ~3 batches — keep the CPU axes lean (the
        # wide axes are a TPU budget). Round variants are TPU-only
        # candidates here: one round step costs ~num_actors seq steps,
        # and this workload is injection-dominated (~2 externals per
        # delivery), so on CPU the probe alone would dwarf the drive.
        on_cpu = jax.devices()[0].platform == "cpu"
        reps = 1 if on_cpu else 2  # measured reps after the warm-up

        def seg_for(params):
            # A round step delivers up to one message per receiver, so a
            # segment of S round steps covers ~S*n deliveries; scale the
            # seg knob down for round variants or every segment pays
            # ~n times the intended work on mostly-frozen lanes.
            s = int(params["seg"])
            if "-round" in params["variant"]:
                return max(4, s // 8)
            return s

        def measure(params):
            k_cfg = variant_config(cfg, params["variant"])
            d = ContinuousSweepDriver(
                app, k_cfg, program_gen, batch=int(params["chunk"]),
                seg_steps=seg_for(params), program_key=program_key,
            )
            d.sweep(d.batch + 64)  # compile outside the timed reps
            rates = []
            for _rep in range(reps + 1):  # first rep dropped as warm-up
                t0 = time.perf_counter()
                for _ in d.sweep_iter(d.batch):
                    pass
                rates.append(d.batch / (time.perf_counter() - t0))
            return median_rate(rates)

        decision = calibrate_sweep(
            app, cfg, program_gen, chunk=512, cache=TuningCache(),
            measure=measure,
            axes={
                "variant": (
                    ["xla-ee"] if on_cpu else ["xla-ee", "xla-round-ee"]
                ),
                "chunk": [256, 512] if on_cpu else [256, 512, 1024],
                "seg": [32, 48] if on_cpu else [32, 48, 64],
            },
            extra_key={"drive": "rehearsal"},
        )
        batch = int(decision.params["chunk"])
        seg = seg_for(decision.params)
        cfg = variant_config(cfg, decision.params["variant"])
        autotune_info = decision.to_json()

    drv = ContinuousSweepDriver(
        app, cfg, program_gen, batch=batch, seg_steps=seg,
        program_key=program_key,
    )
    # Warm-up/compile outside the timed window — at the REAL batch shape
    # (a smaller warm-up batch would jit different shapes and the timed
    # window would re-trace; measured ~3.4s of hidden compile), and past
    # one batch so the refill kernel compiles too.
    drv.sweep(drv.batch + 64)
    hashes = np.zeros(total_lanes, np.uint32)
    got = kept = violations = overflow = 0
    t0 = time.perf_counter()
    for _seed, st, code, h in drv._run(total_lanes):
        if st == ST_OVERFLOW:
            overflow += 1
        else:
            hashes[kept] = h
            kept += 1
        got += 1
        violations += code != 0
    secs = time.perf_counter() - t0
    uniq = np.unique(hashes[:kept])
    return {
        "actors": n,
        "lanes": got,
        "schedules_per_sec": round(got / secs, 1),
        "seconds": round(secs, 2),
        "violations": int(violations),
        "unique_schedules": int(uniq.size),
        "overflow_lanes": overflow,
        "occupancy": round(drv.last_occupancy, 3),
        "dedup_memory_bytes": int(hashes.nbytes),
        "segment_seconds": round(drv.last_segment_seconds, 2),
        "harvest_seconds": round(drv.last_harvest_seconds, 2),
        "harvest_fraction": round(
            drv.last_harvest_seconds
            / max(drv.last_segment_seconds + drv.last_harvest_seconds, 1e-9),
            3,
        ),
        "peak_rss_mb": round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024, 1
        ),
        # Only under DEMI_AUTOTUNE=1 — the off-path output keys are
        # byte-identical to the untuned bench.
        **({"autotune": autotune_info} if autotune_info is not None else {}),
    }


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", default=None,
                        help="run only one section: 2, 3, 4, 5, 6, 7, 8, "
                             "9, 10, 11, 12, 13, 14, 15, 16, 17, or "
                             "'rehearsal'")
    args = parser.parse_args()
    if args.config is not None and args.config != "rehearsal":
        args.config = int(args.config)

    import jax

    from demi_tpu import obs

    def emit(out):
        # Telemetry is OFF by default (the headline must measure the
        # kernels, not the bookkeeping); DEMI_OBS=1 folds the registry
        # snapshot into the record for instrumented bench runs.
        if obs.enabled():
            out["obs"] = obs.REGISTRY.snapshot()
        print(json.dumps(out))

    platform = jax.devices()[0].platform

    out = {
        "metric": "unique schedules explored/sec/chip (5-node raft fuzz, per-delivery invariant checks)",
        "unit": "schedules/sec",
        "platform": platform,
    }
    # configs 2/3 count schedule EXECUTIONS per second like every other
    # section (one DPOR interleaving = one explored schedule, one oracle
    # replay = one replayed schedule), so the 10k/s/chip north star is
    # the shared denominator; unit strings name the execution kind.
    if args.config == 2:
        out["metric"] = (
            "interleavings/sec (DeviceDPOR frontier search, 3-node raft)"
        )
        out["unit"] = "interleavings/sec"
        out["config2"] = bench_config2(jax)
        out["value"] = out["config2"]["interleavings_per_sec"]
        out["vs_baseline"] = round((out["value"] or 0) / 10_000.0, 3)
        emit(out)
        return
    if args.config == 3:
        out["metric"] = (
            "oracle replays/sec (batched DDMin, unreliable broadcast)"
        )
        out["unit"] = "replays/sec"
        out["config3"] = bench_config3(jax)
        out["value"] = out["config3"].get("replays_per_sec")
        out["vs_baseline"] = round((out["value"] or 0) / 10_000.0, 3)
        emit(out)
        return
    if args.config == 4:
        out["metric"] = (
            "schedules/sec (Spark DAGScheduler fuzz, job-completion invariant)"
        )
        out["config4"] = bench_config4(jax)
        out["value"] = out["config4"]["schedules_per_sec"]
        out["vs_baseline"] = round(out["value"] / 10_000.0, 3)
        emit(out)
        return
    if args.config == 5:
        out["metric"] = (
            "schedules/sec (64-actor reliable-broadcast sweep)"
        )
        out["config5"] = bench_config5(jax)
        out["value"] = out["config5"]["schedules_per_sec"]
        out["vs_baseline"] = round(out["value"] / 10_000.0, 3)
        emit(out)
        return
    if args.config == 6:
        out["metric"] = (
            "oracle trials/sec (prefix-fork internal-minimization level, raft)"
        )
        out["unit"] = "trials/sec"
        out["config6"] = bench_config6(jax)
        out["value"] = out["config6"].get("fork_trials_per_sec")
        out["vs_baseline"] = round((out["value"] or 0) / 10_000.0, 3)
        emit(out)
        return
    if args.config == 7:
        out["metric"] = (
            "pipeline speedup (async vs sync minimization, deep raft "
            "ddmin+internal)"
        )
        out["unit"] = "x"
        out["config7"] = bench_config7(jax)
        out["value"] = out["config7"].get("speedup")
        # Target: >= 1.3x end-to-end on CPU at the default depth.
        out["vs_baseline"] = round((out["value"] or 0) / 1.3, 3)
        emit(out)
        return
    if args.config == 8:
        out["metric"] = (
            "frontier rounds/sec (async vs sync DeviceDPOR, 3-node raft)"
        )
        out["unit"] = "rounds/sec"
        out["config8"] = bench_config8(jax)
        out["value"] = out["config8"].get("async_rounds_per_sec")
        # Target: >= 1.2x over the synchronous scratch loop on CPU.
        out["vs_baseline"] = round((out["config8"].get("speedup") or 0) / 1.2, 3)
        emit(out)
        return
    if args.config == 9:
        out["metric"] = (
            "redundancy ratio (explored/classes, sleep-set DPOR A/B, "
            "3-node raft)"
        )
        out["unit"] = "ratio"
        out["config9"] = bench_config9(jax)
        out["value"] = out["config9"].get("redundancy_ratio_pruned")
        # Target: the pruned run sits at the class lower bound (1.0)
        # while the unpruned baseline drifts above it.
        base_ratio = out["config9"].get("redundancy_ratio_base") or 0
        out["vs_baseline"] = (
            round(base_ratio / out["value"], 3) if out["value"] else None
        )
        emit(out)
        return
    if args.config == 10:
        out["metric"] = (
            "checkpoint overhead % (durable DPOR frontier, 3-node raft)"
        )
        out["unit"] = "%"
        out["config10"] = bench_config10(jax)
        out["value"] = out["config10"].get("checkpoint_overhead_pct")
        # Target: persistence costs < 5% of round wall time at the
        # default --checkpoint-every (smaller is better). Overhead is
        # clamped at 0.0, so a measured zero is the BEST result, not a
        # missing one — floor the denominator instead of nulling it.
        out["vs_baseline"] = (
            round(5.0 / max(out["value"], 0.01), 3)
            if out["value"] is not None
            else None
        )
        emit(out)
        return
    if args.config == 11:
        out["metric"] = (
            "continuous-obs overhead % (journal + time series, durable "
            "DPOR frontier)"
        )
        out["unit"] = "%"
        out["config11"] = bench_config11(jax)
        out["value"] = out["config11"].get("journal_overhead_pct")
        # Target: journal + per-round time-series sampling always-on
        # costs < 1% of round wall (smaller is better; a measured zero
        # is the BEST result — floor the denominator, like config 10).
        out["vs_baseline"] = (
            round(1.0 / max(out["value"], 0.01), 3)
            if out["value"] is not None
            else None
        )
        emit(out)
        return
    if args.config == 12:
        out["metric"] = (
            "MCSes/hour speedup (streaming vs staged "
            "fuzz→minimize→replay, multi-violation raft)"
        )
        out["unit"] = "x"
        out["config12"] = bench_config12(jax)
        out["value"] = out["config12"].get("speedup")
        # Target: >= 1.3x MCSes/hour over the staged pipeline with
        # identical MCS sets — the disjoint-host/device (TPU) regime;
        # shared-core CPU tops out ~1.1-1.2x (see bench_config12 doc).
        out["vs_baseline"] = round((out["value"] or 0) / 1.3, 3)
        emit(out)
        return
    if args.config == 13:
        out["metric"] = (
            "aggregate interleavings/sec scaling vs worker count "
            "(sharded exploration fleet, seeded raft frontier)"
        )
        out["unit"] = "x"
        out["config13"] = bench_config13(jax)
        scaling = out["config13"].get("scaling") or {}
        # The headline is the scaling factor at the largest measured
        # worker count (>=2.5x at 4 workers is the acceptance bar).
        tops = [v for v in scaling.values() if v is not None]
        out["value"] = tops[-1] if tops else None
        out["vs_baseline"] = (
            round((out["value"] or 0) / 2.5, 3)
            if out["value"] is not None
            else None
        )
        emit(out)
        return
    if args.config == 14:
        out["metric"] = (
            "aggregate MCSes per serialized busy second, shared-batch "
            "service vs solo-sequential (multi-tenant raft mix)"
        )
        out["unit"] = "x"
        out["config14"] = bench_config14(jax)
        out["value"] = out["config14"].get("speedup")
        # Target: >= 1.15x MCSes per serialized uncontended busy second
        # over running each tenant as a dedicated solo pipeline, with
        # per-tenant artifacts bit-identical and strictly fewer
        # compiled executables + kernel launches.
        out["vs_baseline"] = round((out["value"] or 0) / 1.15, 3)
        emit(out)
        return
    if args.config == 15:
        out["metric"] = (
            "distributed tracing + health-plane overhead % "
            "(2-worker fleet, spans + journal + clock sync)"
        )
        out["unit"] = "%"
        out["config15"] = bench_config15(jax)
        out["value"] = out["config15"].get("tracing_overhead_pct")
        # Target: the pod tracing plane costs < 1% of per-round busy
        # time (smaller is better; a measured zero is the BEST result —
        # floor the denominator, like configs 10/11).
        out["vs_baseline"] = (
            round(1.0 / max(out["value"], 0.01), 3)
            if out["value"] is not None
            else None
        )
        emit(out)
        return
    if args.config == 16:
        out["metric"] = (
            "host-half rounds/sec scaling vs admission shard count "
            "(digest-range-sharded coordinator host half, seeded raft "
            "frontier, bit-identical at every point)"
        )
        out["unit"] = "x"
        out["config16"] = bench_config16(jax)
        scaling = out["config16"].get("scaling") or {}
        # The headline is the scaling factor at the largest measured
        # shard count (>=2.5x at 4 shards is the acceptance bar).
        tops = [v for v in scaling.values() if v is not None]
        out["value"] = tops[-1] if tops else None
        out["vs_baseline"] = (
            round((out["value"] or 0) / 2.5, 3)
            if out["value"] is not None
            else None
        )
        emit(out)
        return
    if args.config == 17:
        out["metric"] = (
            "re-explored classes, scratch/delta (differential "
            "exploration after a one-handler raft edit, seeded "
            "frontier; violations + audit bit-identical, unknown "
            "effects degrade to full)"
        )
        out["unit"] = "x"
        out["config17"] = bench_config17(jax)
        out["value"] = out["config17"].get("reduction_x")
        # Target: >=3x fewer re-explored classes than scratch.
        out["vs_baseline"] = (
            round(out["value"] / 3.0, 3)
            if out["value"] is not None
            else None
        )
        emit(out)
        return
    if args.config == "rehearsal":
        out["metric"] = (
            "schedules/sec (config-5 machinery rehearsal, >=1e5 lanes)"
        )
        out["config5_rehearsal"] = bench_config5_rehearsal(jax)
        out["value"] = out["config5_rehearsal"]["schedules_per_sec"]
        out["vs_baseline"] = round(out["value"] / 10_000.0, 3)
        emit(out)
        return

    value, impl_info = bench_device_raft(jax)
    if impl_info.get("headline_invariant_granularity") == "round":
        out["metric"] = (
            "unique schedules explored/sec/chip (5-node raft fuzz, "
            "round-granularity invariant checks)"
        )
    host = bench_host_raft()
    ttfv = bench_time_to_first_violation(jax)
    config2 = bench_config2(jax)
    config3 = bench_config3(jax)
    config4 = bench_config4(jax)
    config5 = bench_config5(jax)
    config6 = bench_config6(jax)
    config7 = bench_config7(jax)
    config8 = bench_config8(jax)
    config9 = bench_config9(jax)
    config10 = bench_config10(jax)
    config11 = bench_config11(jax)
    config12 = bench_config12(jax)
    config13 = bench_config13(jax)
    config14 = bench_config14(jax)
    config15 = bench_config15(jax)
    config16 = bench_config16(jax)
    config17 = bench_config17(jax)
    rehearsal = bench_config5_rehearsal(jax)
    out.update(
        {
            "value": round(value, 1),
            **impl_info,
            # North star: >=10k schedules/sec/chip (BASELINE.json; the
            # reference publishes no numbers and its JVM can't run here).
            "vs_baseline": round(value / 10_000.0, 3),
            "host_schedules_per_sec": round(host, 1),
            # Raw-vs-raw: the host loop doesn't dedup its executions, so
            # the speedup ratio uses the device's raw lane rate, not the
            # deduped headline. Basis notes when a forced round variant
            # is the numerator (coarser invariant checks than the host's
            # per-delivery loop — not the ratio's usual meaning).
            "device_vs_host": round(impl_info["raw_lanes_per_sec"] / host, 1),
            "device_vs_host_basis": impl_info[
                "headline_invariant_granularity"
            ],
            "time_to_first_violation_s": (
                round(ttfv, 3) if ttfv is not None else None
            ),
            "config2": config2,
            "config3": config3,
            "config4": config4,
            "config5": config5,
            "config6": config6,
            "config7": config7,
            "config8": config8,
            "config9": config9,
            "config10": config10,
            "config11": config11,
            "config12": config12,
            "config13": config13,
            "config14": config14,
            "config15": config15,
            "config16": config16,
            "config17": config17,
            "config5_rehearsal": rehearsal,
        }
    )
    emit(out)


if __name__ == "__main__":
    main()
