"""Top-level runner API: fuzz → minimize pipelines.

Reference: verification/RunnerUtils.scala (1438 LoC) — fuzz:62-147,
runTheGamut:171-500 (the canonical pipeline documented at
RunnerUtils.scala:22-27: fuzz -> shrinkSendContents -> stsSchedDDMin ->
minimizeInternals -> replayExperiment), plus helpers.

Host logic orchestrates; replay trials run on the host STS oracle or, via
``use_device=True``, on the batched device replay kernel (DDMin levels and
internal-minimization rounds become vmapped batches — SURVEY.md §7.2 step 6).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional, Sequence, Tuple

from . import obs
from .config import SchedulerConfig
from .external_events import ExternalEvent, MessageConstructor, Send
from .fuzzing import Fuzzer
from .minimization.ddmin import DDMin, make_dag
from .minimization.internal import (
    OneAtATimeStrategy,
    RemovalStrategy,
    SrcDstFIFORemoval,
    STSSchedMinimizer,
)
from .minimization.provenance import prune_concurrent_events
from .minimization.stats import MinimizationStats
from .minimization.wildcards import WildcardMinimizer
from .schedulers.random import RandomScheduler
from .schedulers.replay import ReplayException, ReplayScheduler, STSScheduler, sts_oracle
from .trace import EventTrace


@dataclass
class FuzzResult:
    program: List[ExternalEvent]
    trace: EventTrace
    violation: Any
    executions: int


def lift_lane_to_host(app, cfg, progs, keys, lane, config=None):
    """The standard device→host lift ritual: traced single-lane re-run of
    sweep lane ``lane``, lowered to a guide, executed on the host oracle.

    Returns (single_lane_result, host_execution_result). Raises
    GuideDivergence if kernel and oracle semantics drift. The host
    result's trace carries its own re-created externals — minimize it
    with ``sts_sched_ddmin(config, host.trace, None, host.violation)``."""
    import jax
    import numpy as np

    from .apps.common import make_host_invariant
    from .device.encoding import device_trace_to_guide
    from .device.explore import make_single_lane_trace_kernel
    from .schedulers.guided import GuidedScheduler

    single = make_single_lane_trace_kernel(app, cfg)(
        jax.tree_util.tree_map(lambda x: x[lane], progs), keys[lane]
    )
    guide = device_trace_to_guide(
        app, np.asarray(single.trace), int(single.trace_len)
    )
    config = config or SchedulerConfig(
        invariant_check=make_host_invariant(app)
    )
    host = GuidedScheduler(config, app).execute_guide(guide)
    return single, host


@dataclass
class GamutResult:
    """One entry per pipeline stage: (stage name, externals count,
    deliveries count, trace)."""

    mcs_externals: List[ExternalEvent]
    final_trace: EventTrace
    stages: List[Tuple[str, int, int]] = field(default_factory=list)
    stats: MinimizationStats = field(default_factory=MinimizationStats)


def _trace_fingerprint(trace: EventTrace) -> int:
    """Order-sensitive digest of a trace's delivered sequence — the host
    analog of the device ``sched_hash`` the autotune reward dedups on."""
    parts = []
    for u in trace.deliveries():
        ev = u.event
        parts.append(
            (
                type(ev).__name__,
                getattr(ev, "receiver", ""),
                str(getattr(ev, "msg", "")),
            )
        )
    return hash(tuple(parts))


def fuzz(
    config: SchedulerConfig,
    fuzzer: Fuzzer,
    max_executions: int = 1000,
    seed: int = 0,
    max_messages: int = 10_000,
    invariant_check_interval: int = 0,
    timer_weight: float = 1.0,
    validate_replay: bool = False,
    controller=None,
    start_execution: int = 0,
    round_hook=None,
    on_violation=None,
    strategy: str = "fully_random",
    **network,
) -> Optional[FuzzResult]:
    """Generate fuzz tests and run them until a violation is found
    (reference: RunnerUtils.fuzz, RunnerUtils.scala:62-147). With
    ``validate_replay``, nondeterministic violations (those a strict replay
    cannot reproduce) are discarded (RunnerUtils.scala:101-132).

    ``controller`` (a ``demi_tpu.tune.ExplorationController``) closes the
    measurement loop on the host tier: each execution runs under proposed
    fuzzer weights and is scored by whether its delivered sequence was new
    (plus a violation bonus), so event kinds that keep finding fresh
    schedules earn weight.

    Durable-state hooks (``demi_tpu.persist``): each execution is a pure
    function of (seed, i) plus the controller's restored state, so a
    resumed run passes ``start_execution`` to skip the executions the
    dead run already burned. ``round_hook(executions_done)`` is called
    after every non-violating execution; returning True stops the loop
    (the preemption guard's boundary — the caller distinguishes
    "preempted" from "exhausted" via its own guard flag).

    ``on_violation(FuzzResult)`` is the streaming-tier hook
    (demi_tpu/pipeline/): instead of RETURNING the first reproduced
    violation, the loop hands it to the hook and keeps fuzzing the
    remaining executions — the host analog of the sweep drivers'
    violation handoff. Returning True from the hook stops the loop;
    with the hook set, ``fuzz`` always returns None (every violation
    flowed through the hook).

    ``strategy`` is the scheduler's (``DSLApp.random_strategy``: an app
    whose channels are FIFO is fuzzed under ``"srcdst_fifo"``, one whose
    channels are datagram under ``"datagram"``, with ``network`` its
    ``dup_weight``, ``drop_weight``, ``max_dups`` and ``max_drops``)."""
    sched = RandomScheduler(
        config,
        seed=seed,
        max_messages=max_messages,
        invariant_check_interval=invariant_check_interval,
        timer_weight=timer_weight,
        strategy=strategy,
        **network,
    )
    for i in range(start_execution, max_executions):
        if controller is not None:
            controller.begin_round()
        program = fuzzer.generate_fuzz_test(seed=seed + i)
        with obs.span("fuzz.execution", seed=seed + i) as sp:
            result = sched.execute(program)
            sp.set(deliveries=result.deliveries,
                   violation=result.violation is not None)
        obs.counter("fuzz.executions").inc()
        # Continuous wire format (obs/journal.py): one record per host
        # fuzz execution — `i + 1` continues a resumed run's numbering
        # (start_execution), so the journal stays contiguous. Gated on
        # an ATTACHED journal, not the obs switch: executions are ~ms
        # (not kernel rounds), so a DEMI_OBS=1 run without a journal
        # must not pay a registry scan per execution.
        if obs.journal.attached():
            obs.journal.emit(
                "fuzz.execution",
                round=i + 1,
                deliveries=result.deliveries,
                violation=result.violation is not None,
            )
        if controller is not None:
            controller.end_round(
                hashes=[_trace_fingerprint(result.trace)],
                violations=int(result.violation is not None),
                lanes=1,
            )
        reproduced = result.violation is not None
        if reproduced:
            obs.counter("fuzz.violations").inc()
            if validate_replay:
                replayer = ReplayScheduler(config)
                try:
                    with obs.span("fuzz.validate_replay"):
                        replayed = replayer.replay(result.trace, program)
                except ReplayException:
                    obs.counter("fuzz.nondeterministic_discarded").inc()
                    reproduced = False
                else:
                    if replayed.violation is None or not (
                        replayed.violation.matches(result.violation)
                    ):
                        obs.counter("fuzz.nondeterministic_discarded").inc()
                        reproduced = False
        if reproduced:
            found = FuzzResult(
                program=program,
                trace=result.trace,
                violation=result.violation,
                executions=i + 1,
            )
            if on_violation is None:
                return found
            if on_violation(found):
                return None
        if round_hook is not None and round_hook(i + 1):
            return None
    return None


def sts_sched_ddmin(
    config: SchedulerConfig,
    trace: EventTrace,
    externals: Optional[Sequence[ExternalEvent]],
    violation: Any,
    stats: Optional[MinimizationStats] = None,
    oracle=None,
    budget=None,
):
    """External-event DDMin over the STS oracle
    (reference: RunnerUtils.stsSchedDDMin, RunnerUtils.scala:642-707).

    ``externals=None`` minimizes over ``trace.original_externals`` — the
    only correct choice for traces that did not execute the caller's own
    event objects (e.g. a device lane lifted through GuidedScheduler,
    whose trace re-creates its externals from the device guide): STS
    projection matches candidate externals to the trace by object/uid
    linkage, so foreign objects silently project to "absent" and the
    full-sequence precheck fails."""
    if externals is None:
        externals = trace.original_externals
        if not externals:
            raise ValueError(
                "externals=None requires trace.original_externals to be set"
            )
    oracle = oracle or sts_oracle(config, trace)
    ddmin = DDMin(
        oracle, check_unmodified=True, stats=stats or MinimizationStats(),
        budget=budget,
    )
    mcs = ddmin.minimize(make_dag(list(externals)), violation)
    verified = ddmin.verify_mcs(mcs, violation)
    return mcs, verified


def minimize_internals(
    config: SchedulerConfig,
    failing_trace: EventTrace,
    externals: Sequence[ExternalEvent],
    violation: Any,
    strategy: Optional[RemovalStrategy] = None,
    stats: Optional[MinimizationStats] = None,
    budget=None,
) -> EventTrace:
    """Reference: RunnerUtils.minimizeInternals (RunnerUtils.scala:980-1003)."""

    def check(candidate: EventTrace) -> Optional[EventTrace]:
        sts = STSScheduler(config, candidate)
        return sts.test_with_trace(candidate, list(externals), violation)

    minimizer = STSSchedMinimizer(
        check, strategy or OneAtATimeStrategy(),
        stats=stats or MinimizationStats(), budget=budget,
    )
    return minimizer.minimize(failing_trace)


def shrink_send_contents(
    config: SchedulerConfig,
    trace: EventTrace,
    externals: Sequence[ExternalEvent],
    violation: Any,
    stats: Optional[MinimizationStats] = None,
) -> List[ExternalEvent]:
    """Mask components of external Send payloads one at a time, keeping
    masks under which the violation still reproduces
    (reference: RunnerUtils.shrinkSendContents, RunnerUtils.scala:1007-1094)."""
    stats = stats or MinimizationStats()
    stats.update_strategy("ShrinkSendContents", "STSSched")
    current = list(externals)
    oracle = sts_oracle(config, trace)
    for pos, event in enumerate(current):
        if not isinstance(event, Send) or event.msg_ctor is None:
            continue
        components = event.msg_ctor.components
        if not components:
            continue
        masked: set = set()
        for ci in range(len(components)):
            trial_mask = masked | {ci}
            trial_send = dataclasses.replace(event)
            object.__setattr__(
                trial_send, "msg_ctor", event.msg_ctor.masked(trial_mask)
            )
            # Keep the original eid so trace surgery still matches.
            object.__setattr__(trial_send, "eid", event.eid)
            trial = list(current)
            trial[pos] = trial_send
            if oracle.test(trial, violation, stats=stats) is not None:
                masked = trial_mask
                current = trial
    return current


def extract_fresh_dep_graph(
    config: SchedulerConfig,
    trace: EventTrace,
    externals: Sequence[ExternalEvent],
):
    """Harvest a DepTracker (happens-before forest + stable DporEvent ids)
    from one trace-steered execution, for seeding DPOR-as-oracle via
    ``SchedulerConfig.original_dep_graph`` (reference:
    RunnerUtils.extractFreshDepGraph, RunnerUtils.scala:946-977).
    Returns (tracker, delivered_ids)."""
    from .schedulers.dep_tracker import DepTracker
    from .schedulers.dpor import _DporExecution, trace_to_steering_keys

    tracker = DepTracker(config.fingerprinter)
    tracker.begin_execution()
    execution = _DporExecution(
        config, tracker, (), max_messages=100_000,
        initial_keys=trace_to_steering_keys(trace, config.fingerprinter),
    )
    execution.execute(list(externals))
    return tracker, list(execution.delivered_ids)


def edit_distance_dpor_ddmin(
    config: SchedulerConfig,
    trace: EventTrace,
    externals: Sequence[ExternalEvent],
    violation: Any,
    max_max_distance: int = 8,
    stats: Optional[MinimizationStats] = None,
    dpor_kwargs: Optional[dict] = None,
    checkpoint_dir: Optional[str] = None,
    resume: bool = False,
    app=None,
    device_cfg=None,
):
    """External-event DDMin over a resumable DPOR oracle with a growing
    edit-distance budget, steered by the recorded violating trace and
    seeded with its dep graph (reference: RunnerUtils.editDistanceDporDDMin,
    RunnerUtils.scala:812-879). With ``checkpoint_dir``, the dep graph is
    persisted; ``resume=True`` reloads it across restarts
    (Serialization.scala:176-187).

    With ``app`` (a DSLApp), probes run on the device-batched DPOR oracle
    instead — whole backtrack frontiers per vmapped kernel launch, steered
    by the recorded trace. On both paths the finished MCS is checkpointed
    (stage "incddmin"); ``resume=True`` returns it without re-searching."""
    from .minimization.incremental_ddmin import IncrementalDDMin

    if checkpoint_dir is not None and resume:
        from .serialization import load_stage

        restored = load_stage(checkpoint_dir, "incddmin", app)
        if restored is not None:
            restored_externals, _ = restored
            return make_dag(restored_externals)

    def _checkpoint_result(mcs_dag):
        if checkpoint_dir is not None:
            from .serialization import save_stage

            save_stage(
                checkpoint_dir, "incddmin", mcs_dag.get_all_events(), trace
            )
        return mcs_dag

    if app is not None:
        import dataclasses as _dc

        from .device.batch_oracle import default_device_config
        from .device.dpor_sweep import DeviceDPOROracle

        device_cfg = device_cfg or default_device_config(
            app, trace, externals, record_trace=True, record_parents=True,
        )
        if not (device_cfg.record_trace and device_cfg.record_parents):
            device_cfg = _dc.replace(
                device_cfg, record_trace=True, record_parents=True
            )
        oracle = DeviceDPOROracle(
            app, device_cfg, config, initial_trace=trace,
            **{k: v for k, v in (dpor_kwargs or {}).items()
               if k in ("batch_size", "max_rounds")},
        )
        inc = IncrementalDDMin(
            config,
            max_max_distance=max_max_distance,
            stats=stats or MinimizationStats(),
            oracle=oracle,
        )
        return _checkpoint_result(
            inc.minimize(make_dag(list(externals)), violation)
        )

    tracker = None
    if checkpoint_dir is not None and resume:
        # Only an explicit resume reloads a persisted dep graph — a stale
        # one from an earlier experiment in the same dir would silently
        # degrade steering (ids/fingerprints minted for a different trace).
        from .serialization import load_dep_graph

        tracker = load_dep_graph(checkpoint_dir, config.fingerprinter)
    if tracker is None:
        tracker, _ = extract_fresh_dep_graph(config, trace, externals)
        if checkpoint_dir is not None:
            from .serialization import save_dep_graph

            save_dep_graph(checkpoint_dir, tracker)
    seeded = dataclasses.replace(config, original_dep_graph=tracker)
    inc = IncrementalDDMin(
        seeded,
        max_max_distance=max_max_distance,
        stats=stats or MinimizationStats(),
        dpor_kwargs=dpor_kwargs,
        initial_trace=trace,
    )
    mcs = inc.minimize(make_dag(list(externals)), violation)
    return _checkpoint_result(mcs)


def bounded_dpor(
    config: SchedulerConfig,
    externals: Sequence[ExternalEvent],
    violation: Any = None,
    max_interleavings: int = 1_000,
    max_messages: int = 2_000,
    budget_seconds: float = float("inf"),
    initial_trace: Optional[EventTrace] = None,
):
    """Bounded systematic exploration (reference: RunnerUtils.boundedDPOR,
    RunnerUtils.scala:881-911). Returns the DPORScheduler (for
    interleavings_explored / shortest_violating) and the violating
    ExecutionResult or None."""
    from .schedulers.dpor import DPORScheduler

    sched = DPORScheduler(
        config,
        max_messages=max_messages,
        max_interleavings=max_interleavings,
        budget_seconds=budget_seconds,
    )
    if initial_trace is not None:
        sched.set_initial_trace(initial_trace)
    result = sched.explore(externals, target_violation=violation)
    return sched, result


def run_the_gamut(
    config: SchedulerConfig,
    fuzz_result: FuzzResult,
    wildcards: bool = True,
    provenance: bool = True,
    internal_strategy: Optional[RemovalStrategy] = None,
    app=None,
    device_cfg=None,
    checkpoint_dir: Optional[str] = None,
    resume: bool = False,
    stage_budget_seconds: Optional[float] = None,
    checker=None,
) -> GamutResult:
    """Drain ``run_the_gamut_streaming`` to completion — the staged
    entry point. The generator IS the pipeline body, so the staged and
    streaming paths cannot drift: same stages, same oracles, same
    per-level decisions, bit-identical MCS."""
    from .minimization.pipeline import drain_stream

    return drain_stream(run_the_gamut_streaming(
        config, fuzz_result, wildcards=wildcards, provenance=provenance,
        internal_strategy=internal_strategy, app=app, device_cfg=device_cfg,
        checkpoint_dir=checkpoint_dir, resume=resume,
        stage_budget_seconds=stage_budget_seconds, checker=checker,
    ))


def run_the_gamut_streaming(
    config: SchedulerConfig,
    fuzz_result: FuzzResult,
    wildcards: bool = True,
    provenance: bool = True,
    internal_strategy: Optional[RemovalStrategy] = None,
    app=None,
    device_cfg=None,
    checkpoint_dir: Optional[str] = None,
    resume: bool = False,
    stage_budget_seconds: Optional[float] = None,
    launch_budget=None,
    checker=None,
):
    """Generator form of the full minimization pipeline (reference:
    RunnerUtils.runTheGamut, RunnerUtils.scala:171-500): provenance
    pruning → external DDMin → internal minimization → wildcard
    (clock-cluster) minimization → final internal minimization.

    Yields ``(kind, tag)`` markers at every resumable boundary — one per
    batched minimizer level/round plus one per completed stage — and
    returns the ``GamutResult`` via ``StopIteration.value``. The
    streaming orchestrator (demi_tpu/pipeline/) advances this generator
    between the fuzz sweep's chunk dispatch and harvest, so minimization
    levels overlap sweep kernels in flight under one launch budget;
    ``run_the_gamut`` drains it synchronously — the pinned A/B baseline
    is the same code path by construction.

    ``stage_budget_seconds`` caps each minimizer stage's wall clock
    (reference: RunnerUtils.scala:180 caps every gamut minimizer): on
    exhaustion the stage keeps its best-so-far result, marks
    ``budget_exhausted`` in its MinimizationStats stage, and the pipeline
    moves on — a pathological wildcard stage can no longer run unbounded.

    With ``app`` (a DSLApp), every stage's candidate trials run as
    device-batched replay kernels — BatchedDDMin levels, batched
    one-at-a-time internal rounds, batched wildcard clusters — and the host
    STS oracle executes only the adopted candidates for bookkeeping traces
    (the BASELINE north-star shape). Without ``app``, everything runs on
    the host STS oracle (arbitrary Python actors).

    With ``checkpoint_dir``, every completed stage's (externals, trace) is
    persisted; ``resume=True`` skips stages whose checkpoints exist and
    restarts after the last completed one (reference: per-stage experiment
    serialization + deserializeExperiment, Serialization.scala /
    RunnerUtils.scala:502-552)."""
    from .serialization import load_stage, save_stage
    from .minimization.stats import StageBudget

    def stage_budget() -> StageBudget:
        return StageBudget(stage_budget_seconds)

    stats = MinimizationStats()
    trace, externals, violation = (
        fuzz_result.trace,
        fuzz_result.program,
        fuzz_result.violation,
    )
    result = GamutResult(mcs_externals=list(externals), final_trace=trace, stats=stats)

    def record(stage: str, ext: Sequence[ExternalEvent], tr: EventTrace):
        result.stages.append((stage, len(ext), len(tr.deliveries())))
        # Stage boundary in the continuous wire format (obs/journal.py):
        # the pipeline's coarse progress marks between the per-level
        # records the batched minimizers emit.
        obs.journal.emit(
            "minimize.stage",
            round=len(result.stages),
            stage=stage,
            externals=len(ext),
            deliveries=len(tr.deliveries()),
        )

    def checkpoint(stage: str, ext: Sequence[ExternalEvent], tr: EventTrace):
        if checkpoint_dir is not None:
            save_stage(checkpoint_dir, stage, ext, tr)

    def restore(stage: str):
        """(externals, trace) if this stage completed in a prior run."""
        if not (resume and checkpoint_dir is not None):
            return None
        restored = load_stage(checkpoint_dir, stage, app)
        if restored is None:
            return None
        # Checkpoints can't persist actor factories; for DSL apps load_stage
        # rebuilds them from the app, but in host mode (app=None) the
        # restored Start/Spawn events carry ctor=None and every later
        # replay would fail with "no factory". Re-bind from the original
        # program's Start events by actor name.
        r_ext, r_trace = restored
        from .events import SpawnEvent
        from .external_events import Start

        by_name = {
            e.name: e.ctor
            for e in fuzz_result.program
            if isinstance(e, Start) and e.ctor is not None
        }
        for e in r_ext:
            if isinstance(e, Start) and e.ctor is None:
                object.__setattr__(e, "ctor", by_name.get(e.name))
        for u in r_trace.events:
            ev = u.event
            if isinstance(ev, SpawnEvent) and ev.ctor is None:
                object.__setattr__(ev, "ctor", by_name.get(ev.name))
        return r_ext, r_trace

    record("original", externals, trace)
    yield ("stage", "original")

    if provenance:
        affected = getattr(violation, "affected_nodes", lambda: ())()
        if affected:
            trace = prune_concurrent_events(trace, affected)
            record("provenance", externals, trace)
            yield ("stage", "provenance")

    if app is None:
        checker = None
    else:
        from .device.batch_oracle import (
            DeviceReplayChecker,
            DeviceSTSOracle,
            default_device_config,
            make_batched_internal_check,
        )
        from .minimization.ddmin import BatchedDDMin
        from .minimization.internal import BatchedInternalMinimizer
        from .minimization.wildcards import BatchedWildcardMinimizer

        if checker is not None:
            # A caller-owned checker (the streaming orchestrator shares
            # one compiled replay oracle across queue frames at a
            # bucketed shape — the multi-tenant minimization seam).
            # Verdicts are pure functions of record bytes, so sharing
            # never changes results; the cfg must be the checker's own.
            device_cfg = checker.cfg
        else:
            device_cfg = device_cfg or default_device_config(
                app, trace, externals
            )
            checker = DeviceReplayChecker(app, device_cfg, config)
            # Streaming orchestration: the checker reports every replay
            # launch into the shared fuzz/minimize in-flight ledger
            # (demi_tpu/pipeline/budget.py) so the split policy sees
            # real lane counts. None (the staged path) costs one branch.
            checker.launch_budget = launch_budget

    # External-event DDMin.
    restored = restore("ddmin")
    if restored is not None:
        externals, trace = restored
    else:
        with obs.span("gamut.ddmin", externals=len(externals)) as sp:
            if checker is not None:
                oracle = DeviceSTSOracle(app, device_cfg, config, trace, checker=checker)
                ddmin = BatchedDDMin(oracle, stats=stats, budget=stage_budget())
                mcs_dag = yield from ddmin.minimize_stream(
                    make_dag(list(externals)), violation
                )
                verified = ddmin.verified_trace
            else:
                mcs_dag, verified = sts_sched_ddmin(
                    config, trace, externals, violation, stats=stats,
                    budget=stage_budget(),
                )
            externals = mcs_dag.get_all_events()
            sp.set(mcs=len(externals))
            if verified is not None:
                trace = verified
        checkpoint("ddmin", externals, trace)
    record("ddmin", externals, trace)
    yield ("stage", "ddmin")

    def _device_int_min(tr: EventTrace):
        minimizer = BatchedInternalMinimizer(
            make_batched_internal_check(checker, list(externals), violation),
            stats=stats,
            budget=stage_budget(),
        )
        return minimizer.minimize_stream(tr)

    # Internal minimization.
    restored = restore("int_min")
    if restored is not None:
        externals, trace = restored
    else:
        with obs.span("gamut.int_min", deliveries=len(trace.deliveries())):
            if checker is not None:
                trace = yield from _device_int_min(trace)
            else:
                trace = minimize_internals(
                    config, trace, externals, violation,
                    strategy=internal_strategy or OneAtATimeStrategy(), stats=stats,
                    budget=stage_budget(),
                )
        checkpoint("int_min", externals, trace)
    record("int_min", externals, trace)
    yield ("stage", "int_min")

    if wildcards:
        def check(candidate: EventTrace) -> Optional[EventTrace]:
            sts = STSScheduler(config, candidate)
            return sts.test_with_trace(candidate, list(externals), violation)

        restored = restore("wildcard")
        if restored is not None:
            externals, trace = restored
        else:
            if checker is not None:
                def batch_verdicts(candidates):
                    return checker.verdicts(
                        candidates, [list(externals)] * len(candidates), violation.code
                    )

                # first_and_last: every cluster-removal tried under both
                # ambiguity policies in the same batch (the device-tier
                # FirstAndLastBacktrack — alternative picks are extra lanes,
                # not sequential backtracks).
                wc = BatchedWildcardMinimizer(
                    batch_verdicts, check, stats=stats, first_and_last=True,
                    budget=stage_budget(),
                )
            else:
                wc = WildcardMinimizer(check, stats=stats, budget=stage_budget())
            with obs.span("gamut.wildcard"):
                trace = wc.minimize(trace, config.fingerprinter)
            checkpoint("wildcard", externals, trace)
        record("wildcard", externals, trace)
        yield ("stage", "wildcard")

        restored = restore("int_min2")
        if restored is not None:
            externals, trace = restored
        else:
            with obs.span("gamut.int_min2"):
                if checker is not None:
                    trace = yield from _device_int_min(trace)
                else:
                    trace = minimize_internals(
                        config, trace, externals, violation,
                        strategy=SrcDstFIFORemoval(), stats=stats,
                        budget=stage_budget(),
                    )
            checkpoint("int_min2", externals, trace)
        record("int_min2", externals, trace)
        yield ("stage", "int_min2")

    result.mcs_externals = list(externals)
    result.final_trace = trace
    return result


def reorder_deliveries(
    config: SchedulerConfig,
    trace: EventTrace,
    externals: Sequence[ExternalEvent],
    new_order: Sequence[int],
    violation: Any = None,
) -> Optional[EventTrace]:
    """Manually permute a trace's internal deliveries and re-execute
    (reference: RunnerUtils.reorderDeliveries, RunnerUtils.scala:1389-1437
    — the "schedule twiddling" tool for by-hand exploration).

    ``new_order`` lists the current delivery positions (as returned by
    ``removable_delivery_indices``) in the desired delivery order; all
    other events keep their positions. Returns the STS-executed trace if
    the candidate replays (and, when ``violation`` is given, reproduces
    it), else None."""
    from .minimization.internal import removable_delivery_indices
    from .minimization.test_oracle import StatelessTestOracle

    slots = removable_delivery_indices(trace)
    assert sorted(new_order) == sorted(slots), (
        "new_order must be a permutation of the trace's delivery positions"
    )
    events = list(trace.events)
    for slot, src_pos in zip(slots, new_order):
        events[slot] = trace.events[src_pos]
    candidate = EventTrace(events, list(externals))
    sts = STSScheduler(config, candidate)
    try:
        result = sts.replay(candidate, list(externals))
    except ReplayException:
        return None
    if violation is not None and (
        result.violation is None or not violation.matches(result.violation)
    ):
        return None
    result.trace.set_original_externals(list(externals))
    return result.trace


def print_minimization_stats(result: GamutResult) -> str:
    """Human-readable pipeline summary (reference:
    RunnerUtils.printMinimizationStats, RunnerUtils.scala:1200-1266)."""
    lines = ["stage            externals  deliveries"]
    for stage, ext, deliv in result.stages:
        lines.append(f"{stage:<16} {ext:>9}  {deliv:>10}")
    for st in result.stats.stages:
        lines.append(
            f"  {st.strategy}/{st.oracle}: {st.total_replays} trials, "
            f"prune {st.prune_duration_seconds:.2f}s"
        )
    lines.append(f"total oracle replays: {result.stats.total_replays}")
    text = "\n".join(lines)
    print(text)
    return text
