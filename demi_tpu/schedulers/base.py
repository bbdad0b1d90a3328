"""Scheduler base: the template every delivery policy plugs into.

In the reference, schedulers implement the ``Scheduler`` trait
(schedulers/Scheduler.scala:13-104) and mix in ``ExternalEventInjector``
(schedulers/ExternalEventInjector.scala) which owns an ``EventOrchestrator``
(schedulers/EventOrchestrator.scala). Because our runtime is sequential by
construction, all three collapse into one straight-line template here:

    execute(externals):
        repeat:
            inject external events until a WaitQuiescence/WaitCondition
            dispatch: loop { choose_next() -> deliver -> capture new pending }
            on quiescence: advance to the next external segment

Subclasses supply the *policy*: how pending events are stored and which one
``choose_next`` picks. The base records the EventTrace, runs the failure
detector, applies Kill/HardKill/Partition semantics, and performs periodic
invariant checks.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from .. import obs
from ..config import SchedulerConfig
from ..events import (
    EXTERNAL,
    BeginExternalAtomicBlock,
    BeginWaitCondition,
    BeginWaitQuiescence,
    CodeBlockEvent,
    EndExternalAtomicBlock,
    HardKillEvent,
    KillEvent,
    MsgDiscarded,
    MsgEvent,
    MsgKept,
    MsgSend,
    PartitionEvent,
    Quiescence,
    SpawnEvent,
    TimerDelivery,
    UnPartitionEvent,
    Unique,
)
from ..external_events import (
    CodeBlock,
    ExternalEvent,
    HardKill,
    Kill,
    Partition,
    Send,
    Start,
    UnPartition,
    WaitCondition,
    WaitQuiescence,
)
from ..runtime.checkpoints import CheckpointCollector
from ..runtime.failure_detector import FDMessageOrchestrator, QueryReachableGroup
from ..runtime.system import ControlledActorSystem, PendingEntry
from ..trace import EventTrace, MetaEventTrace


class ScheduleHalt(Exception):
    """Raised by policies to abort the current execution."""


@dataclass
class ExecutionResult:
    trace: EventTrace
    violation: Optional[Any]  # ViolationFingerprint or None
    deliveries: int
    quiescent: bool  # ended at quiescence (vs. hitting a cap)


class BaseScheduler:
    """Template-method scheduler over a ControlledActorSystem."""

    def __init__(self, config: SchedulerConfig, max_messages: int = 10_000,
                 invariant_check_interval: int = 0):
        self.config = config
        self.max_messages = max_messages
        # 0 = only check at quiescence / end (reference default behavior;
        # RandomScheduler's interval checks via setInvariantCheckInterval).
        # An invariant that may be judged at quiescence only is never
        # judged mid-run, whatever interval a caller passes.
        self.invariant_check_interval = (
            0 if config.quiescence_invariant else invariant_check_interval
        )
        self.system: Optional[ControlledActorSystem] = None
        self.trace = EventTrace()
        self.fd: Optional[FDMessageOrchestrator] = None
        self.checkpointer = CheckpointCollector()
        self.actor_factories: Dict[str, Callable[[], Any]] = {}
        self.deliveries = 0
        self._current_externals: Sequence[ExternalEvent] = ()
        self.logs: List[Tuple[str, str]] = []

    # ------------------------------------------------------------------
    # Policy hooks (subclass responsibility)
    # ------------------------------------------------------------------
    def add_pending(self, entry: PendingEntry) -> None:
        raise NotImplementedError

    def choose_next(self) -> Optional[PendingEntry]:
        """Pick the next entry to deliver, or None for quiescence. Must only
        return entries that are currently deliverable."""
        raise NotImplementedError

    def pending_entries(self) -> List[PendingEntry]:
        """All currently pending entries (for divergence diagnostics)."""
        raise NotImplementedError

    def remove_pending(self, entry: PendingEntry) -> None:
        """Remove one specific pending entry (timer-cancel support)."""
        raise NotImplementedError

    def actor_terminated(self, name: str) -> None:
        """Scrub pending state for a HardKilled actor (reference:
        Scheduler.actorTerminated; RandomScheduler.scala:536-547)."""
        raise NotImplementedError

    def reset_pending(self) -> None:
        raise NotImplementedError

    def _cut_link(self, a: str, b: str) -> None:
        """Partition(a, b): cut the link and drop what is pending on it,
        in both directions (timers are self-sends and externals cross no
        link; see runtime.system.Network for the rule both tiers keep)."""
        self.system.network.partition(a, b)
        link = frozenset((a, b))
        for entry in self.pending_entries():
            if not entry.is_timer and frozenset((entry.snd, entry.rcv)) == link:
                self.remove_pending(entry)

    def choose_outcome(self, entry: PendingEntry) -> str:
        """What the network does with the entry ``choose_next`` picked:
        "deliver" (and consume: all that a network other than a datagram
        one ever does), "keep" (deliver, and leave it pending) or
        "discard" (lose it undelivered)."""
        return "deliver"

    # Optional hooks ----------------------------------------------------
    def on_delivery(self, unique: Unique, entry: PendingEntry) -> None:
        pass

    def on_new_pending(self, unique_send: Optional[Unique], entry: PendingEntry) -> None:
        pass

    def on_quiescence(self) -> None:
        pass

    # ------------------------------------------------------------------
    # The engine
    # ------------------------------------------------------------------
    def prepare(self, externals: Sequence[ExternalEvent]) -> None:
        self.system = ControlledActorSystem()
        self.system.log_listener = self._on_log
        self.trace = EventTrace(original_externals=list(externals))
        self.deliveries = 0
        self.logs = []
        self.reset_pending()
        self._current_externals = list(externals)
        if self.config.enable_failure_detector:
            self.fd = FDMessageOrchestrator(self._fd_enqueue)
        else:
            self.fd = None
        # Per-event log capture for Synoptic-style inference (reference:
        # MetaEventTrace, EventTrace.scala:542-568; retention via
        # HistoricalEventTraces when store_event_traces is on).
        self.meta_trace = MetaEventTrace(self.trace)
        if self.config.store_event_traces:
            from ..minimization.state_machine import HistoricalEventTraces

            HistoricalEventTraces.record(self.meta_trace)

    def execute(self, externals: Sequence[ExternalEvent]) -> ExecutionResult:
        """Run the full external-event program to completion (or a cap),
        recording the trace; returns the final invariant verdict."""
        with obs.span(
            "scheduler.execute",
            scheduler=type(self).__name__,
            externals=len(externals),
        ) as sp:
            self.prepare(externals)
            violation = self._run_program(list(externals))
            quiescent = self.deliveries < self.max_messages
            # An invariant judged at quiescence only gives a run that was
            # cut by the cap no verdict (device twin: explore._finalize).
            if violation is None and (
                quiescent or not self.config.quiescence_invariant
            ):
                violation = self.check_invariant()
            if violation is not None:
                self.meta_trace.set_caused_violation()
            sp.set(deliveries=self.deliveries,
                   violation=violation is not None)
        if obs.enabled():
            obs.counter("scheduler.executions").inc(
                scheduler=type(self).__name__
            )
        return ExecutionResult(
            trace=self.trace,
            violation=violation,
            deliveries=self.deliveries,
            quiescent=quiescent,
        )

    def _run_program(self, program: List[ExternalEvent]) -> Optional[Any]:
        cursor = 0
        violation: Optional[Any] = None
        while True:
            cursor, waiting_cond, budget = self._inject_until_wait(program, cursor)
            if cursor >= len(program) and self.config.quiescence_invariant:
                # The run ends at quiescence: the program's final wait
                # drains whatever budget it carries (device twin:
                # explore._injection_phase).
                budget = None
            violation = self._dispatch_until_quiescence(waiting_cond, budget)
            self.trace.append(self._unique(Quiescence()))
            self.on_quiescence()
            if violation is not None:
                return violation
            if cursor >= len(program):
                return None
            if self.deliveries >= self.max_messages:
                return None

    # -- injection phase -------------------------------------------------
    def _inject_until_wait(
        self, program: List[ExternalEvent], cursor: int
    ) -> Tuple[int, Optional[Callable[[], bool]], Optional[int]]:
        """Interpret external events until a blocking one.

        Reference: EventOrchestrator.inject_until_quiescence
        (EventOrchestrator.scala:132-189)."""
        open_block: Optional[int] = None

        def _close_block() -> None:
            nonlocal open_block
            if open_block is not None:
                self.trace.append(
                    self._unique(EndExternalAtomicBlock(open_block))
                )
                open_block = None

        while cursor < len(program):
            event = program[cursor]
            cursor += 1
            if isinstance(event, WaitQuiescence):
                _close_block()
                self.trace.append(self._unique(BeginWaitQuiescence()))
                return cursor, None, event.budget
            if isinstance(event, WaitCondition):
                _close_block()
                self.trace.append(self._unique(BeginWaitCondition()))
                cond = event.cond or self._dsl_condition(event.cond_id)
                return cursor, cond, event.budget
            # External atomic blocks (reference:
            # ExternalEventInjector.scala:179-216): members inject
            # back-to-back inside Begin/End markers. Injection is already
            # atomic w.r.t. dispatch here; the markers make the block
            # boundary visible to STS replay and trace surgeries.
            if event.block_id != open_block:
                _close_block()
                if event.block_id is not None:
                    self.trace.append(
                        self._unique(BeginExternalAtomicBlock(event.block_id))
                    )
                    open_block = event.block_id
            self._inject_one(event)
        _close_block()
        return cursor, None, None

    def _dsl_condition(self, cond_id: Optional[int]) -> Callable[[], bool]:
        """Host twin of the device OP_WAITCOND segment: evaluate the app's
        jax predicate (DSLApp.conditions[cond_id]) over the live DSL actor
        states, with the device's alive semantics (started, not
        isolated/stopped)."""
        if cond_id is None:
            raise ValueError("WaitCondition needs cond or cond_id")
        from ..runtime.actor import DSLActorAdapter

        def cond() -> bool:
            import numpy as np

            app = None
            for actor in self.system.actors.values():
                if isinstance(actor, DSLActorAdapter):
                    app = actor.app
                    break
            if app is None:
                raise ValueError(
                    "WaitCondition(cond_id=...) requires DSL actors"
                )
            states = np.zeros((app.num_actors, app.state_width), np.int32)
            alive = np.zeros(app.num_actors, bool)
            for i in range(app.num_actors):
                name = app.actor_name(i)
                actor = self.system.actors.get(name)
                if (
                    isinstance(actor, DSLActorAdapter)
                    and name not in self.system.crashed
                    and name not in self.system.network.isolated
                ):
                    states[i] = actor.state
                    alive[i] = True
            from ..apps.common import _jitted_condition

            return bool(_jitted_condition(app, cond_id)(states, alive))

        return cond

    def _inject_one(self, event: ExternalEvent) -> None:
        system = self.system
        if isinstance(event, Start):
            factory = event.ctor or self.actor_factories.get(event.name)
            if factory is None:
                raise ValueError(f"no actor factory for Start({event.name})")
            self.actor_factories[event.name] = factory
            new = system.spawn(event.name, factory)
            self.trace.append(self._unique(SpawnEvent(EXTERNAL, event.name, ctor=factory)))
            self._absorb(new)
            if self.fd:
                self.fd.handle_start_event(event.name)
        elif isinstance(event, Kill):
            system.network.isolate(event.name)
            self.trace.append(self._unique(KillEvent(event.name)))
            if self.fd:
                self.fd.handle_kill_event(event.name)
        elif isinstance(event, HardKill):
            system.hard_kill(event.name)
            self.actor_terminated(event.name)
            self.trace.append(self._unique(HardKillEvent(event.name)))
            if self.fd:
                self.fd.handle_kill_event(event.name)
        elif isinstance(event, Send):
            entry = system.inject(event.name, event.message())
            self._record_send(entry)
        elif isinstance(event, Partition):
            self._cut_link(event.a, event.b)
            self.trace.append(self._unique(PartitionEvent(event.a, event.b)))
            if self.fd:
                self.fd.handle_partition_event(event.a, event.b)
        elif isinstance(event, UnPartition):
            system.network.unpartition(event.a, event.b)
            self.trace.append(self._unique(UnPartitionEvent(event.a, event.b)))
            if self.fd:
                self.fd.handle_unpartition_event(event.a, event.b)
        elif isinstance(event, CodeBlock):
            new = system.run_code_block(event.block)
            self.trace.append(self._unique(CodeBlockEvent(event.label, event.block)))
            self._absorb(new)
        else:
            raise TypeError(f"unknown external event {event!r}")

    # -- dispatch phase --------------------------------------------------
    def _dispatch_until_quiescence(
        self,
        waiting_cond: Optional[Callable[[], bool]],
        budget: Optional[int] = None,
    ) -> Optional[Any]:
        segment_start = self.deliveries
        while True:
            if waiting_cond is not None and waiting_cond():
                return None  # condition satisfied; next external segment
            if budget is not None and self.deliveries - segment_start >= budget:
                return None  # bounded wait expired; next segment
            if self.deliveries >= self.max_messages:
                return None
            try:
                entry = self.choose_next()
            except ScheduleHalt:
                return None
            if entry is None:
                return None
            outcome = self.choose_outcome(entry)
            if outcome == "discard":
                # No handler ran: nothing to judge, no delivery counted.
                self._discard(entry)
                continue
            self._deliver(entry, keep=outcome == "keep")
            if (
                self.invariant_check_interval
                and self.deliveries % self.invariant_check_interval == 0
            ):
                violation = self.check_invariant()
                if violation is not None:
                    return violation

    def _discard(self, entry: PendingEntry) -> None:
        """Datagram channels: the network loses ``entry``, which the
        policy has already taken off its pending structure."""
        self.system.discard(entry)
        self.trace.append(
            Unique(MsgDiscarded(entry.snd, entry.rcv, entry.msg), entry.uid)
        )

    def _deliver(self, entry: PendingEntry, keep: bool = False) -> None:
        system = self.system
        if keep:
            # Datagram channels: the message stays pending as well, as a
            # copy under an id of its own (no MsgSend: nobody sent it).
            copy = system.keep(entry)
            self.trace.append(
                Unique(MsgKept(copy.snd, copy.rcv, copy.msg), copy.uid)
            )
            self.add_pending(copy)
        if entry.is_timer:
            unique = Unique(TimerDelivery(entry.rcv, entry.msg,
                                          self.config.fingerprinter.fingerprint(entry.msg)),
                            entry.uid)
        else:
            unique = Unique(MsgEvent(entry.snd, entry.rcv, entry.msg), entry.uid)
        self.trace.append(unique)
        self.deliveries += 1
        if entry.rcv == "__fd__":
            # Queries addressed to the failure detector are answered by the
            # scheduler itself (reference: FailureDetector.scala:44-149);
            # with the FD disabled they fall into the void like deadLetters.
            if self.fd is not None and isinstance(entry.msg, QueryReachableGroup):
                self.fd.handle_query(entry.snd)
            self.on_delivery(unique, entry)
            return
        new = system.deliver(entry)
        self.on_delivery(unique, entry)
        self._absorb(new)
        for name, msg in system.drain_cancelled_timers():
            self.notify_timer_cancel(name, msg)

    def _absorb(self, new_entries: List[PendingEntry]) -> None:
        for entry in new_entries:
            if entry.is_timer:
                if self.config.ignore_timers:
                    continue
                self.add_pending(entry)
                self.on_new_pending(None, entry)
            else:
                self._record_send(entry)

    def _record_send(self, entry: PendingEntry) -> None:
        unique = Unique(MsgSend(entry.snd, entry.rcv, entry.msg), entry.uid)
        self.trace.append(unique)
        self.add_pending(entry)
        self.on_new_pending(unique, entry)

    def _fd_enqueue(self, snd: str, rcv: str, msg: Any) -> None:
        entry = self.system.inject_from(snd, rcv, msg)
        self._record_send(entry)

    def notify_timer_cancel(self, name: str, msg: Any) -> None:
        """Drop the first matching pending timer, so a cancelled timer can
        never be delivered (reference: WrappedCancellable →
        Scheduler.notify_timer_cancel, Instrumenter.scala:1145-1173).
        Without this, replay/STS/DPOR could deliver timers the recorded
        system cancelled — interleavings it could not exhibit."""
        for entry in self.pending_entries():
            if entry.is_timer and entry.rcv == name and entry.msg == msg:
                self.remove_pending(entry)
                return

    # -- invariant checking ----------------------------------------------
    def check_invariant(self) -> Optional[Any]:
        if self.config.invariant_check is None:
            return None
        checkpoint = self.checkpointer.collect(self.system)
        return self.config.invariant_check(self._current_externals, checkpoint)

    def _unique(self, event) -> Unique:
        return Unique(event, self.system.id_gen.next())

    def _on_log(self, name: str, line: str) -> None:
        self.logs.append((name, line))
        self.meta_trace.append_log_output(f"{name}: {line}")
