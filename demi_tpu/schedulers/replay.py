"""Trace-following schedulers: strict replay and STS-style ignore-absent
replay.

Reference: schedulers/ReplayScheduler.scala (408 LoC) — exact replay that
dies on nondeterminism — and schedulers/STSScheduler.scala (920 LoC) — the
workhorse TestOracle for minimization, which *skips* expected-but-absent
events (the STS heuristic, STSScheduler.scala:74-83,405-559).

Matching policy:
  - external deliveries are matched to their re-injected sends by the
    recorded uid linkage (robust to payload re-binding by
    recompute_external_msg_sends / shrinkSendContents);
  - internal deliveries by (snd, rcv, fingerprint) FIFO
    (reference: ReplayScheduler.scala:49-50);
  - timers by (rcv, fingerprint);
  - WildCardMatch expected events by selector over the pending pool
    (reference: STSScheduler.scala:696-708).
"""

from __future__ import annotations

import copy
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..config import SchedulerConfig
from ..events import (
    EXTERNAL,
    BeginExternalAtomicBlock,
    BeginUnignorableEvents,
    BeginWaitCondition,
    BeginWaitQuiescence,
    CodeBlockEvent,
    EndExternalAtomicBlock,
    EndUnignorableEvents,
    Event,
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
    WildCardMatch,
)
from ..external_events import ExternalEvent
from ..minimization.test_oracle import TestOracle, StatelessTestOracle
from ..runtime.system import PendingEntry
from ..trace import EventTrace
from .base import BaseScheduler, ExecutionResult
from .random import _violation_matches


class ReplayException(Exception):
    """Nondeterminism detected during strict replay
    (reference: ReplayScheduler.scala:24-25)."""


class _ReplayPending:
    """Pending pool with the three matching indexes described above."""

    def __init__(self, fingerprinter):
        self.fingerprinter = fingerprinter
        self.by_key: Dict[Tuple[str, str, Any], List[PendingEntry]] = {}
        self.timers: Dict[Tuple[str, Any], List[PendingEntry]] = {}
        self.by_external_uid: Dict[int, PendingEntry] = {}
        self.all: List[PendingEntry] = []

    def add(self, entry: PendingEntry, external_uid: Optional[int] = None) -> None:
        self.all.append(entry)
        if entry.is_timer:
            key = (entry.rcv, self.fingerprinter.fingerprint(entry.msg))
            self.timers.setdefault(key, []).append(entry)
        else:
            key = (entry.snd, entry.rcv, self.fingerprinter.fingerprint(entry.msg))
            self.by_key.setdefault(key, []).append(entry)
            if external_uid is not None:
                self.by_external_uid[external_uid] = entry
                # Reverse link stored on the entry itself (O(1) discard,
                # survives the deepcopy snapshots peek takes).
                entry.ext_uid = external_uid

    def _discard(self, entry: PendingEntry) -> None:
        self.all.remove(entry)
        if entry.is_timer:
            key = (entry.rcv, self.fingerprinter.fingerprint(entry.msg))
            self.timers[key].remove(entry)
        else:
            key = (entry.snd, entry.rcv, self.fingerprinter.fingerprint(entry.msg))
            self.by_key[key].remove(entry)
            ext_uid = getattr(entry, "ext_uid", None)
            if ext_uid is not None:
                self.by_external_uid.pop(ext_uid, None)

    def pop_external(self, recorded_uid: int) -> Optional[PendingEntry]:
        entry = self.by_external_uid.get(recorded_uid)
        if entry is not None:
            self._discard(entry)
        return entry

    def pop_internal(self, snd: str, rcv: str, msg: Any) -> Optional[PendingEntry]:
        key = (snd, rcv, self.fingerprinter.fingerprint(msg))
        queue = self.by_key.get(key)
        if queue:
            entry = queue[0]
            self._discard(entry)
            return entry
        return None

    def pop_timer(self, rcv: str, msg: Any) -> Optional[PendingEntry]:
        key = (rcv, self.fingerprinter.fingerprint(msg))
        queue = self.timers.get(key)
        if queue:
            entry = queue[0]
            self._discard(entry)
            return entry
        return None

    def pop_wildcard(
        self, rcv: str, wc: WildCardMatch, deliverable=None, resolver=None
    ) -> Optional[PendingEntry]:
        candidates = [
            e
            for e in self.all
            if e.rcv == rcv
            and wc.matches(e.msg, self.fingerprinter)
            # Only deliverable entries are candidates (device-tier parity:
            # the wildcard mask is ANDed with deliverable_mask).
            and (deliverable is None or deliverable(e))
        ]
        if not candidates:
            return None
        if wc.selector is not None:
            idx = wc.selector([e.msg for e in candidates])
            if idx is None:
                return None
            entry = candidates[idx]
        elif resolver is not None:
            idx = resolver.pick(
                [e.msg for e in candidates], self.fingerprinter, wc.policy
            )
            entry = candidates[idx]
        elif wc.policy == "last":
            entry = candidates[-1]
        else:
            entry = candidates[0]
        self._discard(entry)
        return entry

    def remove_for_actor(self, name: str) -> None:
        for entry in [e for e in self.all if e.rcv == name or e.snd == name]:
            self._discard(entry)


class TraceFollowingScheduler(BaseScheduler):
    """Shared engine for Replay/STS: walk the expected trace, applying
    external records and delivering matching pending entries."""

    #: what to do when an expected delivery has no pending match:
    #: "raise" (strict replay) or "ignore" (STS).
    absent_policy = "raise"

    def __init__(
        self,
        config: SchedulerConfig,
        max_messages: int = 100_000,
        allow_peek: bool = False,
        max_peek_messages: int = 10,
    ):
        super().__init__(config, max_messages)
        self.rpending: Optional[_ReplayPending] = None
        self.ignored_absent: List[Unique] = []
        self._unignorable_depth = 0
        self.allow_peek = allow_peek
        self.max_peek_messages = max_peek_messages
        self.peeked_prefixes = 0
        # Optional wildcard ambiguity resolver (pick-script + backtrack
        # registration; see minimization/wildcards.py AmbiguityResolver).
        self.ambiguity_resolver = None

    # BaseScheduler policy hooks (we bypass its dispatch loop but reuse
    # prepare/_deliver/_absorb/_record_send plumbing).
    def reset_pending(self) -> None:
        self.rpending = _ReplayPending(self.config.fingerprinter)
        self.ignored_absent = []
        self._unignorable_depth = 0
        self._next_external_uid: Optional[int] = None
        # Datagram channels: a recorded MsgKept says the next MsgEvent's
        # message stays pending.
        self._keep_next = False

    def add_pending(self, entry: PendingEntry) -> None:
        self.rpending.add(entry, external_uid=self._next_external_uid)
        self._next_external_uid = None

    def pending_entries(self) -> List[PendingEntry]:
        return list(self.rpending.all)

    def remove_pending(self, entry: PendingEntry) -> None:
        self.rpending._discard(entry)

    def actor_terminated(self, name: str) -> None:
        self.rpending.remove_for_actor(name)

    def choose_next(self):  # not used by trace-following dispatch
        return None

    # -- the replay loop ---------------------------------------------------
    def replay(
        self,
        trace: EventTrace,
        externals: Sequence[ExternalEvent],
    ) -> ExecutionResult:
        self.prepare(externals)
        rebound = trace.recompute_external_msg_sends(externals)
        expected: List[Unique] = [
            Unique(ev, u.id) for ev, u in zip(rebound, trace.events)
        ]
        violation = None
        for exp in expected:
            self._step(exp)
            if self.deliveries >= self.max_messages:
                break
        violation = self.check_invariant()
        if violation is not None:
            self.meta_trace.set_caused_violation()
        return ExecutionResult(
            trace=self.trace,
            violation=violation,
            deliveries=self.deliveries,
            quiescent=True,
        )

    def _step(self, exp: Unique) -> None:
        event = exp.event
        if isinstance(event, SpawnEvent):
            factory = event.ctor or self.actor_factories.get(event.name)
            if factory is None:
                raise ReplayException(f"no factory recorded for {event.name}")
            self.actor_factories[event.name] = factory
            new = self.system.spawn(event.name, factory)
            self.trace.append(self._unique(SpawnEvent(EXTERNAL, event.name, ctor=factory)))
            self._absorb(new)
            if self.fd:
                self.fd.handle_start_event(event.name)
        elif isinstance(event, KillEvent):
            self.system.network.isolate(event.name)
            self.trace.append(self._unique(KillEvent(event.name)))
            if self.fd:
                self.fd.handle_kill_event(event.name)
        elif isinstance(event, HardKillEvent):
            self.system.hard_kill(event.name)
            self.actor_terminated(event.name)
            self.trace.append(self._unique(HardKillEvent(event.name)))
            if self.fd:
                self.fd.handle_kill_event(event.name)
        elif isinstance(event, PartitionEvent):
            self._cut_link(event.a, event.b)
            self.trace.append(self._unique(PartitionEvent(event.a, event.b)))
            if self.fd:
                self.fd.handle_partition_event(event.a, event.b)
        elif isinstance(event, UnPartitionEvent):
            self.system.network.unpartition(event.a, event.b)
            self.trace.append(self._unique(UnPartitionEvent(event.a, event.b)))
            if self.fd:
                self.fd.handle_unpartition_event(event.a, event.b)
        elif isinstance(event, CodeBlockEvent):
            if event.block is not None:
                new = self.system.run_code_block(event.block)
                self._absorb(new)
            self.trace.append(self._unique(CodeBlockEvent(event.label, event.block)))
        elif isinstance(event, MsgSend):
            if event.is_external:
                entry = self.system.inject(event.rcv, event.msg)
                self._next_external_uid = exp.id
                self._record_send(entry)
            # internal sends re-occur as delivery side effects; skip.
        elif isinstance(event, MsgKept):
            self._keep_next = True
        elif isinstance(event, MsgDiscarded):
            # Datagram channels: the recorded run lost this message.
            entry = self.rpending.pop_internal(event.snd, event.rcv, event.msg)
            if entry is None:
                self._handle_absent(exp)
            else:
                self._discard(entry)
        elif isinstance(event, MsgEvent):
            self._replay_delivery(exp, event)
        elif isinstance(event, TimerDelivery):
            entry = self.rpending.pop_timer(event.rcv, event.msg)
            if entry is None:
                self._handle_absent(exp)
            elif self.system.deliverable(entry):
                self._deliver(entry)
        elif isinstance(event, Quiescence):
            self.trace.append(self._unique(Quiescence()))
        elif isinstance(event, BeginWaitQuiescence):
            self.trace.append(self._unique(BeginWaitQuiescence()))
        elif isinstance(event, BeginWaitCondition):
            self.trace.append(self._unique(BeginWaitCondition()))
        elif isinstance(event, BeginUnignorableEvents):
            self._unignorable_depth += 1
            self.trace.append(self._unique(event))
        elif isinstance(event, EndUnignorableEvents):
            self._unignorable_depth = max(0, self._unignorable_depth - 1)
            self.trace.append(self._unique(event))
        elif isinstance(event, BeginExternalAtomicBlock):
            # An external atomic block's recorded consequences are
            # unignorable during its extent: the reference defers
            # ignore-absent decisions until the live block ends
            # (STSScheduler.scala:414-444) — in this synchronous engine
            # the block's injections are deterministic, so the faithful
            # rendering is 'absences inside the block raise'.
            self._unignorable_depth += 1
            self.trace.append(self._unique(event))
        elif isinstance(event, EndExternalAtomicBlock):
            self._unignorable_depth = max(0, self._unignorable_depth - 1)
            self.trace.append(self._unique(event))
        # other meta events: ignore

    def _replay_delivery(self, exp: Unique, event: MsgEvent) -> None:
        entry = self._match_delivery(exp, event)
        if (
            entry is None
            and self.allow_peek
            and self._unignorable_depth == 0
            # External deliveries match by recorded-uid linkage, which probe
            # deliveries can never create — peeking for them is guaranteed
            # to fail and just costs two full-system snapshots.
            and not (event.is_external and not isinstance(event.msg, WildCardMatch))
        ):
            entry = self._peek(exp, event)
        keep, self._keep_next = self._keep_next, False
        if entry is None:
            self._handle_absent(exp)
            return
        if self.system.deliverable(entry):
            self._deliver(entry, keep=keep)
        # Undeliverable (partitioned/killed receiver): dropped, as recorded
        # kills/partitions dictate.

    def _match_delivery(self, exp: Unique, event: MsgEvent) -> Optional[PendingEntry]:
        if isinstance(event.msg, WildCardMatch):
            return self.rpending.pop_wildcard(
                event.rcv, event.msg, deliverable=self.system.deliverable,
                resolver=self.ambiguity_resolver,
            )
        if event.is_external:
            return self.rpending.pop_external(exp.id)
        return self.rpending.pop_internal(event.snd, event.rcv, event.msg)

    def _peek(self, exp: Unique, event: MsgEvent) -> Optional[PendingEntry]:
        """Try to *enable* the absent expected event by delivering up to
        max_peek_messages unexpected pending messages in FIFO order; keep
        the enabling prefix on success, roll everything back on failure.

        Reference: STSScheduler.peek (STSScheduler.scala:314-378) +
        IntervalPeekScheduler (IntervalPeekScheduler.scala:130-173). The
        reference checkpoints the Instrumenter and runs a separate
        scheduler; a by-construction runtime just snapshots itself."""
        system_snap = self.system.checkpoint()
        pending_snap = copy.deepcopy(self.rpending)
        trace_len = len(self.trace.events)
        deliveries_before = self.deliveries
        logs_len = len(self.logs)
        for _ in range(self.max_peek_messages):
            candidate = next(
                (e for e in self.rpending.all if self.system.deliverable(e)), None
            )
            if candidate is None:
                break
            self.rpending._discard(candidate)
            self._deliver(candidate)
            found = self._match_delivery(exp, event)
            if found is not None:
                self.peeked_prefixes += 1
                return found
        # Roll back the failed probe.
        self.system.restore(system_snap)
        self.rpending = pending_snap
        del self.trace.events[trace_len:]
        del self.logs[logs_len:]
        self.deliveries = deliveries_before
        return None

    def _handle_absent(self, exp: Unique) -> None:
        if self.absent_policy == "raise" or self._unignorable_depth > 0:
            raise ReplayException(
                f"expected event has no pending match: {exp!r}; "
                f"pending={[(e.snd, e.rcv) for e in self.rpending.all]!r}"
            )
        self.ignored_absent.append(exp)
        # Divergence-abort modes (reference: STSScheduler
        # unexpectedTransitions/abortingDueToDivergence, :167-183): strict
        # aborts on the first absence; lax tolerates a handful.
        if self.config.abort_upon_divergence:
            raise ReplayException(f"divergence (absent {exp!r}), strict abort")
        if (
            self.config.abort_upon_divergence_lax
            and len(self.ignored_absent) > max(4, self.deliveries // 4)
        ):
            raise ReplayException(
                f"divergence ({len(self.ignored_absent)} absents), lax abort"
            )


class ReplayScheduler(TraceFollowingScheduler):
    """Strict deterministic replay (reference: ReplayScheduler.scala)."""

    absent_policy = "raise"


class STSScheduler(TraceFollowingScheduler, TestOracle):
    """STS-style TestOracle: project the original trace onto the candidate
    external subsequence, replay it skipping expected-but-absent events, and
    check whether the target violation reappears
    (reference: STSScheduler.test, STSScheduler.scala:199-310)."""

    absent_policy = "ignore"

    def __init__(
        self,
        config: SchedulerConfig,
        original_trace: EventTrace,
        max_messages: int = 100_000,
        **kwargs,
    ):
        super().__init__(config, max_messages, **kwargs)
        self.original_trace = original_trace

    def test(
        self,
        externals: Sequence[ExternalEvent],
        violation_fingerprint: Any,
        stats=None,
        init: Optional[str] = None,
    ) -> Optional[EventTrace]:
        filtered = (
            self.original_trace.filter_failure_detector_messages()
            .filter_checkpoint_messages()
            .subsequence_intersection(
                externals, filter_known_absents=self.config.filter_known_absents
            )
        )
        return self.test_with_trace(filtered, externals, violation_fingerprint, stats)

    def test_with_trace(
        self,
        expected: EventTrace,
        externals: Sequence[ExternalEvent],
        violation_fingerprint: Any,
        stats=None,
    ) -> Optional[EventTrace]:
        """Replay a caller-supplied expected schedule (internal minimization
        hands in the original trace minus candidate deliveries; reference:
        RunnerUtils.testWithStsSched, RunnerUtils.scala:913-943)."""
        if stats is not None:
            stats.record_replay()
            stats.record_replay_start()
        try:
            result = self.replay(expected, externals)
        except ReplayException:
            return None
        finally:
            if stats is not None:
                stats.record_replay_end()
        if result.violation is not None and _violation_matches(
            violation_fingerprint, result.violation
        ):
            result.trace.set_original_externals(list(externals))
            return result.trace
        return None


def sts_oracle(
    config: SchedulerConfig, original_trace: EventTrace, **kwargs
) -> StatelessTestOracle:
    """Fresh STSScheduler per test() call (state-leak hygiene; reference:
    StatelessTestOracle, TestOracle.scala:69-93)."""
    return StatelessTestOracle(
        lambda: STSScheduler(config, original_trace, **kwargs)
    )
