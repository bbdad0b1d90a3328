"""RandomScheduler: the fuzzer — explores random interleavings of pending
messages subject to partitions, with periodic invariant checks.

Reference: schedulers/RandomScheduler.scala (909 LoC). Policy notes carried
over:
  - A chosen-but-undeliverable entry (crossing a partition / isolated or
    stopped receiver) is *dropped*, like a real lossy network
    (RandomScheduler.scala:292).
  - Timer loop-avoidance: a timer re-armed immediately after its own delivery
    is parked and only re-enters the pending pool after some non-timer
    delivery (justScheduledTimers/timersToResend,
    RandomScheduler.scala:100-117,549-559).
  - Pluggable RandomizationStrategy: FullyRandom (uniform over the pending
    set) or SrcDstFIFO (per-(src,dst) FIFO queues = TCP-like semantics,
    random across pairs; RandomScheduler.scala:624-909).
  - Beyond the reference: the "datagram" strategy (``DSLApp.channels``) is
    FullyRandom's choice plus an outcome for each chosen message: deliver
    and consume, deliver and keep pending, or discard undelivered
    (``choose_outcome``; device twin: ``explore._datagram_outcome``).

Randomness is an explicit seeded PRNG — the reference seeds from wall clock
(Util.scala:110), which SURVEY.md §7.3 flags as a reproducibility bug to fix.
"""

from __future__ import annotations

import random as _random
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..config import SchedulerConfig
from ..external_events import ExternalEvent
from ..runtime.system import PendingEntry
from ..trace import EventTrace
from .base import BaseScheduler, ExecutionResult


class RandomizationStrategy:
    """Owns the pending-event structure and the random choice."""

    def __init__(self, rng: _random.Random):
        self.rng = rng

    def add(self, entry: PendingEntry) -> None:
        raise NotImplementedError

    def pop(self) -> Optional[PendingEntry]:
        """Remove and return a random candidate (deliverability is checked
        by the caller)."""
        raise NotImplementedError

    def entries(self) -> List[PendingEntry]:
        raise NotImplementedError

    def remove_for_actor(self, name: str) -> None:
        raise NotImplementedError

    def remove_entry(self, entry: PendingEntry) -> None:
        raise NotImplementedError

    def requeue(self, entry: PendingEntry) -> None:
        """Put back an entry popped but not delivered (blocked receiver),
        preserving the structure's ordering guarantees. Default: add()."""
        self.add(entry)

    def clear(self) -> None:
        raise NotImplementedError


class FullyRandom(RandomizationStrategy):
    """Uniform over all pending events (reference:
    RandomScheduler.scala:635-697, backed by a RandomizedHashSet).

    ``timer_weight`` scales the probability of picking a timer relative to
    a message: timer-driven protocols (Raft elections) otherwise spend most
    of the schedule churning timeouts. 1.0 = plain uniform."""

    def __init__(self, rng: _random.Random, timer_weight: float = 1.0):
        super().__init__(rng)
        self.timer_weight = timer_weight
        self._pool: List[PendingEntry] = []

    def add(self, entry: PendingEntry) -> None:
        self._pool.append(entry)

    def pop(self) -> Optional[PendingEntry]:
        if not self._pool:
            return None
        if self.timer_weight != 1.0:
            timers, non_timers = [], []
            for i, e in enumerate(self._pool):
                (timers if e.is_timer else non_timers).append(i)
            wt = self.timer_weight * len(timers)
            total = wt + len(non_timers)
            if total > 0 and timers and non_timers:
                if self.rng.uniform(0, total) < wt:
                    i = self.rng.choice(timers)
                else:
                    i = self.rng.choice(non_timers)
                self._pool[i], self._pool[-1] = self._pool[-1], self._pool[i]
                return self._pool.pop()
        # O(1) random removal: swap chosen with last, pop
        # (the reference's RandomizedHashSet trick, Util.scala:110-185).
        i = self.rng.randrange(len(self._pool))
        self._pool[i], self._pool[-1] = self._pool[-1], self._pool[i]
        return self._pool.pop()

    def entries(self) -> List[PendingEntry]:
        return list(self._pool)

    def remove_for_actor(self, name: str) -> None:
        self._pool = [e for e in self._pool if e.rcv != name and e.snd != name]

    def remove_entry(self, entry: PendingEntry) -> None:
        self._pool.remove(entry)

    def clear(self) -> None:
        self._pool.clear()


class SrcDstFIFO(RandomizationStrategy):
    """Per-(src,dst) FIFO channels: pick a random nonempty channel, deliver
    its head — models TCP-ordered links (reference:
    RandomScheduler.scala:702-909). Timers live in a separate random pool."""

    def __init__(self, rng: _random.Random):
        super().__init__(rng)
        self._queues: Dict[Tuple[str, str], List[PendingEntry]] = {}
        self._timers: List[PendingEntry] = []

    def add(self, entry: PendingEntry) -> None:
        if entry.is_timer:
            self._timers.append(entry)
        else:
            self._queues.setdefault(entry.key(), []).append(entry)

    def pop(self) -> Optional[PendingEntry]:
        nonempty = [k for k, q in self._queues.items() if q]
        n_choices = len(nonempty) + len(self._timers)
        if n_choices == 0:
            return None
        i = self.rng.randrange(n_choices)
        if i < len(nonempty):
            return self._queues[nonempty[i]].pop(0)
        return self._timers.pop(i - len(nonempty))

    def entries(self) -> List[PendingEntry]:
        out = [e for q in self._queues.values() for e in q]
        out.extend(self._timers)
        return out

    def remove_for_actor(self, name: str) -> None:
        for key in list(self._queues):
            if name in key:
                del self._queues[key]
        self._timers = [e for e in self._timers if e.rcv != name]

    def remove_entry(self, entry: PendingEntry) -> None:
        if entry.is_timer:
            self._timers.remove(entry)
        else:
            self._queues[entry.key()].remove(entry)

    def requeue(self, entry: PendingEntry) -> None:
        """A popped channel head goes back to the FRONT of its channel —
        appending would silently reorder the TCP-modeled FIFO."""
        if entry.is_timer:
            self._timers.append(entry)
        else:
            self._queues.setdefault(entry.key(), []).insert(0, entry)

    def clear(self) -> None:
        self._queues.clear()
        self._timers.clear()


class RandomScheduler(BaseScheduler):
    def __init__(
        self,
        config: SchedulerConfig,
        seed: int = 0,
        max_messages: int = 10_000,
        invariant_check_interval: int = 0,
        strategy: str = "fully_random",
        timer_weight: float = 1.0,
        dup_weight: float = 0.0,
        drop_weight: float = 0.0,
        max_dups: int = 0,
        max_drops: int = 0,
    ):
        super().__init__(config, max_messages, invariant_check_interval)
        self.seed = seed
        self.strategy_name = strategy
        self.timer_weight = timer_weight
        if strategy != "datagram" and (dup_weight or drop_weight):
            raise ValueError(
                "dup_weight and drop_weight need strategy='datagram' (an "
                "app whose DSLApp.channels are 'datagram')"
            )
        # The datagram strategy's weights and per-execution budgets.
        self.dup_weight, self.drop_weight = dup_weight, drop_weight
        self.max_dups, self.max_drops = max_dups, max_drops
        self.dups = self.drops = 0
        self.rng = _random.Random(seed)
        self.pending = self._make_strategy()
        self._just_delivered_timers: set = set()
        self._parked_timers: List[PendingEntry] = []

    def _make_strategy(self) -> RandomizationStrategy:
        if self.strategy_name in ("fully_random", "datagram"):
            return FullyRandom(self.rng, timer_weight=self.timer_weight)
        if self.strategy_name == "srcdst_fifo":
            return SrcDstFIFO(self.rng)
        raise ValueError(f"unknown strategy {self.strategy_name}")

    # -- policy hooks ------------------------------------------------------
    def reset_pending(self) -> None:
        self.rng = _random.Random(self.seed)
        self.pending = self._make_strategy()
        self._just_delivered_timers = set()
        self._parked_timers = []
        self.dups = self.drops = 0

    def choose_outcome(self, entry: PendingEntry) -> str:
        """The datagram strategy's draw, one for every chosen entry as
        the device step draws one (``explore._datagram_outcome``): the
        first ``dup_weight`` of it keeps an actor's message pending while
        ``max_dups`` lasts, the next ``drop_weight`` loses it while
        ``max_drops`` lasts. A timer and an external send are delivered
        exactly once."""
        if self.strategy_name != "datagram":
            return "deliver"
        u = self.rng.random()
        if entry.is_timer or entry.is_external:
            return "deliver"
        if u < self.dup_weight:
            if self.dups < self.max_dups:
                self.dups += 1
                return "keep"
        elif u < self.dup_weight + self.drop_weight:
            if self.drops < self.max_drops:
                self.drops += 1
                return "discard"
        return "deliver"

    def add_pending(self, entry: PendingEntry) -> None:
        if entry.is_timer:
            key = (entry.rcv, self.config.fingerprinter.fingerprint(entry.msg))
            if key in self._just_delivered_timers:
                self._parked_timers.append(entry)
                return
        self.pending.add(entry)

    def choose_next(self) -> Optional[PendingEntry]:
        # Messages to ask-blocked actors are NOT lossy-network droppable:
        # they stay pending until the actor unblocks (reference:
        # Instrumenter blocked-actor tracking keeps mailboxes intact,
        # Instrumenter.scala:679-727).
        stashed: List[PendingEntry] = []
        try:
            while True:
                entry = self.pending.pop()
                if entry is None:
                    return None
                if self.system.deliverable(entry):
                    return entry
                if self.system.deliverable(entry, ignore_blocked=True):
                    stashed.append(entry)
                    continue
                # else: dropped, like a lossy network (see module docstring)
        finally:
            # Reverse order: repeated front-inserts then restore the
            # original relative order of same-channel entries.
            for e in reversed(stashed):
                self.pending.requeue(e)

    def pending_entries(self) -> List[PendingEntry]:
        return self.pending.entries() + list(self._parked_timers)

    def remove_pending(self, entry: PendingEntry) -> None:
        if entry in self._parked_timers:
            self._parked_timers.remove(entry)
        else:
            self.pending.remove_entry(entry)

    def actor_terminated(self, name: str) -> None:
        self.pending.remove_for_actor(name)
        self._parked_timers = [e for e in self._parked_timers if e.rcv != name]

    def notify_timer_cancel(self, name: str, msg: Any) -> None:
        for e in self.pending.entries():
            if e.is_timer and e.rcv == name and e.msg == msg:
                self.pending.remove_entry(e)
                return
        for e in self._parked_timers:
            if e.rcv == name and e.msg == msg:
                self._parked_timers.remove(e)
                return

    def on_delivery(self, unique, entry: PendingEntry) -> None:
        if entry.is_timer:
            key = (entry.rcv, self.config.fingerprinter.fingerprint(entry.msg))
            self._just_delivered_timers.add(key)
        else:
            if self._just_delivered_timers or self._parked_timers:
                self._just_delivered_timers.clear()
                for t in self._parked_timers:
                    self.pending.add(t)
                self._parked_timers = []

    # -- fuzzing entry points ---------------------------------------------
    def explore(
        self,
        externals: Sequence[ExternalEvent],
        max_executions: int = 100,
    ) -> Optional[ExecutionResult]:
        """Run up to max_executions random executions of the program; return
        the first violating one (reference: RandomScheduler.explore,
        RandomScheduler.scala:226-272)."""
        for i in range(max_executions):
            self.seed = self.rng.randrange(2**63)
            result = self.execute(externals)
            if result.violation is not None:
                return result
        return None

    def non_blocking_explore(
        self,
        externals: Sequence[ExternalEvent],
        max_executions: int = 100,
    ):
        """Non-blocking form of ``explore`` (reference:
        RandomScheduler.nonBlockingExplore, RandomScheduler.scala:184-211
        — there a daemon runs exploration and hands the result to a
        callback; the Python-idiomatic analog is a generator the caller
        drains at its own pace). Yields every ExecutionResult as it
        completes — violating or not — so the caller can interleave its
        own work, harvest multiple violations, or stop early by closing
        the generator. The device-tier twin is
        parallel.sweep.SweepDriver.sweep_async."""
        for _ in range(max_executions):
            self.seed = self.rng.randrange(2**63)
            yield self.execute(externals)

    # -- TestOracle interface (reference: RandomScheduler.test,
    # RandomScheduler.scala:45; used by randomDDMin) ----------------------
    def test(
        self,
        externals: Sequence[ExternalEvent],
        violation_fingerprint: Any,
        stats=None,
        init: Optional[str] = None,
        max_executions: int = 1,
    ) -> Optional[EventTrace]:
        for _ in range(max_executions):
            self.seed = self.rng.randrange(2**63)
            result = self.execute(externals)
            if stats is not None:
                stats.record_replay()
            if result.violation is not None and _violation_matches(
                violation_fingerprint, result.violation
            ):
                return result.trace
        return None


def _violation_matches(target: Any, found: Any) -> bool:
    """Reference: RandomScheduler.violationMatches
    (RandomScheduler.scala:138-154)."""
    if target is None:
        return True
    matcher = getattr(target, "matches", None)
    if matcher is not None:
        return bool(matcher(found))
    return target == found
