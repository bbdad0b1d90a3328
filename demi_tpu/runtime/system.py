"""The controlled actor system: a sequential, fully-interposed event loop.

This is the L2 equivalent of the reference's ``Instrumenter``
(verification/Instrumenter.scala, 1388 LoC) — with the crucial design
inversion SURVEY.md §7.1 calls for: the reference *reclaims* control from a
concurrent JVM dispatcher via weaving, semaphores, and a TellEnqueue
linearization protocol (AuxilaryTypes.scala:120-145); here the framework
*owns* the event loop outright, so one-delivery-at-a-time semantics hold by
construction and none of that machinery exists.

What a delivery does:
    scheduler picks a PendingEntry -> system.deliver(entry) -> the actor's
    receive() runs; every send/timer it performs is captured into the
    returned list of new PendingEntry records (never delivered inline).

Schedulers own the pending-event structures and trace recording (as in the
reference, Scheduler.scala:13-104); the system owns actors, the simulated
network, vector clocks, and crash state.
"""

from __future__ import annotations

import copy
import dataclasses
import hashlib
import itertools
import random as _random
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

from .. import obs
from ..events import EXTERNAL, FAILURE_DETECTOR, IdGenerator
from .actor import Actor, Context


def _sanitizer():
    """The active replay sanitizer (None when DEMI_SANITIZE is off).
    Imported lazily: analysis.sanitize imports this module for the
    HarnessError base."""
    from ..analysis import sanitize

    return sanitize.active()


class HarnessError(Exception):
    """Infrastructure failure (dead bridge process, broken transport) —
    NOT an application crash. deliver() re-raises these instead of
    converting them into actor-crashed semantics, so a dead test harness
    can never masquerade as a clean passing run."""


@dataclass
class PendingEntry:
    """One captured, undelivered event (message send or armed timer).

    ``uid`` links the MsgSend record to its eventual MsgEvent record in the
    trace (reference: UniqueMsgSend/UniqueMsgEvent sharing ids,
    EventTrace.scala:16-18)."""

    uid: int
    snd: str
    rcv: str
    msg: Any
    is_timer: bool = False
    # Sender's vector clock snapshot at send time (for ShiViz export).
    vc: Optional[Dict[str, int]] = field(default=None, compare=False, repr=False)
    # Capture-time content digest (DEMI_SANITIZE only): deliver()
    # re-digests and flags messages mutated while pending.
    sent_digest: Optional[bytes] = field(default=None, compare=False, repr=False)

    @property
    def is_external(self) -> bool:
        return self.snd == EXTERNAL

    def key(self) -> Tuple[str, str]:
        return (self.snd, self.rcv)


class Network:
    """Simulated network state: symmetric link cuts + isolated ("Kill"ed)
    actors. Reference: EventOrchestrator.scala:51-59 (partitioned/
    inaccessible sets) and crosses_partition:345-351.

    A cut link drops its messages, by the rule the device tier shares
    (device/core.py: external_effects, insert_rows): what is pending
    between the two ends when the link is cut is gone
    (BaseScheduler._cut_link), and what one end sends the other while it
    is cut never becomes pending (_capture_send). So no pending entry
    ever crosses a cut link; the reference drops such a message when it
    is picked instead, which differs for one sent before the cut and
    picked after the heal. A hard-killed actor's mail goes the same way:
    what is pending to or from it at the HardKill is scrubbed
    (Scheduler.actor_terminated) and what a peer sends it while it is
    down is dropped at the send (dead letters), so heartbeats to a dead
    node cannot pile up; an external send waits for the restart. An
    isolated actor's messages are held."""

    def __init__(self):
        self.cut: Set[frozenset] = set()
        self.isolated: Set[str] = set()

    def partition(self, a: str, b: str) -> None:
        self.cut.add(frozenset((a, b)))

    def unpartition(self, a: str, b: str) -> None:
        self.cut.discard(frozenset((a, b)))

    def isolate(self, name: str) -> None:
        self.isolated.add(name)

    def unisolate(self, name: str) -> None:
        self.isolated.discard(name)

    def crosses_partition(self, snd: str, rcv: str) -> bool:
        if snd in self.isolated or rcv in self.isolated:
            return True
        return frozenset((snd, rcv)) in self.cut

    def snapshot(self):
        return (set(self.cut), set(self.isolated))

    def restore(self, snap) -> None:
        self.cut, self.isolated = set(snap[0]), set(snap[1])


class ControlledActorSystem:
    """Owns the application actors and executes single deliveries on demand."""

    def __init__(self, id_gen: Optional[IdGenerator] = None):
        self.id_gen = id_gen or IdGenerator()
        self.actors: Dict[str, Actor] = {}
        self.crashed: Set[str] = set()
        self.stopped: Set[str] = set()  # HardKilled names (may be re-Started)
        # What a HardKilled actor had on disk (Actor.durable_state), handed
        # to the actor its next Start creates. Device twin:
        # device/core.py external_effects.
        self.durable: Dict[str, Any] = {}
        # Blocked-ask semantics (bridge tier only; in-framework DSL apps are
        # CPS-style and never block — SURVEY §7.3). name -> reply predicate:
        # while present, only entries satisfying the predicate are
        # deliverable to that actor (reference: Instrumenter blocked-actor
        # tracking, Instrumenter.scala:679-727).
        self.blocked_asks: Dict[str, Callable[[PendingEntry], bool]] = {}
        # CPS-ask continuations (Context.ask): name -> (reply_pred,
        # on_reply). The matching reply runs on_reply instead of receive.
        self.pending_asks: Dict[str, Tuple[Callable, Callable]] = {}
        self.network = Network()
        self.vector_clocks: Dict[str, Dict[str, int]] = {}
        self.log_listener: Optional[Callable[[str, str], None]] = None
        # Send-capture buffer, active only inside deliver()/spawn().
        self._capturing: Optional[List[PendingEntry]] = None
        # uid of the entry currently being delivered (None outside
        # deliver / during on_start) — seeds Context.rng() so handler
        # randomness is deterministic per delivery and replay-stable.
        self._current_uid: Optional[int] = None
        # Sanitizer resolved once per capture window (_with_capture), so
        # per-send digest sealing costs no env read when disabled.
        self._active_sanitizer = None
        # Last completed (or aborted) capture buffer — the crash path reads
        # this, since _with_capture's finally clears _capturing before the
        # exception propagates.
        self._last_capture: List[PendingEntry] = []
        self._cancelled_timers: List[Tuple[str, Any]] = []

    # -- introspection -----------------------------------------------------
    def actor_names(self) -> List[str]:
        return sorted(self.actors.keys())

    def actor(self, name: str) -> Actor:
        return self.actors[name]

    def is_alive(self, name: str) -> bool:
        return name in self.actors and name not in self.crashed

    def is_crashed(self, name: str) -> bool:
        return name in self.crashed

    def deliverable(self, entry: PendingEntry, ignore_blocked: bool = False) -> bool:
        """Would delivering this entry have any effect right now?
        ``ignore_blocked`` answers "deliverable once the receiver's ask
        unblocks?" — schedulers use it to keep (not drop) messages to
        blocked actors.

        Mirrors the drop-predicate schedulers consult in the reference
        (RandomScheduler.scala:292, STSScheduler.scala:608)."""
        if entry.rcv == FAILURE_DETECTOR:
            # The perfect FD is scheduler-side and always reachable from
            # live actors (reference: FailureDetector.scala placeholder).
            return entry.snd not in self.network.isolated
        if entry.rcv not in self.actors or entry.rcv in self.crashed:
            return False
        if not ignore_blocked:
            blocked = self.blocked_asks.get(entry.rcv)
            if blocked is not None and not blocked(entry):
                return False
        if entry.is_timer or entry.is_external:
            return entry.rcv not in self.network.isolated
        return not self.network.crosses_partition(entry.snd, entry.rcv)

    # -- lifecycle ---------------------------------------------------------
    def spawn(self, name: str, factory: Callable[[], Actor]) -> List[PendingEntry]:
        """Create (or re-create after HardKill) an actor; runs on_start with
        send capture. Returns entries produced during on_start."""
        if name in self.actors and name not in self.stopped:
            # Re-Start of an isolated actor = recovery: just un-isolate
            # (reference: EventOrchestrator.trigger_start:219-231).
            self.network.unisolate(name)
            return []
        self.actors[name] = factory()
        kept = self.durable.pop(name, None)
        if kept is not None:
            # A restart after HardKill: the new actor gets back what the
            # old one had on disk (DSLApp.durable), before on_start runs.
            self.actors[name].restore_durable(kept)
        self.stopped.discard(name)
        self.crashed.discard(name)
        self.network.unisolate(name)
        self.vector_clocks.setdefault(name, {})
        return self._with_capture(
            name, lambda ctx: self.actors[name].on_start(ctx)
        )

    def hard_kill(self, name: str) -> None:
        """Actually stop the actor (reference:
        EventOrchestrator.trigger_hard_kill:243-312). The scheduler must
        scrub its own pending state via Scheduler.actor_terminated."""
        actor = self.actors.pop(name, None)
        if actor is not None:
            stop = getattr(actor, "on_stop", None)
            if stop is not None:
                stop()
            keep = getattr(actor, "durable_state", None)
            kept = keep() if keep is not None else None
            if kept is not None:
                self.durable[name] = kept
        self.stopped.add(name)
        self.crashed.discard(name)
        self.blocked_asks.pop(name, None)
        self.pending_asks.pop(name, None)

    # -- blocked-ask bookkeeping (bridge tier) ----------------------------
    def block_actor(self, name: str, reply_pred: Callable[[PendingEntry], bool]) -> None:
        self.blocked_asks[name] = reply_pred

    def unblock_actor(self, name: str) -> None:
        self.blocked_asks.pop(name, None)

    def blocked_actors(self) -> List[str]:
        return sorted(self.blocked_asks.keys())

    # -- CPS ask (in-framework tier; Context.ask) -------------------------
    def register_ask(
        self,
        name: str,
        dst: str,
        match: Optional[Callable[[Any], bool]],
        on_reply: Callable,
    ) -> None:
        """Block ``name`` until a non-timer message from ``dst`` (passing
        ``match``) arrives; route that reply to ``on_reply`` instead of
        receive (reference: blocked-actor tracking + PromiseActorRef,
        Instrumenter.scala:679-877)."""

        def reply_pred(entry: PendingEntry) -> bool:
            return (
                not entry.is_timer
                and entry.snd == dst
                and (match is None or bool(match(entry.msg)))
            )

        self.blocked_asks[name] = reply_pred
        self.pending_asks[name] = (reply_pred, on_reply)

    # -- the one delivery --------------------------------------------------
    def deliver(self, entry: PendingEntry) -> List[PendingEntry]:
        """Run the receiver's handler for this entry, capturing its effects.

        Raising handlers mark the actor crashed (reference:
        Instrumenter.actorCrashed:184-199); effects captured before the
        crash are kept."""
        assert self.deliverable(entry), f"undeliverable entry {entry!r}"
        if obs.enabled():
            obs.counter("runtime.deliveries").inc(
                kind="timer" if entry.is_timer else "message"
            )
        if entry.rcv == FAILURE_DETECTOR:
            # The FD endpoint is scheduler-side bookkeeping, not an actor;
            # delivering to it at this layer has no actor-side effect
            # (schedulers answer queries via FDMessageOrchestrator).
            return []
        actor = self.actors[entry.rcv]
        self._merge_vector_clock(entry)
        # CPS-ask reply routing: a matching reply unblocks the asker and
        # runs its continuation instead of receive.
        ask = self.pending_asks.get(entry.rcv)
        if ask is not None and ask[0](entry):
            del self.pending_asks[entry.rcv]
            self.unblock_actor(entry.rcv)
            handler = lambda ctx: ask[1](ctx, entry.msg)  # noqa: E731
        else:
            handler = lambda ctx: actor.receive(ctx, entry.snd, entry.msg)  # noqa: E731
        san = _sanitizer()
        if san is not None:
            # Replay sanitizer (DEMI_SANITIZE): pending-mutation check,
            # receive-mutation digests, and time/random traps around the
            # handler. A strict-mode trip raises SanitizerError — a
            # HarnessError, so it propagates instead of reading as an
            # application crash.
            san.check_pending(entry)
            handler = (
                lambda ctx, h=handler, e=entry: san.run(h, ctx, e)  # noqa: E731
            )
        self._current_uid = entry.uid
        try:
            return self._with_capture(entry.rcv, handler)
        except HarnessError:
            raise
        except Exception:
            # Effects performed before the crash are kept: in the reference
            # (Akka), tells made before the throw already sit in mailboxes
            # when Instrumenter.actorCrashed runs.
            obs.counter("runtime.actor_crashes").inc()
            self.crashed.add(entry.rcv)
            return self._last_capture

    def run_code_block(self, block: Callable[[], None]) -> List[PendingEntry]:
        """Execute an external CodeBlock with send capture attributed to
        EXTERNAL (reference: Instrumenter.scala:934-955)."""
        return self._with_capture(EXTERNAL, lambda ctx: block())

    # -- send capture ------------------------------------------------------
    def inject(self, rcv: str, msg: Any) -> PendingEntry:
        """An externally-injected message (snd = EXTERNAL)."""
        return PendingEntry(self.id_gen.next(), EXTERNAL, rcv, msg, vc={})

    def inject_from(self, snd: str, rcv: str, msg: Any) -> PendingEntry:
        """Synthetic-endpoint traffic (failure detector, etc.)."""
        return PendingEntry(self.id_gen.next(), snd, rcv, msg, vc={})

    # -- datagram channels (DSLApp.channels) -------------------------------
    def keep(self, entry: PendingEntry) -> PendingEntry:
        """The network delivers ``entry`` and keeps it: the copy that
        stays pending, the same message under an id of its own (the
        scheduler delivers ``entry`` itself next). Only an actor's
        message is ever repeated."""
        assert not entry.is_timer and not entry.is_external, entry
        obs.counter("runtime.net.kept").inc()
        return dataclasses.replace(entry, uid=self.id_gen.next())

    def discard(self, entry: PendingEntry) -> None:
        """The network loses the pending ``entry``: no handler runs, no
        vector clock moves. The scheduler has taken it off its pending
        structure; nothing of it is left here."""
        assert not entry.is_timer and not entry.is_external, entry
        obs.counter("runtime.net.discarded").inc()

    def _with_capture(self, name: str, fn: Callable[[Context], None]) -> List[PendingEntry]:
        # Clear before anything can raise, so deliver()'s crash path can
        # never return a previous delivery's capture.
        self._last_capture = []
        assert self._capturing is None, "re-entrant delivery"
        self._capturing = []
        self._active_sanitizer = _sanitizer()
        ctx = Context(self, name)
        try:
            fn(ctx)
        finally:
            captured = self._capturing
            self._capturing = None
            self._last_capture = captured
            self._current_uid = None
            self._active_sanitizer = None
        return captured

    def _capture_send(self, snd: str, rcv: str, msg: Any) -> None:
        assert self._capturing is not None, "send outside a delivery"
        if frozenset((snd, rcv)) in self.network.cut or rcv in self.stopped:
            return  # a cut link or a dead node drops at the send (see Network)
        vc = dict(self.vector_clocks.get(snd, {}))
        san = self._active_sanitizer
        self._capturing.append(
            PendingEntry(
                self.id_gen.next(), snd, rcv, msg, vc=vc,
                sent_digest=san.seal(msg) if san is not None else None,
            )
        )

    def _capture_timer(self, name: str, msg: Any) -> None:
        assert self._capturing is not None, "timer armed outside a delivery"
        san = self._active_sanitizer
        self._capturing.append(
            PendingEntry(
                self.id_gen.next(), name, name, msg, is_timer=True,
                sent_digest=san.seal(msg) if san is not None else None,
            )
        )

    def _cancel_timer(self, name: str, msg: Any) -> None:
        # Also retract it from the capture buffer if armed in this delivery.
        if self._capturing is not None:
            self._capturing[:] = [
                e
                for e in self._capturing
                if not (e.is_timer and e.rcv == name and e.msg == msg)
            ]
        self._cancelled_timers.append((name, msg))

    def drain_cancelled_timers(self) -> List[Tuple[str, Any]]:
        """Scheduler hook: timer cancellations since last drain (reference:
        Scheduler.notify_timer_cancel)."""
        out = self._cancelled_timers
        self._cancelled_timers = []
        return out

    def _capture_log(self, name: str, line: str) -> None:
        if self.log_listener is not None:
            self.log_listener(name, line)

    # -- harness-sanctioned randomness (Context.rng) ----------------------
    def delivery_rng(self, name: str) -> _random.Random:
        """Deterministic PRNG scoped to the current delivery: seeded by
        (actor, delivered entry uid), both stable across re-executions
        (uids come from the checkpointed IdGenerator), so replays draw
        identical streams. This is the fix the `unseeded-random` lint
        rule points at."""
        tag = "start" if self._current_uid is None else str(self._current_uid)
        seed = int.from_bytes(
            hashlib.blake2b(
                f"{name}:{tag}".encode(), digest_size=8
            ).digest(),
            "big",
        )
        return _random.Random(seed)

    # -- vector clocks (ShiViz export; reference: Util.scala:202-233) ------
    def _merge_vector_clock(self, entry: PendingEntry) -> None:
        rcv_clock = self.vector_clocks.setdefault(entry.rcv, {})
        for actor, t in (entry.vc or {}).items():
            rcv_clock[actor] = max(rcv_clock.get(actor, 0), t)
        rcv_clock[entry.rcv] = rcv_clock.get(entry.rcv, 0) + 1

    # -- whole-system checkpoint (for STSSched Peek; reference:
    # Instrumenter.scala:63-75,1230-1286) -------------------------------
    def checkpoint(self):
        return copy.deepcopy(
            (
                self.actors,
                self.crashed,
                self.stopped,
                self.network.snapshot(),
                self.vector_clocks,
                self.id_gen.state(),
                # Ask state must survive peek rollbacks: losing a blocked
                # ask would make deferred messages deliverable mid-probe.
                self.blocked_asks,
                self.pending_asks,
                self.durable,
            )
        )

    def restore(self, snap) -> None:
        (actors, crashed, stopped, net, vcs, idstate,
         blocked, asks, durable) = copy.deepcopy(snap)
        self.durable = durable
        self.actors = actors
        self.crashed = crashed
        self.stopped = stopped
        self.network.restore(net)
        self.vector_clocks = vcs
        self.blocked_asks = blocked
        self.pending_asks = asks
        self.id_gen.restore(idstate)
        # Actors whose state lives outside this process (bridge proxies)
        # roll their external side back now (BridgeActor.post_restore).
        for actor in self.actors.values():
            hook = getattr(actor, "post_restore", None)
            if hook is not None:
                hook()
