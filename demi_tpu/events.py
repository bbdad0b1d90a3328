"""Internal event vocabulary for recorded executions.

This is the TPU-native framework's equivalent of the reference's internal
event model (reference: src/main/scala/verification/schedulers/AuxilaryTypes.scala:12-107).
Events are plain frozen dataclasses so they are hashable, comparable, and
serializable; the device tier re-encodes the message-bearing subset as
fixed-width integer records (see demi_tpu/device/encoding.py).

Design departures from the reference:
  - No JVM object identity: ``Unique`` ids are drawn from an explicit
    ``IdGenerator`` instance that is threaded through (and checkpointed by)
    the runtime, never a process-wide singleton, so replays are reproducible.
  - ``WildCardMatch`` is data plus an optional host-side selector; the device
    tier lowers the data part (class tag + policy enum) to a jittable match.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Tuple

# Sentinel actor names. The reference uses akka's "deadLetters" as the sender
# of externally-injected messages (EventTrace.scala, EventTypes.isExternal);
# we use an explicit sentinel. The failure detector and checkpoint collector
# are placeholder endpoints whose traffic is synthesized/intercepted by the
# scheduler (reference: FailureDetector.scala:32-37, CheckpointCollector.scala:17-22).
EXTERNAL = "__external__"
FAILURE_DETECTOR = "__fd__"
CHECKPOINT_SINK = "__checkpoint_sink__"
SCHEDULER = "__scheduler__"

_SYNTHETIC_NAMES = frozenset({EXTERNAL, FAILURE_DETECTOR, CHECKPOINT_SINK, SCHEDULER})


def is_synthetic(name: str) -> bool:
    return name in _SYNTHETIC_NAMES


class IdGenerator:
    """Monotonic id source for ``Unique`` events.

    Reference: AuxilaryTypes.scala:83-93 (IDGenerator). Unlike the reference's
    global singleton, instances are explicit so that (a) serialized experiments
    can restore the counter for stable ids, and (b) parallel explorations don't
    contend on one counter.
    """

    def __init__(self, start: int = 1):
        self._next = start

    def next(self) -> int:
        value = self._next
        self._next += 1
        return value

    # -- persistence -------------------------------------------------------
    def state(self) -> int:
        return self._next

    def restore(self, state: int) -> None:
        self._next = state


class Event:
    """Base marker for internal (recorded) events."""

    __slots__ = ()


@dataclass(frozen=True)
class MsgSend(Event):
    """A message send captured by the runtime (not yet delivered)."""

    snd: str
    rcv: str
    msg: Any

    @property
    def is_external(self) -> bool:
        return self.snd == EXTERNAL


@dataclass(frozen=True)
class MsgEvent(Event):
    """A message delivery (the scheduler chose to dispatch it)."""

    snd: str
    rcv: str
    msg: Any

    @property
    def is_external(self) -> bool:
        return self.snd == EXTERNAL


@dataclass(frozen=True)
class TimerDelivery(Event):
    """Delivery of a timer the runtime converted into a schedulable event.

    All timers in the controlled runtime are scheduler-controlled events
    (the reference converts akka scheduler timers the same way,
    WeaveActor.aj:234-335); a timer is a self-send with ``timer=True`` on
    the pending pool entry.
    """

    rcv: str
    msg: Any
    fingerprint: Any = None


@dataclass(frozen=True)
class MsgKept(Event):
    """Datagram channels (``DSLApp.channels``): the network is about to
    deliver this message and keeps it pending as well, so a later
    delivery may repeat it. Recorded right before the delivery's
    ``MsgEvent``, under the id of the copy that stays pending: every
    delivery of the trace then has an id of its own."""

    snd: str
    rcv: str
    msg: Any


@dataclass(frozen=True)
class MsgDiscarded(Event):
    """Datagram channels: the network lost this pending message; it
    reached no handler. Recorded under the lost message's id."""

    snd: str
    rcv: str
    msg: Any


@dataclass(frozen=True)
class SpawnEvent(Event):
    parent: str
    name: str
    # Host tier keeps the behavior factory around for respawns; excluded from
    # equality so traces compare structurally.
    ctor: Optional[Callable[[], Any]] = field(default=None, compare=False, repr=False)


@dataclass(frozen=True)
class KillEvent(Event):
    name: str


@dataclass(frozen=True)
class HardKillEvent(Event):
    name: str


@dataclass(frozen=True)
class PartitionEvent(Event):
    a: str
    b: str


@dataclass(frozen=True)
class UnPartitionEvent(Event):
    a: str
    b: str


@dataclass(frozen=True)
class CodeBlockEvent(Event):
    """Record that an external code block ran at this point."""

    label: str = ""
    block: Optional[Callable[[], None]] = field(default=None, compare=False, repr=False)


@dataclass(frozen=True)
class Quiescence(Event):
    """No deliverable messages remained; the runtime reached quiescence."""


@dataclass(frozen=True)
class BeginWaitQuiescence(Event):
    """Marker: an external WaitQuiescence began here."""


@dataclass(frozen=True)
class BeginWaitCondition(Event):
    """Marker: an external WaitCondition began here."""


@dataclass(frozen=True)
class BeginUnignorableEvents(Event):
    """Events until the matching End must not be skipped by ignore-absent
    replay (reference: AuxilaryTypes.scala BeginUnignorableEvents)."""


@dataclass(frozen=True)
class EndUnignorableEvents(Event):
    pass


@dataclass(frozen=True)
class BeginExternalAtomicBlock(Event):
    block_id: int


@dataclass(frozen=True)
class EndExternalAtomicBlock(Event):
    block_id: int


# Events that annotate rather than drive the execution.
META_EVENT_TYPES = (
    Quiescence,
    BeginWaitQuiescence,
    BeginWaitCondition,
    BeginUnignorableEvents,
    EndUnignorableEvents,
    BeginExternalAtomicBlock,
    EndExternalAtomicBlock,
)


def is_meta_event(event: Event) -> bool:
    """Reference: AuxilaryTypes.scala:72-81 (MetaEvents.isMetaEvent)."""
    return isinstance(event, META_EVENT_TYPES)


def is_message_event(event: Event) -> bool:
    return isinstance(event, (MsgSend, MsgEvent, TimerDelivery))


@dataclass(frozen=True)
class Unique:
    """An event tagged with a trace-stable id.

    Reference: AuxilaryTypes.scala Unique. Ids disambiguate otherwise-equal
    events (two identical sends at different points) during minimization.
    """

    event: Event
    id: int

    def __repr__(self) -> str:  # compact: ids dominate debugging output
        return f"U{self.id}:{self.event!r}"


@dataclass(frozen=True)
class WildCardMatch:
    """Match any pending message satisfying a selector, in place of an exact
    (snd, rcv, fingerprint) match during replay.

    Reference: AuxilaryTypes.scala:109-118. The host tier may use an arbitrary
    ``selector(pending_msgs, backtrack_setter) -> Optional[index]``; the device
    tier only understands the declarative fields (``class_tag`` + ``policy``),
    which the ambiguity-resolution strategies compile down to
    (see demi_tpu/minimization/wildcards.py).
    """

    class_tag: Any = None  # message class/tag to match, None = any
    policy: str = "first"  # "first" | "last" | "backtrack"
    selector: Optional[Callable[..., Optional[int]]] = field(
        default=None, compare=False, repr=False
    )

    def matches(self, msg: Any, fingerprinter=None) -> bool:
        if self.class_tag is None:
            return True
        tag = self.class_tag
        if isinstance(msg, tuple) and len(msg) > 0:
            # Device-DSL messages are (tag, *fields) tuples.
            return msg[0] == tag
        return type(msg).__name__ == tag or isinstance(msg, tag) if isinstance(tag, type) else type(msg).__name__ == tag


def event_to_external_repr(event: Event) -> Optional[Tuple]:
    """Structural key used when matching internal events against external
    events (subsequence intersection). None for purely internal events."""
    if isinstance(event, SpawnEvent):
        return ("start", event.name)
    if isinstance(event, KillEvent):
        return ("kill", event.name)
    if isinstance(event, HardKillEvent):
        return ("hardkill", event.name)
    if isinstance(event, PartitionEvent):
        return ("partition", event.a, event.b)
    if isinstance(event, UnPartitionEvent):
        return ("unpartition", event.a, event.b)
    if isinstance(event, CodeBlockEvent):
        return ("codeblock", event.label)
    return None


def replace(event, **kwargs):
    return dataclasses.replace(event, **kwargs)
