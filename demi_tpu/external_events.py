"""User-facing external event vocabulary: the fault/input language.

Reference: src/main/scala/verification/ExternalEvents.scala (202 LoC).
External events are what the fuzzer generates and what DDMin minimizes.
Each instance carries a unique ``eid`` (reference: UniqueExternalEvent,
ExternalEvents.scala:14-31) so that structurally-equal events at different
trace positions stay distinguishable across subsequence trials.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional, Sequence


# Op codes of an external event's lowered row: one numbering for the
# fuzzer's op rows (fuzzing/program.py) and the device's program arrays
# (device/core.py re-exports them). A closure-form WaitCondition and a
# CodeBlock are host-tier-only and have none.
OP_END = 0
OP_START = 1
OP_KILL = 2
OP_SEND = 3
OP_WAIT = 4  # a = delivery budget, 0 = until quiescence
OP_PARTITION = 5
OP_UNPARTITION = 6
OP_HARDKILL = 7
# Wait until app condition `a` holds (DSLApp.conditions[a]), with optional
# delivery budget `b` — the device-lowerable WaitCondition form.
OP_WAITCOND = 8


class _EidCounter:
    def __init__(self):
        self._next = 1

    def next(self) -> int:
        value = self._next
        self._next += 1
        return value

    def ensure_floor(self, floor: int) -> None:
        """Advance past ``floor`` — deserialization restores recorded eids
        and must keep fresh events from aliasing them (eids are identity)."""
        if self._next <= floor:
            self._next = floor + 1


_eid_counter = _EidCounter()


def _next_eid() -> int:
    return _eid_counter.next()


def ensure_eid_floor(floor: int) -> None:
    _eid_counter.ensure_floor(floor)


class MessageConstructor:
    """Late-bound constructor for externally injected messages.

    Reference: ExternalMessageConstructor (ExternalEvents.scala:43-55). Late
    binding lets replays rebuild messages that close over live actor handles,
    and ``mask_components`` supports payload shrinking
    (RunnerUtils.shrinkSendContents, RunnerUtils.scala:1007-1094): a
    constructor may expose sub-components (e.g. a membership list) that the
    minimizer can mask out one at a time.
    """

    def __init__(self, fn: Callable[[], Any], components: Optional[Sequence[Any]] = None):
        self._fn = fn
        self._components = list(components) if components is not None else []
        self._masked: frozenset = frozenset()

    def __call__(self) -> Any:
        return self.construct()

    def construct(self) -> Any:
        if self._masked and self._components:
            return self._fn_with_mask()
        return self._fn()

    # -- shrinking support -------------------------------------------------
    @property
    def components(self) -> List[Any]:
        return list(self._components)

    def masked(self, masked_indices) -> "MessageConstructor":
        clone = MessageConstructor(self._fn, self._components)
        clone._masked = frozenset(masked_indices)
        return clone

    def _fn_with_mask(self):
        kept = [c for i, c in enumerate(self._components) if i not in self._masked]
        return self._fn(kept) if _accepts_arg(self._fn) else self._fn()

    def __repr__(self):
        return f"MessageConstructor(masked={sorted(self._masked)})"


def _accepts_arg(fn) -> bool:
    try:
        import inspect

        sig = inspect.signature(fn)
        return len(sig.parameters) >= 1
    except (TypeError, ValueError):
        return False


def constant_message(msg: Any) -> MessageConstructor:
    return MessageConstructor(lambda: msg)


@dataclass(frozen=True, eq=False)
class ExternalEvent:
    """Base class. Identity (eid) equality: minimization must distinguish
    equal-looking events at different positions."""

    eid: int = field(default_factory=_next_eid, init=False)
    # External atomic block membership (reference:
    # ExternalEventInjector.scala:179-216 begin/endExternalAtomicBlock):
    # consecutive events sharing a block id inject as one atomic batch
    # (Begin/End markers recorded around them), minimize as ONE atom
    # (all-or-nothing, never interleaved), and replay unignorably. Assign
    # via ``atomic_block(...)``.
    block_id: Optional[int] = field(default=None, init=False, compare=False)

    # Identity semantics but stable hashing across pickling.
    def __eq__(self, other):
        return isinstance(other, ExternalEvent) and self.eid == other.eid

    def __hash__(self):
        return hash(self.eid)

    @property
    def label(self) -> str:
        return f"e{self.eid}"


@dataclass(frozen=True, eq=False)
class Start(ExternalEvent):
    """Spawn (or respawn, re-enabling traffic) an actor by name.

    Reference: ExternalEvents.scala Start(propCtor, name); a later Start for
    a previously Killed name acts as recovery (EventOrchestrator.trigger_start).
    """

    name: str = ""
    ctor: Optional[Callable[[], Any]] = field(default=None, compare=False, repr=False)


@dataclass(frozen=True, eq=False)
class Kill(ExternalEvent):
    """Isolate an actor: all of its traffic is dropped, but it is not stopped
    (reference semantics: Kill = isolation, EventOrchestrator.scala:51-59)."""

    name: str = ""


@dataclass(frozen=True, eq=False)
class HardKill(ExternalEvent):
    """Actually stop the actor and scrub its pending state
    (reference: EventOrchestrator.trigger_hard_kill:243-312)."""

    name: str = ""


@dataclass(frozen=True, eq=False)
class Send(ExternalEvent):
    name: str = ""
    msg_ctor: MessageConstructor = field(default=None, compare=False, repr=False)

    def message(self) -> Any:
        return self.msg_ctor.construct()


@dataclass(frozen=True, eq=False)
class WaitQuiescence(ExternalEvent):
    """Block injection until no deliverable messages remain.

    ``budget`` bounds the wait: advance after quiescence OR after that many
    deliveries in the segment, whichever first. Timer-driven apps (Raft
    elections re-arm forever) never truly quiesce — the reference copes by
    capping whole runs (RandomScheduler.setMaxMessages,
    RandomScheduler.scala:54-57); a per-segment budget keeps multi-phase
    programs progressing instead. None = strict quiescence; budget must be
    >= 1 (0 would mean opposite things on the two tiers)."""

    budget: Optional[int] = None

    def __post_init__(self):
        if self.budget is not None and self.budget < 1:
            raise ValueError("WaitQuiescence budget must be None or >= 1")


@dataclass(frozen=True, eq=False)
class WaitCondition(ExternalEvent):
    """Block injection until a condition holds
    (reference: ExternalEventInjector.scala:541-580 re-arm semantics).

    Two forms: ``cond`` — an arbitrary zero-arg host closure (host-tier
    only, like the reference's); ``cond_id`` — an index into the app's
    ``DSLApp.conditions`` jax predicates, usable on BOTH tiers (the
    device kernels end the dispatch segment when the predicate holds).
    ``budget`` optionally bounds the wait in deliveries, like
    WaitQuiescence."""

    cond: Callable[[], bool] = field(default=None, compare=False, repr=False)
    cond_id: Optional[int] = None
    budget: Optional[int] = None


@dataclass(frozen=True, eq=False)
class Partition(ExternalEvent):
    a: str = ""
    b: str = ""


@dataclass(frozen=True, eq=False)
class UnPartition(ExternalEvent):
    a: str = ""
    b: str = ""


@dataclass(frozen=True, eq=False)
class CodeBlock(ExternalEvent):
    """Run an arbitrary host-side block atomically at this point."""

    block: Callable[[], None] = field(default=None, compare=False, repr=False)
    label: str = ""


def externals_summary(events: Sequence[ExternalEvent]) -> str:
    parts = []
    for e in events:
        if isinstance(e, Start):
            parts.append(f"Start({e.name})")
        elif isinstance(e, Kill):
            parts.append(f"Kill({e.name})")
        elif isinstance(e, HardKill):
            parts.append(f"HardKill({e.name})")
        elif isinstance(e, Send):
            parts.append(f"Send({e.name})")
        elif isinstance(e, WaitQuiescence):
            parts.append("WaitQuiescence")
        elif isinstance(e, WaitCondition):
            parts.append("WaitCondition")
        elif isinstance(e, Partition):
            parts.append(f"Partition({e.a},{e.b})")
        elif isinstance(e, UnPartition):
            parts.append(f"UnPartition({e.a},{e.b})")
        elif isinstance(e, CodeBlock):
            parts.append(f"CodeBlock({e.label})")
        else:
            parts.append(type(e).__name__)
    return " ".join(parts)


def atomic_block(
    events: Sequence[ExternalEvent], block_id: Optional[int] = None
) -> List[ExternalEvent]:
    """Mark ``events`` as one external atomic block (reference:
    beginExternalAtomicBlock / endExternalAtomicBlock,
    ExternalEventInjector.scala:179-216 — the mechanism a nondeterministic
    external client uses to mark 'this batch is one logical input'):

      - injection applies the members back-to-back with Begin/End markers
        recorded around them (schedulers/base.py);
      - DDMin removes the block all-or-nothing and never interleaves
        other events into it (minimization/event_dag.py atomize);
      - STS replay treats the block's recorded consequences as
        unignorable — absences inside it raise instead of being skipped
        (schedulers/replay.py), the sequential-world rendering of the
        reference's 'wait for block end before deciding whether its
        messages show up' (STSScheduler.scala:414-444).

    Returns the same event objects (mutated in place: block ids ride the
    eid counter so deserialization can floor past them). Members must be
    used contiguously and must not contain Wait* events."""
    events = list(events)
    bid = block_id if block_id is not None else _next_eid()
    for e in events:
        if isinstance(e, (WaitQuiescence, WaitCondition)):
            raise ValueError(f"atomic blocks cannot contain waits: {e!r}")
        object.__setattr__(e, "block_id", bid)
    return events


def sanity_check_externals(events: Sequence[ExternalEvent]) -> None:
    """Reject trivially malformed fuzz tests: sends/kills of never-started
    actors (reference: Fuzzer.validateFuzzTest, Fuzzer.scala:126-133) and
    non-contiguous atomic blocks."""
    started = set()
    closed_blocks = set()
    open_block: Optional[int] = None
    for e in events:
        if e.block_id != open_block:
            if open_block is not None:
                closed_blocks.add(open_block)
            if e.block_id in closed_blocks:
                raise ValueError(
                    f"atomic block {e.block_id} is not contiguous at {e}"
                )
            open_block = e.block_id
        if isinstance(e, Start):
            started.add(e.name)
        elif isinstance(e, (Kill, HardKill)):
            if e.name not in started:
                raise ValueError(f"{e} targets never-started actor {e.name}")
        elif isinstance(e, Send):
            if e.name not in started:
                raise ValueError(f"{e} targets never-started actor {e.name}")
        elif isinstance(e, (WaitQuiescence, WaitCondition)):
            if e.block_id is not None:
                raise ValueError(f"atomic blocks cannot contain waits: {e!r}")
