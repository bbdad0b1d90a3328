"""EventDag / AtomicEvent: DDMin's input domain.

Reference: minification/Util.scala:46-304. An AtomicEvent groups external
events that must be removed together (a Start with its Kill, a Partition with
its UnPartition, explicitly conjoined pairs such as HardKill+recovery).
EventDag views are order-preserving subsequences with union defined by the
original ordering.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from ..external_events import (
    ExternalEvent,
    Kill,
    Partition,
    Start,
    UnPartition,
)


class AtomicEvent:
    def __init__(self, *events: ExternalEvent):
        assert events
        self.events: Tuple[ExternalEvent, ...] = tuple(events)

    def __repr__(self):
        return f"Atomic({', '.join(e.label for e in self.events)})"


class EventDag:
    def get_all_events(self) -> List[ExternalEvent]:
        raise NotImplementedError

    def get_atomic_events(self) -> List[AtomicEvent]:
        raise NotImplementedError

    def remove_events(self, to_remove: Sequence[AtomicEvent]) -> "EventDag":
        raise NotImplementedError

    def union(self, other: "EventDag") -> "EventDag":
        raise NotImplementedError

    def __len__(self) -> int:
        return len(self.get_all_events())


def _remove(events: Sequence[ExternalEvent], to_remove: Sequence[AtomicEvent]) -> List[ExternalEvent]:
    removed = {e.eid for atom in to_remove for e in atom.events}
    return [e for e in events if e.eid not in removed]


class UnmodifiedEventDag(EventDag):
    def __init__(self, events: Sequence[ExternalEvent]):
        self.events = list(events)
        self.event_to_idx: Dict[int, int] = {e.eid: i for i, e in enumerate(self.events)}
        self._conjoined: Dict[int, int] = {}  # eid <-> eid, symmetric

    def conjoin_atoms(self, e1: ExternalEvent, e2: ExternalEvent) -> None:
        """Explicitly group two events into one atom (used for HardKill +
        recovery pairs; reference: RunnerUtils.scala:311-327)."""
        for e in (e1, e2):
            if e.eid not in self.event_to_idx:
                raise ValueError(f"unknown external event {e!r}")
            assert e.eid not in self._conjoined
        self._conjoined[e1.eid] = e2.eid
        self._conjoined[e2.eid] = e1.eid

    def get_all_events(self) -> List[ExternalEvent]:
        return list(self.events)

    def get_atomic_events(self) -> List[AtomicEvent]:
        return self.atomize(self.events)

    def remove_events(self, to_remove: Sequence[AtomicEvent]) -> EventDag:
        return EventDagView(self, _remove(self.events, to_remove))

    def union(self, other: EventDag) -> EventDag:
        if len(other.get_all_events()) != 0:
            raise ValueError("union with nonempty dag on the full dag")
        return self

    # -- atomization (reference: get_atomic_events, Util.scala:197-265) ----
    def atomize(self, given_events: Sequence[ExternalEvent]) -> List[AtomicEvent]:
        by_eid = {e.eid: e for e in self.events}
        atoms: List[AtomicEvent] = []
        # External atomic blocks (ExternalEvent.block_id): members form ONE
        # atom — DDMin removes them all-or-nothing, exactly the
        # reference's treatment of a task's begin/endExternalAtomicBlock
        # extent. Pairing is transitive: a Start..Kill or conjoined pair
        # with one foot in a block pulls the other foot in.
        block_of = {
            e.eid: e.block_id for e in given_events if e.block_id is not None
        }
        block_groups: Dict[int, List[ExternalEvent]] = {}

        def place(*events: ExternalEvent) -> None:
            bids = {block_of.get(e.eid) for e in events} - {None}
            if len(bids) > 1:
                raise ValueError(
                    f"events pair across atomic blocks: {events!r}"
                )
            if bids:
                block_groups.setdefault(bids.pop(), []).extend(events)
            else:
                atoms.append(AtomicEvent(*events))

        # Explicitly conjoined pairs first.
        conjoined = [e for e in given_events if e.eid in self._conjoined]
        seen: set = set()
        for e in conjoined:
            if e.eid in seen:
                continue
            partner = by_eid[self._conjoined[e.eid]]
            seen.add(e.eid)
            seen.add(partner.eid)
            place(e, partner)

        # Domain knowledge: Start..Kill and Partition..UnPartition pair up.
        open_dual: Dict[str, ExternalEvent] = {}
        for e in given_events:
            if e.eid in self._conjoined:
                continue
            if isinstance(e, Kill):
                start = open_dual.pop(("start", e.name), None)
                if start is None:
                    raise ValueError(f"Kill({e.name}) without preceding Start")
                place(start, e)
            elif isinstance(e, Start):
                # A restart after a HardKill, or a second Start of one
                # that is up: the open one stands alone.
                again = open_dual.pop(("start", e.name), None)
                if again is not None:
                    place(again)
                open_dual[("start", e.name)] = e
            elif isinstance(e, Partition):
                again = open_dual.pop(("part", e.a, e.b), None)
                if again is not None:
                    place(again)
                open_dual[("part", e.a, e.b)] = e
            elif isinstance(e, UnPartition):
                part = open_dual.pop(("part", e.a, e.b), None)
                if part is None:
                    raise ValueError(f"UnPartition({e.a},{e.b}) without Partition")
                place(part, e)
            else:
                place(e)

        # Unpaired Starts/Partitions stand alone.
        for e in open_dual.values():
            place(e)

        for members in block_groups.values():
            members.sort(key=lambda e: self.event_to_idx[e.eid])
            atoms.append(AtomicEvent(*members))

        total = sum(len(a.events) for a in atoms)
        assert total == len(given_events), (total, len(given_events))
        atoms.sort(key=lambda a: self.event_to_idx[a.events[0].eid])
        return atoms


class EventDagView(EventDag):
    def __init__(self, parent: UnmodifiedEventDag, events: Sequence[ExternalEvent]):
        self.parent = parent
        self.events = list(events)

    def get_all_events(self) -> List[ExternalEvent]:
        return list(self.events)

    def get_atomic_events(self) -> List[AtomicEvent]:
        return self.parent.atomize(self.events)

    def remove_events(self, to_remove: Sequence[AtomicEvent]) -> EventDag:
        return EventDagView(self.parent, _remove(self.events, to_remove))

    def union(self, other: EventDag) -> EventDag:
        merged = {e.eid: e for e in self.events}
        for e in other.get_all_events():
            merged[e.eid] = e
        ordered = sorted(merged.values(), key=lambda e: self.parent.event_to_idx[e.eid])
        assert len(ordered) == len(self.events) + len(other.get_all_events()), (
            "union of overlapping views"
        )
        return EventDagView(self.parent, ordered)
