"""A fuzzed program as its op rows, with the events as a view.

``Fuzzer.generate_fuzz_test`` records what it draws as plain ints, one
row per external event, in the numbering the device's program arrays use
(``external_events.OP_*``): the row's kind, its actor index(es) or wait
budget / ``cond_id``, and for a send its payload tuple. Held column-wise
(``kind``, ``a``, ``b`` are parallel lists) so the lowering writes each
with one slice assignment (``device/encoding.py``). The
``ExternalEvent`` objects the host tier works with are made from the
rows the first time anybody looks at one, all at once and in order, and
kept: ``prog[i] is prog[i]``.
"""

from __future__ import annotations

from collections.abc import Sequence as _SequenceABC
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ..external_events import (
    OP_HARDKILL,
    OP_KILL,
    OP_PARTITION,
    OP_SEND,
    OP_START,
    OP_UNPARTITION,
    OP_WAIT,
    OP_WAITCOND,
    ExternalEvent,
    HardKill,
    Kill,
    Partition,
    Send,
    Start,
    UnPartition,
    WaitCondition,
    WaitQuiescence,
    atomic_block,
    constant_message,
    sanity_check_externals,
)

#: Kinds whose ``a`` (and for the two-ended ones ``b``) is an actor index.
ACTOR_KINDS = frozenset(
    (OP_START, OP_KILL, OP_HARDKILL, OP_SEND, OP_PARTITION, OP_UNPARTITION)
)


class _SlotState:
    """Pickling for a ``__slots__`` class whose caches (``_transient``:
    slot -> the value a copy starts with) stay behind."""

    __slots__ = ()
    _transient: Dict[str, Any] = {}

    def __getstate__(self):
        return tuple(
            self._transient[name] if name in self._transient
            else getattr(self, name)
            for name in self.__slots__
        )

    def __setstate__(self, state) -> None:
        for name, value in zip(self.__slots__, state):
            setattr(self, name, value)


class ProgramFrame(_SlotState):
    """What every program of one fuzzer shares: its prefix and postfix
    events, and the actor-name table its rows index (the prefix's
    ``Start`` names, in order). ``plain`` says the rows are the whole
    program: a prefix of Starts only and no postfix, so the prefix is
    rows too (``pre_*``) and the program lowers without an event."""

    __slots__ = (
        "prefix", "postfix", "names", "index", "ctors", "plain",
        "pre_kind", "pre_a", "last_kind", "_table",
    )
    # The per-app table is a cache (and holds the app).
    _transient = {"_table": (None, None)}

    def __init__(
        self, prefix: Sequence[ExternalEvent], postfix: Sequence[ExternalEvent]
    ):
        self.prefix = list(prefix)
        self.postfix = list(postfix)
        starts = {e.name: e for e in self.prefix if isinstance(e, Start)}
        self.names: Tuple[str, ...] = tuple(starts)
        self.index: Dict[str, int] = {n: i for i, n in enumerate(self.names)}
        # A restart carries the ctor of the name's (last) prefix Start.
        self.ctors: Tuple[Optional[Callable[[], Any]], ...] = tuple(
            e.ctor for e in starts.values()
        )
        self.plain = not self.postfix and all(
            isinstance(e, Start) for e in self.prefix
        )
        self.pre_kind: List[int] = (
            [OP_START] * len(self.prefix) if self.plain else []
        )
        self.pre_a: List[int] = (
            [self.index[e.name] for e in self.prefix] if self.plain else []
        )
        # What the wait rules ask of the event before the first drawn
        # one: its kind if it is a wait, None where there is none.
        last = self.prefix[-1] if self.prefix else None
        self.last_kind: Optional[int] = (
            None if last is None
            else OP_WAIT if isinstance(last, WaitQuiescence)
            else OP_WAITCOND if isinstance(last, WaitCondition)
            else OP_START
        )
        self._table: Tuple[Any, Optional[List[int]]] = (None, None)

    def actor_ids(self, app) -> Optional[List[int]]:
        """``app.actor_id`` of each name of the table, or None where that
        is the index itself (``dsl_start_events``' order). Built once per
        app: a fuzzer feeds one."""
        if self._table[0] is not app:
            ids = [app.actor_id(n) for n in self.names]
            self._table = (
                app, None if ids == list(range(len(ids))) else ids
            )
        return self._table[1]




class FuzzProgram(_SlotState, _SequenceABC):
    """One fuzzed program: rows first, ``Sequence[ExternalEvent]`` on
    demand. Not a ``list``: code that needs one (``program + [...]``, an
    in-place edit) says ``list(program)``; what it gets is the cached
    events. A program is never edited in place, so one that is still a
    ``FuzzProgram`` is what the fuzzer drew."""

    __slots__ = (
        "frame", "lowerable", "kind", "a", "b", "payloads", "blocks",
        "_events",
    )
    # Pickled as its rows: the events are a view, made anew (fresh
    # ``eid``s) where the copy is first looked at.
    _transient = {"_events": None}

    def __init__(self, frame: ProgramFrame, row_sends: bool = True):
        self.frame = frame
        # Whether the rows alone are the program's device form: not with
        # a prefix or postfix that is no row, nor with sends that are
        # ``Send`` objects of a generator without a row form.
        self.lowerable = frame.plain and row_sends
        self.kind: List[int] = list(frame.pre_kind)
        self.a: List[int] = list(frame.pre_a)
        self.b: List[int] = [0] * len(self.kind)
        # (row, payload) of each send; the payload is an int tuple, or
        # the ``Send`` itself from a generator that has no row form.
        self.payloads: List[Tuple[int, Any]] = []
        # [start, stop) row spans of the external atomic blocks.
        self.blocks: List[Tuple[int, int]] = []
        self._events: Optional[List[ExternalEvent]] = None

    # -- the view ----------------------------------------------------------
    def events(self) -> List[ExternalEvent]:
        """The program's events, made once, in program order (so ``eid``s
        ascend, and a block's id follows its members')."""
        if self._events is None:
            self._events = self._materialise()
        return self._events

    def _materialise(self) -> List[ExternalEvent]:
        frame = self.frame
        names, ctors = frame.names, frame.ctors
        n_pre = len(frame.pre_kind)
        events: List[ExternalEvent] = list(frame.prefix)
        offset = len(events) - n_pre  # rows[n_pre:] follow the prefix
        payload_of = dict(self.payloads)
        block_stop = {stop: start for start, stop in self.blocks}
        a_col, b_col = self.a, self.b
        for i in range(n_pre, len(self.kind)):
            k, a, b = self.kind[i], a_col[i], b_col[i]
            if k == OP_SEND:
                p = payload_of[i]
                ev: ExternalEvent = (
                    p if isinstance(p, Send)
                    else Send(names[a], constant_message(p))
                )
            elif k == OP_WAIT:
                ev = WaitQuiescence(budget=a or None)
            elif k == OP_START:
                ev = Start(names[a], ctor=ctors[a])
            elif k == OP_KILL:
                ev = Kill(names[a])
            elif k == OP_HARDKILL:
                ev = HardKill(names[a])
            elif k == OP_PARTITION:
                ev = Partition(names[a], names[b])
            elif k == OP_UNPARTITION:
                ev = UnPartition(names[a], names[b])
            elif k == OP_WAITCOND:
                ev = WaitCondition(cond_id=a, budget=b)
            else:
                raise ValueError(f"row {i}: unknown kind {k}")
            events.append(ev)
            start = block_stop.get(i + 1)
            if start is not None:
                atomic_block(events[start + offset : i + 1 + offset])
        events.extend(frame.postfix)
        if not frame.plain:
            # The trailing-wait rule over events (Fuzzer.scala:122-175);
            # a plain program's rows already end as it says.
            if not events or not isinstance(events[-1], WaitQuiescence):
                events.append(WaitQuiescence())
            elif events[-1].budget is not None and not frame.postfix:
                # The run ends with the last segment (reference
                # semantics); a *generated* budgeted trailing wait would
                # cap the final drain. A user-supplied postfix wait is
                # kept verbatim — a bounded final drain there is
                # deliberate.
                events[-1] = WaitQuiescence()
        sanity_check_externals(events)
        return events

    def __len__(self) -> int:
        if self._events is not None:
            return len(self._events)
        if self.frame.plain:
            return len(self.kind)
        return len(self.events())

    def __getitem__(self, i):
        return self.events()[i]

    def __iter__(self):
        return iter(self.events())

    def __repr__(self) -> str:
        return f"FuzzProgram({len(self)} events)"

