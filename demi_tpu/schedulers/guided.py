"""GuidedScheduler: re-execute a device-recorded schedule on the host oracle.

The device explore kernel records compact (src, dst, msg) delivery records;
this scheduler replays them through the ControlledActorSystem to produce a
*full* host EventTrace (Unique ids, MsgSends, markers) that the minimization
stack consumes. It is also the host half of the device↔host parity tests:
if the guide doesn't execute cleanly here, the device kernel diverged from
oracle semantics.
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence, Tuple

from ..config import SchedulerConfig
from ..dsl import DSLApp
from ..external_events import ExternalEvent, HardKill, Kill, Partition, Send, Start, UnPartition
from ..external_events import MessageConstructor
from ..runtime.actor import dsl_actor_factory
from ..runtime.system import PendingEntry
from .base import BaseScheduler, ExecutionResult
from ..events import Quiescence

from ..device.core import (
    OP_HARDKILL,
    OP_KILL,
    OP_PARTITION,
    OP_SEND,
    OP_START,
    OP_UNPARTITION,
    OP_WAIT,
    OP_WAITCOND,
)


class GuideDivergence(Exception):
    """A guide step had no matching pending entry on the host oracle
    (over datagram channels: a second delivery of a message that was
    consumed, with no "keep" before it), or (an app whose channels are
    FIFO) delivers a message that is not its channel's oldest pending
    one, or keeps or discards what this network never would."""


class GuidedScheduler(BaseScheduler):
    def __init__(self, config: SchedulerConfig, app: DSLApp, max_messages: int = 100_000):
        super().__init__(config, max_messages)
        self.app = app
        self._pending: List[PendingEntry] = []

    # -- policy hooks ------------------------------------------------------
    def reset_pending(self) -> None:
        self._pending = []

    def add_pending(self, entry: PendingEntry) -> None:
        self._pending.append(entry)

    def pending_entries(self) -> List[PendingEntry]:
        return list(self._pending)

    def remove_pending(self, entry: PendingEntry) -> None:
        self._pending.remove(entry)

    def actor_terminated(self, name: str) -> None:
        self._pending = [
            e for e in self._pending if e.rcv != name and e.snd != name
        ]

    def choose_next(self):
        return None

    # -- guided execution --------------------------------------------------
    def execute_guide(self, guide: Sequence[Tuple]) -> ExecutionResult:
        """guide: list of ("ext", op, a, b, msg) / ("deliver", src, dst, msg,
        is_timer) from device_trace_to_guide; over datagram channels
        also ("keep", ...) (deliver, and leave the message pending) and
        ("discard", ...) (take it off the pending set undelivered)."""
        self.prepare([])
        externals: List[ExternalEvent] = []
        for step in guide:
            if step[0] == "ext":
                _, op, a, b, msg = step
                ext = self._ext_event(op, a, b, msg)
                if ext is not None:
                    externals.append(ext)
                    self._inject_one(ext)
            else:
                kind, src, dst, msg, is_timer = step
                entry = self._match(src, dst, msg, is_timer)
                if entry is None:
                    raise GuideDivergence(f"no pending match for {step!r}")
                if kind != "deliver" and (
                    self.app.channels != "datagram"
                    or entry.is_timer or entry.is_external
                ):
                    raise GuideDivergence(
                        f"{step!r}: only an actor's message over datagram "
                        f"channels is kept or discarded (this app's are "
                        f"{self.app.channels!r})"
                    )
                self._pending.remove(entry)
                if not self.system.deliverable(entry):
                    raise GuideDivergence(f"guide entry undeliverable: {step!r}")
                if kind == "discard":
                    self._discard(entry)
                else:
                    self._deliver(entry, keep=kind == "keep")
        self.trace.append(self._unique(Quiescence()))
        self.trace.set_original_externals(externals)
        self._current_externals = externals
        # A guide that stops with mail deliverable is an unfinished lane's:
        # under an invariant judged at quiescence only it has no verdict,
        # here as on the device.
        quiescent = not self.config.quiescence_invariant or not any(
            self.system.deliverable(e) for e in self._pending
        )
        violation = self.check_invariant() if quiescent else None
        if violation is not None:
            self.meta_trace.set_caused_violation()
        return ExecutionResult(
            trace=self.trace,
            violation=violation,
            deliveries=self.deliveries,
            quiescent=quiescent,
        )

    def _ext_event(self, op: int, a: int, b: int, msg) -> Optional[ExternalEvent]:
        app = self.app
        if op == OP_START:
            return Start(app.actor_name(a), ctor=dsl_actor_factory(app, a))
        if op == OP_KILL:
            return Kill(app.actor_name(a))
        if op == OP_HARDKILL:
            return HardKill(app.actor_name(a))
        if op == OP_SEND:
            trimmed = tuple(msg)
            return Send(app.actor_name(a), MessageConstructor(lambda m=trimmed: m))
        if op == OP_PARTITION:
            return Partition(app.actor_name(a), app.actor_name(b))
        if op == OP_UNPARTITION:
            return UnPartition(app.actor_name(a), app.actor_name(b))
        if op in (OP_WAIT, OP_WAITCOND):
            return None  # waits are implicit in the guide's delivery order
        raise ValueError(f"unknown guide op {op}")

    def _match(
        self, src: int, dst: int, msg: Tuple, is_timer: bool
    ) -> Optional[PendingEntry]:
        app = self.app
        dst_name = app.actor_name(dst)
        src_name = (
            app.actor_name(src) if src < app.num_actors else None
        )  # None = EXTERNAL
        fifo = app.channels == "fifo" and not is_timer
        for entry in self._pending:  # FIFO: first match
            if entry.is_timer != is_timer:
                continue
            if entry.rcv != dst_name:
                continue
            if not is_timer:
                if src_name is None:
                    if not entry.is_external:
                        continue
                elif entry.snd != src_name:
                    continue
            if self._msg_key(entry.msg) != tuple(msg):
                if fifo:
                    # ``_pending`` is in sending order: this is the
                    # channel's oldest, and only it may be delivered.
                    raise GuideDivergence(
                        f"{(src, dst, tuple(msg))!r} is not the oldest "
                        f"pending message of its FIFO channel: "
                        f"{self._msg_key(entry.msg)!r} was sent first"
                    )
                continue
            return entry
        return None

    def _msg_key(self, msg) -> Tuple:
        row = tuple(int(x) for x in msg)
        return row + (0,) * (self.app.msg_width - len(row))
