"""Fuzzer: generates external-event programs (fuzz tests).

Reference: src/main/scala/verification/fuzzing/Fuzzer.scala (194 LoC).
A fuzz test is: prefix (Starts + app bootstrap) ++ weighted random
choice among {Kill, Send, Partition, UnPartition, WaitQuiescence} ++ postfix,
always ending in WaitQuiescence, never two consecutive WaitQuiescence
(Fuzzer.scala:122-175). Seeding is explicit (the reference seeds from wall
clock, Fuzzer.scala:67 — fixed here for reproducibility).
"""

from __future__ import annotations

import random as _random
from dataclasses import dataclass, fields
from typing import Callable, List, Optional, Sequence

from .. import obs

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
    Send,
)
from .program import FuzzProgram, ProgramFrame


# The two choices that are no op of their own: a restart lowers to a
# Start, an atomic block to its sends.
_RESTART = -1
_ATOMIC = -2
_NO_WAITCOND_AFTER = (None, OP_WAIT, OP_WAITCOND)
# The choice scan's order (FuzzerWeights' field order).
_CHOICE_KINDS = (
    OP_KILL, OP_SEND, OP_WAIT, OP_PARTITION, OP_UNPARTITION, OP_HARDKILL,
    _RESTART, OP_WAITCOND, _ATOMIC,
)


class MessageGenerator:
    """App-supplied generator of external Send events
    (reference: Fuzzer.scala:8-10). ``generate`` is the contract. A
    generator may also give the row form the fuzzer records,
    ``generate_row(rng, alive) -> (target name, payload tuple) | None``
    with the same draws (``apps/common.DSLSendGenerator`` does); the
    fuzzer calls it where the class defines it at least as specifically
    as ``generate``, and unpacks ``generate``'s ``Send`` otherwise."""

    def generate(self, rng: _random.Random, alive: Sequence[str]) -> Optional[Send]:
        raise NotImplementedError

    def reset(self) -> None:
        """Called at the start of each generated program; stateful
        generators (counters etc.) restart here."""

    # ``note_fault(op, name)``, where a generator defines it, is told of
    # every Kill, HardKill (``op`` is the op's code) and restart
    # (``OP_START``) as the fuzzer draws it, with the actor's name: for a
    # generator that stands for a part of the environment which sees the
    # faults (chain replication's master, ``apps/chain.py``). The
    # ``alive`` list of the next draw cannot show a kill and a restart of
    # one actor that both fall between two sends. It takes no draw.


@dataclass
class FuzzerWeights:
    """Relative choice weights (reference: FuzzerWeights, Fuzzer.scala:24-58)."""

    kill: float = 0.01
    send: float = 0.3
    wait_quiescence: float = 0.1
    partition: float = 0.0
    unpartition: float = 0.0
    # Crash-recovery language: HardKill really stops an actor (state +
    # pending scrubbed); restart re-issues the prefix Start for a killed
    # name (recovery, EventOrchestrator.trigger_start semantics). Off by
    # default — crash/recovery fuzzing is opt-in like partitions.
    hard_kill: float = 0.0
    restart: float = 0.0
    # Condition waits (WaitCondition(cond_id=...)): drawn only for apps
    # with a DSLApp.conditions table (Fuzzer(num_conditions=...)); always
    # budgeted so an unsatisfiable predicate can't wedge a lane.
    wait_condition: float = 0.0
    # External atomic blocks: a batch of 2-4 sends marked as one logical
    # input (external_events.atomic_block) — injected atomically,
    # minimized all-or-nothing, unignorable under STS replay.
    atomic_block: float = 0.0

    def as_dict(self) -> dict:
        """kind -> weight, in field order (the tuner's coordinate space)."""
        return {f.name: getattr(self, f.name) for f in fields(self)}

    @classmethod
    def from_dict(cls, weights: dict) -> "FuzzerWeights":
        """Inverse of ``as_dict``; unknown kinds are rejected so a tuner
        typo can't silently drop a weight."""
        known = {f.name for f in fields(cls)}
        unknown = set(weights) - known
        if unknown:
            raise ValueError(f"unknown fuzzer weight kinds: {sorted(unknown)}")
        return cls(**weights)


class Fuzzer:
    def __init__(
        self,
        num_events: int,
        weights: FuzzerWeights,
        message_gen: MessageGenerator,
        prefix: Sequence[ExternalEvent],
        postfix: Sequence[ExternalEvent] = (),
        max_kills: Optional[int] = None,
        wait_budget: Optional[tuple] = None,
        num_conditions: int = 0,
        max_sends: Optional[int] = None,
        unkillable: Sequence[str] = (),
    ):
        self.num_events = num_events
        self.weights = weights
        self.message_gen = message_gen
        # Prefix, postfix and generator are fixed at construction: what
        # every program shares of them (the actor-name table, the
        # prefix's rows, the generator's row form) is made once, here.
        self.prefix = list(prefix)
        self.postfix = list(postfix)
        self._frame = ProgramFrame(self.prefix, self.postfix)
        self._send_row, self._row_sends = _send_rows(
            message_gen, self._frame.index
        )
        self._note_fault = getattr(message_gen, "note_fault", None)
        self._choice_key: Optional[tuple] = None
        self._choices: tuple = (0, ())
        # How many named wait predicates the app declares
        # (len(DSLApp.conditions)); wait_condition draws cond_ids < this.
        self.num_conditions = num_conditions
        # Keeping a quorum alive is the app's concern; cap kills so fuzz runs
        # don't trivially kill everyone (the reference relies on weights).
        self.max_kills = max_kills
        # Names no Kill or HardKill is drawn for (``DSLApp.unkillable``):
        # they stay in ``alive``, so sends and partitions reach them.
        self.unkillable = frozenset(unkillable)
        # Cap on client sends, atomic blocks' members included: where one
        # send fans out to thousands of deliveries (a 64-node broadcast
        # floods 4,033) the pool's bound is a bound on the floods in
        # flight. A capped send is a futile draw, like a dry generator.
        self.max_sends = max_sends
        # (lo, hi) delivery budget for generated WaitQuiescence events.
        # Bounded waits leave messages PENDING at the segment boundary, so
        # later externals (crashes, restarts) interleave mid-flood — without
        # this, every generated wait drains the network and crash-recovery
        # races (e.g. lost-vote-durability) are unreachable. The trailing
        # drain wait stays unlimited.
        self.wait_budget = wait_budget

    def set_weights(self, weights: FuzzerWeights) -> None:
        """Swap the choice weights at runtime (the autotune loop retunes
        them between sweep rounds). ``generate_fuzz_test`` reads
        ``self.weights`` per call, so the swap takes effect on the next
        generated program; a given (weights, seed) pair always yields the
        same program regardless of when the swap happened."""
        total = sum(getattr(weights, f.name) for f in fields(FuzzerWeights))
        if total <= 0:
            raise ValueError("fuzzer weights must have a positive total")
        self.weights = weights
        if obs.enabled():
            for f in fields(FuzzerWeights):
                obs.gauge("fuzz.weight").set(
                    getattr(weights, f.name), kind=f.name
                )

    def checkpoint_state(self) -> dict:
        """JSON-able snapshot of the fuzzer's mutable state — just the
        live weights: generation is a pure function of (weights, seed),
        which is what makes a resumed corpus sweep bit-identical."""
        return {"weights": self.weights.as_dict()}

    def restore_state(self, state: dict) -> None:
        self.set_weights(FuzzerWeights.from_dict(state["weights"]))

    def _choice_table(self):
        """``(total weight, ((kind, weight), ...))`` of the live weights,
        read anew at every call (the autotune loop swaps and edits them
        between programs) and rebuilt when a value changed. A zero
        weight is never chosen and takes nothing from the scan's ``r``,
        so the table leaves those out, to the same choice."""
        w = self.weights
        key = (
            w.kill, w.send, w.wait_quiescence, w.partition, w.unpartition,
            w.hard_kill, w.restart, w.wait_condition, w.atomic_block,
        )
        if key != self._choice_key:
            self._choice_key = key
            self._choices = (
                sum(key),
                tuple((c, wt) for c, wt in zip(_CHOICE_KINDS, key) if wt != 0),
            )
        return self._choices

    def generate_fuzz_test(self, seed: int) -> FuzzProgram:
        """The program of ``seed`` under the live weights: one loop of
        draws that records each op as a row (``fuzzing/program.py``).
        The events the host tier reads are made from the rows when
        first looked at; ``list(program)`` is a real list."""
        rng = _random.Random(seed)
        gen = self.message_gen
        gen.reset()
        frame = self._frame
        index = frame.index
        send_row = self._send_row
        note_fault = self._note_fault
        prog = FuzzProgram(frame, self._row_sends)
        kind, col_a, col_b = prog.kind, prog.a, prog.b
        payloads = prog.payloads
        alive = list(frame.names)
        killed: List[str] = []
        kills = 0
        partitions: List[tuple] = []
        cut: set = set()  # the same pairs, for the membership test
        # Kind and wait budget of the event before the next drawn one
        # (the wait rules look back one event; the prefix's last counts).
        last = frame.last_kind

        total, choices = self._choice_table()
        random = rng.random
        num_events = self.num_events
        max_kills = self.max_kills
        unkillable = self.unkillable
        # The sends drawn so far are ``len(payloads)``: the cap keeps no
        # count of its own, and costs an uncapped program one test a send.
        max_sends = self.max_sends
        generated = 0
        futile = 0
        while generated < num_events:
            if futile > 1000:
                # Every choice is exhausted (send generator dry, kills
                # capped, ...) — stop with what we have rather than spin.
                break
            before = generated
            r = total * random()  # rng.uniform(0, total), to the bit
            op = OP_SEND
            for code, wt in choices:
                if r < wt:
                    op = code
                    break
                r -= wt
            if op == OP_SEND:
                row = (
                    send_row(rng, alive)
                    if max_sends is None or len(payloads) < max_sends
                    else None
                )
                if row is not None:
                    payloads.append((len(kind), row[1]))
                    kind.append(OP_SEND)
                    col_a.append(index[row[0]])
                    col_b.append(0)
                    generated += 1
            elif op == OP_WAIT:
                if last is not None and last != OP_WAIT:
                    budget = 0
                    if self.wait_budget is not None:
                        budget = rng.randint(*self.wait_budget)
                        if budget < 1:
                            raise ValueError(
                                "WaitQuiescence budget must be None or >= 1"
                            )
                    kind.append(OP_WAIT)
                    col_a.append(budget)
                    col_b.append(0)
                    generated += 1
            elif op == OP_KILL or op == OP_HARDKILL:
                # Without unkillable names the candidates are ``alive``
                # itself: the same draw as ever.
                mortal = (
                    [name for name in alive if name not in unkillable]
                    if unkillable else alive
                )
                if mortal and (max_kills is None or kills < max_kills):
                    victim = rng.choice(mortal)
                    alive.remove(victim)
                    killed.append(victim)
                    kills += 1
                    kind.append(op)
                    col_a.append(index[victim])
                    col_b.append(0)
                    generated += 1
                    if note_fault is not None:
                        note_fault(op, victim)
            elif op == _RESTART:
                if killed:
                    name = rng.choice(killed)
                    killed.remove(name)
                    alive.append(name)
                    kind.append(OP_START)
                    col_a.append(index[name])
                    col_b.append(0)
                    generated += 1
                    if note_fault is not None:
                        note_fault(OP_START, name)
            elif op == _ATOMIC:
                # Cap the batch at the remaining event budget so generated
                # programs never overshoot num_events; with <2 remaining a
                # block is impossible — fall back to a plain send.
                remaining = num_events - generated
                if max_sends is not None:
                    remaining = min(remaining, max_sends - len(payloads))
                start = len(kind)
                # Under 2 it is 1, or 0 where the send cap is spent.
                want = (
                    rng.randint(2, min(4, remaining)) if remaining >= 2
                    else remaining
                )
                for _ in range(want):
                    row = send_row(rng, alive)
                    if row is None:
                        break
                    payloads.append((len(kind), row[1]))
                    kind.append(OP_SEND)
                    col_a.append(index[row[0]])
                    col_b.append(0)
                got = len(kind) - start
                if got >= 2:
                    prog.blocks.append((start, start + got))
                    generated += got
                elif got:  # generator ran dry mid-batch: plain send
                    generated += 1
            elif op == OP_WAITCOND:
                if self.num_conditions > 0 and last not in _NO_WAITCOND_AFTER:
                    lo, hi = self.wait_budget or (5, 40)
                    kind.append(OP_WAITCOND)
                    col_a.append(rng.randrange(self.num_conditions))
                    # Clamp: budget 0 would encode as strict/unbudgeted,
                    # breaking the always-budgeted guarantee for
                    # wait_budget ranges with lo=0.
                    col_b.append(max(1, rng.randint(lo, hi)))
                    generated += 1
            elif op == OP_PARTITION:
                pairs = [
                    (a, b)
                    for i, a in enumerate(alive)
                    for b in alive[i + 1 :]
                    if (a, b) not in cut
                ]
                if pairs:
                    pair = rng.choice(pairs)
                    partitions.append(pair)
                    cut.add(pair)
                    kind.append(OP_PARTITION)
                    col_a.append(index[pair[0]])
                    col_b.append(index[pair[1]])
                    generated += 1
            elif op == OP_UNPARTITION:
                if partitions:
                    pair = rng.choice(partitions)
                    partitions.remove(pair)
                    cut.discard(pair)
                    kind.append(OP_UNPARTITION)
                    col_a.append(index[pair[0]])
                    col_b.append(index[pair[1]])
                    generated += 1
            if generated == before:
                futile += 1
            else:
                futile = 0
                last = kind[-1]

        if obs.enabled():
            obs.counter("fuzz.programs_generated").inc()
            obs.counter("fuzz.events_generated").inc(generated)
            obs.histogram("fuzz.program_events").observe(generated)
        if frame.plain:
            # Always end in a wait, and that one unbounded: the run ends
            # with the last segment (reference semantics), so a
            # *generated* budgeted trailing wait would cap the final
            # drain. (With a prefix or postfix that is not rows, the
            # view applies the rule to the events: program.py.)
            if not kind or kind[-1] != OP_WAIT:
                kind.append(OP_WAIT)
                col_a.append(0)
                col_b.append(0)
            else:
                col_a[-1] = 0
        return prog


def _send_rows(gen, index):
    """``(rng, alive) -> (target name, payload) | None`` over ``gen``,
    and whether the payloads are int tuples (the row form): its
    ``generate_row`` where the class defines one at least as
    specifically as ``generate`` (a subclass that overrides only
    ``generate`` means that one), else ``generate`` with its ``Send``
    unpacked, the ``Send`` itself standing as the payload (one to an
    actor the prefix never started is rejected here, as the events'
    sanity check would)."""
    row_form = False
    for klass in type(gen).__mro__:
        if "generate_row" in vars(klass):
            row_form = True
            break
        if "generate" in vars(klass):
            break
    if row_form:
        return gen.generate_row, True

    def unpacked(rng, alive):
        send = gen.generate(rng, alive)
        if send is None:
            return None
        if send.name not in index:
            raise ValueError(f"{send} targets never-started actor {send.name}")
        return send.name, send

    return unpacked, False
