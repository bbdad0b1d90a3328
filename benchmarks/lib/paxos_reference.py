"""The plain reference of the ``paxos11-datagram`` deployment: Multi-Paxos
as van Renesse & Altinbuken publish it (*Paxos Made Moderately Complex*,
ACM Computing Surveys 47(3), 2015), f + 1 replicas, f + 1 leaders and
2f + 1 acceptors as Python objects with real sets (``waitfor``) and dicts
(``pvalues``, ``accepted``, ``proposals``, ``decisions``), over a network
that may deliver a pending message and keep it, or lose it. No JAX, no
masks, nothing of the program and nothing of the host tier: it reads a
lane's recorded trace in the record encoding ``demi_tpu/device/core.py``
documents (int rows ``(kind, a, b, msg...)``: kind 1 a delivery from ``a``
to ``b``, 2 a timer at ``b``, **5 a delivery that leaves its message
pending, 6 a pending message lost undelivered**, ``10 + op`` an external
event; the clients are sender n) and replays it by the protocol's own
rules.

- the network: a bag of pending messages. A send joins it. A delivery
  (kind 1) takes one pending copy of exactly that message out and hands it
  to the receiver; a kept delivery (kind 5) hands it over and leaves the
  copy; a discard (kind 6) takes the copy out and hands it to nobody.
  **A record whose message is not pending is ``Diverged``**: a second
  delivery of a message that was consumed, with no kept delivery before
  it. Only an actor's message is kept or discarded (a timer or a client's
  send that is, is ``Diverged``), and at most ``max_dups`` and
  ``max_drops`` times a schedule where the caller states them. A message
  to a stopped (hard-killed) actor is lost at the send, but a client's,
  which waits; what is pending to or from an actor when it is hard-killed
  is lost, its timers too. (Isolation and link cuts, which the
  deployment's mix never draws, follow the harness's rules.)
- a ballot is the pair (round, leader), compared as pairs; on the wire it
  is ``round * leaders + leader``; bottom is -1 (``None`` here).
- a message is ``(tag, f1, f2, f3)`` and, on a P1B, the acceptor's table:
  ``log_cap`` ballots then ``log_cap`` commands, -1 and 0 where it has
  accepted nothing.
- replica, acceptor, leader with its scout and commanders: the paper's
  figures, as ``demi_tpu/apps/paxos.py``'s module doc tables them (the
  configuration file lists the departures: scouts and commanders are part
  of their leader and a reply names the scout or commander it answers; a
  BACKOFF timer between a preemption and the next scout).
  ``bug="count_replies"``: a scout and a commander count matching replies
  and fire at a majority; the protocol as published keeps the set of
  acceptors that answered.
- the invariant, judged after every delivery that reached a handler and
  once more where the trace ends, over replicas that are up: two
  different commands decided for one slot, held by two replicas or handed
  to one (code 1).

``replay`` raises ``Diverged`` where the trace does what this network or
this protocol cannot, or goes on after the verdict.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

REQUEST, PROPOSE, DECISION, P1A, P1B, P2A, P2B, BACKOFF = range(1, 9)
WINDOW = 5

REC_DELIVERY, REC_TIMER, REC_KEPT, REC_DISCARDED, REC_EXT_BASE = 1, 2, 5, 6, 10
OP_START, OP_KILL, OP_SEND, OP_WAIT = 1, 2, 3, 4
OP_PARTITION, OP_UNPARTITION, OP_HARDKILL, OP_WAITCOND = 5, 6, 7, 8

Ballot = Tuple[int, int]  # (round, leader index)


class Diverged(Exception):
    """The trace did something the network or the protocol does not allow."""


@dataclass
class Replica:
    slot_in: int = 1
    slot_out: int = 1
    requests: Set[int] = field(default_factory=set)
    proposals: Dict[int, int] = field(default_factory=dict)
    decisions: Dict[int, int] = field(default_factory=dict)
    performed: int = 0
    handed_both: bool = False


@dataclass
class Acceptor:
    ballot: Optional[Ballot] = None
    # slot -> (ballot, command) of the highest ballot accepted there
    accepted: Dict[int, Tuple[Ballot, int]] = field(default_factory=dict)


@dataclass
class Leader:
    ballot: Ballot
    active: bool = False
    proposals: Dict[int, int] = field(default_factory=dict)
    # The scout: alive?, who answered (the bug: how many answers), pmax.
    scouting: bool = True
    scout_answered: Set[int] = field(default_factory=set)
    scout_answers: int = 0
    pvalues: Dict[int, Tuple[Ballot, int]] = field(default_factory=dict)
    # The commanders, one a slot: slot -> (its ballot, who answered), and
    # how many answers it has heard.
    commanders: Dict[int, Tuple[Ballot, Set[int]]] = field(default_factory=dict)
    commander_answers: Dict[int, int] = field(default_factory=dict)
    adoptions: int = 0
    preempts: int = 0


@dataclass
class Outcome:
    digests: List[tuple]  # one an actor, by role (``digest``)
    alive: List[bool]
    code: int
    step: int             # deliveries when the verdict fell
    deliveries: int       # deliveries that reached a handler
    kept: int
    discarded: int
    peak_pending: int
    committed: int        # the largest slot_out - 1 of a replica
    adoptions: int
    preempts: int


class _Cluster:
    def __init__(self, n: int, log_cap: int, bug: Optional[str]):
        f, rest = divmod(n - 3, 4)
        if rest or f < 1:
            raise ValueError(f"{n} actors are no f+1, f+1, 2f+1 deployment")
        self.n, self.cap, self.bug = n, log_cap, bug
        self.replicas = list(range(0, f + 1))
        self.leaders = list(range(f + 1, 2 * f + 2))
        self.acceptors = list(range(2 * f + 2, n))
        self.majority = len(self.acceptors) // 2 + 1
        self.started = [False] * n
        self.isolated = [False] * n
        self.stopped = [False] * n
        self.cut: set = set()
        self.actors: List[object] = [None] * n
        # Pending: (src, dst, msg, is_timer); the clients are src n.
        self.pending: List[tuple] = []

    # -- wire ---------------------------------------------------------------
    def wire(self, ballot: Optional[Ballot]) -> int:
        return -1 if ballot is None else ballot[0] * len(self.leaders) + ballot[1]

    def unwire(self, b: int) -> Optional[Ballot]:
        return None if b < 0 else divmod(b, len(self.leaders))

    def message(self, tag, f1=0, f2=0, f3=0, table=None) -> tuple:
        ballots, commands = [-1] * self.cap, [0] * self.cap
        if table is None:
            ballots = [0] * self.cap
        else:
            for slot, (ballot, command) in table.items():
                ballots[slot - 1], commands[slot - 1] = self.wire(ballot), command
        return (tag, f1, f2, f3, *ballots, *commands)

    # -- the harness's rules ------------------------------------------------
    def up(self, node: int) -> bool:
        return self.started[node] and not self.isolated[node] and not self.stopped[node]

    def deliverable(self, src: int, dst: int, is_timer: bool) -> bool:
        if not self.up(dst):
            return False
        return is_timer or src >= self.n or not self.isolated[src]

    def send(self, src: int, dst: int, msg: tuple, is_timer: bool = False) -> None:
        if src < self.n and not is_timer and (
            self.stopped[dst] or frozenset((src, dst)) in self.cut
        ):
            return  # lost at the send
        self.pending.append((src, dst, msg, is_timer))

    def drop(self, lost) -> None:
        self.pending = [p for p in self.pending if not lost(p)]

    def spawn(self, node: int) -> None:
        if node in self.replicas:
            self.actors[node] = Replica()
        elif node in self.acceptors:
            self.actors[node] = Acceptor()
        else:
            me = Leader(ballot=(0, self.leaders.index(node)))
            self.actors[node] = me
            self.start_scout(node, me)

    # -- replica ------------------------------------------------------------
    def propose(self, node: int, r: Replica) -> None:
        while r.slot_in < r.slot_out + WINDOW and r.requests:
            if r.slot_in > self.cap:
                break  # past the log: further requests wait for ever
            if r.slot_in not in r.decisions:
                command = min(r.requests)
                r.requests.remove(command)
                r.proposals[r.slot_in] = command
                for leader in self.leaders:
                    self.send(node, leader, self.message(PROPOSE, r.slot_in, command))
            r.slot_in += 1

    def on_request(self, node: int, r: Replica, command: int) -> None:
        if 1 <= command <= self.cap:
            r.requests.add(command)
        self.propose(node, r)

    def on_decision(self, node: int, r: Replica, slot: int, command: int) -> None:
        if 1 <= slot <= self.cap and command:
            if slot in r.decisions:
                if r.decisions[slot] != command:
                    r.handed_both = True
            else:
                r.decisions[slot] = command
            while r.slot_out in r.decisions:
                decided = r.decisions[r.slot_out]
                ours = r.proposals.pop(r.slot_out, None)
                if ours is not None and ours != decided:
                    r.requests.add(ours)
                if not any(
                    r.decisions.get(s) == decided for s in range(1, r.slot_out)
                ):
                    r.performed += 1
                r.slot_out += 1
        self.propose(node, r)

    # -- acceptor -------------------------------------------------------------
    def on_p1a(self, node: int, a: Acceptor, sender: int, b: Ballot) -> None:
        if a.ballot is None or b > a.ballot:
            a.ballot = b
        if sender in self.leaders:
            self.send(node, sender, self.message(
                P1B, self.wire(a.ballot), self.wire(b), table=a.accepted
            ))

    def on_p2a(self, node, a: Acceptor, sender, b: Ballot, slot, command) -> None:
        if b == a.ballot and 1 <= slot <= self.cap:
            a.accepted[slot] = (b, command)
        if sender in self.leaders:
            self.send(node, sender, self.message(
                P2B, self.wire(a.ballot), slot, self.wire(b)
            ))

    # -- leader, scout, commanders ----------------------------------------------
    def start_scout(self, node: int, me: Leader) -> None:
        me.scouting = True
        me.scout_answered, me.scout_answers, me.pvalues = set(), 0, {}
        for acceptor in self.acceptors:
            self.send(node, acceptor, self.message(P1A, self.wire(me.ballot)))

    def start_commander(self, node: int, me: Leader, slot: int) -> None:
        me.commanders[slot] = (me.ballot, set())
        me.commander_answers[slot] = 0
        for acceptor in self.acceptors:
            self.send(node, acceptor, self.message(
                P2A, self.wire(me.ballot), slot, me.proposals[slot]
            ))

    def enough(self, answered: Set[int], answers: int) -> bool:
        if self.bug == "count_replies":
            return answers >= self.majority  # BUG: a reply heard twice counts twice
        return len(answered) >= self.majority

    def preempted(self, node: int, me: Leader, b: Optional[Ballot]) -> None:
        if b is not None and b > me.ballot:
            me.active = me.scouting = False
            me.ballot = (b[0] + 1, self.leaders.index(node))
            me.preempts += 1
            self.send(node, node, self.message(BACKOFF, self.wire(me.ballot)), True)

    def on_propose(self, node: int, me: Leader, slot: int, command: int) -> None:
        if 1 <= slot <= self.cap and command and slot not in me.proposals:
            me.proposals[slot] = command
            if me.active:
                self.start_commander(node, me, slot)

    def on_p1b(self, node, me: Leader, sender, b, asked, msg) -> None:
        if not me.scouting or sender not in self.acceptors or asked != me.ballot:
            return  # to a scout that is no more
        if b != me.ballot:
            self.preempted(node, me, b)
            return
        ballots = msg[4 : 4 + self.cap]
        commands = msg[4 + self.cap : 4 + 2 * self.cap]
        for slot, (wired, command) in enumerate(zip(ballots, commands), 1):
            ballot = self.unwire(wired)
            if ballot is not None and (
                slot not in me.pvalues or ballot > me.pvalues[slot][0]
            ):
                me.pvalues[slot] = (ballot, command)
        me.scout_answered.add(sender)
        me.scout_answers += 1
        if self.enough(me.scout_answered, me.scout_answers):
            for slot, (_ballot, command) in me.pvalues.items():
                me.proposals[slot] = command
            me.commanders, me.commander_answers = {}, {}
            for slot in sorted(me.proposals):
                self.start_commander(node, me, slot)
            me.active, me.scouting = True, False
            me.adoptions += 1

    def on_p2b(self, node, me: Leader, sender, b, slot, asked) -> None:
        if sender not in self.acceptors or slot not in me.commanders:
            return
        ballot, answered = me.commanders[slot]
        if asked != ballot:
            return  # to a commander that is no more
        if b != ballot:
            if b is not None and b > ballot:
                del me.commanders[slot], me.commander_answers[slot]
            self.preempted(node, me, b)
            return
        answered.add(sender)
        me.commander_answers[slot] += 1
        if self.enough(answered, me.commander_answers[slot]):
            del me.commanders[slot], me.commander_answers[slot]
            for replica in self.replicas:
                self.send(node, replica, self.message(
                    DECISION, slot, me.proposals[slot]
                ))

    def on_backoff(self, node: int, me: Leader) -> None:
        if not me.active and not me.scouting:
            self.start_scout(node, me)

    # -- one delivery -----------------------------------------------------------
    def receive(self, node: int, sender: int, msg: tuple) -> None:
        tag, f1, f2, f3 = msg[:4]
        actor = self.actors[node]
        if isinstance(actor, Replica):
            if tag == REQUEST:
                self.on_request(node, actor, f1)
            elif tag == DECISION:
                self.on_decision(node, actor, f1, f2)
        elif isinstance(actor, Acceptor):
            if tag == P1A:
                self.on_p1a(node, actor, sender, self.unwire(f1))
            elif tag == P2A:
                self.on_p2a(node, actor, sender, self.unwire(f1), f2, f3)
        elif tag == PROPOSE:
            self.on_propose(node, actor, f1, f2)
        elif tag == P1B:
            self.on_p1b(node, actor, sender, self.unwire(f1), self.unwire(f2), msg)
        elif tag == P2B:
            self.on_p2b(node, actor, sender, self.unwire(f1), f2, self.unwire(f3))
        elif tag == BACKOFF:
            self.on_backoff(node, actor)

    def verdict(self) -> int:
        held: Dict[int, set] = {}
        for node in self.replicas:
            if not self.up(node):
                continue
            r = self.actors[node]
            if r.handed_both:
                return 1
            for slot, command in r.decisions.items():
                held.setdefault(slot, set()).add(command)
        return int(any(len(commands) > 1 for commands in held.values()))

    def digest(self, node: int) -> tuple:
        """An actor's state as plain values, by role (ballots as wired)."""
        actor = self.actors[node]
        if actor is None:
            return ("none",)
        if isinstance(actor, Replica):
            return (
                "replica", actor.slot_in, actor.slot_out,
                tuple(sorted(actor.requests)),
                tuple(sorted(actor.proposals.items())),
                tuple(sorted(actor.decisions.items())),
                actor.performed, int(actor.handed_both),
            )
        if isinstance(actor, Acceptor):
            return (
                "acceptor", self.wire(actor.ballot),
                tuple(sorted(
                    (slot, self.wire(b), c)
                    for slot, (b, c) in actor.accepted.items()
                )),
            )
        counting = self.bug == "count_replies"
        return (
            "leader", self.wire(actor.ballot), int(actor.active),
            int(actor.scouting),
            actor.scout_answers if counting else tuple(
                sorted(a - self.acceptors[0] for a in actor.scout_answered)
            ),
            tuple(sorted(
                (slot, self.wire(b), c) for slot, (b, c) in actor.pvalues.items()
            )),
            tuple(sorted(actor.proposals.items())),
            tuple(sorted(
                (slot, self.wire(ballot),
                 actor.commander_answers[slot] if counting else tuple(
                    sorted(a - self.acceptors[0] for a in answered)
                ))
                for slot, (ballot, answered) in actor.commanders.items()
            )),
            actor.adoptions, actor.preempts,
        )


def replay(
    num_actors: int,
    log_cap: int,
    records: Sequence[Sequence[int]],
    length: int,
    bug: Optional[str] = None,
    max_dups: Optional[int] = None,
    max_drops: Optional[int] = None,
) -> Outcome:
    net = _Cluster(num_actors, log_cap, bug)
    n, width = num_actors, 4 + 2 * log_cap
    deliveries = kept = discarded = peak = code = 0
    verdict_at = None
    for i in range(int(length)):
        kind, a, b = (int(x) for x in records[i][:3])
        if kind == 0:
            continue
        if verdict_at is not None:
            raise Diverged(
                f"record {i}: the lane went on after delivery {verdict_at} "
                "broke the invariant"
            )
        msg = tuple(int(x) for x in records[i][3 : 3 + width])
        if kind in (REC_DELIVERY, REC_TIMER, REC_KEPT, REC_DISCARDED):
            is_timer = kind == REC_TIMER
            entry = (a, b, msg, is_timer)
            if entry not in net.pending:
                raise Diverged(
                    f"record {i}: {msg[:4]} from {a} to {b} is not pending: "
                    "this protocol never sent it, or a message that was "
                    "consumed is delivered again with no kept delivery "
                    "before it"
                )
            if not net.deliverable(a, b, is_timer):
                raise Diverged(f"record {i}: {(a, b, msg[:4])} is not deliverable")
            if kind in (REC_KEPT, REC_DISCARDED) and a >= n:
                raise Diverged(
                    f"record {i}: a client's send is delivered exactly once"
                )
            if kind == REC_KEPT:
                kept += 1
            else:
                net.pending.remove(entry)
            if kind == REC_DISCARDED:
                discarded += 1
            else:
                deliveries += 1
                net.receive(b, a, msg)
                code = net.verdict()
                if code:
                    verdict_at = deliveries
            if max_dups is not None and kept > max_dups:
                raise Diverged(f"record {i}: more than {max_dups} kept deliveries")
            if max_drops is not None and discarded > max_drops:
                raise Diverged(f"record {i}: more than {max_drops} discards")
        elif kind >= REC_EXT_BASE:
            op = kind - REC_EXT_BASE
            if op == OP_START:
                fresh = not net.started[a] or net.stopped[a]
                net.started[a], net.isolated[a], net.stopped[a] = True, False, False
                if fresh:
                    net.spawn(a)
            elif op == OP_KILL:
                net.isolated[a] = True
            elif op == OP_HARDKILL:
                net.stopped[a] = True
                net.drop(lambda p: a in (p[0], p[1]))
            elif op == OP_SEND:
                net.send(n, a, msg)
            elif op == OP_PARTITION:
                link = frozenset((a, b))
                net.cut.add(link)
                net.drop(lambda p: not p[3] and frozenset(p[:2]) == link)
            elif op == OP_UNPARTITION:
                net.cut.discard(frozenset((a, b)))
            elif op not in (OP_WAIT, OP_WAITCOND):
                raise Diverged(f"record {i}: unknown external op {op}")
        else:
            raise Diverged(f"record {i}: a {kind} record is none of this network's")
        peak = max(peak, len(net.pending))
    leaders = [net.actors[i] for i in net.leaders if net.actors[i] is not None]
    return Outcome(
        digests=[net.digest(i) for i in range(n)],
        alive=[net.up(i) for i in range(n)],
        code=code or net.verdict(),
        step=verdict_at if verdict_at is not None else deliveries,
        deliveries=deliveries,
        kept=kept,
        discarded=discarded,
        peak_pending=peak,
        committed=max(
            [net.actors[i].slot_out - 1 for i in net.replicas
             if net.actors[i] is not None] or [0]
        ),
        adoptions=sum(me.adoptions for me in leaders),
        preempts=sum(me.preempts for me in leaders),
    )
