"""The plain reference of the ``chain7-fifo`` deployment: t servers of chain
replication (van Renesse & Schneider, OSDI 2004, sec. 3) with its failure
repairs, over per-pair FIFO queues, as Python objects with ``hist`` and
``sent`` lists as the paper writes them. No JAX, no masks, nothing of the
program: it reads a lane's recorded trace in the record encoding
``demi_tpu/device/core.py`` documents (int rows ``(kind, a, b, msg...)``:
kind 1 a delivery from ``a`` to ``b``, kind ``10 + op`` an external event;
a message is ``(tag, a, b)``; the client and the master are sender t) and
replays it by the protocol's own rules.

- the network: one queue for each (sender, receiver) pair, the client and
  the master together being one sender. A send joins its queue's end; **a
  delivery must be its queue's head** (``Diverged`` if it is not: the
  guarantee the configuration states). A message to a stopped
  (hard-killed) server is lost at the send, but the client's and the
  master's, which wait; what is queued to or from a server when it is
  hard-killed is lost. (Isolation and link cuts, which the deployment's
  mix never draws, follow the harness's rules: an isolated server receives
  nothing and nothing it sent is delivered; a cut link loses what is queued
  on it and what is sent over it.)
- a server: ``hist`` (the updates it holds, in order; an update's sequence
  number is its place), ``sent`` (the suffix of ``hist`` not yet
  acknowledged), its place in the chain (head? tail? predecessor,
  successor), whether it is OUT, CATCHING_UP (a new tail being brought up
  to date: ``target`` entries make it a member) or a MEMBER, whether its
  predecessor's link is set up, the master's epoch of what last set its
  successor, and whether it owes its successor a CATCHUP. A first spawn is
  a MEMBER at its place in 0 -> 1 -> ... -> t - 1. A later spawn has
  nothing but its two ghost counts; the first message it handles finds it
  a respawn, and it is OUT before it looks at the message.
- UPDATE(value), from the client: a MEMBER head with room that does not
  hold the value appends it; a tail too counts it acknowledged, else
  FWD(place, value) goes to the successor.
- FWD(n, value), from the predecessor over a link that is set up: one it
  holds is dropped; else it is written at place n (over a FIFO link n is
  always the next place). A CATCHING_UP server with ``target`` entries is
  a MEMBER. A MEMBER tail counts all acknowledged and sends ACK(len hist)
  to the predecessor; a server that is no tail forwards.
- ACK(n), from the successor: entries up to n leave ``sent``; not the head:
  ACK(n) to the predecessor.
- BECOME_HEAD(e), BECOME_TAIL(e), NEWPRED(p, e), JOIN(p, e), from the
  master; RECONNECT(n, e), from a new successor; CATCHUP(m), from the
  predecessor: as ``demi_tpu/apps/chain.py``'s module doc tables them (the
  configuration file gives the paper's section for each). A RECONNECT is
  answered with one burst on one queue: CATCHUP, then the FWDs of every
  entry after n. ``bug="no_resend"``: a server whose successor after a
  middle failure (n > 0) lacks max(1, 3L/16) entries or more sends the
  CATCHUP alone.
- the invariant, judged after every delivery and once more where the
  trace ends, over servers that are up, have handled a message in this
  life and are MEMBERs: two hold different values at a place both have
  (code 1: Update Propagation); one holds fewer entries than another
  counts acknowledged (code 2).

``replay`` raises ``Diverged`` where the trace delivers a message that is
not the head of its queue or could not be delivered, or goes on after the
verdict.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

UPDATE, FWD, ACK, BECOME_HEAD, BECOME_TAIL = 1, 2, 3, 4, 5
NEWPRED, RECONNECT, CATCHUP, JOIN = 6, 7, 8, 9
OUT, CATCHING_UP, MEMBER = 0, 1, 2

REC_DELIVERY, REC_EXT_BASE = 1, 10
OP_START, OP_KILL, OP_SEND, OP_WAIT = 1, 2, 3, 4
OP_PARTITION, OP_UNPARTITION, OP_HARDKILL, OP_WAITCOND = 5, 6, 7, 8
WIDTH = 3


class Diverged(Exception):
    """The trace did something the network or the protocol does not allow."""


@dataclass
class Server:
    status: int = MEMBER
    is_head: bool = False
    is_tail: bool = False
    pred: Optional[int] = None
    succ: Optional[int] = None
    hist: List[int] = field(default_factory=list)
    sent: List[int] = field(default_factory=list)
    target: int = 0
    awake: bool = False
    linked: bool = True
    succ_epoch: int = 0
    owes: bool = False

    @property
    def acked(self) -> int:
        return len(self.hist) - len(self.sent)


@dataclass
class Outcome:
    statuses: List[int]
    hists: List[List[int]]
    acked: List[int]
    spawns: List[int]
    alive: List[bool]
    code: int
    step: int            # deliveries when the verdict fell
    deliveries: int
    peak_pending: int
    resent: int          # FWD rows sent in RECONNECT bursts
    reconfigs: int       # configuration messages applied


class _Chain:
    def __init__(self, t: int, log_cap: int, bug: Optional[str]):
        self.t, self.cap, self.bug = t, log_cap, bug
        self.window = max(1, 3 * log_cap // 16)
        self.started = [False] * t
        self.isolated = [False] * t
        self.stopped = [False] * t
        self.cut = set()
        self.servers = [Server() for _ in range(t)]
        self.spawns = [0] * t
        self.resent = [0] * t
        self.reconfigs = [0] * t
        self.queues: Dict[tuple, List[tuple]] = {}
        self.held = 0

    # -- the network -------------------------------------------------------
    def up(self, node: int) -> bool:
        return (
            self.started[node] and not self.isolated[node]
            and not self.stopped[node]
        )

    def deliverable(self, src: int, dst: int) -> bool:
        return self.up(dst) and (src >= self.t or not self.isolated[src])

    def send(self, src: int, dst: Optional[int], *msg: int) -> None:
        if dst is None:
            return
        if src < self.t and (
            self.stopped[dst] or frozenset((src, dst)) in self.cut
        ):
            return  # lost at the send
        msg = msg + (0,) * (WIDTH - len(msg))
        self.queues.setdefault((src, dst), []).append(msg)
        self.held += 1

    def drop(self, lost) -> None:
        for pair in [p for p in self.queues if lost(p)]:
            self.held -= len(self.queues.pop(pair))

    def spawn(self, node: int) -> None:
        self.spawns[node] += 1
        t = self.t
        self.servers[node] = Server(
            is_head=node == 0, is_tail=node == t - 1,
            pred=node - 1 if node else None,
            succ=node + 1 if node < t - 1 else None,
        )

    # -- the protocol ------------------------------------------------------
    def tail_acks(self, me: int) -> None:
        s = self.servers[me]
        s.sent = []
        if not s.is_head:
            self.send(me, s.pred, ACK, len(s.hist))

    def caught_up(self, s: Server) -> bool:
        if s.status == CATCHING_UP and len(s.hist) >= s.target:
            s.status = MEMBER
            return True
        return False

    def burst(self, me: int, have: int) -> None:
        """CATCHUP and the entries after ``have``, to the successor."""
        s = self.servers[me]
        have = max(0, min(have, len(s.hist)))
        rows = list(enumerate(s.hist))[have:]
        if self.bug == "no_resend" and have > 0 and len(rows) >= self.window:
            rows = []
        self.send(me, s.succ, CATCHUP, max(len(s.hist), s.target))
        for place, value in rows:
            self.send(me, s.succ, FWD, place + 1, value)
        self.resent[me] += len(rows)

    def receive(self, me: int, sender: int, msg: tuple) -> None:
        s = self.servers[me]
        if not s.awake:
            if self.spawns[me] > 1:
                s = self.servers[me] = Server(status=OUT, linked=False)
            s.awake = True
        tag, a, b = msg
        client = sender >= self.t
        if tag == UPDATE:
            if (
                client and s.status == MEMBER and s.is_head
                and len(s.hist) < self.cap and a not in s.hist
            ):
                s.hist.append(a)
                if s.is_tail:
                    s.sent = []
                else:
                    s.sent.append(a)
                    self.send(me, s.succ, FWD, len(s.hist), a)
        elif tag == FWD:
            if (
                s.status != OUT and sender == s.pred and s.linked
                and a > len(s.hist)
            ):
                acked = s.acked
                place = min(a, self.cap)
                s.hist += [0] * (place - 1 - len(s.hist))   # a gap: no FIFO
                s.hist.append(b)
                s.sent = s.hist[acked:]
                self.caught_up(s)
                if s.is_tail:
                    if s.status == MEMBER:
                        self.tail_acks(me)
                else:
                    self.send(me, s.succ, FWD, a, b)
        elif tag == ACK:
            if s.status != OUT and sender == s.succ:
                upto = max(s.acked, min(a, len(s.hist)))
                s.sent = s.hist[upto:]
                if not s.is_head:
                    self.send(me, s.pred, ACK, a)
        elif tag == BECOME_HEAD:
            if client and s.status != OUT:
                s.is_head, s.pred, s.linked = True, None, True
                self.reconfigs[me] += 1
        elif tag == BECOME_TAIL:
            if client and s.status != OUT and a > s.succ_epoch:
                s.is_tail, s.succ, s.succ_epoch, s.owes = True, None, a, False
                self.reconfigs[me] += 1
                if s.status == MEMBER:
                    self.tail_acks(me)
        elif tag == NEWPRED:
            if client and s.status != OUT:
                s.pred, s.is_head, s.linked = a, False, False
                self.reconfigs[me] += 1
                self.send(me, a, RECONNECT, len(s.hist), b)
        elif tag == RECONNECT:
            if not client and b > s.succ_epoch:
                s.succ, s.is_tail, s.succ_epoch = sender, False, b
                if s.status == MEMBER or s.target > 0:
                    self.burst(me, a)
                else:
                    s.owes = True
        elif tag == CATCHUP:
            if s.status != OUT and sender == s.pred:
                s.linked, s.target = True, max(s.target, a)
                if self.caught_up(s) and s.is_tail:
                    self.tail_acks(me)
                if s.owes:
                    self.burst(me, 0)
                s.owes = False
        elif tag == JOIN:
            if client and s.status == OUT:
                s.status, s.pred = CATCHING_UP, a
                self.reconfigs[me] += 1
                if b > s.succ_epoch:
                    s.is_tail, s.succ, s.succ_epoch, s.owes = True, None, b, False
                self.send(me, a, RECONNECT, 0, b)

    def verdict(self) -> int:
        members = [
            s for i, s in enumerate(self.servers)
            if self.up(i) and s.awake and s.status == MEMBER
        ]
        for x in members:
            for y in members:
                both = min(len(x.hist), len(y.hist))
                if x.hist[:both] != y.hist[:both]:
                    return 1
        if any(len(x.hist) < y.acked for x in members for y in members):
            return 2
        return 0


def replay(
    num_servers: int,
    log_cap: int,
    records: Sequence[Sequence[int]],
    length: int,
    bug: Optional[str] = None,
) -> Outcome:
    net = _Chain(num_servers, log_cap, bug)
    t = num_servers
    deliveries = peak = code = 0
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
        msg = tuple(int(x) for x in records[i][3 : 3 + WIDTH])
        if kind == REC_DELIVERY:
            queue = net.queues.get((a, b))
            if not queue:
                raise Diverged(f"record {i}: nothing is queued from {a} to {b}")
            if queue[0] != msg:
                raise Diverged(
                    f"record {i}: {msg} from {a} to {b} is not the head of "
                    f"its queue ({queue[0]} was sent first)"
                )
            if not net.deliverable(a, b):
                raise Diverged(f"record {i}: {(a, b, msg)} is not deliverable")
            queue.pop(0)
            net.held -= 1
            deliveries += 1
            net.receive(b, a, msg)
            code = net.verdict()
            if code:
                verdict_at = deliveries
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
                net.drop(lambda pair: a in pair)
            elif op == OP_SEND:
                net.send(t, a, *msg)
            elif op == OP_PARTITION:
                link = frozenset((a, b))
                net.cut.add(link)
                net.drop(lambda pair: frozenset(pair) == link)
            elif op == OP_UNPARTITION:
                net.cut.discard(frozenset((a, b)))
            elif op not in (OP_WAIT, OP_WAITCOND):
                raise Diverged(f"record {i}: unknown external op {op}")
        else:
            raise Diverged(f"record {i}: a {kind} record is no chain server's")
        peak = max(peak, net.held)
    return Outcome(
        statuses=[s.status for s in net.servers],
        hists=[list(s.hist) for s in net.servers],
        acked=[s.acked for s in net.servers],
        spawns=list(net.spawns),
        alive=[net.up(i) for i in range(t)],
        code=code or net.verdict(),
        step=verdict_at if verdict_at is not None else deliveries,
        deliveries=deliveries,
        peak_pending=peak,
        resent=sum(net.resent),
        reconfigs=sum(net.reconfigs),
    )
