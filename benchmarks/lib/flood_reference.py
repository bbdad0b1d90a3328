"""The plain reference of the ``bcast64-flood`` deployment: eager reliable
broadcast (Cachin, Guerraoui, Rodrigues, *Introduction to Reliable and
Secure Distributed Programming*, 2nd ed., Algorithm 3.3) over a network
with crash-stop, crash-recovery and isolation, in sets and lists. No JAX,
nothing of the program: it reads a lane's recorded trace in the record
encoding ``demi_tpu/device/core.py`` documents (int rows ``(kind, a, b,
msg...)``: kind 1 a delivery from ``a`` to ``b``, kind ``10 + op`` an
external event) and replays it by the protocol's own rules:

- on the first delivery of broadcast ``id`` at a node, the node adds it to
  its delivered set and relays it to every other node;
- a message to a stopped (hard-killed) node is lost at the send; what is
  pending to or from a node when it is hard-killed is lost; a restarted
  node starts with an empty delivered set;
- an isolated (soft-killed) node receives nothing and nothing it sent is
  delivered until it is started again; its mail is held, not lost;
- a cut link loses what is pending on it and what is sent over it;
- agreement, judged at quiescence only: all alive nodes (started, neither
  isolated nor stopped) have delivered the same set.

``replay`` raises ``Diverged`` where the trace delivers a message the
reference does not hold or could not deliver.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence, Set

REC_DELIVERY = 1
REC_EXT_BASE = 10
OP_START, OP_KILL, OP_SEND, OP_WAIT = 1, 2, 3, 4
OP_PARTITION, OP_UNPARTITION, OP_HARDKILL, OP_WAITCOND = 5, 6, 7, 8
TAG_BCAST = 1


class Diverged(Exception):
    """The trace did something the protocol's rules do not allow."""


@dataclass
class Outcome:
    delivered: List[Set[int]]   # per node: the broadcast ids it delivered
    alive: List[bool]
    code: int                   # 0 agreement, 1 two alive nodes differ
    quiescent: bool             # nothing was deliverable at the trace's end
    deliveries: int
    peak_pending: int


class _Net:
    def __init__(self, n: int):
        self.n = n
        self.started = [False] * n
        self.isolated = [False] * n
        self.stopped = [False] * n
        self.cut: Set[frozenset] = set()
        self.delivered: List[Set[int]] = [set() for _ in range(n)]
        # (src, dst, id) -> how many are pending; src n = a client
        self.pending: Dict[tuple, int] = {}
        self.held = 0

    def deliverable(self, src: int, dst: int) -> bool:
        if not self.started[dst] or self.stopped[dst] or self.isolated[dst]:
            return False
        return src >= self.n or not self.isolated[src]

    def send(self, src: int, dst: int, bid: int) -> None:
        if src < self.n and (
            self.stopped[dst] or frozenset((src, dst)) in self.cut
        ):
            return  # lost at the send
        self.pending[(src, dst, bid)] = self.pending.get((src, dst, bid), 0) + 1
        self.held += 1

    def drop(self, lost) -> None:
        for entry in [e for e in self.pending if lost(e)]:
            self.held -= self.pending.pop(entry)


def replay(num_nodes: int, records: Sequence[Sequence[int]], length: int) -> Outcome:
    net = _Net(num_nodes)
    n = num_nodes
    deliveries = peak = 0
    for i in range(int(length)):
        kind, a, b = (int(x) for x in records[i][:3])
        if kind == REC_DELIVERY:
            bid = int(records[i][4])
            entry = (a, b, bid)
            if entry not in net.pending:
                raise Diverged(f"record {i}: {entry} is not pending")
            if not net.deliverable(a, b):
                raise Diverged(f"record {i}: {entry} is not deliverable")
            net.pending[entry] -= 1
            if not net.pending[entry]:
                del net.pending[entry]
            net.held -= 1
            deliveries += 1
            if int(records[i][3]) == TAG_BCAST and bid not in net.delivered[b]:
                net.delivered[b].add(bid)
                for peer in range(n):
                    if peer != b:
                        net.send(b, peer, bid)
        elif kind >= REC_EXT_BASE:
            op = kind - REC_EXT_BASE
            if op == OP_START:
                if not net.started[a] or net.stopped[a]:
                    net.delivered[a] = set()   # a fresh node, or a recovery
                net.started[a], net.isolated[a], net.stopped[a] = True, False, False
            elif op == OP_KILL:
                net.isolated[a] = True
            elif op == OP_HARDKILL:
                net.stopped[a] = True
                net.drop(lambda p: a in p[:2])
            elif op == OP_SEND:
                net.send(n, a, int(records[i][4]))
            elif op == OP_PARTITION:
                link = frozenset((a, b))
                net.cut.add(link)
                net.drop(lambda p: frozenset(p[:2]) == link)
            elif op == OP_UNPARTITION:
                net.cut.discard(frozenset((a, b)))
            elif op not in (OP_WAIT, OP_WAITCOND):
                raise Diverged(f"record {i}: unknown external op {op}")
        elif kind != 0:
            raise Diverged(f"record {i}: a {kind} record is no broadcast's")
        peak = max(peak, net.held)
    alive = [
        net.started[i] and not net.isolated[i] and not net.stopped[i]
        for i in range(n)
    ]
    sets = [net.delivered[i] for i in range(n) if alive[i]]
    return Outcome(
        delivered=net.delivered,
        alive=alive,
        code=int(any(s != sets[0] for s in sets)),
        quiescent=not any(net.deliverable(s, d) for s, d, _ in net.pending),
        deliveries=deliveries,
        peak_pending=peak,
    )
