"""The plain reference of the ``kafka5-acks-all`` deployment: Apache Kafka's
partition replication as KIP-101 found it (the design document's sec. 4.7;
KIP-101's leader epochs with KIP-279's reply; KIP-497's maximal ISR) over
per-pair FIFO links with crash-recovery from disk, isolation and link cuts;
a class a broker, a class a replica, lists for logs and epoch caches, sets
for in-sync sets. No JAX, no arrays, nothing of the program: it reads a
lane's recorded trace in the record encoding ``demi_tpu/device/core.py``
documents (int rows ``(kind, a, b, msg...)``: kind 1 a delivery from ``a``
to ``b``, kind 2 a timer at ``b``, kind ``10 + op`` an external event) and
replays it by the sources' own rules.

- nodes ``0 .. n - 2`` are brokers, node ``n - 1`` the controller; there
  are n partitions (one more than brokers). Partition p has replicas
  (p, p + 1, p + 2) mod brokers, in that order of preference.
  A broker lists the partitions it replicates in ascending order; entry j of
  a FETCH it sends, and of the reply, is the j-th of that list (p = -1
  where it does not ask).
- the controller boots with no broker live and every partition leaderless
  at epoch 0 with its replicas as ISR. REGISTER from a broker it holds live
  first expires that broker alone (below); then the broker is live and
  heard, leads every partition of its own that has no leader and holds it
  in its ISR (epoch + 1, version + 1), and is sent LEADER_AND_ISR for each
  of its partitions; the other live replicas of a partition whose state
  changed are sent it too. HEARTBEAT from a live broker marks it heard;
  from another it is a REGISTER. T_SESSION expires every live broker not
  heard through 4 of them on end, all at once: each leaves the ISRs that keep
  another member (if a whole ISR goes, its highest broker stays), a
  partition whose leader went takes the first live member of its ISR in
  assignment order, or none; a partition either changed gets epoch + 1,
  version + 1 and LEADER_AND_ISR to its live replicas. ALTER_ISR is a
  compare-and-set on (leader, epoch, version).
- LEADER_AND_ISR at a broker: an epoch at or below its own is dropped. The
  named leader takes the role, the ISR and the version, forgets what it
  knew of the followers, notes (epoch, log end) in its epoch cache and does
  not truncate. Another becomes a follower; with a leader to follow it asks
  it OFFSETS_FOR_EPOCH(p, epoch, e), e the epoch of its last record (an
  empty log fetches at once), and leaves p out of its fetches until the
  answer. ``truncate_to_hw`` (0.10.2): it asks nobody, and unless it
  follows that leader already it cuts its log to its own high watermark.
- OFFSETS_FOR_EPOCH at the leader of that epoch (anyone else answers with an
  error, and the follower asks again at its next T_FETCH): the largest
  epoch of its cache at or below e, and the start of the next one (its log
  end if there is none). ``epoch_unknown_replies_leo``: an e the cache does
  not hold is answered (e, log end). At the follower, still truncating
  under that leader and epoch, with e still its last record's epoch: found
  = e cuts the log to end_offset and fetching starts; else the records of
  an epoch above found go, the log is cut to end_offset, and it asks again
  with its new last record's epoch (an empty log fetches).
- FETCH at a broker, an entry at a time: not the leader, or of another
  epoch: an error entry, counted. Else the follower's offset is noted, it
  has caught up if the offset is the leader's log end, and one that is in
  neither the ISR nor in flight, at or past the high watermark, is proposed
  (ALTER_ISR with ISR + it) if nothing else is in flight. Then the high
  watermark of every partition it leads rises to the least noted offset of
  ISR + in-flight additions (its own is its log end), and each entry is
  answered (p, 0, epoch, hw, offset, k <= 3 records from offset).
- FETCH_RESP at a follower, an entry at a time: taken only from its leader,
  in its epoch, while fetching, with base = its log end; the records are
  appended (the cache notes each new epoch), hw = min(log end, leader's).
- PRODUCE: a leader with 2 in its ISR and room appends (value, epoch), else
  counts a rejection; a follower that knows a leader forwards what the
  operator sent it, once.
- T_ISR: a leader with nothing in flight proposes its ISR without those
  that have not caught up through 8 T_ISRs on end; all are then forgotten
  as caught up. The reply of its epoch: ok installs ISR and version; either
  way nothing is in flight. T_CKPT writes every high watermark to disk.
- on disk: the logs, the epoch caches, the checkpointed high watermarks (and
  the ghost counts). A restarted broker has those and nothing else; its
  high watermarks are the checkpoints, never past the log's end.
- the network: per (sender, receiver) queues, delivered head first; timers
  are chosen freely. A message to a stopped node, or over a cut link, is
  lost at the send; what is queued to or from a node when it is hard-killed,
  and on a link when it is cut, is lost.
- the invariant, after every delivery, over live brokers a, b of one
  partition: a leads in an epoch above the one in which b last exposed a
  higher high watermark than a's log end (code 1); the values differ at an
  offset below both high watermarks (code 2).

``replay`` raises ``Diverged`` where the trace delivers a message that is
not the head of its queue or could not be delivered, or goes on after the
verdict.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set

REC_DELIVERY, REC_TIMER = 1, 2
REC_EXT_BASE = 10
OP_START, OP_KILL, OP_SEND, OP_WAIT = 1, 2, 3, 4
OP_PARTITION, OP_UNPARTITION, OP_HARDKILL, OP_WAITCOND = 5, 6, 7, 8

(T_FETCH, T_ISR, T_CKPT, T_HEARTBEAT, T_SESSION, REGISTER, HEARTBEAT,
 LEADER_AND_ISR, ALTER_ISR, ALTER_ISR_RESP, FETCH, FETCH_RESP,
 OFFSETS_FOR_EPOCH, OFFSETS_FOR_EPOCH_RESP, PRODUCE) = range(1, 16)
TIMERS = (T_FETCH, T_ISR, T_CKPT, T_HEARTBEAT, T_SESSION)
REPLICATION = 3
MIN_INSYNC = 2
RECORDS = 3
CACHE = 8
SESSION_MISSES = 4
LAG_MISSES = 8
NO_ROLE, FOLLOWER, LEADER = 0, 1, 2
COUNTS = (
    "acked", "rejected", "elected", "isr_shrunk", "isr_grown", "truncated",
    "fenced", "epoch_overflow",
)


class Diverged(Exception):
    """The trace did something the protocol's rules do not allow."""


def mask_of(brokers) -> int:
    return sum(1 << b for b in brokers)


def set_of(mask: int) -> Set[int]:
    return {b for b in range(31) if mask >> b & 1}


@dataclass
class Replica:
    """One partition at one broker."""

    # on disk
    log: List[tuple] = field(default_factory=list)        # (value, epoch)
    checkpoint: int = 0
    cache: List[tuple] = field(default_factory=list)      # (epoch, start)
    exposed: int = 0
    exposed_at: int = 0
    counts: Dict[str, int] = field(
        default_factory=lambda: {name: 0 for name in COUNTS}
    )
    # in memory
    role: int = NO_ROLE
    epoch: int = -1
    leader: Optional[int] = None
    hw: int = 0
    truncating: bool = False
    isr: Set[int] = field(default_factory=set)
    version: int = 0
    adding: Set[int] = field(default_factory=set)
    removing: Set[int] = field(default_factory=set)
    fetched: Dict[int, int] = field(default_factory=dict)
    caught: Set[int] = field(default_factory=set)
    lag: Dict[int, int] = field(default_factory=dict)

    @property
    def end(self) -> int:
        return len(self.log)

    @property
    def last_epoch(self) -> int:
        return self.log[-1][1] if self.log else -1


@dataclass
class Controller:
    leader: List[Optional[int]]
    epoch: List[int]
    isr: List[Set[int]]
    version: List[int]
    live: Set[int] = field(default_factory=set)
    heard: Set[int] = field(default_factory=set)
    missed: Dict[int, int] = field(default_factory=dict)


@dataclass
class Outcome:
    brokers: List[Dict[int, Replica]]
    controller: Controller
    spawns: List[int]
    alive: List[bool]
    code: int
    step: int            # deliveries when the verdict fell
    deliveries: int
    peak_pending: int
    counts: Dict[str, int]
    overflow: int        # epochs that found a cache full


class _Cluster:
    def __init__(self, n: int, log_cap: int, bug: Optional[str]):
        self.n, self.cap, self.bug = n, log_cap, bug
        self.brokers_n = n - 1
        self.ctrl = n - 1
        self.parts = partitions = n   # one more than brokers
        b = self.brokers_n
        self.replicas = [
            [(p + r) % b for r in range(REPLICATION)] for p in range(partitions)
        ]
        self.held = [
            [p for p in range(partitions) if i in self.replicas[p]]
            for i in range(b)
        ]
        self.width = 2 + (6 + 2 * RECORDS) * max(len(h) for h in self.held)
        self.entries = max(len(h) for h in self.held)
        self.started = [False] * n
        self.isolated = [False] * n
        self.stopped = [False] * n
        self.cut: Set[frozenset] = set()
        self.brokers: List[Dict[int, Replica]] = [
            {p: Replica() for p in self.held[i]} for i in range(b)
        ]
        self.controller = Controller(
            leader=[None] * partitions, epoch=[0] * partitions,
            isr=[set(reps) for reps in self.replicas], version=[0] * partitions,
        )
        self.spawns = [0] * n
        self.queues: Dict[tuple, List[tuple]] = {}
        self.timers: List[tuple] = []      # (node, msg)
        self.pending = 0

    # -- the network -------------------------------------------------------
    def up(self, node: int) -> bool:
        return (
            self.started[node] and not self.isolated[node]
            and not self.stopped[node]
        )

    def deliverable(self, src: int, dst: int) -> bool:
        return self.up(dst) and (
            src >= self.n or src == dst or not self.isolated[src]
        )

    def pad(self, fields) -> tuple:
        msg = tuple(int(x) for x in fields)
        return msg + (0,) * (self.width - len(msg))

    def send(self, src: int, dst: int, *fields) -> None:
        if src < self.n and (
            self.stopped[dst] or frozenset((src, dst)) in self.cut
        ):
            return  # lost at the send
        self.queues.setdefault((src, dst), []).append(self.pad(fields))
        self.pending += 1

    def arm(self, node: int, tag: int) -> None:
        self.timers.append((node, self.pad((tag,))))
        self.pending += 1

    def drop(self, lost) -> None:
        for pair in [pair for pair in self.queues if lost(pair)]:
            self.pending -= len(self.queues.pop(pair))

    def spawn(self, node: int) -> None:
        """A first start, or a restart from disk."""
        self.spawns[node] += 1
        if node == self.ctrl:
            self.arm(node, T_SESSION)
            return
        for p, old in self.brokers[node].items():
            self.brokers[node][p] = Replica(
                log=old.log, checkpoint=old.checkpoint, cache=old.cache,
                exposed=old.exposed, exposed_at=old.exposed_at,
                counts=old.counts, hw=min(old.checkpoint, len(old.log)),
            )
        self.send(node, self.ctrl, REGISTER, node)
        for tag in (T_FETCH, T_ISR, T_CKPT, T_HEARTBEAT):
            self.arm(node, tag)

    # -- the controller ----------------------------------------------------
    def expire(self, gone: Set[int]) -> Set[int]:
        """The partitions whose state changed."""
        c = self.controller
        c.live -= gone
        changed = set()
        for p in range(self.parts):
            isr = c.isr[p]
            left = isr - gone
            if left != isr:
                new = left or {max(isr)}
                if new != isr:
                    c.isr[p] = new
                    changed.add(p)
            if c.leader[p] in gone:
                able = [
                    b for b in self.replicas[p]
                    if b in c.live and b in c.isr[p]
                ]
                c.leader[p] = able[0] if able else None
                changed.add(p)
            if p in changed:
                c.epoch[p] += 1
                c.version[p] += 1
        return changed

    def state_to(self, p: int, b: int) -> None:
        c = self.controller
        leader = -1 if c.leader[p] is None else c.leader[p]
        self.send(self.ctrl, b, LEADER_AND_ISR, p, leader, c.epoch[p],
                  mask_of(c.isr[p]), c.version[p])

    def register(self, b: int, bounce: bool) -> None:
        c = self.controller
        changed = self.expire({b}) if bounce else set()
        c.live.add(b)
        c.heard.add(b)
        c.missed[b] = 0
        for p in self.held[b]:
            if c.leader[p] is None and b in c.isr[p]:
                c.leader[p] = b
                c.epoch[p] += 1
                c.version[p] += 1
                changed.add(p)
        for p in range(self.parts):
            for r in self.replicas[p]:
                if r in c.live and (p in changed or r == b):
                    self.state_to(p, r)

    def controller_receive(self, src: int, msg: tuple) -> None:
        c = self.controller
        tag = msg[0]
        if tag == REGISTER:
            self.register(src, bounce=src in c.live)
        elif tag == HEARTBEAT:
            if src in c.live:
                c.heard.add(src)
                c.missed[src] = 0
            else:
                self.register(src, bounce=False)
        elif tag == T_SESSION:
            silent = c.live - c.heard
            c.missed = {b: c.missed.get(b, 0) + 1 for b in silent}
            gone = {b for b in silent if c.missed[b] >= SESSION_MISSES}
            for b in gone:
                del c.missed[b]
            changed = self.expire(gone)
            c.heard = set()
            for p in sorted(changed):
                for r in self.replicas[p]:
                    if r in c.live:
                        self.state_to(p, r)
            self.arm(self.ctrl, T_SESSION)
        elif tag == ALTER_ISR:
            p, epoch, isr, version = msg[1:5]
            ok = (
                0 <= p < self.parts and c.leader[p] == src
                and c.epoch[p] == epoch and c.version[p] == version
            )
            if ok:
                c.isr[p] = set_of(isr)
                c.version[p] += 1
            held = 0 <= p < self.parts
            self.send(self.ctrl, src, ALTER_ISR_RESP, p, epoch, int(ok),
                      mask_of(c.isr[p]) if held else 0,
                      c.version[p] if held else 0)
        else:
            raise Diverged(f"the controller was handed a {tag}")

    # -- a broker ----------------------------------------------------------
    def note_epoch(self, r: Replica, epoch: int, start: int) -> None:
        """``leader-epoch-checkpoint``."""
        if r.cache and r.cache[-1][0] >= epoch:
            return
        r.cache = [entry for entry in r.cache if entry[1] < start]
        if len(r.cache) == CACHE:
            r.cache.pop(0)
            r.counts["epoch_overflow"] += 1
        r.cache.append((epoch, start))

    def cut_log(self, r: Replica, end: int) -> None:
        end = min(r.end, end)
        r.counts["truncated"] += r.end - end
        r.log = r.log[:end]
        r.cache = [entry for entry in r.cache if entry[1] < end]
        r.hw = min(r.hw, end)

    def add_record(self, r: Replica, value: int, epoch: int) -> None:
        self.note_epoch(r, epoch, r.end)
        r.log = r.log + [(value, epoch)]

    def advance(self, me: int) -> None:
        """The high watermark of each partition ``me`` leads (design 4.7,
        KIP-497)."""
        for r in self.brokers[me].values():
            if r.role != LEADER:
                continue
            maximal = r.isr | r.adding
            if not maximal:
                continue
            low = min(
                r.end if b == me else r.fetched.get(b, 0) for b in maximal
            )
            hw = max(r.hw, min(low, r.end))
            if len(r.isr) >= MIN_INSYNC:
                r.counts["acked"] += hw - r.hw
            r.hw = hw
            if hw > r.exposed:
                r.exposed, r.exposed_at = hw, r.epoch

    def ask_offsets(self, me: int, p: int, r: Replica) -> None:
        self.send(me, r.leader, OFFSETS_FOR_EPOCH, p, r.epoch, r.last_epoch)

    def broker_receive(self, me: int, src: int, msg: tuple) -> None:
        mine = self.brokers[me]
        tag = msg[0]
        if tag == T_FETCH:
            for leader in range(self.brokers_n):
                entries, asked = [], 0
                for p in self.held[me]:
                    r = mine[p]
                    if (
                        r.role == FOLLOWER and r.leader == leader
                        and not r.truncating
                    ):
                        entries += [p, r.end, r.epoch]
                        asked += 1
                    else:
                        entries += [-1, 0, 0]
                entries += [-1, 0, 0] * (self.entries - len(self.held[me]))
                if asked:
                    self.send(me, leader, FETCH, me, asked, *entries)
            for p in self.held[me]:
                r = mine[p]
                if (
                    r.role == FOLLOWER and r.leader is not None
                    and r.truncating and r.log
                ):
                    self.ask_offsets(me, p, r)
            self.arm(me, T_FETCH)
        elif tag == T_ISR:
            for p in self.held[me]:
                r = mine[p]
                if r.role != LEADER:
                    continue
                r.lag = {
                    b: r.lag.get(b, 0) + 1
                    for b in range(self.brokers_n) if b not in r.caught
                }
                lagging = {
                    b for b in r.isr - {me} if r.lag.get(b, 0) >= LAG_MISSES
                }
                if lagging and not r.adding and not r.removing:
                    r.removing = set(lagging)
                    self.send(me, self.ctrl, ALTER_ISR, p, r.epoch,
                              mask_of(r.isr - lagging), r.version)
                r.caught = set()
            self.arm(me, T_ISR)
        elif tag == T_CKPT:
            for r in mine.values():
                r.checkpoint = r.hw
            self.arm(me, T_CKPT)
        elif tag == T_HEARTBEAT:
            self.send(me, self.ctrl, HEARTBEAT, me)
            self.arm(me, T_HEARTBEAT)
        elif tag == LEADER_AND_ISR:
            self.on_leader_and_isr(me, msg)
        elif tag == ALTER_ISR_RESP:
            p, epoch, ok, isr, version = msg[1:6]
            r = mine.get(p)
            if r is not None and r.role == LEADER and r.epoch == epoch:
                if ok:
                    new = set_of(isr)
                    if new - r.isr:
                        r.counts["isr_grown"] += 1
                    if r.isr - new:
                        r.counts["isr_shrunk"] += 1
                    r.isr, r.version = new, version
                r.adding, r.removing = set(), set()
        elif tag == FETCH:
            self.on_fetch(me, msg)
        elif tag == FETCH_RESP:
            self.on_fetch_resp(me, src, msg)
        elif tag == OFFSETS_FOR_EPOCH:
            self.on_offsets(me, src, msg)
        elif tag == OFFSETS_FOR_EPOCH_RESP:
            self.on_offsets_resp(me, src, msg)
        elif tag == PRODUCE:
            p, value, forwarded = msg[1:4]
            r = mine.get(p)
            if r is None:
                pass
            elif r.role == LEADER:
                if len(r.isr) >= MIN_INSYNC and r.end < self.cap:
                    self.add_record(r, value, r.epoch)
                else:
                    r.counts["rejected"] += 1
            elif (
                r.role == FOLLOWER and r.leader is not None
                and r.leader != me and not forwarded
            ):
                self.send(me, r.leader, PRODUCE, p, value, 1)
        else:
            raise Diverged(f"broker {me} was handed a {tag}")
        self.advance(me)

    def on_leader_and_isr(self, me: int, msg: tuple) -> None:
        p, leader, epoch, isr, version = msg[1:6]
        r = self.brokers[me].get(p)
        if r is None or epoch <= r.epoch:
            return
        followed = r.role == FOLLOWER and r.leader == leader
        r.epoch = epoch
        r.adding, r.removing, r.caught, r.fetched = set(), set(), set(), {}
        r.lag = {}
        if leader == me:
            r.role, r.leader, r.truncating = LEADER, me, False
            r.isr, r.version = set_of(isr), version
            self.note_epoch(r, epoch, r.end)
            r.counts["elected"] += 1
            return
        r.role = FOLLOWER
        r.leader = None if leader < 0 else leader
        if r.leader is None:
            r.truncating = True     # nobody to fetch from
        elif self.bug == "truncate_to_hw":
            if not followed:        # BUG: to its own high watermark
                self.cut_log(r, r.hw)
                r.truncating = False
        elif r.log:
            r.truncating = True
            self.ask_offsets(me, p, r)
        else:
            r.truncating = False

    def on_fetch(self, me: int, msg: tuple) -> None:
        b = msg[1]
        mine = self.brokers[me]
        asked = [
            tuple(msg[3 + 3 * j : 6 + 3 * j]) for j in range(self.entries)
        ]
        served, fenced, proposals = {}, set(), []
        for j, (p, offset, epoch) in enumerate(asked):
            if p < 0:
                continue
            r = mine.get(p)
            if r is None:
                continue
            if r.role != LEADER or r.epoch != epoch:
                fenced.add(p)
                continue
            served[j] = r
            r.fetched[b] = offset
            if offset >= r.end:
                r.caught.add(b)
            if (
                b not in r.isr | r.adding and offset >= r.hw
                and not r.adding and not r.removing
            ):
                r.adding = {b}
                proposals.append((p, r))
        for p in fenced:
            mine[p].counts["fenced"] += 1
        self.advance(me)
        entries, count = [], 0
        for j, (p, offset, epoch) in enumerate(asked):
            if p < 0:
                entries += [-1] + [0] * (5 + 2 * RECORDS)
                continue
            count += 1
            r = served.get(j)
            if r is None:
                entries += [p, 1] + [0] * (4 + 2 * RECORDS)
                continue
            records = r.log[offset : offset + RECORDS]
            flat = [x for record in records for x in record]
            flat += [0] * (2 * RECORDS - len(flat))
            entries += [p, 0, r.epoch, r.hw, offset, len(records)] + flat
        if count:
            self.send(me, b, FETCH_RESP, count, *entries)
        for p, r in sorted(proposals, key=lambda item: item[0]):
            self.send(me, self.ctrl, ALTER_ISR, p, r.epoch,
                      mask_of(r.isr | r.adding), r.version)

    def on_fetch_resp(self, me: int, src: int, msg: tuple) -> None:
        width = 6 + 2 * RECORDS
        for j, p in enumerate(self.held[me]):
            entry = msg[2 + width * j : 2 + width * (j + 1)]
            r = self.brokers[me][p]
            got, err, epoch, hw, base, k = entry[:6]
            if (
                got != p or err or r.role != FOLLOWER or r.leader != src
                or r.epoch != epoch or r.truncating or base != r.end
            ):
                continue
            for i in range(min(k, RECORDS)):
                if r.end < self.cap:
                    self.add_record(r, entry[6 + 2 * i], entry[7 + 2 * i])
            r.hw = min(r.end, hw)

    def on_offsets(self, me: int, src: int, msg: tuple) -> None:
        p, epoch, e = msg[1:4]
        r = self.brokers[me].get(p)
        if r is None or r.role != LEADER or r.epoch != epoch:
            self.send(me, src, OFFSETS_FOR_EPOCH_RESP, p, epoch, 1, e, -1, -1)
            return
        older = [entry[0] for entry in r.cache if entry[0] <= e]
        later = [entry[1] for entry in r.cache if entry[0] > e]
        found = max(older) if older else -1
        end = min(later) if later else r.end
        if (
            self.bug == "epoch_unknown_replies_leo"
            and e not in [entry[0] for entry in r.cache]
        ):
            found, end = e, r.end     # BUG: KIP-279's corner
        self.send(me, src, OFFSETS_FOR_EPOCH_RESP, p, epoch, 0, e, found, end)

    def on_offsets_resp(self, me: int, src: int, msg: tuple) -> None:
        p, epoch, err, e, found, end = msg[1:7]
        r = self.brokers[me].get(p)
        if (
            r is None or r.role != FOLLOWER or not r.truncating
            or r.leader != src or r.epoch != epoch or err
            or r.last_epoch != e
        ):
            return
        if found == e:
            self.cut_log(r, end)
            r.truncating = False
            return
        kept = len([record for record in r.log if record[1] <= found])
        self.cut_log(r, min(kept, end))
        if r.log:
            self.ask_offsets(me, p, r)
        else:
            r.truncating = False

    def receive(self, dst: int, src: int, msg: tuple) -> None:
        if dst == self.ctrl:
            self.controller_receive(src, msg)
        else:
            self.broker_receive(dst, src, msg)

    def verdict(self) -> int:
        live = [b for b in range(self.brokers_n) if self.up(b)]
        for p in range(self.parts):
            here = [self.brokers[b][p] for b in live if p in self.brokers[b]]
            for a in here:
                for b in here:
                    if (
                        a.role == LEADER and a.epoch > b.exposed_at
                        and a.end < b.exposed
                    ):
                        return 1
        for p in range(self.parts):
            here = [self.brokers[b][p] for b in live if p in self.brokers[b]]
            for a in here:
                for b in here:
                    below = min(a.hw, a.end, b.hw, b.end)
                    if any(
                        a.log[o][0] != b.log[o][0] for o in range(below)
                    ):
                        return 2
        return 0


def replay(
    num_nodes: int,
    log_cap: int,
    records: Sequence[Sequence[int]],
    length: int,
    bug: Optional[str] = None,
) -> Outcome:
    n = num_nodes
    net = _Cluster(n, log_cap, bug)
    width = net.width
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
        msg = tuple(int(x) for x in records[i][3 : 3 + width])
        if kind == REC_TIMER:
            if a != b or msg[0] not in TIMERS or (b, msg) not in net.timers:
                raise Diverged(f"record {i}: no timer {msg[0]} is armed at {b}")
            if not net.up(b):
                raise Diverged(f"record {i}: {b} is not up")
            net.timers.remove((b, msg))
        elif kind == REC_DELIVERY:
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
        if kind in (REC_TIMER, REC_DELIVERY):
            net.pending -= 1
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
                gone = [t for t in net.timers if t[0] == a]
                net.timers = [t for t in net.timers if t[0] != a]
                net.pending -= len(gone)
            elif op == OP_SEND:
                net.send(n, a, *msg)
            elif op == OP_PARTITION:
                link = frozenset((a, b))
                net.cut.add(link)
                net.drop(lambda pair: frozenset(pair) == link)
            elif op == OP_UNPARTITION:
                net.cut.discard(frozenset((a, b)))
            elif op not in (OP_WAIT, OP_WAITCOND):
                raise Diverged(f"record {i}: unknown external op {op}")
        else:
            raise Diverged(f"record {i}: a {kind} record is no broker's")
        peak = max(peak, net.pending)
    replicas = [r for held in net.brokers for r in held.values()]
    total = lambda name: sum(r.counts[name] for r in replicas)  # noqa: E731
    return Outcome(
        brokers=net.brokers,
        controller=net.controller,
        spawns=list(net.spawns),
        alive=[net.up(i) for i in range(n)],
        code=code or net.verdict(),
        step=verdict_at if verdict_at is not None else deliveries,
        deliveries=deliveries,
        peak_pending=peak,
        counts={
            "committed": total("acked"),
            "elections": total("elected"),
            "isr_changes": total("isr_shrunk") + total("isr_grown"),
            "truncated": total("truncated"),
            "fenced": total("fenced"),
            "restores": sum(max(s - 1, 0) for s in net.spawns[: n - 1]),
        },
        overflow=total("epoch_overflow"),
    )
