"""The plain reference of the ``raft7-reconfig`` deployment: Raft as Ongaro's
dissertation has it (*Consensus: Bridging Theory and Practice*, Stanford
2014: fig. 3.1 with its persistent state, fig. 4.1's single-server
membership changes with 4.2.1-4.2.3 and 4.4, fig. 5.3's InstallSnapshot,
6.4's no-op, and the raft-dev fix of 10 July 2015) over a network with
crash-stop, crash-recovery from disk, isolation and link cuts; a class a
server, a list for the log, a dict for the snapshot, sets for
configurations. No JAX, no arrays, nothing of the program: it reads a lane's
recorded trace in the record encoding ``demi_tpu/device/core.py`` documents
(int rows ``(kind, a, b, msg...)``: kind 1 a delivery from ``a`` to ``b``,
kind 2 a timer at ``b``, kind ``10 + op`` an external event) and replays it
by the figures' own rules.

- servers ``0 .. members - 1`` boot with one configuration entry at index 1
  (term 0, committed); the others are spares with no log and no
  configuration, who never stand (4.4).
- a server's configuration is the latest configuration entry in its log,
  committed or not, else its snapshot's, else none (4.1). It is looked up
  when a delivery begins, when the leader appends one, and when a delivery
  ends.
- ELECTION (a timer; always re-armed): a server that is in its
  configuration and no leader forgets that it heard a leader, or, having
  heard none since the last such timer, stands: term + 1, votes for
  itself, REQ_VOTE(term, lastIdx, lastTerm) to the other members.
- REQ_VOTE: a server that has heard a leader since its last ELECTION drops
  it whole (4.2.3). Else a higher term is adopted, and the vote is granted
  if the term is current, the server has not voted for another, and the
  candidate's log is at least as up to date. The configuration is not
  consulted.
- VOTE_REPLY: a candidate that holds votes of a majority of its own
  configuration leads: next = last + 1, match = 0, a NOOP of its term
  appended (6.4), its heartbeat timer armed, everyone sent what it lacks.
- what a server lacks: where next <= the leader's snapshot index, that
  snapshot in one INSTALL_SNAPSHOT(term, lastIdx, lastTerm, lastCfg,
  digest, reg[8]); else APPEND(term, prevIdx, prevTerm, commit, n, up to 4
  entries from next). Built from the leader's state when the delivery ends.
- APPEND (fig. 3.1's receiver): a lower term is refused; prevIdx past the
  log's end, or held with another term, is refused (at the snapshot's index
  the snapshot's term counts; below it nothing is checked and what the
  snapshot covers is skipped); a conflicting entry goes with all after it;
  new ones are appended while the window (``log_cap`` entries above the
  snapshot) has room; commit = min(leaderCommit, last new entry). The reply
  carries the last new index, or on a refusal the follower's last index.
- APPEND_REPLY / SNAPSHOT_REPLY at the leader of that term: match and next
  rise; a refusal puts next at min(next - 1, follower's last + 1), at least
  1. A sender that is still behind is sent what it lacks.
- INSTALL_SNAPSHOT (fig. 5.3): a lower term is refused; one no newer than
  the server's own changes nothing; a log that holds lastIdx with lastTerm
  keeps what follows; else the log goes. The state machine is the
  snapshot's where it was behind it (or the log went).
- CLIENT(key, v): a leader with room appends CMD and replicates; another
  forwards what the client sent it to the leader it knows of, once.
- ADMIN(op, server): a leader with no change pending, for which the change
  is one, takes it. AddServer catches the server up first (4.2.1): a round
  ends when its match reaches the leader's last index of the round's start;
  it is fast if at most 4 entries came meanwhile, and then the change goes
  on; a slow round, or a heartbeat that finds the round unfinished, counts,
  and past 10 the change is dropped. Then the leader waits until its latest
  configuration entry is committed and (the 2015 fix) an entry of its own
  term is, appends the new configuration and uses it at once.
- every delivery ends so: a leader commits the largest index of its term
  that a majority of its configuration holds (itself only where it is a
  member, 4.2.2); it works its pending change off if the delivery was a
  heartbeat, a reply or the ADMIN; every server applies what is committed
  (CMD writes a register; every entry advances the digest, d' = d * 1000003
  + h(index, kind, value) in 32 bits), a leader that applied a
  configuration it is not in steps down; a server that has applied
  ``snapshot_every`` entries above its snapshot snapshots through what it
  applied.
- on disk (fig. 3.1, 5.1): term, vote, log, snapshot. A restarted server has
  those and nothing else; its first delivery loads the snapshot into the
  state machine.
- the network: as ``vsr_reference``: a message to a stopped node, or over a
  cut link, is lost at the send; what is pending to or from a node when it
  is hard-killed, and on a link when it is cut, is lost; an isolated node
  receives nothing and nothing it sent is delivered.
- the invariant, after every delivery: two live leaders in one term (code
  1); two live servers that applied different histories up to an index both
  applied in this life and among their last 64 (code 2).

Seeded bugs: ``reconfig_before_noop`` (fig. 4.1 as printed: no wait for an
entry of the leader's own term); ``snapshot_keeps_config`` (the
configuration below the log is a variable in memory that an installed
snapshot does not set and a restart resets to the boot configuration).

``replay`` raises ``Diverged`` where the trace delivers a message the
reference does not hold or could not deliver, or goes on after the verdict.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set

REC_DELIVERY, REC_TIMER = 1, 2
REC_EXT_BASE = 10
OP_START, OP_KILL, OP_SEND, OP_WAIT = 1, 2, 3, 4
OP_PARTITION, OP_UNPARTITION, OP_HARDKILL, OP_WAITCOND = 5, 6, 7, 8

(ELECTION, HEARTBEAT, REQ_VOTE, VOTE_REPLY, APPEND, APPEND_REPLY,
 INSTALL_SNAPSHOT, SNAPSHOT_REPLY, CLIENT, ADMIN) = range(1, 11)
TIMERS = (ELECTION, HEARTBEAT)
WIDTH = 18
BATCH = 4
KEYS = 8
RING = 64
ROUNDS = 10
NOOP, CMD, CFG = 0, 1, 2
FOLLOWER, CANDIDATE, LEADER = 0, 1, 2
ADD, REMOVE = 1, 2


class Diverged(Exception):
    """The trace did something the protocol's rules do not allow."""


def wrap(x: int) -> int:
    x &= 0xFFFFFFFF
    return x - (1 << 32) if x >= 1 << 31 else x


def mix(digest: int, index: int, kind: int, value: int) -> int:
    return wrap(
        digest * 1000003 + (index * 8191 + kind) * 131071 + value * 31 + 7
    )


def mask_of(servers) -> int:
    return sum(1 << i for i in servers)


def set_of(mask: int) -> Set[int]:
    return {i for i in range(31) if mask >> i & 1}


@dataclass
class Server:
    # on disk
    term: int = 0
    voted_for: Optional[int] = None
    log: List[tuple] = field(default_factory=list)   # (term, kind, value)
    base: int = 0                                    # the snapshot's index
    snapshot: Dict = field(default_factory=lambda: {
        "term": 0, "config": set(), "digest": 0, "reg": [0] * KEYS,
    })
    # in memory
    role: int = FOLLOWER
    commit: int = 0
    applied: int = 0
    votes: Set[int] = field(default_factory=set)
    next: Dict[int, int] = field(default_factory=dict)
    match: Dict[int, int] = field(default_factory=dict)
    hint: Optional[int] = None
    reg: List[int] = field(default_factory=lambda: [0] * KEYS)
    digest: int = 0
    heard: bool = False
    pending: Optional[tuple] = None                  # (op, server)
    goal: int = 0
    round: int = 0
    config: Set[int] = field(default_factory=set)    # looked up, see above
    config_at: int = 0
    memory_config: Set[int] = field(default_factory=set)
    history: Dict[int, int] = field(default_factory=dict)  # index -> digest
    applied_from: int = 0

    @property
    def last(self) -> int:
        return self.base + len(self.log)

    def entry(self, index: int) -> tuple:
        return self.log[index - self.base - 1]

    def term_at(self, index: int) -> int:
        if self.base < index <= self.last:
            return self.entry(index)[0]
        return self.snapshot["term"] if index == self.base else -1


@dataclass
class Outcome:
    servers: List[Server]
    spawns: List[int]
    alive: List[bool]
    code: int
    step: int            # deliveries when the verdict fell
    deliveries: int
    peak_pending: int
    counts: Dict[str, int]


class _Cluster:
    def __init__(self, n, log_cap, snapshot_every, bug, members=None):
        self.n, self.cap, self.every, self.bug = n, log_cap, snapshot_every, bug
        self.members = n - 2 if members is None else members
        self.started = [False] * n
        self.isolated = [False] * n
        self.stopped = [False] * n
        self.cut: Set[frozenset] = set()
        self.servers = [self.fresh(i) for i in range(n)]
        self.spawns = [0] * n
        self.pending: Dict[tuple, int] = {}
        self.held = 0
        self.now = -1
        self.counts = {
            "reconfigs": [0] * n, "compactions": 0, "snap_sent": 0,
            "snap_installed": 0,
        }

    def fresh(self, i: int) -> Server:
        server = Server()
        if i < self.members:
            boot = set(range(self.members))
            server.log.append((0, CFG, mask_of(boot)))
            server.commit = 1
            server.config, server.config_at = set(boot), 1
            server.memory_config = set(boot)
        return server

    # -- the network -------------------------------------------------------
    def up(self, node: int) -> bool:
        return (
            self.started[node] and not self.isolated[node]
            and not self.stopped[node]
        )

    def deliverable(self, src: int, dst: int) -> bool:
        if not self.up(dst):
            return False
        return src >= self.n or src == dst or not self.isolated[src]

    def send(self, src: int, dst: int, *fields) -> None:
        msg = tuple(int(x) for x in fields)
        msg += (0,) * (WIDTH - len(msg))
        timer = src == dst and msg[0] in TIMERS
        if src < self.n and not timer and (
            self.stopped[dst] or frozenset((src, dst)) in self.cut
        ):
            return  # lost at the send
        entry = (src, dst, msg, self.now)
        self.pending[entry] = self.pending.get(entry, 0) + 1
        self.held += 1

    def drop(self, lost) -> None:
        for entry in [e for e in self.pending if lost(e)]:
            self.held -= self.pending.pop(entry)

    def spawn(self, node: int) -> None:
        """A first start, or a restart from disk."""
        old, new = self.servers[node], self.fresh(node)
        if self.spawns[node]:
            new.term, new.voted_for = old.term, old.voted_for
            new.log, new.base, new.snapshot = old.log, old.base, old.snapshot
        self.spawns[node] += 1
        self.servers[node] = new
        self.send(node, node, ELECTION)

    # -- configurations ----------------------------------------------------
    def below_log(self, r: Server) -> Set[int]:
        if self.bug == "snapshot_keeps_config":
            return set(r.memory_config)   # BUG: not the snapshot's
        return set(r.snapshot["config"])

    def latest_config(self, r: Server, upto: Optional[int] = None):
        for index in range(r.last, r.base, -1):
            if upto is not None and index > upto:
                continue
            _, kind, value = r.entry(index)
            if kind == CFG:
                return set_of(value), index
        return self.below_log(r), r.base

    def look_up(self, r: Server) -> None:
        r.config, r.config_at = self.latest_config(r)

    @staticmethod
    def majority(config: Set[int]) -> int:
        return len(config) // 2 + 1

    def targets(self, me: int, r: Server) -> Set[int]:
        learner = (
            {r.pending[1]} if r.pending and r.pending[0] == ADD else set()
        )
        return (r.config | learner) - {me}

    # -- the protocol ------------------------------------------------------
    def newer_term(self, r: Server, term: int) -> None:
        if term > r.term:
            r.term, r.voted_for, r.role = term, None, FOLLOWER
            r.votes, r.hint, r.pending, r.round = set(), None, None, 0

    def lead(self, me: int, r: Server) -> None:
        r.role, r.hint = LEADER, me
        r.next = {i: r.last + 1 for i in range(self.n)}
        r.match = {i: 0 for i in range(self.n)}
        r.pending, r.round = None, 0
        if len(r.log) < self.cap:
            r.log.append((r.term, NOOP, 0))
        self.send(me, me, HEARTBEAT)

    def from_leader(self, r: Server, sender: int, term: int) -> bool:
        if term != r.term:
            return False
        if r.role == CANDIDATE:
            r.role = FOLLOWER
        r.hint, r.heard = sender, True
        return True

    def forward(self, me: int, r: Server, sender: int, msg: tuple) -> None:
        if (
            r.role != LEADER and sender >= self.n and r.hint is not None
            and r.hint != me
        ):
            self.send(me, r.hint, msg[0], msg[1], msg[2])

    def replied(self, me, r, sender, term, ok, index):
        self.newer_term(r, term)
        if r.role != LEADER or term != r.term:
            return set(), 0
        if ok:
            r.match[sender] = max(r.match[sender], index)
            r.next[sender] = r.match[sender] + 1
        else:
            r.next[sender] = max(min(r.next[sender] - 1, index + 1), 1)
        return ({sender} if r.next[sender] <= r.last else set()), 1

    def receive(self, me: int, sender: int, msg: tuple) -> None:
        r = self.servers[me]
        if r.applied < r.base:
            # the first delivery after a restart: load the snapshot
            r.reg, r.digest = list(r.snapshot["reg"]), r.snapshot["digest"]
            r.applied = r.applied_from = r.base
            r.commit = max(r.commit, r.base)
        self.look_up(r)
        tag, f1, f2, f3, f4, f5 = msg[:6]
        send: Set[int] = set()
        work = 0
        if tag == ELECTION:
            if me in r.config and r.role != LEADER:
                if r.heard:
                    r.heard = False
                else:
                    r.term += 1
                    r.role, r.voted_for, r.votes = CANDIDATE, me, {me}
                    r.hint = None
                    for dst in sorted(r.config - {me}):
                        self.send(me, dst, REQ_VOTE, r.term, r.last,
                                  r.term_at(r.last))
                    if self.majority(r.config) <= 1:
                        self.lead(me, r)
                        send = self.targets(me, r)
            self.send(me, me, ELECTION)
        elif tag == HEARTBEAT:
            if r.role == LEADER:
                send, work = self.targets(me, r), 2
                self.send(me, me, HEARTBEAT)
        elif tag == REQ_VOTE:
            if r.heard:
                return self.finish(me, r, send, work)   # 4.2.3
            self.newer_term(r, f1)
            up_to_date = (f3, f2) >= (r.term_at(r.last), r.last)
            grant = (
                f1 == r.term and r.voted_for in (None, sender) and up_to_date
            )
            if grant:
                r.voted_for = sender
            self.send(me, sender, VOTE_REPLY, r.term, grant)
        elif tag == VOTE_REPLY:
            self.newer_term(r, f1)
            if r.role == CANDIDATE and f1 == r.term and f2:
                r.votes.add(sender)
                if len(r.votes & r.config) >= self.majority(r.config):
                    self.lead(me, r)
                    send = self.targets(me, r)
        elif tag == APPEND:
            self.newer_term(r, f1)
            current = self.from_leader(r, sender, f1)
            prev, prev_term, leader_commit, count = f2, f3, f4, f5
            ok = current and prev <= r.last and (
                prev < r.base or r.term_at(prev) == prev_term
            )
            if ok:
                for k in range(min(count, BATCH)):
                    entry = tuple(msg[6 + 3 * k : 9 + 3 * k])
                    index = prev + 1 + k
                    if index <= r.base:
                        continue    # the snapshot covers it
                    if index > r.base + self.cap:
                        break       # no room until it has compacted
                    if index <= r.last and r.entry(index)[0] == entry[0]:
                        continue
                    del r.log[index - r.base - 1 :]
                    r.log.append(entry)
                last_new = min(prev + count, r.base + self.cap)
                r.commit = max(r.commit, min(leader_commit, last_new))
            self.send(me, sender, APPEND_REPLY, r.term, ok,
                      last_new if ok else r.last)
        elif tag == APPEND_REPLY:
            send, work = self.replied(me, r, sender, f1, f2, f3)
        elif tag == SNAPSHOT_REPLY:
            send, work = self.replied(me, r, sender, f1, 1, f2)
        elif tag == INSTALL_SNAPSHOT:
            self.newer_term(r, f1)
            current = self.from_leader(r, sender, f1)
            last_idx, last_term, last_cfg, digest = f2, f3, f4, f5
            reg = list(msg[6 : 6 + KEYS])
            if current and last_idx > r.base:
                keep = last_idx <= r.last and r.term_at(last_idx) == last_term
                if keep:
                    r.log = r.log[last_idx - r.base :]    # step 6
                else:
                    r.log = []                            # step 7
                if not keep or last_idx > r.applied:      # step 8
                    r.reg, r.digest = list(reg), digest
                    r.applied = r.applied_from = last_idx
                r.commit = max(r.commit, last_idx)
                r.base = last_idx
                r.snapshot = {
                    "term": last_term, "config": set_of(last_cfg),
                    "digest": digest, "reg": list(reg),
                }
                if self.bug != "snapshot_keeps_config":
                    r.memory_config = set_of(last_cfg)
                self.counts["snap_installed"] += 1
            self.send(me, sender, SNAPSHOT_REPLY, r.term,
                      last_idx if current else 0)
        elif tag == CLIENT:
            if r.role == LEADER and len(r.log) < self.cap:
                r.log.append((r.term, CMD, (f1 % KEYS) * 65536 + f2 % 65536))
                send = self.targets(me, r)
            self.forward(me, r, sender, msg)
        elif tag == ADMIN:
            op, server = f1, f2
            sane = op in (ADD, REMOVE) and 0 <= server < self.n
            if (
                r.role == LEADER and r.pending is None and sane
                and (op == ADD) != (server in r.config)
            ):
                r.pending, r.goal = (op, server), r.last
                r.round = 1 if op == ADD else 0
                work = 1
                if op == ADD:
                    send = {server}
            self.forward(me, r, sender, msg)
        else:
            raise Diverged(f"a message with tag {tag} is no raft server's")
        self.finish(me, r, send, work)

    def finish(self, me: int, r: Server, send: Set[int], work: int) -> None:
        if r.role == LEADER:
            self.advance_commit(me, r)
            if work and r.pending is not None and self.work_off(me, r, work):
                send = self.targets(me, r)
        self.apply(me, r)
        self.compact(r)
        self.look_up(r)
        for dst in sorted(send):
            self.replicate(me, r, dst)

    def advance_commit(self, me: int, r: Server) -> None:
        for index in range(r.last, r.commit, -1):
            if index <= r.base or r.entry(index)[0] != r.term:
                continue
            holders = [
                i for i in r.config
                if (r.last if i == me else r.match[i]) >= index
            ]
            if len(holders) >= self.majority(r.config):
                r.commit = index
                return

    def work_off(self, me: int, r: Server, work: int) -> bool:
        """Fig. 4.1 at the leader; True where it appended a configuration."""
        op, server = r.pending
        if op == ADD and r.round > 0:
            reached = (r.last if server == me else r.match[server]) >= r.goal
            if reached and r.last - r.goal <= BATCH:
                r.round = 0
            else:
                if reached or work == 2:
                    r.round += 1
                if reached:
                    r.goal = r.last
                if r.round > ROUNDS:
                    r.pending, r.round = None, 0
                    return False
        if r.round > 0:
            return False
        waited = r.config_at <= r.commit
        if self.bug != "reconfig_before_noop":
            waited = waited and r.term_at(r.commit) == r.term   # the 2015 fix
        if not waited or len(r.log) >= self.cap:
            return False
        new = r.config | {server} if op == ADD else r.config - {server}
        r.log.append((r.term, CFG, mask_of(new)))
        r.config, r.config_at, r.pending = new, r.last, None
        return True

    def apply(self, me: int, r: Server) -> None:
        lead = r.role == LEADER
        removed = False
        while r.applied < min(r.commit, r.last):
            r.applied += 1
            _, kind, value = r.entry(r.applied)
            if kind == CMD:
                r.reg[value // 65536] = value % 65536
            r.digest = mix(r.digest, r.applied, kind, value)
            r.history[r.applied] = r.digest
            if kind == CFG and lead:
                self.counts["reconfigs"][me] += 1
                removed = removed or me not in set_of(value)
        if removed:     # 4.2.2
            r.role, r.hint, r.pending, r.round = FOLLOWER, None, None, 0

    def compact(self, r: Server) -> None:
        if r.applied - r.base < self.every:
            return
        config, _ = self.latest_config(r, upto=r.applied)
        r.snapshot = {
            "term": r.term_at(r.applied), "config": set(config),
            "digest": r.digest, "reg": list(r.reg),
        }
        r.memory_config = set(config)
        r.log = r.log[r.applied - r.base :]
        r.base = r.applied
        self.counts["compactions"] += 1

    def replicate(self, me: int, r: Server, dst: int) -> None:
        nxt = r.next[dst]
        if nxt <= r.base:
            snap = r.snapshot
            self.counts["snap_sent"] += 1
            self.send(me, dst, INSTALL_SNAPSHOT, r.term, r.base, snap["term"],
                      mask_of(snap["config"]), snap["digest"], *snap["reg"])
            return
        prev = nxt - 1
        entries = r.log[prev - r.base : prev - r.base + BATCH]
        flat = [x for entry in entries for x in entry]
        self.send(me, dst, APPEND, r.term, prev, r.term_at(prev), r.commit,
                  len(entries), *flat)

    def in_ring(self, r: Server, index: int) -> bool:
        return max(r.applied - RING, r.applied_from) < index <= r.applied

    def verdict(self) -> int:
        live = [r for i, r in enumerate(self.servers) if self.up(i)]
        for a in live:
            for b in live:
                if a is not b and a.role == b.role == LEADER and a.term == b.term:
                    return 1
        for a in live:
            for b in live:
                for index, digest in a.history.items():
                    if (
                        self.in_ring(a, index) and self.in_ring(b, index)
                        and b.history.get(index) != digest
                    ):
                        return 2
        return 0


def replay(
    num_nodes: int,
    log_cap: int,
    snapshot_every: int,
    records: Sequence[Sequence[int]],
    length: int,
    bug: Optional[str] = None,
    members: Optional[int] = None,
) -> Outcome:
    net = _Cluster(num_nodes, log_cap, snapshot_every, bug, members)
    n = num_nodes
    deliveries = peak = code = 0
    verdict_at = None
    linked = int(length) > 0 and len(records[0]) >= 3 + WIDTH + 2
    for i in range(int(length)):
        kind, a, b = (int(x) for x in records[i][:3])
        if kind == 0:
            continue
        if linked:
            net.now = i
        if verdict_at is not None:
            raise Diverged(
                f"record {i}: the lane went on after delivery {verdict_at} "
                "broke the invariant"
            )
        msg = tuple(int(x) for x in records[i][3 : 3 + WIDTH])
        if kind in (REC_DELIVERY, REC_TIMER):
            sent_by = int(records[i][3 + WIDTH]) if linked else -1
            entry = (a, b, msg, sent_by)
            if (kind == REC_TIMER) != (a == b and msg[0] in TIMERS):
                raise Diverged(f"record {i}: {entry[:3]} is of the wrong kind")
            if entry not in net.pending:
                raise Diverged(f"record {i}: {entry} is not pending")
            if not net.deliverable(a, b):
                raise Diverged(f"record {i}: {entry} is not deliverable")
            net.pending[entry] -= 1
            if not net.pending[entry]:
                del net.pending[entry]
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
                net.drop(lambda p: a in p[:2])
            elif op == OP_SEND:
                net.send(n, a, *msg)
            elif op == OP_PARTITION:
                link = frozenset((a, b))
                net.cut.add(link)
                net.drop(lambda p: p[0] != p[1] and frozenset(p[:2]) == link)
            elif op == OP_UNPARTITION:
                net.cut.discard(frozenset((a, b)))
            elif op not in (OP_WAIT, OP_WAITCOND):
                raise Diverged(f"record {i}: unknown external op {op}")
        else:
            raise Diverged(f"record {i}: a {kind} record is no raft server's")
        peak = max(peak, net.held)
    counts = dict(net.counts)
    counts["reconfigs"] = max(net.counts["reconfigs"])
    counts["committed"] = max(r.commit for r in net.servers)
    counts["restores"] = sum(max(s - 1, 0) for s in net.spawns)
    return Outcome(
        servers=net.servers,
        spawns=list(net.spawns),
        alive=[net.up(i) for i in range(n)],
        code=code or net.verdict(),
        step=verdict_at if verdict_at is not None else deliveries,
        deliveries=deliveries,
        peak_pending=peak,
        counts=counts,
    )
