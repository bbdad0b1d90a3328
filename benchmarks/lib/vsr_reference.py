"""The plain reference of the ``vsr5-recovery`` deployment: N replicas of
Viewstamped Replication Revisited (Liskov & Cowling, MIT-CSAIL-TR-2012-021:
normal operation 4.1, view change 4.2, recovery with no disk but a nonce
4.3, state transfer 5.2) over a network with crash-stop, crash-recovery,
isolation and link cuts, in dicts and lists. No JAX, no masks, nothing of
the program: it reads a lane's recorded trace in the record encoding
``demi_tpu/device/core.py`` documents (int rows ``(kind, a, b, msg...)``:
kind 1 a delivery from ``a`` to ``b``, kind 2 a timer at ``b``, kind
``10 + op`` an external event; a message is ``(tag, f1, f2, f3, f4,
log[L])``) and replays it by the protocol's own rules. f = (N - 1) // 2,
the primary of view v is replica v mod N, a log entry is a request's value.

- BOOT (a timer, once a spawn): the spawn count, the one word on disk,
  goes up. The first spawn is NORMAL in view 0. A later one takes the
  count as its nonce, is RECOVERING, and sends RECOVERY(nonce) to all
  others.
- COMMIT_TIMER: a NORMAL primary sends COMMIT(v, k) to all others. Every
  timer re-arms.
- VIEW_TIMER: a NORMAL backup or a replica in VIEW_CHANGE loses one tick
  of patience, and with none left starts view v + 1. Hearing from the
  view's primary (a PREPARE or COMMIT of the view, a log installed, a view
  just started) gives back all ``PATIENCE`` ticks. A RECOVERING replica
  sends its RECOVERY again.
- REQUEST(value): a NORMAL primary with room appends a value it does not
  hold and sends PREPARE(v, n, k, value) to all others; a NORMAL backup
  forwards what a client sent it to primary(v), and forwards nothing a
  replica sent it.
- PREPARE(v, n, k, m), PREPAREOK(v, n), COMMIT(v, k), under the view rule:
  only a NORMAL replica acts; an older view's is dropped; a newer view's
  cuts the log to its committed prefix and asks the sender,
  GETSTATE(v, commit). In its own view: a PREPARE of the next entry is
  appended and acknowledged, PREPAREOK(v, n) to the primary (commit rises
  to min(k, n)); one it holds is acknowledged again, PREPAREOK(v, opn); one
  past a gap asks, GETSTATE(v, opn). The primary notes an acknowledgement
  and commits what f + 1 replicas, itself among them, hold: never more
  than its own log. A COMMIT raises commit to min(k, opn), and past the
  log's end asks, GETSTATE(v, opn).
- STARTVIEWCHANGE(v): a newer view's starts it (view v, VIEW_CHANGE, what
  was gathered dropped, STARTVIEWCHANGE(v) to all others). In view v and
  VIEW_CHANGE the sender is noted; at f others, once a view,
  DOVIEWCHANGE(v, v', n, k, log) goes to primary(v), whose own goes
  straight in.
- DOVIEWCHANGE: a newer view's starts it. primary(v) in VIEW_CHANGE
  gathers them; at f + 1 it installs the log with the largest (v', n)
  (``bug="dvc_by_opnum"``: the largest n), commit at least the largest k,
  and sends STARTVIEW(v, n, k, log) to all others.
- STARTVIEW(v, n, k, log): a newer view's, or this view's at a replica not
  NORMAL in it, is installed; with uncommitted entries it is acknowledged,
  PREPAREOK(v, n).
- RECOVERY(x): a NORMAL replica answers RECOVERYRESPONSE(v, x, n, k, log)
  if it is its view's primary, else (v, x, -1, -1, no log).
- RECOVERYRESPONSE: a RECOVERING replica gathers those with its nonce; at
  f + 1 senders, once the newest view among them is that of a primary's
  answer, it installs that answer. ``bug="recover_any"``: at f + 1
  senders whatever their views, with the newest primary's answer it holds
  or, holding none, an empty log in the newest view it heard of.
- GETSTATE(v, n'): a NORMAL replica in view v answers NEWSTATE(v, n, k,
  log); a NORMAL replica installs a NEWSTATE of a newer view, or of its
  own with a longer log.
- the network: a message to a stopped (hard-killed) node, or over a cut
  link, is lost at the send; what is pending to or from a node when it is
  hard-killed, its timers too, is lost; what is pending on a link when it
  is cut is lost. A restarted node has its initial state but its spawn
  count, and its three timers armed again. An isolated node receives
  nothing and nothing it sent is delivered until it is started again.
- the invariant, judged after every delivery and once more where the
  trace ends, over replicas that are up and NORMAL or VIEW_CHANGE: two
  hold different entries at an index both count committed and both hold
  (code 1); one counts more committed than its log holds (code 2).

``replay`` raises ``Diverged`` where the trace delivers a message the
reference does not hold or could not deliver, or goes on after the
verdict. Where the records carry the device's creation links (two more
columns, ``record_parents``), a message is held under the record that sent
it.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set

REC_DELIVERY, REC_TIMER = 1, 2
REC_EXT_BASE = 10
OP_START, OP_KILL, OP_SEND, OP_WAIT = 1, 2, 3, 4
OP_PARTITION, OP_UNPARTITION, OP_HARDKILL, OP_WAITCOND = 5, 6, 7, 8

(BOOT, COMMIT_TIMER, VIEW_TIMER, REQUEST, PREPARE, PREPAREOK, COMMIT,
 STARTVIEWCHANGE, DOVIEWCHANGE, STARTVIEW, RECOVERY, RECOVERYRESPONSE,
 GETSTATE, NEWSTATE) = range(1, 15)
TIMERS = (BOOT, COMMIT_TIMER, VIEW_TIMER)
HEAD = 5          # tag and four fields; the log follows
PATIENCE = 3      # VIEW_TIMER ticks before a backup suspects its primary
BOOTING, NORMAL, VIEW_CHANGE, RECOVERING = 0, 1, 2, 3


class Diverged(Exception):
    """The trace did something the protocol's rules do not allow."""


@dataclass
class Replica:
    view: int = 0
    status: int = BOOTING
    log: List[int] = field(default_factory=list)
    commit: int = 0
    last_normal: int = 0
    nonce: int = 0
    patience: int = 0
    acks: Dict[int, int] = field(default_factory=dict)
    svc: Set[int] = field(default_factory=set)
    dvc_sent: bool = False
    dvc: List[tuple] = field(default_factory=list)    # (sender, v', n, k, log)
    answered: Set[int] = field(default_factory=set)   # to this nonce
    newest: int = 0                                   # view among the answers
    held: Optional[tuple] = None                      # a primary's (v, k, log)


@dataclass
class Outcome:
    views: List[int]
    statuses: List[int]
    logs: List[List[int]]
    commits: List[int]
    spawns: List[int]
    alive: List[bool]
    code: int
    step: int            # deliveries when the verdict fell
    deliveries: int
    peak_pending: int
    log_rows: int        # messages sent that carried a log


class _Cluster:
    def __init__(self, n: int, log_cap: int, bug: Optional[str]):
        self.n, self.cap, self.bug = n, log_cap, bug
        self.f = (n - 1) // 2
        self.started = [False] * n
        self.isolated = [False] * n
        self.stopped = [False] * n
        self.cut: Set[frozenset] = set()
        self.replicas = [Replica() for _ in range(n)]
        self.spawns = [0] * n         # the one word on disk
        # (src, dst, message, sent by record) -> how many are pending; src n
        # = the client, src = dst a timer, record -1 where there are no links
        self.pending: Dict[tuple, int] = {}
        self.held = 0
        self.now = -1
        self.log_rows = 0

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

    def message(self, tag, f1=0, f2=0, f3=0, f4=0, log=None) -> tuple:
        if log is not None:
            self.log_rows += 1
        body = list(log or ())
        return (tag, f1, f2, f3, f4) + tuple(body + [0] * (self.cap - len(body)))

    def send(self, src: int, dst: int, msg: tuple) -> None:
        timer = src == dst and msg[0] in TIMERS
        if src < self.n and not timer and (
            self.stopped[dst] or frozenset((src, dst)) in self.cut
        ):
            return  # lost at the send
        entry = (src, dst, msg, self.now)
        self.pending[entry] = self.pending.get(entry, 0) + 1
        self.held += 1

    def to_others(self, src: int, *fields, log=None) -> None:
        for dst in range(self.n):
            if dst != src:
                self.send(src, dst, self.message(*fields, log=log))

    def drop(self, lost) -> None:
        for entry in [e for e in self.pending if lost(e)]:
            self.held -= self.pending.pop(entry)

    def spawn(self, node: int) -> None:
        self.replicas[node] = Replica()
        for tag in TIMERS:
            self.send(node, node, self.message(tag))

    # -- the protocol ------------------------------------------------------
    def primary(self, view: int) -> int:
        return view % self.n

    def install(self, r: Replica, view, commit, log) -> None:
        r.view, r.status, r.last_normal = view, NORMAL, view
        r.log, r.commit, r.patience = list(log), commit, PATIENCE

    def start_view(self, me: int, view: int) -> None:
        r = self.replicas[me]
        r.view, r.status, r.patience = view, VIEW_CHANGE, PATIENCE
        r.svc, r.dvc_sent, r.dvc = set(), False, []
        self.to_others(me, STARTVIEWCHANGE, view)

    def gather(self, me: int, vote: tuple) -> None:
        """A DOVIEWCHANGE at the new primary; at f + 1 the view starts."""
        r = self.replicas[me]
        r.dvc.append(vote)
        if len({sender for sender, *_ in r.dvc}) < self.f + 1:
            return
        if self.bug == "dvc_by_opnum":
            best = max(r.dvc, key=lambda d: d[2])
        else:
            best = max(r.dvc, key=lambda d: (d[1], d[2]))
        commit = max([r.commit] + [d[3] for d in r.dvc])
        self.install(r, r.view, commit, best[4])
        r.acks = {me: len(r.log)}
        self.to_others(me, STARTVIEW, r.view, len(r.log), r.commit, log=r.log)

    def view_rule(self, me: int, sender: int, view: int) -> bool:
        r = self.replicas[me]
        if r.status != NORMAL or view < r.view:
            return False
        if view > r.view:
            del r.log[min(len(r.log), r.commit):]
            self.send(me, sender, self.message(GETSTATE, view, r.commit))
            return False
        r.patience = PATIENCE
        return True

    def receive(self, me: int, sender: int, msg: tuple) -> None:
        r = self.replicas[me]
        tag, f1, f2, f3, f4 = msg[:HEAD]
        lead = self.primary(r.view)
        if tag == BOOT:
            if r.status != BOOTING:
                return
            self.spawns[me] += 1
            if self.spawns[me] == 1:
                r.status, r.patience = NORMAL, PATIENCE
            else:
                r.status, r.nonce = RECOVERING, self.spawns[me]
                self.to_others(me, RECOVERY, r.nonce)
        elif tag == COMMIT_TIMER:
            if r.status == NORMAL and lead == me:
                self.to_others(me, COMMIT, r.view, r.commit)
            self.send(me, me, self.message(COMMIT_TIMER))
        elif tag == VIEW_TIMER:
            if r.status == VIEW_CHANGE or (r.status == NORMAL and lead != me):
                if r.patience == 0:
                    self.start_view(me, r.view + 1)
                else:
                    r.patience -= 1
            elif r.status == RECOVERING:
                self.to_others(me, RECOVERY, r.nonce)
            self.send(me, me, self.message(VIEW_TIMER))
        elif tag == REQUEST:
            if r.status != NORMAL:
                return
            if lead != me:
                if sender >= self.n:
                    self.send(me, lead, self.message(REQUEST, f1))
            elif f1 not in r.log and len(r.log) < self.cap:
                r.log.append(f1)
                r.acks[me] = len(r.log)
                self.to_others(me, PREPARE, r.view, len(r.log), r.commit, f1)
        elif tag == PREPARE:
            if not self.view_rule(me, sender, f1):
                return
            if f2 > len(r.log) + 1:
                self.send(me, lead, self.message(GETSTATE, f1, len(r.log)))
                return
            if f2 == len(r.log) + 1:
                if len(r.log) == self.cap:
                    return
                r.log.append(f4)
                r.commit = max(r.commit, min(f3, len(r.log)))
            self.send(me, lead, self.message(PREPAREOK, f1, len(r.log)))
        elif tag == PREPAREOK:
            if not self.view_rule(me, sender, f1) or lead != me:
                return
            r.acks[sender] = max(r.acks.get(sender, 0), f2)
            counts = sorted(
                (r.acks.get(i, 0) for i in range(self.n)), reverse=True
            )
            r.commit = max(r.commit, min(counts[self.f], len(r.log)))
        elif tag == COMMIT:
            if not self.view_rule(me, sender, f1):
                return
            r.commit = max(r.commit, min(f2, len(r.log)))
            if f2 > len(r.log):
                self.send(me, lead, self.message(GETSTATE, f1, len(r.log)))
        elif tag in (STARTVIEWCHANGE, DOVIEWCHANGE):
            if r.status not in (NORMAL, VIEW_CHANGE):
                return
            if f1 > r.view:
                self.start_view(me, f1)
            if r.status != VIEW_CHANGE or f1 != r.view:
                return
            if tag == DOVIEWCHANGE:
                if self.primary(f1) == me:
                    self.gather(me, (sender, f2, f3, f4, list(msg[HEAD:HEAD + f3])))
                return
            r.svc.add(sender)
            if len(r.svc - {me}) >= self.f and not r.dvc_sent:
                r.dvc_sent = True
                if self.primary(r.view) == me:
                    self.gather(
                        me, (me, r.last_normal, len(r.log), r.commit, list(r.log))
                    )
                else:
                    self.send(me, self.primary(r.view), self.message(
                        DOVIEWCHANGE, r.view, r.last_normal, len(r.log),
                        r.commit, log=r.log,
                    ))
        elif tag == STARTVIEW:
            if r.status not in (NORMAL, VIEW_CHANGE):
                return
            if f1 > r.view or (f1 == r.view and r.status != NORMAL):
                self.install(r, f1, max(r.commit, f3), msg[HEAD:HEAD + f2])
                if f2 > f3:
                    self.send(
                        me, self.primary(f1), self.message(PREPAREOK, f1, f2)
                    )
        elif tag == RECOVERY:
            if r.status != NORMAL:
                return
            if lead == me:
                answer = self.message(
                    RECOVERYRESPONSE, r.view, f1, len(r.log), r.commit, log=r.log
                )
            else:
                answer = self.message(RECOVERYRESPONSE, r.view, f1, -1, -1)
            self.send(me, sender, answer)
        elif tag == RECOVERYRESPONSE:
            if r.status != RECOVERING or f2 != r.nonce:
                return
            r.answered.add(sender)
            r.newest = max(r.newest, f1)
            if f3 >= 0 and (r.held is None or f1 >= r.held[0]):
                r.held = (f1, f4, list(msg[HEAD:HEAD + f3]))
            if len(r.answered) < self.f + 1:
                return
            if r.held is not None and (
                r.held[0] == r.newest or self.bug == "recover_any"
            ):
                self.install(r, *r.held)
            elif self.bug == "recover_any":
                self.install(r, r.newest, 0, [])
            else:
                return
            r.acks = {me: len(r.log)}
        elif tag == GETSTATE:
            if r.status == NORMAL and f1 == r.view:
                self.send(me, sender, self.message(
                    NEWSTATE, r.view, len(r.log), r.commit, log=r.log
                ))
        elif tag == NEWSTATE:
            if r.status == NORMAL and (
                f1 > r.view or (f1 == r.view and f2 > len(r.log))
            ):
                self.install(
                    r, f1, max(r.commit, min(f3, f2)), msg[HEAD:HEAD + f2]
                )
        else:
            raise Diverged(f"a message with tag {tag} is no VSR replica's")

    def verdict(self) -> int:
        live = [
            r for i, r in enumerate(self.replicas)
            if self.up(i) and r.status in (NORMAL, VIEW_CHANGE)
        ]
        for a in live:
            for b in live:
                both = min(a.commit, b.commit, len(a.log), len(b.log))
                if a.log[:both] != b.log[:both]:
                    return 1
        if any(r.commit > len(r.log) for r in live):
            return 2
        return 0


def replay(
    num_nodes: int,
    log_cap: int,
    records: Sequence[Sequence[int]],
    length: int,
    bug: Optional[str] = None,
) -> Outcome:
    net = _Cluster(num_nodes, log_cap, bug)
    n, width = num_nodes, HEAD + log_cap
    deliveries = peak = code = 0
    verdict_at = None
    linked = int(length) > 0 and len(records[0]) >= 3 + width + 2
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
        msg = tuple(int(x) for x in records[i][3 : 3 + width])
        if kind in (REC_DELIVERY, REC_TIMER):
            sent_by = int(records[i][3 + width]) if linked else -1
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
                net.send(n, a, msg)
            elif op == OP_PARTITION:
                link = frozenset((a, b))
                net.cut.add(link)
                net.drop(lambda p: p[0] != p[1] and frozenset(p[:2]) == link)
            elif op == OP_UNPARTITION:
                net.cut.discard(frozenset((a, b)))
            elif op not in (OP_WAIT, OP_WAITCOND):
                raise Diverged(f"record {i}: unknown external op {op}")
        else:
            raise Diverged(f"record {i}: a {kind} record is no VSR replica's")
        peak = max(peak, net.held)
    return Outcome(
        views=[r.view for r in net.replicas],
        statuses=[r.status for r in net.replicas],
        logs=[list(r.log) for r in net.replicas],
        commits=[r.commit for r in net.replicas],
        spawns=list(net.spawns),
        alive=[net.up(i) for i in range(n)],
        code=code or net.verdict(),
        step=verdict_at if verdict_at is not None else deliveries,
        deliveries=deliveries,
        peak_pending=peak,
        log_rows=net.log_rows,
    )
