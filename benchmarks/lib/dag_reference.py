"""The plain reference of the ``spark17-shuffle200`` deployment: a driver
(the DAGScheduler, node 0) and its executors running one job of S stages
of T tasks, every task launched twice (speculative execution), over a
network with crash-stop, crash-recovery and isolation, in sets of
``(stage, task)``. No JAX, no masks, nothing of the program: it reads a
lane's recorded trace in the record encoding ``demi_tpu/device/core.py``
documents (int rows ``(kind, a, b, msg...)``: kind 1 a delivery from ``a``
to ``b``, kind ``10 + op`` an external event; a message is ``(tag, stage,
task)``) and replays it by the protocol's own rules:

- ``SubmitJob`` at the driver, while it is at stage 0 and not done:
  launch stage 0. An executor ignores one.
- launching stage s: ``LaunchTask(s, t)`` for every task t, twice: copy c
  goes to executor ``1 + (t + c) % E``.
- ``LaunchTask(s, t)`` at an executor: it adds (s, t) to its executed set
  and sends ``TaskDone(s, t)`` to the driver.
- ``TaskDone(s, t)`` at the driver, while the job runs: if s is the
  current stage (the epoch check; ``epoch_check=False`` is the control,
  the protocol's ``stale_task`` bug) it credits (current stage, t); when
  all T tasks of the stage are credited it moves on: the next stage is
  launched, or after the last the job is done. Nothing is resubmitted.
- a message to a stopped (hard-killed) node is lost at the send; what is
  pending to or from a node when it is hard-killed is lost; a restarted
  node starts with its initial state: an executor with nothing executed,
  the driver at stage 0 with nothing credited.
- an isolated (soft-killed) node receives nothing and nothing it sent is
  delivered until it is started again; its mail is held, not lost.
- a cut link loses what is pending on it and what is sent over it.
- the invariant, judged after every delivery and once more where the
  trace ends: a driver that is up and says done has credited no task
  that no executor that is up holds (code 1). The first delivery that
  breaks it is the verdict's step, and the lane's last.

``replay`` raises ``Diverged`` where the trace delivers a message the
reference does not hold or could not deliver, or goes on after the
verdict. Where the records carry the device's creation links (two more
columns, ``record_parents``: the index of the record that sent the
message), a message is held under the record that sent it, so a stage
launched by another delivery than the trace says is refused: the final
sets alone would not tell, since a stage launched early still ends with
the same tasks credited.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence, Set, Tuple

REC_DELIVERY = 1
REC_EXT_BASE = 10
OP_START, OP_KILL, OP_SEND, OP_WAIT = 1, 2, 3, 4
OP_PARTITION, OP_UNPARTITION, OP_HARDKILL, OP_WAITCOND = 5, 6, 7, 8
TAG_SUBMIT, TAG_LAUNCH, TAG_DONE = 1, 2, 3
PARENT_COLUMN = 6   # (kind, a, b, tag, stage, task, sent by, previous at b)
DRIVER = 0

Task = Tuple[int, int]  # (stage, task)


class Diverged(Exception):
    """The trace did something the protocol's rules do not allow."""


@dataclass
class Outcome:
    stage: int                  # the driver's current stage
    done: bool                  # the driver's done flag
    credited: Set[Task]         # what the driver credited
    executed: List[Set[Task]]   # per node: what it executed (node 0: nothing)
    alive: List[bool]
    code: int                   # 0 clean, 1 a credited task nobody up holds
    step: int                   # deliveries when the verdict fell
    quiescent: bool             # nothing was deliverable at the trace's end
    deliveries: int
    peak_pending: int


class _Cluster:
    def __init__(self, n: int, stages: int, tasks: int, epoch_check: bool):
        self.n, self.stages, self.tasks = n, stages, tasks
        self.epoch_check = epoch_check
        self.started = [False] * n
        self.isolated = [False] * n
        self.stopped = [False] * n
        self.cut: Set[frozenset] = set()
        self.stage, self.done = 0, False
        self.credited: Set[Task] = set()
        self.executed: List[Set[Task]] = [set() for _ in range(n)]
        # (src, dst, tag, stage, task, sent by record) -> how many are
        # pending; src n = a client, record -1 where the trace has no links
        self.pending: Dict[tuple, int] = {}
        self.held = 0
        self.now = -1   # the record being replayed, where links are kept

    # -- the network -------------------------------------------------------
    def up(self, node: int) -> bool:
        return (
            self.started[node] and not self.isolated[node]
            and not self.stopped[node]
        )

    def deliverable(self, src: int, dst: int) -> bool:
        if not self.up(dst):
            return False
        return src >= self.n or not self.isolated[src]

    def send(self, src: int, dst: int, msg: tuple) -> None:
        if src < self.n and (
            self.stopped[dst] or frozenset((src, dst)) in self.cut
        ):
            return  # lost at the send
        entry = (src, dst) + msg + (self.now,)
        self.pending[entry] = self.pending.get(entry, 0) + 1
        self.held += 1

    def drop(self, lost) -> None:
        for entry in [e for e in self.pending if lost(e)]:
            self.held -= self.pending.pop(entry)

    def reset(self, node: int) -> None:
        self.executed[node] = set()
        if node == DRIVER:
            self.stage, self.done, self.credited = 0, False, set()

    # -- the protocol ------------------------------------------------------
    def launch(self, stage: int) -> None:
        executors = self.n - 1
        for copy in (0, 1):
            for task in range(self.tasks):
                self.send(
                    DRIVER, 1 + (task + copy) % executors,
                    (TAG_LAUNCH, stage, task),
                )

    def receive(self, node: int, tag: int, stage: int, task: int) -> None:
        if tag == TAG_SUBMIT:
            if node == DRIVER and self.stage == 0 and not self.done:
                self.launch(0)
        elif tag == TAG_LAUNCH:
            if node != DRIVER:
                self.executed[node].add((stage, task))
                self.send(node, DRIVER, (TAG_DONE, stage, task))
        elif tag == TAG_DONE:
            if node != DRIVER or self.done or self.stage >= self.stages:
                return
            if self.epoch_check and stage != self.stage:
                return  # a late duplicate of an earlier stage
            self.credited.add((self.stage, task))
            if all((self.stage, t) in self.credited for t in range(self.tasks)):
                self.stage += 1
                if self.stage >= self.stages:
                    self.done = True
                else:
                    self.launch(self.stage)
        else:
            raise Diverged(f"a message with tag {tag} is no DAG scheduler's")

    def phantom(self) -> bool:
        """Done, and a credited task that no executor that is up holds."""
        if not (self.done and self.up(DRIVER)):
            return False
        held: Set[Task] = set()
        for node in range(1, self.n):
            if self.up(node):
                held |= self.executed[node]
        return not self.credited <= held


def replay(
    num_nodes: int,
    stages: int,
    tasks: int,
    records: Sequence[Sequence[int]],
    length: int,
    epoch_check: bool = True,
) -> Outcome:
    net = _Cluster(num_nodes, stages, tasks, epoch_check)
    n = num_nodes
    deliveries = peak = 0
    verdict_at = None
    linked = int(length) > 0 and len(records[0]) >= PARENT_COLUMN + 1
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
        if kind == REC_DELIVERY:
            msg = tuple(int(x) for x in records[i][3:6])
            sent_by = int(records[i][PARENT_COLUMN]) if linked else -1
            entry = (a, b) + msg + (sent_by,)
            if entry not in net.pending:
                raise Diverged(f"record {i}: {entry} is not pending")
            if not net.deliverable(a, b):
                raise Diverged(f"record {i}: {entry} is not deliverable")
            net.pending[entry] -= 1
            if not net.pending[entry]:
                del net.pending[entry]
            net.held -= 1
            deliveries += 1
            net.receive(b, *msg)
            if net.phantom():
                verdict_at = deliveries
        elif kind >= REC_EXT_BASE:
            op = kind - REC_EXT_BASE
            if op == OP_START:
                if not net.started[a] or net.stopped[a]:
                    net.reset(a)   # a fresh node, or a recovery
                net.started[a], net.isolated[a], net.stopped[a] = True, False, False
            elif op == OP_KILL:
                net.isolated[a] = True
            elif op == OP_HARDKILL:
                net.stopped[a] = True
                net.drop(lambda p: a in p[:2])
            elif op == OP_SEND:
                net.send(n, a, tuple(int(x) for x in records[i][3:6]))
            elif op == OP_PARTITION:
                link = frozenset((a, b))
                net.cut.add(link)
                net.drop(lambda p: frozenset(p[:2]) == link)
            elif op == OP_UNPARTITION:
                net.cut.discard(frozenset((a, b)))
            elif op not in (OP_WAIT, OP_WAITCOND):
                raise Diverged(f"record {i}: unknown external op {op}")
        else:
            raise Diverged(f"record {i}: a {kind} record is no DAG scheduler's")
        peak = max(peak, net.held)
    return Outcome(
        stage=net.stage,
        done=net.done,
        credited=net.credited,
        executed=net.executed,
        alive=[net.up(i) for i in range(n)],
        code=int(verdict_at is not None or net.phantom()),
        step=verdict_at if verdict_at is not None else deliveries,
        quiescent=not any(net.deliverable(e[0], e[1]) for e in net.pending),
        deliveries=deliveries,
        peak_pending=peak,
    )
