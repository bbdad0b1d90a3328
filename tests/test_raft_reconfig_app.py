"""Raft with persistent state, single-server membership changes and
InstallSnapshot (``apps/raft_reconfig.py``) on the normal path, at small
size on the CPU (the deployment is ``benchmarks/configs/raft7-reconfig.json``
cut to ``log_cap`` 8, ``snapshot_every`` 4, 256 deliveries and 48 fuzzed
events): the figures' rules on the host tier, one named delivery at a time
(4.1's fallback, 4.2.2's removed leader, 4.2.3's deaf follower, 4.4's
spare, fig. 5.3's steps 6 to 8, a log that commits past ``log_cap``, the
2015 scenario and the snapshot that loses its configuration, each with the
bug and without); ``DSLApp.durable`` at the row's scale on both tiers;
device lane, host oracle and the plain reference
(``benchmarks/lib/reconfig_reference.py``: classes, lists, sets, no JAX)
agreeing lane for lane on fuzzed crash-recovery-and-partition schedules;
the operator. The normal path (the CLI's verbs, the producers, some
thousands of lanes of the fixed protocol) is ``test_raft_reconfig_cli.py``."""

import importlib.util
import os
import random
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from demi_tpu.apps import raft_reconfig as rr
from demi_tpu.apps.common import make_host_invariant
from demi_tpu.config import SchedulerConfig
from demi_tpu.device.continuous import ContinuousSweepDriver
from demi_tpu.device.core import ST_DONE, ST_VIOLATION
from demi_tpu.device.encoding import (
    device_trace_to_guide, lower_program, stack_programs,
)
from demi_tpu.device.explore import make_single_lane_trace_kernel
from demi_tpu.external_events import OP_HARDKILL, OP_START
from demi_tpu.parallel.distributed import build_workload
from demi_tpu.runtime.actor import dsl_actor_factory
from demi_tpu.runtime.system import ControlledActorSystem
from demi_tpu.schedulers.guided import GuidedScheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module   # dataclasses look their module up
    spec.loader.exec_module(module)
    return module


reference = _load("benchmarks/lib/reconfig_reference.py", "reconfig_reference")

L, EVERY = 8, 4
NOOP, CMD, CFG = rr.K_NOOP, rr.K_CMD, rr.K_CFG


def workload(bug=None, **over):
    return {
        "app": "raft_reconfig", "nodes": 7, "bug": bug, "log_cap": L,
        "snapshot_every": EVERY, "seed": 0,
        "num_events": 48, "max_messages": 256, "pool": 128,
        "timer_weight": 0.1, "send_weight": 0.5, "wait_weight": 0.28,
        "wait_budget": [1, 40], "hard_kill_weight": 0.08,
        "restart_weight": 0.1, "partition_weight": 0.04, "kill_weight": 0.0,
        "max_kills": 4, **over,
    }


def lane_key(seed):
    return jax.random.fold_in(jax.random.PRNGKey(0), seed)


def mask(*servers):
    return sum(1 << i for i in servers)


# -- (a) the figures' rules, one delivery at a time, on the host tier -------

class Cluster:
    """The host tier's actor system with its mail held here, so that a
    test delivers what it names: ``ControlledActorSystem`` and the
    ``DSLActorAdapter`` over the app's one handler, no scheduler. The
    invariant is judged after every delivery (``codes``)."""

    def __init__(self, n=5, members=3, bug=None):
        self.app = rr.make_raft_reconfig_app(
            n, log_cap=L, snapshot_every=EVERY, bug=bug, members=members
        )
        self.lay = rr.state_layout(n, L)
        self.system = ControlledActorSystem()
        self.mail = []
        self.codes = []
        self.judge = jax.jit(self.app.invariant)
        for i in range(n):
            self.start(i)

    def name(self, i):
        return self.app.actor_name(i)

    def start(self, i):
        self.mail += self.system.spawn(
            self.name(i), dsl_actor_factory(self.app, i)
        )

    def hard_kill(self, i):
        self.system.hard_kill(self.name(i))
        self.mail = [
            e for e in self.mail if self.name(i) not in (e.snd, e.rcv)
        ]

    def inject(self, i, *fields):
        msg = tuple(fields) + (0,) * (self.app.msg_width - len(fields))
        self.mail.append(self.system.inject(self.name(i), msg))

    def client(self, i, key, v):
        self.inject(i, rr.T_CLIENT, key, v)
        self.deliver(rr.T_CLIENT, i)

    def admin(self, i, op, server):
        self.inject(i, rr.T_ADMIN, op, server)
        self.deliver(rr.T_ADMIN, i)

    def find(self, tag, dst=None, src=None):
        return [
            e for e in self.mail if e.msg[0] == tag
            and (dst is None or e.rcv == self.name(dst))
            and (src is None or e.snd == self.name(src))
        ]

    def code(self):
        n = self.app.num_actors
        states = np.zeros((n, self.app.state_width), np.int32)
        alive = np.zeros(n, bool)
        for i in range(n):
            actor = self.system.actors.get(self.name(i))
            if actor is not None:
                states[i], alive[i] = actor.state, True
        return int(self.judge(states, alive))

    def take(self, entry):
        if entry in self.mail:       # else handed in by the test
            self.mail.remove(entry)
        self.mail += self.system.deliver(entry)
        self.codes.append(self.code())

    def deliver(self, tag, dst=None, src=None):
        self.take(self.find(tag, dst, src)[0])

    def lose(self, tag=None, dst=None, src=None):
        for entry in list(self.mail):
            if entry.is_timer or (tag is not None and entry.msg[0] != tag):
                continue
            if dst is not None and entry.rcv != self.name(dst):
                continue
            if src is not None and entry.snd != self.name(src):
                continue
            self.mail.remove(entry)

    def drain(self, among=None):
        """Every message, oldest first, until only timers are left; with
        ``among``, only what travels between those servers (the rest is
        lost: they are cut off)."""
        while True:
            if among is not None:
                names = {self.name(i) for i in among}
                for entry in list(self.mail):
                    if not entry.is_timer and not (
                        entry.rcv in names
                        and (entry.snd in names or entry.is_external)
                    ):
                        self.mail.remove(entry)
            due = [e for e in self.mail if not e.is_timer]
            if not due:
                return
            self.take(due[0])

    def stand(self, i):
        """ELECTION at ``i`` until it stands (the first may only clear
        HEARD)."""
        term = self.get(i, "TERM")
        for _ in range(2):
            if self.get(i, "TERM") == term:
                self.deliver(rr.T_ELECTION, i)
        assert self.get(i, "TERM") == term + 1

    def elect(self, i, voters, among=None):
        """``i`` stands and ``voters`` answer; every other REQ_VOTE is
        lost, and so is what the new leader sends at once."""
        self.stand(i)
        for v in voters:
            self.deliver(rr.T_REQ_VOTE, v, i)
            self.deliver(rr.T_VOTE_REPLY, i, v)
        self.lose(rr.T_REQ_VOTE, src=i)
        assert self.get(i, "ROLE") == rr.LEADER
        self.lose(rr.T_APPEND, src=i)

    def beat(self, i, among=None):
        self.deliver(rr.T_HEARTBEAT, i)
        self.drain(among)

    def row(self, i):
        return self.system.actors[self.name(i)].state

    def get(self, i, field):
        return int(self.row(i)[getattr(rr, field)])

    def array(self, i, field):
        start, length = self.lay[field]
        return self.row(i)[start : start + length].tolist()

    def log(self, i):
        held = self.get(i, "LOG_LEN")
        return list(zip(
            self.array(i, "LOG_T")[:held], self.array(i, "LOG_K")[:held],
            self.array(i, "LOG_V")[:held],
        ))


BOOT3 = (0, CFG, mask(0, 1, 2))


def led_cluster(n=5, members=3, bug=None, among=None):
    """Server 0 leads term 1 and its no-op is committed everywhere it
    reaches (``among``; by default the boot configuration)."""
    c = Cluster(n, members, bug)
    among = list(range(members)) if among is None else among
    c.elect(0, [v for v in among if v != 0][: members // 2])
    c.beat(0, among)
    c.beat(0, among)
    return c


def test_a_leader_appends_a_noop_and_commits_commands():
    c = led_cluster()
    assert c.log(0) == [BOOT3, (1, NOOP, 0)]
    assert [c.get(i, "COMMIT") for i in range(3)] == [2, 2, 2]
    c.client(1, 5, 77)           # a follower forwards what a client sent it
    c.deliver(rr.T_CLIENT, 0, 1)
    c.drain([0, 1, 2])
    c.beat(0, [0, 1, 2])
    assert c.log(2)[-1] == (1, CMD, 5 * 65536 + 77)
    assert [c.array(i, "REG")[5] for i in range(3)] == [77, 77, 77]
    assert len({c.get(i, "DIGEST") for i in range(3)}) == 1
    assert not any(c.codes)


def test_a_spare_never_stands_and_counts_in_no_quorum():
    c = led_cluster()
    assert c.get(3, "CFG") == 0 and c.log(3) == []
    for _ in range(3):
        c.deliver(rr.T_ELECTION, 3)
    assert (c.get(3, "TERM"), c.get(3, "ROLE")) == (0, rr.FOLLOWER)
    assert not c.find(rr.T_REQ_VOTE, src=3)
    # it answers a candidate all the same (4.1)
    c.stand(1)
    assert {e.rcv for e in c.find(rr.T_REQ_VOTE, src=1)} == {"g0", "g2"}


def test_a_server_that_has_heard_a_leader_is_deaf_to_a_candidate():
    c = led_cluster()
    assert c.get(2, "HEARD") == 1
    c.stand(1)                                   # term 2
    c.deliver(rr.T_REQ_VOTE, 2, 1)
    assert c.get(2, "TERM") == 1 and not c.find(rr.T_VOTE_REPLY, 1)
    c.deliver(rr.T_ELECTION, 2)                  # its own timeout passes
    assert c.get(2, "HEARD") == 0 and c.get(2, "TERM") == 1
    c.stand(1)                                   # term 3
    c.deliver(rr.T_REQ_VOTE, 2, 1)
    assert c.get(2, "TERM") == 3 and c.get(2, "VOTED_FOR") == 1
    assert c.find(rr.T_VOTE_REPLY, 1)[0].msg[:3] == (rr.T_VOTE_REPLY, 3, 1)


def add_server(c, leader, server, among):
    c.admin(leader, rr.ADD, server)
    c.drain(among)
    c.beat(leader, among)
    c.beat(leader, among)


def test_add_server_catches_up_then_joins_and_remove_server_leaves():
    c = led_cluster()
    c.admin(0, rr.ADD, 3)
    assert c.get(0, "PENDING") == rr.ADD * 8 + 3
    assert c.get(0, "CATCHUP_ROUND") == 1 and c.get(0, "CFG") == mask(0, 1, 2)
    c.admin(0, rr.ADD, 4)                        # one at a time: dropped
    assert c.get(0, "PENDING") == rr.ADD * 8 + 3
    c.drain([0, 1, 2, 3])                        # the round, then the entry
    assert c.get(0, "PENDING") == 0
    assert c.log(0)[-1] == (1, CFG, mask(0, 1, 2, 3))
    assert c.get(0, "CFG") == mask(0, 1, 2, 3)   # used at once
    assert c.get(0, "CFG_IDX") == 3 and c.get(3, "CFG") == mask(0, 1, 2, 3)
    c.beat(0, [0, 1, 2, 3])
    assert c.get(0, "COMMIT") == 3 and c.get(0, "CFG_COMMITTED") == 1
    c.admin(0, rr.ADD, 3)                        # already so: nothing
    assert c.get(0, "PENDING") == 0
    c.admin(0, rr.REMOVE, 1)
    assert c.log(0)[-1] == (1, CFG, mask(0, 2, 3))
    c.drain([0, 2, 3])
    c.beat(0, [0, 2, 3])
    assert c.get(0, "COMMIT") == 4 and c.get(0, "CFG_COMMITTED") == 2
    assert not any(c.codes)


def test_a_new_server_that_does_not_answer_is_given_up():
    c = led_cluster()
    c.admin(0, rr.ADD, 4)
    for beats in range(rr.CATCHUP_ROUNDS):
        assert c.get(0, "PENDING") == rr.ADD * 8 + 4
        c.beat(0, [0, 1, 2])
    assert c.get(0, "PENDING") == 0 and c.get(0, "CATCHUP_ROUND") == 0
    assert c.get(0, "CFG") == mask(0, 1, 2)


def test_a_truncated_configuration_entry_falls_back():
    c = led_cluster(n=7, members=5)
    boot = (0, CFG, mask(0, 1, 2, 3, 4))
    c.admin(0, rr.ADD, 5)
    c.drain([0, 5])                  # caught up, appended, sent to 5 ...
    c.deliver(rr.T_HEARTBEAT, 0)
    c.deliver(rr.T_APPEND, 4, 0)     # ... and to 4, whose answer is lost
    c.lose()
    grown = mask(0, 1, 2, 3, 4, 5)
    assert [c.get(i, "CFG") for i in (0, 4, 5)] == [grown] * 3
    assert c.get(4, "CFG_IDX") == 3 and c.get(0, "COMMIT") == 2
    for deaf in (2, 3):
        c.deliver(rr.T_ELECTION, deaf)   # their timeouts pass
    c.elect(1, [2, 3])               # term 2, by the boot configuration
    c.beat(1, [1, 2, 3, 4])          # its no-op overwrites index 3 at 4
    assert c.log(4) == [boot, (1, NOOP, 0), (2, NOOP, 0)]
    assert c.get(4, "CFG") == mask(0, 1, 2, 3, 4) and c.get(4, "CFG_IDX") == 1
    c.beat(1, [0, 1, 2, 3, 4])
    assert c.get(0, "CFG") == mask(0, 1, 2, 3, 4) and c.get(0, "ROLE") == 0
    assert not any(c.codes)


def test_a_removed_leader_commits_its_removal_without_itself_and_steps_down():
    c = led_cluster()
    c.admin(0, rr.REMOVE, 0)
    assert c.get(0, "CFG") == mask(1, 2) and c.get(0, "ROLE") == rr.LEADER
    c.deliver(rr.T_APPEND, 1, 0)
    c.deliver(rr.T_APPEND_REPLY, 0, 1)
    # itself and server 1 hold it: one of the two members, no majority
    assert c.get(0, "COMMIT") == 2 and c.get(0, "ROLE") == rr.LEADER
    c.deliver(rr.T_APPEND, 2, 0)
    c.deliver(rr.T_APPEND_REPLY, 0, 2)
    assert c.get(0, "COMMIT") == 3 and c.get(0, "CFG_COMMITTED") == 1
    assert c.get(0, "ROLE") == rr.FOLLOWER      # 4.2.2
    for _ in range(3):
        c.deliver(rr.T_ELECTION, 0)              # no member: never stands
    assert c.get(0, "TERM") == 1
    assert not any(c.codes)


def compacted_cluster(bug=None, n=5):
    """Server 0 leads {0, 1, 2}; server 2 hears nothing after the no-op;
    six commands are committed by 0 and 1, who both compact."""
    c = led_cluster(n=n, bug=bug)
    for v in range(1, 7):
        c.client(0, v % 3, v)
        c.drain([0, 1])
        c.beat(0, [0, 1])
    return c


def test_a_schedule_commits_more_than_the_log_holds():
    c = compacted_cluster()
    for v in range(7, 20):
        c.client(0, v % 8, v)
        c.drain([0, 1])
        c.beat(0, [0, 1])
    assert c.get(0, "COMMIT") == c.get(1, "COMMIT") == 21 > 2 * L
    assert c.get(0, "COMPACTIONS") >= 4 and c.get(0, "LOG_LEN") <= L
    assert c.get(0, "LOG_BASE") == 20 and c.get(0, "APPLIED") == 21
    assert c.array(0, "REG") == c.array(1, "REG")
    assert c.array(0, "RING") == c.array(1, "RING")
    # the lagging member catches up through a snapshot and the tail
    c.beat(0, [0, 1, 2])
    c.beat(0, [0, 1, 2])
    assert c.get(2, "SNAP_INSTALLED") == 1 and c.get(0, "SNAP_SENT") >= 1
    assert c.get(2, "APPLIED") == 21 and c.get(2, "DIGEST") == c.get(0, "DIGEST")
    assert c.array(2, "REG") == c.array(0, "REG")
    assert not any(c.codes)


def test_install_snapshot_loads_the_configuration_and_the_state_machine():
    c = compacted_cluster()
    add_server(c, 0, 3, [0, 1, 3])
    assert c.get(3, "SNAP_INSTALLED") == 1 and c.get(3, "LOG_BASE") >= EVERY
    assert c.get(3, "CFG") == mask(0, 1, 2, 3)
    assert c.get(3, "SNAP_CFG") == mask(0, 1, 2)  # as of the snapshot
    assert c.array(3, "REG") == c.array(0, "REG")
    assert c.get(3, "DIGEST") == c.get(0, "DIGEST")
    assert c.get(3, "RING_FROM") >= EVERY
    assert not any(c.codes)


def test_install_snapshot_keeps_a_matching_suffix():
    c = compacted_cluster()
    everyone = [0, 1, 2]
    c.beat(0, everyone)
    c.beat(0, everyone)
    assert [c.get(i, "LOG_BASE") for i in everyone] == [8, 8, 8]
    c.client(0, 1, 7)
    c.drain(everyone)
    assert c.get(0, "APPLIED") == 9
    at_nine = c.array(0, "REG"), c.get(0, "DIGEST")
    c.client(0, 2, 8)
    c.drain(everyone)
    c.beat(0, everyone)
    assert c.get(2, "APPLIED") == 10 and len(c.log(2)) == 2
    digest, tail = c.get(2, "DIGEST"), c.log(2)[1:]
    installed = c.get(2, "SNAP_INSTALLED")
    # a snapshot that ends inside what server 2 holds (fig. 5.3, step 6)
    snap = (rr.T_INSTALL, 1, 9, 1, mask(0, 1, 2), at_nine[1], *at_nine[0])
    c.take(c.system.inject_from(
        c.name(0), c.name(2), snap + (0,) * (18 - len(snap))
    ))
    assert c.get(2, "LOG_BASE") == 9 and c.log(2) == tail
    assert c.get(2, "DIGEST") == digest and c.get(2, "APPLIED") == 10
    assert c.get(2, "SNAP_DIGEST") == at_nine[1]
    assert c.array(2, "SNAP_REG") == at_nine[0]
    assert c.get(2, "SNAP_INSTALLED") == installed + 1
    assert c.get(2, "RING_FROM") == 8       # the state machine was not reset
    assert c.find(rr.T_SNAP_REPLY, 0)[0].msg[:3] == (rr.T_SNAP_REPLY, 1, 9)
    # one that is no newer than its own changes nothing
    before = c.row(2).copy()
    c.take(c.system.inject_from(
        c.name(0), c.name(2), snap + (0,) * (18 - len(snap))
    ))
    np.testing.assert_array_equal(c.row(2), before)
    assert not any(c.codes)


# The 2015 scenario (Ongaro, raft-dev, 10 July 2015), servers S1..S5 as
# 0..4, the configuration C = {0, 1, 2, 3}.

def the_2015_scenario(bug):
    c = Cluster(5, 4, bug)
    c.elect(0, [1, 2])                      # S1 leads term 1
    c.admin(0, rr.ADD, 4)                   # D = C + S5 ...
    c.drain([0, 4])                         # ... reaches S5 alone
    if bug is None:
        # the leader has committed nothing of its term: it waits
        assert c.get(0, "PENDING") == rr.ADD * 8 + 4
        assert c.get(0, "CATCHUP_ROUND") == 0
        assert c.log(0) == [(0, CFG, mask(0, 1, 2, 3)), (1, NOOP, 0)]
        return c
    assert c.log(0)[-1] == c.log(4)[-1] == (1, CFG, mask(0, 1, 2, 3, 4))
    assert c.get(0, "COMMIT") == 1
    c.stand(1)                              # S2 stands for term 2
    for voter in (2, 3):
        c.deliver(rr.T_REQ_VOTE, voter, 1)
        c.deliver(rr.T_VOTE_REPLY, 1, voter)
    c.deliver(rr.T_REQ_VOTE, 0, 1)          # S1 loses its role
    assert c.get(1, "ROLE") == rr.LEADER and c.get(0, "ROLE") == rr.FOLLOWER
    c.lose()
    c.admin(1, rr.REMOVE, 0)                # E = C - S1, before its no-op
    assert c.log(1)[-1] == (2, CFG, mask(1, 2, 3))
    c.deliver(rr.T_APPEND, 2, 1)
    c.deliver(rr.T_APPEND_REPLY, 1, 2)      # S2 and S3 of E: committed
    assert c.get(1, "COMMIT") == 3 and c.get(1, "APPLIED") == 3
    c.lose()
    c.deliver(rr.T_ELECTION, 4)             # S5 has heard S1 of late
    c.stand(0)                              # S1 stands for term 3
    for voter in (3, 4):                    # S4 and S5 of D: a majority
        c.deliver(rr.T_REQ_VOTE, voter, 0)
        c.deliver(rr.T_VOTE_REPLY, 0, voter)
    assert c.get(0, "ROLE") == rr.LEADER and c.get(0, "TERM") == 3
    assert not any(c.codes)
    c.drain([0, 3, 4])                      # D and S1's no-op commit
    return c


def test_the_2015_scenario_violates_as_published():
    c = the_2015_scenario("reconfig_before_noop")
    assert c.codes[-1] == 2 and c.codes.index(2) == len(c.codes) - 1
    assert c.get(0, "APPLIED") >= 3 and c.get(1, "APPLIED") == 3
    ring = [c.array(i, "RING") for i in (0, 1)]
    assert ring[0][2] == ring[1][2]         # index 2: a no-op at both
    assert ring[0][3] != ring[1][3]         # index 3: D here, E there


def test_the_2015_scenario_is_refused_by_the_fix():
    c = the_2015_scenario(None)
    # ... and goes on once an entry of its term is committed
    c.beat(0, [0, 1, 2, 4])
    c.beat(0, [0, 1, 2, 4])
    assert c.log(0)[-1] == (1, CFG, mask(0, 1, 2, 3, 4))
    assert c.get(0, "PENDING") == 0 and not any(c.codes)


# The snapshot that loses its configuration: {0, 1, 2} becomes {1, 2, 3}
# while 2 hears nothing; 2 catches up through a snapshot.

def the_snapshot_scenario(bug):
    c = Cluster(4, 3, bug)
    c.elect(1, [0])
    c.beat(1, [0, 1])
    c.beat(1, [0, 1])
    add_server(c, 1, 3, [0, 1, 3])
    assert c.get(1, "CFG") == mask(0, 1, 2, 3) and c.get(1, "COMMIT") == 3
    c.admin(1, rr.REMOVE, 0)
    c.drain([1, 3])
    c.beat(1, [1, 3])
    assert c.get(1, "COMMIT") == 4 and c.get(1, "LOG_BASE") == 4
    assert c.get(1, "SNAP_CFG") == mask(1, 2, 3)
    assert c.get(0, "CFG") == mask(0, 1, 2, 3)       # never heard of it
    c.beat(1, [1, 2])                                # 2 gets the snapshot
    assert c.get(2, "SNAP_INSTALLED") == 1 and c.get(2, "LOG_BASE") == 4
    assert c.get(2, "SNAP_CFG") == mask(1, 2, 3)
    return c


def test_a_snapshot_that_loses_its_configuration_splits_the_cluster():
    c = the_snapshot_scenario("snapshot_keeps_config")
    assert c.get(2, "CFG") == mask(0, 1, 2)          # its own, kept
    c.deliver(rr.T_ELECTION, 0)                      # 0 heard 1 of late
    c.stand(2)                                       # term 2
    assert {e.rcv for e in c.find(rr.T_REQ_VOTE, src=2)} == {"g0", "g1"}
    c.deliver(rr.T_REQ_VOTE, 0, 2)
    c.deliver(rr.T_VOTE_REPLY, 2, 0)
    assert c.get(2, "ROLE") == rr.LEADER             # by {0, 2} of the old
    c.lose(rr.T_REQ_VOTE)
    c.drain([0, 2])                                  # its no-op commits
    assert c.get(2, "COMMIT") == 5 and not any(c.codes)
    c.client(1, 3, 9)                                # the old leader's too
    c.drain([1, 3])
    assert c.get(1, "COMMIT") == 5 and c.codes[-1] == 2


def test_a_snapshot_with_its_configuration_does_not():
    c = the_snapshot_scenario(None)
    assert c.get(2, "CFG") == mask(1, 2, 3)
    c.stand(2)
    assert {e.rcv for e in c.find(rr.T_REQ_VOTE, src=2)} == {"g1", "g3"}
    c.drain()
    assert not any(c.codes)


def test_the_shapes_are_the_issues():
    app = rr.make_raft_reconfig_app(7, log_cap=32, snapshot_every=16)
    assert (app.msg_width, app.max_outbox, app.state_width) == (18, 8, 216)
    assert app.state_width == rr.state_width(7, 32)
    assert len(app.durable) == 115 and app.spawn_count == rr.RESTORES
    assert set(range(rr.TERM, rr.SNAP_INSTALLED + 1)) <= set(app.durable)
    assert not {rr.ROLE, rr.COMMIT, rr.APPLIED, rr.CFG, rr.HEARD} & set(app.durable)
    assert app.timer_tags == (1, 2) and len(app.tag_names) == 11
    assert [name for name, _ in app.progress] == [
        "committed", "reconfigs", "compactions", "snap_sent",
        "snap_installed", "restores",
    ]
    assert app.channels == "any" and app.invariant_at == "delivery"
    boot, spare = app.init_state(4), app.init_state(5)
    assert boot[rr.CFG] == 0b11111 and boot[rr.LOG_LEN] == boot[rr.COMMIT] == 1
    assert spare[rr.CFG] == 0 and spare[rr.LOG_LEN] == spare[rr.COMMIT] == 0
    for bad in (
        dict(bug="no_such_bug"), dict(num_actors=2), dict(num_actors=9),
        dict(log_cap=65), dict(snapshot_every=33),
    ):
        with pytest.raises(ValueError):
            rr.make_raft_reconfig_app(**{"num_actors": 7, "log_cap": 32, **bad})


def test_no_branch_sends_more_rows_than_the_outbox_holds():
    app = rr.make_raft_reconfig_app(7, log_cap=L)
    state = jnp.asarray(app.init_state(0))
    handler = jax.jit(app.handler)
    for tag in range(1, rr.NUM_TAGS + 1):
        msg = jnp.zeros(app.msg_width, jnp.int32).at[0].set(tag)
        new, out = handler(jnp.int32(0), state, jnp.int32(1), msg)
        assert out.shape == (app.max_outbox, 2 + app.msg_width), tag
        assert new.shape == state.shape and new.dtype == jnp.int32


# -- (b) DSLApp.durable at the row's scale, on both tiers -------------------

def restart_is_from_disk(app, before, after, server):
    """``after`` is ``before`` in every durable word, the init row in
    every other, and one more life."""
    durable = np.zeros(app.state_width, bool)
    durable[list(app.durable)] = True
    fresh = app.init_state(server)
    fresh[rr.RESTORES] = before[rr.RESTORES] + 1
    np.testing.assert_array_equal(after[durable], before[durable])
    np.testing.assert_array_equal(after[~durable], fresh[~durable])
    assert durable.sum() == len(app.durable)
    # what the test is for: the disk held something
    assert before[rr.LOG_BASE] >= EVERY and before[rr.TERM] >= 1
    assert before[durable].any() and (before != after).any()


def test_a_restart_keeps_the_durable_row_on_the_host_tier():
    c = compacted_cluster()
    add_server(c, 0, 3, [0, 1, 3])
    for server in (1, 3):       # one that compacted, a spare that installed
        before = c.row(server).copy()
        assert before[rr.COMPACTIONS if server == 1 else rr.SNAP_INSTALLED] >= 1
        c.hard_kill(server)
        c.start(server)
        restart_is_from_disk(c.app, before, c.row(server), server)
        # ... and its first delivery is a recovery from it
        c.deliver(rr.T_ELECTION, server)
        assert c.get(server, "APPLIED") == c.get(server, "LOG_BASE")
        assert c.array(server, "REG") == c.array(server, "SNAP_REG")
        assert c.get(server, "CFG") == mask(0, 1, 2, 3)
    assert not any(c.codes)


def run_lanes(app, cfg, gen, progs, keys):
    """The lanes run to their end through the continuous driver's own
    kernels: the final ``ScheduleState``, on the host."""
    lanes = keys.shape[0]
    drv = ContinuousSweepDriver(app, cfg, gen, batch=lanes, seg_steps=64)
    state = drv.init(keys)
    for steps in range(0, cfg.max_steps, 64):
        state = drv.segment(state, progs, jnp.full(lanes, steps, jnp.int32))
    return jax.device_get(drv.finalize(state))


@pytest.mark.parametrize("what", ["COMPACTIONS", "SNAP_INSTALLED"])
def test_a_restart_keeps_the_durable_row_on_the_device(swept, what):
    """``core.external_effects``, the step kernel's own, on the final
    state of a fuzzed lane: a HardKill, then a Start."""
    from demi_tpu.device import core
    from demi_tpu.device.explore import _precomputed

    app, cfg = swept["app"], swept["cfg"]
    rows = np.asarray(swept["state"].actor_state)
    found = [
        (lane, server)
        for lane in range(rows.shape[0]) for server in range(7)
        if rows[lane, server, getattr(rr, what)] >= 1
        and rows[lane, server, rr.LOG_BASE] >= EVERY
        and swept["state"].status[lane] == ST_DONE
    ]
    assert len(found) >= 3, what
    init_states, initial_rows = _precomputed(app, cfg)
    no_msg = jnp.zeros(cfg.msg_width, jnp.int32)

    @jax.jit
    def restart(state, server):
        for op in (core.OP_HARDKILL, core.OP_START):
            state, _rows, _rec, _on = core.external_effects(
                state, cfg, app, initial_rows, init_states, jnp.int32(op),
                server, jnp.int32(0), no_msg,
            )
        return state.actor_state

    for lane, server in found[:: max(1, len(found) // 6)]:
        state = jax.tree_util.tree_map(
            lambda x: jnp.asarray(x[lane]), swept["state"]
        )
        after = np.asarray(restart(state, jnp.int32(server)))
        restart_is_from_disk(app, rows[lane, server], after[server], server)
        others = [i for i in range(7) if i != server]
        np.testing.assert_array_equal(after[others], rows[lane, others])


# -- (c) device, host oracle and the plain reference, lane for lane ---------

# Fuzz seeds: the first 20, and the one of the first 4,096 on which
# ``snapshot_keeps_config`` breaks the invariant in 256 deliveries; then
# four on which it does in 768 (96 events, pool 256), both codes among them.
BUG = "snapshot_keeps_config"
SEEDS = list(range(20)) + [165]
LONG = dict(max_messages=768, num_events=96, pool=256)
LONG_SEEDS = [62, 349, 436, 1184]


def _swept(seeds, **over):
    """The seeds run to their end through the continuous driver's own
    kernels, and what the per-lane lifts need."""
    app, cfg, fuzzer = build_workload(workload(BUG, **over))
    gen = lambda s: fuzzer.generate_fuzz_test(seed=s)  # noqa: E731
    progs = stack_programs([lower_program(app, cfg, gen(s)) for s in seeds])
    keys = jax.vmap(lane_key)(np.asarray(seeds, np.uint32))
    state = run_lanes(app, cfg, gen, progs, keys)
    return {
        "app": app, "cfg": cfg, "progs": progs, "keys": keys, "state": state,
        "kernel": make_single_lane_trace_kernel(app, cfg), "lifted": {},
    }


@pytest.fixture(scope="module")
def swept():
    return _swept(SEEDS)


@pytest.fixture(scope="module")
def swept_long():
    return _swept(LONG_SEEDS, **LONG)


def lifted(swept, lane):
    if lane not in swept["lifted"]:
        app = swept["app"]
        single = swept["kernel"](
            jax.tree_util.tree_map(lambda x: x[lane], swept["progs"]),
            swept["keys"][lane],
        )
        guide = device_trace_to_guide(
            app, np.asarray(single.trace), int(single.trace_len)
        )
        sched = GuidedScheduler(
            SchedulerConfig(invariant_check=make_host_invariant(app)), app
        )
        host = sched.execute_guide(guide)
        rows = {
            app.actor_id(name): np.asarray(actor.state)
            for name, actor in sched.system.actors.items()
        }
        swept["lifted"][lane] = (single, host, rows)
    return swept["lifted"][lane]


def test_the_seeds_do_the_deployments_work(swept):
    rows = np.asarray(swept["state"].actor_state)
    status = np.asarray(swept["state"].status).tolist()
    assert status == [ST_DONE] * 20 + [ST_VIOLATION]
    assert rows[:, :, rr.COMPACTIONS].sum() >= 21
    assert rows[:, :, rr.SNAP_INSTALLED].sum() >= 8
    assert rows[:, :, rr.CFG_COMMITTED].sum() >= 4
    assert (rows[:, :, rr.RESTORES] > 1).sum() >= 21
    assert rows[:, :, rr.COMMIT].max() > L


def _device_and_host_agree(swept, lane):
    state = swept["state"]
    single, host, rows = lifted(swept, lane)
    code = int(state.violation[lane])
    host_code = host.violation.code if host.violation is not None else 0
    assert int(single.violation) == code == host_code
    assert int(single.sched_hash) == int(state.sched_hash[lane])
    assert int(single.deliveries) == int(state.deliveries[lane]) == host.deliveries
    assert rows, "no server is left on the host"
    for i, row in rows.items():
        np.testing.assert_array_equal(row, state.actor_state[lane][i], str(i))


def _the_plain_reference_agrees(swept, lane):
    single, host, rows = lifted(swept, lane)
    ref = reference.replay(
        7, L, EVERY, np.asarray(single.trace).tolist(), int(single.trace_len),
        bug=BUG,
    )
    host_code = host.violation.code if host.violation is not None else 0
    assert ref.code == host_code
    assert ref.step == ref.deliveries == host.deliveries
    lay = rr.state_layout(7, L)

    def array(row, field):
        start, length = lay[field]
        return row[start : start + length].tolist()

    for i, row in rows.items():
        r = ref.servers[i]
        held = len(r.log)
        assert (r.term, -1 if r.voted_for is None else r.voted_for) == (
            int(row[rr.TERM]), int(row[rr.VOTED_FOR])
        ), i
        assert (r.base, held) == (int(row[rr.LOG_BASE]), int(row[rr.LOG_LEN])), i
        assert [tuple(e) for e in r.log] == list(zip(
            array(row, "LOG_T")[:held], array(row, "LOG_K")[:held],
            array(row, "LOG_V")[:held],
        )), i
        assert (r.role, r.commit, r.applied, r.digest) == (
            int(row[rr.ROLE]), int(row[rr.COMMIT]), int(row[rr.APPLIED]),
            int(row[rr.DIGEST]),
        ), i
        assert r.reg == array(row, "REG"), i
        assert r.snapshot["reg"] == array(row, "SNAP_REG"), i
        assert (
            reference.mask_of(r.snapshot["config"]), r.snapshot["term"],
            r.snapshot["digest"],
        ) == (
            int(row[rr.SNAP_CFG]), int(row[rr.SNAP_TERM]),
            int(row[rr.SNAP_DIGEST]),
        ), i
        assert (reference.mask_of(r.config), r.config_at) == (
            int(row[rr.CFG]), int(row[rr.CFG_IDX])
        ), i
        assert ref.spawns[i] == int(row[rr.RESTORES]), i
    final = swept["state"].actor_state[lane]
    for name, fn in swept["app"].progress:
        assert ref.counts[name] == int(fn(jnp.asarray(final))), name


@pytest.mark.parametrize("lane", range(len(SEEDS)))
def test_device_and_host_agree_on_a_fuzzed_lane(swept, lane):
    """Same code, same delivered sequence, same final rows."""
    _device_and_host_agree(swept, lane)


@pytest.mark.parametrize("lane", range(len(SEEDS)))
def test_the_plain_reference_agrees_on_a_fuzzed_lane(swept, lane):
    """Verdict, step, and every server's disk, role, commit index, state
    machine and configuration, against the host oracle's rows; the
    counts against the device's."""
    _the_plain_reference_agrees(swept, lane)


def test_the_long_seeds_hold_both_codes(swept_long):
    assert sorted(np.asarray(swept_long["state"].violation).tolist()) == [
        1, 2, 2, 2,
    ]


@pytest.mark.parametrize("lane", range(len(LONG_SEEDS)))
def test_device_and_host_agree_on_a_violating_lane(swept_long, lane):
    _device_and_host_agree(swept_long, lane)


@pytest.mark.parametrize("lane", range(len(LONG_SEEDS)))
def test_the_plain_reference_agrees_on_a_violating_lane(swept_long, lane):
    _the_plain_reference_agrees(swept_long, lane)


def test_the_reference_by_the_fixed_protocol_parts_from_the_program(swept_long):
    """The control: replayed by the figure's own step 8, a lane in which
    the bug fired is refused or judged otherwise."""
    parted = 0
    for lane in range(len(LONG_SEEDS)):
        single, host, _rows = lifted(swept_long, lane)
        try:
            ref = reference.replay(
                7, L, EVERY, np.asarray(single.trace).tolist(),
                int(single.trace_len), bug=None,
            )
        except reference.Diverged:
            parted += 1
            continue
        parted += ref.code != host.violation.code
    assert parted == len(LONG_SEEDS)


def test_the_reference_is_plain():
    with open(reference.__file__, encoding="utf-8") as f:
        source = f.read()
    code = source.split('"""')[2]
    assert "import jax" not in code and "demi_tpu" not in code
    assert "numpy" not in code


# -- (d) the operator --------------------------------------------------------

def operator(seed=0):
    app = rr.make_raft_reconfig_app(7, log_cap=L)
    return app, rr.ReconfigOperator(app), random.Random(seed)


def test_the_operator_is_deterministic_in_its_seed():
    def rows(seed):
        app, gen, rng = operator(seed)
        out = []
        for k in range(60):
            if k == 20:
                gen.note_fault(OP_HARDKILL, "g2")
            if k == 40:
                gen.note_fault(OP_START, "g2")
            out.append(gen.generate_row(rng, app.actor_names()))
        return out

    assert rows(5) == rows(5) and rows(5) != rows(6)
    kinds = {row[1][0] for row in rows(5)}
    assert kinds == {rr.T_CLIENT, rr.T_ADMIN}
    assert all(len(row[1]) == 18 for row in rows(5))
    # a reset starts the program over
    app, gen, rng = operator(5)
    first = [gen.generate_row(rng, ()) for _ in range(10)]
    gen.reset()
    rng = random.Random(5)
    assert [gen.generate_row(rng, ()) for _ in range(10)] == first


def test_the_operator_replaces_the_member_it_knows_down():
    app, gen, rng = operator(1)
    gen.note_fault(OP_HARDKILL, "g3")
    admin = []
    while len(admin) < 2:
        name, msg = gen.generate_row(rng, ())
        assert name != "g3"                   # nothing goes to a dead server
        if msg[0] == rr.T_ADMIN:
            admin.append(msg[:3])
    assert admin[0] == (rr.T_ADMIN, rr.REMOVE, 3)          # member remove
    assert admin[1][:2] == (rr.T_ADMIN, rr.ADD) and admin[1][2] in (5, 6)
    assert "g3" not in gen.belief and len(gen.belief) == 5
    # with all members up, the one to go is drawn; a restarted server
    # that is no member is a spare like any other
    gen.note_fault(OP_START, "g3")
    while True:
        _name, msg = gen.generate_row(rng, ())
        if msg[0] == rr.T_ADMIN:
            break
    assert msg[1] == rr.REMOVE and "g%d" % msg[2] not in gen.belief
    assert len(gen.belief) == 4
    gen.note_fault(OP_HARDKILL, "g0")
    for name in list(gen.up):
        gen.note_fault(OP_HARDKILL, name)
    assert gen.generate_row(rng, ()) is None  # nobody to speak to


def test_the_fuzzer_tells_the_operator_of_every_fault():
    app, cfg, fuzzer = build_workload(workload())
    assert isinstance(fuzzer.message_gen, rr.ReconfigOperator)
    seen = []
    real = fuzzer.message_gen.note_fault
    fuzzer._note_fault = lambda op, name: (seen.append((op, name)), real(op, name))
    program = fuzzer.generate_fuzz_test(seed=11)
    kinds = [type(e).__name__ for e in program]
    assert len(seen) == kinds.count("HardKill") + kinds.count("Start") - 7 > 0
