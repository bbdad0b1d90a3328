"""Kafka's partition replication (``apps/kafka.py``) on the normal path, at
small size on the CPU (the deployment is ``benchmarks/configs/
kafka5-acks-all.json`` cut to 4 brokers and a controller, 5 partitions,
``log_cap`` 8, 256 deliveries and 48 fuzzed events): the sources' rules on
the host tier, one named delivery at a time (election from the ISR, the
fetch that carries the high watermark one round trip late, ISR shrink and
expand by compare-and-set, fencing by epoch, KIP-101's scenario 1, a
scenario-2 divergence and KIP-279's corner, each with its bug and without);
``DSLApp.durable`` at the row's scale on both tiers; device lane, host
oracle and the plain reference (``benchmarks/lib/kafka_reference.py``:
classes, lists, sets, no JAX) agreeing lane for lane on fuzzed
crash-recovery-and-partition schedules over FIFO links; the operator; the
actor the fault program may cut off and may not kill. The normal path (the
CLI's verbs, the producers, the fixed protocol's clean lanes) is
``test_kafka_cli.py``."""

import hashlib
import importlib.util
import json
import os
import random
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from demi_tpu.apps import kafka as kf
from demi_tpu.apps.common import make_host_invariant
from demi_tpu.config import SchedulerConfig
from demi_tpu.device.continuous import ContinuousSweepDriver
from demi_tpu.device.core import ST_DONE, ST_VIOLATION
from demi_tpu.device.encoding import (
    device_trace_to_guide, lower_program, stack_programs,
)
from demi_tpu.device.explore import make_single_lane_trace_kernel
from demi_tpu.external_events import (
    OP_HARDKILL, OP_START, HardKill, Kill, Partition,
)
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


reference = _load("benchmarks/lib/kafka_reference.py", "kafka_reference")

N, L = 5, 8     # 4 brokers and the controller: 5 partitions
CTRL = N - 1
REPLICAS, HELD = kf.assignment(N - 1, N)
LAY = kf.state_layout(N, L)
SLOTS = LAY["slots"][0]


def workload(bug=None, **over):
    return {
        "app": "kafka", "nodes": N, "bug": bug,
        "log_cap": L, "seed": 0, "num_events": 48, "max_messages": 256,
        "pool": 128, "timer_weight": 0.3, "send_weight": 0.4,
        "wait_weight": 0.28, "wait_budget": [1, 40], "hard_kill_weight": 0.08,
        "restart_weight": 0.12, "partition_weight": 0.04, "kill_weight": 0.0,
        "max_kills": 4, **over,
    }


def lane_key(seed):
    return jax.random.fold_in(jax.random.PRNGKey(0), seed)


def mask(*brokers):
    return sum(1 << b for b in brokers)


def field(row, name, b=None, p=None):
    """A word of a row: a slot field of partition ``p`` at broker ``b``
    (an array for a log, a cache or F_LEO), or a controller's field."""
    start, length = LAY[name]
    words = np.asarray(row[start : start + length])
    if b is None:
        return words.tolist() if length > 1 else int(words[0])
    rows = words.reshape(SLOTS, -1)[HELD[b].index(p)]
    return rows.tolist() if rows.shape[0] > 1 else int(rows[0])


# -- (a) the sources' rules, one delivery at a time, on the host tier -------

class Cluster:
    """The host tier's actor system with its mail held here in the order
    it was sent, so that a test delivers what it names: a non-timer only
    as the oldest of its (sender, receiver) channel. The invariant is
    judged after every delivery (``codes``)."""

    def __init__(self, bug=None):
        self.app = kf.make_kafka_app(N, log_cap=L, bug=bug)
        self.system = ControlledActorSystem()
        self.mail = []
        self.codes = []
        self.judge = jax.jit(self.app.invariant)
        self.down = set()
        self.start(CTRL)
        for b in range(N - 1):
            self.start(b)

    def name(self, i):
        return self.app.actor_name(i)

    def start(self, i):
        self.down.discard(i)
        self.mail += self.system.spawn(
            self.name(i), dsl_actor_factory(self.app, i)
        )

    def hard_kill(self, i):
        self.down.add(i)
        self.system.hard_kill(self.name(i))
        self.mail = [
            e for e in self.mail if self.name(i) not in (e.snd, e.rcv)
        ]

    def find(self, tag, dst=None, src=None):
        return [
            e for e in self.mail if e.msg[0] == tag
            and (dst is None or e.rcv == self.name(dst))
            and (src is None or e.snd == self.name(src))
        ]

    def code(self):
        states = np.zeros((N, self.app.state_width), np.int32)
        alive = np.zeros(N, bool)
        for i in range(N):
            actor = self.system.actors.get(self.name(i))
            if actor is not None:
                states[i], alive[i] = actor.state, True
        return int(self.judge(states, alive))

    def take(self, entry):
        if not entry.is_timer:
            channel = [
                e for e in self.mail if not e.is_timer
                and (e.snd, e.rcv) == (entry.snd, entry.rcv)
            ]
            assert channel[0] is entry, "not the head of its channel"
        self.mail.remove(entry)
        self.mail += self.system.deliver(entry)
        self.codes.append(self.code())

    def deliver(self, tag, dst=None, src=None):
        self.take(self.find(tag, dst, src)[0])

    def lose(self, tag, dst=None, src=None):
        """A cut link: the messages are gone."""
        lost = self.find(tag, dst, src)
        assert lost
        for entry in lost:
            self.mail.remove(entry)

    def drain(self):
        """Every message, oldest first, until only timers are left."""
        while True:
            due = [e for e in self.mail if not e.is_timer]
            if not due:
                return
            self.take(due[0])

    def tick(self, tag, b):
        self.deliver(tag, b)
        self.drain()

    def produce(self, b, p, value):
        msg = (kf.T_PRODUCE, p, value, 0) + (0,) * (self.app.msg_width - 4)
        self.mail.append(self.system.inject(self.name(b), msg))
        self.drain()

    def fetch(self, *brokers):
        for b in brokers:
            self.tick(kf.T_FETCH, b)

    def session(self, silent=()):
        """Every broker that is up and not ``silent`` heartbeats; then the
        controller's session timer."""
        for b in range(N - 1):
            if b not in self.down and b not in silent:
                self.tick(kf.T_HEARTBEAT, b)
        self.deliver(kf.T_SESSION, CTRL)

    def row(self, i):
        return self.system.actors[self.name(i)].state

    def get(self, b, p, name):
        return field(self.row(b), name, b, p)

    def log(self, b, p):
        held = self.get(b, p, "LEO")
        return list(zip(
            self.get(b, p, "LOG_V")[:held], self.get(b, p, "LOG_E")[:held]
        ))

    def cache(self, b, p):
        held = self.get(b, p, "EP_LEN")
        return list(zip(
            self.get(b, p, "EP_E")[:held], self.get(b, p, "EP_S")[:held]
        ))

    def ctrl(self, name):
        return field(self.row(CTRL), name)


def booted(bug=None):
    """Brokers 0..3 register in that order: broker 1 leads partition 1,
    broker 0 the other four, all in epoch 1 with their full ISRs."""
    c = Cluster(bug)
    c.drain()
    return c


def committed(c, values, p=0):
    """``values`` produced at p's leader and fetched by both followers
    until all three hold them below their high watermarks."""
    leader = c.ctrl("C_LEADER")[p]
    others = [b for b in REPLICAS[p] if b != leader]
    for value in values:
        c.produce(leader, p, value)
    for _ in range(1 + (len(values) + kf.RECORDS - 1) // kf.RECORDS):
        c.fetch(*others)
    c.fetch(*others)


def expire(c, *brokers):
    """The brokers' sessions run out: silent through SESSION_MISSES session
    timers on end. What the last one sent is still in the mail."""
    for _ in range(kf.SESSION_MISSES + 1):   # the first may hold them heard
        if any(c.ctrl("LIVE") >> b & 1 for b in brokers):
            c.session(silent=brokers)
    assert not any(c.ctrl("LIVE") >> b & 1 for b in brokers)


def lag_out(c, leader, *fetching):
    """LAG_MISSES ISR timers at ``leader`` during which only ``fetching``
    fetch; the last one's proposal is still in the mail."""
    for _ in range(kf.LAG_MISSES):
        c.drain()
        c.fetch(*fetching)
        c.deliver(kf.T_ISR, leader)


def test_the_first_replica_to_register_leads_and_followers_fetch():
    c = booted()
    assert c.ctrl("C_LEADER") == [0, 1, 0, 0, 0]
    assert c.ctrl("C_EPOCH") == [1] * 5 and c.ctrl("C_ZKV") == [1] * 5
    assert c.ctrl("C_ISR") == [
        mask(0, 1, 2), mask(1, 2, 3), mask(0, 2, 3), mask(0, 1, 3),
        mask(0, 1, 2),
    ]
    assert c.ctrl("LIVE") == mask(0, 1, 2, 3)
    assert [c.get(b, 0, "ROLE") for b in (0, 1, 2)] == [
        kf.LEADER, kf.FOLLOWER, kf.FOLLOWER,
    ]
    assert c.cache(0, 0) == [(1, 0)] and c.get(0, 0, "ELECTED") == 1
    c.produce(0, 0, 41)
    assert c.log(0, 0) == [(41, 1)] and c.get(0, 0, "HW") == 0
    c.fetch(1, 2)              # the record; the leader learns offset 0
    assert c.log(1, 0) == c.log(2, 0) == [(41, 1)]
    assert [c.get(b, 0, "HW") for b in (0, 1, 2)] == [0, 0, 0]
    c.fetch(1)
    assert c.get(0, 0, "HW") == 0       # broker 2 still bounds it
    c.fetch(2)                 # the leader learns offset 1 from both
    assert c.get(0, 0, "HW") == 1 and c.get(0, 0, "ACKED") == 1
    assert (c.get(0, 0, "EXPOSED"), c.get(0, 0, "EXPOSED_AT")) == (1, 1)
    # KIP-101, Motivation: a follower's HW is one round trip behind
    assert (c.get(1, 0, "HW"), c.get(2, 0, "HW")) == (0, 1)
    c.fetch(1)
    assert c.get(1, 0, "HW") == 1
    assert not any(c.codes)


def test_a_broker_that_is_not_the_leader_forwards_once():
    c = booted()
    c.produce(1, 0, 7)          # broker 1 follows broker 0 on partition 0
    assert c.log(0, 0) == [(7, 1)] and c.log(1, 0) == []
    c.produce(3, 0, 8)          # broker 3 does not replicate it: dropped
    assert c.log(0, 0) == [(7, 1)]
    assert not any(c.codes)


def test_a_fetch_carries_three_records_and_a_full_log_rejects():
    c = booted()
    for value in range(1, L + 2):
        c.produce(0, 0, value)
    assert c.get(0, 0, "LEO") == L and c.get(0, 0, "REJECTED") == 1
    c.fetch(1)
    assert c.get(1, 0, "LEO") == kf.RECORDS
    c.fetch(1)
    c.fetch(1)
    assert c.log(1, 0) == c.log(0, 0)
    assert not any(c.codes)


def test_the_isr_shrinks_and_grows_by_compare_and_set():
    c = booted()
    committed(c, [1])
    c.tick(kf.T_ISR, 0)         # clears CAUGHT (all had caught up)
    lag_out(c, 0, 1)            # broker 2 does not fetch through 8 of them
    assert c.get(0, 0, "LAG")[:3] == [9, 0, 8] and c.get(0, 0, "ISR") == mask(0, 1, 2)
    c.drain()
    assert c.get(0, 0, "ISR") == mask(0, 1) and c.get(0, 0, "ISR_SHRUNK") == 1
    assert c.ctrl("C_ISR")[0] == mask(0, 1) and c.ctrl("C_ZKV")[0] == 2
    assert c.ctrl("C_EPOCH")[0] == 1   # the ISR's version moves, not the epoch
    c.produce(0, 0, 2)
    c.fetch(1)
    c.fetch(1)
    assert c.get(0, 0, "HW") == 2      # without broker 2
    c.fetch(2)                  # offset 1 < HW: not yet
    assert c.get(0, 0, "PEND_ADD") == 0
    c.fetch(2)                  # offset 2 >= HW: proposed, and granted
    assert c.get(0, 0, "ISR") == mask(0, 1, 2) and c.get(0, 0, "ISR_GROWN") == 1
    assert c.ctrl("C_ZKV")[0] == 3
    assert not any(c.codes)


def test_a_stale_version_is_refused_and_the_maximal_isr_bounds_the_hw():
    c = booted()
    committed(c, [1])
    c.tick(kf.T_ISR, 0)
    lag_out(c, 0, 1)            # proposes ISR - {2}: in flight
    assert c.get(0, 0, "PEND_DEL") == mask(2)
    held = c.find(kf.T_ALTER_ISR)[0]
    c.mail.remove(held)         # slow on its way to the controller
    c.produce(0, 0, 2)
    c.fetch(1)
    c.fetch(1)
    assert c.get(0, 0, "HW") == 1      # KIP-497: broker 2 still bounds it
    # meanwhile the znode's version moved on (by hand: no other writer of
    # partition 0's state is at large in this script)
    znode = np.array(c.row(CTRL))
    znode[LAY["C_ZKV"][0]] += 1
    c.system.actors[c.name(CTRL)].state = znode
    c.mail.append(held)
    c.drain()
    assert c.get(0, 0, "ISR") == mask(0, 1, 2) and c.get(0, 0, "PEND_DEL") == 0
    assert c.get(0, 0, "ISR_SHRUNK") == 0
    assert not any(c.codes)


def test_a_zombie_leader_is_fenced_by_the_epoch():
    c = booted()
    committed(c, [1])
    expire(c, 0)
    assert c.ctrl("C_LEADER")[0] == 1 and c.ctrl("C_EPOCH")[0] == 2
    assert c.ctrl("C_ISR")[0] == mask(1, 2)
    c.lose(kf.T_LEADER_AND_ISR, dst=2)     # broker 2 hears nothing yet
    c.drain()
    assert c.get(1, 0, "ROLE") == kf.LEADER and c.get(0, 0, "ROLE") == kf.LEADER
    c.produce(0, 0, 2)          # the zombie appends ...
    c.fetch(2)                  # ... and broker 2, in its epoch, takes it
    assert c.log(2, 0)[-1] == (2, 1)
    assert c.get(0, 0, "HW") == 1          # broker 1 fetches from it no more
    before = c.get(0, 0, "FENCED")
    c.tick(kf.T_HEARTBEAT, 0)   # expired: the heartbeat registers it again
    assert c.get(0, 0, "ROLE") == kf.FOLLOWER and c.get(0, 0, "EPOCH") == 2
    assert c.log(0, 0) == [(1, 1)]         # KIP-101: cut to where epoch 2 began
    assert c.get(0, 0, "TRUNCATED") == 1
    c.fetch(2)                  # still in epoch 1, to a broker no leader now
    assert c.get(0, 0, "FENCED") == before + 1
    assert not any(c.codes)


def test_unclean_election_is_off():
    c = booted()
    committed(c, [1])
    c.tick(kf.T_ISR, 0)
    lag_out(c, 0)               # nobody fetches: ISR = {0}
    c.drain()
    assert c.ctrl("C_ISR")[0] == mask(0)
    c.hard_kill(0)
    expire(c, 0)
    c.drain()
    assert c.ctrl("C_LEADER")[0] == -1 and c.ctrl("C_ISR")[0] == mask(0)
    assert c.get(1, 0, "LEADER") == -1 and c.get(1, 0, "ROLE") == kf.FOLLOWER
    c.start(0)
    c.drain()
    assert c.ctrl("C_LEADER")[0] == 0 and c.get(0, 0, "ROLE") == kf.LEADER
    assert c.log(0, 0) == [(1, 1)] and c.get(0, 0, "EPOCH") == 3
    assert not any(c.codes)


def scenario_1(bug):
    """KIP-101, Motivation, scenario 1: the leader acknowledges a record
    that both followers hold above their high watermarks; a follower that
    changes leader cuts its log to its high watermark; the new leader
    fails before that follower has fetched again, and the follower leads."""
    c = booted(bug)
    committed(c, [1])
    c.produce(0, 0, 2)
    c.fetch(1, 2)               # both hold record 2; HW is 1 everywhere
    c.fetch(1)
    c.deliver(kf.T_FETCH, 2)
    c.deliver(kf.T_FETCH_REQ, 0, 2)        # the leader's HW passes record 2
    assert (c.get(0, 0, "HW"), c.get(0, 0, "EXPOSED")) == (2, 2)
    assert (c.get(1, 0, "HW"), c.get(2, 0, "HW")) == (1, 1)
    c.hard_kill(0)
    expire(c, 0)
    c.drain()
    assert c.ctrl("C_LEADER")[0] == 1 and c.ctrl("C_EPOCH")[0] == 2
    assert c.get(1, 0, "LEO") == 2
    c.hard_kill(1)              # before broker 2 has fetched from it
    expire(c, 1)
    c.drain()
    assert c.ctrl("C_LEADER")[0] == 2 and c.get(2, 0, "ROLE") == kf.LEADER
    c.start(0)                  # the old leader remembers what it exposed
    c.drain()
    return c


def test_scenario_1_loses_an_acknowledged_record_as_shipped():
    c = scenario_1("truncate_to_hw")
    assert c.log(2, 0) == [(1, 1)] and c.get(2, 0, "TRUNCATED") == 1
    assert c.codes[-1] == 1 and 2 not in c.codes


def test_scenario_1_keeps_it_with_leader_epochs():
    c = scenario_1(None)
    assert c.log(2, 0) == [(1, 1), (2, 1)] and c.get(2, 0, "TRUNCATED") == 0
    assert c.cache(2, 0) == [(1, 0), (3, 2)]
    c.fetch(0)
    assert c.log(0, 0) == c.log(2, 0)
    assert not any(c.codes)


def scenario_2(bug):
    """The divergence of KIP-101's scenario 2, as these rules reach it: a
    follower holds its leader's unacknowledged record, misses the epoch in
    which that leader was deposed and rewrote the offset, and meets the
    same leader again one epoch on."""
    c = booted(bug)
    committed(c, [1])
    c.produce(0, 0, 2)
    c.fetch(2)                  # broker 2 holds record 2, unacknowledged
    assert c.log(2, 0) == [(1, 1), (2, 1)] and c.get(0, 0, "HW") == 1
    expire(c, 0)                # a false expiry: broker 1 leads epoch 2
    c.lose(kf.T_LEADER_AND_ISR, dst=2)     # a cut link
    c.drain()
    c.tick(kf.T_HEARTBEAT, 0)   # broker 0 registers again, as a follower
    assert c.log(0, 0) == [(1, 1)] and c.get(0, 0, "LEADER") == 1
    c.mail = [e for e in c.mail if e.is_timer or c.name(2) not in (e.snd, e.rcv)]
    c.fetch(0)                  # ... and is proposed for the ISR
    c.fetch(0)
    assert c.ctrl("C_ISR")[0] == mask(0, 1, 2)
    c.produce(1, 0, 3)          # offset 1 again, in epoch 2
    c.fetch(0)
    assert c.log(0, 0) == [(1, 1), (3, 2)]
    expire(c, 1, 2)             # brokers 1 and 2 expire: broker 0, epoch 3
    assert c.ctrl("C_LEADER")[0] == 0 and c.ctrl("C_EPOCH")[0] == 3
    c.drain()
    c.tick(kf.T_HEARTBEAT, 2)   # broker 2 is back, still in epoch 1
    assert c.get(2, 0, "EPOCH") == 3 and c.get(2, 0, "LEADER") == 0
    c.fetch(2)
    c.fetch(2)
    c.fetch(2)
    return c


def test_scenario_2_diverges_below_the_high_watermark_as_shipped():
    c = scenario_2("truncate_to_hw")
    assert c.log(2, 0) == [(1, 1), (2, 1)] and c.log(0, 0) == [(1, 1), (3, 2)]
    assert c.get(2, 0, "HW") == 2 and c.get(0, 0, "HW") == 2
    assert c.codes[-1] == 2 and 1 not in c.codes


def test_scenario_2_is_cut_by_the_leader_epoch():
    c = scenario_2(None)
    assert c.log(2, 0) == c.log(0, 0) == [(1, 1), (3, 2)]
    assert c.get(2, 0, "TRUNCATED") == 1
    assert not any(c.codes)


def kip_279(bug):
    """KIP-279: a fast fail-over leaves a follower with records of an epoch
    its next leader never saw."""
    c = booted(bug)
    committed(c, [1])
    expire(c, 0)                # broker 1 leads epoch 2
    c.drain()
    c.tick(kf.T_HEARTBEAT, 0)   # broker 0 is back as a follower
    c.fetch(0)
    c.fetch(0)
    assert c.ctrl("C_ISR")[0] == mask(0, 1, 2)
    c.produce(1, 0, 2)          # record 2 in epoch 2: nobody fetches it
    expire(c, 1)                # broker 0 leads epoch 3, knowing epochs 1, 3
    c.drain()
    assert c.ctrl("C_LEADER")[0] == 0 and c.cache(0, 0) == [(1, 0), (3, 1)]
    c.produce(0, 0, 3)
    c.fetch(2)
    c.fetch(2)
    assert c.get(0, 0, "HW") == 2
    c.tick(kf.T_HEARTBEAT, 1)   # broker 1 is back with its epoch-2 record
    c.fetch(1)
    c.fetch(1)
    return c


def test_kip_279_diverges_with_the_first_reply():
    c = kip_279("epoch_unknown_replies_leo")
    assert c.log(1, 0) == [(1, 1), (2, 2)] and c.log(0, 0) == [(1, 1), (3, 3)]
    assert c.codes[-1] == 2 and 1 not in c.codes


def test_kip_279_is_cut_by_the_reply_that_names_the_epoch_found():
    c = kip_279(None)
    assert c.log(1, 0) == c.log(0, 0) == [(1, 1), (3, 3)]
    assert c.get(1, 0, "TRUNCATED") == 1
    assert not any(c.codes)


def test_the_epoch_cache_drops_its_oldest_entry_and_counts():
    c = booted()
    for epoch in range(2, kf.EPOCHS + 3):
        c.produce(0, 0, epoch)
        msg = (kf.T_LEADER_AND_ISR, 0, 0, epoch, mask(0, 1, 2), epoch)
        msg += (0,) * (c.app.msg_width - len(msg))
        c.mail.append(c.system.inject(c.name(0), msg))
        c.drain()
    assert c.get(0, 0, "EP_LEN") == kf.EPOCHS
    # epoch 9 found the cache full (epoch 1 went); epoch 10 found the log
    # full too, so epoch 9's entry, which held no record, went first
    assert c.get(0, 0, "EPOCH_OVERFLOW") == 1
    assert c.cache(0, 0)[0] == (2, 1) and c.cache(0, 0)[-2:] == [(8, 7), (10, 8)]


def test_the_shapes_are_the_issues():
    app = kf.make_kafka_app(6, log_cap=24, bug="truncate_to_hw")
    lay = kf.state_layout(6, 24)
    assert (app.msg_width, app.max_outbox, app.state_width) == (50, 19, 421)
    assert lay["slots"][0] == 4 and lay["width"][0] == 421
    assert len(app.durable) == 308 and app.spawn_count == kf.RESTORES
    assert not {
        word for name in ("HW", "ROLE", "EPOCH", "ISR", "F_LEO", "BOOTED")
        for word in range(lay[name][0], lay[name][0] + lay[name][1])
    } & set(app.durable)
    assert app.timer_tags == (1, 2, 3, 4, 5) and len(app.tag_names) == 16
    assert [name for name, _ in app.progress] == [
        "committed", "elections", "isr_changes", "truncated", "fenced",
        "restores",
    ]
    assert app.channels == "fifo" and app.invariant_at == "delivery"
    assert app.unkillable == (5,)
    replicas, held = kf.assignment(5, 6)
    assert replicas[4] == [4, 0, 1] and [len(h) for h in held] == [4, 4, 4, 3, 3]
    for bad in (
        dict(bug="no_such_bug"), dict(num_actors=3), dict(num_actors=10),
        dict(log_cap=2),
    ):
        with pytest.raises(ValueError):
            kf.make_kafka_app(**{"num_actors": 6, "log_cap": 24, **bad})


def test_no_branch_sends_more_rows_than_the_outbox_holds():
    app = kf.make_kafka_app(N, log_cap=L)
    handler = jax.jit(app.handler)
    for actor in (0, CTRL):
        state = jnp.asarray(app.init_state(actor))
        for tag in range(1, kf.NUM_TAGS + 1):
            msg = jnp.zeros(app.msg_width, jnp.int32).at[0].set(tag)
            new, out = handler(jnp.int32(actor), state, jnp.int32(1), msg)
            assert out.shape == (app.max_outbox, 2 + app.msg_width), tag
            assert new.shape == state.shape and new.dtype == jnp.int32


# -- (b) DSLApp.durable at the row's scale, on both tiers -------------------

def restart_is_from_disk(app, before, after, broker):
    """``after`` is ``before`` in every durable word, the init row in
    every other, and one more life."""
    durable = np.zeros(app.state_width, bool)
    durable[list(app.durable)] = True
    fresh = app.init_state(broker)
    fresh[kf.RESTORES] = before[kf.RESTORES] + 1
    np.testing.assert_array_equal(after[durable], before[durable])
    np.testing.assert_array_equal(after[~durable], fresh[~durable])
    assert durable.sum() == len(app.durable)
    # what the test is for: the disk held something, memory too
    start, length = LAY["LEO"]
    assert before[start : start + length].any()
    start, length = LAY["HW"]
    assert before[start : start + length].any()
    assert not after[start : start + length].any()


def test_a_restart_keeps_the_durable_row_on_the_host_tier():
    c = booted()
    committed(c, [1, 2])
    c.tick(kf.T_CKPT, 1)
    before = c.row(1).copy()
    assert field(before, "HW_CKPT", 1, 0) == 2
    c.hard_kill(1)
    c.start(1)
    restart_is_from_disk(c.app, before, c.row(1), 1)
    # ... and its first delivery takes the checkpoint for its HW
    c.deliver(kf.T_CKPT, 1)
    assert c.get(1, 0, "HW") == 2 and c.log(1, 0) == [(1, 1), (2, 1)]
    assert c.get(1, 0, "ROLE") == kf.NONE and c.get(1, 0, "EPOCH") == -1
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


def test_a_restart_keeps_the_durable_row_on_the_device(swept):
    """``core.external_effects``, the step kernel's own, on the final
    state of a fuzzed lane: a HardKill, then a Start."""
    from demi_tpu.device import core
    from demi_tpu.device.explore import _precomputed

    app, cfg = swept["app"], swept["cfg"]
    rows = np.asarray(swept["state"].actor_state)
    leo = slice(LAY["LEO"][0], LAY["LEO"][0] + SLOTS)
    hw = slice(LAY["HW"][0], LAY["HW"][0] + SLOTS)
    found = [
        (lane, b)
        for lane in range(rows.shape[0]) for b in range(N - 1)
        if rows[lane, b, leo].any() and rows[lane, b, hw].any()
        and swept["state"].status[lane] == ST_DONE
    ]
    assert len(found) >= 3
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

    for lane, b in found[:: max(1, len(found) // 6)]:
        state = jax.tree_util.tree_map(
            lambda x: jnp.asarray(x[lane]), swept["state"]
        )
        after = np.asarray(restart(state, jnp.int32(b)))
        restart_is_from_disk(app, rows[lane, b], after[b], b)
        others = [i for i in range(N) if i != b]
        np.testing.assert_array_equal(after[others], rows[lane, others])


# -- (c) device, host oracle and the plain reference, lane for lane ---------

BUG = "truncate_to_hw"
SEEDS = list(range(64))
# ... and four of the first 512 on which the bug breaks the invariant in
# 1,024 deliveries (96 events, pool 256; none does in 256).
LONG = dict(max_messages=1024, num_events=96, pool=256)
LONG_SEEDS = [1, 7, 113, 193]


def _swept(seeds, bug=BUG, **over):
    """The seeds run to their end through the continuous driver's own
    kernels, and what the per-lane lifts need."""
    app, cfg, fuzzer = build_workload(workload(bug, **over))
    gen = lambda s: fuzzer.generate_fuzz_test(seed=s)  # noqa: E731
    progs = stack_programs([lower_program(app, cfg, gen(s)) for s in seeds])
    keys = jax.vmap(lane_key)(np.asarray(seeds, np.uint32))
    state = run_lanes(app, cfg, gen, progs, keys)
    return {
        "app": app, "cfg": cfg, "progs": progs, "keys": keys, "state": state,
        "kernel": make_single_lane_trace_kernel(app, cfg), "lifted": {},
        "bug": bug,
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
    rows = np.asarray(swept["state"].actor_state)[:, : N - 1]

    def total(name):
        start, length = LAY[name]
        return int(rows[:, :, start : start + length].sum())

    assert set(np.asarray(swept["state"].status).tolist()) <= {
        ST_DONE, ST_VIOLATION,
    }
    assert total("ELECTED") >= 3 * len(SEEDS)
    assert total("ISR_SHRUNK") + total("ISR_GROWN") >= len(SEEDS)
    assert total("TRUNCATED") >= 4 and total("FENCED") >= len(SEEDS)
    assert total("ACKED") >= 8
    assert (rows[:, :, kf.RESTORES] > 1).sum() >= len(SEEDS)
    assert total("EPOCH_OVERFLOW") == 0


def _device_and_host_agree(swept, lane):
    state = swept["state"]
    single, host, rows = lifted(swept, lane)
    code = int(state.violation[lane])
    host_code = host.violation.code if host.violation is not None else 0
    assert int(single.violation) == code == host_code
    assert int(single.sched_hash) == int(state.sched_hash[lane])
    assert int(single.deliveries) == int(state.deliveries[lane]) == host.deliveries
    assert rows, "no actor is left on the host"
    for i, row in rows.items():
        np.testing.assert_array_equal(row, state.actor_state[lane][i], str(i))


def compare_with_reference(ref, rows, app_rows=None):
    """Every broker's logs, epoch caches, high watermarks, roles, epochs,
    ISRs and counts, and the controller's table, against the rows."""
    for i, row in rows.items():
        if i == CTRL:
            c = ref.controller
            assert [-1 if x is None else x for x in c.leader] == field(row, "C_LEADER")
            assert c.epoch == field(row, "C_EPOCH")
            assert [reference.mask_of(x) for x in c.isr] == field(row, "C_ISR")
            assert c.version == field(row, "C_ZKV")
            assert reference.mask_of(c.live) == field(row, "LIVE")
            assert reference.mask_of(c.heard) == field(row, "HEARD")
            assert [c.missed.get(b, 0) for b in range(N - 1)] == field(row, "C_MISSED")
            continue
        assert ref.spawns[i] == int(row[kf.RESTORES]), i
        fresh = int(row[kf.BOOTED]) == 0
        for p in HELD[i]:
            r = ref.brokers[i][p]
            get = lambda name: field(row, name, i, p)  # noqa: E731
            where = (i, p)
            held = len(r.log)
            assert held == get("LEO"), where
            assert [tuple(x) for x in r.log] == list(zip(
                get("LOG_V")[:held], get("LOG_E")[:held]
            )), where
            assert not any(get("LOG_V")[held:]), where
            assert r.cache == list(zip(
                get("EP_E")[: len(r.cache)], get("EP_S")[: len(r.cache)]
            )) and len(r.cache) == get("EP_LEN"), where
            assert r.checkpoint == get("HW_CKPT"), where
            # a restarted broker takes its checkpoint at its first delivery
            assert r.hw == (min(get("HW_CKPT"), held) if fresh else get("HW")), where
            assert (r.exposed, r.exposed_at) == (
                get("EXPOSED"), get("EXPOSED_AT")
            ), where
            assert (r.role, r.epoch) == (get("ROLE"), get("EPOCH")), where
            assert (-1 if r.leader is None else r.leader) == get("LEADER"), where
            if r.role == reference.FOLLOWER:
                assert int(r.truncating) == get("FSTATE"), where
            if r.role == reference.LEADER:
                assert (reference.mask_of(r.isr), r.version) == (
                    get("ISR"), get("ZKV")
                ), where
                assert (
                    reference.mask_of(r.adding), reference.mask_of(r.removing),
                    reference.mask_of(r.caught),
                ) == (get("PEND_ADD"), get("PEND_DEL"), get("CAUGHT")), where
                assert [
                    0 if b == i else r.fetched.get(b, 0) for b in range(N - 1)
                ] == [0 if b == i else x for b, x in enumerate(get("F_LEO"))], where
            assert [r.lag.get(b, 0) for b in range(N - 1)] == get("LAG"), where
            for name in reference.COUNTS:
                assert r.counts[name] == get(name.upper()), (where, name)


def _the_plain_reference_agrees(swept, lane):
    single, host, rows = lifted(swept, lane)
    ref = reference.replay(
        N, L, np.asarray(single.trace).tolist(), int(single.trace_len),
        bug=swept["bug"],
    )
    host_code = host.violation.code if host.violation is not None else 0
    assert ref.code == host_code
    assert ref.step == ref.deliveries == host.deliveries
    compare_with_reference(ref, rows)
    final = swept["state"].actor_state[lane]
    for name, fn in swept["app"].progress:
        assert ref.counts[name] == int(fn(jnp.asarray(final))), name


@pytest.mark.parametrize("lane", range(len(SEEDS)))
def test_device_and_host_agree_on_a_fuzzed_lane(swept, lane):
    """Same code, same delivered sequence, same final rows, word for
    word, under hard kills, restarts and cut links over FIFO channels."""
    _device_and_host_agree(swept, lane)


@pytest.mark.parametrize("lane", range(0, len(SEEDS), 2))
def test_the_plain_reference_agrees_on_a_fuzzed_lane(swept, lane):
    """Verdict, step, and every broker's disk, roles, epochs, ISRs, high
    watermarks and counts, and the controller's table, against the host
    oracle's rows; the progress counts against the device's."""
    _the_plain_reference_agrees(swept, lane)


def test_the_long_seeds_lose_an_acknowledged_record(swept_long):
    assert np.asarray(swept_long["state"].violation).tolist() == [1, 1, 1, 1]


@pytest.mark.parametrize("lane", range(len(LONG_SEEDS)))
def test_device_and_host_agree_on_a_violating_lane(swept_long, lane):
    _device_and_host_agree(swept_long, lane)


@pytest.mark.parametrize("lane", range(len(LONG_SEEDS)))
def test_the_plain_reference_agrees_on_a_violating_lane(swept_long, lane):
    _the_plain_reference_agrees(swept_long, lane)


def test_the_reference_by_the_fixed_protocol_parts_from_the_program(swept_long):
    """The control: replayed by KIP-101's rule, a lane in which the bug
    fired is refused or judged otherwise."""
    parted = 0
    for lane in range(len(LONG_SEEDS)):
        single, host, _rows = lifted(swept_long, lane)
        try:
            ref = reference.replay(
                N, L, np.asarray(single.trace).tolist(),
                int(single.trace_len), bug=None,
            )
        except reference.Diverged:
            parted += 1
            continue
        parted += ref.code != host.violation.code
    assert parted == len(LONG_SEEDS)


def test_the_reference_refuses_a_delivery_out_of_channel_order(swept):
    """The control of the FIFO discipline: two messages of one channel
    swapped in a recorded trace."""
    single, _host, _rows = lifted(swept, 0)
    trace = np.asarray(single.trace).tolist()
    length = int(single.trace_len)
    last = {}
    for i in range(length):
        kind, a, b = trace[i][:3]
        if kind != reference.REC_DELIVERY:
            continue
        j = last.get((a, b))
        if j is not None and trace[j][3:] != trace[i][3:]:
            swapped = list(trace)
            swapped[i], swapped[j] = swapped[j], swapped[i]
            with pytest.raises(reference.Diverged):
                reference.replay(N, L, swapped, length, bug=BUG)
            return
        last[(a, b)] = i
    raise AssertionError("no channel carried two different messages")


def test_the_reference_is_plain():
    with open(reference.__file__, encoding="utf-8") as f:
        source = f.read()
    code = source.split('"""')[2]
    assert "import jax" not in code and "demi_tpu" not in code
    assert "numpy" not in code


# -- (d) the operator and the actor that is cut off and never killed --------

def operator(seed=0):
    app = kf.make_kafka_app(6, log_cap=L)
    return app, kf.ProduceOperator(app), random.Random(seed)


def test_the_operator_is_deterministic_in_its_seed():
    def rows(seed):
        app, gen, rng = operator(seed)
        out = []
        for k in range(60):
            if k == 20:
                gen.note_fault(OP_HARDKILL, "k2")
            if k == 40:
                gen.note_fault(OP_START, "k2")
            out.append(gen.generate_row(rng, app.actor_names()))
        return out

    assert rows(5) == rows(5) and rows(5) != rows(6)
    assert {row[1][0] for row in rows(5)} == {kf.T_PRODUCE}
    assert all(len(row[1]) == 50 for row in rows(5))
    assert [row[1][2] for row in rows(5)] == list(range(1, 61))   # unique values
    assert {row[1][1] for row in rows(5)} == set(range(6))
    app, gen, rng = operator(5)
    first = [gen.generate_row(rng, ()) for _ in range(10)]
    gen.reset()
    rng = random.Random(5)
    assert [gen.generate_row(rng, ()) for _ in range(10)] == first


def test_the_operator_speaks_to_the_first_replica_it_believes_up():
    app, gen, rng = operator(1)
    for _ in range(30):
        name, msg = gen.generate_row(rng, ())
        assert name == "k%d" % (msg[1] % 5)        # the preferred leader
    gen.note_fault(OP_HARDKILL, "k2")
    seen = set()
    for _ in range(60):
        name, msg = gen.generate_row(rng, ())
        assert name != "k2"
        seen.add((msg[1], name))
    assert (2, "k3") in seen and (1, "k1") in seen
    gen.note_fault(OP_HARDKILL, "k3")
    gen.note_fault(OP_HARDKILL, "k4")
    assert all(
        row is None or row[1][1] != 2
        for row in (gen.generate_row(rng, ()) for _ in range(60))
    )                                              # partition 2: nobody up
    gen.note_fault(OP_START, "k2")
    assert ("k2", 2) in {
        (row[0], row[1][1])
        for row in (gen.generate_row(rng, ()) for _ in range(60)) if row
    }


def test_the_fuzzer_tells_the_operator_of_every_fault():
    app, cfg, fuzzer = build_workload(workload())
    assert isinstance(fuzzer.message_gen, kf.ProduceOperator)
    seen = []
    real = fuzzer.message_gen.note_fault
    fuzzer._note_fault = lambda op, name: (seen.append((op, name)), real(op, name))
    program = fuzzer.generate_fuzz_test(seed=11)
    kinds = [type(e).__name__ for e in program]
    assert len(seen) == kinds.count("HardKill") + kinds.count("Start") - N > 0


def test_no_drawn_program_kills_the_controller_and_cuts_reach_it():
    app, cfg, fuzzer = build_workload(workload(
        hard_kill_weight=0.3, kill_weight=0.1, restart_weight=0.2,
        partition_weight=0.2, max_kills=None,
    ))
    ctrl = app.actor_name(CTRL)
    assert fuzzer.unkillable == {ctrl}
    killed, cut = set(), 0
    for seed in range(200):
        for event in fuzzer.generate_fuzz_test(seed=seed):
            if isinstance(event, (Kill, HardKill)):
                killed.add(event.name)
            elif isinstance(event, Partition):
                cut += ctrl in (event.a, event.b)
    assert killed == {app.actor_name(b) for b in range(N - 1)}
    assert cut > 50


def test_an_app_that_names_no_such_actor_draws_the_programs_it_drew():
    """``raft5-nemesis``'s mix, 64 seeds, against a hash recorded at the
    parent commit (18fa505): the kill draw consumes the same numbers."""
    with open(os.path.join(ROOT, "benchmarks/configs/raft5-nemesis.json")) as f:
        app, cfg, fuzzer = build_workload(json.load(f)["workload"])
    assert app.unkillable == () and fuzzer.unkillable == frozenset()
    h = hashlib.sha256()
    for seed in range(64):
        prog = fuzzer.generate_fuzz_test(seed=seed)
        h.update(repr((
            list(prog.kind), list(prog.a), list(prog.b),
            [(i, tuple(p)) for i, p in prog.payloads],
        )).encode())
    assert h.hexdigest() == (
        "af5bf2070848beb2cf1c104a2fe7d773a11933cc101bfdb3634a3c42ddd910b7"
    )
