"""Chain replication with its failure repairs (``apps/chain.py``) on the
normal path, at small size on the CPU (the deployment is
``benchmarks/configs/chain7-fifo.json`` cut to 4 servers and ``log_cap``
8): each repair alone on the host tier, with the master
(``chain_send_generator``) speaking and the mail delivered channel head by
channel head (head, tail, middle with its resend, a new tail with its copy,
a kill during a join, two adjacent kills, a server killed before it handled
anything); device lane, host oracle and the plain reference
(``benchmarks/lib/chain_reference.py``: objects and lists, no JAX) agreeing
lane for lane on fuzzed fail-stop schedules; the unmodified protocol clean
over FIFO channels and broken without them; the seeded bug found by a small
sweep and lifted with its code."""

import dataclasses
import importlib.util
import os
import random
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from demi_tpu.apps import chain
from demi_tpu.apps.common import make_host_invariant
from demi_tpu.config import SchedulerConfig
from demi_tpu.device.continuous import ContinuousSweepDriver
from demi_tpu.device.core import ST_DONE, ST_VIOLATION, insert_form
from demi_tpu.device.encoding import (
    device_trace_to_guide, lower_program, stack_programs,
)
from demi_tpu.device.explore import make_single_lane_trace_kernel
from demi_tpu.external_events import OP_HARDKILL, OP_START
from demi_tpu.parallel.distributed import build_workload
from demi_tpu.parallel.sweep import SweepDriver
from demi_tpu.runner import lift_lane_to_host
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


chain_reference = _load("benchmarks/lib/chain_reference.py", "chain_reference")

T, L = 4, 8


def workload(bug="no_resend", nodes=T, log_cap=L):
    return {
        "app": "chain", "nodes": nodes, "bug": bug, "log_cap": log_cap,
        "seed": 0, "num_events": 40, "max_messages": 512, "pool": 128,
        "timer_weight": 1.0, "send_weight": 0.55, "wait_weight": 0.25,
        "wait_budget": [1, 40], "hard_kill_weight": 0.08,
        "restart_weight": 0.12, "partition_weight": 0.0, "kill_weight": 0.0,
        "max_kills": nodes - 1,
    }


def lane_key(seed):
    return jax.random.fold_in(jax.random.PRNGKey(0), seed)


# -- (a) each repair alone, on the host tier --------------------------------

class Cluster:
    """The host tier's actor system with its mail held here as one FIFO
    queue a (sender, receiver) pair, and the master beside it: a test
    kills, restarts and asks the master for its next send, and the mail is
    delivered head by head in an order a seeded ``rng`` picks."""

    def __init__(self, n=T, bug=None, seed=0):
        self.app = chain.make_chain_app(n, log_cap=L, bug=bug)
        self.master = chain.chain_send_generator(self.app)
        self.system = ControlledActorSystem()
        self.rng = random.Random(seed)
        self.mail = []
        for i in range(n):
            self.mail += self.system.spawn(
                self.name(i), dsl_actor_factory(self.app, i)
            )

    def name(self, i):
        return self.app.actor_name(i)

    def up(self):
        return [
            self.name(i) for i in range(self.app.num_actors)
            if self.name(i) in self.system.actors
        ]

    def hard_kill(self, i):
        self.system.hard_kill(self.name(i))
        self.mail = [
            e for e in self.mail if self.name(i) not in (e.snd, e.rcv)
        ]
        self.master.note_fault(OP_HARDKILL, self.name(i))

    def restart(self, i):
        self.mail += self.system.spawn(
            self.name(i), dsl_actor_factory(self.app, i)
        )
        self.master.note_fault(OP_START, self.name(i))

    def draw(self, want=None):
        """The master's (or the client's) next send, injected."""
        name, msg = self.master.generate_row(self.rng, self.up())
        if want is not None:
            assert msg[0] == want, (self.app.tag_name(msg[0]), msg)
        self.mail.append(self.system.inject(name, msg))
        return msg

    def updates(self, k):
        for _ in range(k):
            self.draw(chain.U_UPDATE)

    def heads(self):
        seen, out = set(), []
        for e in self.mail:
            if (e.snd, e.rcv) not in seen:
                seen.add((e.snd, e.rcv))
                out.append(e)
        return out

    def step(self, entry=None):
        entry = entry or self.rng.choice(self.heads())
        self.mail.remove(entry)
        self.mail += self.system.deliver(entry)

    def drain(self, steps=None):
        while self.mail and steps != 0:
            self.step()
            steps = None if steps is None else steps - 1
            assert self.code() == 0

    def row(self, i):
        return self.system.actors[self.name(i)].state

    def hist(self, i):
        row = self.row(i)
        return row[chain.HIST : chain.HIST + row[chain.OPN]].tolist()

    def members(self):
        return [
            i for i in range(self.app.num_actors)
            if self.name(i) in self.system.actors
            and self.row(i)[chain.AWAKE] == 1
            and self.row(i)[chain.STATUS] == chain.MEMBER
        ]

    def code(self):
        n = self.app.num_actors
        states = np.zeros((n, self.app.state_width), np.int32)
        alive = np.zeros(n, bool)
        for i in range(n):
            if self.name(i) in self.system.actors:
                states[i], alive[i] = self.row(i), True
        return int(self.app.invariant(jnp.asarray(states), jnp.asarray(alive)))

    def settled(self, members, entries):
        """Drained: these servers are the members, every one holds the
        same ``entries`` updates, all of them acknowledged."""
        self.drain()
        assert self.members() == members
        hists = [self.hist(i) for i in members]
        assert all(h == hists[0] for h in hists) and len(hists[0]) == entries
        assert [int(self.row(i)[chain.ACKED]) for i in members] == (
            [entries] * len(members)
        )
        assert self.code() == 0


SEEDS = range(6)


@pytest.mark.parametrize("seed", SEEDS)
def test_updates_travel_down_and_acknowledgements_back(seed):
    c = Cluster(seed=seed)
    c.updates(5)
    c.settled([0, 1, 2, 3], 5)
    assert c.hist(0) == [1, 2, 3, 4, 5]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_head_fails(seed):
    c = Cluster(seed=seed)
    c.updates(4)
    c.drain(steps=seed + 2)
    c.hard_kill(0)
    c.draw(chain.U_BECOME_HEAD)
    c.updates(3)                       # to the new head
    c.drain()
    assert c.row(1)[chain.IS_HEAD] == 1 and c.row(1)[chain.PRED] == chain.NONE
    c.settled([1, 2, 3], len(c.hist(1)))
    assert c.hist(1)[-3:] == [5, 6, 7]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_tail_fails(seed):
    c = Cluster(seed=seed)
    c.updates(4)
    c.drain(steps=seed + 3)
    c.hard_kill(3)
    c.draw(chain.U_BECOME_TAIL)
    c.updates(2)
    c.settled([0, 1, 2], 6)
    assert c.row(2)[chain.IS_TAIL] == 1 and c.row(2)[chain.SUCC] == chain.NONE


@pytest.mark.parametrize("seed", SEEDS)
def test_a_middle_server_fails_and_its_predecessor_resends(seed):
    c = Cluster(seed=seed)
    c.updates(4)
    # all four reach server 1 and none goes further: what 1 sent 2 dies
    # with it
    while c.row(1)[chain.OPN] < 4:
        c.step(next(e for e in c.heads() if e.rcv in (c.name(0), c.name(1))))
    c.hard_kill(2)
    assert c.draw(chain.U_NEWPRED)[1] == 1
    c.updates(2)
    c.settled([0, 1, 3], 6)
    assert c.row(1)[chain.RESENT_ROWS] >= 4
    assert c.row(3)[chain.PRED] == 1 and c.row(1)[chain.SUCC] == 3


def test_with_no_resend_the_gap_breaks_update_propagation():
    c = Cluster(bug="no_resend")
    c.updates(4)
    while c.row(1)[chain.OPN] < 4:
        c.step(next(e for e in c.heads() if e.rcv in (c.name(0), c.name(1))))
    c.step(next(e for e in c.heads() if e.rcv == c.name(2)))   # 2 holds one
    c.step(next(e for e in c.heads() if e.rcv == c.name(3)))   # and 3
    c.hard_kill(2)
    c.draw(chain.U_NEWPRED)
    c.updates(1)
    codes = set()
    while c.mail:
        c.step(c.mail[0])
        codes.add(c.code())
    assert 1 in codes and c.row(1)[chain.RESENT_ROWS] == 0
    assert c.hist(3)[:1] == [1] and 0 in c.hist(3)     # a hole where 2..4 were


@pytest.mark.parametrize("seed", SEEDS)
def test_a_restarted_server_joins_as_the_new_tail(seed):
    c = Cluster(seed=seed)
    c.updates(3)
    c.drain()
    c.hard_kill(1)
    c.restart(1)
    row = c.row(1)
    assert row[chain.SPAWNS] == 2 and row[chain.AWAKE] == 0
    assert c.draw(chain.U_NEWPRED)[1] == 0           # to server 2
    assert c.draw(chain.U_JOIN)[1] == 3              # behind the tail
    c.updates(2)
    c.settled([0, 1, 2, 3], 5)
    assert c.row(1)[chain.IS_TAIL] == 1 and c.row(1)[chain.PRED] == 3
    assert c.row(3)[chain.IS_TAIL] == 0 and c.row(3)[chain.SUCC] == 1
    assert c.row(3)[chain.RESENT_ROWS] == 3          # the copy of Hist


def test_a_joining_server_is_no_member_until_it_holds_its_target():
    c = Cluster()
    c.updates(4)
    c.drain()
    c.hard_kill(3)
    c.draw(chain.U_BECOME_TAIL)
    c.restart(3)
    c.draw(chain.U_JOIN)
    c.drain(steps=0)
    while c.row(3)[chain.OPN] < 3:
        c.step(next(e for e in c.heads() if e.rcv in (c.name(2), c.name(3))))
        if c.row(3)[chain.AWAKE]:
            assert c.row(3)[chain.STATUS] != chain.MEMBER
            assert c.row(3)[chain.ACKED] == 0        # it acknowledges nothing
    c.settled([0, 1, 2, 3], 4)


@pytest.mark.parametrize("seed", SEEDS)
def test_a_kill_during_a_join(seed):
    c = Cluster(seed=seed)
    c.updates(3)
    c.drain()
    c.hard_kill(3)
    c.draw(chain.U_BECOME_TAIL)
    c.restart(3)
    c.draw(chain.U_JOIN)
    c.updates(1)
    c.drain(steps=seed + 1)            # the join is somewhere on its way
    c.hard_kill(3)
    c.draw(chain.U_BECOME_TAIL)        # to server 2, again
    c.updates(1)
    c.settled([0, 1, 2], 5)
    assert c.row(2)[chain.IS_TAIL] == 1
    # and it joins once more, behind the same tail
    c.restart(3)
    c.draw(chain.U_JOIN)
    c.settled([0, 1, 2, 3], 5)
    assert c.row(3)[chain.SPAWNS] == 3


@pytest.mark.parametrize("seed", SEEDS)
def test_two_adjacent_servers_fail_between_two_draws(seed):
    c = Cluster(seed=seed)
    c.updates(4)
    c.drain(steps=2 * seed + 2)
    c.hard_kill(2)
    c.hard_kill(1)
    assert c.draw(chain.U_NEWPRED)[1] == 0           # one message, to 3
    c.updates(2)
    c.settled([0, 3], 6)


@pytest.mark.parametrize("seed", SEEDS)
def test_a_join_behind_a_server_that_is_catching_up(seed):
    c = Cluster(seed=seed)
    c.updates(3)
    c.drain()
    c.hard_kill(2)
    c.hard_kill(3)
    c.draw(chain.U_BECOME_TAIL)        # server 1
    c.restart(3)
    c.restart(2)
    assert c.draw(chain.U_JOIN)[1] == 1              # 3 behind 1
    assert c.draw(chain.U_JOIN)[1] == 3              # 2 behind 3
    c.updates(2)
    c.settled([0, 1, 2, 3], 5)
    assert c.row(2)[chain.IS_TAIL] == 1 and c.row(3)[chain.SUCC] == 2


def test_a_server_killed_before_it_handled_anything_comes_back_out():
    """No mark of its own could tell: the runtime's spawn count does."""
    c = Cluster()
    c.updates(2)                       # at the head's door, undelivered
    c.hard_kill(3)
    c.restart(3)
    assert c.row(3)[chain.SPAWNS] == 2 and c.row(3)[chain.AWAKE] == 0
    c.draw(chain.U_BECOME_TAIL)
    # server 2 has not heard yet and forwards to it: the first message of
    # its second life is that FWD
    while c.row(2)[chain.OPN] < 1:
        c.step(next(
            e for e in c.heads()
            if e.rcv != c.name(3) and e.msg[0] != chain.U_BECOME_TAIL
        ))
    c.step(next(e for e in c.mail if e.rcv == c.name(3)))
    assert c.row(3)[chain.AWAKE] == 1
    assert c.row(3)[chain.STATUS] == chain.OUT and c.row(3)[chain.OPN] == 0
    c.draw(chain.U_JOIN)
    c.settled([0, 1, 2, 3], 2)


def test_the_shapes_are_the_issues():
    app = chain.make_chain_app(7, log_cap=64, bug="no_resend")
    assert (app.msg_width, app.max_outbox, app.state_width) == (3, 66, 15 + 64)
    assert app.channels == "fifo" and app.invariant_at == "delivery"
    assert app.timer_tags == () and app.initial_msgs is None
    assert app.durable == (chain.RESENT_ROWS, chain.RECONFIGS)
    assert app.spawn_count == chain.SPAWNS
    assert [name for name, _ in app.progress] == [
        "committed", "resent", "reconfigs",
    ]
    with pytest.raises(ValueError):
        chain.make_chain_app(4, log_cap=8, bug="read_uncommitted")
    # the fixture the differential tests keep is what it was
    old = chain.make_chain_app(4, bug="read_uncommitted")
    assert (old.state_width, old.max_outbox, old.channels) == (4, 1, "any")


def test_the_master_speaks_to_no_dead_server_and_draws_nothing():
    app = chain.make_chain_app(T, log_cap=L)
    master = chain.chain_send_generator(app)
    names = list(app.actor_names())

    class NoDraws:
        def __getattr__(self, name):
            raise AssertionError(f"the master drew rng.{name}")

    master.note_fault(OP_HARDKILL, names[0])
    master.note_fault(OP_HARDKILL, names[3])
    master.note_fault(OP_START, names[0])
    alive = names[1:3] + names[:1]
    sent = [master.generate_row(NoDraws(), alive) for _ in range(5)]
    assert [(n, app.tag_name(m[0])) for n, m in sent] == [
        (names[1], "BecomeHead"), (names[2], "BecomeTail"),
        (names[0], "Join"), (names[1], "Update"), (names[1], "Update"),
    ]
    assert [m[1:] for _n, m in sent] == [(1, 0), (2, 0), (2, 3), (1, 0), (2, 0)]
    master.reset()
    assert master.generate_row(NoDraws(), names) == (names[0], (1, 1, 0))


# -- (b) device, host oracle and the plain reference, lane for lane ---------

# Fuzz seeds: the first 40, and eight of those in the first 512 on which
# ``no_resend`` breaks the invariant.
FUZZ = list(range(40))


def _swept(seeds, log_cap=L, index_mode=None):
    app, cfg, fuzzer = build_workload(workload(log_cap=log_cap))
    if index_mode is not None:
        cfg = dataclasses.replace(cfg, index_mode=index_mode)
    gen = lambda s: fuzzer.generate_fuzz_test(seed=s)  # noqa: E731
    lanes = len(seeds)
    progs = stack_programs([lower_program(app, cfg, gen(s)) for s in seeds])
    keys = jax.vmap(lane_key)(np.asarray(seeds, np.uint32))
    drv = ContinuousSweepDriver(app, cfg, gen, batch=lanes, seg_steps=64)
    state = drv.init(keys)
    for steps in range(0, cfg.max_steps, 64):
        state = drv.segment(state, progs, jnp.full(lanes, steps, jnp.int32))
    state = jax.device_get(drv.finalize(state))
    return {
        "app": app, "cfg": cfg, "progs": progs, "keys": keys, "state": state,
        "kernel": make_single_lane_trace_kernel(app, cfg), "lifted": {},
    }


@pytest.fixture(scope="module")
def violating_seeds():
    app, cfg, fuzzer = build_workload(workload())
    driver = SweepDriver(app, cfg, lambda s: fuzzer.generate_fuzz_test(seed=s))
    found = []
    driver.violation_hook = lambda seeds, codes: found.extend(
        zip(np.asarray(seeds).tolist(), np.asarray(codes).tolist())
    )
    result = driver.sweep(512, 256, mode="continuous")
    assert result.overflow_lanes == 0
    return app, cfg, fuzzer, sorted(found), result.lanes_digest


@pytest.fixture(scope="module")
def swept(violating_seeds):
    found = [s for s, _code in violating_seeds[3] if s >= 40][:8]
    assert len(found) == 8
    return _swept(FUZZ + found)


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


def test_no_resend_is_found_and_lifts(violating_seeds):
    app, cfg, fuzzer, found, _digest = violating_seeds
    assert found and {code for _s, code in found} == {1}
    assert len(found) < 512 // 2
    seed = found[0][0]
    progs = stack_programs(
        [lower_program(app, cfg, fuzzer.generate_fuzz_test(seed=seed))]
    )
    keys = jnp.stack([lane_key(seed)])
    single, host = lift_lane_to_host(app, cfg, progs, keys, 0)
    assert int(single.violation) == 1 and host.violation.code == 1


def test_the_seeds_hold_both_verdicts(swept):
    state = swept["state"]
    assert set(np.asarray(state.status).tolist()) == {ST_DONE, ST_VIOLATION}
    assert (np.asarray(state.violation)[40:] == 1).all()


# What the two runs above gave at PR 40's tree (commit 8c30193, the invariant
# as every pair of members over a slice of Hist), recorded there: the 512
# lanes' digest (seed, status, code, sched_hash) and the seeds that violate,
# all code 1; the 48 lanes' codes, the delivery at which each stopped (a
# violating lane stops at the delivery that broke the invariant) and their
# sequence hashes.
PINNED_DIGEST = 0x5ADA26D66BE2C110
PINNED_VIOLATING = [
    8, 11, 15, 36, 46, 57, 60, 64, 68, 84, 85, 91, 94, 95, 98, 105, 113, 114,
    119, 129, 132, 146, 147, 148, 154, 156, 160, 163, 164, 167, 169, 170, 171,
    181, 184, 196, 204, 211, 216, 223, 228, 240, 244, 249, 250, 254, 260, 269,
    271, 272, 273, 287, 292, 294, 298, 302, 311, 312, 316, 317, 332, 345, 357,
    358, 359, 366, 373, 378, 395, 396, 398, 419, 440, 447, 454, 461, 466, 473,
    490, 498, 511,
]
PINNED_CODES = [
    0, 0, 0, 0, 0, 0, 0, 0, 1, 0, 0, 1, 0, 0, 0, 1, 0, 0, 0, 0, 0, 0, 0, 0,
    0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 1, 0, 0, 0, 1, 1, 1, 1, 1, 1, 1, 1,
]
PINNED_DELIVERIES = [
    98, 83, 87, 67, 56, 77, 84, 53, 16, 83, 77, 13, 53, 82, 93, 13, 79, 77,
    85, 66, 75, 100, 93, 75, 77, 81, 74, 79, 94, 67, 75, 76, 92, 86, 55, 72,
    35, 64, 80, 86, 54, 49, 17, 23, 35, 42, 29, 34,
]
PINNED_HASHES = [
    3239852469, 3545814206, 1478089559, 2491871879, 356963990, 4078995865,
    1886826398, 518230646, 729663784, 4235196847, 660415732, 3450893412,
    3166059721, 3572828726, 1649350013, 4202567544, 804503596, 1352825361,
    900937500, 1222409884, 4041693240, 2790122361, 3960803228, 2021721381,
    1832616688, 3103302195, 1894064542, 504346449, 1600828952, 2112641134,
    2401937295, 1193749253, 3770763725, 831647080, 2686620869, 1245903560,
    439312453, 100698269, 2991427819, 289842114, 3017749830, 1375318182,
    517026508, 3421372230, 2679053574, 2971015417, 1151801723, 3244869398,
]


def test_a_whole_run_is_what_it_was_with_the_pairwise_invariant(
    violating_seeds, swept
):
    """The same lanes violate at the same delivery with the same code."""
    _app, _cfg, _fuzzer, found, digest = violating_seeds
    assert found == [(seed, 1) for seed in PINNED_VIOLATING]
    assert digest == PINNED_DIGEST
    state = swept["state"]
    assert np.asarray(state.violation).tolist() == PINNED_CODES
    assert np.asarray(state.deliveries).tolist() == PINNED_DELIVERIES
    assert np.asarray(state.sched_hash).tolist() == PINNED_HASHES
    assert (np.asarray(state.status) == ST_VIOLATION).tolist() == [
        code != 0 for code in PINNED_CODES
    ]


@pytest.mark.parametrize("lane", range(48))
def test_device_host_and_the_plain_reference_agree_on_a_fuzzed_lane(swept, lane):
    """Same code, same delivered sequence, same final rows; and the
    reference, which refuses a delivery that is not its queue's head."""
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
    ref = chain_reference.replay(
        T, L, np.asarray(single.trace).tolist(), int(single.trace_len),
        bug="no_resend",
    )
    assert ref.code == code
    assert ref.step == ref.deliveries == host.deliveries
    actors = state.actor_state[lane]
    for i in range(T):
        row = actors[i]
        assert ref.spawns[i] == int(row[chain.SPAWNS]), i
        if not row[chain.AWAKE]:
            continue       # a life that handled nothing: the init row still
        assert (ref.statuses[i], ref.acked[i]) == (
            int(row[chain.STATUS]), int(row[chain.ACKED])
        ), i
        assert ref.hists[i] == row[chain.HIST : chain.HIST + row[chain.OPN]].tolist()
    assert ref.resent == int(actors[:, chain.RESENT_ROWS].sum())
    assert ref.reconfigs == int(actors[:, chain.RECONFIGS].sum())


def test_the_reference_without_the_bug_parts_on_a_violating_lane(swept):
    parted = 0
    for lane in range(40, 48):
        single, _host, _rows = lifted(swept, lane)
        try:
            ref = chain_reference.replay(
                T, L, np.asarray(single.trace).tolist(),
                int(single.trace_len), bug=None,
            )
            parted += ref.code != 1
        except chain_reference.Diverged:
            parted += 1
    assert parted == 8


def test_a_whole_run_is_the_same_on_the_chips_insert_as_on_the_scatter_one():
    """``index_mode='onehot'`` is the chip's path and a CPU's 'auto' is
    scatter, so nothing above runs the one-hot insert's head bit (a flag
    of the packed word). At ``log_cap`` 32 an insert holds 35 rows, which
    builds the short pass too (the cell's shape class: K = 67): the head
    bit through both branches of its ``case``."""
    seeds = list(range(24))
    onehot = _swept(seeds, log_cap=32, index_mode="onehot")
    scatter = _swept(seeds, log_cap=32, index_mode="scatter")
    assert insert_form(onehot["cfg"]) == "short"
    assert insert_form(scatter["cfg"]) == "scatter"
    a, b = onehot["state"], scatter["state"]
    assert a.insert_full_steps is not None and b.insert_full_steps is None
    full = np.asarray(a.insert_full_steps)
    assert 0 < full.min() and full.max() < 512      # both branches ran
    for name in ("status", "violation", "deliveries", "sched_hash",
                 "actor_state", "seq_counter"):
        np.testing.assert_array_equal(getattr(a, name), getattr(b, name), name)
    # the pools hold the same rows (slot for slot: both fill the lowest
    # free slots in row order), heads among them
    valid = np.asarray(a.pool_valid)
    np.testing.assert_array_equal(valid, b.pool_valid)
    np.testing.assert_array_equal(
        np.asarray(a.pool_head) & valid, np.asarray(b.pool_head) & valid
    )
    assert set(np.asarray(a.status).tolist()) <= {ST_DONE, ST_VIOLATION}


def _violations(app, cfg, fuzzer, lanes):
    driver = SweepDriver(app, cfg, lambda s: fuzzer.generate_fuzz_test(seed=s))
    result = driver.sweep(lanes, 512, mode="continuous")
    assert result.overflow_lanes == 0
    return result.violations


def test_the_protocol_as_published_is_clean_over_fifo_channels():
    """At most t - 1 kills a program: 0 violating of 2,048 lanes."""
    app, cfg, fuzzer = build_workload(workload(bug=None))
    assert cfg.srcdst_fifo
    assert _violations(app, cfg, fuzzer, 2048) == 0


def test_and_broken_where_the_channels_keep_no_order():
    """The control: the discipline, not luck, keeps the protocol safe."""
    app, cfg, fuzzer = build_workload(workload(bug=None))
    app = dataclasses.replace(app, channels="any")
    cfg = dataclasses.replace(cfg, srcdst_fifo=False)
    assert _violations(app, cfg, fuzzer, 256) > 128
