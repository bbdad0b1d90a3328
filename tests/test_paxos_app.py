"""Multi-Paxos as published (``demi_tpu/apps/paxos.py``: 3 replicas, 3
leaders, 5 acceptors at f = 2) over datagram channels, at ``log_cap`` 8:
the shapes the app builds and the counts it refuses; the clients; a
commander that decides on one acceptor's answer heard thrice, scripted on
the host tier; device, host oracle and the plain reference
(``benchmarks/lib/paxos_reference.py``: real sets and dicts, no JAX)
agreeing lane for lane on every actor's state over seeded schedules with
kept and discarded deliveries; the three verdicts (the protocol as
published never violates under duplication, ``count_replies`` never
without it, and does with it, each such lane lifting to the host with
code 1)."""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from demi_tpu.apps.common import make_host_invariant
from demi_tpu.apps.paxos import (
    T_DECISION, T_P1A, T_P1B, T_P2A, T_P2B, T_PROPOSE, T_REQUEST, PaxosClient,
    make_paxos_app, request, state_width,
)
from demi_tpu.config import SchedulerConfig
from demi_tpu.device.continuous import ContinuousSweepDriver
from demi_tpu.device.encoding import (
    device_trace_to_guide, host_sched_hash, lower_program, stack_programs,
)
from demi_tpu.device.explore import make_single_lane_trace_kernel
from demi_tpu.parallel.distributed import build_workload
from demi_tpu.parallel.sweep import SweepDriver
from demi_tpu.schedulers.guided import GuidedScheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, path)
    )
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module  # dataclasses look their module up
    spec.loader.exec_module(module)
    return module


paxos_reference = _load("benchmarks/lib/paxos_reference.py", "paxos_reference")
row_digest = _load(
    "benchmarks/tests/paxos_reference_on_chip.py", "paxos_reference_on_chip"
).row_digest

L = 8
N = 11


def workload(**over):
    base = {
        "app": "paxos", "nodes": N, "bug": "count_replies", "log_cap": L,
        "seed": 0, "num_events": 96, "max_messages": 2048, "pool": 256,
        "timer_weight": 0.2, "send_weight": 0.60, "wait_weight": 0.28,
        "hard_kill_weight": 0.12, "restart_weight": 0.0, "kill_weight": 0.0,
        "partition_weight": 0.0, "max_kills": 4, "wait_budget": [1, 40],
        "dup_weight": 0.25, "drop_weight": 0.02, "max_dups": 256,
        "max_drops": 16,
    }
    base.update(over)
    return base


def lane_key(seed):
    return jax.random.fold_in(jax.random.PRNGKey(0), seed)


# -- the app -------------------------------------------------------------------

def test_the_shapes_are_the_issues():
    app = make_paxos_app(11, log_cap=32, bug="count_replies")
    assert app.channels == "datagram" and app.random_strategy == "datagram"
    assert app.msg_width == 4 + 2 * 32 == 68
    assert app.max_outbox == 5 * 32 + 1 == 161
    assert app.state_width == state_width(32) == 136
    assert app.invariant_at == "delivery"
    assert [name for name, _fn in app.progress] == [
        "committed", "adoptions", "preempts"
    ]
    app, cfg, _fuzzer = build_workload(workload(log_cap=32, pool=512))
    assert cfg.datagram and cfg.dup_weight == 0.25 and cfg.max_drops == 16
    assert (cfg.num_actors, cfg.msg_width, cfg.max_outbox) == (11, 68, 161)


@pytest.mark.parametrize("nodes", [3, 5, 9, 10, 12])
def test_other_counts_are_refused(nodes):
    with pytest.raises(ValueError, match="2f \\+ 1 \\+ 2\\(f \\+ 1\\)"):
        make_paxos_app(nodes)
    with pytest.raises(SystemExit, match="--app paxos"):
        build_workload(workload(nodes=nodes))


def test_an_unknown_bug_is_refused():
    with pytest.raises(ValueError, match="count_replies"):
        make_paxos_app(11, bug="forget")


def test_the_clients_send_every_command_to_every_replica_and_draw_nothing():
    app = make_paxos_app(11, log_cap=L)
    client = PaxosClient(app)
    names = list(app.actor_names())

    class NoDraws:
        def __getattr__(self, name):
            raise AssertionError(f"the clients drew rng.{name}")

    sent = [client.generate_row(NoDraws(), names) for _ in range(18)]
    assert all(msg[0] == T_REQUEST for _name, msg in sent)
    # one round: nine sends, each command once to each replica, and each
    # replica hears a different command first
    first = sent[:9]
    assert sorted((name, msg[1]) for name, msg in first) == sorted(
        (name, c) for name in names[:3] for c in (1, 2, 3)
    )
    assert [msg[1] for _name, msg in first[:3]] == [1, 2, 3]
    assert {msg[1] for _name, msg in sent[9:]} == {4, 5, 6}
    # a replica that is down is passed over; no replica up, no send
    client.reset()
    up = names[1:]
    assert client.generate_row(NoDraws(), up) == (names[1], request(app, 2))
    assert client.generate_row(NoDraws(), names[3:]) is None
    # pure in the seed: a fuzzer's program is the same twice
    _app, _cfg, fuzzer = build_workload(workload())
    once = list(fuzzer.generate_fuzz_test(seed=5).payloads)
    assert once == list(fuzzer.generate_fuzz_test(seed=5).payloads)


# -- one slot, scripted on the host tier ----------------------------------------

class Cluster:
    """The 11 actors on the host oracle, delivering named messages one at
    a time (``keep`` leaves the message pending)."""

    def __init__(self, bug):
        from demi_tpu.apps.common import dsl_start_events

        self.app = make_paxos_app(N, log_cap=L, bug=bug)
        self.sched = GuidedScheduler(
            SchedulerConfig(invariant_check=make_host_invariant(self.app)),
            self.app,
        )
        self.sched.prepare([])
        for start in dsl_start_events(self.app):
            self.sched._inject_one(start)

    def pending(self, tag, src=None, dst=None):
        name = self.app.actor_name
        return [
            e for e in self.sched._pending
            if e.msg[0] == tag
            and (src is None or e.snd == name(src))
            and (dst is None or e.rcv == name(dst))
        ]

    def deliver(self, tag, src=None, dst=None, keep=False, f1=None):
        entry = next(
            e for e in self.pending(tag, src, dst)
            if f1 is None or e.msg[1] == f1
        )
        self.sched._pending.remove(entry)
        self.sched._deliver(entry, keep=keep)

    def request(self, replica, command):
        from demi_tpu.external_events import Send, constant_message

        self.sched._inject_one(Send(
            self.app.actor_name(replica),
            constant_message(request(self.app, command)),
        ))
        self.deliver(T_REQUEST, dst=replica)

    def row(self, actor):
        return self.sched.system.actors[self.app.actor_name(actor)].state

    def code(self):
        found = self.sched.check_invariant()
        return 0 if found is None else found.code


def _one_answer_thrice(bug):
    """Leader 3 is adopted at ballot 0 by acceptors 6, 7, 8, which then
    adopt leader 4's ballot 1; acceptor 9 has heard of neither and 10 only
    of ballot 0. Leader 3's P2A for slot 1 reaches acceptor 10 alone, whose
    P2B is delivered three times (kept twice)."""
    c = Cluster(bug)
    for acceptor in (6, 7, 8, 10):
        c.deliver(T_P1A, src=3, dst=acceptor)
    for acceptor in (6, 7, 8):
        c.deliver(T_P1B, src=acceptor, dst=3)
    assert c.row(3)[1] == 1  # active
    c.request(0, 1)
    c.deliver(T_PROPOSE, src=0, dst=3)
    # the rival: ballot 1 is adopted by 6, 7, 8 before they see slot 1
    for acceptor in (6, 7, 8):
        c.deliver(T_P1A, src=4, dst=acceptor)
    c.deliver(T_P2A, src=3, dst=10)
    c.deliver(T_P2B, src=10, dst=3, keep=True)
    c.deliver(T_P2B, src=10, dst=3, keep=True)
    c.deliver(T_P2B, src=10, dst=3)
    return c


def test_as_published_one_answer_heard_thrice_decides_nothing():
    c = _one_answer_thrice(None)
    assert not c.pending(T_DECISION)
    # the commander still waits for two more acceptors
    assert int(c.row(3)[8 + 3 * L]) & 0b11111 == 0b10000


def test_count_replies_decides_on_it_and_a_higher_ballot_decides_otherwise():
    c = _one_answer_thrice("count_replies")
    assert len(c.pending(T_DECISION)) == 3
    c.deliver(T_DECISION, src=3, dst=0)
    assert c.code() == 0
    # leader 4 is adopted by 6, 7, 8, none of which accepted slot 1, and
    # proposes the command its own replica asked for
    for acceptor in (6, 7, 8):
        c.deliver(T_P1B, src=acceptor, dst=4, f1=1)
    assert c.row(4)[1] == 1
    c.request(1, 2)
    c.deliver(T_PROPOSE, src=1, dst=4)
    for acceptor in (6, 7, 8):
        c.deliver(T_P2A, src=4, dst=acceptor)
        c.deliver(T_P2B, src=acceptor, dst=4)
    c.deliver(T_DECISION, src=4, dst=1)
    # replica 0 holds command 1 in slot 1, replica 1 command 2
    assert c.code() == 1


# -- device, host oracle and the plain reference, lane for lane -----------------

FUZZ = list(range(12))


@pytest.fixture(scope="module")
def violating_seeds():
    """One sweep of 2,048 lanes of ``count_replies`` under duplication."""
    app, cfg, fuzzer = build_workload(workload())
    driver = SweepDriver(app, cfg, lambda s: fuzzer.generate_fuzz_test(seed=s))
    found = []
    driver.violation_hook = lambda seeds, codes: found.extend(
        zip(np.asarray(seeds).tolist(), np.asarray(codes).tolist())
    )
    result = driver.sweep(2048, 512, mode="continuous")
    assert result.overflow_lanes == 0
    return app, cfg, fuzzer, sorted(found)


@pytest.fixture(scope="module")
def swept(violating_seeds):
    """The first twelve seeds and four violating ones, run to their end
    through the continuous driver's segment kernel."""
    app, cfg, fuzzer, found = violating_seeds
    seeds = FUZZ + [s for s, _code in found if s >= len(FUZZ)][:4]
    assert len(seeds) == 16
    gen = lambda s: fuzzer.generate_fuzz_test(seed=s)  # noqa: E731
    progs = stack_programs([lower_program(app, cfg, gen(s)) for s in seeds])
    keys = jax.vmap(lane_key)(np.asarray(seeds, np.uint32))
    drv = ContinuousSweepDriver(app, cfg, gen, batch=len(seeds), seg_steps=64)
    state = drv.init(keys)
    for steps in range(0, cfg.max_steps, 64):
        state = drv.segment(
            state, progs, jnp.full(len(seeds), steps, jnp.int32)
        )
    state = jax.device_get(drv.finalize(state))
    return {
        "app": app, "cfg": cfg, "progs": progs, "keys": keys, "state": state,
        "seeds": seeds, "codes": dict(found),
        "kernel": make_single_lane_trace_kernel(app, cfg),
    }


def test_the_three_verdicts(violating_seeds):
    """Under duplication ``count_replies`` violates in some lanes, code 1;
    the protocol as published in none of as many; and ``count_replies``
    in none where nothing is repeated or lost."""
    _app, _cfg, _fuzzer, found = violating_seeds
    assert 8 <= len(found) <= 164  # 0.4% to 8% of 2,048
    assert {code for _seed, code in found} == {1}
    for over in (
        {"bug": None},
        {"dup_weight": 0.0, "drop_weight": 0.0},
    ):
        app, cfg, fuzzer = build_workload(workload(**over))
        result = SweepDriver(
            app, cfg, lambda s: fuzzer.generate_fuzz_test(seed=s)
        ).sweep(2048, 512, mode="continuous")
        assert result.lanes == 2048
        assert (result.violations, result.overflow_lanes) == (0, 0), over


@pytest.mark.parametrize("lane", range(16))
def test_device_host_and_the_plain_reference_agree_on_a_fuzzed_lane(swept, lane):
    app, cfg, state = swept["app"], swept["cfg"], swept["state"]
    single = swept["kernel"](
        jax.tree_util.tree_map(lambda x: x[lane], swept["progs"]),
        swept["keys"][lane],
    )
    records, length = np.asarray(single.trace), int(single.trace_len)
    sched = GuidedScheduler(
        SchedulerConfig(invariant_check=make_host_invariant(app)), app
    )
    host = sched.execute_guide(device_trace_to_guide(app, records, length))
    ref = paxos_reference.replay(
        N, L, records.tolist(), length, bug="count_replies",
        max_dups=cfg.max_dups, max_drops=cfg.max_drops,
    )
    want = swept["codes"].get(swept["seeds"][lane], 0)
    host_code = host.violation.code if host.violation is not None else 0
    assert ref.code == host_code == want
    assert int(single.violation) == int(state.violation[lane]) == want
    assert (
        ref.step == ref.deliveries == host.deliveries
        == int(single.deliveries) == int(state.deliveries[lane])
    )
    assert (ref.kept, ref.discarded) == (
        int(state.dups[lane]), int(state.drops[lane])
    )
    assert (
        host_sched_hash(app, host.trace) == int(single.sched_hash)
        == int(state.sched_hash[lane])
    )
    # every actor's state, three ways (the host has no row of an actor a
    # hard kill stopped; the device keeps its last)
    rows = state.actor_state[lane]
    for actor in range(N):
        digest = row_digest(actor, rows[actor], N, L, "count_replies")
        assert ref.digests[actor] == digest, actor
        live = sched.system.actors.get(app.actor_name(actor))
        if live is not None:
            assert row_digest(
                actor, live.state, N, L, "count_replies"
            ) == digest, actor
    counts = {
        name: int(fn(jnp.asarray(rows))) for name, fn in app.progress
    }
    assert counts == {
        "committed": ref.committed, "adoptions": ref.adoptions,
        "preempts": ref.preempts,
    }


def test_the_chips_one_hot_path_gives_the_same_lanes(swept):
    """On the CPU ``index_mode='auto'`` is scatter; the chip takes the
    one-hot forms (here with the short insert pass: an outbox of 41
    rows) and the outcome draw reads the chosen row through them."""
    import dataclasses

    from demi_tpu.device.core import _short_insert_built
    from demi_tpu.device.explore import make_explore_kernel

    app, cfg = swept["app"], swept["cfg"]
    onehot = dataclasses.replace(cfg, index_mode="onehot")
    assert _short_insert_built(onehot) and not _short_insert_built(cfg)
    lanes = [0, 1, 2, 3, 12, 13, 14, 15]
    progs = jax.tree_util.tree_map(lambda x: x[np.asarray(lanes)], swept["progs"])
    keys = swept["keys"][np.asarray(lanes)]
    res = jax.device_get(make_explore_kernel(app, onehot)(progs, keys))
    state = swept["state"]
    for k, lane in enumerate(lanes):
        assert int(res.violation[k]) == int(state.violation[lane])
        assert int(res.deliveries[k]) == int(state.deliveries[lane])
        assert int(res.sched_hash[k]) == int(state.sched_hash[lane])


def test_the_lanes_hold_kept_and_discarded_deliveries_and_both_verdicts(swept):
    state = swept["state"]
    assert (state.dups > 0).all() and (state.drops > 0).any()
    assert (state.violation[:12] == 0).any() and (state.violation[12:] == 1).all()


def test_the_reference_as_published_parts_on_every_violating_lane(swept):
    for lane in range(12, 16):
        single = swept["kernel"](
            jax.tree_util.tree_map(lambda x: x[lane], swept["progs"]),
            swept["keys"][lane],
        )
        try:
            ref = paxos_reference.replay(
                N, L, np.asarray(single.trace).tolist(),
                int(single.trace_len), bug=None,
            )
        except paxos_reference.Diverged:
            continue
        assert ref.code != int(single.violation)


def test_the_reference_refuses_a_second_delivery_of_a_consumed_message(swept):
    single = swept["kernel"](
        jax.tree_util.tree_map(lambda x: x[0], swept["progs"]),
        swept["keys"][0],
    )
    records, length = np.asarray(single.trace).copy(), int(single.trace_len)
    kept = next(i for i in range(length) if records[i, 0] == 5)
    records[kept, 0] = 1  # consumed, yet the lane delivers it again later
    with pytest.raises(paxos_reference.Diverged, match="consumed"):
        paxos_reference.replay(N, L, records.tolist(), length,
                               bug="count_replies")
    # and a budget that the schedule exceeds
    with pytest.raises(paxos_reference.Diverged, match="kept deliveries"):
        paxos_reference.replay(
            N, L, np.asarray(single.trace).tolist(), length,
            bug="count_replies", max_dups=1,
        )
