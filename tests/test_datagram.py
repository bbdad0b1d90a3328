"""``DSLApp.channels = "datagram"`` on every tier, on an app other than
paxos (a 3-node raft with the field forced): the step's outcome draw
(deliver and consume, deliver and keep, discard) under its two weights and
two budgets; the device lane against its host lift, record for record and
hash for hash; the guided replay, the host ``RandomScheduler`` strategy and
the strict replay; the Python gate (an ``"any"`` and a ``"fifo"`` app lower
to the segment they lowered to at the parent commit, sha256 for sha256);
the refusals (DPOR, the ``dpor`` and ``minimize`` verbs, the device replay
checker, ``round_delivery``, a weight given to another network)."""

import argparse
import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from demi_tpu import cli, obs
from demi_tpu.apps.common import make_host_invariant
from demi_tpu.apps.raft import make_raft_app
from demi_tpu.config import SchedulerConfig
from demi_tpu.device.continuous import (
    make_init_kernel, make_segment_kernel,
)
from demi_tpu.device.core import (
    REC_DELIVERY, REC_DISCARDED, REC_KEPT, REC_TIMER, DeviceConfig,
    ScheduleState, delivery_effects, init_state, insert_rows,
)
from demi_tpu.device.dpor_sweep import DeviceDPOR
from demi_tpu.device.encoding import (
    device_trace_to_guide, empty_programs, host_sched_hash, lower_program,
    stack_programs,
)
from demi_tpu.device.explore import ExtProgram, make_explore_kernel
from demi_tpu.device.replay import make_replay_kernel
from demi_tpu.events import (
    MsgDiscarded, MsgEvent, MsgKept, MsgSend, TimerDelivery,
)
from demi_tpu.parallel.distributed import build_workload, workload_args
from demi_tpu.parallel.sweep import SweepDriver
from demi_tpu.persist.checkpoint import handler_fingerprint
from demi_tpu.runner import lift_lane_to_host
from demi_tpu.schedulers.guided import GuideDivergence, GuidedScheduler
from demi_tpu.schedulers.random import RandomScheduler
from demi_tpu.schedulers.replay import ReplayScheduler
from demi_tpu.serialization import _event_from_json, _event_to_json
from demi_tpu.events import Unique

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

RAFT = {
    "app": "raft", "nodes": 3, "bug": "multivote", "num_events": 12,
    "max_messages": 200, "pool": 128, "timer_weight": 0.2,
    "dup_weight": 0.2, "drop_weight": 0.1, "max_dups": 6, "max_drops": 3,
}
LANES = 24


def datagram_raft():
    return dataclasses.replace(
        make_raft_app(3, bug="multivote"), channels="datagram"
    )


def lane_key(seed):
    return jax.random.fold_in(jax.random.PRNGKey(0), seed)


@pytest.fixture(scope="module")
def swept():
    """``LANES`` fuzzed lanes of the datagram raft through the explore
    kernel, and their programs and keys for the lifts."""
    app = datagram_raft()
    args = workload_args(RAFT)
    cfg = DeviceConfig.for_workload(app, args)
    fuzzer = cli.build_fuzzer(app, args)
    progs = stack_programs([
        lower_program(app, cfg, fuzzer.generate_fuzz_test(seed=s))
        for s in range(LANES)
    ])
    keys = jax.vmap(lane_key)(np.arange(LANES, dtype=np.uint32))
    result = make_explore_kernel(app, cfg)(progs, keys)
    return {
        "app": app, "cfg": cfg, "progs": progs, "keys": keys,
        "result": jax.device_get(result), "lifted": {},
    }


def lifted(swept, lane):
    if lane not in swept["lifted"]:
        swept["lifted"][lane] = lift_lane_to_host(
            swept["app"], swept["cfg"], swept["progs"], swept["keys"], lane
        )
    return swept["lifted"][lane]


# -- the field ----------------------------------------------------------------

def test_the_field_takes_a_third_value():
    app = datagram_raft()
    assert app.random_strategy == "datagram"
    assert handler_fingerprint(app) != handler_fingerprint(
        make_raft_app(3, bug="multivote")
    )


def test_for_workload_takes_the_four_knobs_for_a_datagram_app_only():
    args = workload_args(RAFT)
    cfg = DeviceConfig.for_workload(datagram_raft(), args)
    assert cfg.datagram
    assert (cfg.dup_weight, cfg.drop_weight, cfg.max_dups, cfg.max_drops) == (
        0.2, 0.1, 6, 3
    )
    raft = make_raft_app(3, bug="multivote")
    with pytest.raises(ValueError, match="--dup-weight and --drop-weight"):
        DeviceConfig.for_workload(raft, args)
    # the budgets alone move nothing, and neither weight is the default
    quiet = workload_args(dict(RAFT, dup_weight=0.0, drop_weight=0.0))
    plain = DeviceConfig.for_workload(raft, quiet)
    assert not plain.datagram and plain.max_dups == 0
    assert workload_args({}).dup_weight == workload_args({}).drop_weight == 0
    with pytest.raises(ValueError, match="datagram"):
        DeviceConfig.for_app(raft, dup_weight=0.1)


def test_every_verbs_builder_refuses_a_weight_for_another_network():
    with pytest.raises(SystemExit, match="--dup-weight and --drop-weight"):
        build_workload(dict(RAFT))
    with pytest.raises(SystemExit, match="'any'"):
        cli.main([
            "sweep", "--app", "raft", "--nodes", "3", "--drop-weight", "0.1",
        ])


def test_only_a_datagram_lane_carries_the_two_counts():
    raft = make_raft_app(3, bug="multivote")
    plain = init_state(
        raft, DeviceConfig.for_app(raft), jax.random.PRNGKey(0)
    )
    assert plain.dups is None and plain.drops is None
    app = datagram_raft()
    cfg = DeviceConfig.for_workload(app, workload_args(RAFT))
    lane = init_state(app, cfg, jax.random.PRNGKey(0))
    assert int(lane.dups) == int(lane.drops) == 0
    assert ScheduleState._fields[-2:] == ("dups", "drops")


# -- one delivery, three outcomes ---------------------------------------------

@pytest.fixture(scope="module")
def one_pending():
    """A lane with one actor's message pending in row 0 (a RequestVote
    from node 1 to node 0) and every actor started."""
    app = datagram_raft()
    cfg = DeviceConfig.for_workload(app, workload_args(RAFT))
    state = init_state(app, cfg, jax.random.PRNGKey(0))
    state = state._replace(started=jnp.ones(3, bool))
    msg = jnp.zeros((1, cfg.msg_width), jnp.int32).at[0, :3].set(
        jnp.asarray([1, 1, 0], jnp.int32)
    )
    state = insert_rows(
        state, cfg, jnp.asarray([True]), jnp.asarray([1], jnp.int32),
        jnp.asarray([0], jnp.int32), jnp.asarray([False]),
        jnp.asarray([False]), msg,
    )
    return app, cfg, state


def _outcome(one_pending, keep, discard):
    app, cfg, state = one_pending
    new, rows, _rec = delivery_effects(
        state, cfg, app, jnp.int32(0), jnp.bool_(keep), jnp.bool_(discard)
    )
    return state, new, rows


def test_a_kept_delivery_leaves_one_pending_copy_with_its_payload(one_pending):
    state, new, _rows = _outcome(one_pending, True, False)
    assert bool(new.pool_valid[0]) and int(new.pool_valid.sum()) == 1
    assert int(new.pool_seq[0]) == int(state.pool_seq[0])
    assert np.array_equal(new.pool_msg[0], state.pool_msg[0])
    assert int(new.deliveries) == 1
    assert (int(new.dups), int(new.drops)) == (1, 0)
    # the handler ran: the receiver's row moved
    assert not np.array_equal(new.actor_state[0], state.actor_state[0])


def test_a_discarded_message_reaches_no_handler(one_pending):
    state, new, rows = _outcome(one_pending, False, True)
    assert not bool(new.pool_valid.any())
    assert np.array_equal(new.actor_state, state.actor_state)
    assert np.array_equal(new.timer_mem_valid, state.timer_mem_valid)
    assert not bool(rows.valid.any())
    assert int(new.deliveries) == 0
    assert (int(new.dups), int(new.drops)) == (0, 1)


def test_the_three_outcomes_fold_to_three_hashes(one_pending):
    hashes = {
        int(_outcome(one_pending, keep, discard)[1].sched_hash)
        for keep, discard in ((False, False), (True, False), (False, True))
    }
    assert len(hashes) == 3


# -- device lane = host lift --------------------------------------------------

def test_both_outcomes_are_drawn_and_the_budgets_bind(swept):
    kept = dropped = 0
    for lane in range(LANES):
        single, _host = lifted(swept, lane)
        kinds = np.asarray(single.trace)[: int(single.trace_len), 0]
        lane_kept = int((kinds == REC_KEPT).sum())
        lane_dropped = int((kinds == REC_DISCARDED).sum())
        assert lane_kept <= RAFT["max_dups"]
        assert lane_dropped <= RAFT["max_drops"]
        kept += lane_kept
        dropped += lane_dropped
    # at these weights most lanes spend both budgets
    assert kept > LANES * RAFT["max_dups"] // 2
    assert dropped > LANES * RAFT["max_drops"] // 2


def test_timers_and_external_sends_are_never_touched(swept):
    app = swept["app"]
    for lane in range(LANES):
        single, host = lifted(swept, lane)
        trace = np.asarray(single.trace)[: int(single.trace_len)]
        net = trace[(trace[:, 0] == REC_KEPT) | (trace[:, 0] == REC_DISCARDED)]
        assert (net[:, 1] < app.num_actors).all()        # no external send
        assert not np.isin(net[:, 3], app.timer_tags).any()   # no timer
        for u in host.trace.events:
            if isinstance(u.event, (MsgKept, MsgDiscarded)):
                assert u.event.snd in app.actor_names()
                assert not app.is_timer_msg(u.event.msg)


@pytest.mark.parametrize("lane", range(0, LANES, 2))
def test_a_lane_lifts_code_for_code_and_hash_for_hash(swept, lane):
    single, host = lifted(swept, lane)
    result = swept["result"]
    code = host.violation.code if host.violation is not None else 0
    assert code == int(single.violation) == int(result.violation[lane])
    # kept deliveries count, discarded messages do not
    assert host.deliveries == int(single.deliveries) == int(
        result.deliveries[lane]
    )
    assert int(single.sched_hash) == int(result.sched_hash[lane])
    assert host_sched_hash(swept["app"], host.trace) == int(single.sched_hash)
    # the host trace says what the device's records say, in order
    kinds = np.asarray(single.trace)[: int(single.trace_len), 0]
    device = [int(k) for k in kinds if k in (
        REC_DELIVERY, REC_TIMER, REC_KEPT, REC_DISCARDED
    )]
    hosted, kept = [], False
    for u in host.trace.events:
        if isinstance(u.event, MsgKept):
            kept = True
        elif isinstance(u.event, MsgDiscarded):
            hosted.append(REC_DISCARDED)
        elif isinstance(u.event, TimerDelivery):
            hosted.append(REC_TIMER)
        elif isinstance(u.event, MsgEvent):
            hosted.append(REC_KEPT if kept else REC_DELIVERY)
            kept = False
    assert hosted == device


def test_the_violating_lanes_are_found_on_both_tiers(swept):
    violating = np.flatnonzero(swept["result"].violation)
    assert len(violating)
    for lane in violating[:2]:
        _single, host = lifted(swept, int(lane))
        assert host.violation is not None and host.violation.code == 1


# -- the guided replay ---------------------------------------------------------

def _guide(swept, lane):
    single, _host = lifted(swept, lane)
    return device_trace_to_guide(
        swept["app"], np.asarray(single.trace), int(single.trace_len)
    )


def _execute(app, guide):
    return GuidedScheduler(
        SchedulerConfig(invariant_check=make_host_invariant(app)), app
    ).execute_guide(guide)


def test_a_second_delivery_without_a_keep_is_a_divergence(swept):
    guide = _guide(swept, 0)
    at = next(i for i, step in enumerate(guide) if step[0] == "keep")
    again = guide[at][1:]
    assert any(step[1:] == again for step in guide[at + 1 :])
    consumed = list(guide)
    consumed[at] = ("deliver",) + again
    with pytest.raises(GuideDivergence, match="no pending match"):
        _execute(swept["app"], consumed)


def test_a_guide_that_keeps_or_discards_is_followed(swept):
    guide = _guide(swept, 0)
    host = _execute(swept["app"], guide)
    events = [type(u.event) for u in host.trace.events]
    assert events.count(MsgKept) == sum(s[0] == "keep" for s in guide)
    assert events.count(MsgDiscarded) == sum(s[0] == "discard" for s in guide)
    # every delivery of the trace has an id of its own, kept ones too
    ids = [
        u.id for u in host.trace.events
        if isinstance(u.event, (MsgEvent, MsgDiscarded))
    ]
    assert len(ids) == len(set(ids))


def test_no_other_network_keeps_or_discards(swept):
    guide = _guide(swept, 0)
    plain = make_raft_app(3, bug="multivote")
    with pytest.raises(GuideDivergence, match="datagram"):
        _execute(plain, guide)


def test_a_timer_is_neither_kept_nor_discarded(swept):
    guide = _guide(swept, 0)
    at = next(
        i for i, step in enumerate(guide) if step[0] == "deliver" and step[4]
    )
    for kind in ("keep", "discard"):
        bad = list(guide)
        bad[at] = (kind,) + guide[at][1:]
        with pytest.raises(GuideDivergence, match="only an actor's message"):
            _execute(swept["app"], bad)


# -- the host strategy and the strict replay ------------------------------------

@pytest.fixture(scope="module")
def host_runs():
    app = datagram_raft()
    args = workload_args(RAFT)
    fuzzer = cli.build_fuzzer(app, args)
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    sched = RandomScheduler(
        config, seed=3, max_messages=200, invariant_check_interval=1,
        strategy=app.random_strategy, timer_weight=0.2, dup_weight=0.2,
        drop_weight=0.1, max_dups=6, max_drops=3,
    )
    runs = []
    for seed in range(6):
        sched.seed = seed
        program = list(fuzzer.generate_fuzz_test(seed=seed))
        runs.append((program, sched.execute(program)))
    return app, config, runs


def test_the_host_strategy_draws_the_three_outcomes_under_the_budgets(host_runs):
    app, _config, runs = host_runs
    kept = dropped = 0
    for _program, result in runs:
        events = [u.event for u in result.trace.events]
        lane_kept = [e for e in events if isinstance(e, MsgKept)]
        lane_dropped = [e for e in events if isinstance(e, MsgDiscarded)]
        assert len(lane_kept) <= 6 and len(lane_dropped) <= 3
        for e in lane_kept + lane_dropped:
            assert e.snd in app.actor_names() and not app.is_timer_msg(e.msg)
        kept += len(lane_kept)
        dropped += len(lane_dropped)
        # a discarded message was sent and is never delivered
        delivered = {u.id for u in result.trace.events
                     if isinstance(u.event, MsgEvent)}
        sent = {u.id for u in result.trace.events
                if isinstance(u.event, (MsgSend, MsgKept))}
        for u in result.trace.events:
            if isinstance(u.event, MsgDiscarded):
                assert u.id in sent and u.id not in delivered
    assert kept and dropped


def test_the_strict_replay_follows_a_datagram_trace(host_runs):
    _app, config, runs = host_runs
    for program, result in runs:
        replayed = ReplayScheduler(config).replay(result.trace, program)
        assert replayed.deliveries == result.deliveries
        assert (replayed.violation is None) == (result.violation is None)
        kinds = lambda trace: [  # noqa: E731
            type(u.event).__name__ for u in trace.events
            if isinstance(u.event, (MsgEvent, MsgKept, MsgDiscarded,
                                    TimerDelivery))
        ]
        assert kinds(replayed.trace) == kinds(result.trace)


def test_a_weight_needs_the_datagram_strategy():
    config = SchedulerConfig()
    with pytest.raises(ValueError, match="datagram"):
        RandomScheduler(config, dup_weight=0.1)


def test_the_two_events_serialize():
    for event in (MsgKept("r1", "r0", (1, 2, 3)),
                  MsgDiscarded("r1", "r0", (1, 2, 3))):
        again = _event_from_json(_event_to_json(Unique(event, 7)), None)
        assert again == Unique(event, 7)


# -- the continuous sweep and its counts ---------------------------------------

def test_a_sweep_is_the_same_at_any_chunking_and_counts_the_network(swept):
    app, cfg = swept["app"], swept["cfg"]
    fuzzer = cli.build_fuzzer(app, workload_args(RAFT))
    gen = lambda s: fuzzer.generate_fuzz_test(seed=s)  # noqa: E731
    whole = SweepDriver(app, cfg, gen).sweep(LANES, LANES, mode="continuous")
    obs.enable()
    try:
        obs.TRACER.clear()
        parts = SweepDriver(app, cfg, gen).sweep(LANES, 8, mode="continuous")
        counts = obs.stage_counts()
    finally:
        obs.disable()
        obs.TRACER.clear()
    assert whole.lanes_digest == parts.lanes_digest
    assert whole.violations == int((swept["result"].violation != 0).sum())
    assert counts["sweep.net.delivered"] == int(
        swept["result"].deliveries.sum()
    )
    assert 0 < counts["sweep.net.kept"] <= LANES * RAFT["max_dups"]
    assert 0 < counts["sweep.net.discarded"] <= LANES * RAFT["max_drops"]


def test_no_other_network_counts_it():
    raft = make_raft_app(3, bug="multivote")
    args = workload_args(dict(RAFT, dup_weight=0.0, drop_weight=0.0))
    cfg = DeviceConfig.for_workload(raft, args)
    fuzzer = cli.build_fuzzer(raft, args)
    obs.enable()
    try:
        obs.TRACER.clear()
        SweepDriver(
            raft, cfg, lambda s: fuzzer.generate_fuzz_test(seed=s)
        ).sweep(8, 8, mode="continuous")
        counts = obs.stage_counts()
    finally:
        obs.disable()
        obs.TRACER.clear()
    assert not [name for name in counts if name.startswith("sweep.net.")]


# -- the Python gate: the other networks' programs are the parent's --------------

@pytest.mark.parametrize("config,record,index_mode,sha", [
    # (the one segment here with the insert's short pass: its ``case`` has
    # had a third region since PR 45; at cccab40 it was a5fae2621ef1c20d...
    # The three one-hot segments are what PR 50's tree lowers: their table
    # reads are selects since, not ``dot_general``s; before, 4cf328d82ca3...,
    # 483476c6ff21..., 6750f2f0840b...)
    ("chain7-fifo", False, "onehot",
     "089e080bfb600a595ff6de7542a1acc76b09cf8fe12553115dd140d60ebf58b6"),
    ("chain7-fifo", False, "scatter",
     "aa8cbeabca70a00b8c808370dfa2229e2a37e51cc19fb1544392360e5f94a450"),
    ("raft5-nemesis", False, "onehot",
     "953542ec686b291ae2550f15142d2ee48c6770305009003871d0dcba51d7dce7"),
    ("raft5-multivote", True, "onehot",
     "f23f5827583bc65c7118e8204e971cda4a644e1a0dc4a3083d847a336cb6d020"),
])
def test_a_fifo_and_an_any_app_lower_to_the_parents_segment(
    config, record, index_mode, sha
):
    """The segment (4 lanes, 8 steps) of a benchmark configuration, byte
    for byte what commit cccab40 lowered, before the outcome draw was
    there (``record``: the DPOR verb's shape, whose kernel shares
    ``delivery_effects``). ``tests/test_channels.py`` pins
    raft5-multivote's sweep shape; CHANGES.md has all 24."""
    with open(os.path.join(
        ROOT, "benchmarks", "configs", f"{config}.json"
    )) as f:
        workload = json.load(f)["workload"]
    app, cfg, _ = build_workload(dict(workload), record=record)
    cfg = dataclasses.replace(cfg, index_mode=index_mode)
    state = make_init_kernel(app, cfg)(
        jax.random.split(jax.random.PRNGKey(0), 4)
    )
    progs = ExtProgram(*(jnp.asarray(x) for x in empty_programs(cfg, 4)))
    text = make_segment_kernel(app, cfg, 8).lower(
        state, progs, jnp.zeros(4, jnp.int32)
    ).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == sha


# -- the refusals ---------------------------------------------------------------

def test_dpor_refuses_a_datagram_app_by_name():
    app = datagram_raft()
    cfg = DeviceConfig.for_app(
        app, datagram=True, record_trace=True, record_parents=True
    )
    with pytest.raises(ValueError, match="DPOR .* datagram"):
        DeviceDPOR(app, cfg, [])


def test_the_device_replay_checker_refuses_a_datagram_app_by_name():
    app = datagram_raft()
    with pytest.raises(ValueError, match="device replay checker .* datagram"):
        make_replay_kernel(app, DeviceConfig.for_app(app))


def test_round_delivery_refuses_datagram_channels_by_name():
    app = datagram_raft()
    with pytest.raises(ValueError, match="round_delivery .* datagram"):
        DeviceConfig.for_app(
            app, datagram=True, round_delivery=True, trace_capacity=64
        )
    with pytest.raises(ValueError, match="srcdst_fifo .* datagram"):
        DeviceConfig.for_app(app, datagram=True, srcdst_fifo=True)


@pytest.mark.parametrize("verb,more", [
    ("dpor", []), ("minimize", ["-e", "nowhere"]),
])
def test_the_verbs_that_cannot_follow_it_say_so(verb, more):
    with pytest.raises(SystemExit, match=f"{verb}: .* datagram"):
        cli.main([verb, "--app", "paxos", "--nodes", "11"] + more)
