"""``DSLApp.channels``: the order an app's network keeps is the app's, and
every tier obeys it. ``DeviceConfig.for_workload`` derives ``srcdst_fifo``
from it and builds for an ``"any"`` app exactly what it built before the
field was there (one lowered segment pinned to its sha256 at the parent
commit); the guided replay refuses a delivery that is not its channel's
oldest pending one; ``DeviceDPOR`` and the ``dpor`` verb turn a FIFO app
away; the host fuzz runs it under ``SrcDstFIFO``; the continuous driver
carries the head bits through its refills to the one-shot kernel's digest.
``DSLApp.spawn_count``, which came with the first FIFO app, is held to both
tiers at the end."""

import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from demi_tpu import cli
from demi_tpu.apps.broadcast import make_broadcast_app
from demi_tpu.apps.chain import U_UPDATE, chain_send_generator, make_chain_app
from demi_tpu.apps.common import dsl_start_events, make_host_invariant
from demi_tpu.apps.raft import make_raft_app
from demi_tpu.config import SchedulerConfig
from demi_tpu.device.continuous import make_init_kernel, make_segment_kernel
from demi_tpu.device.core import OP_SEND, OP_START, DeviceConfig
from demi_tpu.device.dpor_sweep import DeviceDPOR
from demi_tpu.device.encoding import (
    device_trace_to_guide, empty_programs, lower_program,
)
from demi_tpu.device.explore import ExtProgram, make_single_lane_trace_kernel
from demi_tpu.dsl import DSLApp
from demi_tpu.events import HardKillEvent, MsgEvent, MsgSend
from demi_tpu.external_events import (
    HardKill, MessageConstructor, Send, Start, WaitQuiescence,
)
from demi_tpu.fuzzing import Fuzzer, FuzzerWeights
from demi_tpu.parallel.distributed import build_workload, workload_args
from demi_tpu.parallel.sweep import SweepDriver
from demi_tpu.persist.checkpoint import handler_fingerprint
from demi_tpu.runtime.actor import dsl_actor_factory
from demi_tpu.runtime.system import ControlledActorSystem
from demi_tpu.schedulers.guided import GuideDivergence, GuidedScheduler
from demi_tpu.schedulers.random import RandomScheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHAIN = {
    "app": "chain", "nodes": 4, "bug": None, "log_cap": 8, "seed": 0,
    "num_events": 40, "max_messages": 512, "pool": 128, "timer_weight": 1.0,
    "send_weight": 0.55, "wait_weight": 0.25, "wait_budget": [1, 40],
    "hard_kill_weight": 0.08, "restart_weight": 0.12,
    "partition_weight": 0.0, "kill_weight": 0.0, "max_kills": 3,
}


def test_the_field_takes_two_values():
    app = make_broadcast_app(3, reliable=True)
    assert app.channels == "any" and app.random_strategy == "fully_random"
    fifo = dataclasses.replace(app, channels="fifo")
    assert fifo.random_strategy == "srcdst_fifo"
    with pytest.raises(ValueError, match="channels"):
        dataclasses.replace(app, channels="tcp")


def test_for_workload_derives_the_discipline_from_the_app():
    args = workload_args(CHAIN)
    chain = make_chain_app(4, log_cap=8)
    cfg = DeviceConfig.for_workload(chain, args)
    assert cfg.srcdst_fifo and cfg.track_fifo_heads
    loose = dataclasses.replace(chain, channels="any")
    assert not DeviceConfig.for_workload(loose, args).srcdst_fifo
    # an "any" app: the configuration it always had, field for field
    raft = make_raft_app(3, bug="multivote")
    have = DeviceConfig.for_workload(raft, args)
    assert have == DeviceConfig.for_app(
        raft, pool_capacity=128, max_steps=512, max_external_ops=45,
        invariant_interval=1, timer_weight=1.0,
    )
    assert not have.srcdst_fifo
    # and every verb's builder passes it on
    _app, built, _fuzzer = build_workload(CHAIN)
    assert built.srcdst_fifo


@pytest.mark.parametrize("index_mode,sha", [
    # (the one-hot segment is PR 50's: its two table reads are selects
    # since, not ``dot_general``s; at 1ebcfc9 it was 922690ca7f72c3b0...)
    ("onehot",
     "8834fe2e369fdf37de7f52fbc6a25cfbef9f000f5b2d5d223198012fe0d22022"),
    ("scatter",
     "cf6c29992a742026e25cf7a38884a28764d9526896cf27d72c6c538fa8b8a0b6"),
])
def test_an_any_app_lowers_to_the_parents_segment(index_mode, sha):
    """``raft5-multivote``'s segment (4 lanes, 8 steps), byte for byte what
    commit 1ebcfc9 lowered, before ``DSLApp.channels`` and
    ``DSLApp.spawn_count`` were there (the scatter one; the one-hot one
    byte for byte what PR 50's tree lowers)."""
    with open(os.path.join(
        ROOT, "benchmarks", "configs", "raft5-multivote.json"
    )) as f:
        workload = json.load(f)["workload"]
    app, cfg, _ = build_workload(dict(workload))
    cfg = dataclasses.replace(cfg, index_mode=index_mode)
    state = make_init_kernel(app, cfg)(
        jax.random.split(jax.random.PRNGKey(0), 4)
    )
    progs = ExtProgram(*(jnp.asarray(x) for x in empty_programs(cfg, 4)))
    text = make_segment_kernel(app, cfg, 8).lower(
        state, progs, jnp.zeros(4, jnp.int32)
    ).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == sha


def test_the_checkpoint_fingerprint_knows_the_field():
    app = make_broadcast_app(3, reliable=True)
    assert handler_fingerprint(app) != handler_fingerprint(
        dataclasses.replace(app, channels="fifo")
    )


# -- the guided replay ------------------------------------------------------

def _two_updates_guide(first, second):
    """Two client updates to the head of a 2-server chain, delivered in
    the order given."""
    ext = 2   # the external sender's id
    return [
        ("ext", OP_START, 0, 0, (0, 0, 0)),
        ("ext", OP_START, 1, 0, (0, 0, 0)),
        ("ext", OP_SEND, 0, 0, (U_UPDATE, 1, 0)),
        ("ext", OP_SEND, 0, 0, (U_UPDATE, 2, 0)),
        ("deliver", ext, 0, (U_UPDATE, first, 0), False),
        ("deliver", ext, 0, (U_UPDATE, second, 0), False),
    ]


def _guided(app):
    return GuidedScheduler(
        SchedulerConfig(invariant_check=make_host_invariant(app)), app
    )


def test_the_guided_replay_refuses_a_delivery_that_is_not_its_channels_oldest():
    app = make_chain_app(2, log_cap=4)
    assert _guided(app).execute_guide(_two_updates_guide(1, 2)).deliveries == 2
    with pytest.raises(GuideDivergence, match="oldest"):
        _guided(app).execute_guide(_two_updates_guide(2, 1))
    # the same guide is any network's to deliver
    loose = dataclasses.replace(app, channels="any")
    assert _guided(loose).execute_guide(_two_updates_guide(2, 1)).deliveries == 2


def test_a_device_lane_of_a_fifo_app_lifts_and_an_any_lane_of_it_does_not():
    """What ``verbs/sweep.py: check`` leans on: a lane the device ran
    without the discipline is refused on the host."""
    app, cfg, fuzzer = build_workload(CHAIN)
    cfg = dataclasses.replace(cfg, record_trace=True)
    loose_cfg = dataclasses.replace(cfg, srcdst_fifo=False)
    refused = 0
    for seed in range(6):
        prog = lower_program(app, cfg, fuzzer.generate_fuzz_test(seed=seed))
        key = jax.random.fold_in(jax.random.PRNGKey(0), seed)
        kept = make_single_lane_trace_kernel(app, cfg)(prog, key)
        guide = device_trace_to_guide(
            app, np.asarray(kept.trace), int(kept.trace_len)
        )
        host = _guided(app).execute_guide(guide)
        assert host.violation is None and int(kept.violation) == 0
        loose = make_single_lane_trace_kernel(app, loose_cfg)(prog, key)
        guide = device_trace_to_guide(
            app, np.asarray(loose.trace), int(loose.trace_len)
        )
        try:
            _guided(app).execute_guide(guide)
        except GuideDivergence:
            refused += 1
    assert refused >= 4


# -- DPOR -------------------------------------------------------------------

def test_device_dpor_refuses_a_fifo_app():
    app = make_chain_app(3, log_cap=4)
    cfg = DeviceConfig.for_workload(
        app, workload_args(CHAIN), record_trace=True, record_parents=True
    )
    program = dsl_start_events(app) + [WaitQuiescence()]
    with pytest.raises(ValueError, match="channels are FIFO"):
        DeviceDPOR(app, cfg, program)


def test_the_dpor_verb_refuses_it_in_one_sentence():
    with pytest.raises(SystemExit, match="dpor: DPOR does not explore"):
        cli.main(["dpor", "--app", "chain", "--nodes", "3", "--log-cap", "4"])


def test_the_cli_knows_the_app():
    with pytest.raises(SystemExit, match="chain, kafka, paxos, raft"):
        cli.main(["sweep", "--app", "nosuch"])


# -- the host fuzz ----------------------------------------------------------

def _small_chain_fuzzer(app):
    return Fuzzer(
        num_events=14,
        weights=FuzzerWeights(
            kill=0.0, send=0.6, wait_quiescence=0.2, hard_kill=0.08,
            restart=0.12,
        ),
        message_gen=chain_send_generator(app),
        prefix=dsl_start_events(app), max_kills=2, wait_budget=(1, 6),
    )


def _out_of_order(trace):
    """Deliveries of a channel that overtook an earlier send of it."""
    sent_at, last, bad = {}, {}, 0
    for at, unique in enumerate(trace.events):
        event = unique.event
        if isinstance(event, MsgSend):
            sent_at[unique.id] = at
        elif isinstance(event, MsgEvent):
            pair = (event.snd, event.rcv)
            bad += sent_at[unique.id] < last.get(pair, -1)
            last[pair] = sent_at[unique.id]
        elif isinstance(event, HardKillEvent):
            last = {p: v for p, v in last.items() if event.name not in p}
    return bad


def test_the_host_fuzz_under_fifo_never_delivers_out_of_channel_order():
    """1,000 schedules; the same app under ``fully_random`` does."""
    app = make_chain_app(3, log_cap=4)
    fuzzer = _small_chain_fuzzer(app)
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    deliveries = 0
    sched = RandomScheduler(
        config, max_messages=64, invariant_check_interval=1,
        strategy=app.random_strategy,
    )
    for seed in range(1000):
        sched.seed = seed
        result = sched.execute(fuzzer.generate_fuzz_test(seed=seed))
        assert result.violation is None, seed
        assert _out_of_order(result.trace) == 0, seed
        deliveries += result.deliveries
    assert deliveries > 10_000
    loose = RandomScheduler(config, max_messages=64, strategy="fully_random")
    assert sum(
        _out_of_order(loose.execute(fuzzer.generate_fuzz_test(seed=s)).trace)
        for s in range(40)
    ) > 0


def test_the_fuzz_verb_hands_the_strategy_on(monkeypatch):
    seen = {}

    def fake_fuzz(config, fuzzer, **kw):
        seen.update(kw)

    monkeypatch.setattr("demi_tpu.runner.fuzz", fake_fuzz)
    rc = cli.main([
        "fuzz", "--app", "chain", "--nodes", "3", "--log-cap", "4",
        "--max-executions", "1",
    ])
    assert rc == 1 and seen["strategy"] == "srcdst_fifo"
    cli.main(["fuzz", "--app", "raft", "--nodes", "3", "--max-executions", "1"])
    assert seen["strategy"] == "fully_random"


# -- the continuous driver --------------------------------------------------

def test_the_continuous_driver_gives_the_one_shot_kernels_digest():
    """96 lanes through 32 resident: every lane but the first 32 starts in
    a refilled slot, its ``pool_head`` leaf with it."""
    app, cfg, fuzzer = build_workload(dict(CHAIN, bug="no_resend"))
    gen = lambda s: fuzzer.generate_fuzz_test(seed=s)  # noqa: E731
    one_shot = SweepDriver(app, cfg, gen).sweep(96, 96, mode="chunked")
    refilled = SweepDriver(app, cfg, gen).sweep(96, 32, mode="continuous")
    assert refilled.lanes_digest == one_shot.lanes_digest
    assert refilled.violations == one_shot.violations > 0
    assert refilled.overflow_lanes == 0


def test_the_build_stage_says_whether_a_kernel_keeps_head_bits():
    from demi_tpu import obs
    from demi_tpu.obs import spans

    chain = make_chain_app(3, log_cap=4)
    raft = make_raft_app(3)
    args = workload_args(CHAIN)
    spans._reset_setup()
    try:
        make_segment_kernel(chain, DeviceConfig.for_workload(chain, args), 8)
        make_segment_kernel(raft, DeviceConfig.for_workload(raft, args), 8)
        said = [
            e["args"]["fifo"] for e in obs.setup_ledger()["timeline"]
            if e["args"].get("what") == "make_segment_kernel"
        ]
        assert said == [True, False]
    finally:
        spans._reset_setup()


# -- DSLApp.spawn_count -----------------------------------------------------

def _counting_app(spawn_count):
    """Two actors that count what they handle in word 1, which a restart
    resets; word 0 is the spawn count. Judged where the run ends, the
    invariant's code is actor 0's ``10 * word 0 + word 1``."""
    def handler(actor_id, state, snd, msg):
        return state.at[1].set(state[1] + 1), jnp.zeros((1, 3), jnp.int32)

    return DSLApp(
        name="k", num_actors=2, state_width=2, msg_width=1, max_outbox=1,
        init_state=lambda i: np.zeros(2, np.int32), handler=handler,
        invariant=lambda states, alive: 10 * states[0, 0] + states[0, 1],
        invariant_at="quiescence", spawn_count=spawn_count,
    )


def test_the_runtime_counts_fresh_starts_on_both_tiers():
    app = _counting_app(0)
    name = app.actor_name(0)
    program = [
        Start(name, ctor=dsl_actor_factory(app, 0)),
        Send(name, MessageConstructor(lambda: (1,))), WaitQuiescence(),
        HardKill(name), Start(name, ctor=dsl_actor_factory(app, 0)),
        HardKill(name), Start(name, ctor=dsl_actor_factory(app, 0)),
        WaitQuiescence(),
    ]
    cfg = DeviceConfig.for_app(
        app, pool_capacity=8, max_steps=16, max_external_ops=16,
        record_trace=True,
    )
    lane = make_single_lane_trace_kernel(app, cfg)(
        lower_program(app, cfg, program), jax.random.PRNGKey(0)
    )
    # three lives, and nothing else of the first is left
    assert int(lane.deliveries) == 1 and int(lane.violation) == 30
    system = ControlledActorSystem()
    for _ in range(3):
        system.spawn(name, dsl_actor_factory(app, 0))
        system.hard_kill(name)
    system.spawn(name, dsl_actor_factory(app, 0))
    assert system.actors[name].state.tolist() == [4, 0]
    with pytest.raises(ValueError, match="spawn_count"):
        _counting_app(2)


def test_an_app_without_a_spawn_count_keeps_nothing():
    app = _counting_app(None)
    assert app.kept_words == () and make_raft_app(3).kept_words == ()
    system = ControlledActorSystem()
    name = app.actor_name(0)
    system.spawn(name, dsl_actor_factory(app, 0))
    system.hard_kill(name)
    assert not system.durable
