"""``DSLApp.durable``: the state words a ``HardKill`` followed by a
``Start`` keeps, on the device (the explore kernel and the replay kernel)
and on the host tier, by one rule; a soft ``Kill`` keeps all state, as
ever; and an app that declares none builds the program it always did (a
Python gate in ``external_effects``)."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from demi_tpu.apps.common import dsl_start_events, make_host_invariant
from demi_tpu.config import SchedulerConfig
from demi_tpu.device import DeviceConfig, make_explore_kernel, make_replay_kernel
from demi_tpu.device.continuous import make_init_kernel, make_segment_kernel
from demi_tpu.device.encoding import (
    empty_programs, lower_expected_trace, lower_program, stack_programs,
)
from demi_tpu.device.explore import ExtProgram
from demi_tpu.dsl import DSLApp
from demi_tpu.external_events import (
    HardKill, Kill, MessageConstructor, Send, Start, WaitQuiescence,
)
from demi_tpu.parallel.distributed import build_workload
from demi_tpu.runtime.actor import dsl_actor_factory
from demi_tpu.runtime.system import ControlledActorSystem
from demi_tpu.schedulers import RandomScheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DISK, MEMORY, SPARE = 0, 1, 2
PING = 1


def counter_app(durable):
    """Two actors that count the pings they get in two words, one of
    them ``durable``. Judged where the run ends, the invariant's code is
    actor 0's two counts, 10 x DISK + MEMORY: what a verdict says of the
    state, in the explore kernel, the replay kernel and the host oracle
    alike."""

    def handler(actor_id, state, snd, msg):
        bump = jnp.asarray([1, 1, 0], jnp.int32)
        return state + bump, jnp.zeros((1, 3), jnp.int32)

    return DSLApp(
        name="c", num_actors=2, state_width=3, msg_width=1, max_outbox=1,
        init_state=lambda i: np.asarray([0, 0, 7 + i], np.int32),
        handler=handler,
        invariant=lambda states, alive: 10 * states[0, DISK] + states[0, MEMORY],
        invariant_at="quiescence", durable=durable,
    )


def program(app, down, up=True):
    ping = Send(app.actor_name(0), MessageConstructor(lambda: (PING,)))
    again = [Start(app.actor_name(0), ctor=dsl_actor_factory(app, 0))] if up else []
    return dsl_start_events(app) + [
        ping, ping, WaitQuiescence(), down(app.actor_name(0)), *again,
        ping, WaitQuiescence(),
    ]


def device_cfg(app):
    return DeviceConfig.for_app(
        app, pool_capacity=8, max_steps=16, max_external_ops=12,
        invariant_interval=app.invariant_interval,
    )


CASES = [
    # (durable, how it goes down, the code where the run ends)
    ((DISK,), HardKill, 31),     # the disk's count survives, the other restarts
    ((), HardKill, 11),          # nothing declared: a restart is a first start
    ((DISK, MEMORY), HardKill, 33),
    ((DISK,), Kill, 33),         # a soft kill keeps all state, durable or not
    ((), Kill, 33),
]


@pytest.mark.parametrize("durable,down,code", CASES)
def test_on_the_device(durable, down, code):
    app = counter_app(durable)
    cfg = device_cfg(app)
    progs = stack_programs([lower_program(app, cfg, program(app, down))] * 4)
    res = make_explore_kernel(app, cfg)(
        progs, jax.random.split(jax.random.PRNGKey(0), 4)
    )
    assert np.asarray(res.violation).tolist() == [code] * 4
    assert np.asarray(res.deliveries).tolist() == [3] * 4


@pytest.mark.parametrize("durable,down,code", CASES)
def test_on_the_host_and_through_the_replay_kernel(durable, down, code):
    app = counter_app(durable)
    cfg = device_cfg(app)
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    events = program(app, down)
    result = RandomScheduler(config, seed=3, max_messages=32).execute(events)
    assert result.violation is not None and result.violation.code == code
    records = lower_expected_trace(
        app, cfg,
        result.trace.filter_failure_detector_messages()
        .filter_checkpoint_messages().subsequence_intersection(events),
        events, max_records=cfg.max_steps + cfg.max_external_ops,
    )
    replayed = make_replay_kernel(app, cfg)(
        records[None], jax.random.split(jax.random.PRNGKey(0), 1)
    )
    assert int(replayed.violation[0]) == code
    assert int(replayed.ignored_absent[0]) == 0


def test_the_other_words_are_the_init_states_again():
    app = counter_app((DISK,))
    system = ControlledActorSystem()
    name = app.actor_name(1)
    system.spawn(name, dsl_actor_factory(app, 1))
    actor = system.actors[name]
    actor.state[:] = (5, 6, 0)
    system.hard_kill(name)
    assert name not in system.actors and list(system.durable) == [name]
    system.spawn(name, dsl_actor_factory(app, 1))
    assert system.actors[name].state.tolist() == [5, 0, 8]
    assert not system.durable                  # handed over, not kept
    # never hard-killed: nothing is on disk for a first start to find
    fresh = ControlledActorSystem()
    fresh.spawn(name, dsl_actor_factory(app, 1))
    assert fresh.actors[name].state.tolist() == [0, 0, 8]


def test_a_checkpoint_holds_the_disk_of_a_node_that_is_down():
    app = counter_app((DISK,))
    system = ControlledActorSystem()
    name = app.actor_name(0)
    system.spawn(name, dsl_actor_factory(app, 0))
    system.actors[name].state[DISK] = 4
    system.hard_kill(name)
    snap = system.checkpoint()
    system.spawn(name, dsl_actor_factory(app, 0))
    assert system.actors[name].state[DISK] == 4
    system.restore(snap)
    assert name not in system.actors
    system.spawn(name, dsl_actor_factory(app, 0))
    assert system.actors[name].state[DISK] == 4


def test_a_durable_index_outside_the_state_is_refused():
    with pytest.raises(ValueError, match="durable"):
        counter_app((3,))


def _segment_text(app, cfg, lanes=4):
    state = make_init_kernel(app, cfg)(
        jax.random.split(jax.random.PRNGKey(0), lanes)
    )
    progs = ExtProgram(*(jnp.asarray(x) for x in empty_programs(cfg, lanes)))
    return make_segment_kernel(app, cfg, 8).lower(
        state, progs, jnp.zeros(lanes, jnp.int32)
    ).as_text()


@pytest.mark.parametrize("index_mode", ["onehot", "scatter"])
def test_an_app_with_no_durable_word_lowers_to_the_program_it_had(index_mode):
    """``raft5-multivote``'s segment: the field at its default, set to
    the empty tuple, and (the gate has teeth) naming a word."""
    with open(os.path.join(
        ROOT, "benchmarks", "configs", "raft5-multivote.json"
    )) as f:
        workload = json.load(f)["workload"]
    app, cfg, _ = build_workload(dict(workload))
    cfg = dataclasses.replace(cfg, index_mode=index_mode)
    assert app.durable == () and app.progress == ()
    default = _segment_text(app, cfg)
    assert _segment_text(dataclasses.replace(app, durable=()), cfg) == default
    assert _segment_text(dataclasses.replace(app, durable=(1,)), cfg) != default
