"""Device replay peek (DeviceConfig.replay_peek): the batched-oracle twin
of STSScheduler.allow_peek / IntervalPeekScheduler — an expected delivery
with no pending match gets a chance to be ENABLED by delivering pending
entries FIFO; the prefix is kept on success, the lane rolls back
wholesale on failure."""

import numpy as np

import jax

from demi_tpu.apps.broadcast import make_broadcast_app
from demi_tpu.apps.common import dsl_start_events, make_host_invariant
from demi_tpu.config import SchedulerConfig
from demi_tpu.device import DeviceConfig
from demi_tpu.device.encoding import lower_expected_trace
from demi_tpu.device.replay import make_replay_kernel
from demi_tpu.events import MsgEvent
from demi_tpu.external_events import MessageConstructor, Send, WaitQuiescence
from demi_tpu.schedulers import BasicScheduler
from demi_tpu.schedulers.replay import STSScheduler
from demi_tpu.trace import EventTrace


def _doctored_fixture():
    """Reliable 3-node broadcast trace with the ENABLING delivery cut:
    the external bcast delivery to n0 is removed, so every relay record
    after it is expected-but-absent until a peek re-delivers it."""
    app = make_broadcast_app(3, reliable=True)
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    program = dsl_start_events(app) + [
        Send(app.actor_name(0), MessageConstructor(lambda: (1, 0))),
        WaitQuiescence(),
    ]
    recorded = BasicScheduler(config).execute(program)
    assert recorded.violation is None
    full = recorded.trace.subsequence_intersection(program)
    enabler = next(
        i for i, u in enumerate(full.events)
        if isinstance(u.event, MsgEvent) and u.event.is_external
    )
    doctored = EventTrace(
        [u for i, u in enumerate(full.events) if i != enabler],
        list(full.original_externals or program),
    )
    full_deliveries = sum(
        1 for u in recorded.trace.events if isinstance(u.event, MsgEvent)
    )
    return app, config, program, doctored, full_deliveries


def test_replay_peek_enables_absent_expected():
    app, config, program, doctored, full_deliveries = _doctored_fixture()
    base = DeviceConfig.for_app(
        app, pool_capacity=64, max_steps=64, max_external_ops=8
    )
    records = np.stack(
        [lower_expected_trace(app, base, doctored, program, max_records=64)]
    )
    keys = jax.random.split(jax.random.PRNGKey(0), 1)

    no_peek = make_replay_kernel(app, base)(records, keys)
    assert int(no_peek.peeked[0]) == 0
    assert int(no_peek.ignored_absent[0]) > 0
    assert int(no_peek.deliveries[0]) < full_deliveries

    import dataclasses

    peek_cfg = dataclasses.replace(base, replay_peek=3)
    peeked = make_replay_kernel(app, peek_cfg)(records, keys)
    assert int(peeked.peeked[0]) >= 1
    assert int(peeked.ignored_absent[0]) == 0
    # The peek re-delivered the cut enabler, then every relay matched:
    # the full delivery count is restored.
    assert int(peeked.deliveries[0]) == full_deliveries


def test_replay_peek_matches_host_sts_peek():
    """Same doctored schedule through the host STSScheduler with
    allow_peek: both tiers enable the absent relays and end with the same
    delivery count."""
    app, config, program, doctored, full_deliveries = _doctored_fixture()
    sts = STSScheduler(config, doctored, allow_peek=True)
    result = sts.replay(doctored, program)
    assert sts.peeked_prefixes >= 1
    host_deliveries = sum(
        1 for u in result.trace.events if isinstance(u.event, MsgEvent)
    )
    assert host_deliveries == full_deliveries


def test_replay_peek_rolls_back_on_failure():
    """An expected delivery that no peek can enable (its message never
    existed) must leave the lane exactly where ignore-absent would:
    deliveries equal, the probe prefix rolled back."""
    import dataclasses

    app = make_broadcast_app(3, reliable=True)
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    program = dsl_start_events(app) + [
        Send(app.actor_name(0), MessageConstructor(lambda: (1, 0))),
        WaitQuiescence(),
    ]
    recorded = BasicScheduler(config).execute(program)
    full = recorded.trace.subsequence_intersection(program)
    # Forge an expected delivery of a message id nobody ever sends.
    from demi_tpu.events import Unique

    forged = EventTrace(list(full.events), list(full.original_externals or ()))
    bogus = Unique(
        MsgEvent(app.actor_name(1), app.actor_name(2), (1, 7)), 999_999
    )
    forged.events.insert(len(forged.events) // 2, bogus)
    base = DeviceConfig.for_app(
        app, pool_capacity=64, max_steps=64, max_external_ops=8
    )
    records = np.stack(
        [lower_expected_trace(app, base, forged, program, max_records=64)]
    )
    keys = jax.random.split(jax.random.PRNGKey(0), 1)
    plain = make_replay_kernel(app, base)(records, keys)
    peeky = make_replay_kernel(
        app, dataclasses.replace(base, replay_peek=3)
    )(records, keys)
    assert int(peeky.peeked[0]) == 0  # nothing could enable it
    assert int(peeky.deliveries[0]) == int(plain.deliveries[0])
    assert int(peeky.violation[0]) == int(plain.violation[0])
    assert int(peeky.ignored_absent[0]) == int(plain.ignored_absent[0])
