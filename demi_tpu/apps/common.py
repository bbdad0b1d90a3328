"""Glue between DSL apps and the host tier: invariant adaptation, Start
prefixes, and fuzzer message generation.

The device tier evaluates ``app.invariant(states, alive)`` directly as a
jitted predicate; here we adapt the same function to the host oracle's
checkpoint-based invariant signature (externals, {name -> CheckpointReply})
(reference signature: TestOracle.scala:27).
"""

from __future__ import annotations

import random as _random
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from ..dsl import DSLApp
from ..external_events import Send, Start, constant_message
from ..minimization.test_oracle import IntViolation
from ..runtime.actor import dsl_actor_factory

def _jitted_invariant(app: DSLApp):
    # Cached on the app instance (id(app)-keyed globals collide after GC).
    fn = getattr(app, "_jitted_invariant", None)
    if fn is None:
        from ..utils.hostjit import host_jit

        fn = host_jit(app.invariant)
        object.__setattr__(app, "_jitted_invariant", fn)
    return fn


def _jitted_condition(app: DSLApp, cond_id: int):
    """Host evaluation of DSLApp.conditions[cond_id] (WaitCondition's
    dual-tier form); cached per app like the invariant."""
    cache = getattr(app, "_jitted_conditions", None)
    if cache is None:
        cache = {}
        object.__setattr__(app, "_jitted_conditions", cache)
    fn = cache.get(cond_id)
    if fn is None:
        from ..utils.hostjit import host_jit

        fn = cache[cond_id] = host_jit(app.conditions[cond_id])
    return fn


def make_host_invariant(app: DSLApp) -> Callable:
    """Adapt the app's jitted (states, alive) -> int32 predicate to the host
    checkpoint-based invariant. Actors absent/crashed/isolated -> not alive."""
    assert app.invariant is not None

    def invariant(externals, checkpoint) -> Optional[IntViolation]:
        states = np.zeros((app.num_actors, app.state_width), np.int32)
        alive = np.zeros(app.num_actors, bool)
        for i in range(app.num_actors):
            reply = checkpoint.get(app.actor_name(i))
            if reply is not None and reply.data is not None:
                states[i] = np.asarray(reply.data, np.int32)
                alive[i] = True
        code = int(_jitted_invariant(app)(states, alive))
        if code != 0:
            affected = tuple(
                app.actor_name(i) for i in range(app.num_actors) if alive[i]
            )
            return IntViolation(code, affected)
        return None

    # When it may be judged travels with the invariant (the schedulers
    # read it through ``SchedulerConfig.quiescence_invariant``).
    invariant.at_quiescence = app.invariant_at == "quiescence"
    return invariant


def dsl_start_events(app: DSLApp) -> List[Start]:
    """Start prefix spawning every actor of the app."""
    return [
        Start(app.actor_name(i), ctor=dsl_actor_factory(app, i))
        for i in range(app.num_actors)
    ]


class DSLSendGenerator:
    """Fuzzer message generator sending app-provided messages to random alive
    actors, or all to ``target`` (an actor name: a protocol whose clients
    speak to one node). ``make_msg(rng, counter) -> tuple`` builds the
    payload."""

    def __init__(
        self,
        app: DSLApp,
        make_msg: Callable[[_random.Random, int], tuple],
        target: Optional[str] = None,
    ):
        self.app = app
        self.make_msg = make_msg
        self.target = target
        self._counter = 0

    def reset(self) -> None:
        self._counter = 0

    def generate_row(
        self, rng: _random.Random, alive: Sequence[str]
    ) -> Optional[Tuple[str, tuple]]:
        """The send as the fuzzer records it: (target name, payload).
        With a ``target`` the draws are the same (a program's other
        events stay where a random addressee left them), and a send
        drawn while the target is down is futile: not sent, not
        counted."""
        if not alive:
            return None
        self._counter += 1
        msg = self.make_msg(rng, self._counter)
        if msg is None:
            return None
        drawn = rng.choice(list(alive))
        if self.target is None:
            return drawn, msg
        if self.target not in alive:
            self._counter -= 1
            return None
        return self.target, msg

    def generate(self, rng: _random.Random, alive: Sequence[str]) -> Optional[Send]:
        row = self.generate_row(rng, alive)
        if row is None:
            return None
        target, msg = row
        return Send(target, constant_message(msg))
