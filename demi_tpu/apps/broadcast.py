"""Reliable broadcast: the canonical fixture app.

Stands in for the reference's out-of-repo demi-applications test apps
(SURVEY.md §4; BASELINE.json config 5: "synthetic reliable-broadcast,
64 actors"). Protocol: on first receipt of BCAST(id), mark it delivered and
relay it to every other node. Safety invariant (checked at quiescence):
agreement — all alive nodes have delivered the same set.

``reliable=False`` seeds the classic bug: no relay, so killing the
first receiver mid-broadcast strands the message at a subset of nodes.

The handler is jax-traceable and drives both the host oracle and the
device kernels unchanged.
"""

from __future__ import annotations

import random as _random
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..dsl import DSLApp
from .common import DSLSendGenerator

TAG_BCAST = 1
MAX_IDS = 30  # broadcast ids fit one int32 bitmask


def make_broadcast_app(
    num_actors: int, reliable: bool = True, name: str = "n"
) -> DSLApp:
    state_width = 1  # state[0] = bitmask of delivered broadcast ids
    msg_width = 2  # (tag, bcast_id)
    max_outbox = num_actors

    def init_state(actor_id: int) -> np.ndarray:
        return np.zeros(state_width, np.int32)

    def handler(actor_id, state, snd, msg):
        tag, bid = msg[0], msg[1]
        bit = jnp.where(
            (bid >= 0) & (bid < MAX_IDS), jnp.int32(1) << bid, jnp.int32(0)
        )
        already = (state[0] & bit) != 0
        deliver = (tag == TAG_BCAST) & ~already & (bit != 0)
        # Index-free write (width-1 state): keeps the handler free of
        # scatter ops, which XLA serialises on the TPU (device/ops.py).
        new_state = jnp.where(deliver, state[0] | bit, state[0])[None]
        dsts = jnp.arange(max_outbox, dtype=jnp.int32)
        if reliable:
            valid = deliver & (dsts != actor_id) & (dsts < num_actors)
        else:
            valid = jnp.zeros_like(dsts, dtype=bool)
        outbox = jnp.stack(
            [
                valid.astype(jnp.int32),
                dsts,
                jnp.full((max_outbox,), TAG_BCAST, jnp.int32),
                jnp.full((max_outbox,), bid, jnp.int32),
            ],
            axis=1,
        )
        return new_state, outbox

    def invariant(states, alive):
        """Agreement: any two alive nodes with different delivered sets is a
        violation (code 1)."""
        masks = states[:, 0]
        disagree = (
            (masks[:, None] != masks[None, :]) & alive[:, None] & alive[None, :]
        )
        return jnp.where(jnp.any(disagree), jnp.int32(1), jnp.int32(0))

    return DSLApp(
        name=name,
        num_actors=num_actors,
        state_width=state_width,
        msg_width=msg_width,
        max_outbox=max_outbox,
        init_state=init_state,
        handler=handler,
        invariant=invariant,
        invariant_at="quiescence",
        tag_names=("", "BCAST"),
    )


def broadcast_send_generator(app: DSLApp) -> DSLSendGenerator:
    def make_msg(rng: _random.Random, counter: int) -> Optional[Tuple[int, int]]:
        # Ids must stay distinct within one program (aliased ids would mask
        # stranded broadcasts from the agreement invariant); the generator
        # resets per program, and the fuzzer's futile-guard handles a dry
        # generator gracefully.
        if counter > MAX_IDS:
            return None
        return (TAG_BCAST, counter - 1)

    return DSLSendGenerator(app, make_msg)
