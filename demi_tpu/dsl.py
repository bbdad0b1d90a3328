"""The dual-tier application DSL: write an app once, run it on the host
oracle *and* inside the vmapped device kernels.

The reference tests arbitrary JVM applications by weaving interposition into
them (WeaveActor.aj). A TPU-native framework cannot interpose on arbitrary
Python, and more importantly the hot path — thousands of schedules advancing
in lockstep — requires actor handlers that XLA can trace. So in-framework
applications are written against this restricted DSL:

  - Actor state is a fixed-width ``int32[state_width]`` vector.
  - A message is a fixed-width ``int32[msg_width]`` record; ``msg[0]`` is the
    tag. On the host tier messages appear as plain int tuples.
  - The handler is a *pure, jax-traceable* function
        handler(actor_id, state, snd_id, msg) -> (state', outbox)
    with ``outbox: int32[max_outbox, 2 + msg_width]`` rows of
    ``(valid, dst, msg...)``. No Python control flow on traced values —
    use jnp.where / lax.switch.
  - Timers are self-sends whose tag is in ``timer_tags``; the runtime holds
    them as always-deliverable scheduler-controlled events (the reference
    converts JVM timers the same way, WeaveActor.aj:234-335). Delivering a
    timer consumes it; handlers re-arm by re-emitting.
  - The safety invariant is a jax-traceable predicate over all actor states
    returning an int32 violation fingerprint (0 = no violation).

The same handler drives both tiers, so host-vs-device differences isolate
engine bugs, not app bugs (the test strategy SURVEY.md §4 calls for).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

# Outbox row layout: (valid, dst, msg[0..W-1])
OUT_VALID = 0
OUT_DST = 1
OUT_MSG = 2


#: ``DSLApp.channels`` and the host ``RandomScheduler`` strategy of each.
_RANDOM_STRATEGY = {
    "any": "fully_random", "fifo": "srcdst_fifo", "datagram": "datagram",
}
CHANNELS = tuple(_RANDOM_STRATEGY)


@dataclass(frozen=True)
class DSLApp:
    """A complete application-under-test definition."""

    name: str
    num_actors: int
    state_width: int
    msg_width: int
    max_outbox: int
    # init_state(actor_id: int) -> int32[state_width]  (static python int id)
    init_state: Callable[[int], np.ndarray]
    # handler(actor_id, state, snd_id, msg) -> (state', outbox)
    handler: Callable
    # initial_msgs(actor_id: int) -> int32[k, 2+msg_width] rows emitted at spawn
    initial_msgs: Optional[Callable[[int], np.ndarray]] = None
    # invariant(states: int32[N, S], alive: bool[N]) -> int32 fingerprint (0 = ok)
    invariant: Optional[Callable] = None
    timer_tags: Tuple[int, ...] = ()
    tag_names: Tuple[str, ...] = ()  # for pretty-printing
    # Named wait predicates (states, alive) -> bool, referenced by
    # WaitCondition(cond_id=k) — the dual-tier form of the reference's
    # host-closure WaitCondition (ExternalEventInjector.scala:541-580):
    # the same jax predicate gates injection on the host oracle and ends
    # the dispatch segment inside the device kernels.
    conditions: Tuple[Callable, ...] = ()
    # When the invariant may be judged: "delivery" (after any delivery: a
    # state predicate that must hold throughout, raft's election safety)
    # or "quiescence" (only once nothing is deliverable and the program
    # has ended: a property of the outcome, broadcast's agreement, false
    # in the middle of any flood). A property of the invariant, so every
    # verb reads it here (``DeviceConfig.for_workload``, the host tier
    # through ``make_host_invariant``). Under "quiescence" a run ends at
    # quiescence or has no verdict: the program's final wait drains
    # whatever budget it carries, and a run cut by its step budget is
    # unfinished, not judged.
    invariant_at: str = "delivery"
    # Indices of ``state`` that a HardKill followed by a Start keeps: the
    # words an actor has on disk (VSR's recovery counter, a raft that
    # persists term and vote). Every other word is ``init_state``'s again,
    # and the restarted actor gets its ``initial_msgs`` as a first start
    # does; a soft Kill keeps all state, as ever. Both tiers keep them
    # (``device/core.py: external_effects``; ``ControlledActorSystem``'s
    # ``hard_kill`` and ``spawn``). Empty, a restart is a first start.
    durable: Tuple[int, ...] = ()
    # Named counts of a finished schedule, ``(name, states[N, S] -> int32)``:
    # what the protocol got done (views changed, replicas recovered). The
    # continuous sweep sums each over the lanes it retires and counts
    # ``sweep.app.<name>``, only while spans are live; the step kernel
    # carries nothing for them. An app with none costs and counts nothing.
    progress: Tuple[Tuple[str, Callable], ...] = ()
    # Index of a state word in which the runtime counts the actor's fresh
    # starts (its first Start, and every Start after a HardKill): 1 in a
    # first life, 2 after the first restart. Kept like a durable word and
    # raised before the actor handles anything, on both tiers, so a
    # handler can tell a restart from a first start even where the
    # earlier life handled no message and left no durable mark of its own
    # (chain replication's servers: a restarted one is no member).
    # ``init_state`` leaves the word 0. None: no such word, and the
    # program an app lowers to is what it was.
    spawn_count: Optional[int] = None
    # The order the network keeps: "any" (a pending message may be
    # delivered at any time: the reference's FullyRandom) or "fifo" (per
    # (sender, receiver) pair, non-timer messages are delivered in the
    # order they were sent: TCP links, Akka's guarantee, the reference's
    # SrcDstFIFO; timers stay individually choosable, and the external
    # sender is one source, so its sends to one actor are ordered too).
    # A property of the deployment, so like ``invariant_at`` no verb
    # chooses it: ``DeviceConfig.for_workload`` derives ``srcdst_fifo``
    # from it, the host fuzz its ``RandomScheduler`` strategy, and the
    # guided replay refuses a delivery that is not its channel's oldest.
    # Or "datagram": no order, and the network may deliver a pending
    # message and keep it (a later delivery repeats it) or lose it
    # (Lamport's model, an RPC layer that retries, UDP). Which, and how
    # often, is the scheduler's choice: at every dispatch step one more
    # draw is read against ``--dup-weight`` and ``--drop-weight`` under
    # the budgets ``--max-dups`` and ``--max-drops``
    # (``DeviceConfig.for_workload`` derives ``datagram`` and takes the
    # four from the workload; the host ``RandomScheduler`` draws the same
    # three outcomes). Only an actor's message is kept or lost: a timer
    # and an external send are delivered exactly once.
    channels: str = "any"
    # Actors the fault program may cut off and may not kill: a part of the
    # deployment whose survival the source takes as given (Kafka's
    # ZooKeeper quorum, ``apps/kafka.py``'s controller). A property of the
    # deployment, so like ``channels`` no verb has a flag: the fuzzer draws
    # no Kill or HardKill of such an actor (``cli.build_fuzzer`` names them
    # to it) and draws its partitions as any other's. Empty, every started
    # actor is a candidate, and the draws are what they were.
    unkillable: Tuple[int, ...] = ()

    def __post_init__(self):
        if self.channels not in CHANNELS:
            raise ValueError(
                f"channels must be one of {CHANNELS}, got {self.channels!r}"
            )
        if self.invariant_at not in ("delivery", "quiescence"):
            raise ValueError(
                f"invariant_at must be 'delivery' or 'quiescence', "
                f"got {self.invariant_at!r}"
            )
        if any(not 0 <= i < self.num_actors for i in self.unkillable):
            raise ValueError(
                f"unkillable actors {self.unkillable!r} must lie in "
                f"0..{self.num_actors - 1}"
            )
        if any(not 0 <= i < self.state_width for i in self.kept_words):
            raise ValueError(
                f"durable indices {self.durable!r} and spawn_count "
                f"{self.spawn_count!r} must lie in 0..{self.state_width - 1}"
            )

    @property
    def invariant_interval(self) -> int:
        """The deliveries between invariant checks, in both tiers'
        numbering: 1, or 0 for "at the run's end only"."""
        return 1 if self.invariant_at == "delivery" else 0

    @property
    def kept_words(self) -> Tuple[int, ...]:
        """The state words a HardKill followed by a Start keeps."""
        if self.spawn_count is None:
            return self.durable
        return self.durable + (self.spawn_count,)

    @property
    def random_strategy(self) -> str:
        """The host ``RandomScheduler``'s strategy for ``channels``."""
        return _RANDOM_STRATEGY[self.channels]

    # -- naming ------------------------------------------------------------
    def actor_name(self, actor_id: int) -> str:
        return f"{self.name}{actor_id}"

    def actor_id(self, name: str) -> int:
        prefix = self.name
        if not name.startswith(prefix):
            raise KeyError(name)
        return int(name[len(prefix):])

    def actor_names(self) -> Tuple[str, ...]:
        return tuple(self.actor_name(i) for i in range(self.num_actors))

    def is_timer_msg(self, msg) -> bool:
        return int(msg[0]) in self.timer_tags

    def tag_name(self, tag: int) -> str:
        if 0 <= tag < len(self.tag_names):
            return self.tag_names[tag]
        return str(tag)


# -- traced-index helpers for handlers --------------------------------------
#
# Handlers run inside the vmapped device kernels; a traced-index read/write
# (``state[i]`` / ``state.at[i].set``) there lowers to a batched gather or
# scatter, which XLA serializes on TPU (profiled at ms each inside the step
# scan — see device/ops.py). These one-hot forms are pure elementwise code.
# State/outbox vectors are narrow (tens of lanes), so the O(width) cost is
# negligible on every backend — handlers should ALWAYS use these for traced
# indices (static python-int indices are fine to index directly).

def vget(vec, i):
    """vec[i] for a traced scalar index into a 1-D vector."""
    oh = jnp.arange(vec.shape[0]) == i
    if vec.dtype == jnp.bool_:
        return jnp.any(oh & vec)
    return jnp.sum(jnp.where(oh, vec, 0))


def vset(vec, i, val, enabled=True):
    """Functional ``vec[i] = val if enabled`` for a traced scalar index."""
    oh = (jnp.arange(vec.shape[0]) == i) & enabled
    return jnp.where(oh, val, vec)


def vgather(vec, idx):
    """vec[idx] for a traced index *vector* -> same shape as ``idx``."""
    oh = idx[:, None] == jnp.arange(vec.shape[0])[None, :]
    if vec.dtype == jnp.bool_:
        return jnp.any(oh & vec[None, :], axis=1)
    return jnp.sum(jnp.where(oh, vec[None, :], 0), axis=1)


def seg_set(vec, start: int, seg):
    """Functional ``vec[start:start+len(seg)] = seg`` for a STATIC start.
    Static slice + concatenate instead of dynamic_update_slice: under vmap
    the latter lowers to scatter, which XLA serialises on the TPU."""
    return jnp.concatenate([vec[:start], seg, vec[start + seg.shape[0]:]])


def row_set(mat, i, row, enabled=True):
    """Functional ``mat[i] = row if enabled`` for a traced row index."""
    oh = (jnp.arange(mat.shape[0]) == i) & enabled
    return jnp.where(oh[:, None], row[None, :], mat)


def outbox_rows(max_outbox: int, msg_width: int, *rows: Sequence[int]) -> np.ndarray:
    """Helper for building a padded outbox array eagerly (init/initial_msgs)."""
    out = np.zeros((max_outbox, 2 + msg_width), dtype=np.int32)
    for i, row in enumerate(rows):
        out[i, OUT_VALID] = 1
        out[i, OUT_DST] = row[0]
        msg = row[1:]
        out[i, OUT_MSG : OUT_MSG + len(msg)] = msg
    return out


# Sender-id sentinel for externally injected messages (device encoding uses
# num_actors for EXTERNAL; host adapters translate).
def external_sender_id(app: DSLApp) -> int:
    return app.num_actors
