"""Host-tier actor API.

Interposition is *by construction*: actors only interact with the world
through the ``Context`` the runtime hands them, so every send/timer is
captured without any bytecode weaving (this replaces the reference's entire
L1 layer, WeaveActor.aj — see SURVEY.md §2.7).

Blocking ``ask`` exists at this tier as CPS sugar (``Context.ask``): the
asker names a continuation for the reply and is blocked — nothing else is
deliverable to it — until a matching reply arrives, which routes to the
continuation instead of ``receive``. This covers the reference's
blocked-actor tracking + PromiseActorRef interposition
(Instrumenter.scala:679-877) without temp-actor refs: replies are matched
by (sender, predicate) rather than by a woven promise ref. The *device*
tier stays CPS-by-construction (SURVEY.md §7.3) — handlers are total jax
functions and never block.
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Tuple

import numpy as np

from ..dsl import DSLApp, OUT_DST, OUT_MSG, OUT_VALID


class Context:
    """Capability object passed to receive(); the only way an actor can act.

    Sends are captured by the runtime and become scheduler-controlled pending
    events; nothing is delivered until a scheduler picks it.
    """

    def __init__(self, system, name: str):
        self._system = system
        self.name = name

    def send(self, dst: str, msg: Any) -> None:
        self._system._capture_send(self.name, dst, msg)

    def set_timer(self, msg: Any) -> None:
        """Register a timer: an always-deliverable self-event the scheduler
        may fire at any time (delivering it consumes it; re-arm by calling
        again)."""
        self._system._capture_timer(self.name, msg)

    def cancel_timer(self, msg: Any) -> None:
        self._system._cancel_timer(self.name, msg)

    def ask(
        self,
        dst: str,
        msg: Any,
        on_reply: Callable[["Context", Any], None],
        match: Optional[Callable[[Any], bool]] = None,
    ) -> None:
        """Blocking ask, CPS-style: send ``msg`` to ``dst`` and block this
        actor until a non-timer message from ``dst`` (satisfying ``match``
        if given) arrives; that reply is routed to ``on_reply(ctx, reply)``
        instead of ``receive``. Everything else addressed to this actor
        stays pending (not dropped) while blocked, exactly like the
        reference's ask interposition (Instrumenter.scala:679-877).

        ``on_reply`` may itself ``ask`` (chained asks). A reply never
        arriving is a quiescent deadlock — visible to invariants via the
        system's ``blocked_actors()`` and each actor's ``_blocked``-aware
        checkpoint (see ``ask_deadlock_invariant``)."""
        self.send(dst, msg)
        self._system.register_ask(self.name, dst, match, on_reply)

    def log(self, line: str) -> None:
        self._system._capture_log(self.name, line)

    def rng(self):
        """Deterministic per-delivery ``random.Random`` — the
        harness-sanctioned replacement for module-level random (lint
        rule ``unseeded-random``, ``demi_tpu lint``). Seeded by (actor,
        delivery uid), so every re-execution and strict replay draws the
        identical stream; the DEMI_SANITIZE traps never fire on it."""
        return self._system.delivery_rng(self.name)


class Actor:
    """Base class for host-tier (rich Python) application actors."""

    def on_start(self, ctx: Context) -> None:  # noqa: B027
        pass

    def receive(self, ctx: Context, snd: str, msg: Any) -> None:
        raise NotImplementedError

    def checkpoint_state(self) -> Any:
        """State snapshot for invariant checking (CheckpointReply payload)."""
        return None

    def durable_state(self) -> Any:
        """What survives a HardKill (the actor's disk), or None. The
        system hands it to ``restore_durable`` of the actor the next
        Start creates, before its ``on_start``."""
        return None

    def restore_durable(self, kept: Any) -> None:  # noqa: B027
        pass


class DSLActorAdapter(Actor):
    """Runs one actor of a DSLApp on the host oracle, calling the *same*
    jax-traceable handler the device kernels trace. The handler is jitted
    once per app (static shapes) so the host oracle stays fast."""

    def __init__(self, app: DSLApp, actor_id: int):
        self.app = app
        self.actor_id = actor_id
        self.state = np.asarray(app.init_state(actor_id), dtype=np.int32)
        assert self.state.shape == (app.state_width,), (
            f"init_state({actor_id}) shape {self.state.shape} != ({app.state_width},)"
        )

    def on_start(self, ctx: Context) -> None:
        if self.app.spawn_count is not None:
            # The runtime's count of fresh starts (``restore_durable`` ran
            # before this on a restart).
            self.state[self.app.spawn_count] += 1
        if self.app.initial_msgs is None:
            return
        rows = np.asarray(self.app.initial_msgs(self.actor_id), dtype=np.int32)
        self._emit(ctx, rows)

    def receive(self, ctx: Context, snd: str, msg: Any) -> None:
        snd_id = self._sender_id(snd)
        msg_arr = np.asarray(msg, dtype=np.int32)
        handler = _jitted_handler(self.app)
        new_state, outbox = handler(
            np.int32(self.actor_id), self.state, np.int32(snd_id), msg_arr
        )
        self.state = np.asarray(new_state, dtype=np.int32)
        self._emit(ctx, np.asarray(outbox, dtype=np.int32))

    def checkpoint_state(self) -> np.ndarray:
        return self.state.copy()

    def durable_state(self) -> Optional[np.ndarray]:
        if not self.app.kept_words:
            return None
        return self.state[list(self.app.kept_words)].copy()

    def restore_durable(self, kept: np.ndarray) -> None:
        self.state[list(self.app.kept_words)] = kept

    # -- helpers -----------------------------------------------------------
    def _sender_id(self, snd: str) -> int:
        try:
            return self.app.actor_id(snd)
        except (KeyError, ValueError):
            return self.app.num_actors  # external / synthetic sender

    def _emit(self, ctx: Context, rows: np.ndarray) -> None:
        for row in rows:
            if row[OUT_VALID] == 0:
                continue
            dst_id = int(row[OUT_DST])
            msg = tuple(int(x) for x in row[OUT_MSG:])
            if dst_id == self.actor_id and self.app.is_timer_msg(msg):
                ctx.set_timer(msg)
            else:
                ctx.send(self.app.actor_name(dst_id), msg)


def _jitted_handler(app: DSLApp):
    # Cached on the app instance itself — a global dict keyed by id(app)
    # collides when ids are reused after GC.
    fn = getattr(app, "_jitted_handler", None)
    if fn is None:
        from ..utils.hostjit import host_jit

        fn = host_jit(app.handler)
        object.__setattr__(app, "_jitted_handler", fn)
    return fn


def dsl_actor_factory(app: DSLApp, actor_id: int) -> Callable[[], Actor]:
    return lambda: DSLActorAdapter(app, actor_id)
