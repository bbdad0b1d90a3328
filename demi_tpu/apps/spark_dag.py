"""Mini DAG scheduler: the Spark-class fixture.

Stands in for the reference's Spark case study (BASELINE.json config 4:
"Spark DAGScheduler fuzz, job-completion invariant"; demi-applications
spark branch). Actor 0 is the master (DAGScheduler); the rest are workers.
A job is S stages of T tasks; the master launches each task twice
(speculative execution, as Spark does) and advances to the next stage when
the current stage's mask completes; after the last stage it declares the
job done.

Safety invariant (code 1): job_done ⇒ every task the master credited was
actually executed by some worker that is up — masters must not credit work
nobody holds. A worker that is killed, or hard-killed and restarted (its
executed set starts empty), holds nothing: under crash-recovery the
master, which resubmits nothing, can declare a job done whose work is gone.

Seeded bug ``bug="stale_task"``: the master ignores the stage field of
TASK_DONE and credits late/duplicate completions from earlier stages to the
*current* stage (the missing-epoch-check bug class the reference's Spark
study targets), so speculative duplicates from stage s complete stage s+1
without its tasks ever running.
"""

from __future__ import annotations

import random as _random
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..dsl import DSLApp, row_set, vget, vset
from .common import DSLSendGenerator

T_SUBMIT = 1
T_LAUNCH = 2  # (tag, stage, task)
T_DONE = 3  # (tag, stage, task)

MSG_W = 3

# Master state layout: [current_stage, job_done, credited_mask[stage 0..S-1]]
CUR = 0
DONE_FLAG = 1
MASKS = 2
# Worker state layout: [_, _, executed_mask[stage 0..S-1]] (same width).
# A stage's mask is one int32 word up to 31 tasks, and ceil(T / 32) words
# past that (``mask_words``): task t is bit ``t % 32`` of word ``t // 32``
# of its stage, stage s's words at MASKS + s * words. A SQL shuffle's
# default 200 tasks a stage are 7 words.
WORD_BITS = 32


def mask_words(tasks_per_stage: int) -> int:
    """int32 words of one stage's task mask."""
    return max(1, -(-tasks_per_stage // WORD_BITS))


def make_spark_app(
    num_workers: int,
    num_stages: int = 2,
    tasks_per_stage: int = 4,
    bug: Optional[str] = None,
    name: str = "s",
) -> DSLApp:
    n = num_workers + 1  # + master (actor 0)
    S = num_stages
    T = tasks_per_stage
    W = mask_words(T)
    state_width = MASKS + S * W
    max_outbox = 2 * T + 1
    # Per state word: the stage whose mask it is part of (-1: none) and
    # what it reads when every task it holds is in (a whole word is -1).
    stage_of = np.full(state_width, -1, np.int32)
    stage_of[MASKS:] = np.repeat(np.arange(S, dtype=np.int32), W)
    full_words = np.zeros(state_width, np.uint32)
    for w in range(W):
        bits = min(WORD_BITS, T - WORD_BITS * w)
        full_words[MASKS + w : state_width : W] = (1 << bits) - 1
    full_words = full_words.astype(np.int32)

    def _task_bit(stage, task):
        """``(state index, bit)`` of ``task`` in the mask of ``stage``
        (clipped to a stage that is there); bit 0 for no such task."""
        known = (task >= 0) & (task < T)
        word = jnp.clip(task >> 5, 0, W - 1)  # task // WORD_BITS
        bit = jnp.where(known, jnp.int32(1) << (task & 31), 0)
        return MASKS + jnp.clip(stage, 0, S - 1) * W + word, bit

    def init_state(actor_id: int) -> np.ndarray:
        return np.zeros(state_width, np.int32)

    def _launch_rows(actor_id, stage):
        """Master launches all tasks of ``stage`` twice (speculative)."""
        k = max_outbox
        rows_task = jnp.arange(k, dtype=jnp.int32) % jnp.int32(max(T, 1))
        copy = (jnp.arange(k, dtype=jnp.int32) >= T).astype(jnp.int32)
        valid = (jnp.arange(k) < 2 * T).astype(jnp.int32)
        worker = 1 + (rows_task + copy) % jnp.int32(num_workers)
        zeros = jnp.zeros(k, jnp.int32)
        return jnp.stack(
            [valid, worker, zeros + T_LAUNCH, zeros + stage, rows_task],
            axis=1,
        )

    def on_submit(actor_id, state, snd, msg):
        is_master = actor_id == 0
        fresh = state[CUR] == 0
        launch = is_master & fresh & (state[DONE_FLAG] == 0)
        out = _launch_rows(actor_id, jnp.int32(0))
        out = jnp.where(launch, out, jnp.zeros_like(out))
        return state, out

    def on_launch(actor_id, state, snd, msg):
        stage, task = msg[1], msg[2]
        is_worker = actor_id != 0
        at, bit = _task_bit(stage, task)
        state = vset(state, at, vget(state, at) | bit, is_worker)
        out = jnp.zeros((max_outbox, 2 + MSG_W), jnp.int32)
        row = jnp.stack(
            [jnp.int32(1), jnp.int32(0), jnp.int32(T_DONE), stage, task]
        )
        out = row_set(out, 0, jnp.where(is_worker, row, out[0]))
        return state, out

    def on_done(actor_id, state, snd, msg):
        stage, task = msg[1], msg[2]
        is_master = actor_id == 0
        cur = state[CUR]
        running = (state[DONE_FLAG] == 0) & (cur < S)
        if bug == "stale_task":
            # BUG: stage field ignored — late completions credit the
            # current stage.
            relevant = is_master & running
        else:
            relevant = is_master & running & (stage == cur)
        at, bit = _task_bit(cur, task)
        state = vset(state, at, vget(state, at) | jnp.where(relevant, bit, 0))
        in_cur = jnp.asarray(stage_of) == jnp.clip(cur, 0, S - 1)
        stage_complete = relevant & jnp.all(
            ~in_cur | (state == jnp.asarray(full_words))
        )
        next_stage = cur + 1
        state = vset(state, CUR, jnp.where(stage_complete, next_stage, cur))
        job_done = stage_complete & (next_stage >= S)
        state = vset(state, DONE_FLAG,
            jnp.where(job_done, 1, state[DONE_FLAG])
        )
        launch_next = stage_complete & (next_stage < S)
        out = _launch_rows(actor_id, next_stage)
        out = jnp.where(launch_next, out, jnp.zeros_like(out))
        return state, out

    def handler(actor_id, state, snd, msg):
        tag = jnp.clip(msg[0], 1, 3) - 1
        return jax.lax.switch(
            tag, [on_submit, on_launch, on_done], actor_id, state, snd, msg
        )

    def invariant(states, alive):
        """job_done ⇒ every credited task was executed by some worker
        that is up."""
        master = states[0]
        credited = master[MASKS:]
        # A worker that is down (killed, or stopped and not restarted)
        # holds nothing, as the host oracle's checkpoint has it (no
        # reply from a crashed or isolated actor): without the mask the
        # device read a dead worker's last state and the tiers could
        # part on a clean lane.
        executed = jnp.where(alive[1:, None], states[1:, MASKS:], 0)
        executed_union = jnp.bitwise_or.reduce(executed, axis=0)
        phantom = credited & ~executed_union
        bad = (master[DONE_FLAG] == 1) & jnp.any(phantom != 0) & alive[0]
        return jnp.where(bad, jnp.int32(1), jnp.int32(0))

    return DSLApp(
        name=name,
        num_actors=n,
        state_width=state_width,
        msg_width=MSG_W,
        max_outbox=max_outbox,
        init_state=init_state,
        handler=handler,
        invariant=invariant,
        tag_names=("", "SubmitJob", "LaunchTask", "TaskDone"),
    )


def spark_send_generator(app: DSLApp) -> DSLSendGenerator:
    """External SubmitJob, addressed to the master (a worker ignores
    one, and a program holds at most one)."""

    def make_msg(rng: _random.Random, counter: int):
        if counter > 1:
            return None  # one job per program
        return (T_SUBMIT, 0, 0)

    return DSLSendGenerator(app, make_msg, target=app.actor_name(0))
