"""Vmapped random schedule exploration: the device-tier RandomScheduler.

One lane = one candidate schedule. Each scan step either injects one
external op (injection segments are atomic w.r.t. dispatch, matching the
host BaseScheduler) or delivers one uniformly-chosen deliverable pool entry.
``vmap`` advances a whole batch of lanes per XLA step; the driver shards the
batch axis over the TPU mesh (demi_tpu/parallel).

Replaces the reference hot loop (SURVEY.md §3.1: ~1 ms/message of JVM
synchronization) with a few fused gathers/scatters per delivered message
across thousands of lanes at once.
"""

from __future__ import annotations

import time
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..dsl import DSLApp
from . import ops
from .core import (
    OP_END,
    OP_WAIT,
    OP_WAITCOND,
    ST_DISPATCH,
    ST_DONE,
    ST_INJECT,
    ST_UNFINISHED,
    ST_VIOLATION,
    DeviceConfig,
    RowProposal,
    ScheduleState,
    _append_record,
    alive_mask,
    check_invariant,
    delivery_effects,
    deliverable_mask,
    external_effects,
    fifo_head_mask,
    init_state,
    insert_rows,
)


class ExtProgram(NamedTuple):
    """Per-lane external program, op-encoded (see core.py)."""

    op: jnp.ndarray  # [E] int32
    a: jnp.ndarray  # [E] int32
    b: jnp.ndarray  # [E] int32
    msg: jnp.ndarray  # [E, W] int32


class LaneResult(NamedTuple):
    status: jnp.ndarray  # int32
    violation: jnp.ndarray  # int32 (0 = none)
    deliveries: jnp.ndarray  # int32
    trace: jnp.ndarray  # [T, rec_width] (zero-size when not recording)
    trace_len: jnp.ndarray  # int32
    # uint32 fingerprint of the delivered sequence (core.ScheduleState
    # .sched_hash): equal hashes = identical schedules, so sweeps can
    # report UNIQUE schedules explored, not just lanes swept.
    sched_hash: jnp.ndarray  # uint32


def broadcast_program(prog: ExtProgram, b: int) -> ExtProgram:
    """One lowered external program broadcast across a lane batch
    (NumPy views, no copies) — the ONE batch-layout rule shared by the
    DPOR frontier driver and the fleet worker's remote round execution
    (demi_tpu/fleet), so a leased round's program rows mean exactly
    what the coordinator's would."""
    return ExtProgram(
        *(
            np.broadcast_to(np.asarray(x), (b,) + np.asarray(x).shape)
            for x in prog
        )
    )


def _precomputed(app: DSLApp, cfg: DeviceConfig):
    n = cfg.num_actors
    init_states = np.stack(
        [np.asarray(app.init_state(i), np.int32) for i in range(n)]
    )
    if app.initial_msgs is not None:
        rows = [np.asarray(app.initial_msgs(i), np.int32) for i in range(n)]
        k0 = max(r.shape[0] for r in rows)
        initial_rows = np.zeros((n, k0, 2 + cfg.msg_width), np.int32)
        for i, r in enumerate(rows):
            initial_rows[i, : r.shape[0]] = r
    else:
        initial_rows = np.zeros((n, 0, 2 + cfg.msg_width), np.int32)
    return jnp.asarray(init_states), jnp.asarray(initial_rows)


def _injection_phase(
    state: ScheduleState,
    cfg: DeviceConfig,
    app: DSLApp,
    prog: ExtProgram,
    initial_rows,
    init_states,
    injecting,
):
    """The masked injection half of a fused step (inert unless `injecting`:
    op -> OP_END): applies the current external op's effects and all segment
    bookkeeping (budget/final/cond), returning the proposed pool rows for
    the shared insert. Shared verbatim by the sequential step and the
    round-delivery step (rounds.py) so the two kernels cannot drift."""
    oh = cfg.use_onehot
    e = prog.op.shape[0]
    cur = jnp.clip(state.ext_cursor, 0, e - 1)
    exhausted = state.ext_cursor >= e
    cur_op = ops.get_scalar(prog.op, cur, oh)
    op = jnp.where(injecting & ~exhausted, cur_op, OP_END)
    state, inj_rows, inj_rec, inj_enabled = external_effects(
        state, cfg, app, initial_rows, init_states,
        op,
        ops.get_scalar(prog.a, cur, oh),
        ops.get_scalar(prog.b, cur, oh),
        ops.get_row(prog.msg, cur, oh),
    )
    new_cursor = state.ext_cursor + (injecting & ~exhausted).astype(jnp.int32)
    raw_op = jnp.where(exhausted, OP_END, cur_op)
    is_wait_like = (raw_op == OP_WAIT) | (raw_op == OP_WAITCOND)
    to_dispatch = injecting & (
        is_wait_like | (raw_op == OP_END) | (new_cursor >= e)
    )
    # Bounded quiescence: a WAIT op carries its budget in field `a`, a
    # WAITCOND in field `b` (`a` is its condition id); 0 = strict. A
    # final drain — entered via OP_END *or* by running off the end of
    # a full-length program — is unlimited (stale budgets must not cap
    # it).
    seg_budget = jnp.where(
        injecting,
        jnp.where(
            raw_op == OP_WAIT,
            ops.get_scalar(prog.a, cur, oh),
            jnp.where(
                raw_op == OP_WAITCOND,
                ops.get_scalar(prog.b, cur, oh),
                jnp.where(
                    (raw_op == OP_END) | (new_cursor >= e),
                    0,
                    state.seg_budget,
                ),
            ),
        ),
        state.seg_budget,
    ).astype(jnp.int32)
    # Host-parity run-end semantics (reference: execution ends with the
    # segment of the LAST external event): the segment we're entering is
    # final if this op is OP_END / past-the-end, or a WAIT/WAITCOND with
    # nothing but OP_END after it.
    next_cur = jnp.clip(new_cursor, 0, e - 1)
    next_op = jnp.where(
        new_cursor >= e, OP_END, ops.get_scalar(prog.op, next_cur, oh)
    )
    final_seg = to_dispatch & (
        (raw_op == OP_END)
        | (new_cursor >= e)
        | (is_wait_like & (next_op == OP_END))
    )
    if app.invariant_at == "quiescence":
        # A run ends at quiescence or has no verdict: the final segment
        # drains whatever budget its wait carries (the bounded waits
        # before it keep theirs). Host twin: BaseScheduler._run_program.
        seg_budget = jnp.where(final_seg, 0, seg_budget).astype(jnp.int32)
    state = state._replace(
        ext_cursor=new_cursor,
        seg_budget=seg_budget,
        seg_start=jnp.where(
            to_dispatch, state.deliveries, state.seg_start
        ).astype(jnp.int32),
        final_seg=jnp.where(to_dispatch, final_seg, state.final_seg),
        seg_cond=jnp.where(
            to_dispatch,
            jnp.where(
                raw_op == OP_WAITCOND,
                ops.get_scalar(prog.a, cur, oh),
                jnp.int32(-1),
            ),
            state.seg_cond,
        ).astype(jnp.int32),
    )
    return state, inj_rows, inj_rec, inj_enabled, to_dispatch


def _segment_cond_met(state: ScheduleState, app: DSLApp, dispatching):
    """WaitCondition gating: True when this dispatch segment's condition
    (seg_cond >= 0) currently holds. The host checks the condition BEFORE
    each delivery and ends the segment without delivering once it holds;
    masking every candidate reproduces that exactly (the quiescence test
    sees no deliverable and flips the segment)."""
    if not app.conditions:
        return jnp.bool_(False)
    branches = [
        (lambda s, fn=fn: fn(s.actor_state, alive_mask(s))
         .astype(jnp.bool_))
        for fn in app.conditions
    ]
    cid = jnp.clip(state.seg_cond, 0, len(branches) - 1)
    return (
        (state.seg_cond >= 0)
        & jax.lax.switch(cid, branches, state)
        & dispatching
    )


def _datagram_outcome(state: ScheduleState, cfg: DeviceConfig, idx, key):
    """``(keep, discard)`` for the delivery of pool entry ``idx`` over
    datagram channels: one uniform read against the two weights. The
    first ``dup_weight`` of it keeps the message pending while the lane's
    ``max_dups`` lasts, the next ``drop_weight`` loses it while
    ``max_drops`` lasts, the rest (and a draw whose budget is spent)
    delivers as ever. Only an actor's message has an outcome: a timer and
    an external send (the fuzzer can send twice itself) are delivered
    exactly once. Host twin: ``RandomScheduler.choose_outcome``."""
    oh = cfg.use_onehot
    safe_idx = jnp.minimum(idx, cfg.pool_capacity - 1)
    from_actor = (
        (idx < cfg.pool_capacity)
        & ~ops.get_scalar(state.pool_timer, safe_idx, oh)
        & (ops.get_scalar(state.pool_src, safe_idx, oh) < cfg.num_actors)
    )
    u = jax.random.uniform(key)
    keep = from_actor & (u < cfg.dup_weight) & (state.dups < cfg.max_dups)
    discard = (
        from_actor & (u >= cfg.dup_weight)
        & (u < cfg.dup_weight + cfg.drop_weight)
        & (state.drops < cfg.max_drops)
    )
    return keep, discard


def make_step_fn(app: DSLApp, cfg: DeviceConfig):
    """The fused, branchless step: injection and dispatch effects are both
    computed with masks (inert op / invalid index for the inactive side) and
    their pool inserts merge into ONE insert_rows pass per step.

    Under vmap a ``lax.cond`` on a lane's own predicate executes both
    branches anyway, so the old two-branch form paid the insert machinery
    twice per step (two prefix sums, then on a CPU a searchsorted and 8
    scatters, on a TPU one [K, pool] compare for the packed word and K
    whole-row selects over the pool: ``core.insert_rows``); profiling
    shows the insert dominates step cost. Fusing removes a full insert
    pass and both cond selects.

    The step's one real branch is inside that insert, and only where it
    carries many rows (``core._short_insert_built``): its index, "how many
    lanes of the batch insert more than ``INSERT_SHORT_ROWS`` rows in this
    step: none, up to ``INSERT_BURST_LANES``, more", is one scalar for the
    whole batch (a ``custom_vmap`` rule counts them), so the compiled step
    holds a ``case`` of three regions: the short pass serves every step in
    which no resident lane sends a wide outbox; where a few do, the short
    pass serves the batch and a loop takes those lanes, one at a time,
    through the full [K, pool] pass; only where many burst in one step
    does the whole batch pay it.

    Over datagram channels (``cfg.datagram``) the dispatch side draws an
    outcome beside the index (``_datagram_outcome``): deliver and consume,
    deliver and keep, or discard. A Python gate, as ``timer_weight != 1.0``
    is: any other app lowers to the program it always did."""
    init_states, initial_rows = _precomputed(app, cfg)
    oh = cfg.use_onehot

    def step(state: ScheduleState, prog: ExtProgram) -> ScheduleState:
        # Frozen lanes (done/violation/overflow) need no outer guard: every
        # effect below is masked by `injecting`/`dispatching`, so their
        # state is bit-preserved without the selects a vmapped lax.cond
        # would pay.
        active = state.status < ST_DONE
        injecting = active & (state.status == ST_INJECT)
        dispatching = active & (state.status == ST_DISPATCH)
        rec_idx = state.trace_len  # creator link for this step's insert

        state, inj_rows, inj_rec, inj_enabled, to_dispatch = _injection_phase(
            state, cfg, app, prog, initial_rows, init_states, injecting
        )

        # ----- dispatch side (inert unless `dispatching`: idx -> P) -------
        cond_met = _segment_cond_met(state, app, dispatching)
        mask = deliverable_mask(state, cfg) & dispatching & ~cond_met
        if cfg.srcdst_fifo:
            # TCP-ordered channels: only FIFO heads (and timers) compete.
            mask = mask & fifo_head_mask(state, cfg)
        count = jnp.sum(mask.astype(jnp.int32))
        any_deliverable = count > 0

        key, sub = ops.rng_split(state.rng)  # == jax.random.split, bit for bit
        if cfg.timer_weight != 1.0:
            # Two-stage choice: class (timer vs message) by weighted counts,
            # then uniform within class (host counterpart: FullyRandom with
            # timer_weight).
            tmask = mask & state.pool_timer
            mmask = mask & ~state.pool_timer
            tcount = jnp.sum(tmask.astype(jnp.int32))
            mcount = jnp.sum(mmask.astype(jnp.int32))
            sub, sub2 = ops.rng_split(sub)
            wt = cfg.timer_weight * tcount
            p_timer = jnp.where(
                (tcount > 0) & (mcount > 0),
                wt / jnp.maximum(wt + mcount, 1e-9),
                jnp.where(tcount > 0, 1.0, 0.0),
            )
            pick_timer = jax.random.uniform(sub2) < p_timer
            mask = jnp.where(pick_timer, tmask, mmask)
            count = jnp.where(pick_timer, tcount, mcount)
        if cfg.datagram:
            # The outcome's draw, split off as the class draw is.
            sub, sub3 = ops.rng_split(sub)
        u = jax.random.uniform(sub)
        k = jnp.minimum((u * count).astype(jnp.int32), jnp.maximum(count - 1, 0))
        idx = ops.first_true_index(mask, k, oh)
        idx = jnp.where(
            any_deliverable & dispatching, idx, jnp.int32(cfg.pool_capacity)
        )
        keep = discard = None
        if cfg.datagram:
            keep, discard = _datagram_outcome(state, cfg, idx, sub3)
        # rng advances only on dispatch steps (keeps the schedule stream
        # identical to the unfused kernel).
        state = state._replace(
            rng=jnp.where(dispatching, key, state.rng)
        )
        state, del_rows, del_rec = delivery_effects(
            state, cfg, app, idx, keep, discard
        )

        # ----- the ONE pool insert for both sides -------------------------
        rows = RowProposal.concat(inj_rows, del_rows)
        state = insert_rows(
            state, cfg, rows.valid, rows.src, rows.dst, rows.timer,
            rows.parked, rows.msg,
            crec=rec_idx if cfg.record_parents else None,
        )
        if cfg.record_trace:
            # At most one record per lane per step: the delivery's when one
            # happened, else the injection's.
            delivered = idx < cfg.pool_capacity
            rec = jnp.where(delivered, del_rec, inj_rec)
            state = _append_record(
                state, cfg, rec, delivered | (injecting & inj_enabled)
            )

        # One invariant evaluation per step serves both the interval check
        # and quiescence finalization (both see the post-delivery state).
        inv_code = check_invariant(state, app)

        # ----- interval invariant check (dispatch side) -------------------
        if cfg.invariant_interval:
            due = (state.deliveries % cfg.invariant_interval) == 0
            code = jnp.where(due & any_deliverable, inv_code, jnp.int32(0))
            state = state._replace(
                status=jnp.where(
                    code != 0, jnp.int32(ST_VIOLATION), state.status
                ),
                violation=jnp.where(
                    code != 0, code.astype(jnp.int32), state.violation
                ),
            )

        # ----- status resolution ------------------------------------------
        # Inject side: move to dispatch at segment boundaries (unless the
        # insert flipped the lane to overflow).
        status = jnp.where(
            injecting & (state.status == ST_INJECT) & to_dispatch,
            jnp.int32(ST_DISPATCH),
            state.status,
        )
        # Dispatch side: quiescence = nothing deliverable or budget spent.
        budget_spent = (state.seg_budget > 0) & (
            state.deliveries - state.seg_start >= state.seg_budget
        )
        quiescent = (
            dispatching
            & (~any_deliverable | budget_spent)
            & (status == ST_DISPATCH)
        )
        fin_code = inv_code
        status = jnp.where(
            quiescent,
            jnp.where(
                state.final_seg,
                jnp.where(fin_code != 0, jnp.int32(ST_VIOLATION), jnp.int32(ST_DONE)),
                jnp.int32(ST_INJECT),
            ),
            status,
        )
        violation = jnp.where(
            quiescent & state.final_seg, fin_code.astype(jnp.int32), state.violation
        )
        return state._replace(status=status, violation=violation)

    return step


def make_any_step_fn(app: DSLApp, cfg: DeviceConfig):
    """The cfg-selected step function: round-delivery or sequential. The
    single dispatch point for every driver (explore, continuous)."""
    if cfg.round_delivery:
        from .rounds import make_round_step_fn  # lazy: rounds imports us

        return make_round_step_fn(app, cfg)
    return make_step_fn(app, cfg)


#: The explore-kernel variant family: lane axis (leading | '-trailing') ×
#: loop form ('-ee' = early-exit while_loop) × delivery granularity
#: ('-round' = round-delivery mode, whose invariant checks are
#: round-granularity — semantics-preserving only when
#: ``invariant_interval == 0``). These are the names bench.py measures
#: and the autotuner (demi_tpu/tune) selects among.
EXPLORE_VARIANTS: Tuple[str, ...] = (
    "xla",
    "xla-trailing",
    "xla-ee",
    "xla-trailing-ee",
    "xla-round-ee",
    "xla-trailing-round-ee",
)


def variant_config(cfg: DeviceConfig, name: str) -> DeviceConfig:
    """The DeviceConfig a variant name implies ('-ee' / '-round' are cfg
    toggles; the lane axis is a kernel-construction choice)."""
    import dataclasses

    if name.split("-")[0] != "xla":
        raise ValueError(f"unknown explore variant {name!r}")
    overrides = {}
    if name.endswith("-ee"):
        overrides["early_exit"] = True
    if "-round" in name:
        overrides["round_delivery"] = True
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def make_explore_kernel_variant(app: DSLApp, cfg: DeviceConfig, name: str):
    """Build the explore kernel for a named variant — ONE parser for the
    variant grammar, shared by bench.py's measurement matrix and the
    autotuner's calibration reps so the two can never mean different
    kernels by the same name."""
    lane_axis = "trailing" if "-trailing" in name else "leading"
    return make_explore_kernel(
        app, variant_config(cfg, name), lane_axis=lane_axis
    )


def _finalize(state: ScheduleState, app, cfg) -> ScheduleState:
    """The verdict of a lane that ran out of steps mid-flight: under an
    invariant judged after any delivery, the invariant of whatever state
    was reached; under one judged at quiescence only, none (the lane is
    unfinished; host twin: ``BaseScheduler.execute``)."""
    if app.invariant_at == "quiescence":
        return state._replace(status=jnp.int32(ST_UNFINISHED))
    code = check_invariant(state, app)
    return state._replace(
        status=jnp.where(code != 0, ST_VIOLATION, ST_DONE).astype(jnp.int32),
        violation=code.astype(jnp.int32),
    )


def make_run_lane(app: DSLApp, cfg: DeviceConfig):
    """One lane, program to completion (or step cap): the single source of
    lane semantics shared by the batch explore kernel and the single-lane
    trace kernel (the pair whose agreement the device→host lift relies on)."""
    step = make_any_step_fn(app, cfg)

    def run_lane(prog: ExtProgram, key, start_state=None) -> LaneResult:
        if start_state is None:
            state = init_state(app, cfg, key)
            i0 = jnp.int32(0)
        else:
            # Forked lane (device/fork.py): resume from the trunk's
            # snapshot with this lane's own rng. The trunk only ran
            # injection steps, which never consume rng, so the resumed
            # stream is bit-identical to a scratch lane's with this key.
            state = start_state.state._replace(rng=key)
            i0 = start_state.steps

        if cfg.early_exit or start_state is not None:
            # Under vmap the cond is OR-reduced across the batch: the loop
            # runs only as long as some lane is still live. (Forked lanes
            # always take this form — their remaining budget is dynamic —
            # and a frozen lane's step is a bit-exact no-op, so the result
            # matches the fixed-length scan.)
            def cond(carry):
                s, i = carry
                return (s.status < ST_DONE) & (i < cfg.max_steps)

            def wl_body(carry):
                s, i = carry
                return step(s, prog), i + 1

            state, _ = jax.lax.while_loop(
                cond, wl_body, (state, i0)
            )
        else:
            def body(state, _):
                return step(state, prog), None

            state, _ = jax.lax.scan(body, state, None, length=cfg.max_steps)
        # Lanes that ran out of steps mid-flight: evaluate the invariant on
        # whatever was reached (parity: host caps via max_messages then
        # checks).
        state = jax.lax.cond(
            state.status < ST_DONE, lambda s: _finalize(s, app, cfg), lambda s: s, state
        )
        return LaneResult(
            status=state.status,
            violation=state.violation,
            deliveries=state.deliveries,
            trace=state.trace,
            trace_len=state.trace_len,
            sched_hash=state.sched_hash,
        )

    return run_lane


def make_explore_kernel(
    app: DSLApp,
    cfg: DeviceConfig,
    lane_axis: str = "leading",
    start_state: bool = False,
):
    """Returns jitted ``kernel(progs: ExtProgram[B], keys[B]) -> LaneResult[B]``.

    Each lane runs its external program to completion (or a cap) delivering
    uniformly-random deliverable messages — the device RandomScheduler.

    ``lane_axis='trailing'`` runs the batch along the LAST axis of every
    internal array (vmap in_axes=-1): per-lane [pool]-shaped ops become
    [pool, B] with the big batch dimension minor — the axis the TPU VPU
    vectorizes — instead of a pool-sized minor axis padded to the vector
    width. The public interface is unchanged (inputs/outputs stay
    lane-leading; transposes happen inside the jit) and results are
    bit-identical.

    ``start_state=True`` adds a third kernel argument — a device/fork.py
    ``PrefixSnapshot`` broadcast across the lane axis — so a batch forks
    from one trunk's injection-prefix state with per-lane rng; False keeps
    the two-argument lowering byte-identical."""
    run_lane = make_run_lane(app, cfg)
    if start_state:
        if lane_axis != "leading":
            raise ValueError("start_state fork kernels are lane-leading only")
        return _counted_kernel(
            jax.jit(
                jax.vmap(
                    lambda prog, key, snap: run_lane(prog, key, snap),
                    in_axes=(0, 0, None),
                )
            ),
            "explore-fork",
        )
    if lane_axis == "leading":
        return _counted_kernel(jax.jit(jax.vmap(run_lane)), "explore")
    if lane_axis != "trailing":
        raise ValueError(f"lane_axis must be leading/trailing, got {lane_axis!r}")

    vmapped = jax.vmap(run_lane, in_axes=-1, out_axes=0)

    def call(progs: ExtProgram, keys) -> LaneResult:
        progs_t = ExtProgram(
            *(jnp.moveaxis(jnp.asarray(x), 0, -1) for x in progs)
        )
        keys_t = jnp.moveaxis(jnp.asarray(keys), 0, -1)
        return vmapped(progs_t, keys_t)

    return _counted_kernel(jax.jit(call), "explore-trailing")


def _counted_kernel(kernel, name: str):
    """Launch-count telemetry around a jitted lane kernel. Deliberately
    records launches/lanes only — no block_until_ready, so async dispatch
    (the double-buffered sweep path) keeps overlapping. Telemetry off =
    one branch per LAUNCH (not per lane/step), so the bench headline is
    untouched. Under the launch profiler (DEMI_PROFILE=1 /
    --profile-rounds) the async-visible DISPATCH cost — tracing plus
    enqueue, never the device wait — is attributed per launch shape."""
    from ..obs.profiler import PROFILER

    def call(progs, keys, *rest):
        if obs.enabled():
            obs.counter("device.kernel.launches").inc(kernel=name)
            obs.counter("device.kernel.lanes").inc(
                int(keys.shape[0]), kernel=name
            )
        if PROFILER.enabled:
            t0 = time.perf_counter()
            out = kernel(progs, keys, *rest)
            PROFILER.dispatch(
                name, int(keys.shape[0]), time.perf_counter() - t0
            )
            return out
        return kernel(progs, keys, *rest)

    return call


def make_single_lane_trace_kernel(app: DSLApp, cfg: DeviceConfig):
    """Single-lane explore with trace recording on: re-runs a violating
    lane's seed to extract its full delivery record for host reconstruction."""
    import dataclasses

    overrides = {"record_trace": True}
    if cfg.round_delivery and not cfg.trace_capacity:
        # Round steps append up to num_actors records each; a sweep cfg
        # without an explicit capacity gets the safe upper bound here —
        # it's ONE lane, so the [steps*N, rec_width] trace is small.
        overrides["trace_capacity"] = cfg.max_steps * cfg.num_actors
    traced_cfg = dataclasses.replace(cfg, **overrides)
    kernel = jax.jit(make_run_lane(app, traced_cfg))

    def call(prog, key):
        # Each call is one device->host lift (a violating lane re-traced
        # for host reconstruction) — worth a span: lifts bound how fast
        # sweep hits turn into minimizable experiments.
        with obs.span("device.trace_lift"):
            res = kernel(prog, key)
            jax.block_until_ready(res.trace_len)
        obs.counter("device.trace_lifts").inc()
        return res

    return call
