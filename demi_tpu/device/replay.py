"""Batched schedule replay: the device-tier STS oracle.

Each lane consumes a prescribed record sequence (the host-lowered expected
trace of one DDMin candidate — see encoding.py): external records are
applied directly; delivery records are matched against the pending pool by
(src, dst, exact message) with FIFO (min arrival seq) disambiguation, and
*skipped when absent* — the STS ignore-absent heuristic
(reference: STSScheduler.scala:405-559) — so a whole minimization level
replays as one vmapped batch.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..dsl import DSLApp
from . import ops
from .core import (
    OP_END,
    REC_NONE,
    REC_DELIVERY,
    REC_EXT_BASE,
    REC_TIMER,
    REC_WILDCARD,
    ST_DONE,
    ST_VIOLATION,
    DeviceConfig,
    RowProposal,
    ScheduleState,
    _append_record,
    check_invariant,
    deliverable_mask,
    datagram_refusal,
    delivery_effects,
    external_effects,
    init_state,
    insert_rows,
)
from .explore import _precomputed


class ReplayResult(NamedTuple):
    status: jnp.ndarray
    violation: jnp.ndarray  # int32 final invariant code
    deliveries: jnp.ndarray
    ignored_absent: jnp.ndarray  # int32: expected deliveries with no match
    # Expected deliveries ENABLED by a successful peek prefix
    # (cfg.replay_peek > 0; 0 otherwise).
    peeked: jnp.ndarray


def _is_delivery_kind(kind):
    return (kind == REC_DELIVERY) | (kind == REC_TIMER) | (kind == REC_WILDCARD)


def _replay_cfg(cfg: DeviceConfig) -> DeviceConfig:
    """Replay matches by content + pool_seq FIFO and never reads the
    incremental head bits — skip their maintenance entirely
    (head_recompute flips track_fifo_heads off; fifo_head_mask is never
    called here). Shared with the prefix-fork trunk runner (device/fork.py)
    so trunk snapshots and fork lanes agree on every array shape."""
    import dataclasses

    if cfg.track_fifo_heads:
        cfg = dataclasses.replace(cfg, head_recompute=True)
    return cfg


def make_replay_record_fn(app: DSLApp, cfg: DeviceConfig):
    """The fused record application ``replay_record(state, rec, active) ->
    (state', peek_hit)`` shared by ``make_replay_run_lane`` and the
    prefix-fork trunk runner. ``cfg`` must be pre-normalized by
    ``_replay_cfg``."""
    if app.channels == "datagram" or cfg.datagram:
        raise ValueError(datagram_refusal("the device replay checker"))
    init_states, initial_rows = _precomputed(app, cfg)
    big = jnp.int32(2**30)

    def _delivery_match(state: ScheduleState, kind, a, b, msg):
        """Pending-pool match mask for one expected delivery record."""
        is_timer_rec = kind == REC_TIMER
        is_wild = kind == REC_WILDCARD
        mask = deliverable_mask(state, cfg)
        exact = (
            (state.pool_dst == b)
            & jnp.all(state.pool_msg == msg[None, :], axis=1)
            & (state.pool_timer == is_timer_rec)
            # Timers self-address; messages match on sender too.
            & (is_timer_rec | (state.pool_src == a))
        )
        # Wildcard (reference: WildCardMatch selectors,
        # STSScheduler.scala:696-708): receiver + class tag only.
        wild = (state.pool_dst == a) & (state.pool_msg[:, 0] == msg[0])
        return mask & jnp.where(is_wild, wild, exact)

    def _deliver_fifo_pending(state: ScheduleState):
        """Deliver the FIFO-earliest deliverable pending entry (the peek
        probe's unexpected delivery), full effects + insert + trace."""
        dmask = deliverable_mask(state, cfg)
        seqs = jnp.where(dmask, state.pool_seq, big)
        pidx = jnp.where(
            jnp.any(dmask), jnp.argmin(seqs), jnp.int32(cfg.pool_capacity)
        ).astype(jnp.int32)
        rec_idx = state.trace_len
        state, prow, prec = delivery_effects(state, cfg, app, pidx)
        state = insert_rows(
            state, cfg, prow.valid, prow.src, prow.dst, prow.timer,
            prow.parked, prow.msg,
            crec=rec_idx if cfg.record_parents else None,
        )
        if cfg.record_trace:
            state = _append_record(
                state, cfg, prec, pidx < cfg.pool_capacity
            )
        return state

    def replay_record(state: ScheduleState, rec, active):
        """Fused, branchless record application: the external and delivery
        sides both run with masks (inert op / invalid index for whichever
        doesn't apply) and share ONE pool-insert pass — same shape as the
        fused explore step (both lax.cond branches would execute under vmap
        anyway, and the O(pool) insert machinery dominates).

        Returns (state', peek_hit): peek_hit is True when
        ``cfg.replay_peek`` enabled an otherwise-absent expected delivery
        by delivering a pending prefix (device twin of STSScheduler.peek,
        STSScheduler.scala:314-378: keep the enabling prefix, roll the
        whole lane back on failure)."""
        kind = rec[0]
        # Explicit msg slice: parent-tracked records carry a trailing
        # column that must not leak into message matching.
        a, b, msg = rec[1], rec[2], rec[3 : 3 + cfg.msg_width]
        is_ext = active & (kind >= REC_EXT_BASE)
        is_delivery = active & _is_delivery_kind(kind)

        # External side (inert op unless is_ext).
        op = jnp.where(is_ext, kind - REC_EXT_BASE, OP_END)
        state, ext_rows, ext_rec, ext_enabled = external_effects(
            state, cfg, app, initial_rows, init_states, op, a, b, msg
        )

        peek_hit = jnp.bool_(False)
        if cfg.replay_peek:
            # The snapshot is the carry itself (functional rollback): run
            # the probe on a forked state; commit only if the expected
            # delivery became matchable within the budget.
            need = is_delivery & ~jnp.any(
                _delivery_match(state, kind, a, b, msg)
            )

            def peek_cond(carry):
                s, j, found = carry
                return (
                    need
                    & (j < cfg.replay_peek)
                    & ~found
                    & jnp.any(deliverable_mask(s, cfg))
                )

            def peek_body(carry):
                s, j, _ = carry
                s = _deliver_fifo_pending(s)
                found = jnp.any(_delivery_match(s, kind, a, b, msg))
                return s, j + 1, found

            s_peek, _, found = jax.lax.while_loop(
                peek_cond, peek_body, (state, jnp.int32(0), jnp.bool_(False))
            )
            state = jax.tree_util.tree_map(
                lambda old, new: jnp.where(found, new, old), state, s_peek
            )
            peek_hit = found

        # Delivery side (invalid index unless is_delivery and matched).
        # Re-capture the record index: peeked deliveries appended records.
        rec_idx = state.trace_len
        is_wild = kind == REC_WILDCARD
        match = _delivery_match(state, kind, a, b, msg)
        any_match = jnp.any(match)
        # policy: FIFO (earliest arrival) or, for wildcard "last",
        # latest arrival.
        want_last = is_wild & (b == 1)
        seqs_first = jnp.where(match, state.pool_seq, big)
        seqs_last = jnp.where(match, state.pool_seq, -big)
        idx = jnp.where(
            want_last, jnp.argmax(seqs_last), jnp.argmin(seqs_first)
        ).astype(jnp.int32)
        idx = jnp.where(
            any_match & is_delivery, idx, jnp.int32(cfg.pool_capacity)
        )
        state, del_rows, del_rec = delivery_effects(state, cfg, app, idx)

        rows = RowProposal.concat(ext_rows, del_rows)
        state = insert_rows(
            state, cfg, rows.valid, rows.src, rows.dst, rows.timer,
            rows.parked, rows.msg,
            crec=rec_idx if cfg.record_parents else None,
        )
        if cfg.record_trace:
            delivered = idx < cfg.pool_capacity
            out_rec = jnp.where(delivered, del_rec, ext_rec)
            state = _append_record(
                state, cfg, out_rec, delivered | (is_ext & ext_enabled)
            )
        return state, peek_hit

    return replay_record


def make_replay_apply_fn(app: DSLApp, cfg: DeviceConfig):
    """``apply_one(state, ignored, peeked, rec)`` — one record plus the
    ignored-absent / peek accounting, shared by the lane loop below and
    the prefix-fork trunk (device/fork.py). ``cfg`` must be pre-normalized
    by ``_replay_cfg``."""
    replay_record = make_replay_record_fn(app, cfg)

    def apply_one(state, ignored, peeked, rec):
        before = state.deliveries
        state, peek_hit = replay_record(
            state, rec, state.status < ST_DONE
        )
        was_delivery = _is_delivery_kind(rec[0])
        skipped = was_delivery & (state.deliveries == before) & (state.status < ST_DONE)
        return (
            state,
            ignored + skipped.astype(jnp.int32),
            peeked + peek_hit.astype(jnp.int32),
        )

    return apply_one


def make_replay_run_lane(app: DSLApp, cfg: DeviceConfig):
    """Unjitted single-lane replay ``run_lane(records, key,
    start_state=None) -> ReplayResult`` (composable with vmap/jit/shardings
    by callers). ``start_state`` (a device/fork.py PrefixSnapshot) resumes
    the lane from a trunk snapshot — ``records`` are then the remaining
    (left-shifted) suffix; the default None keeps today's lowering
    byte-identical."""
    cfg = _replay_cfg(cfg)
    apply_one = make_replay_apply_fn(app, cfg)

    def run_lane(records, key, start_state=None) -> ReplayResult:
        if start_state is None:
            state = init_state(app, cfg, key)
            ignored0 = peeked0 = jnp.int32(0)
        else:
            # Forked lane: the trunk already applied the shared prefix.
            # rng is per-lane for contract parity with the explore fork
            # (replay itself never consumes it).
            state = start_state.state._replace(rng=key)
            ignored0 = start_state.ignored
            peeked0 = start_state.peeked

        if cfg.early_exit:
            # Stop at trailing padding (REC_NONE) or a finished lane; under
            # vmap the cond is OR-reduced, so the batch runs only as long
            # as the longest live candidate — minimization candidates
            # shrink far below the shared static record shape.
            n_rec = records.shape[0]

            oh = cfg.use_onehot

            def cond(carry):
                s, _ig, _pk, i = carry
                kind = ops.get_scalar(
                    records[:, 0], jnp.minimum(i, n_rec - 1), oh
                )
                return (i < n_rec) & (kind != REC_NONE) & (s.status < ST_DONE)

            def wl_body(carry):
                s, ig, pk, i = carry
                rec = ops.get_row(records, jnp.minimum(i, n_rec - 1), oh)
                s, ig, pk = apply_one(s, ig, pk, rec)
                return (s, ig, pk, i + 1)

            state, ignored, peeked, _ = jax.lax.while_loop(
                cond, wl_body,
                (state, ignored0, peeked0, jnp.int32(0)),
            )
        else:
            def body(carry, rec):
                state, ignored, peeked = carry
                state, ignored, peeked = apply_one(state, ignored, peeked, rec)
                return (state, ignored, peeked), None

            (state, ignored, peeked), _ = jax.lax.scan(
                body, (state, ignored0, peeked0), records
            )
        # Aborted lanes (overflow) must not report a verdict computed from
        # truncated state — mask their violation to 0 so batched-oracle
        # consumers reading only `violation` never count them as
        # reproducing.
        aborted = state.status >= ST_DONE
        code = jnp.where(aborted, jnp.int32(0), check_invariant(state, app))
        status = jnp.where(
            aborted,
            state.status,
            jnp.where(code != 0, ST_VIOLATION, ST_DONE),
        ).astype(jnp.int32)
        return ReplayResult(
            status=status,
            violation=code.astype(jnp.int32),
            deliveries=state.deliveries,
            ignored_absent=ignored,
            peeked=peeked,
        )

    return run_lane


def make_replay_kernel(app: DSLApp, cfg: DeviceConfig, start_state: bool = False):
    """Returns jitted ``kernel(records[B, R, rec_width], keys[B]) ->
    ReplayResult[B]`` replaying each lane's prescribed schedule.

    With ``start_state=True`` the kernel takes a third argument — a
    device/fork.py ``PrefixSnapshot`` shared across the lane axis
    (``vmap in_axes=None``) — and ``records`` are each lane's remaining
    suffix; False keeps the two-argument lowering byte-identical."""
    run_lane = make_replay_run_lane(app, cfg)
    if not start_state:
        return jax.jit(jax.vmap(run_lane))
    return jax.jit(
        jax.vmap(
            lambda records, key, snap: run_lane(records, key, snap),
            in_axes=(0, 0, None),
        )
    )
