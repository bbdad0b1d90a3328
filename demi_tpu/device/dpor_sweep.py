"""Batched device DPOR: explore many backtrack points per kernel launch.

The reference explores one interleaving at a time (DPORwHeuristics runs a
full JVM execution per backtrack point). Here a backtrack point is a
*prescription* — a prefix of delivery records plus the flipped event — and
a whole frontier of prescriptions runs as one vmapped batch: each lane
follows its prescription (skipping absent records, divergence-tolerant)
and continues with random exploration; lanes record parent-tracked traces
(DeviceConfig.record_parents), from which the host derives the
happens-before forest and the next round's racing pairs with no
re-execution. SURVEY §7.2 step 7: the racing-pair scan is data-parallel
bit math; only the frontier priority queue stays host-side.

Host half: a whole round's prescriptions are derived in ONE
batch-native call (``native.racing_prescriptions_batch`` — C++ when a
compiler exists, its NumPy twin otherwise) and deduped on content
digests; the tests hold both, and a whole search, to a per-lane
assembly they keep (``_legacy_prescriptions``). Every DeviceDPOR tracks
its ``host_seconds``/``device_seconds`` split (the ``dpor.host_share``
gauge, bench configs 2/8).
"""

from __future__ import annotations

import os
import time
from typing import Dict, List, NamedTuple, Optional, Sequence, Set, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs
from ..config import SchedulerConfig
from ..dsl import DSLApp
from ..external_events import ExternalEvent
from ..schedulers.dpor import arvind_distance
from . import ops
from .core import (
    REC_DELIVERY,
    REC_TIMER,
    ST_DISPATCH,
    ST_DONE,
    ST_VIOLATION,
    DeviceConfig,
    ScheduleState,
    check_invariant,
    datagram_refusal,
    deliver_index,
    deliverable_mask,
    init_state,
    insert_form,
)
from .encoding import lower_program
from .explore import ExtProgram, LaneResult, _finalize, make_step_fn
from .explored_log import ExploredLog, ExploredView, PrescList


def make_prescribed_dispatch(app: DSLApp, cfg: DeviceConfig):
    """``prescribed_dispatch(state, presc, cursor) -> (state', cursor',
    found)``: deliver the first matchable prescribed record at/after
    ``cursor`` (skipping absent ones — divergence tolerance), with the
    per-delivery invariant check. Shared by the lane step below and the
    prefix-fork trunk runner (device/fork.py) so the two cannot drift."""
    big = jnp.int32(2**30)
    r_max = cfg.max_steps
    oh = cfg.use_onehot

    def match_record(state: ScheduleState, rec):
        is_timer_rec = rec[0] == REC_TIMER
        mask = deliverable_mask(state, cfg)
        exact = (
            (state.pool_dst == rec[2])
            & jnp.all(state.pool_msg == rec[3 : 3 + cfg.msg_width][None, :], axis=1)
            & (state.pool_timer == is_timer_rec)
            & (is_timer_rec | (state.pool_src == rec[1]))
        )
        match = mask & exact
        seqs = jnp.where(match, state.pool_seq, big)
        idx = jnp.argmin(seqs).astype(jnp.int32)
        return jnp.where(jnp.any(match), idx, jnp.int32(cfg.pool_capacity))

    def prescribed_dispatch(state: ScheduleState, presc, cursor):
        # Skip past absent prescribed records to the first matchable one.
        def cond(c3):
            c, idx, _ = c3
            rec_kind = ops.get_scalar(
                presc[:, 0], jnp.minimum(c, r_max - 1), oh
            )
            in_range = (c < r_max) & (
                (rec_kind == REC_DELIVERY) | (rec_kind == REC_TIMER)
            )
            return in_range & (idx >= cfg.pool_capacity)

        def body(c3):
            c, _, skips = c3
            idx = match_record(
                state, ops.get_row(presc, jnp.minimum(c, r_max - 1), oh)
            )
            found = idx < cfg.pool_capacity
            return (
                jnp.where(found, c, c + 1),
                idx,
                skips + jnp.where(found, 0, 1),
            )

        c, idx, _ = jax.lax.while_loop(
            cond, body, (cursor, jnp.int32(cfg.pool_capacity), jnp.int32(0))
        )
        found = idx < cfg.pool_capacity
        new_state = deliver_index(state, cfg, app, idx)
        # Per-delivery invariant checks apply during prefix replay too
        # (transient violations — e.g. two-leaders healed by a later
        # step-down — are exactly what DPOR prescribes its way into).
        if cfg.invariant_interval:
            code = jnp.where(
                found, check_invariant(new_state, app), jnp.int32(0)
            )
            new_state = new_state._replace(
                status=jnp.where(
                    code != 0, jnp.int32(ST_VIOLATION), new_state.status
                ),
                violation=jnp.where(
                    code != 0, code.astype(jnp.int32), new_state.violation
                ),
            )
        return new_state, jnp.where(found, c + 1, c), found

    return prescribed_dispatch


def make_dpor_run_lane(app: DSLApp, cfg: DeviceConfig):
    """Unjitted single-lane DPOR sweep ``run_lane(prog, prescription, key,
    start_state=None) -> LaneResult`` (composable with vmap/jit by callers
    — the kernel below and parallel/mesh.py's sharded twin).
    cfg must have record_trace and record_parents on.

    Dispatch follows the prescription while records match (absent records
    are skipped — divergence tolerance), then falls back to the explore
    step's random choice. ``start_state`` (a device/fork.py
    PrefixSnapshot) resumes from a trunk's state + committed cursor with
    this lane's own rng; the default None keeps today's lowering
    byte-identical."""
    assert cfg.record_trace and cfg.record_parents
    base_step = make_step_fn(app, cfg)
    r_max = cfg.max_steps
    recw = cfg.rec_width
    prescribed_dispatch = make_prescribed_dispatch(app, cfg)

    def step(carry, presc, prog):
        state, cursor = carry

        oh = cfg.use_onehot

        in_dispatch = state.status == ST_DISPATCH
        rec_kind = ops.get_scalar(
            presc[:, 0], jnp.minimum(cursor, r_max - 1), oh
        )
        presc_active = in_dispatch & (cursor < r_max) & (
            (rec_kind == REC_DELIVERY) | (rec_kind == REC_TIMER)
        )

        def with_prescription(args):
            state, cursor = args
            new_state, new_cursor, found = prescribed_dispatch(
                state, presc, cursor
            )
            # If nothing in the prescription matched, fall back to the
            # normal (random) step from the ORIGINAL state.
            fell_back = ~found
            rnd = base_step(state, prog)
            out = jax.tree_util.tree_map(
                lambda a, b: jnp.where(fell_back, a, b), rnd, new_state
            )
            return out, new_cursor

        def without(args):
            state, cursor = args
            return base_step(state, prog), cursor

        state, cursor = jax.lax.cond(
            presc_active, with_prescription, without, (state, cursor)
        )
        return (state, cursor), None

    def run_lane(prog: ExtProgram, presc, key, start_state=None) -> LaneResult:
        if start_state is None:
            state = init_state(app, cfg, key)
            cursor0 = jnp.int32(0)
            (state, _cursor), _ = jax.lax.scan(
                lambda carry, _: step(carry, presc, prog),
                (state, cursor0), None, length=cfg.max_steps,
            )
        else:
            # Forked lane: the trunk delivered the shared-prefix records
            # (rng untouched — prescribed dispatch never splits it), so
            # resuming with this lane's key and the remaining step budget
            # is bit-identical to a scratch lane. Frozen lanes' steps are
            # no-ops, so the while_loop matches the fixed-length scan.
            state = start_state.state._replace(rng=key)

            def cond(carry):
                (s, _cur), i = carry
                return (s.status < ST_DONE) & (i < cfg.max_steps)

            def body(carry):
                sc, i = carry
                sc, _ = step(sc, presc, prog)
                return sc, i + 1

            (state, _cursor), _ = jax.lax.while_loop(
                cond, body,
                ((state, start_state.cursor), start_state.steps),
            )
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


class DporSleepResult(NamedTuple):
    """LaneResult plus the device-encoded sleep-set observations (the
    sleep-kernel return type; leading fields mirror LaneResult so every
    existing consumer reads it unchanged)."""

    status: jnp.ndarray
    violation: jnp.ndarray
    deliveries: jnp.ndarray
    trace: jnp.ndarray
    trace_len: jnp.ndarray
    sched_hash: jnp.ndarray
    # Per sleeping row: first at-or-after-node delivery ordinal whose
    # record was dependent with (or content-identical to) it —
    # BIG_ORDINAL = still asleep at lane end.
    sleep_wake: jnp.ndarray  # [sleep_cap] int32
    # First at-or-after-node ordinal that delivered a still-sleeping
    # row (the redundant-suffix marker; BIG_ORDINAL = never).
    sleep_slept: jnp.ndarray  # int32


def make_dpor_sleep_run_lane(
    app: DSLApp, cfg: DeviceConfig, sleep_cap: int, commute_matrix=None
):
    """The sleep-set twin of ``make_dpor_run_lane``: same lane semantics
    bit-for-bit (state, cursor, and rng math are shared — LaneResult
    fields are identical to the plain kernel's), plus per-step wake
    tracking over a bounded block of sleeping records.

    ``run_lane(prog, presc, key, sleep_rows[S, recw], sleep_from,
    start_state=None) -> DporSleepResult``. Tracking applies to
    deliveries at ordinals >= ``sleep_from`` — the NODE ordinal, i.e.
    the length of the lane's identity prescription (prefix + flip);
    rows before it are the path TO the node the sleep rows attach at,
    so they neither wake nor trip them, while the wakeup-sequence guide
    rows beyond it are ordinary tracked deliveries. A tracked delivery
    wakes every sleeping row it is dependent with — same receiver and
    not proven commuting by ``commute_matrix`` (the
    ``StaticIndependence.device_matrix()`` baked in as a kernel
    constant), or content-identical — and a tracked delivery content-
    identical to a still-sleeping row marks the redundant suffix.
    Forked lanes resume with wake state intact because ordinals are
    absolute (``state.deliveries`` rides the snapshot) and the fork
    planner clamps trunk prefixes below every member's node under
    sleep mode, so the pre-fork segment is entirely untracked.

    Why the fixed ``sleep_from`` ordinal is safe against divergence
    (prescribed rows skipped would otherwise shift the real node
    earlier and leave a wake window untracked — unsound over-pruning):
    a DERIVED prescription's identity is its source lane's own
    delivered records plus a co-enabled flip, both of which replay
    deterministically from init (prescribed dispatch never consumes
    rng, injections are deterministic, and the matcher's lowest-seq
    pick is a function of state alone) — so the first ``sleep_from``
    deliveries cannot diverge. The only divergence-prone prescriptions
    are host-lowered SEEDS and post-node guide rows; seeds carry no
    sleep rows, and guide rows sit at ordinals >= ``sleep_from`` where
    tracking is already on."""
    from ..analysis.sleep import BIG_ORDINAL

    assert cfg.record_trace and cfg.record_parents
    base_step = make_step_fn(app, cfg)
    prescribed_dispatch = make_prescribed_dispatch(app, cfg)
    r_max = cfg.max_steps
    recw = cfg.rec_width
    oh = cfg.use_onehot
    big = jnp.int32(BIG_ORDINAL)
    mat = (
        None
        if commute_matrix is None
        else jnp.asarray(np.asarray(commute_matrix), jnp.int32)
    )

    def wake_update(old_state, new_state, sleep_from, sleep_rows, wake,
                    slept):
        delivered = new_state.deliveries > old_state.deliveries
        ordv = old_state.deliveries  # this delivery's absolute ordinal
        row = ops.get_row(
            new_state.trace, jnp.maximum(new_state.trace_len - 1, 0), oh
        )
        valid = sleep_rows[:, 0] != 0
        same_dst = sleep_rows[:, 2] == row[2]
        content_eq = (
            (sleep_rows[:, 0] == row[0])
            & same_dst
            & jnp.all(
                sleep_rows[:, 3: recw - 2] == row[3: recw - 2][None, :],
                axis=1,
            )
            & ((row[0] == REC_TIMER) | (sleep_rows[:, 1] == row[1]))
        )
        if mat is None:
            dep = same_dst
        else:
            m = mat.shape[0]
            tr, ts = row[3], sleep_rows[:, 3]
            ir = jnp.where((tr >= 0) & (tr < m - 1), tr, m - 1)
            isx = jnp.where((ts >= 0) & (ts < m - 1), ts, m - 1)
            dep = same_dst & (mat[ir, isx] == 0)
        dep = dep | content_eq
        asleep = wake >= big
        tracked = delivered & (ordv >= sleep_from)
        wake = jnp.where(tracked & valid & asleep & dep, ordv, wake)
        hit = tracked & jnp.any(valid & asleep & content_eq)
        slept = jnp.where(hit & (slept >= big), ordv, slept)
        return wake, slept

    def step(carry, presc, prog, sleep_rows, sleep_from):
        state, cursor, wake, slept = carry
        in_dispatch = state.status == ST_DISPATCH
        rec_kind = ops.get_scalar(
            presc[:, 0], jnp.minimum(cursor, r_max - 1), oh
        )
        presc_active = in_dispatch & (cursor < r_max) & (
            (rec_kind == REC_DELIVERY) | (rec_kind == REC_TIMER)
        )

        def with_prescription(args):
            state, cursor = args
            new_state, new_cursor, found = prescribed_dispatch(
                state, presc, cursor
            )
            fell_back = ~found
            rnd = base_step(state, prog)
            out = jax.tree_util.tree_map(
                lambda a, b: jnp.where(fell_back, a, b), rnd, new_state
            )
            return out, new_cursor

        def without(args):
            state, cursor = args
            return base_step(state, prog), cursor

        new_state, new_cursor = jax.lax.cond(
            presc_active, with_prescription, without, (state, cursor)
        )
        wake, slept = wake_update(
            state, new_state, sleep_from, sleep_rows, wake, slept
        )
        return (new_state, new_cursor, wake, slept)

    def run_lane(prog, presc, key, sleep_rows, sleep_from, start_state=None):
        wake0 = jnp.full((sleep_cap,), BIG_ORDINAL, jnp.int32)
        if start_state is None:
            carry = (init_state(app, cfg, key), jnp.int32(0), wake0, big)
            carry, _ = jax.lax.scan(
                lambda c, _: (
                    step(c, presc, prog, sleep_rows, sleep_from), None
                ),
                carry, None, length=cfg.max_steps,
            )
            state, _cursor, wake, slept = carry
        else:
            state0 = start_state.state._replace(rng=key)

            def cond(c2):
                (s, *_rest), i = c2
                return (s.status < ST_DONE) & (i < cfg.max_steps)

            def body(c2):
                c, i = c2
                return step(c, presc, prog, sleep_rows, sleep_from), i + 1

            carry, _ = jax.lax.while_loop(
                cond, body,
                ((state0, start_state.cursor, wake0, big),
                 start_state.steps),
            )
            state, _cursor, wake, slept = carry
        state = jax.lax.cond(
            state.status < ST_DONE,
            lambda s: _finalize(s, app, cfg), lambda s: s, state,
        )
        return DporSleepResult(
            status=state.status,
            violation=state.violation,
            deliveries=state.deliveries,
            trace=state.trace,
            trace_len=state.trace_len,
            sched_hash=state.sched_hash,
            sleep_wake=wake,
            sleep_slept=slept,
        )

    return run_lane


def make_dpor_kernel(
    app: DSLApp, cfg: DeviceConfig, start_state: bool = False,
    sleep_cap: int = 0, commute_matrix=None,
):
    """jitted ``kernel(progs[B], prescriptions[B, R, recw], keys[B]) ->
    LaneResult[B]`` (see make_dpor_run_lane). ``start_state=True`` adds a
    fourth argument — a device/fork.py PrefixSnapshot broadcast across the
    lane axis — resuming the whole batch from one trunk's state.
    ``sleep_cap > 0`` builds the sleep-set variant instead: the kernel
    takes an extra ``sleep_rows[B, sleep_cap, recw]`` input and returns
    ``DporSleepResult`` (LaneResult fields are bit-identical to the
    plain kernel's — the wake tracking is observation-only)."""
    if sleep_cap > 0:
        run_sleep = make_dpor_sleep_run_lane(
            app, cfg, sleep_cap, commute_matrix
        )
        if not start_state:
            return jax.jit(
                jax.vmap(run_sleep, in_axes=(0, 0, 0, 0, 0))
            )
        return jax.jit(
            jax.vmap(
                lambda prog, presc, key, srows, sfrom, snap: run_sleep(
                    prog, presc, key, srows, sfrom, snap
                ),
                in_axes=(0, 0, 0, 0, 0, None),
            )
        )
    run_lane = make_dpor_run_lane(app, cfg)
    if not start_state:
        return jax.jit(jax.vmap(run_lane))
    return jax.jit(
        jax.vmap(
            lambda prog, presc, key, snap: run_lane(prog, presc, key, snap),
            in_axes=(0, 0, 0, None),
        )
    )


def lane_keys(seeds):
    """The DPOR lane key scheme: ``fold_in(PRNGKey(0), seed)`` per lane.
    One definition for the in-process loop and the fleet worker, which
    receives seeds — not keys — on the wire."""
    return jax.vmap(
        lambda s: jax.random.fold_in(jax.random.PRNGKey(0), s)
    )(np.asarray(seeds, np.uint32))


@obs.spans.staged(
    "setup.build", what="build_dpor_kernel",
    insert=lambda app, cfg, *a, **kw: insert_form(cfg),
)
def build_dpor_kernel(
    app: DSLApp, cfg: DeviceConfig, mesh=None, start_state: bool = False,
    sleep_cap: int = 0, commute_matrix=None,
):
    """``make_dpor_kernel`` or, under a mesh, its lane-sharded twin
    (parallel/mesh.py) — one selector shared by ``DeviceDPOR`` and
    ``DeviceDPOROracle`` so the two cannot build different kernels for
    the same (mesh, sleep) choice."""
    if mesh is None:
        return make_dpor_kernel(
            app, cfg, start_state=start_state, sleep_cap=sleep_cap,
            commute_matrix=commute_matrix,
        )
    from ..parallel.mesh import shard_dpor_kernel, shard_dpor_sleep_kernel

    if sleep_cap > 0:
        return shard_dpor_sleep_kernel(
            app, cfg, mesh, sleep_cap, commute_matrix=commute_matrix,
            start_state=start_state,
        )
    return shard_dpor_kernel(app, cfg, mesh, start_state=start_state)


# ---------------------------------------------------------------------------
# Host side: the search's switches, its oracle and its driver
# ---------------------------------------------------------------------------

def _resolve_static_independence(app: DSLApp, explicit=None):
    """Resolve the static-pruning switch into a relation (or None).

    ``explicit`` may be an analysis.StaticIndependence instance (used as
    given — the bench passes audit-mode relations), True (build one from
    the app's handler analysis), False (off), or None (the
    ``DEMI_STATIC_PRUNE`` env flag decides). Off by default: static
    pruning changes which backtracks are derived, so like every
    schedule-space feature here it ships opt-in."""
    from ..analysis import StaticIndependence, static_prune_enabled

    if explicit is not None and not isinstance(explicit, bool):
        return explicit
    if static_prune_enabled(explicit):
        return StaticIndependence.for_app(app)
    return None


def _resolve_sleep_sets(app: DSLApp, explicit=None, independence=None):
    """Resolve the sleep-set switch into an analysis.SleepSets (or None).

    ``explicit`` may be a SleepSets instance (used as given — the bench
    passes observe-/audit-mode objects), True (build one from the app),
    False (off), or None (the ``DEMI_SLEEP_SETS`` env flag decides).
    ``independence`` (a StaticIndependence, when static pruning is also
    on) doubles as the dependence oracle; otherwise one is derived from
    the app purely for dependence — its prune ledger is never consulted.
    Off by default: sleep-set pruning removes whole explored schedules,
    so like every schedule-space feature here it ships opt-in with the
    unpruned path as the pinned A/B baseline."""
    from ..analysis import SleepSets, StaticIndependence, sleep_sets_enabled

    if explicit is not None and not isinstance(explicit, bool):
        return explicit
    if sleep_sets_enabled(explicit):
        rel = (
            independence
            if independence is not None
            else StaticIndependence.for_app(app)
        )
        return SleepSets(independence=rel)
    return None


class DeviceDPOROracle:
    """TestOracle over DeviceDPOR: systematic batched search for a target
    violation on a given external program; positives lift to full host
    EventTraces via GuidedScheduler (BASELINE config 2 shape: bounded
    DPOR search on raft-class apps).

    Resumable: one DeviceDPOR (frontier + explored set) is kept per
    external subsequence, so repeated DDMin probes of the same subsequence
    continue the search instead of restarting (the device analog of
    ResumableDPOR, IncrementalDeltaDebugging.scala:94-122). With
    ``initial_trace`` set, each fresh instance is seeded with the recorded
    schedule's prescription; ``max_distance`` (set by IncrementalDDMin)
    caps backtracks by edit distance to it.

    One jitted DPOR kernel (and fork kernel) is shared across the
    resumable instances — the kernel closes over (app, cfg) only, the
    program is data — so a DDMin run probing many subsequences compiles
    once instead of once per subsequence.

    Async surface (``async_min``, default the ``DEMI_ASYNC_MIN`` env
    switch): ``supports_async`` + ``test_window`` let the speculative
    minimizers (DDMin's left/right pair batching, LeftToRightRemoval's
    windows) batch a whole window of probes' frontier rounds into one
    device launch (``explore_window``); each probe's instance state
    commits only when its resolver is consulted, so an unconsulted probe
    leaves its resumable frontier exactly as the sequential path would.
    ``double_buffer`` threads through to each instance's in-flight round
    dispatch (see DeviceDPOR); left None it follows ``async_min`` as this
    oracle resolved it (``_resolve_double_buffer``), so an oracle told
    ``async_min=True`` needs no variable set for its instances to agree.
    ``host_shards`` is each instance's admission shard count."""

    def __init__(
        self,
        app: DSLApp,
        cfg: DeviceConfig,
        config: SchedulerConfig,
        batch_size: int = 64,
        max_rounds: int = 20,
        initial_trace=None,
        autotune: bool = False,
        prefix_fork: Optional[bool] = None,
        async_min: Optional[bool] = None,
        double_buffer: Optional[bool] = None,
        static_independence=None,
        sleep_sets=None,
        mesh=None,
        host_shards: Optional[int] = None,
    ):
        from ..minimization.pipeline import async_min_enabled
        from .fork import prefix_fork_enabled

        self.app = app
        self.cfg = cfg
        self.config = config
        self.mesh = mesh
        self.batch_size = batch_size
        self.max_rounds = max_rounds
        self.last_interleavings = 0
        self.initial_trace = initial_trace
        self.prefix_fork = prefix_fork
        self.host_shards = host_shards
        # One static may-commute relation shared by every resumable
        # instance (the relation is per-app; its prune ledger aggregates
        # across instances — what static_stats reports).
        self.static_independence = _resolve_static_independence(
            app, static_independence
        )
        # Sleep sets: resolved per INSTANCE (class/wakeup state is
        # per-subsequence — prescriptions from different external
        # programs must never class-merge), but the on/off decision and
        # the shared sleep kernels are resolved once here.
        from ..analysis import sleep_cap as _sleep_cap
        from ..analysis import sleep_sets_enabled

        if sleep_sets is not None and not isinstance(sleep_sets, bool):
            # Class/wakeup state is per-subsequence: a single caller
            # SleepSets shared across resumable instances would merge
            # class spaces from different external programs. Refuse
            # loudly instead of silently substituting.
            raise TypeError(
                "DeviceDPOROracle takes sleep_sets as bool/None; "
                "per-instance SleepSets are built internally"
            )
        self.sleep_sets = (
            sleep_sets
            if isinstance(sleep_sets, bool)
            else sleep_sets_enabled(None)
        )
        sleep_matrix = None
        if self.sleep_sets:
            rel = (
                self.static_independence
                if self.static_independence is not None
                else None
            )
            if rel is None:
                from ..analysis import StaticIndependence

                rel = StaticIndependence.for_app(app)
            self._sleep_dependence = rel
            sleep_matrix = rel.device_matrix()
        else:
            self._sleep_dependence = None
        self._sleep_kernel_cap = _sleep_cap() if self.sleep_sets else 0
        self._sleep_matrix = sleep_matrix
        self.max_distance: Optional[int] = None
        # Measurement-guided budget control: each resumable DPOR instance
        # gets its own DporBudgetTuner (frontier dynamics are
        # per-subsequence), fed by the per-round redundant/pruned counts.
        self.autotune = autotune
        self._async = async_min_enabled(async_min)
        self._double_buffer = _resolve_double_buffer(
            double_buffer, self._async
        )
        # Shared kernels: under a mesh every instance's rounds shard over
        # the same lane-sharded twin.
        self._kernel = build_dpor_kernel(
            app, cfg, mesh=mesh, sleep_cap=self._sleep_kernel_cap,
            commute_matrix=self._sleep_matrix,
        )
        self._fork_kernel = (
            build_dpor_kernel(
                app, cfg, mesh=mesh, start_state=True,
                sleep_cap=self._sleep_kernel_cap,
                commute_matrix=self._sleep_matrix,
            )
            if prefix_fork_enabled(prefix_fork)
            else None
        )
        self._instances: Dict[Tuple, DeviceDPOR] = {}

    @property
    def supports_async(self) -> bool:
        """True when the async-minimization pipeline is on — what the
        speculative minimizers probe before using ``test_window``."""
        return self._async

    def set_initial_trace(self, trace) -> None:
        self.initial_trace = trace

    @property
    def fork_stats(self) -> Optional[dict]:
        """Aggregate prefix-fork statistics across the resumable
        instances (None when forking is off) — what the CLI reports."""
        stats = [
            inst._forker.stats_view()
            for inst in self._instances.values()
            if inst._forker is not None
        ]
        if not stats:
            return None
        out: Dict[str, int] = {}
        for s in stats:
            for k, v in s.items():
                out[k] = out.get(k, 0) + v
        return out

    def tuner_summaries(self) -> List[dict]:
        """Public view of each resumable instance's budget-tuner state
        (empty unless ``autotune=True``) — what the CLI reports."""
        return [
            {
                "rounds": inst.tuner.rounds,
                "round_batch": inst.tuner.round_batch,
                "max_distance": inst.tuner.max_distance,
            }
            for inst in self._instances.values()
            if inst.tuner is not None
        ]

    def async_stats(self) -> Dict[str, int]:
        """In-flight round economics summed across the resumable
        instances — what the CLI and bench config 8 report."""
        out = {"inflight_rounds": 0, "inflight_hits": 0, "inflight_waste": 0}
        for inst in self._instances.values():
            for k in out:
                out[k] += inst.async_stats[k]
        return out

    @property
    def static_stats(self) -> Optional[Dict[str, int]]:
        """Static-pruning ledger (None when the relation is off) — what
        the CLI summary and bench report: racing pairs skipped because
        the flip was provably a no-op, by kind."""
        if self.static_independence is None:
            return None
        return dict(self.static_independence.pruned_total)

    @property
    def sleep_stats(self) -> Optional[Dict[str, object]]:
        """Sleep-set ledger summed across the resumable instances (None
        when sleep sets are off) — what the CLI summary reports: prune
        counts by kind, distinct classes, and the aggregate redundancy
        ratio."""
        if not self.sleep_sets:
            return None
        pruned = {"sleep": 0, "class": 0}
        classes = explored = 0
        for inst in self._instances.values():
            if inst.sleep is None:
                continue
            for k, v in inst.sleep.pruned_total.items():
                pruned[k] = pruned.get(k, 0) + v
            classes += len(inst.sleep.classes)
            explored += len(inst.explored)
        return {
            "pruned": pruned,
            "classes": classes,
            "explored": explored,
            "redundancy_ratio": (
                round(explored / classes, 4) if classes else None
            ),
        }

    @property
    def lane_sharding(self) -> Optional[dict]:
        """Devices a whole-batch round's output spanned and lanes on each
        (None before any round ran) — what the CLI summary reports."""
        for inst in self._instances.values():
            if inst.lane_sharding is not None:
                return inst.lane_sharding
        return None

    def host_share(self) -> Optional[float]:
        """Host-vs-device wall-time split summed across the resumable
        instances (None before any round ran) — the CLI summary's
        host-share figure."""
        host = sum(i.host_seconds for i in self._instances.values())
        dev = sum(i.device_seconds for i in self._instances.values())
        total = host + dev
        return host / total if total > 0 else None

    def _instance(self, externals) -> DeviceDPOR:
        key = tuple(e.eid for e in externals)
        inst = self._instances.get(key)
        if inst is None:
            from ..analysis import SleepSets

            inst = DeviceDPOR(
                self.app, self.cfg, externals, self.batch_size,
                mesh=self.mesh,
                prefix_fork=self.prefix_fork,
                double_buffer=self._double_buffer,
                kernel=self._kernel,
                fork_kernel=self._fork_kernel,
                host_shards=self.host_shards,
                static_independence=(
                    self.static_independence
                    if self.static_independence is not None
                    else False
                ),
                sleep_sets=(
                    SleepSets(
                        independence=self._sleep_dependence,
                        cap=self._sleep_kernel_cap,
                    )
                    if self.sleep_sets
                    else False
                ),
            )
            if self.initial_trace is not None:
                inst.seed(
                    steering_prescription(
                        self.app, self.cfg, self.initial_trace, externals
                    )
                )
            if self.autotune:
                from ..tune import DporBudgetTuner

                inst.tuner = DporBudgetTuner(
                    batch=self.batch_size, max_distance=self.max_distance
                )
            self._instances[key] = inst
        inst.max_distance = self.max_distance
        if inst.tuner is not None:
            # The caller's budget (IncrementalDDMin's growing cap) is the
            # floor; a tuner that widened past it keeps its wider budget.
            inst.tuner.max_distance = (
                self.max_distance
                if inst.tuner.max_distance is None
                else max_distance_union(
                    inst.tuner.max_distance, self.max_distance
                )
            )
            if inst.tuner.max_distance is not None:
                inst.max_distance = inst.tuner.max_distance
        return inst

    @staticmethod
    def _check_fingerprint(violation_fingerprint) -> None:
        if violation_fingerprint is not None and not hasattr(
            violation_fingerprint, "code"
        ):
            # Device verdicts are int codes (same contract as
            # DeviceSTSOracle); don't silently widen unknown fingerprints
            # to accept-anything.
            raise TypeError(
                "DeviceDPOROracle needs an IntViolation-style fingerprint "
                f"(got {type(violation_fingerprint).__name__})"
            )

    def _lift(self, externals, found, violation_fingerprint):
        """Lift a violating device lane to a full host EventTrace via
        GuidedScheduler — the host half of a probe (and the part
        ``test_window`` keeps on-consult, in sequential order)."""
        from ..schedulers.guided import GuidedScheduler, GuideDivergence
        from .encoding import device_trace_to_guide

        records, trace_len = found
        guide = device_trace_to_guide(self.app, records, trace_len)
        gs = GuidedScheduler(self.config, self.app)
        # No per-delivery check needed here: a violating device lane halts
        # at the violation, so the lifted trace's final state carries it.
        try:
            result = gs.execute_guide(guide)
        except GuideDivergence:
            obs.counter("dpor.lift_divergences").inc()
            return None  # device/host mismatch = non-reproduction
        if result.violation is None:
            return None
        if violation_fingerprint is not None and not violation_fingerprint.matches(
            result.violation
        ):
            return None
        result.trace.set_original_externals(list(externals))
        return result.trace

    def test(self, externals, violation_fingerprint, stats=None, init=None):
        if stats is not None:
            stats.record_replay()
        self._check_fingerprint(violation_fingerprint)
        dpor = self._instance(externals)
        target = getattr(violation_fingerprint, "code", None)
        with obs.span(
            "dpor.oracle_probe", externals=len(externals)
        ) as sp:
            found = dpor.explore(
                target_code=target, max_rounds=self.max_rounds
            )
            sp.set(found=found is not None)
        self.last_interleavings = dpor.interleavings
        if found is None:
            return None
        return self._lift(list(externals), found, violation_fingerprint)

    def test_window(self, candidates, violation_fingerprint):
        """One batched window of DPOR probes: per-candidate lazy
        resolvers whose consulted prefix behaves exactly like sequential
        ``test`` calls. The device work — every probe's frontier rounds —
        runs eagerly up front via ``explore_window`` (left and right
        probes' rounds share launches), but each probe's resumable
        instance state (explored set, frontier, interleavings, tuner)
        commits only when its resolver is consulted: the pre-window
        snapshot is restored immediately after exploration, and the
        resolver swaps in the post-window snapshot. A probe the caller
        never consults — DDMin's right half after a left success — leaves
        its instance exactly as the sequential path (which never ran it)
        would have. The host lift stays on-consult, in consult order."""
        self._check_fingerprint(violation_fingerprint)
        target = getattr(violation_fingerprint, "code", None)
        probes: List[tuple] = []
        window: List[DeviceDPOR] = []
        seen_keys = set()
        for ext in candidates:
            key = tuple(e.eid for e in ext)
            if key in seen_keys:
                # Duplicate subsequence in one window: the second probe
                # must observe the first's committed state, which only
                # exists at consult time — resolve it sequentially.
                probes.append((list(ext), None, None))
                continue
            seen_keys.add(key)
            dpor = self._instance(ext)
            probes.append((list(ext), dpor, _dpor_search_state(dpor)))
            window.append(dpor)
        with obs.span("dpor.window", probes=len(window)) as sp:
            founds = explore_window(window, target, self.max_rounds)
            sp.set(found=sum(f is not None for f in founds))
        posts = [_dpor_search_state(d) for d in window]
        by_inst = {id(d): k for k, d in enumerate(window)}
        for _ext, dpor, pre in probes:
            if dpor is not None:
                _dpor_restore_state(dpor, pre)

        def resolver(i: int):
            ext, dpor, _pre = probes[i]
            if dpor is None:
                return self.test(ext, violation_fingerprint)
            k = by_inst[id(dpor)]
            _dpor_restore_state(dpor, posts[k])
            self.last_interleavings = dpor.interleavings
            found = founds[k]
            if found is None:
                return None
            return self._lift(ext, found, violation_fingerprint)

        return [(lambda i=i: resolver(i)) for i in range(len(probes))]


def max_distance_union(a: Optional[int], b: Optional[int]) -> Optional[int]:
    """The looser of two edit-distance budgets (None = unbounded)."""
    if a is None or b is None:
        return None
    return max(a, b)


def _resolve_double_buffer(
    explicit: Optional[bool] = None, async_min: Optional[bool] = None
) -> bool:
    """Resolve the in-flight-round switch: an explicit constructor arg
    wins (bench and the calibrated tune axis pass one); otherwise the
    feature rides the async-minimization switch — ``async_min`` where the
    caller was told it (the oracle), else the ``DEMI_ASYNC_MIN`` umbrella
    flag — and defaults on only where speculation is free: platforms
    where host and device are disjoint. On CPU the device lanes run on
    the host's own cores, so a mispredicted in-flight launch burns real
    compute; there the tuner (``tune.calibrate_dpor_inflight``) must
    measure the trade."""
    if explicit is not None:
        return bool(explicit)
    from ..minimization.pipeline import async_min_enabled

    if not async_min_enabled(async_min):
        return False
    return jax.devices()[0].platform != "cpu"


def _dpor_search_state(dpor: "DeviceDPOR") -> tuple:
    """Snapshot of a DeviceDPOR's host-side search state — everything a
    round mutates. ``test_window`` uses it to run speculative probes'
    rounds eagerly (their device work shares the window launch) while
    committing their instance state only on consult, so an unconsulted
    probe leaves its resumable frontier exactly as the sequential path
    would have."""
    tuner = None
    if dpor.tuner is not None:
        tuner = (
            dpor.tuner.rounds, dpor.tuner.round_batch,
            dpor.tuner.max_distance,
        )
    sleep_state = None
    if dpor.sleep is not None:
        sleep_state = (
            set(dpor.sleep.classes),
            {k: list(v) for k, v in dpor.sleep._node_flips.items()},
            dict(dpor.sleep.pruned_total),
        )
    return (
        dpor._explored_log.copy(), dpor.frontier.copy(), dpor.original,
        dpor.max_distance, dpor.interleavings, dpor.round_batch,
        dict(dpor.async_stats), tuner, set(dpor._explored_digests),
        dpor.host_seconds, dpor.device_seconds,
        dict(dpor._sleep_rows),
        set(dpor._suppressed_digests), set(dpor.violation_codes),
        sleep_state, dict(dpor._guides),
    )


def _dpor_restore_state(dpor: "DeviceDPOR", state: tuple) -> None:
    # The explored log rolls back in place (the lists that index it stay
    # bound to it); the durable-checkpoint pack cache re-validates itself
    # against it (prefix + last-entry check) and rebuilds when the
    # rollback invalidated it.
    dpor._explored_log.restore(state[0])
    (
        dpor.frontier, dpor.original, dpor.max_distance,
        dpor.interleavings, dpor.round_batch, async_stats, tuner,
        dpor._explored_digests, dpor.host_seconds, dpor.device_seconds,
    ) = (
        state[1].copy(), state[2], state[3], state[4],
        state[5], dict(state[6]), state[7], set(state[8]),
        state[9], state[10],
    )
    dpor._sleep_rows = dict(state[11])
    dpor._suppressed_digests = set(state[12])
    dpor.violation_codes = set(state[13])
    if getattr(dpor, "_sharder", None) is not None:
        # Snapshots hold the digest sets FLAT; a sharded instance
        # re-partitions them by digest range on restore (also how an
        # N-shard checkpoint restores into M shards).
        from ..fleet.shard import DigestShards

        dpor._explored_digests = DigestShards(
            dpor._host_shards, dpor._explored_digests
        )
        dpor._suppressed_digests = DigestShards(
            dpor._host_shards, dpor._suppressed_digests
        )
    dpor._guides = dict(state[15])
    if state[14] is not None and dpor.sleep is not None:
        dpor.sleep.classes = set(state[14][0])
        dpor.sleep._node_flips = {
            k: list(v) for k, v in state[14][1].items()
        }
        dpor.sleep.pruned_total = dict(state[14][2])
    if tuner is not None and dpor.tuner is not None:
        (
            dpor.tuner.rounds, dpor.tuner.round_batch,
            dpor.tuner.max_distance,
        ) = tuner


def steering_prescription(
    app: DSLApp,
    cfg: DeviceConfig,
    trace,
    externals: Sequence[ExternalEvent],
) -> Tuple[Tuple[int, ...], ...]:
    """Lower a recorded violating EventTrace to a DPOR prescription (its
    delivery/timer records in order) so the first device execution replays
    the recorded schedule — the device analog of the host scheduler's
    initial-trace steering (DPORwHeuristics.scala:542-555). Prescription
    following is divergence-tolerant, so a projected subsequence's missing
    records are skipped."""
    from .encoding import lower_expected_trace

    projected = (
        trace.filter_failure_detector_messages()
        .filter_checkpoint_messages()
        .subsequence_intersection(list(externals))
    )
    recs = lower_expected_trace(app, cfg, projected, externals, cfg.max_steps)
    keep = recs[np.isin(recs[:, 0], (REC_DELIVERY, REC_TIMER))]
    return tuple(map(tuple, keep.tolist()))


#: Why ``DeviceDPOR`` (and the ``dpor`` verb) turn a FIFO app away.
FIFO_REFUSAL = (
    "DPOR does not explore an app whose channels are FIFO "
    "(DSLApp.channels): its racing analysis reverses two messages of one "
    "(sender, receiver) pair, an order such a network cannot deliver; "
    "sweep it instead"
)


#: Why they turn a datagram app away.
DATAGRAM_REFUSAL = datagram_refusal(
    "DPOR (its racing analysis reverses two deliveries of two messages)"
)


class DeviceDPOR:
    """Frontier-batched DPOR driver: rounds of B prescriptions per kernel
    launch, deepest-first priority, explored-set dedup.

    The frontier persists across ``explore`` calls (resumability — the
    device analog of DPORwHeuristics keeping depGraph/backTrack intact
    across test() calls, :225-254); ``seed`` plants an initial-trace
    prescription; ``max_distance`` caps accepted backtracks by modified
    edit distance to the seeded schedule (ArvindDistanceOrdering's metric
    over record identities).

    ``double_buffer`` (default: on under ``DEMI_ASYNC_MIN`` on non-CPU
    platforms — see ``_resolve_double_buffer``) overlaps rounds: round
    N+1's prescriptions are planned, grouped, and dispatched as a FULL
    in-flight launch while round N's codes are still on device, on the
    prediction that round N's harvest adds nothing that outranks the
    current frontier. A correct prediction makes the next harvest free of
    dispatch latency; a misprediction discards the in-flight launch
    unharvested, so the explored set, frontier, and every per-lane result
    stay bit-identical to the synchronous loop (lane keys depend only on
    the round index, which speculation preserves)."""

    @obs.spans.staged("setup.build", what="DeviceDPOR")
    def __init__(
        self,
        app: DSLApp,
        cfg: DeviceConfig,
        program: Sequence[ExternalEvent],
        batch_size: int = 64,
        mesh=None,
        prefix_fork: Optional[bool] = None,
        fork_bucket: int = 8,
        fork_min_group: Optional[int] = None,
        double_buffer: Optional[bool] = None,
        kernel=None,
        fork_kernel=None,
        static_independence=None,
        sleep_sets=None,
        key_mode: Optional[str] = None,
        host_shards: Optional[int] = None,
    ):
        assert cfg.record_trace and cfg.record_parents
        if app.channels == "fifo":
            raise ValueError(FIFO_REFUSAL)
        if app.channels == "datagram":
            raise ValueError(DATAGRAM_REFUSAL)
        self.app = app
        self.cfg = cfg
        # Static may-commute relation resolved FIRST: the sleep-set
        # machinery reuses it as its dependence oracle when both are on.
        self.static_independence = _resolve_static_independence(
            app, static_independence
        )
        # Sleep sets + race-reversal class dedup (analysis/sleep.py; off
        # by default / DEMI_SLEEP_SETS=1): frontier prescriptions carry
        # bounded sleep rows the device kernel tracks wake ordinals for,
        # the racing scan refuses reversals asleep at their branch, and
        # admitted prescriptions dedup on Mazurkiewicz-canonical class
        # keys — counted in analysis.sleep_pruned, never admitted.
        self.sleep = _resolve_sleep_sets(
            app, sleep_sets, self.static_independence
        )
        # Per-lane rng keys: 'position' (the default — key = cumulative
        # batch position) or 'content' (key derived from the
        # prescription's content digest, so a prescription explores the
        # SAME suffix regardless of where pruning shifts it in the
        # round order). Sleep mode defaults to content keys: the A/B
        # contract (pruned explored ⊆ unpruned, violations preserved)
        # only holds when pruning cannot reshuffle every surviving
        # lane's randomness. Padding lanes all share the empty
        # prescription's key under content mode — determinism traded
        # for pad diversification, exactly the redundancy-measurement
        # trade.
        if key_mode is None:
            key_mode = "content" if self.sleep is not None else "position"
        if key_mode not in ("position", "content"):
            raise ValueError(
                f"key_mode must be 'position' or 'content', got {key_mode!r}"
            )
        self.key_mode = key_mode
        if mesh is not None:
            # Frontier rounds sharded over the device mesh (SURVEY.md
            # §2.8: the batch axis covers EVERY batched workload, the
            # search kernels included). Rounds are padded to batch_size,
            # which must divide over the mesh axis.
            from ..parallel.mesh import LANES

            if batch_size % mesh.shape[LANES]:
                raise ValueError(
                    f"batch_size {batch_size} must be a multiple of the "
                    f"mesh axis {mesh.shape[LANES]}"
                )
        if kernel is not None:
            # A caller-shared kernel (DeviceDPOROracle keeps one per
            # app/cfg/mesh): every fresh DeviceDPOR otherwise jits its own
            # closure, so a DDMin run probing many subsequences would
            # recompile the identical kernel per subsequence. With sleep
            # sets on the caller must share a SLEEP kernel (same
            # sleep_cap/matrix), and with a mesh the SHARDED twin — the
            # oracle does both.
            self.kernel = kernel
        else:
            self.kernel = build_dpor_kernel(
                app, cfg, mesh=mesh, **self._sleep_kernel_args()
            )
        self.prog = lower_program(app, cfg, list(program))
        self.batch_size = batch_size
        # Prefix-fork (device/fork.py, DEMI_PREFIX_FORK=1 / --prefix-fork):
        # frontier prescriptions grouped by shared prefix; each group
        # resumes from a (LRU-cached) trunk snapshot instead of replaying
        # the prefix per lane. Per-lane keys are assigned by batch
        # position on both paths, so round results are bit-identical.
        from .fork import prefix_fork_enabled

        self._forker = None
        if prefix_fork_enabled(prefix_fork):
            from .fork import (
                PrefixForker,
                make_dpor_prefix_resume_runner,
                make_dpor_prefix_runner,
            )

            self._fork_kernel = fork_kernel or build_dpor_kernel(
                app, cfg, mesh=mesh, start_state=True,
                **self._sleep_kernel_args(),
            )
            if fork_min_group is None:
                # A trunk run is a SINGLE-lane O(prefix) execution and a
                # fork group is an extra kernel launch: on CPU — where a
                # vectorized lane costs nearly as much as a scalar one
                # and launches are not free — even the 4-7-lane sibling
                # groups the bucketed selection now produces lose to one
                # whole-batch launch when the trunk cache misses (round
                # prefixes are round-unique, so misses dominate; measured
                # on bench config 8). Require half a batch before a CPU
                # trunk pays; on accelerators the batched lanes are
                # effectively free next to the trunk launch, so keep the
                # planner's permissive default.
                fork_min_group = (
                    max(8, batch_size // 2)
                    if jax.devices()[0].platform == "cpu"
                    else 2
                )
            self._forker = PrefixForker(
                make_dpor_prefix_runner(app, cfg),
                bucket=fork_bucket,
                min_group=fork_min_group,
                driver="dpor",
                # Prescribed-resume trunks: a trunk-cache miss resumes
                # the nearest cached ancestor over the remaining
                # prescription rows (O(bucket)) instead of re-following
                # the full prefix (O(p)) — the DPOR twin of the replay
                # checker's hierarchical trunks.
                resume_runner=make_dpor_prefix_resume_runner(app, cfg),
                # Cross-round trunk reuse (the PR 6 ~0%-hit debt):
                # DEMI_FORK_ANCHOR_STRIDE=N caches anchor snapshots
                # every N buckets while building a trunk, so a later
                # round's round-unique prefix resumes the deepest
                # shared anchor instead of starting over. Keys are
                # match-normalized (see _dispatch_round), which is what
                # makes cross-round sharing possible at all. Measured
                # on the config-8 sequential frontier: trunk hit rate
                # 0.13 -> 0.64 by round 6 (parent + anchor resumes);
                # under the double-buffered round composition the
                # anchors cost extra launches without hits on CPU —
                # so, like every fork feature, opt-in until measured
                # where launches are cheap.
                anchor_stride=int(
                    os.environ.get("DEMI_FORK_ANCHOR_STRIDE", "0")
                ) or None,
                # Anchors live or die by LRU headroom: a chain caches
                # one snapshot per stride boundary, and the SHALLOW
                # boundaries — the ones every racing family shares —
                # are also the least-recently-used entries, so a tight
                # cache evicts exactly the reusable ones first. One
                # snapshot is a single lane's state (tens of KB), so
                # hundreds stay cheap.
                capacity=(
                    512
                    if os.environ.get("DEMI_FORK_ANCHOR_STRIDE", "0") != "0"
                    else 32
                ),
            )
        self._mesh = mesh
        # Layout of the first whole-batch round harvested: devices its
        # output spanned and lanes on each.
        self.lane_sharding: Optional[dict] = None
        self._double_buffer = _resolve_double_buffer(double_buffer)
        # In-flight round economics (the signal calibrate_dpor_inflight
        # and bench config 8 read): speculative launches, and how many
        # were used vs discarded.
        self.async_stats = {
            "inflight_rounds": 0,
            "inflight_hits": 0,
            "inflight_waste": 0,
        }
        # Host-share accounting (always on — two perf_counter reads per
        # round): wall time blocked harvesting device results vs
        # everything else in the frontier loop. The dpor.host_share gauge
        # (obs) and bench configs 2/8 read these.
        self.host_seconds = 0.0
        self.device_seconds = 0.0
        # What was admitted, in admission order and in columns
        # (device/explored_log.py): a prescription is its source lane's
        # delivery rows, kept once per lane, plus a length, the flipped
        # row and the content digest. ``explored`` is a read-only set
        # view over it, the frontier a list of indices into it; a Python
        # tuple exists only where somebody asks for one. The durable-
        # checkpoint codec serializes the log as delta frames and the
        # frontier as the indices, with an incremental pack cache so each
        # snapshot packs only the entries admitted since the last one
        # (demi_tpu/persist).
        self._explored_log = ExploredLog(cfg.rec_width)
        self._explored = ExploredView(self)
        self._persist_pack_cache = None
        # The 16-byte content keys of everything in the log: what decides
        # membership, for the round's candidates (digested in the scan)
        # and for a tuple from outside (``p in explored`` digests it).
        self._explored_digests: Set[bytes] = set()
        self.admit_tuples([tuple()])  # the root, log index 0
        self.frontier = [0]
        # Adaptive (n_presc, n_rows) buffer hint for the batch scan.
        self._batch_size_hint: Optional[Tuple[int, int]] = None
        # Persistent scan output buffers for the unsharded batch path:
        # the adaptive size hint lives per INSTANCE (native.ScanBuffers)
        # instead of per call, so a steady-state round reallocates
        # nothing.
        from ..native import ScanBuffers

        self._scan_buffers = ScanBuffers()
        # Digest-range-sharded admission (fleet/shard.py; host_shards >
        # 1 via the constructor, which --host-shards reaches):
        # the round's scan/filter/dedup pipeline runs as N concurrent
        # digest-range shards, then a serial canonical merge
        # (_admit_stream) applies fresh admissions in the sequential
        # round order — explored/class/violation sets, frontier, and
        # first-found record stay bit-identical at any shard count.
        # The digest sets become DigestShards (a drop-in set facade
        # partitioned by range) so each shard's dedup thread owns a
        # disjoint slice. Composes with sleep sets, static pruning,
        # prefix-fork, and double-buffering: sharding only touches how
        # one harvested round's candidates are scanned and deduped.
        from ..fleet.shard import resolve_host_shards

        self._host_shards = resolve_host_shards(host_shards)
        self._sharder = None
        if self._host_shards > 1:
            from ..fleet.shard import DigestShards, ShardedAdmission

            self._sharder = ShardedAdmission(self._host_shards)
            self._explored_digests = DigestShards(
                self._host_shards, self._explored_digests
            )
        self.original: Optional[Tuple] = None
        self.max_distance: Optional[int] = None
        # Closed seeded exploration (analysis/delta.py): when False, the
        # prescription-free PADDING lanes still run (the kernel batch
        # shape is compiled) but their harvested races are not admitted
        # to the frontier — every explored class then descends from a
        # seeded prescription and carries an exact trunk-divergence
        # index in its meta, which is what differential re-verification
        # transfers on. Default True keeps the classic behavior: pads
        # diversify the frontier with random exploration.
        self.pad_exploration: bool = True
        self.interleavings = 0
        # Sleep-set side state: per-prescription sleep rows (frontier
        # entries stay plain tuples — selection, dedup, and every parity
        # surface are untouched), plus the class-suppressed digests, kept
        # as the explored digests are.
        self._sleep_rows: Dict[Tuple, Tuple[Tuple[int, ...], ...]] = {}
        self._suppressed_digests: Set[bytes] = set()
        if self._sharder is not None:
            from ..fleet.shard import DigestShards

            self._suppressed_digests = DigestShards(self._host_shards)
        # Wakeup-sequence guides (sleep mode only): a reversal's
        # EXECUTION follows the full bounded wakeup sequence — prefix,
        # flipped record, then the source lane's remaining deliveries in
        # order (divergence-tolerant) — while its frontier IDENTITY
        # stays ``prefix + flip`` (wakeup-tree node identity: suffix
        # reorderings collapse into the same node, which is what turns
        # classic DPOR's re-derivations into raw-redundant hits). Keyed
        # by the identity tuple; ``_pack`` substitutes the guide rows.
        self._guides: Dict[Tuple, np.ndarray] = {}
        # Admitted prescription -> canonical class key (sleep mode):
        # lives exactly as long as the guide (popped once executed), so
        # per-round violation witnesses and the published ledger's
        # pending set can attribute lanes to classes.
        self._class_of: Dict[Tuple, tuple] = {}
        if self.sleep is not None:
            self.sleep.note_class(())  # the root schedule's class
            self._class_of[()] = ()
        # Distinct violation codes observed across all lanes of all
        # rounds (always tracked — one np.unique per round): the
        # violation-set preservation surface the sleep-set A/B asserts.
        self.violation_codes: Set[int] = set()
        # Per-code canonical first-found witness: the violating lane
        # record with the smallest trace digest seen so far —
        # {"sha", "class", "trace"}. Min-digest (not chronology) makes
        # the record order-free, so a differential re-exploration and a
        # scratch run converge on identical witnesses (analysis/delta).
        self.violation_witnesses: Dict[int, Dict[str, object]] = {}
        # Continuous observability (obs/journal.py): rounds executed so
        # far (1-based after the first round; checkpointed + restored so
        # a resumed journal stays generation-contiguous) and the last
        # round's local stats, stashed by _process_round for the journal
        # record — a tiny always-on dict, measured inside bench config
        # 11's <1% budget.
        self.round_index = 0
        self._last_round: Dict[str, object] = {}
        # Measurement-guided budget control (demi_tpu/tune): when set, the
        # tuner sees each round's fresh/redundant/pruned prescription
        # counts and adjusts max_distance and round_batch online. The
        # kernel batch stays compiled at batch_size; round_batch caps how
        # many FRONTIER prescriptions are dispatched per round — surplus
        # lanes run prescription-free random exploration, so a
        # redundant-saturated frontier trades prescribed lanes for
        # diversification instead of re-deriving known schedules.
        self.tuner = None
        self.round_batch = batch_size

    @property
    def explored(self) -> ExploredView:
        """Every admitted prescription, as a read-only set of tuples over
        the columnar log and the digest set."""
        return self._explored

    @property
    def frontier(self) -> PrescList:
        """The worklist: prescriptions admitted and not yet executed, as
        a list over the explored log whose items read as tuples of row
        tuples. Assigning a list of tuples (or of log indices) binds it
        to the log."""
        return self._frontier

    @frontier.setter
    def frontier(self, prescriptions) -> None:
        self._frontier = self._list(prescriptions)

    def _list(self, prescriptions) -> PrescList:
        """``prescriptions`` as a list over this driver's log: itself
        when it is one, else each item — an admitted tuple from outside,
        or a log index — looked up."""
        log = self._explored_log
        if isinstance(prescriptions, PrescList) and prescriptions.log is log:
            return prescriptions
        return PrescList(
            log,
            (
                p if isinstance(p, (int, np.integer)) else log.index_of(p)
                for p in prescriptions
            ),
        )

    def admit_tuples(
        self, prescriptions: Sequence[Tuple], keep: bool = True
    ) -> int:
        """Record prescriptions that arrive as tuples of row tuples — a
        seed, a re-seeded class representative, a restored checkpoint —
        as explored: the one way in for writers
        outside the round's admission. The caller has checked that they
        are new. Returns the first one's log index (they follow on)."""
        from ..native import digest_keys

        log = self._explored_log
        first = log.extend_tuples(prescriptions, keep=keep)
        self._explored_digests.update(digest_keys(log.digest[first: log.n]))
        return first

    def load_tuples(self, prescriptions: Sequence[Tuple]) -> None:
        """Replace everything explored by ``prescriptions``, in their
        order (a restored checkpoint's log; the first is the root)."""
        self._explored_log = ExploredLog(self.cfg.rec_width)
        self._explored_digests = set()
        self.admit_tuples(prescriptions, keep=False)

    def seed(self, prescription: Tuple[Tuple[int, ...], ...]) -> None:
        """Plant an initial prescription at the head of the frontier (and
        fix it as the edit-distance origin)."""
        self.original = prescription
        if prescription not in self.explored:
            self.frontier.insert(0, self.admit_tuples([prescription]))
            if self.sleep is not None and prescription:
                # Seeded rows carry no source-lane positions: creation
                # edges onto them never fire (class splits, never
                # falsely merges — see canonical_class_key). The seed's
                # guide is the prescription itself.
                ckey = self.sleep.class_key(
                    np.asarray(prescription, np.int32), None,
                    self.cfg.rec_width,
                )
                # TRUNK_BIT: the seed IS the trunk (zero reversals) —
                # differential exploration always re-executes it (trunk
                # revalidation, analysis/delta.py), and its descendants
                # start their reversal chains from an empty mask.
                from ..analysis.sleep import TRUNK_BIT

                self.sleep.note_class(
                    ckey, guide=prescription, plen=len(prescription),
                    dmask=TRUNK_BIT,
                )
                self._class_of[prescription] = ckey

    def checkpoint_state(self) -> dict:
        """JSON-able snapshot of everything a round mutates (frontier,
        explored tuple/digest sets, sleep rows + class ledger, guides,
        violation codes, rng round counters) — the durable twin of the
        in-memory ``_dpor_search_state``. Round-trips bit-identically:
        a fresh DeviceDPOR built with the same constructor arguments and
        ``restore_state(payload)`` continues exactly where this one
        stood (tests/test_persist.py)."""
        from ..persist.checkpoint import device_dpor_payload

        return device_dpor_payload(self)

    def restore_state(self, payload: dict) -> None:
        """Inverse of ``checkpoint_state``; raises
        ``persist.CheckpointMismatch`` when the payload was captured
        under a different workload shape."""
        from ..persist.checkpoint import restore_device_dpor

        restore_device_dpor(self, payload)

    def _supervised_harvest(
        self, parts, batch: PrescList, prescs: np.ndarray, keys
    ):
        """Harvest one round under the launch supervisor: a failed or
        poisoned launch re-executes the round from its (pure) inputs —
        the round is a function of (prescs, keys, batch) alone, so a
        retry is bit-identical and nothing in the search state needs
        rewinding. Exhausted retries re-raise (strict-io makes that a
        StrictIOError); there is no host twin for the DPOR kernel."""
        from ..obs.profiler import PROFILER
        from ..persist.supervisor import SUPERVISOR

        def attempt(n: int):
            p = parts if n == 0 else self._dispatch_round(
                prescs, keys, batch
            )
            return self._harvest_round(p, len(batch))

        with obs.span("dpor.block", lanes=len(batch)) as sp:
            res = SUPERVISOR.run(attempt, label="dpor.launch")
        if PROFILER.enabled:
            PROFILER.block("dpor", len(batch), sp.seconds)
        return res

    def _pack_round(self, batch: PrescList, base: int):
        """One round's kernel inputs: ``(_pack(batch), _round_keys(...))``
        with ``base`` the interleaving count the round starts from."""
        with obs.span("dpor.pack"):
            return self._pack(batch), self._round_keys(
                len(batch), base, batch=batch
            )

    def _pack(self, prescriptions) -> np.ndarray:
        """The kernel's prescription input for one batch: each lane's
        rows copied from the log's columns (its source lane's delivery
        rows, then the flipped row); in sleep mode a wakeup-sequence
        guide wins."""
        batch = self._list(prescriptions)
        log = self._explored_log
        r, w = self.cfg.max_steps, self.cfg.rec_width
        out = np.zeros((len(batch), r, w), np.int32)
        guides = (
            [self._guides.get(p) for p in batch.tuples()]
            if self.sleep is not None and self._guides
            else None
        )
        for k, i in enumerate(batch.idx):
            guide = guides[k] if guides is not None else None
            if guide is not None:
                m = min(len(guide), r)
                out[k, :m] = guide[:m]
            elif i:
                log.write_rows(i, out[k])
        return out

    def _sleep_from(self, batch) -> np.ndarray:
        """Per-lane node ordinal (sleep mode): the delivery count of the
        lane's IDENTITY prescription (prefix + flip) — wake tracking and
        sleep-membership checks apply at/after it. Guide rows beyond the
        identity are ordinary prescribed deliveries and ARE tracked."""
        return self._list(batch).lengths().astype(np.int32)

    def _progs(self, b: int) -> ExtProgram:
        from .explore import broadcast_program

        return broadcast_program(self.prog, b)

    def _select_batch(
        self, frontier: PrescList
    ) -> Tuple[PrescList, PrescList]:
        """Pure round selection: ``(batch, rest)`` for one frontier round
        — deepest-first with a seeded initial prescription pinned to the
        head, padded to ``batch_size`` with prescription-free lanes. Does
        NOT mutate the input, and is deterministic in (frontier,
        round_batch): because rounds select from the FROZEN generation
        (fresh prescriptions join the next generation — see ``explore``),
        the double-buffered loop's in-flight round is the real next round
        whenever this selection re-runs unchanged after the harvest.

        Depth orders at BUCKET granularity (8 rows — the planner's
        default trunk bucket) with lexicographic content order within a
        bucket (fork-group growth): prescriptions sharing long prefixes
        — same-lane racing families, equal-depth siblings from ANY
        generation — cluster on the same side of the round cut instead
        of scattering across rounds by exact depth. Measured on the
        config-8 frontier this turns the structural 2-lane sibling
        groups into 4-7-lane groups (the size a resume trunk pays for on
        CPU) while staying within 7 rows of strict deepest-first. The
        constant bucket keeps selection independent of any fork
        configuration, so every host-path/async variant explores the
        identical schedule space."""
        frontier = self._ordered_frontier(frontier)
        take = max(1, min(self.round_batch, self.batch_size))
        batch, rest = frontier.split(take)
        return batch.padded(self.batch_size), rest

    def _ordered_frontier(self, frontier) -> PrescList:
        """The ONE round-order rule (see ``_select_batch``): a seeded
        original pinned at the head, then deepest-bucket-first with
        lexicographic content order within a bucket. Bench config 8's
        sibling-clustering measurement calls this too, so it can never
        measure an ordering the frontier doesn't actually use.

        The bucket is a function of the log's length column, so the
        grouping is one stable argsort; the content order within a
        bucket needs the tuples, and is worked out only when a read
        reaches that bucket (``PrescList``): a round materializes the
        buckets its batch comes from and no others."""
        frontier = self._list(frontier)
        if frontier.ordered:
            return frontier
        pinned = (
            self.original is not None and len(frontier) > 0
            and frontier[0] == self.original
        )
        return frontier.by_depth_bucket(8, head=int(pinned))

    def _merge_generations(
        self, gen: PrescList, pending: PrescList
    ) -> Tuple[PrescList, PrescList]:
        """Cross-generation round filling (fork-group growth): when the
        frozen generation can no longer FILL a round, the next generation
        joins it — so a round's batch carries equal-depth prescriptions
        from both generations instead of padding with prescription-free
        lanes, and the PrefixPlanner gets sibling groups worth a resume
        trunk. Deterministic in (gen, pending, round_batch): both the
        synchronous loop and the double-buffered speculation check derive
        the same decision, so a merge at a generation boundary costs at
        most one discarded in-flight launch, never a divergence."""
        gen, pending = self._list(gen), self._list(pending)
        if not pending:
            return gen, pending
        take = max(1, min(self.round_batch, self.batch_size))
        if len(gen) >= take:
            return gen, pending
        return gen + pending, PrescList(self._explored_log)

    def _sleep_kernel_args(self) -> dict:
        """The (sleep_cap, commute_matrix) pair that fixes this
        instance's kernel shape."""
        if self.sleep is None:
            return {"sleep_cap": 0, "commute_matrix": None}
        return {
            "sleep_cap": self.sleep.cap, "commute_matrix": self.sleep.matrix
        }

    def _round_seeds(
        self, n: int, base: int, batch: Optional[PrescList] = None
    ) -> np.ndarray:
        """Per-lane rng seeds (uint32) for one round — pure NumPy, so a
        process that only plans rounds (the fleet coordinator) derives
        them without initialising a JAX backend; ``lane_keys`` folds
        them into keys where the kernel runs. ``key_mode='position'``
        (the default): position in the cumulative interleaving count —
        every round is padded to ``batch_size``, so ``base`` advances
        deterministically and a speculative round N+1 dispatched before
        round N's harvest derives the exact keys the synchronous loop
        would. ``key_mode='content'`` (sleep-set mode): each lane's seed
        derives from its prescription's content digest, so a
        prescription explores the identical suffix no matter where
        pruning shifts it in the round order — the property the sleep
        A/B's explored-subset/violation-preservation contract rests on."""
        if self.key_mode == "content" and batch is not None:
            # The first four bytes of each prescription's digest: the
            # stored one for a list over the log, taken anew for tuples
            # from outside (which need not have been admitted).
            log = self._explored_log
            if isinstance(batch, PrescList) and batch.log is log:
                idx = np.asarray(batch.idx, np.int64)
                return log.digest[idx].view(np.uint32)[:, 0].copy()
            from ..native import prescription_digest

            return np.asarray(
                [
                    int.from_bytes(prescription_digest(p)[:4], "little")
                    for p in batch
                ],
                np.uint32,
            )
        return np.arange(base, base + n, dtype=np.uint32)

    def _round_keys(self, n: int, base: int, batch: Optional[PrescList] = None):
        """Per-lane keys for one round: ``lane_keys(_round_seeds(...))``."""
        return lane_keys(self._round_seeds(n, base, batch=batch))

    def _dispatch_round(self, prescs: np.ndarray, keys, batch: PrescList):
        """Launch one frontier round's lane work WITHOUT pulling results
        — the dispatch half of the round (pair with ``_harvest_round``).
        Returns a list of ``(indices, device LaneResult)`` parts;
        ``indices=None`` means the whole batch in order.

        Scratch mode: one whole-batch kernel launch. Prefix-fork mode:
        prescriptions grouped by bucketed shared prefix (PrefixPlanner);
        each group resumes from a cached trunk snapshot via the
        ``start_state=`` kernel — a trunk-cache miss first tries to
        derive the trunk by resuming the nearest cached ancestor over
        the remaining prescribed rows (``trunk_hier_prescribed``,
        O(bucket) instead of O(prefix)) — and everything else
        (prescription-free pads included) runs the scratch kernel.
        Per-lane keys follow batch position on both paths, so per-lane
        results are bit-identical."""
        from ..obs.profiler import PROFILER

        with obs.span("dpor.dispatch", lanes=len(batch)) as sp:
            sleeps = (
                self._pack_sleep(batch) if self.sleep is not None else None
            )
            sfrom = self._sleep_from(batch) if sleeps is not None else None
            if self._forker is not None and len(batch) >= 2:
                return self._dispatch_forked(
                    prescs, keys, batch, sleeps, sfrom
                )
            if sleeps is None:
                out = [
                    (None, self.kernel(self._progs(len(batch)), prescs, keys))
                ]
            else:
                out = [(
                    None,
                    self.kernel(
                        self._progs(len(batch)), prescs, keys, sleeps, sfrom
                    ),
                )]
        if PROFILER.enabled:
            PROFILER.dispatch("dpor", len(batch), sp.seconds)
        return out

    def _dispatch_forked(
        self, prescs: np.ndarray, keys, batch: PrescList, sleeps, sfrom
    ):
        """The prefix-fork half of ``_dispatch_round``: trunk builds and
        group launches, each timed into the launch ledger by itself."""
        from ..obs.profiler import PROFILER
        from .fork import padded_size, prefix_digest

        keys = np.asarray(keys)
        batch = self._list(batch)
        lengths = (
            np.asarray([len(self._guides.get(p, p)) for p in batch.tuples()])
            if self.sleep is not None
            else batch.lengths()
        )
        # Plan and key trunks over MATCH-NORMALIZED rows: the
        # prescribed-dispatch matcher never reads the parent/prev
        # bookkeeping columns, so two prescriptions identical in the
        # matchable columns execute to bit-identical trunk states even
        # when their source lanes recorded different trace positions.
        # Keying on raw bytes was why cross-round reuse measured ~0%
        # (a re-derived prefix differs from its ancestor only in the
        # flip row's prev column); normalized keys let round N+1's
        # trunks resume round N's. Lanes still receive the ORIGINAL
        # rows — only grouping/caching identity changes.
        plan_rows = prescs.copy()
        plan_rows[:, :, self.cfg.rec_width - 2:] = 0
        groups, scratch = self._forker.plan(plan_rows, lengths)
        if sleeps is not None:
            # Sleep mode: trunk prefixes stop BELOW every member's node
            # ordinal, so the shared (untracked) trunk segment never
            # enters the region the per-lane wake tracking must cover.
            bucket = self._forker.planner.bucket
            adjusted = []
            for g in groups:
                cap = (min(int(sfrom[i]) for i in g.indices) // bucket) * bucket
                if cap <= 0:
                    scratch.extend(g.indices)
                    continue
                if g.prefix_len > cap:
                    g = g._replace(
                        prefix_len=cap,
                        key=prefix_digest(
                            plan_rows[g.indices[0], :cap].tobytes()
                        ),
                    )
                adjusted.append(g)
            groups = adjusted
        parts: List[Tuple[Optional[List[int]], LaneResult]] = []

        for g in groups:
            if not self._forker.should_fork(g):
                scratch.extend(g.indices)
                continue
            # Trunk follows the normalized rows (execution-identical —
            # the matcher ignores the zeroed columns — and the key space
            # the ancestor walk + anchors live in).
            trunk_presc = np.zeros_like(plan_rows[0])
            trunk_presc[: g.prefix_len] = plan_rows[
                g.indices[0], : g.prefix_len
            ]
            t0 = time.perf_counter() if PROFILER.enabled else 0.0
            snap, trunk_steps, hit = self._forker.trunk_hier_prescribed(
                g.key,
                ExtProgram(*(np.asarray(x) for x in self.prog)),
                trunk_presc,
                jax.random.PRNGKey(0),
                g.prefix_len,
            )
            if PROFILER.enabled:
                PROFILER.trunk(
                    "dpor-trunk", 1, time.perf_counter() - t0,
                    shape=f"p={g.prefix_len}",
                )
            full = g.indices + [g.indices[0]] * (
                padded_size(len(g.indices), self._mesh) - len(g.indices)
            )
            t0 = time.perf_counter() if PROFILER.enabled else 0.0
            if sleeps is None:
                res_g = self._fork_kernel(
                    self._progs(len(full)), prescs[full], keys[full], snap
                )
            else:
                res_g = self._fork_kernel(
                    self._progs(len(full)), prescs[full], keys[full],
                    sleeps[full], sfrom[full], snap,
                )
            if PROFILER.enabled:
                PROFILER.dispatch(
                    "dpor-fork", len(full), time.perf_counter() - t0
                )
            parts.append((g.indices, res_g))
            self._forker.note_group(len(g.indices), trunk_steps, hit)
            obs.histogram("dpor.prefix_group_size").observe(len(g.indices))
        if scratch:
            full = scratch + [scratch[0]] * (
                padded_size(len(scratch), self._mesh) - len(scratch)
            )
            t0 = time.perf_counter() if PROFILER.enabled else 0.0
            if sleeps is None:
                res_s = self.kernel(
                    self._progs(len(full)), prescs[full], keys[full]
                )
            else:
                res_s = self.kernel(
                    self._progs(len(full)), prescs[full], keys[full],
                    sleeps[full], sfrom[full],
                )
            if PROFILER.enabled:
                PROFILER.dispatch(
                    "dpor", len(full), time.perf_counter() - t0
                )
            parts.append((scratch, res_s))
            self._forker.note_scratch(len(scratch))
        return parts

    def _pack_sleep(self, batch: PrescList) -> np.ndarray:
        """Fixed-shape sleep input for one round: each lane's sleep rows
        ([B, sleep_cap, recw] int32, kind 0 = empty slot) looked up from
        the frontier side-table — prescription-free padding lanes carry
        none."""
        S, w = self.sleep.cap, self.cfg.rec_width
        out = np.zeros((len(batch), S, w), np.int32)
        if not self._sleep_rows:
            return out
        for k, presc in enumerate(self._list(batch).tuples()):
            rows = self._sleep_rows.get(presc)
            if rows:
                for s, row in enumerate(rows[:S]):
                    out[k, s, : len(row)] = row
        return out

    def _harvest_round(self, parts, batch_len: int) -> LaneResult:
        """Block on a dispatched round's parts and merge them back into
        batch order (np arrays quack like the LaneResult — or
        DporSleepResult — the harvesting loops read)."""
        if len(parts) == 1 and parts[0][0] is None:
            res = parts[0][1]
            jax.block_until_ready(res.violation)
            if self.lane_sharding is None:
                from ..parallel.mesh import lane_sharding_summary

                self.lane_sharding = lane_sharding_summary(res.violation)
            return res
        res_type = type(parts[0][1])
        merged = {}
        for field in res_type._fields:
            ref = np.asarray(getattr(parts[0][1], field))
            merged[field] = np.zeros((batch_len,) + ref.shape[1:], ref.dtype)
        for idx, res in parts:
            jax.block_until_ready(res.violation)
            for field in res_type._fields:
                merged[field][np.asarray(idx)] = np.asarray(
                    getattr(res, field)
                )[: len(idx)]
        return res_type(**merged)

    def _process_round(
        self,
        res: LaneResult,
        batch: PrescList,
        target_code: Optional[int],
        frontier: PrescList,
        frontier_extra: int = 0,
    ) -> Optional[Tuple[np.ndarray, int]]:
        """The host half of a frontier round: telemetry, the violation
        scan, racing-prescription derivation (appended to ``frontier`` in
        place — the caller's NEXT-generation list under the frozen-
        generation policy), and tuner feedback (``frontier_extra`` counts
        worklist entries outside the sink list — the frozen generation's
        remainder — so the tuner sees the full frontier size). Returns a
        violating lane's (records, trace_len) or None.

        The whole round's prescriptions are derived in ONE batch-native
        call (packed int32 rows + per-lane offsets —
        native/trace_analysis.cpp or its NumPy twin) and deduped against
        the explored set on content digests; Python tuples exist only
        where a mode needs them (``_admit_stream``)."""
        batch = self._list(batch)
        self.interleavings += len(batch)
        with obs.span("dpor.pull"):
            if obs.enabled():
                # Device-lane totals for the round (one on-device
                # reduction, one pull) + the exploration-efficiency
                # counters optimal-DPOR tuning reads (redundant = already
                # explored, pruned = over the edit-distance cap).
                from ..obs import lane_stats as _ls

                _ls.record(
                    _ls.reduce_lanes(
                        res.status, res.violation, res.deliveries,
                        len(batch),
                        invariant_interval=self.cfg.invariant_interval,
                    ),
                    driver="dpor",
                )
                obs.counter("dpor.interleavings").inc(len(batch))
            violations = np.asarray(res.violation)[: len(batch)]
            traces = np.asarray(res.trace)
            lens = np.asarray(res.trace_len)
        with obs.span("dpor.violations"):
            round_codes, hit = self._note_violations(
                violations, traces, lens, batch, target_code
            )
        # Local fresh/redundant/pruned counts: the tuner's per-round
        # signal, needed whether or not telemetry is on (the obs
        # counters still carry the cross-round totals).
        if self._sharder is not None:
            fresh_n, redundant_n, pruned_n = self._derive_sharded(
                traces, lens, len(batch), frontier, batch=batch, res=res
            )
        else:
            fresh_n, redundant_n, pruned_n = self._derive_batch(
                traces, lens, len(batch), frontier, batch=batch, res=res
            )
        obs.stage_count("dpor.fresh", fresh_n)
        # Kept beside it so that a job that built no tuple reads 0.
        obs.stage_count("dpor.materialized", 0)
        with obs.span("dpor.account"):
            self._note_round(
                batch, frontier, frontier_extra, round_codes,
                fresh_n, redundant_n, pruned_n,
            )
        return hit

    def _note_violations(
        self, violations, traces, lens, batch: PrescList,
        target_code: Optional[int],
    ):
        """The round's violation bookkeeping: the code ledger, the
        per-code witness (sleep mode) and the hit selection. Returns
        ``(round_codes, hit)``."""
        # Violation-set ledger (always on — one np.unique per round):
        # every distinct nonzero code any lane of any round produced,
        # the preservation surface the sleep-set A/B asserts against.
        round_codes = [int(c) for c in np.unique(violations) if c != 0]
        self.violation_codes.update(round_codes)
        if self.sleep is not None and round_codes:
            # Canonical per-code first-found witness: keep the violating
            # lane whose trace digest is smallest. Min-digest (not
            # chronology) is order-free, so a differential re-run that
            # executes the same prescriptions in different rounds
            # converges on the SAME witness as scratch (analysis/delta).
            import hashlib as _hl

            for code in round_codes:
                for b in np.flatnonzero(violations == code):
                    b = int(b)
                    tr = traces[b][: int(lens[b])]
                    sha = _hl.sha256(tr.tobytes()).hexdigest()[:16]
                    cur = self.violation_witnesses.get(code)
                    if cur is not None and str(cur["sha"]) <= sha:
                        continue
                    self.violation_witnesses[code] = {
                        "sha": sha,
                        "class": self._class_of.get(
                            batch[b] if b < len(batch) else ()
                        ),
                        "trace": np.array(tr, copy=True),
                    }
        hit_mask = (
            violations != 0
            if target_code is None
            else (violations != 0) & (violations == target_code)
        )
        hit_lanes = np.flatnonzero(hit_mask)
        hit = (
            (traces[hit_lanes[0]], int(lens[hit_lanes[0]]))
            if len(hit_lanes)
            else None
        )
        return round_codes, hit

    def _note_round(
        self, batch: PrescList, frontier: PrescList,
        frontier_extra: int, round_codes: List[int],
        fresh_n: int, redundant_n: int, pruned_n: int,
    ) -> None:
        """The round's closing bookkeeping: journal stash, obs series,
        tuner feedback, side-table pruning."""
        # Round-local stats for the journal record (obs/journal.py):
        # stashed always — a handful of ints next to a kernel launch.
        self._last_round = {
            "batch": len(batch),
            "depth": int(batch.lengths().max()) if len(batch) else 0,
            "fresh": int(fresh_n),
            "redundant": int(redundant_n),
            "distance_pruned": int(pruned_n),
            "violations": round_codes,
        }
        if self._sharder is not None:
            # Per-shard scan/dedup stats for the fleet.host_shard
            # journal records + the top FLEET panel's utilization bars.
            self._last_round["host_shards"] = self._sharder.last_stats
        if redundant_n:
            obs.counter("dpor.prescriptions_redundant").inc(redundant_n)
        if pruned_n:
            obs.counter("dpor.prescriptions_distance_pruned").inc(pruned_n)
        obs.gauge("dpor.explored_set_size").set(len(self.explored))
        if self.sleep is not None:
            ratio = self.sleep.redundancy_ratio(len(self.explored))
            if ratio is not None:
                obs.gauge("dpor.redundancy_ratio").set(round(ratio, 4))
        if self.tuner is not None:
            self.tuner.observe_round(
                fresh=fresh_n, redundant=redundant_n, pruned=pruned_n,
                frontier=len(frontier) + frontier_extra,
            )
            self.round_batch = self.tuner.round_batch
            if self.tuner.max_distance is not None:
                self.max_distance = self.tuner.max_distance
        if self.sleep is not None:
            # A harvested prescription never re-enters the worklist
            # (explored-set membership), so its guide and sleep rows are
            # dead — drop them, bounding the side tables to the live
            # frontier instead of the whole explored history. (An
            # unharvested in-flight round that gets requeued was never
            # processed here, so its entries survive for re-dispatch.)
            for p in batch.tuples():
                self._guides.pop(p, None)
                self._sleep_rows.pop(p, None)
                # Executed ⇒ no longer pending; witness capture
                # (``_note_violations``) already consumed the class
                # attribution for this round.
                self._class_of.pop(p, None)

    def _pad_lanes(self, batch: Optional[PrescList]) -> Optional[np.ndarray]:
        """Which lanes of ``batch`` are prescription-free padding, where
        their races are not to be admitted (``pad_exploration`` off);
        None where every lane's are."""
        if self.pad_exploration or batch is None:
            return None
        return batch.lengths() == 0

    def _admit(
        self, presc: Tuple, key: bytes, frontier: PrescList
    ) -> bool:
        """Distance-gate one non-redundant prescription and mark it
        explored under its digest ``key``. Returns True when it is to join
        the frontier; the caller then records the round's admitted
        prescriptions in the log and in ``frontier`` together. The bulk
        path never calls this, so it runs only while this method is the
        driver's own (``_admit_stream``): an override — a subclass, a
        fault injected by a control — is honoured one candidate at a
        time."""
        if (
            self.max_distance is not None
            and self.original is not None
            and arvind_distance(presc, self.original) > self.max_distance
        ):
            return False
        self._explored_digests.add(key)
        return True

    def _sleep_class_check(
        self, presc: Tuple, rows, own_pos, flip, branch: int,
        lane_presc: Tuple, wake_row, ckey=None,
    ):
        """The class-dedup half of sleep-set admission for ONE fresh
        candidate.
        Returns ``(verdict, commit)``: verdict 'class' means the
        candidate's Mazurkiewicz class was already scheduled (suppress);
        verdict None means admit-eligible, and ``commit()`` — called
        after ``_admit`` accepts — registers the class, assigns the
        child's sleep rows (earlier siblings at the node + the source
        lane's still-asleep rows, filtered by independence with the
        flip), and appends the flip to the node's wakeup ledger."""
        sleep = self.sleep
        recw = self.cfg.rec_width
        if ckey is None:
            ckey = sleep.class_key(rows, own_pos, recw)
        if sleep.prune and sleep.class_seen(ckey):
            sleep.note_pruned(klass=1, tier="device")
            # Warm-start accounting: a hit satisfied by PRIOR-run /
            # other-host coverage (fleet class store) counts separately.
            sleep.note_warm(ckey)
            if sleep.audit:
                sleep.note_pruned_prescription(presc)
            return "class", None

        def commit(guide=None):
            # Reversal-chain tag mask: this child is its parent's class
            # plus ONE race reversal — the flip moved before the row it
            # displaced (``guide[branch + 1]``, when the lane's tail
            # survived divergence tolerance). Its footprint is the
            # parent's chain mask (trunk marker dropped) plus both rows
            # of the reversed pair — recorded here, at admission, when
            # the pair is exact knowledge. Unknown parent lineage
            # (root-descended pads, no recorded mask) stays -1 —
            # differential exploration then falls back to the
            # conservative full-key mask.
            from ..analysis.sleep import TRUNK_BIT, guide_row_tag, tag_bit

            pmeta = sleep.class_meta.get(self._class_of.get(lane_presc))
            pmask = (
                int(pmeta[3])
                if pmeta is not None and len(pmeta) > 3 else -1
            )
            if guide is None or pmask < 0:
                dmask = -1
            else:
                dmask = (pmask & ~TRUNK_BIT) | tag_bit(
                    guide_row_tag(flip)
                )
                if branch + 1 < len(guide):
                    dmask |= tag_bit(guide_row_tag(guide[branch + 1]))
            sleep.note_class(
                ckey, guide=guide, plen=len(presc), dmask=dmask
            )
            self._class_of[presc] = ckey
            node_key = np.ascontiguousarray(
                np.asarray(presc[:-1], np.int32).reshape(len(presc) - 1, -1)
            ).tobytes() if len(presc) > 1 else b""
            inherited: List[Tuple[int, ...]] = []
            if wake_row is not None:
                lane_sleep = self._sleep_rows.get(lane_presc, ())
                presc_deliv = int(wake_row[1])
                if branch >= presc_deliv:
                    for s, srow in enumerate(lane_sleep):
                        if s < len(wake_row[0]) and int(wake_row[0][s]) >= branch:
                            inherited.append(srow)
            child = sleep.child_sleep_rows(node_key, flip, recw, inherited)
            if child:
                self._sleep_rows[presc] = child
            sleep.note_admitted_flip(node_key, flip)

        return None, commit

    def _make_guide(
        self, deliv: List[Tuple[int, ...]], branch: int,
        flip: Tuple[int, ...], flip_ord: Optional[int],
    ) -> np.ndarray:
        """Bounded wakeup sequence for one admitted reversal (sleep
        mode): the source lane's deliveries before the branch, the
        flipped record, then the lane's remaining deliveries in order
        with the flipped one removed — so the reversal's subtree
        revisits the source schedule modulo exactly the reversed race
        (divergence tolerance skips rows the flip invalidated), instead
        of diverging into fresh randomness at the node.

        ``flip_ord=None`` locates the flip by FULL-row equality past
        the branch — exact, not approximate: same-receiver deliveries
        always differ in the ``prev`` column (the per-receiver
        program-order chain is strictly increasing), so a full-row
        match identifies the flipped delivery uniquely."""
        if flip_ord is None:
            flip_ord = next(
                (
                    t
                    for t in range(branch + 1, len(deliv))
                    if deliv[t] == flip
                ),
                None,
            )
        rows = list(deliv[:branch]) + [flip]
        if flip_ord is not None:
            rows += list(deliv[branch:flip_ord]) + list(deliv[flip_ord + 1:])
        return np.asarray(rows[: self.cfg.max_steps], np.int32)

    def _sleep_ctx(self, batch: PrescList, res) -> Optional[tuple]:
        """The racing scan's per-lane sleep inputs for one harvested
        round: the packed sleep rows the kernel consumed (a pure
        function of the batch — identical to what was dispatched) plus
        the device-tracked wake/slept/prescribed-count observations."""
        if self.sleep is None or not hasattr(res, "sleep_wake"):
            return None
        n = len(batch)
        return (
            self._pack_sleep(batch),
            np.asarray(res.sleep_wake)[:n],
            np.asarray(res.sleep_slept)[:n],
            self._sleep_from(batch),
        )

    def _derive_batch(
        self, traces, lens, n_lanes: int, frontier: PrescList,
        batch: Optional[PrescList] = None, res=None,
    ) -> Tuple[int, int, int]:
        """Vectorized prescription derivation: one batch-native racing
        call for the whole round, content-digest dedup over the packed
        rows, tuples materialized only for admitted candidates (the
        shared ``_admit_stream`` loop). Returns (fresh, redundant,
        pruned) counts."""
        from ..native import digest_keys, racing_prescriptions_batch
        from ..obs.profiler import PROFILER

        recw = self.cfg.rec_width
        sleep_ctx = (
            self._sleep_ctx(batch, res)
            if batch is not None and res is not None
            else None
        )
        with obs.span("dpor.scan", lanes=n_lanes) as sp:
            rows, offsets, lanes, digests = racing_prescriptions_batch(
                traces[:n_lanes], lens[:n_lanes], recw,
                size_hint=self._batch_size_hint,
                independence=self.static_independence,
                sleep=self.sleep, sleep_ctx=sleep_ctx,
                buffers=self._scan_buffers,
            )
            keys = digest_keys(digests)
        if PROFILER.enabled:
            PROFILER.host_scan("dpor-host-scan", n_lanes, sp.seconds)
        # Adaptive buffer sizing: the next round's scan allocates for
        # this round's volume (+ slack) instead of a blind worst case.
        self._batch_size_hint = (
            max(64, (len(digests) * 5) // 4),
            max(256, (len(rows) * 5) // 4),
        )
        obs.stage_count("dpor.candidates", len(keys))
        with obs.span("dpor.admit", candidates=len(keys)):
            return self._admit_stream(
                rows, offsets, lanes, keys, traces, lens, batch, sleep_ctx,
                frontier,
            )

    def _derive_sharded(
        self, traces, lens, n_lanes: int, frontier: PrescList,
        batch: Optional[PrescList] = None, res=None,
    ) -> Tuple[int, int, int]:
        """Digest-range-sharded derivation (host_shards > 1): the lane
        scan + static/sleep filters + pre-round digest dedup run as N
        concurrent shards (fleet/shard.py — phases A/B compute only
        order-independent facts), then the canonical merge
        (``_admit_stream`` with the precomputed duplicate verdicts)
        applies admissions serially in the exact sequential order.
        Outputs are bit-identical to ``_derive_batch`` at any shard
        count (tests/test_host_shards.py, bench config 16)."""
        from ..obs.profiler import PROFILER

        recw = self.cfg.rec_width
        sleep_ctx = (
            self._sleep_ctx(batch, res)
            if batch is not None and res is not None
            else None
        )
        if self.static_independence is not None:
            # Build the lazily-cached device matrix once, on this
            # thread, before the shard threads read it concurrently.
            self.static_independence.device_matrix()
        with obs.span(
            "dpor.scan", lanes=n_lanes, shards=self._host_shards
        ) as sp:
            scan = self._sharder.scan_round(
                traces, lens, n_lanes, recw,
                independence=self.static_independence,
                sleep=self.sleep, sleep_ctx=sleep_ctx,
                explored=self._explored_digests,
                suppressed=self._suppressed_digests,
            )
        if PROFILER.enabled:
            PROFILER.host_scan(
                "dpor-host-scan", n_lanes, sp.seconds,
                shape=f"b={n_lanes} shards={self._host_shards}",
            )
        # Same global adaptive hint as the sequential path (checkpoint
        # payloads stay identical across shard counts); the per-shard
        # ScanBuffers carry their own capacities independently.
        self._batch_size_hint = (
            max(64, (len(scan.keys) * 5) // 4),
            max(256, (len(scan.rows) * 5) // 4),
        )
        obs.stage_count("dpor.candidates", len(scan.keys))
        with obs.span("dpor.admit", candidates=len(scan.keys)):
            # Phase C: class-key canonicalization (the host half's
            # dominant cost on class-tracked runs) precomputed per owning
            # shard — the merge below only looks keys up.
            class_keys = self._sharder.class_round(
                scan, traces, lens, recw, self.sleep
            )
            return self._admit_stream(
                scan.rows, scan.offsets, scan.lanes, scan.keys, traces,
                lens, batch, sleep_ctx, frontier,
                known_dup=scan.known_dup, shard_ids=scan.shard_ids,
                shard_stats=scan.stats, class_keys=class_keys,
            )

    def _admit_stream(
        self, rows, offsets, lanes, keys, traces, lens,
        batch: Optional[PrescList], sleep_ctx, frontier: PrescList,
        known_dup=None, shard_ids=None, shard_stats=None, class_keys=None,
    ) -> Tuple[int, int, int]:
        """The canonical admission of one round's candidate stream, in
        stream (= lane-major scan) order: digest dedup, sleep-class
        check, distance gate, frontier admission. Shared by the
        sequential and sharded paths — the sharded path passes
        ``known_dup`` (membership against the PRE-round sets, computed
        per digest-range shard), and only the keys added DURING the
        merge are tracked here, which together decide exactly what the
        sequential live-set membership check decides, in the same order.

        Which candidates are fresh is decided in bulk when nothing needs
        a tuple — no sleep sets (their side tables are keyed by the
        tuple), no distance gate (``arvind_distance`` wants the tuple)
        and no override of ``_admit`` (it takes the tuple) — and one
        candidate at a time otherwise. Either way the
        fresh ones go into the explored log as columns, all at once."""
        if (
            self.sleep is None and self.max_distance is None
            and getattr(self._admit, "__func__", None) is _ADMIT
        ):
            fresh, redundant_n, pruned_n = self._fresh_bulk(
                keys, lanes, batch, known_dup
            )
            tuples = None
        else:
            fresh, tuples, redundant_n, pruned_n = self._fresh_each(
                rows, offsets, lanes, keys, traces, lens, batch, sleep_ctx,
                frontier, known_dup, class_keys,
            )
        if shard_stats is not None and len(fresh):
            per_shard = np.bincount(
                np.asarray(shard_ids, np.int64)[fresh],
                minlength=len(shard_stats),
            )
            for stats, count in zip(shard_stats, per_shard.tolist()):
                stats["fresh"] += count
        if len(fresh):
            self._record_fresh(
                fresh, tuples, rows, offsets, lanes, keys, traces, lens,
                frontier,
            )
        return len(fresh), redundant_n, pruned_n

    def _fresh_bulk(
        self, keys, lanes, batch: Optional[PrescList], known_dup
    ) -> Tuple[np.ndarray, int, int]:
        """The fresh candidates of a round, with no work per candidate:
        ``(positions in stream order, redundant, pruned)``, their keys
        added to the explored digests. A key's first occurrence wins,
        which is the sequential loop's same-round rule; with
        ``pad_exploration`` off, padding lanes' candidates are masked
        out before that (observed, never admitted)."""
        explored = self._explored_digests
        suppressed = self._suppressed_digests
        n = len(keys)
        if known_dup is None:
            pos = range(n)
        else:
            pos = np.flatnonzero(~known_dup).tolist()
            keys = [keys[k] for k in pos]
        pads: List[Tuple[int, bytes]] = []
        pad_lane = self._pad_lanes(batch)
        if pad_lane is not None and n:
            is_pad = pad_lane[np.asarray(lanes)[pos]].tolist()
            pads = [(k, key) for k, key, p in zip(pos, keys, is_pad) if p]
            live = [(k, key) for k, key, p in zip(pos, keys, is_pad) if not p]
            pos, keys = [k for k, _ in live], [key for _, key in live]
        # key -> its FIRST position: later writes win, so walk backwards.
        first = dict(zip(reversed(keys), reversed(pos)))
        if known_dup is None:
            new_keys = [
                key for key in first
                if key not in explored and key not in suppressed
            ]
        else:
            new_keys = list(first)
        pruned_n = 0
        for k, key in pads:
            # A padding lane's candidate is redundant when its key was
            # explored before it in the stream, pruned otherwise.
            if known_dup is None and (key in explored or key in suppressed):
                continue
            admitted_at = first.get(key)
            if admitted_at is None or admitted_at > k:
                pruned_n += 1
        explored.update(new_keys)
        fresh = np.sort(
            np.fromiter((first[key] for key in new_keys), np.int64,
                        len(new_keys))
        )
        return fresh, n - len(fresh) - pruned_n, pruned_n

    def _fresh_each(
        self, rows, offsets, lanes, keys, traces, lens,
        batch: Optional[PrescList], sleep_ctx, frontier: PrescList,
        known_dup, class_keys,
    ) -> Tuple[np.ndarray, List[Tuple], int, int]:
        """The fresh candidates of a round, one candidate at a time, for
        the modes that need each as a tuple (sleep sets, the distance
        gate): ``(positions, their tuples, redundant, pruned)``."""
        recw = self.cfg.rec_width
        redundant_n = pruned_n = 0
        fresh: List[int] = []
        tuples: List[Tuple] = []
        explored_digests = self._explored_digests
        offs = offsets.tolist()
        lane_of = np.asarray(lanes).tolist()
        pad_lane = self._pad_lanes(batch)
        lane_tuples = batch.tuples() if self.sleep is not None else None
        # Fresh prescriptions materialize with SHARED per-lane row
        # tuples: a prescription's prefix is by construction the first
        # (mlen - 1) delivery rows of its lane in position order, so one
        # tuple list per lane serves every fresh sibling — O(refs) per
        # prescription instead of a fresh tuple per packed row.
        lane_deliv: Dict[int, Tuple[List[Tuple[int, ...]], np.ndarray]] = {}

        def deliveries_of(b: int) -> Tuple[List[Tuple[int, ...]], np.ndarray]:
            cached = lane_deliv.get(b)
            if cached is None:
                recs = traces[b, : int(lens[b]), :recw]
                pos = np.nonzero(
                    np.isin(recs[:, 0], (REC_DELIVERY, REC_TIMER))
                )[0]
                cached = ([tuple(r) for r in recs[pos].tolist()], pos)
                lane_deliv[b] = cached
            return cached

        if known_dup is None:
            candidates = range(len(keys))
            round_new = None
        else:
            # Known duplicates (vs the pre-round sets) skip in bulk —
            # the merge's per-candidate work is O(fresh), which is what
            # keeps the serial fraction small at high shard counts.
            redundant_n += int(np.count_nonzero(known_dup))
            candidates = np.flatnonzero(~known_dup).tolist()
            round_new = set()
        for k in candidates:
            key = keys[k]
            if round_new is None:
                if key in explored_digests or key in self._suppressed_digests:
                    redundant_n += 1
                    continue
            elif key in round_new:
                # Same-round duplicate: an earlier merge step already
                # explored or class-suppressed this digest — exactly
                # the sequential live-set hit.
                redundant_n += 1
                continue
            lo, hi = offs[k], offs[k + 1]
            b = lane_of[k]
            if pad_lane is not None and pad_lane[b]:
                # Closed seeded exploration: padding-lane races are
                # observed but never admitted (see pad_exploration).
                pruned_n += 1
                continue
            flipped = tuple(rows[hi - 1].tolist())
            deliv, pos = deliveries_of(b)
            m = hi - lo
            presc = tuple(deliv[: m - 1]) + (flipped,)
            commit = None
            if self.sleep is not None:
                wake_row = (
                    (sleep_ctx[1][b], sleep_ctx[3][b])
                    if sleep_ctx is not None
                    else None
                )
                verdict, commit = self._sleep_class_check(
                    presc, rows[lo:hi],
                    list(pos[: m - 1]) + [None], flipped, m - 1,
                    lane_tuples[b] if lane_tuples is not None else tuple(),
                    wake_row,
                    ckey=(
                        class_keys.get(k)
                        if class_keys is not None
                        else None
                    ),
                )
                if verdict == "class":
                    self._suppressed_digests.add(key)
                    if round_new is not None:
                        round_new.add(key)
                    redundant_n += 1
                    continue
            if not self._admit(presc, key, frontier):
                pruned_n += 1
                continue
            if round_new is not None:
                round_new.add(key)
            fresh.append(k)
            tuples.append(presc)
            if self.sleep is not None:
                guide = self._make_guide(deliv, m - 1, flipped, None)
                self._guides[presc] = guide
                if commit is not None:
                    commit(guide)
        return np.asarray(fresh, np.int64), tuples, redundant_n, pruned_n

    def _record_fresh(
        self, fresh: np.ndarray, tuples: Optional[List[Tuple]], rows,
        offsets, lanes, keys, traces, lens, frontier: PrescList,
    ) -> None:
        """Append a round's fresh prescriptions (stream positions
        ``fresh``) to the explored log and the frontier sink. A
        prescription is its lane's first ``m - 1`` delivery rows plus
        the flipped row, so the round adds ONE chunk — the delivery rows
        of the lanes that had a fresh candidate — and a row of columns a
        prescription; ``tuples``, where the per-candidate path built
        them, are kept for its side tables."""
        log = self._explored_log
        recw = self.cfg.rec_width
        lanes_f = np.asarray(lanes)[fresh]
        used = np.unique(lanes_f)
        recs = traces[used, :, :recw]
        kinds = recs[:, :, 0]
        delivered = ((kinds == REC_DELIVERY) | (kinds == REC_TIMER)) & (
            np.arange(recs.shape[1])[None, :]
            < np.asarray(lens)[used][:, None]
        )
        counts = delivered.sum(axis=1)
        lane_start = np.zeros(int(used[-1]) + 1, np.int64)
        lane_start[used] = np.cumsum(counts) - counts
        hi = offsets[fresh + 1]
        first = log.extend(
            log.add_chunk(recs[delivered]),
            lane_start[lanes_f], hi - offsets[fresh], rows[hi - 1],
            np.frombuffer(
                b"".join([keys[k] for k in fresh.tolist()]), np.uint64
            ).reshape(-1, 2),
        )
        if tuples is not None:
            log.keep_tuples(first, tuples)
        frontier.extend(range(first, first + len(fresh)))

    def _note_inflight(self, outcome: str) -> None:
        self.async_stats[f"inflight_{outcome}"] += 1
        obs.counter(f"dpor.inflight_{outcome}").inc()

    @property
    def host_share(self) -> Optional[float]:
        """Fraction of frontier wall time spent host-side (planning,
        packing, racing analysis, dedup) vs blocked on device results —
        the number the vectorized host path exists to shrink. None until
        a round has run."""
        total = self.host_seconds + self.device_seconds
        return self.host_seconds / total if total > 0 else None

    @property
    def static_stats(self) -> Optional[Dict[str, int]]:
        """Static-pruning ledger by kind (None when the relation is
        off) — reported by bench configs 2/8 next to the redundant /
        distance-pruned counts."""
        if self.static_independence is None:
            return None
        return dict(self.static_independence.pruned_total)

    @property
    def sleep_stats(self) -> Optional[Dict[str, object]]:
        """Sleep-set ledger (None when sleep sets are off): prune counts
        by kind, distinct Mazurkiewicz classes among admitted
        prescriptions, and the redundancy ratio (explored over the
        class lower bound — the `bench --config 9` headline)."""
        if self.sleep is None:
            return None
        ratio = self.sleep.redundancy_ratio(len(self.explored))
        return {
            "pruned": dict(self.sleep.pruned_total),
            "classes": len(self.sleep.classes),
            "explored": len(self.explored),
            "redundancy_ratio": round(ratio, 4) if ratio else None,
        }

    def _account_device(self, secs: float) -> None:
        """Fold a device-blocked span into the ledger + obs series. The
        windowed oracle path (``explore_window``) uses this directly, so
        DPOR-oracle windows land in the report's host-share block just
        like plain ``explore`` rounds."""
        self.device_seconds += secs
        if obs.enabled():
            obs.counter("dpor.device_seconds").inc(secs)
            share = self.host_share
            if share is not None:
                obs.gauge("dpor.host_share").set(share)

    def _account_host(self, secs: float) -> None:
        """Host-side twin of ``_account_device``."""
        self.host_seconds += secs
        if obs.enabled():
            obs.counter("dpor.host_seconds").inc(secs)
            share = self.host_share
            if share is not None:
                obs.gauge("dpor.host_share").set(share)

    def _account_round(
        self, round_t0: float, device_secs: float
    ) -> Tuple[float, float]:
        """Fold one frontier round's wall time into the host/device
        split: ``device_secs`` is the harvest-blocked span, the rest of
        the iteration is host work (selection, packing, dispatch prep,
        racing analysis, dedup). Always tracked (two clock reads); the
        ``dpor.host_*`` obs series mirror it when telemetry is on.
        Returns the (host, device) seconds so the journal record can
        carry the per-round split."""
        host_secs = max(0.0, time.perf_counter() - round_t0 - device_secs)
        self._account_device(device_secs)
        self._account_host(host_secs)
        return host_secs, device_secs

    def _journal_round(
        self, host_secs: float, device_secs: float, frontier: int
    ) -> None:
        """One generation-stamped journal record per frontier round —
        the continuous-observability wire format (obs/journal.py):
        per-round wall/host/device seconds, frontier size and depth,
        fresh/redundant/pruned admission counts, in-flight economy, fork
        economy, and the round's violation codes. Called after every
        ``_account_round``; a detached journal costs one branch."""
        self.round_index += 1
        if obs.journal.JOURNAL is None:
            return
        lr = self._last_round
        rec: Dict[str, object] = {
            "round": self.round_index,
            "wall_s": round(host_secs + device_secs, 6),
            "host_s": round(host_secs, 6),
            "device_s": round(device_secs, 6),
            "batch": lr.get("batch", 0),
            "depth": lr.get("depth", 0),
            "fresh": lr.get("fresh", 0),
            "redundant": lr.get("redundant", 0),
            "distance_pruned": lr.get("distance_pruned", 0),
            "violations": lr.get("violations", []),
            "frontier": frontier,
            "interleavings": self.interleavings,
            "explored": len(self.explored),
            "inflight_hits": self.async_stats["inflight_hits"],
            "inflight_waste": self.async_stats["inflight_waste"],
        }
        if self.static_independence is not None:
            rec["static_pruned"] = int(
                sum(self.static_independence.pruned_total.values())
            )
        if self.sleep is not None:
            rec["sleep_pruned"] = int(
                sum(self.sleep.pruned_total.values())
            )
            ratio = self.sleep.redundancy_ratio(len(self.explored))
            if ratio is not None:
                rec["redundancy_ratio"] = round(ratio, 4)
        if self._forker is not None:
            st = self._forker.stats_view()
            rec["fork"] = {
                "prefix_hits": st.get("prefix_hits", 0),
                "steps_saved": st.get("steps_saved", 0),
                "forked_lanes": st.get("forked_lanes", 0),
            }
        obs.journal.emit("dpor.round", **rec)

    def explore(
        self, target_code: Optional[int] = None, max_rounds: int = 20,
        stop_on_violation: bool = True,
    ) -> Optional[Tuple[np.ndarray, int]]:
        """Returns (records, trace_len) of a violating lane, or None.
        Continues from the persisted frontier; call again for more rounds.

        ``stop_on_violation=False`` is COVERAGE mode (the fleet parity
        baseline): a hit is recorded (the FIRST one is returned) but the
        loop keeps draining rounds until the frontier empties or the
        round budget expires, so the explored/class/violation-code sets
        measure the schedule space, not the race to the first bug.

        Rounds are GENERATION-FROZEN: each round's batch is selected from
        the generation frozen at the previous generation boundary, and
        the fresh prescriptions a harvest derives join the NEXT
        generation (picked up when the current one drains). This is
        breadth-style worklist processing — deepest-first within a
        generation — and it is what makes the next round plannable before
        the current round's codes ever leave the device: the harvest
        cannot reorder the generation it was selected from. One
        deterministic exception (fork-group growth): a generation too
        small to fill a round pulls the next generation forward
        (``_merge_generations``), so equal-depth prescriptions from both
        generations batch together instead of padding the round with
        prescription-free lanes.

        With ``double_buffer`` on, round N+1's batch is selected from the
        frozen-generation remainder and dispatched as a FULL in-flight
        launch while round N's codes are still on device. The plan is
        re-checked after the harvest by re-running the (pure,
        deterministic) selection: an exact batch match means the
        in-flight launch IS the next round (per-lane keys depend only on
        the cumulative interleaving count, which padding makes
        deterministic); a mismatch — the tuner moved ``round_batch``
        mid-round — discards the launch unharvested. Either way every
        harvested round is byte-identical to the synchronous loop's,
        which follows the exact same generation policy."""
        job = obs.new_job()
        with obs.spans.first_job(job, "dpor"), obs.span(
            "dpor.search", job=job, max_rounds=max_rounds
        ):
            return self._search(target_code, max_rounds, stop_on_violation)

    def _search(
        self, target_code: Optional[int], max_rounds: int,
        stop_on_violation: bool,
    ) -> Optional[Tuple[np.ndarray, int]]:
        """``explore``'s round loop, under its ``dpor.search`` span."""
        gen = self.frontier
        # The NEXT generation, fed by harvests.
        pending = PrescList(self._explored_log)
        # (batch, parts, n_real, prescs, keys) for the next round — the
        # pure round inputs ride along for poisoned-launch re-dispatch.
        inflight = None
        found = None
        for _ in range(max_rounds):
            if inflight is None and not gen and not pending:
                break
            with obs.span("dpor.round") as round_span:
                round_t0 = time.perf_counter()
                if inflight is not None:
                    batch, parts, _, r_prescs, r_keys = inflight
                    inflight = None
                    # A hit is an in-flight launch actually harvested as
                    # the next round — adoption alone isn't enough (the
                    # budget can expire first, which counts as waste, so
                    # every dispatched launch lands in exactly one
                    # bucket).
                    self._note_inflight("hits")
                else:
                    with obs.span("dpor.select"):
                        # Fork-group growth: a generation that can't
                        # fill a round pulls the next generation forward
                        # (see ``_merge_generations``).
                        gen, pending = self._merge_generations(gen, pending)
                        batch, gen = self._select_batch(gen)
                    r_prescs, r_keys = self._pack_round(
                        batch, self.interleavings
                    )
                    parts = self._dispatch_round(r_prescs, r_keys, batch)
                round_span.set(batch=len(batch), frontier=len(gen))
                spec = None
                if self._double_buffer and gen:
                    with obs.span("dpor.select"):
                        sbatch, srest = self._select_batch(gen)
                    s_prescs, s_keys = self._pack_round(
                        sbatch, self.interleavings + len(batch)
                    )
                    sparts = self._dispatch_round(s_prescs, s_keys, sbatch)
                    # len(gen) - len(srest) real entries precede the
                    # padding in sbatch — the count the budget-expiry
                    # requeue needs (a genuine root ``tuple()`` entry is
                    # falsy, so truthiness can't separate it from
                    # padding). The pure (prescs, keys) inputs ride along
                    # so a poisoned launch can re-execute this round at
                    # harvest time.
                    spec = (sbatch, sparts, len(gen) - len(srest),
                            s_prescs, s_keys)
                    self._note_inflight("rounds")
                t_harvest = time.perf_counter()
                res = self._supervised_harvest(
                    parts, batch, r_prescs, r_keys
                )
                dev_secs = time.perf_counter() - t_harvest
                hit = self._process_round(
                    res, batch, target_code, pending,
                    frontier_extra=len(gen),
                )
                obs.gauge("dpor.frontier_size").set(len(gen) + len(pending))
                stop = False
                if hit is not None:
                    obs.counter("dpor.violations_found").inc()
                    if found is None:
                        found = hit
                    stop = stop_on_violation
                if spec is not None and stop:
                    self._note_inflight("waste")
                elif spec is not None:
                    sbatch, sparts, sreal, s_prescs, s_keys = spec
                    # The speculative batch was selected from the
                    # UNMERGED remainder; validate against the merged
                    # pool the synchronous loop would select from at its
                    # next round top. A merge that changes the selection
                    # discards the in-flight launch — waste, never
                    # divergence.
                    with obs.span("dpor.select"):
                        mgen, mpending = self._merge_generations(
                            gen, pending
                        )
                        abatch, arest = self._select_batch(mgen)
                    if abatch == sbatch:
                        inflight = (sbatch, sparts, sreal, s_prescs, s_keys)
                        gen, pending = arest, mpending
                    else:
                        self._note_inflight("waste")
                with obs.span("dpor.account"):
                    h, d = self._account_round(round_t0, dev_secs)
                    self._journal_round(h, d, len(gen) + len(pending))
            # The round boundary of --profile-rounds: outside the round's
            # span, so a trace window that closes here holds it whole.
            obs.profiler.PROFILER.tick_round()
            if stop:
                break
        if inflight is not None:
            # The round budget expired with a speculative round still on
            # device: it was never harvested, so its prescriptions go
            # back to the worklist head and the next explore() call
            # re-selects (and re-dispatches) them.
            batch, _parts, n_real, _prescs, _keys = inflight
            gen = batch[:n_real] + gen
            self._note_inflight("waste")
        self.frontier = gen + pending
        return found


# The driver's own per-candidate admission: while it is in place (no
# subclass or patch overrides it) a round may be admitted in bulk.
_ADMIT = DeviceDPOR._admit


def explore_window(
    dpors: Sequence["DeviceDPOR"],
    target_code: Optional[int],
    max_rounds: int,
) -> List[Optional[Tuple[np.ndarray, int]]]:
    """Run several DeviceDPOR searches in lockstep, batching concurrent
    frontier rounds' device work — the engine under
    ``DeviceDPOROracle.test_window`` (IncrementalDDMin's speculative
    left/right DDMin probe pairs). Per round, every live instance's batch
    becomes ONE combined kernel launch when the instances share a kernel
    and run scratch (the common DeviceDPOROracle case: one jitted kernel
    serves every resumable instance); under prefix forking each
    instance's fork groups dispatch before any is harvested, so device
    work still overlaps across the window. Each instance's host-side
    round processing is untouched — explored sets, frontiers,
    interleavings, and per-lane keys are all per-instance, so results
    are bit-identical to running the searches sequentially."""
    n = len(dpors)
    found: List[Optional[Tuple[np.ndarray, int]]] = [None] * n
    done = [False] * n
    # Per-instance generation split, mirroring explore(): rounds select
    # from the frozen generation, fresh prescriptions join the pending
    # next generation — same policy, so committed states match the
    # sequential path exactly.
    frontiers = [d.frontier.copy() for d in dpors]
    pendings = [PrescList(d._explored_log) for d in dpors]
    for _ in range(max_rounds):
        live = []
        for i in range(n):
            if done[i]:
                continue
            frontiers[i], pendings[i] = dpors[i]._merge_generations(
                frontiers[i], pendings[i]
            )
            if frontiers[i]:
                live.append(i)
        if not live:
            break
        staged = []
        for i in live:
            batch, frontiers[i] = dpors[i]._select_batch(frontiers[i])
            staged.append(
                (i, batch, dpors[i]._pack(batch),
                 dpors[i]._round_keys(
                     len(batch), dpors[i].interleavings, batch=batch
                 ))
            )
        combined = (
            len(staged) > 1
            and all(dpors[i]._forker is None for i, *_ in staged)
            and all(dpors[i].sleep is None for i, *_ in staged)
            and len({id(dpors[i].kernel) for i, *_ in staged}) == 1
        )
        results: List[Tuple[int, PrescList, LaneResult]] = []
        if combined:
            # One launch for the whole window: lanes are elementwise
            # under vmap, so concatenating the instances' (prog, presc,
            # key) rows yields exactly each instance's own round results.
            from ..persist.supervisor import SUPERVISOR

            progs = [dpors[i]._progs(len(b)) for i, b, *_ in staged]
            t_harvest = time.perf_counter()

            def _combined_launch(_attempt: int):
                r = dpors[staged[0][0]].kernel(
                    ExtProgram(*(
                        np.concatenate(
                            [np.asarray(getattr(p, f)) for p in progs]
                        )
                        for f in ExtProgram._fields
                    )),
                    np.concatenate([prescs for _, _, prescs, _ in staged]),
                    np.concatenate([np.asarray(keys) for *_, keys in staged]),
                )
                jax.block_until_ready(r.violation)
                return r

            res = SUPERVISOR.run(_combined_launch, label="dpor.launch")
            # Window launches serve several instances at once: split the
            # blocked span evenly for the per-instance host-share ledger
            # (through the accounting helper, so windowed oracle rounds
            # reach the dpor.host_share gauge + seconds counters too).
            dev_each = (time.perf_counter() - t_harvest) / len(staged)
            for i, *_ in staged:
                dpors[i]._account_device(dev_each)
            off = 0
            for i, batch, _prescs, _keys in staged:
                results.append((i, batch, LaneResult(*(
                    np.asarray(getattr(res, f))[off: off + len(batch)]
                    for f in LaneResult._fields
                ))))
                off += len(batch)
        else:
            handles = [
                (i, batch, dpors[i]._dispatch_round(prescs, keys, batch),
                 prescs, keys)
                for i, batch, prescs, keys in staged
            ]
            results = []
            for i, batch, parts, prescs, keys in handles:
                t_harvest = time.perf_counter()
                harvested = dpors[i]._supervised_harvest(
                    parts, batch, prescs, keys
                )
                dpors[i]._account_device(time.perf_counter() - t_harvest)
                results.append((i, batch, harvested))
        for i, batch, res in results:
            t_host = time.perf_counter()
            with obs.span(
                "dpor.round", batch=len(batch), frontier=len(frontiers[i])
            ):
                hit = dpors[i]._process_round(
                    res, batch, target_code, pendings[i],
                    frontier_extra=len(frontiers[i]),
                )
            dpors[i]._account_host(time.perf_counter() - t_host)
            if hit is not None:
                obs.counter("dpor.violations_found").inc()
                found[i] = hit
                done[i] = True
    for i, d in enumerate(dpors):
        d.frontier = frontiers[i] + pendings[i]
    return found
