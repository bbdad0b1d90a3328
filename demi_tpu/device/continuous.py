"""Continuous sweep: segment-stepped exploration with mid-flight lane
refill — the continuous-batching trick applied to schedule exploration.

A fixed-length sweep pays for its slowest lane: with heavy-tailed
schedule lengths most of the batch idles (status frozen, steps masked to
no-ops) while a few long lanes finish. Here the kernel runs SHORT
segments and returns the full state batch; between segments the host
harvests finished lanes' verdicts and re-initializes exactly those lanes
with fresh programs/keys (a masked where-merge, no recompilation). Lane
occupancy stays ~100% for any schedule-length distribution.

Per-seed results are bit-identical to the plain explore kernel: a lane's
step stream depends only on its own state/key, frozen lanes are no-ops,
and refill replaces whole lanes atomically (tests/test_continuous.py).

One round of the harvest loop (``_run_batches``), in order: dispatch the
segment (asynchronous) -> MAKE AHEAD -> status pull (the sync point) ->
harvest -> fill -> refill. Which lane gets which program is
decided after the harvest, but what the coming programs are is a
function of ``seed_list[next_idx:]`` alone, so between the dispatch and
the pull the one host thread fuzzes and lowers them into a stock, in seed
order, while the device runs the segment. It stops at the first of: the
segment's result is ready (asked once a program); the stock holds as
many programs as lanes are active (no round can refill more, so the
host never holds more than one resident set); the call's seeds are used
up. The fill hands out the stock first and makes the rest on the spot,
so lane, seed, program and key pair up exactly as without it.

The resident programs are ONE set of host arrays (``op/a/b [b, E]``,
``msg [b, E, W]``: the ``ExtProgram`` every segment takes), allocated
once a call and written in place: a fill lowers each program straight
into its lane's rows (``encoding.lower_into``; a fuzzed program from its
op rows, with no event object), so a retired program's memory serves
the next and nothing is stacked. They are written only between a status
pull and the next dispatch. The stock is a second such block, so what is
made while the device may still read the resident set touches none of
it (the CPU backend may alias NumPy memory); the refill copies the
stock's rows onto the refilled lanes in one indexed assignment per
array. The stock
is a local of one ``_run_batches`` call: a caller's generator may change
between calls (the benchmark's closes over a per-job base), and a
consumer that stops early just drops it. Only a generator the
constructor is told is a function of the seed (``seed_pure``) is called
ahead; any other is called at refill, in refill order.
"""

from __future__ import annotations

import time
from typing import Callable, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs
from ..dsl import DSLApp
from .core import (
    ST_DONE,
    ST_UNFINISHED,
    ST_VIOLATION,
    DeviceConfig,
    ScheduleState,
    insert_form,
)
from .encoding import count_op_arrays, empty_programs, lower_into
from .explore import (
    ExtProgram,
    _finalize,
    init_state,
    make_any_step_fn,
)

LANES = "lanes"


def _lane_sharding(mesh, axis: str = LANES):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P(axis))


def _maybe_shard(fn, mesh, n_args: int, axis: str = LANES):
    """jit ``fn`` with every output leaf lane-sharded over ``mesh`` (all
    leaves carry the batch on their leading axis), or plain jit when mesh
    is None. Outputs-only on purpose: out_shardings *reshards* (host
    inputs get distributed on first touch, state stays resident across
    segments), while strict in_shardings would reject the zero-size
    disabled-trace leaf, which GSPMD canonicalizes to replicated no
    matter what. The refill loop's host side only ever pulls O(batch)
    status/violation/hash vectors."""
    if mesh is None:
        return jax.jit(fn)
    s = _lane_sharding(mesh, axis)
    return jax.jit(fn, out_shardings=s)


def _segment_lane_fn(app: DSLApp, cfg: DeviceConfig, seg_steps: int):
    """Per-lane segment body: advance one lane by ``seg_steps`` steps,
    masking steps at or past the lane's ``cfg.max_steps`` budget (finished
    lanes are frozen no-ops). The step counter rides the scan's carry, not
    its xs: a form first chosen for the Pallas twin (removed in PR 29) and
    kept because it is the compiled program the chip's numbers are of."""
    step = make_any_step_fn(app, cfg)

    def seg_lane(state: ScheduleState, prog: ExtProgram, steps_run):
        def body(carry, _):
            s, i = carry
            live = (steps_run + i) < cfg.max_steps
            s2 = step(s, prog)
            s = jax.tree_util.tree_map(
                lambda a, b: jnp.where(live, b, a), s, s2
            )
            return (s, i + 1), None

        (state, _), _ = jax.lax.scan(
            body, (state, jnp.int32(0)), None, length=seg_steps
        )
        return state

    return seg_lane


@obs.spans.staged(
    "setup.build", what="make_segment_kernel",
    insert=lambda app, cfg, *a, **kw: insert_form(cfg),
    fifo=lambda app, cfg, *a, **kw: cfg.track_fifo_heads,
)
def make_segment_kernel(
    app: DSLApp, cfg: DeviceConfig, seg_steps: int, mesh=None
):
    """jitted ``(state[B], progs[B], steps_run[B]) -> state'[B]``: advance
    every lane by ``seg_steps`` steps (finished lanes are frozen no-ops).

    ``steps_run`` is each lane's step count so far; steps at or past
    ``cfg.max_steps`` are masked out per lane, so bit-parity with the plain
    explore kernel holds for ANY seg_steps, including ones that don't
    divide max_steps (a lane refilled mid-stream stops exactly on budget
    instead of running to the segment boundary).

    ``mesh`` shards the lane batch over its axis (ICI scale-out for the
    refill path; the batch must be a multiple of the mesh size)."""
    seg_lane = _segment_lane_fn(app, cfg, seg_steps)
    return _maybe_shard(jax.vmap(seg_lane), mesh, 3)


def _ready(array) -> bool:
    """Whether a dispatched kernel's result has landed, without blocking
    (the idiom of ``pipeline/orchestrator._handle_ready``). An array with
    no such probe reads ready: then nothing is made ahead."""
    probe = getattr(array, "is_ready", None)
    return True if probe is None else bool(probe())


@obs.spans.staged("setup.build", what="make_init_kernel")
def make_init_kernel(app: DSLApp, cfg: DeviceConfig, mesh=None):
    """jitted ``keys[B] -> ScheduleState[B]`` batch initializer."""
    return _maybe_shard(
        jax.vmap(lambda key: init_state(app, cfg, key)), mesh, 1
    )


@obs.spans.staged("setup.build", what="make_refill_kernel")
def make_refill_kernel(app: DSLApp, cfg: DeviceConfig, mesh=None):
    """jitted ``(state[B], refill[B] bool, fresh[B]) -> state'[B]``:
    lanes with ``refill`` set are replaced by the fresh state wholesale."""

    def refill(state: ScheduleState, mask, fresh: ScheduleState):
        def merge(old, new):
            m = mask.reshape((-1,) + (1,) * (old.ndim - 1))
            return jnp.where(m, new, old)

        return jax.tree_util.tree_map(merge, state, fresh)

    return _maybe_shard(refill, mesh, 3)


@obs.spans.staged("setup.build", what="make_finalize_kernel")
def make_finalize_kernel(app: DSLApp, cfg: DeviceConfig, mesh=None):
    """jitted forced finalization for lanes that exhausted their step
    budget mid-flight (parity: the plain kernel's run-out path)."""

    def fin(state: ScheduleState):
        return jax.lax.cond(
            state.status < ST_DONE,
            lambda s: _finalize(s, app, cfg),
            lambda s: s,
            state,
        )

    return _maybe_shard(jax.vmap(fin), mesh, 1)


class _Stock:
    """Programs made ahead of their refill, in seed order: a ring of
    ``room`` lowered programs in one block of host arrays, apart from
    the resident set's. A slot whose program the ``program_key`` memo
    already held is not made: it keeps its place, ``made`` unset, and
    the fill looks it up."""

    def __init__(self, cfg: DeviceConfig, room: int):
        self.progs = empty_programs(cfg, room)
        self.room = room
        self.head = 0
        self.count = 0
        self.made = np.zeros(room, bool)
        self.from_rows = np.zeros(room, bool)  # lowered from op rows

    def next_slot(self) -> int:
        return (self.head + self.count) % self.room

    def take(self, k: int) -> np.ndarray:
        """The slots of the ``k`` oldest programs, given up."""
        slots = (self.head + np.arange(k)) % self.room
        self.head = (self.head + k) % self.room
        self.count -= k
        return slots


class ContinuousSweepDriver:
    """Seed-space sweep with continuous refill.

    ``program_gen(seed) -> [ExternalEvent]`` as in SweepDriver; verdicts
    per seed are identical to running each seed through the plain explore
    kernel with ``PRNGKey(seed)``."""

    @obs.spans.staged("setup.build", what="ContinuousSweepDriver")
    def __init__(
        self,
        app: DSLApp,
        cfg: DeviceConfig,
        program_gen: Callable,
        batch: int = 256,
        seg_steps: int = 32,
        key_fn: Optional[Callable] = None,
        mesh=None,
        program_key: Optional[Callable] = None,
        seed_pure: bool = False,
    ):
        self.app = app
        self.cfg = cfg
        self.program_gen = program_gen
        # Whether program_gen is a function of the seed within one sweep
        # call. Only then are programs made ahead of their refill (module
        # doc); a generator that reads live state (the autotuned sweep's
        # closes over the controller's weights and tags each seed with
        # the proposal it was drawn under) is called at refill only.
        self.seed_pure = seed_pure
        self.batch = batch
        self.seg_steps = seg_steps
        if mesh is not None and batch % mesh.size:
            raise ValueError(
                f"continuous batch {batch} must be a multiple of the mesh "
                f"size {mesh.size}"
            )
        # key_fn(seed) -> PRNGKey; default matches the plain explore
        # kernel driven with PRNGKey(seed). SweepDriver passes its
        # fold_in(base_key, seed) scheme for cross-mode parity.
        self.key_fn = key_fn or jax.random.PRNGKey
        # Vectorized key derivation (a per-seed Python loop costs 10s of
        # ms per refill round at big batches): key_fn must trace under
        # jit(vmap) over uint32 seeds. A key_fn that cannot is a caller
        # bug, and a device failure here is not answered by a host loop.
        self._vkeys = jax.jit(jax.vmap(self.key_fn))
        # program_key(seed) -> hashable: callers whose generator is
        # periodic in seed (config-5 style sweeps) pass the period key so
        # refill skips re-lowering — at 1e5+ lanes host-side lowering
        # otherwise dominates the harvest path. The RNG stream still uses
        # the raw seed, so equal programs keep distinct schedules.
        self._program_key = program_key
        self._lower_memo: dict = {}
        self.segment = make_segment_kernel(app, cfg, seg_steps, mesh=mesh)
        self.mesh = mesh
        self.init = make_init_kernel(app, cfg, mesh=mesh)
        self.refill = make_refill_kernel(app, cfg, mesh=mesh)
        self.finalize = make_finalize_kernel(app, cfg, mesh=mesh)
        # Occupancy accounting for the last _run: lane-steps spent with a
        # live (unfinished, unparked) lane vs total lane-steps scanned —
        # the number the compaction exists to maximize. A fixed sweep
        # without early exit scans lanes * max_steps; compare
        # last_total_lane_steps against that to see the saving.
        self.last_occupancy: Optional[float] = None
        self.last_total_lane_steps: int = 0
        self.last_live_lane_steps: int = 0
        # Wall-clock attribution for the last _run: device-segment time
        # (dispatch + the status sync) vs everything else (harvest,
        # program lowering, refill) — the scale-rehearsal metric for how
        # much the host-side refill path costs.
        self.last_segment_seconds: float = 0.0
        self.last_harvest_seconds: float = 0.0
        # Devices the last _run's lane state spanned, lanes on each.
        self.last_lane_sharding: Optional[dict] = None
        # Lanes of the last _run that ran out of steps before quiescence
        # under an invariant judged at quiescence only (ST_UNFINISHED:
        # no verdict).
        self.last_unfinished_lanes: int = 0
        # The fullest pool of the last _run, in rows, sampled at its
        # segment boundaries while spans are live (a traced job); None
        # when not sampled. A sample costs a [B, P] reduce and a [B]
        # pull a round, so an untraced sweep takes none, and the step
        # kernel carries no count for it.
        self.last_pool_peak: Optional[int] = None
        self._occupancy = jax.jit(
            lambda valid: jnp.sum(valid, axis=1, dtype=jnp.int32)
        )
        # What the FIFO discipline holds back: the valid non-timer rows
        # of the live lanes and those of them that head their channel,
        # ``[2]``, beside the pool-peak sample and as rarely. None where
        # the channels keep no order: no kernel, no count.
        self._fifo_rows = None
        if cfg.track_fifo_heads:
            def fifo_rows(status, valid, timer, head):
                rows = (status < ST_DONE)[:, None] & valid & ~timer
                return jnp.stack([
                    jnp.sum(rows, dtype=jnp.int32),
                    jnp.sum(rows & head, dtype=jnp.int32),
                ])

            self._fifo_rows = jax.jit(fifo_rows)
        # The app's progress counts (``DSLApp.progress``) of every lane,
        # ``[B, names]``: run at the retire, only while spans are live.
        # None for an app that names none: no kernel, no pull.
        self._progress = None
        if app.progress:
            fns = [fn for _name, fn in app.progress]
            self._progress = jax.jit(jax.vmap(
                lambda states: jnp.stack(
                    [jnp.asarray(fn(states), jnp.int32) for fn in fns]
                )
            ))

    def _record_round_stats(self, state, finished, vio) -> None:
        """Fold one harvest round's finished lanes into the registry
        (device.lane.* counters, driver=continuous) plus refill/occupancy
        gauges. Called at most once per segment round, only when
        telemetry is enabled. Shares reduce_lanes with the chunked/DPOR
        drivers — one definition of every counter — masked to the lanes
        finishing THIS round (each lane is counted exactly once, at
        harvest)."""
        from ..obs import lane_stats as _ls

        _ls.record(
            _ls.reduce_lanes(
                np.asarray(state.status), vio, np.asarray(state.deliveries),
                finished,
                invariant_interval=self.cfg.invariant_interval,
            ),
            driver="continuous",
        )
        obs.counter("device.continuous.rounds").inc()
        if self.last_occupancy is not None:
            obs.gauge("device.continuous.occupancy").set(self.last_occupancy)

    def _memo_hit(self, seed: int):
        """The lowered program the ``program_key`` memo holds for
        ``seed``, or None (no memo, or not lowered yet)."""
        if self._program_key is None:
            return None
        return self._lower_memo.get(self._program_key(seed))

    def _make(
        self, seed: int, clock: List[int], out: ExtProgram, lane: int
    ) -> bool:
        """``program_gen`` then ``lower_into`` row ``lane`` of ``out``
        for one seed, its nanoseconds added to ``clock`` (fuzz, lower).
        Nothing of the program outlives the call but its rows in
        ``out`` (and the memo's copy, where there is a memo). Returns
        whether it was lowered from op rows."""
        t0 = time.perf_counter_ns()
        events = self.program_gen(seed)
        t1 = time.perf_counter_ns()
        from_rows = lower_into(self.app, self.cfg, events, out, lane)
        clock[0] += t1 - t0
        clock[1] += time.perf_counter_ns() - t1
        if self._program_key is not None:
            self._lower_memo[self._program_key(seed)] = ExtProgram(
                *(x[lane].copy() for x in out)
            )
        return from_rows

    def _make_ahead(
        self, pending, seed_list: Sequence[int], start: int, room: int,
        stock: _Stock,
    ) -> None:
        """Between a segment's dispatch and its status pull: make the
        programs of ``seed_list[start + stock.count:]`` into ``stock``,
        in seed order, until ``pending`` (the segment's status) is
        ready, the stock holds ``room`` programs, or the seeds end
        (module doc). A ``program_key`` memo hit costs nothing at refill
        either, so it is not made ahead: it holds its place in the
        stock, unmade, and the fill looks it up. Timed as a fill: a
        ``sweep.fill`` span whose slices are ``sweep.fuzz`` and
        ``sweep.lower``."""
        had = stock.count
        todo = seed_list[start + had : start + room]
        if not todo or _ready(pending):
            return
        clock = [0, 0]
        with obs.span("sweep.fill", ahead=True) as sp:
            for seed in todo:
                slot = stock.next_slot()
                stock.made[slot] = self._memo_hit(seed) is None
                if stock.made[slot]:
                    stock.from_rows[slot] = self._make(
                        seed, clock, stock.progs, slot
                    )
                stock.count += 1
                if _ready(pending):
                    break
            sp.set(programs=stock.count - had)
            sp.slice("sweep.fuzz", clock[0])
            sp.slice("sweep.lower", clock[1])

    def _fill(
        self, seeds: Sequence[int], lanes: Sequence[int], progs: ExtProgram,
        stock: Optional[_Stock] = None,
    ) -> None:
        """Write the program of each seed into its lane of the resident
        arrays ``progs``: from ``stock`` while it lasts (made ahead for
        exactly these seeds, in this order: ``_make_ahead``), one
        indexed copy per array; the rest ``program_gen`` then
        ``lower_into`` on the spot, one seed at a time, each over its
        lane's old program. A program's events, if it ever had any, die
        before the next is generated, and a retired program's memory
        serves the next: a fill's programs held together beside the
        events cost the sweep more than the spans could (PERF.md,
        PR 24). The loop is per lane, so fuzzing and lowering get no
        span each: a clock pair per program sums them, and the
        ``sweep.fill`` span hands the sums to the stages ``sweep.fuzz``
        and ``sweep.lower`` (a no-op with spans off). ``sweep.stack`` is
        the copy out of the stock, all that is left of stacking.
        Counted beside them: ``sweep.programs`` put in a lane,
        ``sweep.prefetched`` of those that were made ahead, and
        ``sweep.row_lowered`` of those lowered from a fuzzed program's
        op rows, here or ahead (a memo hit is lowered by nobody)."""
        clock = [0, 0]
        lanes = np.asarray(lanes, np.intp)
        k = min(stock.count, len(seeds)) if stock is not None else 0
        made = np.zeros(len(seeds), bool)
        rows = 0
        with obs.span("sweep.fill", programs=len(seeds)) as sp:
            with obs.span("sweep.stack"):
                if k:
                    slots = stock.take(k)
                    made[:k] = stock.made[slots]
                    src, dst = slots[made[:k]], lanes[:k][made[:k]]
                    for resident, ahead in zip(progs, stock.progs):
                        resident[dst] = ahead[src]
                    rows = int(stock.from_rows[src].sum())
            lane_of = lanes.tolist()
            for j in np.flatnonzero(~made).tolist():
                lane, seed = lane_of[j], seeds[j]
                hit = self._memo_hit(seed)
                if hit is None:
                    rows += self._make(seed, clock, progs, lane)
                else:
                    for resident, held in zip(progs, hit):
                        resident[lane] = held
            sp.slice("sweep.fuzz", clock[0])
            sp.slice("sweep.lower", clock[1])
            if obs.spans.live():
                obs.stage_count("sweep.programs", len(seeds))
                obs.stage_count("sweep.prefetched", int(made.sum()))
                obs.stage_count("sweep.row_lowered", rows)
                # What this fill lowered, by kind of external op: how
                # much of the fault plane the traffic engages.
                counts = count_op_arrays(progs.op[lanes], progs.a[lanes])
                for kind, n in counts.items():
                    obs.stage_count(f"sweep.ops.{kind}", n)

    def time_to_first_violation(self, max_lanes: int = 1_000_000):
        """Wall-clock seconds until the first violating lane finishes (the
        BASELINE.md headline #2 shape, continuous-refill form). Returns
        (seconds, seed) or (None, None) if ``max_lanes`` seeds stay clean."""
        import time

        t0 = time.perf_counter()
        for seed, code in self.sweep_iter(max_lanes):
            if code != 0:
                return time.perf_counter() - t0, seed
        return None, None

    def sweep_iter(self, total_lanes: int, seeds: Optional[Sequence[int]] = None):
        """Generator form of ``sweep``: yields (seed, violation_code) as
        lanes finish."""
        for seed, _st, code, _h in self._run(total_lanes, seeds=seeds):
            yield seed, code

    def sweep(self, total_lanes: int = 0, seeds: Optional[Sequence[int]] = None):
        """Run ``total_lanes`` sequential seeds — or an explicit ``seeds``
        sequence (a distributed rank's strided partition, a replay list) —
        returning (statuses, violations) keyed by seed."""
        statuses, violations = {}, {}
        for seed, st, code, _h in self._run(total_lanes, seeds=seeds):
            statuses[seed] = st
            violations[seed] = code
        return statuses, violations

    def _run(self, total_lanes: int, seeds: Optional[Sequence[int]] = None):
        """Per-lane view over ``_run_batches``: yields one
        ``(seed, status, violation_code, sched_hash)`` tuple per finished
        lane (the original surface; batch consumers use the arrays)."""
        for seed_a, st_a, code_a, h_a in self._run_batches(
            total_lanes, seeds=seeds
        ):
            for k in range(len(seed_a)):
                yield (
                    int(seed_a[k]), int(st_a[k]), int(code_a[k]),
                    int(h_a[k]),
                )

    def _run_batches(
        self, total_lanes: int, seeds: Optional[Sequence[int]] = None
    ):
        """The harvest loop, yielding one ``(seeds, statuses, codes,
        hashes)`` array quadruple per segment round (only rounds that
        retired lanes yield). Array-granular retirement is what lets the
        SweepDriver's harvest accumulation stay vectorized — per-lane
        Python tuples exist only for callers that ask (``_run``). The
        round's order, and the stock of programs made ahead while the
        segment runs, are in the module doc."""
        seed_list = (
            list(range(total_lanes)) if seeds is None else list(seeds)
        )
        total_lanes = len(seed_list)
        if total_lanes == 0:
            return
        b = min(self.batch, total_lanes)
        if self.mesh is not None:
            # Lane-sharded kernels need a mesh-multiple batch; surplus
            # lanes start inert (never yielded, never refilled).
            align = self.mesh.size
            b = max(align, ((b + align - 1) // align) * align)
        live_lane_steps = 0
        total_lane_steps = 0

        def keys_for(seeds):
            return self._vkeys(jnp.asarray(seeds, jnp.uint32))

        n_live = min(b, total_lanes)
        with obs.span("sweep.prime", lanes=b):
            # Lane i runs seed_list[i]; surplus (mesh-alignment) lanes run
            # the first seed inertly — never yielded, never refilled.
            lane_seed = [
                seed_list[i] if i < n_live else seed_list[0]
                for i in range(b)
            ]
            next_idx = n_live  # next position in seed_list to hand out
            # The resident set's programs: what every segment takes,
            # written in place between a pull and the next dispatch.
            progs = empty_programs(self.cfg, b)
            self._fill(lane_seed, range(b), progs)
            with obs.span("sweep.refill"):
                state = self.init(keys_for(lane_seed))
            steps_run = np.zeros(b, np.int64)
            done_count = 0
            active = np.arange(b) < n_live
            # Lowered programs of seed_list[next_idx:], made while a
            # segment ran; this call's alone (module doc).
            stock = _Stock(self.cfg, b) if self.seed_pure else None

            self.last_segment_seconds = 0.0
            self.last_harvest_seconds = 0.0
            self.last_lane_sharding = None
            self.last_unfinished_lanes = 0
            sample_pool = obs.spans.live()
            self.last_pool_peak = 0 if sample_pool else None
        while done_count < total_lanes:
            with obs.span("sweep.round"):
                n_active = int(active.sum())
                round_live = n_active * self.seg_steps
                total_lane_steps += b * self.seg_steps
                live_lane_steps += round_live
                self.last_occupancy = live_lane_steps / total_lane_steps
                self.last_total_lane_steps = total_lane_steps
                self.last_live_lane_steps = live_lane_steps
                obs.stage_count("sweep.lane_steps", b * self.seg_steps)
                obs.stage_count("sweep.live_lane_steps", round_live)
                t_seg = time.perf_counter()
                with obs.span("sweep.block"):
                    state = self.segment(
                        state, progs, jnp.asarray(steps_run, jnp.int32)
                    )
                    occupancy = (
                        self._occupancy(state.pool_valid)
                        if sample_pool else None
                    )
                    fifo_rows = (
                        self._fifo_rows(
                            state.status, state.pool_valid,
                            state.pool_timer, state.pool_head,
                        )
                        if sample_pool and self._fifo_rows else None
                    )
                t_gap = time.perf_counter()
                if self.seed_pure:
                    self._make_ahead(
                        state.status, seed_list, next_idx, n_active, stock
                    )
                t_pull = time.perf_counter()
                with obs.span("sweep.block"):
                    # The status pull is the sync point: the dispatch
                    # and the wait here are device-segment time; what
                    # was made in the gap, and the rest of the
                    # iteration, is harvest.
                    _status_sync = np.asarray(state.status)
                    if sample_pool:
                        self.last_pool_peak = max(
                            self.last_pool_peak,
                            int(np.asarray(occupancy).max()),
                        )
                        if fifo_rows is not None:
                            pending, heads = np.asarray(fifo_rows).tolist()
                            obs.stage_count("sweep.fifo_pending_rows", pending)
                            obs.stage_count("sweep.fifo_head_rows", heads)
                t_harvest = time.perf_counter()
                self.last_harvest_seconds += t_pull - t_gap
                if self.last_lane_sharding is None:
                    from ..parallel.mesh import lane_sharding_summary

                    self.last_lane_sharding = lane_sharding_summary(
                        state.status
                    )
                self.last_segment_seconds += (t_gap - t_seg) + (
                    t_harvest - t_pull
                )
                steps_run = np.minimum(
                    steps_run + self.seg_steps, self.cfg.max_steps
                )
                # Budget exhaustion: force-finalize overdue live lanes
                # (the plain kernel's run-out-of-steps semantics).
                status = _status_sync
                overdue = (
                    active & (status < ST_DONE)
                    & (steps_run >= self.cfg.max_steps)
                )
                if overdue.any():
                    with obs.span("sweep.finalize"):
                        finalized = self.finalize(state)
                        state = self.refill(
                            state, jnp.asarray(overdue), finalized
                        )
                        status = np.asarray(state.status)
                finished = active & (status >= ST_DONE)
                out = None
                if finished.any():
                    with obs.span("sweep.pull"):
                        vio = np.asarray(state.violation)
                        sh = np.asarray(state.sched_hash)
                        if obs.enabled():
                            # Round-granularity lane telemetry: the
                            # status pull above is the round's one sync
                            # point; deliveries ride the same harvest
                            # (never per segment step).
                            self._record_round_stats(state, finished, vio)
                    with obs.span("sweep.retire"):
                        fin = np.flatnonzero(finished)
                        # Seeds gathered BEFORE refill rewrites lane_seed.
                        out = (
                            np.asarray(lane_seed, np.int64)[fin],
                            status[fin].copy(), vio[fin].copy(),
                            sh[fin].copy(),
                        )
                        done_count += len(fin)
                        unfinished = int(
                            (out[1] == ST_UNFINISHED).sum()
                        )
                        self.last_unfinished_lanes += unfinished
                        if sample_pool:
                            # What the retired lanes put in their pools
                            # (every insert advances ``seq_counter`` by
                            # its rows) against the outbox rows their
                            # deliveries carried through the insert: a
                            # [B] pull each, beside the status pull.
                            obs.stage_count(
                                "sweep.rows_inserted",
                                int(np.asarray(state.seq_counter)[fin].sum()),
                            )
                            obs.stage_count(
                                "sweep.outbox_rows",
                                int(np.asarray(state.deliveries)[fin].sum())
                                * self.cfg.max_outbox,
                            )
                            if state.insert_full_steps is not None:
                                # How often the insert's short pass is
                                # taken: of the steps the retired lanes
                                # were scanned, those in which some
                                # resident lane sent more rows than it
                                # holds (``core.insert_rows``).
                                obs.stage_count(
                                    "sweep.insert_full_steps",
                                    int(np.asarray(
                                        state.insert_full_steps
                                    )[fin].sum()),
                                )
                                obs.stage_count(
                                    "sweep.insert_steps",
                                    int(steps_run[fin].sum()),
                                )
                            if self._progress is not None:
                                # What the retired lanes' protocol got
                                # done: one [B, names] pull.
                                done = np.asarray(
                                    self._progress(state.actor_state)
                                )[fin].sum(axis=0)
                                for (name, _fn), total in zip(
                                    self.app.progress, done.tolist()
                                ):
                                    obs.stage_count(
                                        f"sweep.app.{name}", total
                                    )
                            obs.stage_count("sweep.retired", len(fin))
                            obs.stage_count(
                                "sweep.quiesced",
                                int((out[1] <= ST_VIOLATION).sum()),
                            )
                            obs.stage_count("sweep.unfinished", unfinished)
                        # Refill finished lanes with fresh seeds (or park
                        # them).
                        refill_lanes = set(
                            int(x) for x in np.flatnonzero(finished)[
                                : max(0, total_lanes - next_idx)
                            ]
                        )
                        for lane in np.flatnonzero(finished):
                            active[lane] = False
                    if refill_lanes:
                        fresh_seeds = seed_list[
                            next_idx : next_idx + len(refill_lanes)
                        ]
                        next_idx += len(refill_lanes)
                        # Ascending, as the loop below hands the seeds out.
                        self._fill(
                            fresh_seeds, sorted(refill_lanes), progs, stock
                        )
                        with obs.span("sweep.refill"):
                            mask = np.zeros(b, bool)
                            full_seeds = []
                            k = 0
                            for lane in range(b):
                                if lane in refill_lanes and k < len(
                                    fresh_seeds
                                ):
                                    mask[lane] = True
                                    lane_seed[lane] = fresh_seeds[k]
                                    full_seeds.append(fresh_seeds[k])
                                    active[lane] = True
                                    steps_run[lane] = 0
                                    k += 1
                                else:
                                    full_seeds.append(lane_seed[lane])
                            fresh = self.init(keys_for(full_seeds))
                            state = self.refill(
                                state, jnp.asarray(mask), fresh
                            )
                self.last_harvest_seconds += time.perf_counter() - t_harvest
            # Yield outside every span, and after the timing stop: caller
            # time (a generator consumer may do arbitrary work per item)
            # never counts as the driver's.
            if out is not None:
                yield out
        if sample_pool:
            # Per job, so their ratio is the mean fullest pool's share.
            obs.stage_count("sweep.pool_peak_rows", self.last_pool_peak)
            obs.stage_count("sweep.pool_rows", self.cfg.pool_capacity)
