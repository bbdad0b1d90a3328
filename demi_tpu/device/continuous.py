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
segment (asynchronous) and, right behind it, the finalize of the lanes
that spend their last step in it (which those are follows from
``steps_run``, which the host holds: no pull) -> MAKE AHEAD -> status
pull (the sync point) -> harvest -> fill -> refill. Which lane gets
which program is decided after the harvest, but what the coming programs
are is a function of ``seed_list[next_idx:]`` alone, so between the
dispatch and the pull the one host thread fuzzes and lowers them into a
stock, in seed order, while the device runs the segment. It stops at
the first of: the awaited segment's result is ready (asked once a
program); the stock holds as many programs as lanes are active (no round
can refill more, so the host never holds more than one resident set of
them); the call's seeds are used up. The fill hands out the stock first and makes the rest on the spot,
so lane, seed, program and key pair up exactly as without it.

THE LAG (PR 46; the budget path behind its segment: PR 48). In that
order nothing is in the device's queue while the host harvests: every
round the chip sits through the pull's round trip, the harvest, the fill
and the next dispatch. Where a schedule lives ``_LAG_LIFE`` segments or
more (``_lag``: a rule on ``cfg.max_steps`` over ``seg_steps``, nothing
sets it) the same statements run with the harvest ONE SEGMENT BEHIND the
device. A round of it, in order:

1. dispatch segment k on the state the round before left and, from
   ``steps_run``, the ``finalize`` of the lanes that spend their last
   step in it (``spent``): no pull. That state, F(k), is ``held``, with
   the samples, the steps and the seeds of segment k's lanes;
2. make ahead, then wait for F(k-1)'s status: the one sync point;
3. harvest from F(k-1)'s own leaves: the lanes ``seen`` finished in it
   for the first time (they stopped on their own in segment k-1 and sit
   frozen through segment k: a lane with ``status >= ST_DONE`` is a
   no-op in every step, ``explore.make_step_fn``, and ``finalize`` is
   the identity on it), and the lanes ``owed`` from the round before:
   those segment k-1 was known to spend. ``renewed`` masks the lanes
   whose refill was queued behind F(k-1): it shows their predecessors;
4. fill and queue ONE ``refill`` behind F(k) for the lanes ``seen`` and
   the lanes ``spent``, in lane order, with the next seeds in seed
   order, ``steps_run`` zeroed: segment k+1 runs them live. The
   ``spent`` lanes' verdicts are read at the next round's pull, from
   F(k), which the refill does not touch: they become its ``owed``.

So a lane that stops on its own pays one frozen segment (between the
one it finishes in and the one its refill lands behind:
``live_lane_steps`` counts it out of the segment in flight at its
retire), and a lane that runs to its budget pays none: the host knew,
when it dispatched the segment, that the lane would be free behind it.
A lane is retired once: one that stopped on its own in segment k-1 and
is spent by count in segment k is ``seen`` at round k's pull and taken
out of ``spent`` there; one that stops in the very segment that spends
it is ``spent``, and ``renewed`` keeps the next pull from seeing its
old status. Merging a fresh lane after F(k) gives what merging it
before segment k+1 would have; the harvest always reads segment k-1,
never "whichever is ready", so which lane a seed lands in and the order
of the yielded batches are functions of the seeds and the sizes alone.
Where segment k spends every lane still active and no seed is left,
the next round dispatches nothing and only harvests; where the last
lanes stop on their own, or a consumer stops, one segment is in
flight: its result is dropped, and the next call starts from its own
state. ``held`` keeps F(k) alive through round k+1's harvest, beside
the merged state segment k+1 takes: one state more than the strict
order holds. With no lag the order is strict: the harvest reads the
segment it just dispatched, ``owed`` and ``renewed`` stay empty, and
the lanes a segment spends are ``seen`` in the same round.

The resident programs are host arrays (``op/a/b [b, E]``, ``msg [b, E,
W]``: the ``ExtProgram`` every segment takes), allocated once a call
and written in place: a fill lowers each program straight into its
lane's rows (``encoding.lower_into``; a fuzzed program from its op
rows, with no event object), so a retired program's memory serves the
next and nothing is stacked. A SET IS WRITTEN ONLY AFTER EVERY SEGMENT
DISPATCHED WITH IT HAS BEEN PULLED (the CPU backend may alias NumPy
memory, and a transfer may still read it). With no lag there is one
set, written between a status pull and the next dispatch. Under the lag
segment k, in flight, reads the ``spent`` lanes' rows live while the
fill writes their successors', so there are TWO sets: a refill round
takes the set segment k was not dispatched with (every segment that
took it was pulled by round k's sync point), copies onto it the rows it
lacks (``stale``: the lanes the refill before wrote in the other set;
the two differ in nothing else), writes the refilled lanes' rows there,
and segment k+1 takes it (``tests/test_continuous.py`` holds every
dispatched set to its bytes at dispatch until its segment is pulled).
The stock is one more such block, so what is made while the device may
still read a resident set touches none of it; the refill copies the
stock's rows onto the refilled lanes in one indexed assignment per
array. The stock is a local of one ``_run_batches`` call: a caller's
generator may change between calls (the benchmark's closes over a per-job base), and a
consumer that stops early just drops it. Only a generator the
constructor is told is a function of the seed (``seed_pure``) is called
ahead; any other is called at refill, in refill order.

PRODUCER PROCESSES (PR 42). Where the making is what a call waits for,
it is done by child processes beside the host thread, and the round is
what is left: dispatch -> status pull -> harvest -> fill (one indexed
copy per array out of the ring) -> refill (the lag moves the pull and
what follows it one segment back, as above). Whether it is, the call
observes; nothing sets it. (1) The prime fill makes its first ``_PROBE``
programs on the spot under the clock pair every program runs under:
the programs the call still has to make, at that cost, must give each
producer ``_WORTH_S`` seconds of making (``_producer_count``: none
either where the generator is not ``seed_pure``, where there is a
``program_key`` memo, on one core, or without ``fork``). (2) Making
ahead hides the making while the device is the slower of the two, and
then a fork only costs: so the call counts the nanoseconds its refills
spend making programs the stock had run out of, the device idle
meanwhile, and forks once they reach ``_WORTH_S`` (about what the forks
will cost: it has then lost no more than twice what it could have),
right after a dispatch, behind what the stock holds (the ring starts
with it). The driver remembers the verdict (``_exposed``), and its next
call forks at its prime fill, so that the first resident set comes out
of the ring too. The children are forked inside the call because the
generator is a closure that nothing can pickle and that may differ
from call to call: a child inherits it as it stands. A fork copies the
page tables of a process that holds a TPU client (14 GB resident:
45-75 ms on the one-chip host, about 100 on four chips; PERF.md,
PR 42), on the host thread, so HOW MANY is measured too: the first fork
is timed (what a fork costs is the least the process has timed: one
can read five times that), and the call forks ``sqrt(a resident set's
making / one fork)`` children, the count behind which a fill that waits for a whole
set waits least (``_fewest_wait``). The host thread forks them itself,
one after the other: a child that forked its siblings would start its
own chunks later and spare the host thread nothing at the prime fill,
where it only waits meanwhile.

Who writes which block when. The stock is then a ``_Ring``: one
anonymous shared mapping made before the fork, ``_RING_SETS`` resident
sets long whatever the call's length, cut into chunks of consecutive
seed positions. Child ``i`` makes chunks ``i, i + P, ...`` (behind
the chunks a mid-call fork found made) with the driver's own ``_make`` (the same code on the same seed: the bytes are
the host thread's), each into the ring slot ``chunk % slots``, then
writes the chunk's number into the slot's flag; before a chunk it waits
until the fill has taken the chunk that held the slot a ring earlier
(``head[0]``, the host thread's word). The host thread reads
a slot only after its flag names the chunk it wants, copies, and only
then advances ``taken``: no row is ever written and read at once, and
the resident arrays are the host thread's alone. Where the chunk it
needs is not flagged yet it waits inside a ``sweep.starve`` span, each
wait under a deadline and a ``waitpid(WNOHANG)``; a child that died or
outstayed the deadline is killed, reaped and written off, and the fill
makes that child's positions itself, on the spot, from then on: a lost
child costs time, never a result. The generator's ``finally`` kills and
reaps every child and unmaps the ring, however the call ends.

Why a child may touch nothing but its slots. It is a copy of a process
that holds the chip, a profiler session and threads it did not inherit:
it runs ``_make`` over its chunks and nothing else -- no ``jax`` call, no
span, no ``TraceAnnotation``, no logging, no collector hook (the
parent's would open a ``gc.pause`` span) -- and leaves through
``os._exit``, so no ``atexit`` handler shuts the parent's TPU client
down and no buffered output is written twice. Its clock pairs and the
fuzzer's own counts reach the parent through the ring's ``stats`` rows
(``sweep.producer_ns``; ``fuzz.programs_generated`` /
``fuzz.events_generated`` under ``DEMI_OBS=1``).
"""

from __future__ import annotations

import gc
import mmap
import os
import signal
import time
import warnings
from typing import Callable, List, Optional, Sequence, Tuple

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
    table_reads,
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
    channels=lambda app, cfg, *a, **kw: app.channels,
    table_reads=lambda app, cfg, *a, **kw: table_reads(app, cfg),
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
        self.took_hosts = 0

    def next_slot(self) -> int:
        return (self.head + self.count) % self.room

    def take(self, k: int) -> Tuple[np.ndarray, np.ndarray]:
        """The slots of the oldest programs, ``k`` of them or all there
        are, given up, and which of them were made (``took_hosts`` of
        them: all that were; the name is ``_Producers``')."""
        k = min(k, self.count)
        slots = (self.head + np.arange(k)) % self.room
        self.head = (self.head + k) % self.room
        self.count -= k
        made = self.made[slots]
        self.took_hosts = int(made.sum())
        return slots, made

    def free(self, k: int) -> None:
        """Nothing to tell anybody: the host thread alone writes here."""


# Producer processes (module doc).
_PROBE = 32             # programs the prime fill makes on the spot to price one
_CHUNK = 64             # programs between two flags, at most (a quarter set)
# The ring's length, in resident sets. At least 2: a fill may take a
# whole set from a position inside a chunk, and the chunk it ends in
# must have a slot free of the one it starts in. 4 was no faster
# (PERF.md, PR 42).
_RING_SETS = 2
# Seconds of making a producer must have ahead of it: several forks at
# the chip host's 45-75 ms each (PERF.md, PR 42).
_WORTH_S = 0.25
# Segments a schedule's step budget must hold (``cfg.max_steps`` over
# ``seg_steps``) for the harvest to run one segment behind the device
# (module doc, THE LAG). The lag trades one frozen segment of every
# schedule that stops on its own, between the one it finishes in and the
# one its refill lands behind, for a device that never waits for the
# host's round; a schedule that runs to its budget pays nothing (PR 48).
# 4 is the life ``SweepDriver`` builds for every ``max_steps >= 32``, so
# all nine sweep cells run this order: ``raft5-sweep`` and its ``-x4``
# (4 segments a life; 78% of their lanes end at the budget), deep raft
# and VSR (16), ``raft7-reconfig`` and chain (32), spark (52), paxos
# (64), the flood (72). Under it the order is strict: callers that cut
# a budget into fewer segments (``tools/soak.py``'s 40 steps in
# segments of 28), where a frozen segment is a third of a life or more.
# (On the chip: PERF.md, PRs 46 and 48.)
_LAG_LIFE = 4
_STARVE_DEADLINE_S = 2.0    # a wait for one chunk, before its child is given up
_POLL_S = 1e-4
# The shortest fork this process has timed: what a fork costs it. One
# fork can read five times that (the first after a profiler session
# ends: 239 ms against 60-80; PERF.md, PR 42), and a call that took it
# at its word forked one child where three pay.
_least_fork_ns = float("inf")


def _cores() -> int:
    """Cores this process may run on."""
    affinity = getattr(os, "sched_getaffinity", None)
    return len(affinity(0)) if affinity else (os.cpu_count() or 1)


def _fewest_wait(prime_ns: float, fork_ns: float, most: int) -> int:
    """The number of children, 1 to ``most``, behind which the prime
    fill waits least: ``P`` forks one after the other, then ``prime_ns``
    of making shared by ``P``."""
    return min(most, max(1, round((prime_ns / max(fork_ns, 1)) ** 0.5)))


class _Ring:
    """The stock of a call whose programs producer processes make: the
    ``ExtProgram`` block and the words the processes speak through, laid
    over ONE anonymous shared mapping made before the fork. ``slots``
    chunks of ``chunk`` rows; seed position ``q`` (counted from the
    first a producer makes) lives in row ``q % room``."""

    def __init__(self, cfg: DeviceConfig, resident: int, producers: int):
        self.chunk = max(1, min(_CHUNK, resident // 4))
        self.slots = -(-_RING_SETS * resident // self.chunk)
        self.room = self.slots * self.chunk
        fields = [
            (np.dtype(np.int64), (2,)),             # head
            (np.dtype(np.int64), (producers, 4)),   # stats
            (np.dtype(np.int64), (self.slots,)),    # flags
            (np.dtype(np.uint8), (self.room,)),     # from_rows
        ] + [
            (x.dtype, (self.room,) + x.shape[1:])
            for x in empty_programs(cfg, 0)
        ]
        offsets, size = [], 0
        for dtype, shape in fields:
            offsets.append(size)
            size += -(-int(np.prod(shape)) * dtype.itemsize // 64) * 64
        self.block = mmap.mmap(-1, size)
        views = [
            np.frombuffer(
                self.block, dtype, int(np.prod(shape)), offset
            ).reshape(shape)
            for (dtype, shape), offset in zip(fields, offsets)
        ]
        # The host thread's two words: [0] positions the fill has
        # copied out; [1] how many children share the chunks, written
        # once, after the first fork is timed (0: not decided yet).
        # Everything below is the producers'.
        self.head = views[0]
        # Child i's row: fuzz ns, lower ns, and the fuzzer's own
        # programs and events counts (0 unless telemetry is on).
        self.stats = views[1]
        # Slot s holds chunk ``flags[s] - 1`` whole; 0: none yet.
        self.flags = views[2]
        self.from_rows = views[3]
        self.progs = ExtProgram(*views[4:])

    def close(self) -> None:
        """Unmap. The views go first: a mapping with a buffer exported
        cannot be closed (one still held elsewhere keeps it until it
        dies)."""
        self.head = self.stats = self.flags = self.from_rows = None
        self.progs = None
        try:
            self.block.close()
        except BufferError:
            pass


class _Producers:
    """Forked processes making the programs of ``seeds[first:]`` into a
    ``_Ring``, in seed order, and the host thread's end of it (module
    doc). ``make`` is the driver's ``_make``. At most ``most`` children:
    as many as ``_fewest_wait`` says once the first fork is timed
    (``prime_ns``: a resident set's making; a fork costs the least this
    process has seen one take), or ``forced``, the driver's
    private constructor argument, which is not measured. ``held`` is the
    stock of a call that forks mid-way."""

    def __init__(
        self, cfg: DeviceConfig, make: Callable, seeds: Sequence[int],
        first: int, resident: int, most: int, prime_ns: float,
        forced: Optional[int] = None, held: Optional[_Stock] = None,
    ):
        self.ring = _Ring(cfg, resident, most)
        self.progs, self.from_rows = self.ring.progs, self.ring.from_rows
        self.make = make
        self.seeds = seeds
        self.first = first
        self.todo = len(seeds) - first      # positions to make
        self.cursor = 0                     # next position the fill takes
        self.pids: List[int] = []           # 0 once reaped
        self.children = forced or 0
        # Where the call had a stock of its own (it forks mid-way), the
        # ring starts with what the host thread had made ahead: whole
        # chunks, flagged, that the children begin behind.
        self.hosts = self.took_hosts = 0
        if held is not None and held.count:
            self.hosts = held.count
            slots, _made = held.take(held.count)
            for mine, ahead in zip(self.progs, held.progs):
                mine[: self.hosts] = ahead[slots]
            self.from_rows[: self.hosts] = held.from_rows[slots]
            while self.hosts % self.ring.chunk and self.hosts < self.todo:
                self.from_rows[self.hosts] = make(
                    seeds[first + self.hosts], [0, 0], self.progs, self.hosts
                )
                self.hosts += 1
        self.base = -(-self.hosts // self.ring.chunk)   # the children's first chunk
        self.ring.flags[: self.base] = np.arange(1, self.base + 1)
        parent = os.getpid()
        with obs.span("sweep.fork") as sp, warnings.catch_warnings():
            # JAX and CPython both warn that a fork in a threaded
            # process may deadlock the child: this child runs no code
            # but ``_make``, and every wait for it has a deadline.
            warnings.simplefilter("ignore")
            self.ring.head[1] = self.children
            i = 0
            while i < max(self.children, 1):
                t0 = time.perf_counter_ns()
                try:
                    pid = os.fork()
                except OSError:
                    pid = 0     # no process to be had: the fill makes its share
                else:
                    if pid == 0:
                        self._produce(i, parent)    # never returns
                self.pids.append(pid)
                if not self.children:
                    global _least_fork_ns
                    _least_fork_ns = min(
                        _least_fork_ns, time.perf_counter_ns() - t0
                    )
                    self.children = _fewest_wait(
                        prime_ns, _least_fork_ns, most
                    )
                    self.ring.head[1] = self.children
                i += 1
            sp.set(children=self.children)

    # -- the child -----------------------------------------------------------
    def _produce(self, i: int, parent: int) -> None:
        """Child ``i``, whole: chunks ``base + i, base + i + children,
        ...``, then out through ``os._exit`` whatever happened."""
        code = 1
        try:
            gc.callbacks.clear()
            gc.freeze()     # the inherited heap stays off copy-on-write
            ring, make, seeds = self.ring, self.make, self.seeds
            chunk, slots, first = ring.chunk, ring.slots, self.first
            head, stats = ring.head, ring.stats[i]

            def wait_while(behind) -> None:
                while behind():
                    if os.getppid() != parent:
                        os._exit(0)
                    time.sleep(_POLL_S)

            counters = [
                obs.counter("fuzz.programs_generated"),
                obs.counter("fuzz.events_generated"),
            ]
            had = [c.value() for c in counters]
            clock = [0, 0]
            # (the first child is forked before their number is known)
            wait_while(lambda: head[1] == 0)
            for c in range(self.base + i, -(-self.todo // chunk), int(head[1])):
                # The slot is free once the chunk a ring earlier is taken.
                wait_while(lambda: head[0] < (c - slots + 1) * chunk)
                lo = c * chunk
                row = (c % slots) * chunk
                for q in range(lo, min(lo + chunk, self.todo)):
                    ring.from_rows[row] = make(
                        seeds[first + q], clock, ring.progs, row
                    )
                    row += 1
                stats[0], stats[1] = clock
                stats[2] = counters[0].value() - had[0]
                stats[3] = counters[1].value() - had[1]
                ring.flags[c % slots] = c + 1
            code = 0
        finally:
            os._exit(code)

    # -- the host thread -----------------------------------------------------
    def _reaped(self, i: int) -> bool:
        """Whether child ``i`` is gone (reaping it if it just went)."""
        pid = self.pids[i]
        if pid:
            try:
                pid = 0 if os.waitpid(pid, os.WNOHANG)[0] else pid
            except ChildProcessError:
                pid = 0
            self.pids[i] = pid
        return not pid

    def _kill(self, i: int) -> None:
        pid = self.pids[i]
        if pid:
            try:
                os.kill(pid, signal.SIGKILL)
                os.waitpid(pid, 0)
            except (ProcessLookupError, ChildProcessError):
                pass
            self.pids[i] = 0

    def _flagged(self, c: int) -> bool:
        """Whether chunk ``c`` is whole in its slot, waiting for it
        while its child lives and the deadline lasts."""
        ring = self.ring
        slot = c % ring.slots
        if ring.flags[slot] == c + 1:
            return True
        i = (c - self.base) % self.children
        if not self.pids[i]:
            return False
        with obs.span("sweep.starve", chunk=c):
            deadline = time.monotonic() + _STARVE_DEADLINE_S
            while ring.flags[slot] != c + 1:
                if self._reaped(i):
                    break
                if time.monotonic() > deadline:
                    self._kill(i)
                    break
                time.sleep(_POLL_S)
        # (a child may flag its last chunk and go between two looks)
        return bool(ring.flags[slot] == c + 1)

    def take(self, k: int) -> Tuple[np.ndarray, np.ndarray]:
        """The ring rows of the next ``k`` seed positions, and which of
        them hold a program (the rest were a lost child's: the fill
        makes them); ``took_hosts`` of them were the host thread's own.
        The rows stay the fill's until ``free``."""
        ring = self.ring
        self.took_hosts = min(max(self.hosts - self.cursor, 0), k)
        q = self.cursor + np.arange(k)
        chunk_of = q // ring.chunk
        c0 = int(chunk_of[0])
        whole = np.array(
            [self._flagged(c) for c in range(c0, int(chunk_of[-1]) + 1)]
        )
        return q % ring.room, whole[chunk_of - c0]

    def free(self, k: int) -> None:
        """The ``k`` positions last taken are copied out."""
        self.cursor += k
        self.ring.head[0] = self.cursor

    def close(self) -> None:
        """Kill and reap every child, fold what they counted, unmap."""
        for i in range(len(self.pids)):
            self._kill(i)
        stats = self.ring.stats.sum(axis=0).tolist()
        obs.stage_count("sweep.producer_ns", stats[0] + stats[1])
        if obs.enabled():
            obs.counter("fuzz.programs_generated").inc(stats[2])
            obs.counter("fuzz.events_generated").inc(stats[3])
        self.progs = self.from_rows = None
        self.ring.close()


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
        producers: Optional[int] = None,
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
        # Private, for tests: how many producer processes a call forks
        # where it may fork any (module doc). None, which is what every
        # verb leaves it at, decides from what the call observes.
        self._producers = producers
        # The running call's producers, or None: a test's way in.
        self._producing: Optional[_Producers] = None
        # Whether this driver's last call found its making on the
        # critical path (``_rounds``): the next forks at its prime fill.
        self._exposed = False
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

    def _lag(self) -> int:
        """How many segments the harvest runs behind the device: 1 where
        a schedule's budget is ``_LAG_LIFE`` segments or more, else 0
        (the constant's comment has the trade)."""
        return int(self.cfg.max_steps >= _LAG_LIFE * self.seg_steps)

    def _samples(self, state):
        """A traced job's reduces over the state a segment left,
        dispatched behind it and pulled at its harvest: each lane's
        valid pool rows ``[B]``; the FIFO discipline's two counts
        ``[2]``; the app's progress counts ``[B, names]`` (a finished
        lane's rows stay as the segment left them, whatever is merged
        into the batch later); None for what the kernel or the app has
        not."""
        return (
            self._occupancy(state.pool_valid),
            self._fifo_rows(
                state.status, state.pool_valid, state.pool_timer,
                state.pool_head,
            ) if self._fifo_rows else None,
            self._progress(state.actor_state) if self._progress else None,
        )

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

    def _producer_count(self, left: int, ns_a_program: float) -> int:
        """How many producer processes, at most, the ``left`` programs a
        call has still to make are worth, one costing ``ns_a_program``
        (module doc): as many as have ``_WORTH_S`` seconds of making
        each, up to the cores beside the host thread's; 0 where the
        call may fork none."""
        if (
            not self.seed_pure or self._program_key is not None
            or not hasattr(os, "fork") or left <= 0
        ):
            return 0
        if self._producers is not None:
            return self._producers
        worth = int(left * ns_a_program / 1e9 / _WORTH_S)
        return max(0, min(worth, _cores() - 1))

    def _start_producers(
        self, seed_list: Sequence[int], first: int, resident: int,
        ns_a_program: float, held: Optional[_Stock] = None,
    ) -> Optional[_Producers]:
        """Producer processes for ``seed_list[first:]`` behind what
        ``held`` holds of it, or None where they are not worth it."""
        left = len(seed_list) - first - (held.count if held else 0)
        most = self._producer_count(left, ns_a_program)
        if not most:
            return None
        self._producing = _Producers(
            self.cfg, self._make, seed_list, first, resident, most,
            resident * ns_a_program, self._producers, held,
        )
        obs.stage_count("sweep.producers", self._producing.children)
        return self._producing

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
        stock=None,
    ) -> int:
        """Write the program of each seed into its lane of the resident
        arrays ``progs``: from ``stock`` while it lasts (made ahead for
        exactly these seeds, in this order: by ``_make_ahead`` into a
        ``_Stock``, or by producer processes into a ``_Producers``'
        ring, which lasts as long as its children do), one indexed copy
        per array; the rest ``program_gen`` then ``lower_into`` on the
        spot, one seed at a time, each over its lane's old program. A
        program's events, if it ever had any, die before the next is
        generated, and a retired program's memory serves the next: a
        fill's programs held together beside the events cost the sweep
        more than the spans could (PERF.md, PR 24). The loop is per
        lane, so fuzzing and lowering get no span each: a clock pair per
        program sums them, and the ``sweep.fill`` span hands the sums to
        the stages ``sweep.fuzz`` and ``sweep.lower`` (a no-op with
        spans off): the host thread's making only; the producers' is
        ``sweep.producer_ns``. ``sweep.stack`` is the copy out of the
        stock, all that is left of stacking; ``sweep.starve``
        (``_Producers._flagged``) the wait for a chunk the producers
        have not finished. Counted beside them: ``sweep.programs`` put
        in a lane, ``sweep.prefetched`` of those that the host thread
        made ahead, ``sweep.produced`` of those that a producer made,
        and ``sweep.row_lowered`` of those lowered from a fuzzed
        program's op rows, wherever (a memo hit is lowered by nobody).
        Returns the nanoseconds the programs made here cost."""
        clock = [0, 0]
        lanes = np.asarray(lanes, np.intp)
        made = np.zeros(len(seeds), bool)
        rows = hosts = k = 0
        with obs.span("sweep.fill", programs=len(seeds)) as sp:
            if stock is not None:
                # (a ``_Producers`` waits here for what is not made yet)
                slots, held = stock.take(len(seeds))
                k, hosts = len(slots), stock.took_hosts
                made[:k] = held
            with obs.span("sweep.stack"):
                if k:
                    src, dst = slots[held], lanes[:k][held]
                    # Seed order is row order, but for the ring's wrap:
                    # a run of rows is copied as the slice it is.
                    if len(src) and src[-1] - src[0] == len(src) - 1:
                        src = slice(int(src[0]), int(src[-1]) + 1)
                    for resident, ahead in zip(progs, stock.progs):
                        resident[dst] = ahead[src]
                    rows = int(stock.from_rows[src].sum())
                    stock.free(k)
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
            if obs.spans.folding():
                obs.stage_count("sweep.programs", len(seeds))
                obs.stage_count("sweep.prefetched", hosts)
                obs.stage_count("sweep.produced", int(made.sum()) - hosts)
            if obs.spans.live():
                obs.stage_count("sweep.row_lowered", rows)
                # What this fill lowered, by kind of external op: how
                # much of the fault plane the traffic engages.
                counts = count_op_arrays(progs.op[lanes], progs.a[lanes])
                for kind, n in counts.items():
                    obs.stage_count(f"sweep.ops.{kind}", n)
        return clock[0] + clock[1]

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
        segment runs, are in the module doc. However the call ends (its
        last seed, a consumer that stops early, an exception), the
        producer processes it forked are killed and reaped and their
        ring unmapped here."""
        try:
            yield from self._rounds(total_lanes, seeds)
        finally:
            producers, self._producing = self._producing, None
            if producers is not None:
                producers.close()

    def _rounds(
        self, total_lanes: int, seeds: Optional[Sequence[int]] = None
    ):
        """``_run_batches``' body."""
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

        def count_steps(scanned: int, live: int) -> None:
            nonlocal total_lane_steps, live_lane_steps
            total_lane_steps += scanned
            live_lane_steps += live
            self.last_occupancy = live_lane_steps / total_lane_steps
            self.last_total_lane_steps = total_lane_steps
            self.last_live_lane_steps = live_lane_steps
            obs.stage_count("sweep.lane_steps", scanned)
            obs.stage_count("sweep.live_lane_steps", live)

        def keys_for(seeds):
            return self._vkeys(jnp.asarray(seeds.astype(np.uint32)))

        n_live = min(b, total_lanes)
        seed_arr = np.asarray(seed_list, np.int64)
        if seed_arr.min() < 0 or seed_arr.max() >= 1 << 32:
            # (what the conversion of a list of them raised, at a refill)
            raise OverflowError("a lane's key is made of its seed as uint32")
        lag = self._lag()
        with obs.span("sweep.prime", lanes=b):
            # Lane i runs seed_list[i]; surplus (mesh-alignment) lanes run
            # the first seed inertly — never yielded, never refilled.
            lane_seed = np.full(b, seed_arr[0])
            lane_seed[:n_live] = seed_arr[:n_live]
            next_idx = n_live  # next position in seed_list to hand out
            # The resident set's programs: what every segment takes.
            # Under the lag there are two such sets, and a refill
            # writes the one no segment in flight was dispatched with
            # (module doc); ``stale`` are the rows that one lacks.
            progs = empty_programs(self.cfg, b)
            spare = empty_programs(self.cfg, b) if lag else None
            stale = slice(None)
            # The first few are made on the spot: what one costs is
            # half of whether the rest are worth producer processes.
            probe = min(n_live, _PROBE)
            ns_a_program = self._fill(
                seed_list[:probe], range(probe), progs
            ) / probe
            # Lowered programs of seed_list[probe:], made by producers
            # from here on, or of seed_list[next_idx:], made by the host
            # thread while a segment runs; this call's alone (module doc).
            obs.stage_count("sweep.producers", 0)
            stock = None
            if self._exposed or self._producers is not None:
                stock = self._start_producers(
                    seed_list, probe, b, ns_a_program
                )
            if probe < n_live:
                self._fill(
                    seed_list[probe:n_live], range(probe, n_live), progs,
                    stock,
                )
            if stock is None and self.seed_pure:
                stock = _Stock(self.cfg, b)
            # Nanoseconds this call's refills spent making programs the
            # stock had run out of, the device idle meanwhile: the other
            # half (module doc).
            exposed_ns = 0
            if n_live < b:
                self._fill(
                    seed_list[:1] * (b - n_live), range(n_live, b), progs
                )
            with obs.span("sweep.refill"):
                state = self.init(keys_for(lane_seed))
            steps_run = np.zeros(b, np.int64)
            done_count = 0
            active = np.arange(b) < n_live

            self.last_segment_seconds = 0.0
            self.last_harvest_seconds = 0.0
            self.last_lane_sharding = None
            self.last_unfinished_lanes = 0
            sample_pool = obs.spans.live()
            self.last_pool_peak = 0 if sample_pool else None
        seg_steps, max_steps = self.seg_steps, self.cfg.max_steps
        no_lane = np.zeros(b, bool)
        # Of the segment ``held``: the lanes the host knew spent when it
        # dispatched it, to be yielded at its harvest, and the lanes a
        # refill was queued behind (``held`` shows their predecessors).
        owed = renewed = no_lane
        # What a lagged round harvests: a segment's state (its finalize
        # behind it, no refill yet), its samples, and the steps and the
        # seeds its lanes had run. The first round's is the fresh state.
        held = (state, None, steps_run, lane_seed) if lag else None
        # The status the segment before left, for ``sweep.segments_queued``.
        landed = None
        while done_count < total_lanes:
            with obs.span("sweep.round"):
                # Lanes the host has not seen finish count as live; the
                # retire takes back those the lag's segment held frozen.
                # None is active where the lag's last segment spent
                # every lane left and no seed is: the round only harvests.
                n_active = int(active.sum())
                latest, spent = None, no_lane
                # What the host already knows is counted while spans
                # fold; what buys device work, only while they are live.
                counting = obs.spans.folding()
                t_seg = time.perf_counter()
                if n_active:
                    count_steps(b * seg_steps, n_active * seg_steps)
                    if counting:
                        # (no probe while nothing counts: a dispatch's cost)
                        obs.stage_count("sweep.segments")
                        obs.stage_count(
                            "sweep.segments_queued",
                            landed is not None and not _ready(landed),
                        )
                    ended = steps_run
                    with obs.span("sweep.block"):
                        state = self.segment(
                            state, progs, jnp.asarray(steps_run, jnp.int32)
                        )
                        landed = state.status
                        # A traced job's samples, dispatched behind the
                        # segment and pulled at its harvest: each lane's
                        # valid rows, the FIFO discipline's two counts,
                        # the app's progress counts.
                        samples = (
                            self._samples(state) if sample_pool else None
                        )
                    steps_run = np.minimum(ended + seg_steps, max_steps)
                    # Budget exhaustion (the plain kernel's
                    # run-out-of-steps semantics), queued behind the
                    # segment with no pull: which lanes spend their last
                    # step in it follows from their counts, and
                    # ``finalize`` is the identity on a lane that
                    # finished on its own.
                    spent = active & (ended < max_steps) & (
                        steps_run >= max_steps
                    )
                    if spent.any():
                        with obs.span("sweep.finalize"):
                            state = self.refill(
                                state, jnp.asarray(spent),
                                self.finalize(state),
                            )
                    latest = (state, samples, steps_run, lane_seed)
                t_gap = time.perf_counter()
                harvested, sampled, steps_ended, seeds_then = (
                    held if lag else latest
                )
                held = latest if lag else None
                if type(stock) is _Stock and exposed_ns >= _WORTH_S * 1e9:
                    # The making has cost what the forks will: fork,
                    # while the device runs the segment.
                    self._exposed = True
                    exposed_ns = float("-inf")      # asked once
                    stock = self._start_producers(
                        seed_list, next_idx, b, ns_a_program, stock
                    ) or stock
                if type(stock) is _Stock:
                    self._make_ahead(
                        harvested.status, seed_list, next_idx, n_active,
                        stock,
                    )
                t_pull = time.perf_counter()
                with obs.span("sweep.block"):
                    # The status pull is the sync point: the dispatch
                    # and the wait here are device-segment time; what
                    # was made in the gap, and the rest of the
                    # iteration, is harvest.
                    status = np.asarray(harvested.status)
                    if sampled:
                        occupancy, fifo_rows, progress = sampled
                        self.last_pool_peak = max(
                            self.last_pool_peak,
                            int(np.asarray(occupancy).max()),
                        )
                        if fifo_rows is not None:
                            pending, heads = np.asarray(fifo_rows).tolist()
                            obs.stage_count("sweep.fifo_pending_rows", pending)
                            obs.stage_count("sweep.fifo_head_rows", heads)
                t_harvest = time.perf_counter()
                if counting:
                    # The host thread inside ``segment(...)``, the
                    # samples' dispatch and the ``finalize`` behind it;
                    # and blocked at the status pull. The job row's
                    # alone: ``stage_counts()`` keeps no nanoseconds.
                    obs.spans.job_count(
                        "sweep.dispatch_ns", int((t_gap - t_seg) * 1e9)
                    )
                    obs.spans.job_count(
                        "sweep.wait_ns", int((t_harvest - t_pull) * 1e9)
                    )
                self.last_harvest_seconds += t_pull - t_gap
                if self.last_lane_sharding is None:
                    from ..parallel.mesh import lane_sharding_summary

                    self.last_lane_sharding = lane_sharding_summary(
                        harvested.status
                    )
                self.last_segment_seconds += (t_gap - t_seg) + (
                    t_harvest - t_pull
                )
                # Lanes first seen finished in this pull (under the lag:
                # they stopped on their own a segment ago and sat frozen
                # through the one in flight), and with them the lanes
                # the harvested segment was known to spend. A lane the
                # segment in flight spends by count that is seen
                # finished here is retired here, once.
                seen = active & ~renewed & (status >= ST_DONE)
                retiring = seen | owed
                spent = spent & ~seen
                out = None
                if retiring.any():
                    with obs.span("sweep.pull"):
                        vio = np.asarray(harvested.violation)
                        sh = np.asarray(harvested.sched_hash)
                        if obs.enabled():
                            # Round-granularity lane telemetry: the
                            # status pull above is the round's one sync
                            # point; deliveries ride the same harvest
                            # (never per segment step).
                            self._record_round_stats(harvested, retiring, vio)
                    with obs.span("sweep.retire"):
                        fin = np.flatnonzero(retiring)
                        # The seeds the harvested segment ran: a lane's
                        # refill may have been queued behind it since.
                        out = (seeds_then[fin], status[fin], vio[fin], sh[fin])
                        done_count += len(fin)
                        # (frozen through the segment in flight)
                        count_steps(0, -lag * int(seen.sum()) * seg_steps)
                        unfinished = int(
                            (out[1] == ST_UNFINISHED).sum()
                        )
                        self.last_unfinished_lanes += unfinished
                        if counting:
                            obs.stage_count("sweep.retired", len(fin))
                            # (of them, those the host knew spent: their
                            # lanes were free behind their last segment)
                            obs.stage_count(
                                "sweep.budget_retired", int(owed.sum())
                            )
                        if sampled:
                            # What the retired lanes put in their pools
                            # (every insert advances ``seq_counter`` by
                            # its rows) against the outbox rows their
                            # deliveries carried through the insert: a
                            # [B] pull each, beside the status pull.
                            # The batch-level counts advance in frozen
                            # lanes too: all are read from the state
                            # that finished the lane.
                            rows = np.asarray(harvested.seq_counter)[fin]
                            obs.stage_count("sweep.rows_inserted", rows.sum())
                            rows = np.asarray(harvested.deliveries)[fin]
                            obs.stage_count(
                                "sweep.outbox_rows",
                                int(rows.sum()) * self.cfg.max_outbox,
                            )
                            if harvested.insert_full_steps is not None:
                                # How often the insert's short pass is
                                # taken: of the steps the retired lanes
                                # were scanned, those in which some
                                # resident lane sent more rows than it
                                # holds, and those in which the lane
                                # itself went through the full pass
                                # (``core.insert_rows``).
                                for name, leaf in (
                                    ("insert_full_steps",
                                     harvested.insert_full_steps),
                                    ("insert_full_lane_steps",
                                     harvested.insert_full_lane_steps),
                                ):
                                    obs.stage_count(
                                        f"sweep.{name}",
                                        int(np.asarray(leaf)[fin].sum()),
                                    )
                                obs.stage_count(
                                    "sweep.insert_steps",
                                    int(steps_ended[fin].sum()),
                                )
                            if harvested.dups is not None:
                                # Datagram channels: what the network
                                # did to the retired lanes' messages, a
                                # [B] pull each (``deliveries`` counts a
                                # kept delivery, not a discarded one).
                                for name, leaf in (
                                    ("delivered", harvested.deliveries),
                                    ("kept", harvested.dups),
                                    ("discarded", harvested.drops),
                                ):
                                    obs.stage_count(
                                        f"sweep.net.{name}",
                                        int(np.asarray(leaf)[fin].sum()),
                                    )
                            if progress is not None:
                                # What the retired lanes' protocol got
                                # done: one [B, names] pull.
                                done = np.asarray(progress)[fin].sum(axis=0)
                                for (name, _fn), total in zip(
                                    self.app.progress, done.tolist()
                                ):
                                    obs.stage_count(
                                        f"sweep.app.{name}", total
                                    )
                            obs.stage_count(
                                "sweep.quiesced",
                                int((out[1] <= ST_VIOLATION).sum()),
                            )
                            obs.stage_count("sweep.unfinished", unfinished)
                # The harvest is over: what it read goes before the
                # refill builds a fresh state beside the one in flight.
                harvested = sampled = None
                # Lanes free for the next seeds, in seed order (the rest
                # are parked): those seen finished, and under the lag
                # those the segment in flight spends, whose verdicts the
                # next round reads from ``held``.
                free = seen | spent
                take = np.flatnonzero(free)[: max(0, total_lanes - next_idx)]
                active = active & ~free
                owed, renewed = spent, no_lane
                if len(take):
                    upto = next_idx + len(take)
                    if lag:
                        # The segment in flight took ``progs`` and reads
                        # the rows of the lanes it spends: the fill
                        # writes the other set, brought up to date by
                        # the rows the last refill gave this one, and
                        # the next segment takes that (module doc).
                        progs, spare = spare, progs
                        with obs.span("sweep.stack"):
                            for mine, theirs in zip(progs, spare):
                                mine[stale] = theirs[stale]
                        stale = take
                    spent_ns = self._fill(
                        seed_list[next_idx:upto], take, progs, stock
                    )
                    if type(stock) is _Stock:
                        exposed_ns += spent_ns
                    with obs.span("sweep.refill"):
                        mask = np.zeros(b, bool)
                        mask[take] = True
                        # (fresh arrays: ``held`` keeps the old ones)
                        lane_seed = lane_seed.copy()
                        lane_seed[take] = seed_arr[next_idx:upto]
                        steps_run = np.where(mask, 0, steps_run)
                        active[take] = True
                        state = self.refill(
                            state, jnp.asarray(mask),
                            self.init(keys_for(lane_seed)),
                        )
                    next_idx = upto
                    renewed = mask if lag else no_lane
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
