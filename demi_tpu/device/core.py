"""Device-tier core: tensor encoding of one schedule's full state, and the
shared transition machinery (external-op injection, message delivery, pool
maintenance).

This is the TPU-native replacement for the reference's per-message JVM
dispatch cycle (SURVEY.md §3.1 hot loop, Instrumenter.scala:913-1109): a
schedule's *entire* interposition state — actor states, the pending-message
pool, partitions, timers — lives in fixed-shape int32/bool arrays, and one
``step`` advances one schedule by one event. ``vmap(step)`` advances
thousands of candidate interleavings in lockstep; ``lax.scan`` drives the
step loop under jit.

Dynamic structures become capacity-bounded arrays + masks (SURVEY.md §7.3):
pool overflow surfaces as an explicit per-lane abort status, never silent
truncation.

Record encoding (shared by explore *output* traces and replay *input*
schedules): int32 rows ``(kind, a, b, msg[W])`` with
  kind 0            = none / padding
  kind 1            = message delivery   (a=src, b=dst)
  kind 2            = timer delivery     (a=b=dst)
  kind 5            = message delivered and kept pending (a=src, b=dst;
                      datagram channels only)
  kind 6            = message discarded undelivered      (a=src, b=dst;
                      datagram channels only)
  kind 10+op        = external op applied (a, b = op args)
Host-side lowering lives in demi_tpu/device/encoding.py.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..dsl import DSLApp
# External-op codes (device program encoding of ExternalEvents;
# closure-form WaitCondition and CodeBlock are host-tier-only — the
# cond_id WaitCondition form lowers to OP_WAITCOND). Defined beside the
# events they encode: the fuzzer records its programs in them.
from ..external_events import (  # noqa: F401
    OP_END,
    OP_HARDKILL,
    OP_KILL,
    OP_PARTITION,
    OP_SEND,
    OP_START,
    OP_UNPARTITION,
    OP_WAIT,
    OP_WAITCOND,
)
from . import ops

# Record kinds.
REC_NONE = 0
REC_DELIVERY = 1
REC_TIMER = 2
# Wildcard delivery (replay input only): a=dst, b=policy (0=first/FIFO,
# 1=last), msg[0]=class tag. Lowered from WildCardMatch expected events.
REC_WILDCARD = 4
# Datagram channels (``DSLApp.channels``): the network delivered the
# message and kept it pending, or lost it before any handler saw it.
REC_KEPT = 5
REC_DISCARDED = 6
REC_EXT_BASE = 10  # REC_EXT_BASE + op

# What a kept and a discarded delivery add to ``sched_hash``'s mix.
HASH_KEPT = 0x27D4EB2F
HASH_DISCARDED = 0x165667B1

# Lane status.
ST_DISPATCH = 0
ST_INJECT = 1
ST_DONE = 2
ST_VIOLATION = 3
ST_OVERFLOW = 4
# Under an invariant judged at quiescence only (``DSLApp.invariant_at``):
# the lane ran out of steps before its program ended in quiescence. It has
# no verdict (violation stays 0) and is counted, like an overflowed lane.
ST_UNFINISHED = 5


@dataclasses.dataclass(frozen=True)
class DeviceConfig:
    """Static shapes/capacities for the device kernels."""

    num_actors: int
    state_width: int
    msg_width: int
    max_outbox: int
    pool_capacity: int = 256
    max_external_ops: int = 64
    max_steps: int = 512
    invariant_interval: int = 0  # 0 = only at completion
    record_trace: bool = False
    # Track causal parents in trace records (device DPOR): each delivery
    # record carries the trace index of the delivery/injection that created
    # its message. Requires record_trace.
    record_parents: bool = False
    # Probability weight of picking a pending timer vs a message (host
    # counterpart: FullyRandom.timer_weight). 1.0 = uniform over all.
    timer_weight: float = 1.0
    # Early exit: drive the step loop with lax.while_loop instead of a
    # fixed-length scan, so wall-clock tracks the slowest LIVE lane in the
    # batch rather than max_steps. ~10x on workloads whose lanes finish
    # well under the cap (short minimization candidates, early-quiescing
    # sweeps); ~9% loop overhead when every lane runs the full budget —
    # hence opt-in.
    early_exit: bool = False
    # Dynamic-index strategy for the kernels (see device/ops.py): 'auto'
    # uses one-hot compare+where on TPU (vmapped scatters serialize there)
    # and native gathers/scatters elsewhere; 'onehot'/'scatter' force.
    index_mode: str = "auto"
    # SrcDstFIFO randomization (reference: RandomScheduler.scala:702-909,
    # host twin schedulers/random.py SrcDstFIFO): per-(src,dst) channels
    # are TCP-ordered — only each channel's FIFO head is a delivery
    # candidate; timers stay individually choosable. The head bits are
    # kept as the pool changes (``track_fifo_heads``). An app whose
    # channels are "fifo" (``DSLApp.channels``) gets it from
    # ``for_workload``; no verb has a flag for it.
    srcdst_fifo: bool = False
    # Batched-replay peek (device twin of STSScheduler.allow_peek /
    # IntervalPeekScheduler): when an expected delivery has no pending
    # match, deliver up to this many pending entries FIFO trying to
    # ENABLE it, keeping the prefix on success and rolling the lane back
    # wholesale on failure. 0 = ignore-absent only. Costs a second
    # in-flight state copy per lane while replaying, so opt-in.
    replay_peek: int = 0
    # Synchronous-round dispatch (device-only exploration mode, no host
    # counterpart): each dispatch step selects ONE uniformly-random
    # deliverable entry PER RECEIVER and delivers them all, with effects
    # computed sequential-equivalently to the ascending-receiver-id
    # linearization (deliveries at distinct receivers commute in this
    # actor model — a handler reads/writes only its own state row). Cuts
    # step count for flood workloads (BASELINE config 5) by up to
    # num_actors x; per-receiver delivery ORDER stays fully randomized,
    # which is what the reachable state space depends on. Segment
    # conditions/invariant intervals are evaluated at round (not
    # delivery) granularity; recorded traces are the canonical
    # linearization and replay sequentially (tests/test_rounds.py pins
    # ignored_absent == 0 through the replay kernel).
    round_delivery: bool = False
    # Trace-row capacity when record_trace is on (None = max_steps). The
    # sequential kernels append at most one record per step, so max_steps
    # rows always suffice; round_delivery appends up to num_actors records
    # per step — size this to the expected delivery total there.
    trace_capacity: Optional[int] = None
    # Message-payload storage dtype for the pool/timer-memory columns
    # ('int32' or 'int16'). The [P, W] pool_msg array dominates the
    # per-lane carry, so halving it halves the HBM traffic of the XLA
    # step loop. Handlers always see int32 (cast at the boundary);
    # requires every app message field to fit the narrow range — the
    # app's contract, unchecked on device.
    msg_dtype: str = "int32"
    # Testing-only escape hatch: force the O(P^2) head recompute even in
    # sequential srcdst_fifo kernels (parity pin for the incremental
    # maintenance; tests/test_device_srcdst.py).
    head_recompute: bool = False
    # Datagram channels (``DSLApp.channels``; ``for_workload`` derives it,
    # no verb has a flag): every dispatch step draws an outcome beside
    # the index. With probability ``dup_weight`` the delivered message
    # stays pending (while the lane has kept fewer than ``max_dups``),
    # with the next ``drop_weight`` it is consumed and reaches no handler
    # (fewer than ``max_drops``): the shared workload flags
    # ``--dup-weight``, ``--drop-weight``, ``--max-dups``, ``--max-drops``.
    # Only an actor's message: timers and external sends are delivered
    # exactly once. A lane then carries ``ScheduleState.dups`` / ``drops``;
    # any other kernel is the program it always was.
    datagram: bool = False
    dup_weight: float = 0.0
    drop_weight: float = 0.0
    max_dups: int = 0
    max_drops: int = 0

    def __post_init__(self):
        if self.index_mode not in ("auto", "onehot", "scatter"):
            raise ValueError(
                f"index_mode must be 'auto', 'onehot' or 'scatter', "
                f"got {self.index_mode!r}"
            )
        if self.msg_dtype not in ("int32", "int16"):
            raise ValueError(
                f"msg_dtype must be 'int32' or 'int16', got {self.msg_dtype!r}"
            )
        if not self.datagram and (self.dup_weight or self.drop_weight):
            raise ValueError(
                "dup_weight and drop_weight are for an app whose channels "
                "are 'datagram' (DSLApp.channels): this network delivers a "
                "message at most once and loses none on its own"
            )
        if self.datagram and (self.round_delivery or self.srcdst_fifo):
            raise ValueError(datagram_refusal(
                "round_delivery" if self.round_delivery else "srcdst_fifo"
            ))
        if self.round_delivery and self.record_trace and not self.trace_capacity:
            # Round mode appends up to num_actors records per step; the
            # max_steps fallback that suits the sequential kernels would
            # silently truncate the lift (runtime overflow flags lanes,
            # but an undersized default is a config error — fail fast).
            raise ValueError(
                "round_delivery with record_trace requires an explicit "
                "trace_capacity (expected total deliveries + externals)"
            )

    @property
    def msg_jnp_dtype(self):
        return jnp.int16 if self.msg_dtype == "int16" else jnp.int32

    @property
    def use_onehot(self) -> bool:
        if self.index_mode == "auto":
            return jax.default_backend() == "tpu"
        return self.index_mode == "onehot"

    @property
    def track_fifo_heads(self) -> bool:
        """Incremental per-channel FIFO-head maintenance: srcdst_fifo's
        head test drops from an O(P^2) same-channel compare per step to
        O(K*P) at insert + O(P) at consume. The round kernel recomputes
        per ROUND instead (amortized over up to N deliveries), so only
        the sequential kernels carry the extra state."""
        return self.srcdst_fifo and not self.round_delivery and not (
            self.head_recompute
        )

    @property
    def trace_rows(self) -> int:
        return self.trace_capacity if self.trace_capacity else self.max_steps

    @property
    def rec_width(self) -> int:
        # record_parents appends TWO happens-before columns: `parent`
        # (trace index of the record that created this message — the
        # creation edge) and `prev` (trace index of the previous delivery
        # at the same receiver — the program-order edge). Both -1 if none.
        return 3 + self.msg_width + (2 if self.record_parents else 0)

    @staticmethod
    def for_app(app: DSLApp, **overrides) -> "DeviceConfig":
        defaults = dict(
            num_actors=app.num_actors,
            state_width=app.state_width,
            msg_width=app.msg_width,
            max_outbox=app.max_outbox,
        )
        defaults.update(overrides)
        return DeviceConfig(**defaults)

    @staticmethod
    def for_workload(app: DSLApp, args, **overrides) -> "DeviceConfig":
        """The verbs' one ``DeviceConfig``: shapes from the app,
        capacities from the shared workload flags (``--pool``,
        ``--max-messages``, ``--num-events``, ``--timer-weight``; ``args``
        is the CLI's namespace or one from ``distributed.workload_args``),
        and from the app what is no verb's to choose: when the invariant
        is judged (``DSLApp.invariant_at``) and the order its channels
        keep (``DSLApp.channels``; an app that says "any" builds exactly
        what it always did; one that says "datagram" takes
        ``--dup-weight``, ``--drop-weight``, ``--max-dups`` and
        ``--max-drops`` too, which any other app refuses)."""
        defaults = dict(
            pool_capacity=getattr(args, "pool", None) or 256,
            max_steps=args.max_messages,
            max_external_ops=max(16, args.num_events + app.num_actors + 2),
            invariant_interval=app.invariant_interval,
            timer_weight=args.timer_weight,
        )
        if app.channels == "fifo":
            defaults["srcdst_fifo"] = True
        if app.channels == "datagram":
            defaults.update(datagram=True, **datagram_knobs(args))
        elif datagram_weight_given(args):
            raise ValueError(datagram_flags_refusal(app))
        defaults.update(overrides)
        return DeviceConfig.for_app(app, **defaults)


#: The workload keys of the datagram discipline (``DeviceConfig`` fields
#: and CLI flags of the same names).
DATAGRAM_KNOBS = ("dup_weight", "drop_weight", "max_dups", "max_drops")


def datagram_knobs(args) -> dict:
    """The four from a workload namespace (0 where it has none): what
    ``for_workload`` hands the kernel and the CLI the host scheduler."""
    return {key: getattr(args, key, 0) or 0 for key in DATAGRAM_KNOBS}


def datagram_weight_given(args) -> bool:
    """Whether a workload asks for kept or lost messages at all."""
    knobs = datagram_knobs(args)
    return bool(knobs["dup_weight"] or knobs["drop_weight"])


def datagram_refusal(who: str) -> str:
    """Why ``who`` turns away an app whose channels are datagram."""
    return (
        f"{who} does not take an app whose channels are datagram "
        "(DSLApp.channels): a delivery there may leave its message pending "
        "and a pending message may vanish, and it knows a delivery only as "
        "the one consumption of its message; sweep or fuzz it instead"
    )


def datagram_flags_refusal(app: DSLApp) -> str:
    return (
        f"--dup-weight and --drop-weight are for an app whose channels are "
        f"'datagram'; this app's are {app.channels!r} (DSLApp.channels): "
        "its network delivers a message at most once and loses none on its "
        "own"
    )


class ScheduleState(NamedTuple):
    """Complete state of one schedule (one lane). All arrays, fixed shapes."""

    actor_state: jnp.ndarray  # [N, S] int32
    started: jnp.ndarray  # [N] bool
    isolated: jnp.ndarray  # [N] bool (Kill = isolation)
    stopped: jnp.ndarray  # [N] bool (HardKill)
    cut: jnp.ndarray  # [N, N] bool, symmetric partition matrix
    # Pending pool.
    pool_valid: jnp.ndarray  # [P] bool
    pool_src: jnp.ndarray  # [P] int32 (num_actors = EXTERNAL)
    pool_dst: jnp.ndarray  # [P] int32
    pool_timer: jnp.ndarray  # [P] bool
    pool_parked: jnp.ndarray  # [P] bool (timer loop-avoidance)
    pool_msg: jnp.ndarray  # [P, W] int32
    pool_seq: jnp.ndarray  # [P] int32 arrival order (FIFO matching)
    pool_crec: jnp.ndarray  # [P] int32 trace index of the creating event (-1 none)
    # Per-channel FIFO-head bits ([0] unless cfg.track_fifo_heads):
    # True iff this entry is its (src,dst) channel's earliest-arrival
    # valid non-timer entry. Maintained incrementally by
    # insert_rows/delivery_effects/purges.
    pool_head: jnp.ndarray  # [P] bool (or [0])
    # Timer-parking memory (host: justScheduledTimers keyed (rcv, fp);
    # device: one remembered timer per actor).
    timer_mem: jnp.ndarray  # [N, W] int32
    timer_mem_valid: jnp.ndarray  # [N] bool
    # Per-actor trace index of the last delivery processed by that actor
    # (-1 none): the program-order HB link recorded alongside pool_crec's
    # creation link when record_parents is on.
    last_rec: jnp.ndarray  # [N] int32
    # Program + bookkeeping.
    ext_cursor: jnp.ndarray  # int32: next external op
    seq_counter: jnp.ndarray  # int32
    deliveries: jnp.ndarray  # int32
    # Bounded-quiescence segment tracking (WaitQuiescence budgets):
    seg_budget: jnp.ndarray  # int32, 0 = unlimited
    seg_start: jnp.ndarray  # int32: deliveries when the segment began
    final_seg: jnp.ndarray  # bool: this dispatch segment is the program's last
    # Condition id gating this dispatch segment (-1 = plain quiescence
    # wait): the WaitCondition twin — the segment also ends once
    # app.conditions[seg_cond](states, alive) holds.
    seg_cond: jnp.ndarray  # int32
    status: jnp.ndarray  # int32 (ST_*)
    violation: jnp.ndarray  # int32 fingerprint (0 = none)
    # Rolling FNV-style fold of every delivered (src, dst, timer?, payload):
    # two lanes share sched_hash iff they delivered the same sequence (modulo
    # 32-bit collisions), making "unique schedules explored" measurable
    # without trace recording (BASELINE.json metric name).
    sched_hash: jnp.ndarray  # uint32
    rng: jnp.ndarray  # PRNG key
    # Optional trace recording.
    trace: jnp.ndarray  # [T, rec_width] int32 (or [0,0] when disabled)
    trace_len: jnp.ndarray  # int32
    # Inserts of this lane that took the full [K, P] pass (insert_rows:
    # one count a step in the step kernels, the same in every lane that
    # was resident). No leaf at all (None) where the short pass is not
    # built (``_short_insert_built``): the smaller shapes' compiled
    # programs are what they were.
    insert_full_steps: Optional[jnp.ndarray] = None  # int32
    # Those of them in which THIS lane's rows went through the full pass:
    # it sent more than the short pass holds (alone behind the batch's
    # short pass, or with the whole batch once more than
    # ``INSERT_BURST_LANES`` lanes burst together). None where the count
    # above is.
    insert_full_lane_steps: Optional[jnp.ndarray] = None  # int32
    # Datagram channels: deliveries of this lane that left their message
    # pending, and messages it lost undelivered (the budgets ``max_dups``
    # and ``max_drops`` are held against them). No leaves (None) for any
    # other kernel.
    dups: Optional[jnp.ndarray] = None  # int32
    drops: Optional[jnp.ndarray] = None  # int32


def init_state(app: DSLApp, cfg: DeviceConfig, key) -> ScheduleState:
    n, s, w, p = cfg.num_actors, cfg.state_width, cfg.msg_width, cfg.pool_capacity
    init_states = np.stack(
        [np.asarray(app.init_state(i), np.int32) for i in range(n)]
    )
    trace_shape = (cfg.trace_rows, cfg.rec_width) if cfg.record_trace else (0, 0)
    insert_count = jnp.int32(0) if _short_insert_built(cfg) else None
    return ScheduleState(
        actor_state=jnp.asarray(init_states),
        started=jnp.zeros(n, bool),
        isolated=jnp.zeros(n, bool),
        stopped=jnp.zeros(n, bool),
        cut=jnp.zeros((n, n), bool),
        pool_valid=jnp.zeros(p, bool),
        pool_src=jnp.zeros(p, jnp.int32),
        pool_dst=jnp.zeros(p, jnp.int32),
        pool_timer=jnp.zeros(p, bool),
        pool_parked=jnp.zeros(p, bool),
        pool_msg=jnp.zeros((p, w), cfg.msg_jnp_dtype),
        pool_seq=jnp.zeros(p, jnp.int32),
        pool_crec=jnp.full(p, -1, jnp.int32),
        pool_head=jnp.zeros(p if cfg.track_fifo_heads else 0, bool),
        timer_mem=jnp.zeros((n, w), cfg.msg_jnp_dtype),
        timer_mem_valid=jnp.zeros(n, bool),
        last_rec=jnp.full(n, -1, jnp.int32),
        ext_cursor=jnp.int32(0),
        seq_counter=jnp.int32(0),
        deliveries=jnp.int32(0),
        seg_budget=jnp.int32(0),
        seg_start=jnp.int32(0),
        final_seg=jnp.bool_(False),
        seg_cond=jnp.int32(-1),
        status=jnp.int32(ST_INJECT),
        violation=jnp.int32(0),
        sched_hash=jnp.uint32(0x811C9DC5),  # FNV-1a offset basis
        rng=key,
        trace=jnp.zeros(trace_shape, jnp.int32),
        trace_len=jnp.int32(0),
        insert_full_steps=insert_count,
        insert_full_lane_steps=insert_count,
        dups=jnp.int32(0) if cfg.datagram else None,
        drops=jnp.int32(0) if cfg.datagram else None,
    )


# ---------------------------------------------------------------------------
# Masks
# ---------------------------------------------------------------------------

def deliverable_mask(state: ScheduleState, cfg: DeviceConfig) -> jnp.ndarray:
    """Which pool entries could be delivered right now. Mirrors the host
    ControlledActorSystem.deliverable predicate exactly.

    The ``cut`` matrix is not read here: a cut link drops its messages
    (``external_effects`` scrubs what is pending on it at the Partition,
    ``insert_rows`` masks what is sent over it while it is cut), so no
    pool entry ever crosses one. A stopped node's mail goes the same way
    (scrubbed at the HardKill, masked while it is down); only an external
    send can wait in the pool for one. Isolation (soft ``Kill``) holds."""
    n = cfg.num_actors
    dst = state.pool_dst
    src = state.pool_src
    src_is_external = src >= n
    src_clamped = jnp.minimum(src, n - 1)
    # Three booleans an actor fold to the two tables read here: "the
    # receiver is alive" at dst, "the sender is isolated" at src. The
    # one-hot path packs each into 32-bit words and takes an entry's bit
    # from its word (ops.packed_gather_bool): ceil(N/32) selects over [P]
    # where a one-hot compare is [P, N].
    if cfg.use_onehot:
        dst_alive = ops.packed_gather_bool(alive_mask(state), dst)
        src_isolated = ops.packed_gather_bool(state.isolated, src_clamped)
    else:
        dst_alive = alive_mask(state)[dst]
        src_isolated = state.isolated[src_clamped]
    # timers/externals only need the receiver alive; internal messages
    # also need an un-isolated sender.
    passes_network = jnp.where(
        state.pool_timer | src_is_external, True, ~src_isolated
    )
    return state.pool_valid & ~state.pool_parked & dst_alive & passes_network


def fifo_head_mask(state: ScheduleState, cfg: "DeviceConfig") -> jnp.ndarray:
    """Entries that are their (src,dst) channel's FIFO head (earliest
    arrival seq among valid non-timer entries of the same pair). Timers are
    not channelized and pass through unconditionally.

    With cfg.track_fifo_heads the bits are maintained incrementally
    (insert_rows/delivery_effects/purges) and this is O(P); otherwise
    (round kernel, parity pin) the O(P^2) same-channel recompute runs."""
    if cfg.track_fifo_heads:
        return state.pool_timer | state.pool_head
    return state.pool_timer | recompute_fifo_heads(state)


def recompute_fifo_heads(state: ScheduleState) -> jnp.ndarray:
    """[P] bool: non-timer channel heads, recomputed from scratch."""
    chan = state.pool_valid & ~state.pool_timer
    same_pair = (
        (state.pool_src[:, None] == state.pool_src[None, :])
        & (state.pool_dst[:, None] == state.pool_dst[None, :])
        & chan[:, None]
        & chan[None, :]
    )
    earlier = same_pair & (state.pool_seq[None, :] < state.pool_seq[:, None])
    return chan & ~jnp.any(earlier, axis=1)


def alive_mask(state: ScheduleState) -> jnp.ndarray:
    """Actors the invariant should consider (started, not isolated/stopped;
    host: checkpoint replies None for crashed/isolated actors)."""
    return state.started & ~state.isolated & ~state.stopped


# ---------------------------------------------------------------------------
# Pool maintenance
# ---------------------------------------------------------------------------

# The one-hot insert's short pass (PR 37). A slot reads its row through
# the first INSERT_SHORT_ROWS valid rows wherever no lane of the batch
# inserts more in this step; it is built only where the insert carries
# more than INSERT_SHORT_FACTOR times as many rows, so the raft shapes (K
# under 10) keep the program they had. Measured on the v5e at the spark
# cell's shape (K 402, P 1,024, 256 lanes) and the flood's (K 65, P 4,608,
# 128 lanes), where a step sends one row or a whole outbox, so that 1 to
# 16 rows spare the same steps: 4 read 1 to 3% faster than 8 there
# (PERF.md, PR 37); 8 serves a protocol whose usual outbox is a handful
# of rows.
#
# A lane that inserts more is a burst (a paxos adoption's 160 P2A rows, a
# spark stage launch, a chain repair): about one lane of the resident set
# in such a step, and since PR 45 only the bursting lanes go through the
# full [K, P] pass, one at a time behind the batch's short pass, while at
# most INSERT_BURST_LANES of them burst in one step; past that the whole
# batch takes the full pass as before (a refill wave, the flood's lanes
# that start their floods together). Measured on the v5e, the insert's
# passes alone, microseconds a step (PERF.md, PR 45): at the paxos
# cell's [167, 1024] x 69 columns and 256 lanes the batched full pass
# 2,054 and a trip of the loop 22.7 (the loop is the cheaper up to 68
# bursting lanes); spark's [402, 1024] x 4, 256 lanes: 295 and 4.2 (55);
# chain's [67, 256] x 4, 2,048 lanes: 295 and 2.7 (83); the flood's
# [65, 4608] x 3, 128 lanes: 113 and 4.6 (13). No rule on the batch size
# fits the four (13 of 128, 68 of 256, 83 of 2,048), so one constant
# under the lowest: a step with 9 to 13 bursting lanes is rarer than one
# in a million where a lane bursts in one step of 300, and a wave is
# hundreds. In the paxos cell 8 and 256 read the same (77.17 and 76.43
# schedules/s; the batched pass for all: 49.69).
INSERT_SHORT_ROWS = 8
INSERT_SHORT_FACTOR = 4
INSERT_BURST_LANES = 8


def _short_insert_built(cfg: DeviceConfig) -> bool:
    """Whether a lane of ``cfg`` carries ``insert_full_steps`` and
    ``insert_full_lane_steps``: the step's insert holds an outbox and at
    least one injected row."""
    return cfg.use_onehot and (
        cfg.max_outbox + 1 > INSERT_SHORT_FACTOR * INSERT_SHORT_ROWS
    )


def insert_form(cfg: DeviceConfig) -> str:
    """The form of pool insert a kernel of ``cfg`` is built with: what a
    ``setup.build`` stage says of it."""
    if not cfg.use_onehot:
        return "scatter"
    return "short" if _short_insert_built(cfg) else "rows"


def table_reads(app: DSLApp, cfg: DeviceConfig) -> dict:
    """The form each of the fused step's two one-hot table reads takes in
    a kernel of ``cfg`` (``ops.table_read_form`` at the shapes the step
    reads at: timer parking's ``timer_mem`` rows for an outbox, the
    insert's ``cut`` bits for the outbox and the injected rows): what a
    ``setup.build`` stage says of them."""
    if not cfg.use_onehot:
        return {"gather_rows": "scatter", "gather_mat": "scatter"}
    n, k = cfg.num_actors, cfg.max_outbox
    injected = 1
    if app.initial_msgs is not None:
        injected += max(np.shape(app.initial_msgs(i))[0] for i in range(n))
    return {
        "gather_rows": ops.table_read_form(k, n, cfg.msg_width),
        "gather_mat": ops.table_read_form(k + injected, n, n),
    }


def _sum_where(sel, col):
    """[K, P] bool, [K] -> [P]: the value of the one row a slot selects."""
    return jnp.sum(jnp.where(sel, col[:, None], 0), axis=0)


def _landed_rows(hit, want, prefix, row_msg, pool_msg):
    """``pool_msg`` with each hit slot's row in it: row by row, the slots
    of that row's rank take its W words whole ([P, W] selects over the
    live pool, which fuse into one pass over it: no sum, no stack and no
    merge after). An occupied slot shares its ``prefix`` with the free
    slot before it, hence ``hit``; an invalid row's ``want`` is -1 and
    matches nothing. Ranked on the v5e against a select-and-sum a payload
    word through the shared [K, P] compare, stacked and merged (what PR 32
    shaped at raft's W = 7: a batch carries ``pool_msg`` with W major, so
    each ``row_msg[:, j]`` is a copy of its own out of the W-minor
    proposal: at W = 37 over half of the kernel), the same stacked on
    axis 0 and transposed, one [K, P, W] select-and-sum, and a 0/1 table
    times the rows' bytes on the MXU: first at W = 37, and 7% under the
    columns' kernel at W = 7 (PERF.md, PR 39). The short pass keeps its
    columns (K there is an outbox of 65 or 402 rows, W 2 or 3)."""
    for r in range(row_msg.shape[0]):
        pool_msg = jnp.where(
            (hit & (prefix == want[r]))[:, None], row_msg[r][None, :],
            pool_msg,
        )
    return pool_msg


def _landed_full(want, prefix, cols):
    """``cols`` ([K] each) -> what each slot gets ([P] each): the row whose
    rank among the valid rows (``want``; -1 on an invalid row, which no
    slot matches, as a valid one past the last free slot matches none)
    is the slot's among the free slots (``prefix``). One select-and-sum a
    column over the shared compare: XLA fuses them all into one pass with
    K outermost and writes nothing of [K, P] out (ranked on the v5e
    against an einsum over a [K, C] table, which writes the compare out
    first, and a variadic reduce: PERF.md, PR 32)."""
    sel = want[:, None] == prefix[None, :]
    return tuple(_sum_where(sel, col) for col in cols)


def _landed_short(want, prefix, cols):
    """``_landed_full``'s values wherever at most INSERT_SHORT_ROWS rows
    are valid (anywhere in K): rank by rank, the row of rank r reduced
    to a scalar a column (a [K] compare), then handed to the slot of rank
    r (a [P] one): C x (K + P) compares where the full pass makes K x P.
    Unrolled, so that a batch of lanes reduces [B, K] over its minor axis
    and selects over [B, P] (ranked on the v5e against a [C, K] and a
    [C, P] compare, each summed over an axis, whose operands the compiler
    first transposes to lane-minor; the same with the columns stacked;
    and a 0/1 table times the columns' bytes on the MXU: PERF.md, PR 37)."""
    # ``lax`` primitives, the compares hoisted out of the columns: the
    # same selects and sums as ``jnp.where`` / ``jnp.sum`` give (XLA sees
    # one program), traced at a fraction of the cost: 8 ranks x 69 columns
    # x 3 ``jnp`` calls are each a jitted wrapper's Python path, and the
    # batch rule traces this pass in two regions of its ``case``: the
    # second copy cost the paxos cell 11 s of set-up on the chip's host
    # (PERF.md, PR 45).
    ranks = range(1, INSERT_SHORT_ROWS + 1)
    at_row = [want == rank for rank in ranks]
    at_slot = [prefix == rank for rank in ranks]
    no_row, nothing = jnp.zeros_like(want), jnp.zeros_like(prefix)
    out = []
    for col in cols:
        landed = nothing
        for row_is, slot_is in zip(at_row, at_slot):
            row = jax.lax.reduce(
                jax.lax.select(row_is, col, no_row), np.int32(0),
                jax.lax.add, (0,),
            )
            landed = jax.lax.select(
                slot_is, jax.lax.broadcast(row, prefix.shape), landed
            )
        out.append(landed)
    return tuple(out)


def _landed_burst_lanes(want, prefix, burst, cols):
    """A batch whose lanes ``burst`` ([B] bool) insert more rows than the
    short pass holds: the short pass for every lane, then the bursting
    lanes one at a time through ``_landed_full`` (a loop of as many trips
    as lanes burst: a lane's [K] and [P] rows taken by a dynamic slice on
    the batch axis, a [K, P] compare with no batch beside it, its [P]
    results written over the lane's rows of the landed columns, in place
    in the loop's carry). Ranked on the v5e against a fixed sub-batch of
    C lanes gathered the same way, one ``vmap(_landed_full)`` over
    [C, K, P], written back, which pays C lanes' work where one bursts:
    paxos cell 77.17 schedules/s against 69.13 (C = 2) and 72.29 (C = 4),
    spark cell 401.4 against 384.6 (C = 2), the flood's 165.6 against
    164.3 (PERF.md, PR 45)."""
    landed = jax.vmap(_landed_short)(want, prefix, cols)
    lanes = jnp.arange(burst.shape[0])

    def trip(_, carry):
        left, landed = carry
        lane = jnp.argmax(left)

        def take(x):
            return jax.lax.dynamic_index_in_dim(x, lane, 0, keepdims=False)

        one = _landed_full(
            take(want), take(prefix), tuple(take(col) for col in cols)
        )
        landed = tuple(
            jax.lax.dynamic_update_index_in_dim(whole, row, lane, 0)
            for whole, row in zip(landed, one)
        )
        return left & (lanes != lane), landed

    n_burst = jnp.sum(burst.astype(jnp.int32))
    return jax.lax.fori_loop(0, n_burst, trip, (burst, landed))[1]


@jax.custom_batching.custom_vmap
def _landed_by_batch(want, prefix, n_rows, cols):
    """``(_landed_full(...), 1, 1)``: one lane alone takes the full pass.
    A batch of lanes (the rule below) takes the short one in every step
    where none of them inserts more than it holds; where up to
    ``INSERT_BURST_LANES`` of them do, the short one and then the full
    one for those lanes alone; past that the full one for all. It says
    whether the batch left the short pass, and which lanes took the full
    one. Under ``vmap`` a ``lax.cond`` on a lane's own count would run
    both branches; this index is one scalar for the whole batch, so the
    compiled step holds a real ``case``: the one place a vmapped kernel
    branches. (What the chip said of the limit: the comment over
    ``INSERT_SHORT_ROWS``; of the loop against a fixed sub-batch:
    ``_landed_burst_lanes``.)"""
    return _landed_full(want, prefix, cols), jnp.int32(1), jnp.int32(1)


@_landed_by_batch.def_vmap
def _landed_by_batch_rule(axis_size, in_batched, *args):
    want, prefix, n_rows, cols = jax.tree_util.tree_map(
        lambda x, batched: (
            x if batched else jnp.broadcast_to(x, (axis_size,) + x.shape)
        ),
        args, tuple(in_batched),
    )
    burst = n_rows > INSERT_SHORT_ROWS
    n_burst = jnp.sum(burst.astype(jnp.int32))
    some = n_burst > 0
    many = n_burst > INSERT_BURST_LANES

    def every_lane(landed):
        return lambda want, prefix, burst, cols: jax.vmap(landed)(
            want, prefix, cols
        )

    landed = jax.lax.switch(
        some.astype(jnp.int32) + many.astype(jnp.int32),
        (
            every_lane(_landed_short), _landed_burst_lanes,
            every_lane(_landed_full),
        ),
        want, prefix, burst, cols,
    )
    took = jnp.broadcast_to(some.astype(jnp.int32), (axis_size,))
    took_lane = (burst | many).astype(jnp.int32)
    return (landed, took, took_lane), ((True,) * len(cols), True, True)


def insert_rows(
    state: ScheduleState,
    cfg: DeviceConfig,
    row_valid: jnp.ndarray,  # [K] bool
    row_src: jnp.ndarray,  # [K] int32
    row_dst: jnp.ndarray,  # [K] int32
    row_timer: jnp.ndarray,  # [K] bool
    row_parked: jnp.ndarray,  # [K] bool
    row_msg: jnp.ndarray,  # [K, W] int32
    crec=None,  # int32 trace index of the creating event: scalar or [K]
) -> ScheduleState:
    """Scatter up to K new entries into free pool slots. Overflow (more valid
    rows than free slots) flips the lane status to ST_OVERFLOW."""
    # A cut link and a stopped node drop at the send: an actor's message
    # to a peer it is partitioned from, or to a hard-killed one, never
    # enters the pool (timers are self-sends; an external send crosses no
    # link and waits for its node). Host twin:
    # ControlledActorSystem._capture_send.
    n = cfg.num_actors
    lost = ops.gather_mat(
        state.cut, jnp.minimum(row_src, n - 1), row_dst, cfg.use_onehot
    ) | ops.gather_vec(state.stopped, row_dst, cfg.use_onehot)
    row_valid = row_valid & ~(lost & (row_src < n) & ~row_timer)
    # Proposals carry int32 payloads; storage may be narrower (msg_dtype).
    row_msg = row_msg.astype(state.pool_msg.dtype)
    free = ~state.pool_valid
    # rank among free slots: 1-indexed prefix count
    prefix = ops.prefix_sum(free.astype(jnp.int32), cfg.use_onehot)
    want = ops.prefix_sum(
        row_valid.astype(jnp.int32), cfg.use_onehot
    )  # i-th valid row wants want[i]-th free slot
    # Totals as reductions, not prefix[-1]/want[-1] reads (bit-identical
    # either way).
    n_free = jnp.sum(free.astype(jnp.int32))
    n_rows = jnp.sum(row_valid.astype(jnp.int32))
    overflow = n_rows > n_free  # the last valid row wants a slot past the end
    ok = row_valid & (want <= n_free)

    k = row_valid.shape[0]
    if cfg.track_fifo_heads:
        # A new row heads its channel iff the pool holds no valid
        # non-timer same-channel entry and no EARLIER row of this batch
        # opens the channel first (batch order = arrival order).
        chan_pool = state.pool_valid & ~state.pool_timer
        exists_pool = jnp.any(
            (row_src[:, None] == state.pool_src[None, :])
            & (row_dst[:, None] == state.pool_dst[None, :])
            & chan_pool[None, :],
            axis=1,
        )
        kidx = jnp.arange(k)
        prior_batch = jnp.any(
            (row_src[:, None] == row_src[None, :])
            & (row_dst[:, None] == row_dst[None, :])
            & (kidx[None, :] < kidx[:, None])
            & (ok & ~row_timer)[None, :],
            axis=1,
        )
        row_head = ok & ~row_timer & ~exists_pool & ~prior_batch
    if cfg.use_onehot:
        # Decided from the slot's side: the i-th valid row lands in the
        # i-th free slot, so a free slot of rank r receives a row iff
        # r <= n_rows, and that row is the one with want == r. What a slot
        # knows from its own rank (whether it is hit, its arrival seq, a
        # scalar creator link) is O(P); what it must read from its row is
        # ONE [K, P] compare, contracted against a few [K] columns: a word
        # packing src, dst and the row's bits (src is at most n, the
        # external sender; dst is an actor id: every caller clips) and the
        # creator links where they are per row; the W payload words land
        # as whole rows (``_landed_rows``; a [K] column each on the short
        # pass).
        hit = free & (prefix <= n_rows)
        bits = n.bit_length()
        flags = [row_timer, row_parked]
        if cfg.track_fifo_heads:
            flags.append(row_head)
        word = row_src | (row_dst << bits)
        for i, flag in enumerate(flags):
            word = word | (flag.astype(jnp.int32) << (2 * bits + i))
        # An invalid row matches no slot (prefix is never negative), and
        # neither does a valid one past the last free slot.
        want = jnp.where(row_valid, want, -1)
        per_row_crec = crec is not None and jnp.ndim(crec) > 0
        if (
            state.insert_full_steps is not None
            and k > INSERT_SHORT_FACTOR * INSERT_SHORT_ROWS
        ):
            # Every column through one call: one ``case`` a step.
            cols = [word] + [
                row_msg[:, j].astype(jnp.int32) for j in range(cfg.msg_width)
            ]
            if per_row_crec:
                cols.append(jnp.asarray(crec, jnp.int32))
            cols, took_full, took_full_lane = _landed_by_batch(
                want, prefix, n_rows, tuple(cols)
            )
            state = state._replace(
                insert_full_steps=state.insert_full_steps + took_full,
                insert_full_lane_steps=(
                    state.insert_full_lane_steps + took_full_lane
                ),
            )
            word = cols[0]

            def landed_msg():
                # row_msg is already narrowed: the round trip is exact
                return jnp.where(
                    hit[:, None],
                    jnp.stack(cols[1:1 + cfg.msg_width], axis=1).astype(
                        state.pool_msg.dtype
                    ),
                    state.pool_msg,
                )

            def landed_crec():
                return cols[-1]
        else:
            # The smaller outboxes: the packed word (and a per-row crec)
            # through the one compare, the payload as whole rows.
            sel = want[:, None] == prefix[None, :]
            word = _sum_where(sel, word)

            def landed_msg():
                return _landed_rows(
                    hit, want, prefix, row_msg, state.pool_msg
                )

            def landed_crec():
                return _sum_where(sel, crec)

        field = (1 << bits) - 1

        def bit(i):
            return (word >> (2 * bits + i)) & 1 != 0

        new_state = state._replace(
            pool_valid=state.pool_valid | hit,
            pool_src=jnp.where(hit, word & field, state.pool_src),
            pool_dst=jnp.where(hit, (word >> bits) & field, state.pool_dst),
            pool_timer=jnp.where(hit, bit(0), state.pool_timer),
            pool_parked=jnp.where(hit, bit(1), state.pool_parked),
            pool_msg=landed_msg(),
            # arrival order follows row order: the row of rank r is r-th
            pool_seq=jnp.where(hit, state.seq_counter + prefix, state.pool_seq),
            seq_counter=state.seq_counter + n_rows,
            status=jnp.where(overflow, jnp.int32(ST_OVERFLOW), state.status),
        )
        if cfg.track_fifo_heads:
            new_state = new_state._replace(
                pool_head=jnp.where(hit, bit(2), state.pool_head)
            )
        if crec is not None:
            crec = jnp.asarray(crec, jnp.int32)
            # per-row creator links ([K]) come from round-delivery inserts
            new_state = new_state._replace(
                pool_crec=jnp.where(
                    hit, landed_crec() if per_row_crec else crec,
                    state.pool_crec,
                )
            )
        return new_state
    seqs = state.seq_counter + want  # arrival order follows row order
    slots = ops.rank_slots(prefix, want)  # [K]
    slots = jnp.where(ok, slots, cfg.pool_capacity)  # out-of-range => dropped
    new_state = state._replace(
        pool_head=(
            state.pool_head.at[slots].set(row_head, mode="drop")
            if cfg.track_fifo_heads
            else state.pool_head
        ),
        pool_valid=state.pool_valid.at[slots].set(True, mode="drop"),
        pool_src=state.pool_src.at[slots].set(row_src, mode="drop"),
        pool_dst=state.pool_dst.at[slots].set(row_dst, mode="drop"),
        pool_timer=state.pool_timer.at[slots].set(row_timer, mode="drop"),
        pool_parked=state.pool_parked.at[slots].set(row_parked, mode="drop"),
        pool_msg=state.pool_msg.at[slots].set(row_msg, mode="drop"),
        pool_seq=state.pool_seq.at[slots].set(seqs, mode="drop"),
        seq_counter=state.seq_counter + n_rows,
        status=jnp.where(overflow, jnp.int32(ST_OVERFLOW), state.status),
    )
    if crec is not None:
        # Creator links are only maintained when tracing (DPOR mode) —
        # untraced sweeps skip the extra scatter entirely.
        crec = jnp.asarray(crec, jnp.int32)
        new_state = new_state._replace(
            pool_crec=state.pool_crec.at[slots].set(
                jnp.broadcast_to(crec, (k,)), mode="drop"
            )
        )
    return new_state


# ---------------------------------------------------------------------------
# Delivery
# ---------------------------------------------------------------------------

class RowProposal(NamedTuple):
    """Pool-insert rows proposed by one effects pass (the insert itself is
    deferred so the fused step pays ONE insert for both step kinds)."""

    valid: jnp.ndarray  # [K] bool
    src: jnp.ndarray  # [K] int32
    dst: jnp.ndarray  # [K] int32
    timer: jnp.ndarray  # [K] bool
    parked: jnp.ndarray  # [K] bool
    msg: jnp.ndarray  # [K, W] int32

    @staticmethod
    def concat(a: "RowProposal", b: "RowProposal") -> "RowProposal":
        return RowProposal(
            *(jnp.concatenate([x, y]) for x, y in zip(a, b))
        )


def delivery_effects(
    state: ScheduleState, cfg: DeviceConfig, app: DSLApp, idx: jnp.ndarray,
    keep=None, discard=None,
) -> Tuple[ScheduleState, RowProposal, jnp.ndarray]:
    """Deliver pool entry ``idx`` minus the pool insert: run the app handler
    for the receiver, consume the entry, update timer parking; return the
    outbox as a RowProposal plus the trace record for this delivery.

    ``idx`` must point at a deliverable entry; an invalid index
    (== pool_capacity) makes the whole pass a no-op.

    A datagram kernel (``cfg.datagram``) passes the step's outcome as two
    bools, at most one of them set and neither for a timer or an external
    send: under ``keep`` the entry stays valid, with its ``pool_seq``;
    under ``discard`` it is consumed and nothing else happens (no handler
    effect, no outbox, no timer-memory change, ``deliveries`` as it
    was). The record and ``sched_hash`` say which."""
    n = cfg.num_actors
    oh = cfg.use_onehot
    valid_idx = idx < cfg.pool_capacity
    # What consumes the entry, and what runs the handler.
    consumed = handled = valid_idx
    if cfg.datagram:
        consumed = valid_idx & ~keep
        handled = valid_idx & ~discard
    safe_idx = jnp.minimum(idx, cfg.pool_capacity - 1)
    src = ops.get_scalar(state.pool_src, safe_idx, oh)
    dst = ops.get_scalar(state.pool_dst, safe_idx, oh)
    # Handlers (and trace records) always see int32 payloads regardless
    # of the pool's storage dtype.
    msg = ops.get_row(state.pool_msg, safe_idx, oh).astype(jnp.int32)
    is_timer = ops.get_scalar(state.pool_timer, safe_idx, oh)
    parent_rec = ops.get_scalar(state.pool_crec, safe_idx, oh)

    handler_state = ops.get_row(state.actor_state, dst, oh)
    new_row, outbox = app.handler(dst, handler_state, src, msg)
    # outbox: [K, 2+W] (valid, dst, msg...)
    k = outbox.shape[0]
    ob_valid = (outbox[:, 0] != 0) & handled
    ob_dst = jnp.clip(outbox[:, 1], 0, n - 1)
    ob_msg = outbox[:, 2:]
    ob_src = jnp.full((k,), 0, jnp.int32) + dst
    # Timer classification: self-send with a timer tag.
    if app.timer_tags:
        tags = jnp.asarray(list(app.timer_tags), jnp.int32)
        is_timer_tag = jnp.any(ob_msg[:, 0:1] == tags[None, :], axis=1)
    else:
        is_timer_tag = jnp.zeros(k, bool)
    ob_timer = is_timer_tag & (ob_dst == dst)
    # Park re-armed copies of the remembered timer (loop avoidance).
    mem_match = jnp.all(
        ob_msg == ops.gather_rows(state.timer_mem, ob_dst, oh), axis=1
    ) & ops.gather_vec(state.timer_mem_valid, ob_dst, oh)
    ob_parked = ob_timer & mem_match

    # Apply handler effects only when the delivery really happened.
    new_actor_state = ops.set_row(
        state.actor_state, dst, new_row, handled, oh
    )
    # Fold this delivery into the lane's schedule fingerprint (uint32
    # FNV-style: multiply by an odd prime, mix in src/dst/timer/payload).
    # Wraparound is the modulus; identical delivered sequences hash equal.
    w = msg.shape[0]
    pw = jnp.asarray(
        [pow(31, j, 1 << 32) for j in range(w)], jnp.uint32
    )
    mix = (
        jnp.sum(msg.astype(jnp.uint32) * pw)
        + src.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
        + dst.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
        + is_timer.astype(jnp.uint32) * jnp.uint32(0xC2B2AE35)
    )
    if cfg.datagram:
        # A kept and a discarded delivery fold with constants of their
        # own: two lanes share a hash only if they made the same choices.
        mix = (
            mix + keep.astype(jnp.uint32) * jnp.uint32(HASH_KEPT)
            + discard.astype(jnp.uint32) * jnp.uint32(HASH_DISCARDED)
        )
    folded = state.sched_hash * jnp.uint32(0x01000193) + mix
    # Consume the entry.
    state = state._replace(
        actor_state=new_actor_state,
        pool_valid=ops.set_scalar(
            state.pool_valid, safe_idx, False, consumed, oh
        ),
        deliveries=state.deliveries + handled.astype(jnp.int32),
        sched_hash=jnp.where(valid_idx, folded, state.sched_hash),
    )
    if cfg.datagram:
        state = state._replace(
            dups=state.dups + (valid_idx & keep).astype(jnp.int32),
            drops=state.drops + (valid_idx & discard).astype(jnp.int32),
        )
    if cfg.track_fifo_heads:
        # Promote the consumed channel's successor: recompute head bits
        # for THIS channel only (O(P); the consumed entry may not have
        # been the head — replay delivers by content — so a plain
        # min-seq recompute over the channel is the exact rule).
        upd = valid_idx & ~is_timer
        samech = (
            (state.pool_src == src)
            & (state.pool_dst == dst)
            & state.pool_valid
            & ~state.pool_timer
        )
        seqs = jnp.where(samech, state.pool_seq, jnp.int32(2**30))
        new_head = samech & (state.pool_seq == jnp.min(seqs))
        pool_head = jnp.where(samech & upd, new_head, state.pool_head)
        pool_head = ops.set_scalar(pool_head, safe_idx, False, valid_idx, oh)
        state = state._replace(pool_head=pool_head)

    # Timer memory update: delivering a timer remembers it; delivering a
    # non-timer clears all memory and unparks everything (host semantics:
    # justScheduledTimers cleared + timersToResend flushed on non-timer
    # delivery, RandomScheduler.scala:100-117).
    delivered_timer = is_timer & valid_idx
    cleared = handled & ~is_timer
    timer_mem = jnp.where(
        cleared,
        jnp.zeros_like(state.timer_mem),
        ops.set_row(
            state.timer_mem, dst, msg.astype(state.timer_mem.dtype),
            delivered_timer, oh,
        ),
    )
    timer_mem_valid = jnp.where(
        cleared,
        jnp.zeros_like(state.timer_mem_valid),
        ops.set_scalar(state.timer_mem_valid, dst, True, delivered_timer, oh),
    )
    pool_parked = jnp.where(
        handled & ~is_timer, jnp.zeros_like(state.pool_parked), state.pool_parked
    )
    state = state._replace(
        timer_mem=timer_mem, timer_mem_valid=timer_mem_valid, pool_parked=pool_parked
    )

    rows = RowProposal(ob_valid, ob_src, ob_dst, ob_timer, ob_parked, ob_msg)
    if cfg.record_trace:
        kind = jnp.where(is_timer, REC_TIMER, REC_DELIVERY)
        if cfg.datagram:
            kind = jnp.where(
                keep, REC_KEPT, jnp.where(discard, REC_DISCARDED, kind)
            )
        parts = [jnp.stack([kind, src, dst]), msg]
        if cfg.record_parents:
            # Two HB columns: creation link (pool_crec) + program-order
            # link (previous delivery at this receiver). This record will
            # land at trace index state.trace_len, which also becomes the
            # receiver's new last_rec.
            prev_rec = ops.get_scalar(state.last_rec, dst, oh)
            parts.append(parent_rec[None])
            parts.append(prev_rec[None])
            state = state._replace(
                last_rec=ops.set_scalar(
                    state.last_rec, dst, state.trace_len, handled, oh
                )
            )
        rec = jnp.concatenate(parts)
    else:
        rec = jnp.zeros((0,), jnp.int32)
    return state, rows, rec


def deliver_index(
    state: ScheduleState, cfg: DeviceConfig, app: DSLApp, idx: jnp.ndarray
) -> ScheduleState:
    """Deliver pool entry ``idx``: delivery_effects + the pool insert +
    trace append (the standalone form used by the replay/DPOR kernels)."""
    valid_idx = idx < cfg.pool_capacity
    rec_idx = state.trace_len  # this delivery's record position
    state, rows, rec = delivery_effects(state, cfg, app, idx)
    state = insert_rows(
        state, cfg, rows.valid, rows.src, rows.dst, rows.timer, rows.parked,
        rows.msg, crec=rec_idx if cfg.record_parents else None,
    )
    if cfg.record_trace:
        state = _append_record(state, cfg, rec, valid_idx)
    return state


def _append_record(state: ScheduleState, cfg: DeviceConfig, rec, enabled) -> ScheduleState:
    pos = jnp.minimum(state.trace_len, cfg.trace_rows - 1)
    new_trace = ops.set_row(state.trace, pos, rec, enabled, cfg.use_onehot)
    return state._replace(
        trace=new_trace, trace_len=state.trace_len + enabled.astype(jnp.int32)
    )


# ---------------------------------------------------------------------------
# External-op injection
# ---------------------------------------------------------------------------

def external_effects(
    state: ScheduleState,
    cfg: DeviceConfig,
    app: DSLApp,
    initial_rows: jnp.ndarray,  # [N, K0, 2+W] precomputed initial_msgs per actor
    init_states: jnp.ndarray,  # [N, S]
    op: jnp.ndarray,
    a: jnp.ndarray,
    b: jnp.ndarray,
    msg: jnp.ndarray,  # [W]
) -> Tuple[ScheduleState, RowProposal, jnp.ndarray, jnp.ndarray]:
    """Apply one external op (Start/Kill/Send/Partition/...) minus the pool
    insert; mirrors BaseScheduler._inject_one. Returns the proposed rows
    (Start's initial messages + Send's external message), the trace record,
    and its enabled flag. Pass OP_END to make the whole pass a no-op."""
    n = cfg.num_actors
    oh = cfg.use_onehot
    a_c = jnp.clip(a, 0, n - 1)
    b_c = jnp.clip(b, 0, n - 1)

    is_start = op == OP_START
    is_kill = op == OP_KILL
    is_hardkill = op == OP_HARDKILL
    is_send = op == OP_SEND
    is_partition = op == OP_PARTITION
    is_unpartition = op == OP_UNPARTITION

    was_started = ops.get_scalar(state.started, a_c, oh)
    was_stopped = ops.get_scalar(state.stopped, a_c, oh)
    # Fresh start = first Start or restart after HardKill; a Start for a
    # merely isolated actor is recovery (un-isolate, keep state, no re-emit)
    # — host semantics: ControlledActorSystem.spawn.
    fresh_start = is_start & (~was_started | was_stopped)
    # Start: begin (or recover) actor a.
    started = ops.set_scalar(state.started, a_c, True, is_start, oh)
    isolated = ops.set_scalar(
        state.isolated, a_c, is_kill, is_start | is_kill, oh
    )
    stopped = ops.set_scalar(
        state.stopped, a_c, is_hardkill, is_start | is_hardkill, oh
    )
    # Start after HardKill resets app state, but for the app's durable
    # words (on a first start the old row is the init row). A Python
    # gate: an app that declares none builds the program it always did.
    fresh_row = ops.get_row(init_states, a_c, oh)
    if app.kept_words:
        kept = np.zeros(cfg.state_width, bool)
        kept[list(app.kept_words)] = True
        fresh_row = jnp.where(
            kept, ops.get_row(state.actor_state, a_c, oh), fresh_row
        )
    if app.spawn_count is not None:
        # The runtime's count of this actor's fresh starts.
        fresh_row = fresh_row + (
            np.arange(cfg.state_width) == app.spawn_count
        )
    actor_state = ops.set_row(
        state.actor_state, a_c, fresh_row, fresh_start, oh
    )
    if oh:
        oh_a = ops.onehot(a_c, n)
        oh_b = ops.onehot(b_c, n)
        sym = (oh_a[:, None] & oh_b[None, :]) | (oh_b[:, None] & oh_a[None, :])
        cut = jnp.where(
            sym & (is_partition | is_unpartition), is_partition, state.cut
        )
    else:
        cut_val = jnp.where(
            is_partition,
            True,
            jnp.where(is_unpartition, False, state.cut[a_c, b_c]),
        )
        cut = state.cut.at[a_c, b_c].set(cut_val)
        cut = cut.at[b_c, a_c].set(cut_val)

    # HardKill and Partition scrubs, branchless (the fused step can't
    # afford a lax.cond whose both sides run under vmap anyway). A
    # HardKill drops everything to or from the actor; a Partition drops
    # the messages pending on the link, in both directions (timers are
    # self-sends and externals have src == n: neither matches).
    on_link = (
        ((state.pool_src == a_c) & (state.pool_dst == b_c))
        | ((state.pool_src == b_c) & (state.pool_dst == a_c))
    ) & ~state.pool_timer
    touch = (
        ((state.pool_src == a_c) | (state.pool_dst == a_c)) & is_hardkill
    ) | (on_link & is_partition)
    state = state._replace(
        started=started, isolated=isolated, stopped=stopped,
        actor_state=actor_state, cut=cut,
        pool_valid=state.pool_valid & ~touch,
        pool_head=(
            state.pool_head & ~touch
            if state.pool_head.shape[0]
            else state.pool_head
        ),
    )

    # Proposed rows: the Start's initial messages (fresh-start only) and the
    # Send's external message, as one [K0+1]-row proposal.
    k0 = initial_rows.shape[1]
    if k0 > 0:
        rows = ops.get_row(
            initial_rows.reshape(n, -1), a_c, oh
        ).reshape(k0, 2 + cfg.msg_width)
        r_valid = (rows[:, 0] != 0) & fresh_start
        r_dst = jnp.clip(rows[:, 1], 0, n - 1)
        r_msg = rows[:, 2:]
        if app.timer_tags:
            tags = jnp.asarray(list(app.timer_tags), jnp.int32)
            r_timer = jnp.any(r_msg[:, 0:1] == tags[None, :], axis=1) & (r_dst == a_c)
        else:
            r_timer = jnp.zeros(k0, bool)
        proposal = RowProposal(
            valid=jnp.concatenate([r_valid, is_send[None]]),
            src=jnp.concatenate([jnp.full((k0,), a_c), jnp.asarray([n], jnp.int32)]),
            dst=jnp.concatenate([r_dst, a_c[None]]),
            timer=jnp.concatenate([r_timer, jnp.asarray([False])]),
            parked=jnp.zeros(k0 + 1, bool),
            msg=jnp.concatenate([r_msg, msg[None, :]]),
        )
    else:
        proposal = RowProposal(
            valid=is_send[None],
            src=jnp.asarray([n], jnp.int32),  # EXTERNAL sender id
            dst=a_c[None],
            timer=jnp.asarray([False]),
            parked=jnp.asarray([False]),
            msg=msg[None, :],
        )

    if cfg.record_trace:
        parts = [jnp.stack([REC_EXT_BASE + op, a, b]), msg]
        if cfg.record_parents:
            # External injections have neither creation nor program-order
            # predecessors (both HB columns -1).
            parts.append(jnp.asarray([-1, -1], jnp.int32))
        rec = jnp.concatenate(parts)
    else:
        rec = jnp.zeros((0,), jnp.int32)
    enabled = (op != OP_END) & (op != OP_WAIT) & (op != OP_WAITCOND)
    return state, proposal, rec, enabled


def apply_external_op(
    state: ScheduleState,
    cfg: DeviceConfig,
    app: DSLApp,
    initial_rows: jnp.ndarray,
    init_states: jnp.ndarray,
    op: jnp.ndarray,
    a: jnp.ndarray,
    b: jnp.ndarray,
    msg: jnp.ndarray,
) -> ScheduleState:
    """external_effects + the pool insert + trace append (the standalone
    form used by the replay/DPOR kernels)."""
    rec_idx = state.trace_len  # this op's record position (creator link)
    state, rows, rec, enabled = external_effects(
        state, cfg, app, initial_rows, init_states, op, a, b, msg
    )
    state = insert_rows(
        state, cfg, rows.valid, rows.src, rows.dst, rows.timer, rows.parked,
        rows.msg, crec=rec_idx if cfg.record_parents else None,
    )
    if cfg.record_trace:
        state = _append_record(state, cfg, rec, enabled)
    return state


def check_invariant(
    state: ScheduleState, app: DSLApp
) -> jnp.ndarray:
    return app.invariant(state.actor_state, alive_mask(state))
