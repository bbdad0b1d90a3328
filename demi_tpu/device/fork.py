"""Prefix-fork replay: snapshot device state at branch points, fork lanes.

Every device lane used to replay its schedule from step 0 even though the
dominant workloads are trials that share long common prefixes by
construction: a DPOR backtrack prescription is "the executed prefix plus
one flipped racing delivery", and a ``BatchedDDMin`` /
``BatchedInternalMinimizer`` level's candidates are identical up to the
first removed index. Parsimonious Optimal DPOR (PAPERS.md) gets its
asymptotic win precisely from not re-exploring shared prefixes; the O(1)
autoregressive-caching line of work is the same insight applied to
accelerator state: checkpoint once, fork many.

Device side: ``ScheduleState`` is a fixed-shape NamedTuple, so a snapshot
IS the state. A trunk lane executes the shared prefix once
(``make_replay_prefix_runner`` / ``make_explore_prefix_runner`` /
``make_dpor_prefix_runner``); the ``start_state=``-built kernels broadcast
the snapshot across the lane axis (``vmap(in_axes=None)`` — no per-lane
copy is materialized) and resume with per-lane divergence: remaining
replay records, the full prescription plus the trunk's committed cursor,
or a fresh per-lane rng. Forked results are bit-exact vs scratch because
(a) the trunk replays exactly what a scratch lane's prefix would have and
(b) rng is never consumed before the fork point — injection steps and
prescription-following dispatch never split it (explore.make_step_fn
commits the split only on dispatch steps; prescribed deliveries bypass
the random chooser entirely). The DPOR trunk FREEZES (bit-exact no-op)
the moment no remaining prefix record matches, so the fork lanes redo
that step's decision with the full prescription and their own rng.

Host side: ``PrefixPlanner`` groups a batch of trials by longest common
prefix, bucketed to multiples of ``bucket`` rows so trunk/fork shapes
stay static (a ddmin level's candidates land one group per
first-divergence bucket); ``PrefixCache`` LRU-keeps packed snapshots
keyed by prefix hash so consecutive ddmin levels and DPOR rounds reuse
trunks across kernel launches.

Everything is opt-in: ``DEMI_PREFIX_FORK=1`` / ``--prefix-fork`` (or the
explicit ``prefix_fork=True`` constructor args). With it off, kernels are
built without the ``start_state`` input and their lowering is
byte-identical to the pre-fork tree.

Hierarchical trunks (``PrefixForker.trunk_hier`` +
``make_replay_prefix_resume_runner``): a trunk-cache miss no longer
replays its full prefix — the nearest cached ancestor trunk (one or more
planner buckets shorter) is resumed over just the remaining rows, so a
miss costs O(bucket) and the PrefixCache becomes a trunk tree shared
across ddmin levels and DPOR rounds. All three drivers derive:
``trunk_hier`` serves the replay checker (suffix-record resume),
``trunk_hier_prescribed`` + ``make_dpor_prefix_resume_runner`` serve
``DeviceDPOR`` (the freeze semantics make the ancestor's end state
exactly the longer trunk's state at the freeze step, so the resume
re-follows the FULL prescription from the committed cursor), and
``trunk_from`` + ``make_explore_prefix_resume_runner`` serve the sweep
driver (every group trunk resumes the chunk-wide base trunk — the
common injection rows below the first wait — over just its remaining
injection rows).

Telemetry (``fork.*`` series, plus ``dpor.prefix_group_size``): cache
hits/misses, ``fork.trunk_parent_hits`` (misses served by resuming an
ancestor trunk), ``fork.steps_saved`` (prefix steps the fork lanes did
NOT re-execute, net of the trunk's own run on a cache miss), and
group-size histograms — the signal the tuner's ``calibrate_fork`` axis
(demi_tpu/tune) uses to learn the bucket granularity.
"""

from __future__ import annotations

import hashlib
import os
from collections import OrderedDict
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs
from ..dsl import DSLApp
from ..minimization.pipeline import padded_bucket
from . import ops
from .core import (
    REC_NONE,
    ST_DISPATCH,
    ST_DONE,
    ST_INJECT,
    DeviceConfig,
    ScheduleState,
    init_state,
)


def prefix_fork_enabled(explicit: Optional[bool] = None) -> bool:
    """Resolve the prefix-fork switch: an explicit constructor arg wins,
    otherwise ``DEMI_PREFIX_FORK`` (off by default)."""
    if explicit is not None:
        return bool(explicit)
    return os.environ.get("DEMI_PREFIX_FORK", "").strip().lower() in (
        "1", "true", "yes", "on"
    )


class PrefixSnapshot(NamedTuple):
    """A trunk lane's state at the branch point. ``state`` is the whole
    ScheduleState pytree (already fixed-shape); the scalars carry the
    loop position so forked lanes keep scratch-identical budgets."""

    state: ScheduleState
    steps: jnp.ndarray  # int32: fused-loop steps consumed (explore/dpor) /
    #                     records applied (replay)
    cursor: jnp.ndarray  # int32: prescription cursor committed by the trunk
    ignored: jnp.ndarray  # int32: replay ignored-absent count so far
    peeked: jnp.ndarray  # int32: replay peek-enabled count so far


def fork_lanes(snapshot: PrefixSnapshot, keys) -> ScheduleState:
    """Broadcast a trunk snapshot across the lane axis with per-lane rng
    divergence — the materialized form of what the ``start_state=``
    kernels do implicitly via ``vmap(in_axes=None)``."""
    b = keys.shape[0]
    state = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (b,) + x.shape), snapshot.state
    )
    return state._replace(rng=keys)


def prefix_digest(*parts: bytes) -> bytes:
    """Compact cache key for a prefix's raw bytes."""
    h = hashlib.blake2b(digest_size=16)
    for p in parts:
        h.update(p)
    return h.digest()


def pad_pow2(n: int, floor: int = 8) -> int:
    """Power-of-two batch bucket so fork-group launches reuse compiled
    shapes. Delegates to ``pipeline.padded_bucket`` — the ONE bucket
    formula; ``speculation_room``'s free-lane estimate assumes dispatch
    padding matches it exactly."""
    return max(floor, padded_bucket(n))


def padded_size(n: int, mesh=None) -> int:
    """The launch size for a fork group or scratch sub-batch: power-of-two
    bucketed, then rounded to a mesh-axis multiple when sharded — the one
    padding rule all three fork call sites (replay checker, DeviceDPOR,
    sweep driver) share."""
    n = pad_pow2(n)
    if mesh is not None:
        from ..parallel.mesh import pad_batch_to_devices

        n = pad_batch_to_devices(n, mesh)
    return n


# ---------------------------------------------------------------------------
# Trunk runners: execute ONE lane through a shared prefix, capture state
# ---------------------------------------------------------------------------

def make_replay_prefix_runner(app: DSLApp, cfg: DeviceConfig):
    """jitted ``run_prefix(records[R, recw], key) -> PrefixSnapshot``:
    apply the prefix records (compact, REC_NONE-terminated — one static
    shape for every prefix length) on a single trunk lane and capture the
    full replay carry (state + ignored/peeked counters)."""
    from .replay import _replay_cfg, make_replay_apply_fn

    cfg = _replay_cfg(cfg)
    apply_one = make_replay_apply_fn(app, cfg)
    oh = cfg.use_onehot

    def run_prefix(records, key) -> PrefixSnapshot:
        state = init_state(app, cfg, key)
        n_rec = records.shape[0]

        def cond(carry):
            s, _ig, _pk, i = carry
            kind = ops.get_scalar(
                records[:, 0], jnp.minimum(i, n_rec - 1), oh
            )
            return (i < n_rec) & (kind != REC_NONE) & (s.status < ST_DONE)

        def body(carry):
            s, ig, pk, i = carry
            rec = ops.get_row(records, jnp.minimum(i, n_rec - 1), oh)
            s, ig, pk = apply_one(s, ig, pk, rec)
            return (s, ig, pk, i + 1)

        state, ignored, peeked, i = jax.lax.while_loop(
            cond, body, (state, jnp.int32(0), jnp.int32(0), jnp.int32(0))
        )
        return PrefixSnapshot(
            state=state, steps=i, cursor=i, ignored=ignored, peeked=peeked
        )

    return jax.jit(run_prefix)


def make_replay_prefix_resume_runner(app: DSLApp, cfg: DeviceConfig):
    """jitted ``resume_prefix(records[R, recw], snap) -> PrefixSnapshot``:
    extend a cached ancestor trunk by applying only the REMAINING prefix
    records (compact, REC_NONE-terminated) — the hierarchical-trunk step.
    A trunk-cache miss used to replay its full p-row prefix from scratch;
    deriving it from the parent bucket's cached trunk costs O(bucket)
    instead of O(p), turning the PrefixCache into a trunk tree shared
    across ddmin levels and DPOR rounds. Bit-exact vs a scratch trunk:
    record application is deterministic and replay lanes never consume
    rng, so state(parent) + suffix rows == state(full prefix); a parent
    that finished early (status >= ST_DONE mid-prefix) applies zero
    suffix rows, exactly where the scratch run would have stopped."""
    from .replay import _replay_cfg, make_replay_apply_fn

    cfg = _replay_cfg(cfg)
    apply_one = make_replay_apply_fn(app, cfg)
    oh = cfg.use_onehot

    def resume_prefix(records, snap: PrefixSnapshot) -> PrefixSnapshot:
        n_rec = records.shape[0]

        def cond(carry):
            s, _ig, _pk, i = carry
            kind = ops.get_scalar(
                records[:, 0], jnp.minimum(i, n_rec - 1), oh
            )
            return (i < n_rec) & (kind != REC_NONE) & (s.status < ST_DONE)

        def body(carry):
            s, ig, pk, i = carry
            rec = ops.get_row(records, jnp.minimum(i, n_rec - 1), oh)
            s, ig, pk = apply_one(s, ig, pk, rec)
            return (s, ig, pk, i + 1)

        state, ignored, peeked, i = jax.lax.while_loop(
            cond, body, (snap.state, snap.ignored, snap.peeked, jnp.int32(0))
        )
        return PrefixSnapshot(
            state=state, steps=snap.steps + i, cursor=snap.cursor + i,
            ignored=ignored, peeked=peeked,
        )

    return jax.jit(resume_prefix)


def make_explore_prefix_runner(app: DSLApp, cfg: DeviceConfig):
    """jitted ``run_prefix(prog: ExtProgram, key) -> PrefixSnapshot``: run
    the fused step through the initial injection segment (deterministic —
    rng is only consumed on dispatch steps) and stop the moment the lane
    leaves ST_INJECT. Lanes sharing the program rows up to (one past) the
    first wait-like op share this state bit-exactly."""
    from .explore import make_any_step_fn

    step = make_any_step_fn(app, cfg)

    def run_prefix(prog, key) -> PrefixSnapshot:
        state = init_state(app, cfg, key)

        def cond(carry):
            s, i = carry
            return (s.status == ST_INJECT) & (i < cfg.max_steps)

        def body(carry):
            s, i = carry
            return step(s, prog), i + 1

        state, steps = jax.lax.while_loop(
            cond, body, (state, jnp.int32(0))
        )
        return PrefixSnapshot(
            state=state, steps=steps, cursor=jnp.int32(0),
            ignored=jnp.int32(0), peeked=jnp.int32(0),
        )

    return jax.jit(run_prefix)


def make_explore_prefix_base_runner(app: DSLApp, cfg: DeviceConfig):
    """jitted ``run_base(prog, key, op_limit) -> PrefixSnapshot``: run the
    deterministic injection segment through the first ``op_limit``
    external ops only (a traced scalar — one compile serves every limit)
    and stop while the lane is still ST_INJECT. This is the sweep
    driver's chunk-wide BASE trunk: every lane of a chunk shares the
    program rows below the chunk's common-prefix/first-wait cap, so the
    base runs once and each group trunk derives from it by resuming over
    just its remaining injection rows (``make_explore_prefix_resume_runner``)
    instead of replaying the whole shared segment per group."""
    from .explore import make_any_step_fn

    step = make_any_step_fn(app, cfg)

    def run_base(prog, key, op_limit) -> PrefixSnapshot:
        state = init_state(app, cfg, key)

        def cond(carry):
            s, i = carry
            return (
                (s.status == ST_INJECT)
                & (s.ext_cursor < op_limit)
                & (i < cfg.max_steps)
            )

        def body(carry):
            s, i = carry
            return step(s, prog), i + 1

        state, steps = jax.lax.while_loop(
            cond, body, (state, jnp.int32(0))
        )
        return PrefixSnapshot(
            state=state, steps=steps, cursor=jnp.int32(0),
            ignored=jnp.int32(0), peeked=jnp.int32(0),
        )

    return jax.jit(run_base)


def make_explore_prefix_resume_runner(app: DSLApp, cfg: DeviceConfig):
    """jitted ``resume_prefix(prog, snap) -> PrefixSnapshot``: continue a
    base trunk's injection segment to the group boundary (the moment the
    lane leaves ST_INJECT). Bit-exact vs a scratch group trunk: injection
    is deterministic and never consumes rng, and the base stopped with
    the lane still ST_INJECT below every member's first wait-like op, so
    state(base) + remaining injections == state(full segment). A base
    that overflowed mid-prefix resumes zero steps — exactly where the
    scratch run would have stopped."""
    from .explore import make_any_step_fn

    step = make_any_step_fn(app, cfg)

    def resume_prefix(prog, snap: PrefixSnapshot) -> PrefixSnapshot:
        def cond(carry):
            s, i = carry
            return (s.status == ST_INJECT) & (i < cfg.max_steps)

        def body(carry):
            s, i = carry
            return step(s, prog), i + 1

        state, steps = jax.lax.while_loop(
            cond, body, (snap.state, snap.steps)
        )
        return PrefixSnapshot(
            state=state, steps=steps, cursor=jnp.int32(0),
            ignored=jnp.int32(0), peeked=jnp.int32(0),
        )

    return jax.jit(resume_prefix)


def _dpor_prefix_loop(app: DSLApp, cfg: DeviceConfig):
    """The prescription-following trunk loop shared by the DPOR prefix
    runner and its hierarchical resume twin: follow the prescription
    (injection steps included) and FREEZE — a bit-exact no-op, state and
    cursor untouched — the first time no remaining prescribed record
    matches the pool. Returns ``run(prog, presc, state, cursor, steps)``
    carrying the loop from any starting carry."""
    from .dpor_sweep import make_prescribed_dispatch
    from .explore import make_step_fn

    assert cfg.record_trace and cfg.record_parents
    base_step = make_step_fn(app, cfg)
    pdispatch = make_prescribed_dispatch(app, cfg)

    def run(prog, presc, state, cursor, steps):
        def cond(carry):
            s, _cur, i, frozen = carry
            return (s.status < ST_DONE) & ~frozen & (i < cfg.max_steps)

        def body(carry):
            s, cur, i, _frozen = carry
            in_dispatch = s.status == ST_DISPATCH

            def dispatch_side(args):
                s, cur = args
                ns, ncur, found = pdispatch(s, presc, cur)
                out = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(found, b, a), s, ns
                )
                return out, jnp.where(found, ncur, cur), ~found

            def inject_side(args):
                s, cur = args
                return base_step(s, prog), cur, jnp.bool_(False)

            ns, ncur, froze = jax.lax.cond(
                in_dispatch, dispatch_side, inject_side, (s, cur)
            )
            # A frozen "step" took no action: don't charge the budget.
            return ns, ncur, i + (~froze).astype(jnp.int32), froze

        state, cursor, steps, _ = jax.lax.while_loop(
            cond, body, (state, cursor, steps, jnp.bool_(False))
        )
        return state, cursor, steps

    return run


def make_dpor_prefix_runner(app: DSLApp, cfg: DeviceConfig):
    """jitted ``run_prefix(prog, presc[R, recw], key) -> PrefixSnapshot``:
    follow the prefix prescription (injection steps included) and FREEZE —
    a bit-exact no-op, state and cursor untouched — the first time no
    remaining prefix record matches the pool. A scratch lane would decide
    that step by scanning the full prescription (and possibly falling back
    to its rng); the fork lanes redo exactly that from the snapshot, so
    stopping before the decision is what keeps parity exact."""
    loop = _dpor_prefix_loop(app, cfg)

    def run_prefix(prog, presc, key) -> PrefixSnapshot:
        state = init_state(app, cfg, key)
        state, cursor, steps = loop(
            prog, presc, state, jnp.int32(0), jnp.int32(0)
        )
        return PrefixSnapshot(
            state=state, steps=steps, cursor=cursor,
            ignored=jnp.int32(0), peeked=jnp.int32(0),
        )

    return jax.jit(run_prefix)


def make_dpor_prefix_resume_runner(app: DSLApp, cfg: DeviceConfig):
    """jitted ``resume_prefix(prog, presc[R, recw], snap) -> PrefixSnapshot``:
    extend a cached ancestor DPOR trunk over the REMAINING prescribed
    records — the prescribed-resume (hierarchical) trunk step. Unlike the
    replay twin, the resume takes the FULL trunk prescription, not just
    the suffix rows: the ancestor's committed cursor points into it, and
    the prescribed-dispatch scan must restart from that cursor (records
    between the cursor and the ancestor's prefix end were absent at the
    freeze point, but the scan that decides the next delivery considers
    them together with the new rows).

    Bit-exact vs a scratch full-prefix trunk: the ancestor froze exactly
    at the first step where none of ITS rows matched, with state/cursor
    untouched by the freeze. A scratch trunk over the longer prescription
    behaves identically up to that step (the scans agree wherever the
    shorter prescription still had a match), and at it scans the extra
    rows — which is exactly what re-entering the loop from the ancestor's
    carry with the full prescription and a cleared freeze flag does. The
    resume therefore costs O(remaining rows) device steps instead of
    O(prefix)."""
    loop = _dpor_prefix_loop(app, cfg)

    def resume_prefix(prog, presc, snap: PrefixSnapshot) -> PrefixSnapshot:
        state, cursor, steps = loop(
            prog, presc, snap.state, snap.cursor, snap.steps
        )
        return PrefixSnapshot(
            state=state, steps=steps, cursor=cursor,
            ignored=snap.ignored, peeked=snap.peeked,
        )

    return jax.jit(resume_prefix)


# ---------------------------------------------------------------------------
# Host-side planning: group trials by bucketed longest common prefix
# ---------------------------------------------------------------------------

class PrefixGroup(NamedTuple):
    prefix_len: int  # shared rows (a multiple of the planner bucket)
    indices: List[int]  # batch positions sharing the prefix
    key: bytes  # digest of the shared prefix rows (cache key)


class PrefixPlanner:
    """Group a batch of trials (row-compact int32 record arrays) by
    longest common prefix, bucketed to multiples of ``bucket`` rows so
    trunk/fork shapes stay static.

    ``plan(records[n, R, w], lengths[n])`` returns ``(groups, scratch)``:
    each group's members share ``records[:, :prefix_len]`` byte-exactly;
    trials with no shareable prefix (divergence inside bucket 0) land in
    ``scratch``. Recursion only descends while a chunk-partition keeps at
    least ``min_group`` members together, so a ddmin level's candidates —
    identical up to the first removed index — come out as one group per
    first-divergence bucket.

    ``plan`` partitions by ARRAY prefix-comparison: every bucket chunk of
    the stacked row matrix is content-hashed in one vectorized pass (the
    128-bit scheme of ``native.prescription_digests``' family), and each
    recursion level is a lexsort + boundary scan over those hashes — no
    per-trial ``tobytes`` in the loop. ``plan_reference`` keeps the
    original per-chunk-bytes recursion as the parity baseline
    (``test_prefix_planner_vectorized_matches_reference`` pins
    group-for-group equality)."""

    def __init__(self, bucket: int = 8, min_group: int = 2):
        if bucket < 1:
            raise ValueError(f"bucket must be >= 1, got {bucket}")
        self.bucket = bucket
        self.min_group = min_group

    def plan(
        self, records: np.ndarray, lengths: Sequence[int]
    ) -> Tuple[List[PrefixGroup], List[int]]:
        records = np.asarray(records)
        lengths = np.asarray(lengths)
        n, rmax = records.shape[0], records.shape[1]
        groups: List[PrefixGroup] = []
        scratch: List[int] = []
        if n == 0:
            return groups, scratch
        depth_max = rmax // self.bucket
        # Per-(trial, depth) 2x64-bit chunk content hashes, one
        # vectorized pass over the raw bytes (dtype-agnostic, byte-exact
        # like the reference's tobytes comparison, modulo 128-bit
        # collision odds — the trust level of the blake2b-16 trunk keys).
        if depth_max > 0:
            from ..native.analysis import _mix64, _COL_MULT, _SALTS

            flat = np.ascontiguousarray(records[:, : depth_max * self.bucket])
            nbytes = self.bucket * int(
                np.prod(flat.shape[2:], dtype=np.int64)
            ) * flat.dtype.itemsize
            chunks = flat.view(np.uint8).reshape(n, depth_max, nbytes)
            col_pow = np.ones(nbytes, np.uint64)
            if nbytes > 1:
                col_pow[1:] = _COL_MULT
            col_pow = np.cumprod(col_pow)[::-1]
            cv = (chunks.astype(np.uint64) * col_pow[None, None, :]).sum(
                axis=2, dtype=np.uint64
            )
            h1 = _mix64(cv ^ _SALTS[0])
            h2 = _mix64(cv ^ _SALTS[1])
        full_at = lengths[:, None] >= (
            np.arange(1, depth_max + 1, dtype=np.int64) * self.bucket
        )[None, :] if depth_max else np.zeros((n, 0), bool)

        def emit(idx: np.ndarray, depth: int) -> None:
            if depth == 0:
                scratch.extend(int(i) for i in idx)
                return
            p = depth * self.bucket
            groups.append(
                PrefixGroup(
                    prefix_len=p,
                    indices=[int(i) for i in idx],
                    key=prefix_digest(records[idx[0], :p].tobytes()),
                )
            )

        def split(idx: np.ndarray, depth: int) -> None:
            if depth >= depth_max:
                emit(idx, depth)
                return
            full = full_at[idx, depth]
            deeper, rest = idx[full], idx[~full]
            small = [rest]
            if deeper.size:
                k1, k2 = h1[deeper, depth], h2[deeper, depth]
                order = np.lexsort((k2, k1))
                sd, s1, s2 = deeper[order], k1[order], k2[order]
                breaks = np.flatnonzero(
                    (s1[1:] != s1[:-1]) | (s2[1:] != s2[:-1])
                ) + 1
                bounds = np.concatenate(([0], breaks, [sd.size]))
                for lo, hi in zip(bounds[:-1], bounds[1:]):
                    sub = sd[lo:hi]
                    if sub.size >= self.min_group:
                        split(np.sort(sub), depth + 1)
                    else:
                        small.append(sub)
            rest = np.concatenate(small) if len(small) > 1 else rest
            if rest.size:
                emit(np.sort(rest), depth)

        split(np.arange(n, dtype=np.int64), 0)
        return groups, scratch

    def plan_reference(
        self, records: np.ndarray, lengths: Sequence[int]
    ) -> Tuple[List[PrefixGroup], List[int]]:
        """The original per-chunk-bytes recursion — the parity baseline
        for the vectorized ``plan`` (groups are compared as
        (prefix_len, member-set, key) sets; member ORDER within a group
        is load-free: fork results merge by batch index and per-lane
        keys follow batch position)."""
        records = np.asarray(records)
        lengths = np.asarray(lengths)
        groups: List[PrefixGroup] = []
        scratch: List[int] = []

        def chunk_key(i: int, depth: int) -> bytes:
            lo = depth * self.bucket
            return records[i, lo: lo + self.bucket].tobytes()

        def emit(idxs: List[int], depth: int) -> None:
            if depth == 0:
                scratch.extend(idxs)
                return
            p = depth * self.bucket
            groups.append(
                PrefixGroup(
                    prefix_len=p,
                    indices=list(idxs),
                    key=prefix_digest(records[idxs[0], :p].tobytes()),
                )
            )

        def split(idxs: List[int], depth: int) -> None:
            deeper: Dict[bytes, List[int]] = {}
            rest: List[int] = []
            for i in idxs:
                # Only descend through FULL chunks: a trial ending inside
                # the next chunk forks at the current boundary instead of
                # grouping on padding bytes.
                if lengths[i] >= (depth + 1) * self.bucket:
                    deeper.setdefault(chunk_key(i, depth), []).append(i)
                else:
                    rest.append(i)
            for sub in deeper.values():
                if len(sub) >= self.min_group:
                    split(sub, depth + 1)
                else:
                    rest.extend(sub)
            if rest:
                emit(rest, depth)

        split(list(range(records.shape[0])), 0)
        return groups, scratch


class PrefixCache:
    """LRU of packed trunk snapshots keyed by prefix hash. Entries are
    ``(PrefixSnapshot, trunk_steps)``; one snapshot is a single lane's
    state (a few pool-sized arrays), so a few dozen stay cheap while
    letting consecutive ddmin levels / DPOR rounds reuse trunks across
    kernel launches."""

    def __init__(self, capacity: int = 32):
        self.capacity = capacity
        self._entries: "OrderedDict[bytes, Tuple[PrefixSnapshot, int]]" = (
            OrderedDict()
        )
        self.hits = 0
        self.misses = 0

    def get(self, key: bytes) -> Optional[Tuple[PrefixSnapshot, int]]:
        entry = self._entries.get(key)
        if entry is None:
            self.misses += 1
            return None
        self._entries.move_to_end(key)
        self.hits += 1
        return entry

    def peek(self, key: bytes) -> Optional[Tuple[PrefixSnapshot, int]]:
        """Lookup WITHOUT hit/miss accounting — used by the hierarchical
        ancestor search, whose probes are derivation opportunities, not
        trunk requests (they would otherwise skew the hit rate the tuner
        reads). A found ancestor still refreshes its LRU position."""
        entry = self._entries.get(key)
        if entry is not None:
            self._entries.move_to_end(key)
        return entry

    def put(self, key: bytes, snapshot: PrefixSnapshot, steps: int) -> None:
        self._entries[key] = (snapshot, steps)
        self._entries.move_to_end(key)
        while len(self._entries) > self.capacity:
            self._entries.popitem(last=False)

    def __contains__(self, key: bytes) -> bool:
        return key in self._entries

    def __len__(self) -> int:
        return len(self._entries)


class PrefixForker:
    """Planner + cache + trunk-runner glue shared by the replay checker,
    ``DeviceDPOR``, and the sweep driver's chunked mode. ``runner`` is a
    jitted trunk runner returning a PrefixSnapshot; statistics accumulate
    in ``stats`` (always) and the ``fork.*`` obs series (when telemetry
    is on)."""

    def __init__(
        self,
        runner: Callable[..., PrefixSnapshot],
        bucket: int = 8,
        capacity: int = 32,
        min_group: int = 2,
        driver: str = "replay",
        resume_runner: Optional[Callable[..., PrefixSnapshot]] = None,
        anchor_stride: Optional[int] = None,
    ):
        self.planner = PrefixPlanner(bucket=bucket, min_group=min_group)
        self.cache = PrefixCache(capacity)
        self.runner = runner
        # Hierarchical trunks: ``resume_runner(suffix_records, snapshot)``
        # extends a cached ancestor trunk by only the remaining rows; with
        # it unset, every cache miss replays its full prefix (the pre-
        # hierarchical behavior, still used by the DPOR/sweep drivers).
        self.resume_runner = resume_runner
        # Anchor-chained trunk building (DPOR cross-round reuse): with
        # ``anchor_stride`` set (in planner buckets), a full-prefix miss
        # is built as a CHAIN of resumes that caches a snapshot at every
        # stride boundary along the way. Round prefixes are round-unique
        # at full length (the PR 6 ~0% reuse finding), but consecutive
        # rounds' racing families share long ancestors — the anchors are
        # exactly the sub-bucket keys those ancestors hit, so a later
        # round's trunk derives in O(remaining rows past the shared
        # anchor) instead of O(prefix). Same total prefix steps as one
        # straight run, plus one launch per stride boundary.
        self.anchor_stride = anchor_stride
        self.driver = driver
        self.stats = {
            "groups": 0,
            "forked_lanes": 0,
            "scratch_lanes": 0,
            "prefix_hits": 0,
            "prefix_misses": 0,
            "parent_trunks": 0,
            "steps_saved": 0,
        }
        # steps_saved terms awaiting a host pull: (trunk-steps scalar,
        # multiplier). Resolving a fresh trunk's steps immediately would
        # block async dispatch, so terms accumulate and are pulled lazily
        # (next plan() or stats_view()) — by then the trunk has long run.
        self._deferred: List[Tuple[object, int]] = []

    def plan(self, records, lengths):
        self.resolve_deferred()
        return self.planner.plan(records, lengths)

    def should_fork(self, group: PrefixGroup) -> bool:
        """Fork when the trunk amortizes: a real shared prefix and either
        enough members or an already-cached trunk (free reuse)."""
        return group.prefix_len > 0 and self.amortizes(
            len(group.indices), group.key
        )

    def amortizes(self, n: int, key: bytes) -> bool:
        """The trunk-amortization rule shared by every fork call site
        (the sweep driver groups by exact digest rather than PrefixGroup,
        so it applies this directly)."""
        return n >= self.planner.min_group or key in self.cache

    def trunk(self, key: bytes, *args) -> Tuple[PrefixSnapshot, object, bool]:
        """Cached trunk snapshot: ``(snapshot, trunk_steps, cache_hit)``.
        ``trunk_steps`` stays a device scalar on a fresh miss (pulling it
        here would block async dispatch); it is only read host-side when
        the deferred steps_saved terms resolve."""
        entry = self.cache.get(key)
        if entry is not None:
            self.stats["prefix_hits"] += 1
            obs.counter("fork.prefix_hits").inc(driver=self.driver)
            return entry[0], entry[1], True
        snapshot = self.runner(*args)
        self.cache.put(key, snapshot, snapshot.steps)
        self.stats["prefix_misses"] += 1
        obs.counter("fork.prefix_misses").inc(driver=self.driver)
        return snapshot, snapshot.steps, False

    def trunk_hier(
        self, key: bytes, trunk_records, rng_key, prefix_len: int
    ) -> Tuple[PrefixSnapshot, object, bool]:
        """``trunk`` with hierarchical derivation: on a cache miss, walk
        the prefix down one planner bucket at a time looking for a cached
        ancestor trunk, and derive the missing trunk by resuming it over
        only the remaining rows (O(bucket) instead of O(prefix)). The
        derived snapshot is cached under the full key, so the PrefixCache
        becomes a trunk TREE: a deep ddmin level's trunk forks off the
        previous level's, which forked off the one before it."""
        if self.resume_runner is None or key in self.cache:
            return self.trunk(key, trunk_records, rng_key)
        b = self.planner.bucket
        for q in range(prefix_len - b, 0, -b):
            parent = self.cache.peek(
                prefix_digest(trunk_records[:q].tobytes())
            )
            if parent is None:
                continue
            suffix = np.zeros_like(trunk_records)
            suffix[: prefix_len - q] = trunk_records[q:prefix_len]
            snapshot = self.resume_runner(suffix, parent[0])
            self.cache.put(key, snapshot, snapshot.steps)
            self._note_parent_trunk(parent)
            return snapshot, snapshot.steps, False
        return self.trunk(key, trunk_records, rng_key)

    def trunk_hier_prescribed(
        self, key: bytes, prog, trunk_records, rng_key, prefix_len: int
    ) -> Tuple[PrefixSnapshot, object, bool]:
        """``trunk_hier`` for prescription-following trunks (DeviceDPOR):
        same ancestor walk, but the resume re-follows the FULL trunk
        prescription from the ancestor's committed cursor (freeze
        semantics — see ``make_dpor_prefix_resume_runner``) instead of a
        compacted suffix, so the runner/resume argument shapes are
        (prog, presc, key) / (prog, presc, snap).

        With ``anchor_stride`` set, the build additionally CACHES
        intermediate snapshots at every stride boundary between the
        found ancestor (or scratch) and the full prefix — truncating the
        prescription at a boundary freezes the trunk loop exactly there,
        and resuming the truncation's snapshot with a longer truncation
        is the documented prescribed-resume semantics, so the chain is
        bit-exact vs one straight run (tests/test_fork.py pins it)."""
        if self.resume_runner is None or key in self.cache:
            return self.trunk(key, prog, trunk_records, rng_key)
        b = self.planner.bucket
        parent = None
        parent_q = 0
        for q in range(prefix_len - b, 0, -b):
            entry = self.cache.peek(
                prefix_digest(trunk_records[:q].tobytes())
            )
            if entry is not None:
                parent, parent_q = entry, q
                break
        if self.anchor_stride:
            return self._trunk_anchor_chain(
                key, prog, trunk_records, rng_key, prefix_len,
                parent, parent_q,
            )
        if parent is not None:
            snapshot = self.resume_runner(prog, trunk_records, parent[0])
            self.cache.put(key, snapshot, snapshot.steps)
            self._note_parent_trunk(parent)
            return snapshot, snapshot.steps, False
        return self.trunk(key, prog, trunk_records, rng_key)

    def _trunk_anchor_chain(
        self, key: bytes, prog, trunk_records, rng_key, prefix_len: int,
        parent, parent_q: int,
    ) -> Tuple[PrefixSnapshot, object, bool]:
        """Build a missing trunk as a chain of prescribed resumes,
        caching an anchor snapshot at every ``anchor_stride``-bucket
        boundary (see ``trunk_hier_prescribed``). Starts from the found
        ancestor (``parent`` at ``parent_q`` rows) or scratch."""
        stride = self.planner.bucket * int(self.anchor_stride)
        snap = parent[0] if parent is not None else None
        boundary = (parent_q // stride + 1) * stride
        anchors = 0
        while boundary < prefix_len:
            trunc = np.zeros_like(trunk_records)
            trunc[:boundary] = trunk_records[:boundary]
            akey = prefix_digest(trunk_records[:boundary].tobytes())
            if akey not in self.cache:
                asnap = (
                    self.runner(prog, trunc, rng_key)
                    if snap is None
                    else self.resume_runner(prog, trunc, snap)
                )
                self.cache.put(akey, asnap, asnap.steps)
                snap = asnap
                anchors += 1
            else:
                snap = self.cache.peek(akey)[0]
            boundary += stride
        if anchors:
            self.stats["anchor_trunks"] = (
                self.stats.get("anchor_trunks", 0) + anchors
            )
            obs.counter("fork.anchor_trunks").inc(anchors, driver=self.driver)
        if snap is None:
            return self.trunk(key, prog, trunk_records, rng_key)
        snapshot = self.resume_runner(prog, trunk_records, snap)
        self.cache.put(key, snapshot, snapshot.steps)
        if parent is not None:
            self._note_parent_trunk(parent)
        else:
            self.stats["prefix_misses"] += 1
            obs.counter("fork.prefix_misses").inc(driver=self.driver)
        return snapshot, snapshot.steps, False

    def trunk_from(
        self, key: bytes, parent: Tuple[PrefixSnapshot, object], *args
    ) -> Tuple[PrefixSnapshot, object, bool]:
        """Trunk derived from an EXPLICIT ancestor snapshot (the sweep
        driver's chunk-wide base trunk, which is keyed outside the
        group-digest scheme): cache contract matches ``trunk``; a miss
        resumes the parent over the remaining rows instead of running
        the full prefix."""
        entry = self.cache.get(key)
        if entry is not None:
            self.stats["prefix_hits"] += 1
            obs.counter("fork.prefix_hits").inc(driver=self.driver)
            return entry[0], entry[1], True
        snapshot = self.resume_runner(*args, parent[0])
        self.cache.put(key, snapshot, snapshot.steps)
        self._note_parent_trunk(parent)
        return snapshot, snapshot.steps, False

    def _note_parent_trunk(self, parent) -> None:
        """Shared accounting for a trunk served by ancestor resume: the
        full-key lookup genuinely missed, the ancestor hit is its own
        (cheaper) event, and note_group's steps_saved term — which
        charges the miss as a FULL trunk run — is credited the parent's
        prefix steps so the evidence the fork tuner reads stays unbiased
        for deep hierarchical workloads."""
        self.stats["prefix_misses"] += 1
        self.stats["parent_trunks"] += 1
        obs.counter("fork.prefix_misses").inc(driver=self.driver)
        obs.counter("fork.trunk_parent_hits").inc(driver=self.driver)
        if self.driver == "dpor":
            # The satellite counter report.py's Pipeline block renders
            # next to dpor.inflight_rounds.
            obs.counter("dpor.trunk_parent_hits").inc()
        self._deferred.append((parent[1], 1))

    def note_group(self, size: int, trunk_steps, cache_hit: bool) -> None:
        """Account one fork-group launch: every member skipped the trunk's
        steps; a cache miss pays the trunk once. The steps term is
        deferred (see ``_deferred``)."""
        self.stats["groups"] += 1
        self.stats["forked_lanes"] += size
        self._deferred.append((trunk_steps, size - (0 if cache_hit else 1)))
        obs.histogram("fork.group_size").observe(size, driver=self.driver)

    def note_scratch(self, n: int) -> None:
        self.stats["scratch_lanes"] += n

    def resolve_deferred(self) -> None:
        """Pull any deferred steps_saved terms host-side. Call sites that
        bypass plan() (the sweep driver groups by exact digest) invoke
        this at the START of each round — the previous round's trunks
        have long completed, so the pull costs no dispatch overlap and
        the deferred list stays bounded by one round's groups."""
        if not self._deferred:
            return
        saved = sum(
            int(jax.device_get(steps)) * mult
            for steps, mult in self._deferred
        )
        self._deferred.clear()
        self.stats["steps_saved"] += saved
        obs.counter("fork.steps_saved").inc(saved, driver=self.driver)

    def stats_view(self) -> dict:
        """The statistics dict with every deferred term resolved — what
        the drivers' ``fork_stats`` surfaces."""
        self.resolve_deferred()
        return dict(self.stats)
