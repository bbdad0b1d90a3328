"""Device-batched minimization oracles.

DDMin levels and internal-minimization rounds produce *sets* of candidate
schedules; here each set becomes one vmapped replay batch (SURVEY.md §7.2
step 6, BASELINE north star: "DDMin farms its replay-this-subsequence
trials to the same batched kernel"). Verdicts come from the jitted
invariant; only the adopted candidate is re-executed on the host oracle to
produce the bookkeeping EventTrace.

Record arrays are padded to one static shape so every round reuses the same
compiled kernel.

Async pipeline surface (DEMI_ASYNC_MIN=1 / ``async_min=True``): the
checker adds a ``dispatch``/``harvest`` split (``PendingVerdicts`` keeps
verdict codes on device — no per-group blocking ``np.asarray``), a
``CandidateLowerer`` so a level's candidates lower as row-gathers off one
base lowering, and speculative candidate lanes riding the padded buckets:
harvested speculative codes seed a digest-keyed verdict cache the next
dispatch consumes, shrinking (or skipping) its launch. Verdicts are a
pure function of a lane's record bytes — replay lanes never consume rng —
so every async answer is bit-identical to the synchronous path's
(tests/test_async_min.py pins this).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax

from .. import obs
from ..config import SchedulerConfig
from ..dsl import DSLApp
from ..external_events import ExternalEvent
from ..minimization.pipeline import (
    DEFAULT_SPECULATION_CAP,
    async_min_enabled,
    padded_bucket,
)
from ..minimization.test_oracle import IntViolation, TestOracle
from ..schedulers.replay import STSScheduler
from ..trace import EventTrace
from .core import DeviceConfig
from .encoding import CandidateLowerer, lower_expected_trace
from .replay import make_replay_kernel

#: Per-bucket-size replay key batches. Replay lanes never consume their
#: rng (injection and prescribed dispatch never split it), yet every
#: group/level used to rebuild ``jax.random.split(PRNGKey(0), bucket)``
#: from scratch — pure host churn on the minimization hot path. Bucket
#: sizes are power-of-two (plus mesh-rounded) so a handful of entries
#: serve a whole gamut run.
_REPLAY_KEYS: Dict[int, Any] = {}


def replay_keys(bucket: int):
    keys = _REPLAY_KEYS.get(bucket)
    if keys is None:
        keys = _REPLAY_KEYS[bucket] = jax.random.split(
            jax.random.PRNGKey(0), bucket
        )
    return keys


def default_device_config(
    app: DSLApp,
    trace: EventTrace,
    externals: Sequence[ExternalEvent],
    **overrides,
) -> DeviceConfig:
    """Size the static device shapes from the recorded execution: enough
    steps to replay the whole trace, enough pool for its peak concurrency
    (padded 2x for wildcard/backtrack variants), rounded up to multiples of
    8 so repeated gamut runs reuse compiled kernels."""

    def _round8(n: int) -> int:
        return max(8, (n + 7) // 8 * 8)

    n_events = len(trace.events)
    defaults = dict(
        pool_capacity=_round8(max(64, 2 * n_events)),
        max_steps=_round8(max(64, 2 * n_events)),
        max_external_ops=_round8(len(externals) + 8),
        invariant_interval=app.invariant_interval,
        # Minimization candidates shrink far below the shared static
        # record shape; early exit makes replay wall-clock track the
        # longest live candidate instead of the shape.
        early_exit=True,
    )
    defaults.update(overrides)
    return DeviceConfig.for_app(app, **defaults)


class DeviceReplayChecker:
    """Batched candidate checking for DSL apps: lower candidate expected
    traces, replay them all at once, compare violation codes."""

    def __init__(
        self,
        app: DSLApp,
        cfg: DeviceConfig,
        config: SchedulerConfig,
        mesh=None,
        prefix_fork: Optional[bool] = None,
        fork_bucket: int = 8,
        async_min: Optional[bool] = None,
    ):
        self.app = app
        self.cfg = cfg
        self.config = config
        self.mesh = mesh
        # A mesh shards each candidate batch over its lane axis (one DDMin
        # level spread across chips, SURVEY.md §2.8).
        # Launch-telemetry + profiler parity with the explore kernels:
        # every replay launch passes through _counted_kernel, so the
        # launch profiler (--profile-rounds on minimize) attributes
        # minimizer dispatches per shape exactly like dpor rounds.
        from .explore import _counted_kernel

        if mesh is not None:
            from ..parallel.mesh import shard_replay_kernel

            self.kernel = _counted_kernel(
                shard_replay_kernel(app, cfg, mesh), "replay-mesh"
            )
        else:
            self.kernel = _counted_kernel(
                make_replay_kernel(app, cfg), "replay"
            )
        self.max_records = cfg.max_steps + cfg.max_external_ops
        # Layout of the widest replay launch so far: devices its output
        # spanned and lanes on each (mesh.lane_sharding_summary).
        self.lane_sharding: Optional[dict] = None
        # Prefix-fork (device/fork.py, DEMI_PREFIX_FORK=1 / --prefix-fork):
        # a level's candidates are identical up to the first removed index,
        # so the shared prefix is replayed ONCE on a trunk lane and each
        # first-divergence bucket forks from the (LRU-cached) snapshot —
        # verdicts stay bit-identical to scratch replay.
        from .fork import prefix_fork_enabled

        self._forker = None
        if prefix_fork_enabled(prefix_fork):
            from .fork import PrefixForker, make_replay_prefix_runner

            if mesh is not None:
                from ..parallel.mesh import shard_replay_kernel

                self._fork_kernel = _counted_kernel(
                    shard_replay_kernel(app, cfg, mesh, start_state=True),
                    "replay-fork-mesh",
                )
            else:
                self._fork_kernel = _counted_kernel(
                    make_replay_kernel(app, cfg, start_state=True),
                    "replay-fork",
                )
            from .fork import make_replay_prefix_resume_runner

            self._forker = PrefixForker(
                make_replay_prefix_runner(app, cfg),
                bucket=fork_bucket,
                driver="replay",
                # Hierarchical trunks: derive a missing trunk by resuming
                # the nearest cached ancestor over only the remaining
                # bucket rows (bit-exact vs a scratch trunk run).
                resume_runner=make_replay_prefix_resume_runner(app, cfg),
            )
        # Async minimization pipeline (DEMI_ASYNC_MIN=1 / --async-min):
        # lower-once/gather-many candidate lowering, dispatch/harvest
        # split (verdicts stay on device until harvested), speculative
        # next-level candidates riding the idle padded lanes. Verdicts
        # are a pure function of a lane's record bytes (replay never
        # consumes rng), so every async answer is bit-identical to the
        # synchronous path's.
        self._async = async_min_enabled(async_min)
        # Streaming orchestration (demi_tpu/pipeline/budget.py): when a
        # LaunchBudget is attached, every replay launch reports its lane
        # count under the "minimize" tier — the shared in-flight ledger
        # the fuzz sweep reports into as "fuzz".
        self.launch_budget = None
        self._lowerer = (
            CandidateLowerer(app, cfg, self.max_records) if self._async else None
        )
        self._spec_cache: Dict[bytes, int] = {}
        self.pipeline_stats = {
            "dispatches": 0,
            "launches": 0,
            "lanes_launched": 0,
            "spec_dispatched": 0,
            "spec_hits": 0,
            "spec_waste": 0,
            "dispatch_seconds": 0.0,
            "overlap_seconds": 0.0,
            "harvest_wait_seconds": 0.0,
        }

    @property
    def async_enabled(self) -> bool:
        return self._async

    @property
    def fork_stats(self) -> Optional[dict]:
        """Prefix-fork statistics (None when forking is off)."""
        return None if self._forker is None else self._forker.stats_view()

    def pipeline_snapshot(self) -> dict:
        """Pipeline statistics + the lowering cache's view — what bench
        config 7 and the CLI surface (None-safe: zeros when async is
        off)."""
        out = dict(self.pipeline_stats)
        if self._lowerer is not None:
            out.update(
                {f"lower_{k}": v for k, v in self._lowerer.stats.items()}
            )
            out["lowering_cache_hit_rate"] = round(
                self._lowerer.hit_rate(), 3
            )
        from ..minimization.pipeline import overlap_fraction

        spec_total = out["spec_hits"] + out["spec_waste"]
        out["spec_hit_rate"] = (
            round(out["spec_hits"] / spec_total, 3) if spec_total else 0.0
        )
        out["overlap_fraction"] = round(overlap_fraction(out), 3)
        for k in ("overlap_seconds", "harvest_wait_seconds"):
            out[k] = round(out[k], 4)
        return out

    def prime_base(
        self, trace: EventTrace, externals: Sequence[ExternalEvent]
    ) -> None:
        """Register a level/round baseline with the gather lowerer so its
        candidate subsequences lower as row-gathers. No-op when async is
        off; a base too large for the static record shape is skipped
        (its candidates full-lower — correct, just slower)."""
        if self._lowerer is not None:
            try:
                self._lowerer.register_base(trace, list(externals))
            except ValueError:
                pass

    def verdicts(
        self,
        candidates: Sequence[EventTrace],
        externals_per_candidate: Sequence[Sequence[ExternalEvent]],
        target_code: int,
    ) -> List[bool]:
        if not candidates:
            return []
        if self._async:
            # Same codes, same order — dispatch/harvest back-to-back still
            # consults the speculative verdict cache and the gather
            # lowerer, so synchronous call sites share the pipeline's
            # host-side wins.
            return self.dispatch(
                candidates, externals_per_candidate, target_code
            ).harvest()
        records = np.stack(
            [
                lower_expected_trace(
                    self.app, self.cfg, cand, list(ext), self.max_records
                )
                for cand, ext in zip(candidates, externals_per_candidate)
            ]
        )
        n = len(candidates)
        with obs.span(
            "device.replay_batch", candidates=n
        ) as sp:
            if self._forker is not None and n >= 2:
                codes = self._forked_codes(records, n)
            else:
                codes = self._scratch_codes(records, n)
            hits = sum(int(c) == target_code for c in codes)
            sp.set(reproductions=hits)
        if obs.enabled():
            obs.counter("device.replay.candidates").inc(n)
            obs.counter("device.replay.reproductions").inc(hits)
        return [int(c) == target_code for c in codes]

    # -- async pipeline: dispatch/harvest split -----------------------------

    def dispatch(
        self,
        candidates: Sequence[EventTrace],
        externals_per_candidate: Sequence[Sequence[ExternalEvent]],
        target_code: int,
        speculate: Optional[
            Sequence[Tuple[EventTrace, Sequence[ExternalEvent]]]
        ] = None,
    ) -> "PendingVerdicts":
        """Launch every candidate's replay and return WITHOUT pulling the
        verdicts off device (no blocking ``np.asarray`` — not even per
        fork group). ``speculate`` offers next-level candidates that ride
        the launches' idle padded lanes (the lanes that today replay
        duplicate rows); their harvested codes seed a digest-keyed verdict
        cache the NEXT dispatch consults, so a correct prediction turns a
        whole level into cache hits. Requires ``async_min``."""
        if not self._async:
            raise RuntimeError(
                "DeviceReplayChecker.dispatch requires async_min "
                "(DEMI_ASYNC_MIN=1 / --async-min)"
            )
        t0 = time.perf_counter()
        n = len(candidates)
        pending = PendingVerdicts(self, n, target_code)
        if n == 0:
            return pending
        self.pipeline_stats["dispatches"] += 1
        lowered = [
            self._lowerer.lower(cand, list(ext))
            for cand, ext in zip(candidates, externals_per_candidate)
        ]
        records = np.stack([r for r, _ in lowered])
        # Consume the previous launch's speculative verdicts (digest-keyed:
        # a verdict is a pure function of the record bytes). The cache is
        # single-shot — whatever this dispatch doesn't consume was a
        # misprediction and is discarded.
        consumed = set()
        for i, (_, digest) in enumerate(lowered):
            code = self._spec_cache.get(digest)
            if code is not None:
                pending.codes[i] = code
                consumed.add(digest)
        waste = len(self._spec_cache) - len(consumed)
        if self._spec_cache:
            self.pipeline_stats["spec_hits"] += len(consumed)
            self.pipeline_stats["spec_waste"] += waste
            obs.counter("pipe.spec_hits").inc(len(consumed))
            obs.counter("pipe.spec_waste").inc(waste)
            # The measured free-lane hit rate, visible to the tuner in
            # every snapshot (force_set — same contract as tune.*
            # decisions): of the speculative lanes dispatched so far,
            # the fraction whose verdicts the next level consumed.
            total = (
                self.pipeline_stats["spec_hits"]
                + self.pipeline_stats["spec_waste"]
            )
            obs.REGISTRY.gauge("pipe.spec_hit_rate").force_set(
                round(self.pipeline_stats["spec_hits"] / total, 3)
            )
        self._spec_cache = {}
        todo = [i for i in range(n) if pending.codes[i] == pending.UNRESOLVED]
        spec_pool: List[list] = []
        for strace, sext in list(speculate or [])[:DEFAULT_SPECULATION_CAP]:
            srec, sdig = self._lowerer.lower(strace, list(sext))
            spec_pool.append([sdig, srec, False])
        if todo:
            if self._forker is not None and len(todo) >= 2:
                self._dispatch_forked(pending, records, todo, spec_pool)
            else:
                self._dispatch_scratch(pending, records, todo, spec_pool)
        elif spec_pool:
            # Every candidate was a speculation hit: the level costs no
            # launch at all, and the NEXT level's speculation rides a
            # padding-only launch sized to one bucket.
            self._dispatch_scratch(pending, records, [], spec_pool)
        pending.mark_dispatched(time.perf_counter() - t0)
        return pending

    def _dispatch_scratch(
        self,
        pending: "PendingVerdicts",
        records: np.ndarray,
        idxs: List[int],
        spec_pool: List[list],
    ) -> None:
        """Scratch-replay launch for candidate positions ``idxs``, with
        speculative candidates packed into the padding lanes (leftover
        padding replays row 0, exactly like the synchronous path)."""
        rows = [records[np.asarray(idxs, np.intp)]] if idxs else []
        m = len(idxs)
        # padded_bucket is the ONE bucket formula: speculation_room's
        # free-lane estimate in minimization/pipeline.py assumes it
        # matches the dispatch-side padding exactly.
        bucket = padded_bucket(m)
        if self.mesh is not None:
            from ..parallel.mesh import pad_batch_to_devices

            bucket = pad_batch_to_devices(bucket, self.mesh)
        spec_lanes: List[Tuple[int, bytes]] = []
        fill: List[np.ndarray] = []
        for entry in spec_pool:
            if m + len(fill) >= bucket:
                break
            if entry[2]:
                continue
            entry[2] = True
            spec_lanes.append((m + len(fill), entry[0]))
            fill.append(entry[1])
        if fill:
            rows.append(np.stack(fill))
        pad = bucket - m - len(fill)
        if pad:
            first = records[idxs[0]] if idxs else (
                fill[0] if fill else records[0]
            )
            rows.append(np.repeat(first[None], pad, axis=0))
        batch = np.concatenate(rows) if len(rows) > 1 else rows[0]
        res = self.kernel(batch, replay_keys(bucket))
        self._note_sharding(res.violation)
        self.pipeline_stats["launches"] += 1
        self.pipeline_stats["lanes_launched"] += bucket
        pending.lanes_launched += bucket
        if self.launch_budget is not None:
            self.launch_budget.note_dispatch("minimize", bucket)
        if obs.enabled():
            obs.counter("device.replay.pad_lanes").inc(pad)
        pending.add_part(
            res.violation,
            np.asarray(idxs, np.intp),
            np.arange(len(idxs), dtype=np.intp),
            spec_lanes,
        )

    def _dispatch_forked(
        self,
        pending: "PendingVerdicts",
        records: np.ndarray,
        idxs: List[int],
        spec_pool: List[list],
    ) -> None:
        """Prefix-fork launches with deferred harvest: same grouping,
        trunks (hierarchical), and fork kernels as ``_forked_codes``, but
        each group's violation vector stays on device until the pending
        handle is harvested. Speculative candidates ride a group's padding
        only when they share the group's prefix byte-exactly (their fork
        suffix is then well-defined); the rest ride the scratch launch."""
        from .fork import padded_size

        sub = records[np.asarray(idxs, np.intp)]
        lengths = (sub[:, :, 0] != 0).sum(axis=1)
        groups, scratch = self._forker.plan(sub, lengths)
        r = sub.shape[1]
        for g in groups:
            if not self._forker.should_fork(g):
                scratch.extend(g.indices)
                continue
            p = g.prefix_len
            trunk_records = np.zeros_like(sub[0])
            trunk_records[:p] = sub[g.indices[0], :p]
            snap, trunk_steps, hit = self._forker.trunk_hier(
                g.key, trunk_records, jax.random.PRNGKey(0), p
            )
            k = len(g.indices)
            suffixes = np.zeros((k, r, sub.shape[2]), np.int32)
            suffixes[:, : r - p] = sub[g.indices, p:]
            bucket = padded_size(k, self.mesh)
            spec_lanes: List[Tuple[int, bytes]] = []
            fill: List[np.ndarray] = []
            prefix_bytes = sub[g.indices[0], :p].tobytes()
            for entry in spec_pool:
                if k + len(fill) >= bucket:
                    break
                if entry[2] or entry[1][:p].tobytes() != prefix_bytes:
                    continue
                entry[2] = True
                spec_lanes.append((k + len(fill), entry[0]))
                srow = np.zeros((r, sub.shape[2]), np.int32)
                srow[: r - p] = entry[1][p:]
                fill.append(srow)
            parts = [suffixes]
            if fill:
                parts.append(np.stack(fill))
            pad = bucket - k - len(fill)
            if pad:
                parts.append(np.repeat(suffixes[:1], pad, axis=0))
            batch = np.concatenate(parts) if len(parts) > 1 else parts[0]
            res = self._fork_kernel(batch, replay_keys(bucket), snap)
            self.pipeline_stats["launches"] += 1
            self.pipeline_stats["lanes_launched"] += bucket
            pending.lanes_launched += bucket
            if self.launch_budget is not None:
                self.launch_budget.note_dispatch("minimize", bucket)
            pending.add_part(
                res.violation,
                np.asarray([idxs[i] for i in g.indices], np.intp),
                np.arange(k, dtype=np.intp),
                spec_lanes,
            )
            self._forker.note_group(k, trunk_steps, hit)
        if scratch:
            self._dispatch_scratch(
                pending, records, [idxs[i] for i in scratch], spec_pool
            )
            self._forker.note_scratch(len(scratch))
        # Leftover speculation (no scratch launch, no prefix-compatible
        # group padding) is simply dropped: speculation only ever rides
        # lanes that already exist — it never pays for its own launch.

    def _note_sharding(self, violation_dev) -> None:
        from ..parallel.mesh import lane_sharding_summary

        seen = lane_sharding_summary(violation_dev)
        width = seen["devices"] * seen["lanes_per_device"]
        prev = self.lane_sharding
        if prev is None or width > prev["devices"] * prev["lanes_per_device"]:
            self.lane_sharding = seen

    def _pull_codes(self, violation_dev, bucket: int) -> np.ndarray:
        """The ONE blocking verdict pull of the synchronous paths:
        budget-ledgered (dispatch+harvest bracket the inline block) and
        profiler-attributed as a harvest block, so minimizer launches
        show up in the launch ledger the way dpor rounds do."""
        from ..obs.profiler import PROFILER

        if self.launch_budget is not None:
            self.launch_budget.note_dispatch("minimize", bucket)
        t0 = time.perf_counter() if PROFILER.enabled else 0.0
        arr = np.asarray(violation_dev)
        if PROFILER.enabled:
            PROFILER.block("replay", bucket, time.perf_counter() - t0)
        self._note_sharding(violation_dev)
        if self.launch_budget is not None:
            self.launch_budget.note_harvest("minimize", bucket)
        return arr

    def _scratch_codes(self, records: np.ndarray, n: int) -> np.ndarray:
        """Replay ``records`` from step 0 and return per-lane violation
        codes. Pads the batch axis to a power-of-two bucket: DDMin levels
        and removal rounds shrink the candidate count every iteration, and
        an unpadded batch would recompile the kernel per distinct size
        (profiled: a 150-delivery raft case spent ~4 min, ~100 compiles,
        in ONE internal stage). Padding rows replay candidate 0 again;
        their verdicts are sliced off."""
        bucket = padded_bucket(n)
        if self.mesh is not None:
            from ..parallel.mesh import pad_batch_to_devices

            bucket = pad_batch_to_devices(bucket, self.mesh)
        if bucket > n:
            records = np.concatenate(
                [records, np.repeat(records[:1], bucket - n, axis=0)]
            )
        res = self.kernel(records, replay_keys(bucket))
        if obs.enabled():
            obs.counter("device.replay.pad_lanes").inc(bucket - n)
        return self._pull_codes(res.violation, bucket)[:n]

    def _forked_codes(self, records: np.ndarray, n: int) -> np.ndarray:
        """Prefix-fork verdicts: group candidates by bucketed shared
        prefix, replay each group's trunk once (LRU-cached across calls —
        consecutive ddmin levels and internal rounds share trunks), fork
        the lanes over the remaining suffixes. Groups too small to
        amortize a trunk fall back to the scratch kernel."""
        from .fork import padded_size

        lengths = (records[:, :, 0] != 0).sum(axis=1)
        groups, scratch = self._forker.plan(records, lengths)
        codes = np.zeros(n, np.int32)
        r = records.shape[1]
        for g in groups:
            if not self._forker.should_fork(g):
                scratch.extend(g.indices)
                continue
            p = g.prefix_len
            trunk_records = np.zeros_like(records[0])
            trunk_records[:p] = records[g.indices[0], :p]
            snap, trunk_steps, hit = self._forker.trunk_hier(
                g.key, trunk_records, jax.random.PRNGKey(0), p
            )
            suffixes = np.zeros(
                (len(g.indices), r, records.shape[2]), np.int32
            )
            suffixes[:, : r - p] = records[g.indices, p:]
            bucket = padded_size(len(g.indices), self.mesh)
            if bucket > len(g.indices):
                suffixes = np.concatenate(
                    [suffixes, np.repeat(suffixes[:1], bucket - len(g.indices), axis=0)]
                )
            res = self._fork_kernel(suffixes, replay_keys(bucket), snap)
            codes[np.asarray(g.indices)] = self._pull_codes(
                res.violation, bucket
            )[: len(g.indices)]
            self._forker.note_group(len(g.indices), trunk_steps, hit)
        if scratch:
            codes[np.asarray(scratch)] = self._scratch_codes(
                records[np.asarray(scratch)], len(scratch)
            )
            self._forker.note_scratch(len(scratch))
        return codes

    def host_executed_trace(
        self,
        candidate: EventTrace,
        externals: Sequence[ExternalEvent],
        violation: Any,
    ) -> Optional[EventTrace]:
        # Keep the tiers' replay power matched: when the device kernel
        # peeks (cfg.replay_peek), the host bookkeeping replay must too,
        # with the SAME prefix budget — a larger host budget would let a
        # candidate host-verify via a longer peek than the device oracle
        # that selected it allows (and vice versa on re-runs).
        sts = STSScheduler(
            self.config, candidate,
            allow_peek=self.cfg.replay_peek > 0,
            max_peek_messages=self.cfg.replay_peek,
        )
        return sts.test_with_trace(candidate, list(externals), violation)


class PendingVerdicts:
    """Handle for a dispatched candidate batch: verdict codes stay on
    device (one ``np.asarray`` per launch happens only inside
    ``harvest``), so the host plans — and speculatively executes — while
    the device crunches. The wall clock between dispatch-return and
    harvest is the pipeline's overlap; the blocking pull inside harvest
    is what's left of the old per-group stall."""

    UNRESOLVED = -(1 << 40)  # outside the int32 violation-code range

    def __init__(self, checker: DeviceReplayChecker, n: int, target_code: int):
        self.checker = checker
        self.n = n
        self.target_code = target_code
        self.codes = np.full(n, self.UNRESOLVED, np.int64)
        self._parts: List[tuple] = []
        self._dispatched_at: Optional[float] = None
        self._verdicts: Optional[List[bool]] = None
        # Lanes launched for this handle (budget ledger: dispatched at
        # launch, harvested when the codes are pulled below).
        self.lanes_launched = 0

    def add_part(self, violation_dev, cand_idx, lane_idx, spec_lanes) -> None:
        self._parts.append((violation_dev, cand_idx, lane_idx, spec_lanes))

    def mark_dispatched(self, dispatch_seconds: float) -> None:
        self.checker.pipeline_stats["dispatch_seconds"] += dispatch_seconds
        self._dispatched_at = time.perf_counter()

    def harvest(self) -> List[bool]:
        """Pull every part's codes host-side (idempotent) and seed the
        checker's speculative verdict cache from the spec lanes."""
        if self._verdicts is not None:
            return self._verdicts
        stats = self.checker.pipeline_stats
        if self._dispatched_at is not None:
            overlap = time.perf_counter() - self._dispatched_at
            stats["overlap_seconds"] += overlap
            obs.counter("pipe.overlap_seconds").inc(overlap)
        t0 = time.perf_counter()
        spec_count = 0
        for violation_dev, cand_idx, lane_idx, spec_lanes in self._parts:
            arr = np.asarray(violation_dev)
            if cand_idx.size:
                self.codes[cand_idx] = arr[lane_idx]
            for lane, digest in spec_lanes:
                self.checker._spec_cache[digest] = int(arr[lane])
                spec_count += 1
        self._parts = []
        wait = time.perf_counter() - t0
        stats["harvest_wait_seconds"] += wait
        stats["spec_dispatched"] += spec_count
        obs.counter("pipe.harvest_wait_seconds").inc(wait)
        if self.lanes_launched:
            from ..obs.profiler import PROFILER

            if PROFILER.enabled:
                PROFILER.block("replay", self.lanes_launched, wait)
            if self.checker.launch_budget is not None:
                self.checker.launch_budget.note_harvest(
                    "minimize", self.lanes_launched
                )
            self.lanes_launched = 0
        if obs.enabled():
            # Host-vs-device split of the pipeline's round-trip time:
            # overlap_seconds is host planning done UNDER device
            # execution, harvest_wait is blocked on the device.
            total = stats["overlap_seconds"] + stats["harvest_wait_seconds"]
            if total > 0:
                obs.gauge("pipe.host_share").set(
                    stats["overlap_seconds"] / total
                )
        if spec_count:
            obs.counter("pipe.spec_dispatched").inc(spec_count)
        if self.n and bool((self.codes == self.UNRESOLVED).any()):
            raise RuntimeError(
                "PendingVerdicts.harvest: unresolved candidate lanes"
            )
        self._verdicts = [int(c) == self.target_code for c in self.codes]
        if obs.enabled():
            obs.counter("device.replay.candidates").inc(self.n)
            obs.counter("device.replay.reproductions").inc(
                sum(self._verdicts)
            )
        return self._verdicts


def make_batched_internal_check(
    checker: DeviceReplayChecker,
    externals: Sequence[ExternalEvent],
    violation: IntViolation,
) -> Callable[[List[EventTrace]], List[Optional[EventTrace]]]:
    """batch_check for BatchedInternalMinimizer: device verdicts for all
    candidates, host execution only for the first reproducing one.

    The returned closure also carries the async-pipeline surface the
    speculative minimizer round uses when the checker runs with
    ``async_min``: ``dispatch_round`` (non-blocking launch with a base
    hint for the gather lowerer + speculative next-round candidates),
    ``host_execute`` (the bookkeeping STS execution, callable BETWEEN
    dispatch and harvest so it overlaps device work), and
    ``supports_async``."""

    def batch_check(candidates: List[EventTrace]) -> List[Optional[EventTrace]]:
        verdicts = checker.verdicts(
            candidates, [externals] * len(candidates), violation.code
        )
        out: List[Optional[EventTrace]] = [None] * len(candidates)
        for i, ok in enumerate(verdicts):
            if ok:
                executed = checker.host_executed_trace(
                    candidates[i], externals, violation
                )
                if executed is not None:
                    out[i] = executed
                    break
        return out

    def dispatch_round(
        candidates: List[EventTrace],
        base: Optional[EventTrace] = None,
        speculate: Optional[List[EventTrace]] = None,
    ) -> PendingVerdicts:
        if base is not None:
            checker.prime_base(base, externals)
        return checker.dispatch(
            candidates,
            [externals] * len(candidates),
            violation.code,
            speculate=[(s, externals) for s in (speculate or [])],
        )

    def host_execute(candidate: EventTrace) -> Optional[EventTrace]:
        return checker.host_executed_trace(candidate, externals, violation)

    batch_check.dispatch_round = dispatch_round
    batch_check.host_execute = host_execute
    batch_check.supports_async = checker.async_enabled
    return batch_check


class DeviceSTSOracle(TestOracle):
    """TestOracle for external-event DDMin backed by the device replay
    kernel: each test() lowers the projected candidate and replays it on
    device; positives are re-executed on the host for the bookkeeping trace.
    ``test_batch`` checks a whole DDMin level at once."""

    def __init__(
        self,
        app: DSLApp,
        cfg: DeviceConfig,
        config: SchedulerConfig,
        original_trace: EventTrace,
        checker: Optional[DeviceReplayChecker] = None,
    ):
        # Pass a shared checker to reuse one compiled replay kernel across
        # pipeline stages.
        self.checker = checker or DeviceReplayChecker(app, cfg, config)
        self.original_trace = original_trace
        self.config = config
        self._primed = False

    @property
    def supports_async(self) -> bool:
        """True when the backing checker runs the async pipeline — what
        the speculative minimizers probe before using dispatch_batch /
        test_window."""
        return self.checker.async_enabled

    def _project(self, externals: Sequence[ExternalEvent]) -> EventTrace:
        return (
            self.original_trace.filter_failure_detector_messages()
            .filter_checkpoint_messages()
            .subsequence_intersection(
                list(externals),
                filter_known_absents=self.config.filter_known_absents,
            )
        )

    def test(self, externals, violation_fingerprint, stats=None, init=None):
        if stats is not None:
            stats.record_replay()
        projected = self._project(externals)
        ok = self.checker.verdicts(
            [projected], [externals], violation_fingerprint.code
        )[0]
        if not ok:
            return None
        return self.checker.host_executed_trace(
            projected, externals, violation_fingerprint
        )

    def test_batch(
        self,
        candidates: Sequence[Sequence[ExternalEvent]],
        violation_fingerprint,
    ) -> List[bool]:
        self._prime()
        projected = [self._project(c) for c in candidates]
        return self.checker.verdicts(
            projected, candidates, violation_fingerprint.code
        )

    def _prime(self) -> None:
        """Register the MASTER base with the gather lowerer: the filtered
        original trace. Every candidate projection — any external subset,
        any known-absent pruning outcome — is an event-subsequence of it
        (projection only ever drops events), so one registration serves
        every ddmin level."""
        if not self.checker.async_enabled or self._primed:
            return
        self._primed = True
        ext = self.original_trace.original_externals
        if ext is None:
            return
        master = (
            self.original_trace.filter_failure_detector_messages()
            .filter_checkpoint_messages()
        )
        self.checker.prime_base(master, list(ext))

    def dispatch_batch(
        self,
        candidates: Sequence[Sequence[ExternalEvent]],
        violation_fingerprint,
        speculate: Optional[Sequence[Sequence[ExternalEvent]]] = None,
    ) -> PendingVerdicts:
        """Non-blocking ``test_batch``: returns the pending handle, with
        ``speculate`` (the predicted NEXT level's candidates) riding the
        launch's idle padded lanes. Requires the checker's async mode."""
        self._prime()
        projected = [self._project(c) for c in candidates]
        spec = [(self._project(s), s) for s in (speculate or [])]
        return self.checker.dispatch(
            projected, candidates, violation_fingerprint.code, speculate=spec
        )

    def test_window(
        self,
        candidates: Sequence[Sequence[ExternalEvent]],
        violation_fingerprint,
    ) -> List[Callable[[], Optional[EventTrace]]]:
        """One device launch for a whole speculation window of ``test``
        calls: returns per-candidate lazy resolvers. ``resolvers[i]()``
        behaves exactly like ``test(candidates[i], ...)`` — device verdict
        gates a host bookkeeping execution — but the device work for the
        whole window was batched up front, so a sequential scan that
        consults only a prefix of the window (stopping at its first
        reproduction) discards the rest as speculation waste."""
        self._prime()
        projected = [self._project(c) for c in candidates]
        verdicts = self.checker.verdicts(
            projected, candidates, violation_fingerprint.code
        )

        def resolver(i: int) -> Optional[EventTrace]:
            if not verdicts[i]:
                return None
            return self.checker.host_executed_trace(
                projected[i], candidates[i], violation_fingerprint
            )

        return [
            (lambda i=i: resolver(i)) for i in range(len(candidates))
        ]
