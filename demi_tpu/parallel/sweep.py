"""Sweep driver: schedule-space sweeps at slice and multi-slice scale.

Scale model (SURVEY.md §2.8/§5.8): within a slice, the lane batch shards
over ICI via the mesh kernels (mesh.py); across slices, the *seed/program
space* partitions — each slice takes a disjoint chunk and only violation
summaries travel over DCN (they're O(lanes), not O(state)). In a
multi-process jax.distributed deployment each process calls
``run_chunk`` on its slice's mesh with its ``slice_index``; in-process, the
driver iterates chunks (the single-host path the driver/bench use).

Also provides time-to-first-violation measurement — the BASELINE.md
headline metric against the JVM reference.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs
from ..dsl import DSLApp
from ..device.core import (
    ST_OVERFLOW,
    ST_UNFINISHED,
    ST_VIOLATION,
    DeviceConfig,
)
from ..device.encoding import lower_program, stack_programs
from ..device.explore import make_explore_kernel
from ..external_events import ExternalEvent
from .mesh import (
    LANES,
    lane_sharding_summary,
    make_mesh,
    shard_explore_kernel,
)


@dataclass
class SweepChunkResult:
    slice_index: int
    lanes: int
    violations: int
    codes: dict
    first_violating_lane: Optional[int]  # chunk-local lane index (None: continuous)
    first_violation_code: Optional[int]
    seconds: float
    # The SEED of the first violating lane (global, replayable) — what
    # callers should report; first_violating_lane is chunk-local.
    first_violating_seed: Optional[int] = None
    # Lanes aborted with ST_OVERFLOW (pool too small): these completed no
    # verdict, so any nonzero count means the sweep's numbers undercount.
    overflow_lanes: int = 0
    # Lanes that ran out of steps before quiescence under an invariant
    # judged at quiescence only (ST_UNFINISHED): no verdict either.
    unfinished_lanes: int = 0
    # Deduped device-side schedule fingerprints (LaneResult.sched_hash)
    # for this chunk's real lanes: the honest "unique schedules" numerator.
    unique_hashes: Optional[np.ndarray] = None
    # Order-free digest of this chunk's per-lane (seed, status, code,
    # sched_hash) rows (``lanes_digest``).
    lanes_digest: int = 0
    # Devices the kernel output spanned and lanes on each
    # (``mesh.lane_sharding_summary``).
    lane_sharding: Optional[dict] = None


def lanes_digest(seeds, statuses, codes, hashes) -> int:
    """Order-free 64-bit digest of per-lane results: a wrapping sum of a
    per-lane mix of (seed, status, violation code, sched_hash). Two sweeps
    share it iff (modulo collisions) every seed produced the same three
    values — whatever the harvest order, chunking, mode or number of
    devices. This is how a 4-chip run is compared with a 1-chip run."""
    m = np.uint64(0xFFFFFFFF)
    x = ((np.asarray(seeds).astype(np.uint64) & m) << np.uint64(32)) | (
        np.asarray(hashes).astype(np.uint64) & m
    )
    y = ((np.asarray(statuses).astype(np.uint64) & m) << np.uint64(32)) | (
        np.asarray(codes).astype(np.uint64) & m
    )
    # splitmix64 finalizer over the two words.
    z = x * np.uint64(0x9E3779B97F4A7C15) + y
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    z = z ^ (z >> np.uint64(31))
    return int(z.sum(dtype=np.uint64))


@dataclass
class SweepResult:
    chunks: List[SweepChunkResult] = field(default_factory=list)
    # Lane-step occupancy of the sweep (continuous mode only): fraction of
    # scanned lane-steps spent on live lanes. Chunked sweeps leave it None.
    occupancy: Optional[float] = None
    # Wall-clock seconds of the whole sweep, set by ``SweepDriver.sweep``
    # / ``sweep_autotuned``. Per-chunk ``seconds`` overlap under async
    # dispatch (each spans dispatch→harvest), so their sum double-counts
    # overlapped time; this is the honest denominator for throughput.
    wall_seconds: Optional[float] = None

    @property
    def lanes(self) -> int:
        return sum(c.lanes for c in self.chunks)

    @property
    def violations(self) -> int:
        return sum(c.violations for c in self.chunks)

    @property
    def schedules_per_sec(self) -> float:
        """Throughput from SUMMED per-chunk seconds. Only meaningful when
        chunks never overlapped (strictly sequential harvesting); under
        ``sweep_async`` double-buffering the sum double-counts wall time.
        Prefer ``schedules_per_sec_wall``."""
        secs = sum(c.seconds for c in self.chunks)
        return self.lanes / secs if secs > 0 else 0.0

    @property
    def schedules_per_sec_wall(self) -> float:
        """Wall-clock throughput (the number bench/report quote). Falls
        back to the summed-seconds rate for results built chunk-by-chunk
        outside the driver (no wall clock recorded)."""
        if self.wall_seconds and self.wall_seconds > 0:
            return self.lanes / self.wall_seconds
        return self.schedules_per_sec

    @property
    def codes(self) -> dict:
        """Violation-code counts summed across chunks."""
        merged: dict = {}
        for c in self.chunks:
            for code, n in c.codes.items():
                merged[code] = merged.get(code, 0) + n
        return merged

    @property
    def first_violating_seed(self) -> Optional[int]:
        for c in self.chunks:
            if c.first_violating_seed is not None:
                return c.first_violating_seed
        return None

    @property
    def overflow_lanes(self) -> int:
        return sum(c.overflow_lanes for c in self.chunks)

    @property
    def unfinished_lanes(self) -> int:
        return sum(c.unfinished_lanes for c in self.chunks)

    @property
    def lanes_digest(self) -> int:
        return sum(c.lanes_digest for c in self.chunks) % (1 << 64)

    @property
    def lane_sharding(self) -> Optional[dict]:
        for c in self.chunks:
            if c.lane_sharding is not None:
                return c.lane_sharding
        return None

    @property
    def unique_schedules(self) -> int:
        """Distinct delivered sequences across the whole sweep (union of
        per-chunk fingerprint sets)."""
        parts = [
            c.unique_hashes for c in self.chunks if c.unique_hashes is not None
        ]
        if not parts:
            return 0
        return int(np.unique(np.concatenate(parts)).size)


class _HarvestAccumulator:
    """Vectorized retirement accumulation shared by the continuous sweep
    paths (plain and autotuned): consumes per-round ``(seeds, statuses,
    codes, hashes)`` arrays from ``ContinuousSweepDriver._run_batches``
    and folds them with array ops — no per-lane Python loop on the
    harvest path."""

    def __init__(self):
        self.lanes = 0
        self.violations = 0
        self.overflow = 0
        self.unfinished = 0
        self.codes: dict = {}
        self.first_seed: Optional[int] = None
        self.first_code: Optional[int] = None
        self.digest = 0
        self._hash_parts: List[np.ndarray] = []

    def add(self, seeds, statuses, codes, hashes) -> None:
        self.lanes += len(seeds)
        self.digest = (
            self.digest + lanes_digest(seeds, statuses, codes, hashes)
        ) % (1 << 64)
        self.overflow += int((statuses == ST_OVERFLOW).sum())
        self.unfinished += int((statuses == ST_UNFINISHED).sum())
        # Lanes with a verdict: a cut lane's fingerprint is no schedule.
        self._hash_parts.append(
            np.asarray(hashes)[statuses <= ST_VIOLATION]
        )
        vio = codes != 0
        if vio.any():
            self.violations += int(vio.sum())
            uniq, cnt = np.unique(codes[vio], return_counts=True)
            for code, k in zip(uniq.tolist(), cnt.tolist()):
                self.codes[int(code)] = self.codes.get(int(code), 0) + int(k)
            if self.first_seed is None:
                k = int(np.flatnonzero(vio)[0])
                self.first_seed = int(seeds[k])
                self.first_code = int(codes[k])

    def unique_hashes(self) -> np.ndarray:
        if not self._hash_parts:
            return np.unique(np.asarray([], np.uint32))
        return np.unique(
            np.concatenate(self._hash_parts).astype(np.uint32, copy=False)
        )

    def chunk(self, slice_index: int, seconds: float) -> SweepChunkResult:
        return SweepChunkResult(
            slice_index=slice_index,
            lanes=self.lanes,
            violations=self.violations,
            codes=self.codes,
            first_violating_lane=None,  # continuous mode: no chunk-local index
            first_violation_code=self.first_code,
            seconds=seconds,
            overflow_lanes=self.overflow,
            unfinished_lanes=self.unfinished,
            unique_hashes=self.unique_hashes(),
            first_violating_seed=self.first_seed,
            lanes_digest=self.digest,
        )


class _RewardBucket:
    """Segment-boundary reward attribution for continuous autotuned
    sweeps (the proposal-epoch bucketing that used to live in its own
    driver copy): retirements arrive as arrays, are filtered to the
    epoch that GENERATED them, and an epoch's ``end_round`` fires the
    moment ``chunk_size`` of its own lanes retired — mid-array
    boundaries split exactly where the per-item loop would have fired.
    Nothing is ever mis-credited: a straggler whose epoch already closed
    still counts in the sweep result but not in any reward."""

    def __init__(self, controller, chunk_size: int, epoch_of_seed: dict,
                 cur_epoch: List[int]):
        self.controller = controller
        self.chunk_size = chunk_size
        self.epoch_of_seed = epoch_of_seed
        self.cur_epoch = cur_epoch
        self.lanes = 0
        self.violations = 0
        self.dropped = 0
        self._hash_parts: List[np.ndarray] = []

    def add(self, seeds, statuses, codes, hashes) -> None:
        n = len(seeds)
        # Generation is the ONLY moment fuzzer weights touch a lane, so
        # the tag recorded then is exact attribution. A seed with no tag
        # (never generated under this wrapper) defaults to the epoch
        # current when it is PROCESSED — evaluated per split segment,
        # exactly like the per-item loop's ``.get(seed, cur)``.
        tags = np.fromiter(
            (self.epoch_of_seed.get(int(s), -1) for s in seeds),
            np.int64, n,
        )
        untagged = tags < 0
        pos = 0
        while pos < n:
            cur = self.cur_epoch[0]
            mine = (tags[pos:] == cur) | untagged[pos:]
            idx_mine = np.flatnonzero(mine)
            need = self.chunk_size - self.lanes
            if len(idx_mine) < need:
                take = n - pos  # bucket can't fill: consume the rest
            else:
                take = int(idx_mine[need - 1]) + 1  # through the filler
            m = mine[:take]
            sl = slice(pos, pos + take)
            n_dropped = int((~m).sum())
            if n_dropped:
                self.dropped += n_dropped
                obs.counter("tune.continuous_dropped").inc(n_dropped)
            self.lanes += int(m.sum())
            st, cd = statuses[sl][m], codes[sl][m]
            self._hash_parts.append(
                np.asarray(hashes[sl])[m][st <= ST_VIOLATION]
            )
            self.violations += int((cd != 0).sum())
            if self.lanes >= self.chunk_size:
                self._fire()
                # The next refill's programs generate under the new
                # proposal; already-running lanes keep their old tag.
                self.cur_epoch[0] += 1
                self.controller.begin_round()
            pos += take

    def _fire(self) -> None:
        hashes = (
            np.concatenate(self._hash_parts).tolist()
            if self._hash_parts else []
        )
        self.controller.end_round(
            hashes=hashes, violations=self.violations, lanes=self.lanes,
        )
        obs.counter("tune.continuous_epochs").inc()
        self.lanes = self.violations = 0
        self._hash_parts = []

    def close(self) -> None:
        """Close the final partial epoch — but only if it actually
        retired lanes: scoring an empty bucket would charge the last
        proposal a fabricated zero reward for lanes it never generated.
        Skipping the end_round leaves that proposal un-evaluated, which
        the WeightTuner handles (the next propose() discards the pending
        trial without adopting it)."""
        if self.lanes:
            self._fire()


def segment_steps(max_steps: int) -> int:
    """The steps of one segment of a continuous sweep whose schedules run
    ``max_steps``: a quarter of a schedule, within 8 and 64."""
    return max(8, min(64, max_steps // 4))


class SweepDriver:
    @obs.spans.staged("setup.build", what="SweepDriver")
    def __init__(
        self,
        app: DSLApp,
        cfg: DeviceConfig,
        program_gen: Callable[[int], Sequence[ExternalEvent]],
        mesh=None,
        use_mesh: bool = False,
        variant: Optional[str] = None,
        prefix_fork: Optional[bool] = None,
    ):
        """``variant`` (an ``EXPLORE_VARIANTS`` name, e.g. the autotuner's
        calibrated pick) selects the single-host kernel build: '-ee' /
        '-round' fold into cfg, the lane axis into kernel construction.
        Round variants coarsen invariant checks to round granularity —
        callers pass them only when that is semantics-preserving (``invariant_interval == 0``), which is the
        rule the autotuner itself applies. None is the default build.

        ``prefix_fork`` (default: the DEMI_PREFIX_FORK env switch) makes
        the CHUNKED dispatch path group a chunk's lanes by shared
        injection prefix — program rows up to one past the first
        wait-like op — run each group's deterministic injection segment
        once on a trunk lane (LRU-cached across chunks) and fork the
        group from the snapshot via the ``start_state=`` kernel with
        per-lane rng. Injection never consumes rng, so per-seed results
        are bit-identical to scratch. Continuous-mode sweeps (the
        single-slice default) refill mid-flight and keep their own
        compaction; forking applies to run_chunk / sweep(mode='chunked')
        / sweep_async / sweep_autotuned."""
        from ..device.explore import variant_config

        if variant is not None:
            cfg = variant_config(cfg, variant)
        self.app = app
        self.cfg = cfg
        self.program_gen = program_gen
        self.variant = variant
        if use_mesh:
            # make_explore_kernel and make_explore_kernel_variant count
            # their own launches; the sharded constructor does not.
            from ..device.explore import _counted_kernel

            self.mesh = mesh or make_mesh()
            self.kernel = _counted_kernel(
                shard_explore_kernel(app, cfg, self.mesh), "explore-mesh"
            )
            self._align = self.mesh.shape[LANES]
        else:
            self.mesh = None
            if variant is not None:
                from ..device.explore import make_explore_kernel_variant

                self.kernel = make_explore_kernel_variant(app, cfg, variant)
            else:
                self.kernel = make_explore_kernel(app, cfg)
            self._align = 1
        self._cont_cache = None
        # Continuous observability (obs/journal.py): 1-based chunk
        # counter for the round journal; a checkpointed resume seeds it
        # at the restored chunk count so the journal stays contiguous.
        self.chunk_index = 0
        # Streaming handoff (demi_tpu/pipeline/): called with the
        # violating retirements' (seeds, codes) arrays at every chunk
        # harvest / continuous retirement batch — the sweep keeps
        # running; the hook's owner queues the lanes for minimization.
        self.violation_hook = None
        # Shared fuzz/minimize in-flight ledger (pipeline/budget.py):
        # when attached, every chunk dispatch/harvest reports its lane
        # count under the "fuzz" tier.
        self.launch_budget = None
        # Host-share ledger (always on — a few clock reads per chunk):
        # wall time on host planning/lowering/harvest accumulation vs
        # device segments / blocked kernel waits. Continuous sweeps split
        # exactly (the status pull is the sync point); chunked sweeps
        # attribute the block_until_ready wait as device time. The
        # sweep.host_share gauge and bench config 5 read this.
        self.host_seconds = 0.0
        self.device_seconds = 0.0
        # The request number of the sweep in progress (obs.new_job() at
        # each sweep()/sweep_autotuned() entry): its root spans carry it.
        self._job: Optional[int] = None
        from ..device.fork import prefix_fork_enabled

        self._forker = None
        if prefix_fork_enabled(prefix_fork):
            from ..device.fork import (
                PrefixForker,
                make_explore_prefix_base_runner,
                make_explore_prefix_resume_runner,
                make_explore_prefix_runner,
            )

            self._fork_kernel = (
                shard_explore_kernel(app, self.cfg, self.mesh, start_state=True)
                if self.mesh is not None
                else make_explore_kernel(app, self.cfg, start_state=True)
            )
            self._forker = PrefixForker(
                make_explore_prefix_runner(app, self.cfg), driver="sweep",
                # Prescribed-resume trunks, sweep flavor: group trunks
                # derive from the chunk-wide BASE trunk (the injection
                # rows every lane shares) over just their remaining rows.
                resume_runner=make_explore_prefix_resume_runner(app, self.cfg),
            )
            self._base_runner = make_explore_prefix_base_runner(app, self.cfg)

    @property
    def fork_stats(self) -> Optional[dict]:
        """Prefix-fork statistics (None when forking is off)."""
        return None if self._forker is None else self._forker.stats_view()

    @property
    def host_share(self) -> Optional[float]:
        """Fraction of sweep wall time spent host-side (None until a
        sweep ran) — the vectorized-host-path health number."""
        total = self.host_seconds + self.device_seconds
        return self.host_seconds / total if total > 0 else None

    def _note_share(self, host_secs: float, device_secs: float) -> None:
        self.host_seconds += host_secs
        self.device_seconds += device_secs
        if obs.enabled():
            obs.counter("sweep.host_seconds").inc(host_secs)
            obs.counter("sweep.device_seconds").inc(device_secs)
            share = self.host_share
            if share is not None:
                obs.gauge("sweep.host_share").set(share)

    def _programs(self, seeds: Sequence[int]):
        # Lowered per call: seeds are disjoint across chunks, so a
        # driver-lifetime cache would only ever grow (sweeps can cover 1M+
        # seeds). Pad-duplicates within the chunk hit the local cache.
        cache: dict = {}
        progs = []
        for s in seeds:
            prog = cache.get(s)
            if prog is None:
                prog = lower_program(self.app, self.cfg, self.program_gen(s))
                cache[s] = prog
            progs.append(prog)
        return stack_programs(progs)

    def _dispatch_chunk(
        self,
        seeds: Sequence[int],
        base_key: int = 0,
        base_keys: Optional[Sequence[int]] = None,
    ):
        """Launch one chunk's kernel WITHOUT blocking (jax async
        dispatch); pair with ``_harvest_chunk``.

        ``base_keys`` (parallel to ``seeds``) gives each lane its own
        rng base — the multi-tenant mixed-chunk shape (demi_tpu/service):
        tenants' lanes share one launch but each lane's key is still
        ``fold_in(PRNGKey(base), seed)``, the exact value the lane gets
        in a dedicated solo run, so mixing changes which launch a lane
        rides, never what it computes."""
        real = list(seeds)
        assert real, "empty chunk"
        padded = list(real)
        if base_keys is not None:
            assert len(base_keys) == len(real), "base_keys/seeds mismatch"
            bases = list(base_keys)
        while len(padded) % self._align:
            take = self._align - (len(padded) % self._align)
            padded.extend(real[:take])
            if base_keys is not None:
                bases.extend(bases[:take])
        progs = self._programs(padded)
        if base_keys is None:
            keys = jax.vmap(
                lambda s: jax.random.fold_in(jax.random.PRNGKey(base_key), s)
            )(np.asarray(padded, np.uint32))
        else:
            keys = jax.vmap(
                lambda s, b: jax.random.fold_in(jax.random.PRNGKey(b), s)
            )(
                np.asarray(padded, np.uint32),
                np.asarray(bases, np.uint32),
            )
        t0 = time.perf_counter()
        if self._forker is not None:
            res = self._dispatch_forked(progs, keys)
        else:
            res = self.kernel(progs, keys)
        if self.launch_budget is not None:
            self.launch_budget.note_dispatch("fuzz", len(real))
        return real, res, t0

    def _dispatch_forked(self, progs, keys):
        """Chunked dispatch with prefix forking: lanes grouped by shared
        injection prefix, each group resumed from a (cached) trunk
        snapshot; singletons with no cached trunk run the scratch kernel.
        Group results are sliced, concatenated, and inverse-permuted back
        to chunk order ON DEVICE, so async dispatch is preserved."""
        from ..device.core import OP_END, OP_WAIT, OP_WAITCOND
        from ..device.explore import LaneResult
        from ..device.fork import padded_size, prefix_digest

        self._forker.resolve_deferred()  # prior chunk's steps_saved terms
        op = np.asarray(progs.op)
        a, b, msg = np.asarray(progs.a), np.asarray(progs.b), np.asarray(progs.msg)
        batch = op.shape[0]
        groups: dict = {}
        min_j = op.shape[1]
        for i in range(batch):
            # The trunk's injection segment reads program rows up to the
            # first wait-like/END op, plus the NEXT op's kind (final_seg
            # lookahead) — rows [:j+2] over-cover that exactly.
            boundary = np.nonzero(
                (op[i] == OP_WAIT) | (op[i] == OP_WAITCOND) | (op[i] == OP_END)
            )[0]
            j = int(boundary[0]) if len(boundary) else op.shape[1] - 1
            min_j = min(min_j, j)
            end = min(j + 2, op.shape[1])
            digest = prefix_digest(
                op[i, :end].tobytes(), a[i, :end].tobytes(),
                b[i, :end].tobytes(), msg[i, :end].tobytes(),
            )
            groups.setdefault(digest, []).append(i)
        # The chunk-wide base trunk is itself a single-lane kernel launch,
        # so derive it lazily on the first group that actually amortizes —
        # a fully-scratch chunk (all groups below min_group) pays nothing.
        base = base_missing = object()

        def take(tree, idx):
            idx = np.asarray(idx)
            return jax.tree_util.tree_map(lambda x: jnp.asarray(x)[idx], tree)

        parts = []  # (original indices, sliced LaneResult)
        scratch: list = []
        for digest, idx in groups.items():
            if not self._forker.amortizes(len(idx), digest):
                scratch.extend(idx)
                continue
            if base is base_missing:
                base = self._base_trunk(progs, op, a, b, msg, min_j)
            group_prog = jax.tree_util.tree_map(
                lambda x: np.asarray(x)[idx[0]], progs
            )
            if base is not None:
                # Prescribed-resume trunk, sweep flavor: the group trunk
                # derives from the chunk-wide base snapshot over just its
                # remaining injection rows (O(group suffix), not O(whole
                # shared segment)) — bit-exact because the base stopped
                # inside the rows every lane shares, still ST_INJECT.
                snap, trunk_steps, hit = self._forker.trunk_from(
                    digest, base, group_prog
                )
            else:
                snap, trunk_steps, hit = self._forker.trunk(
                    digest, group_prog, jax.random.PRNGKey(0)
                )
            full = idx + [idx[0]] * (padded_size(len(idx), self.mesh) - len(idx))
            res = self._fork_kernel(take(progs, full), take(keys, full), snap)
            parts.append(
                (idx, jax.tree_util.tree_map(lambda x: x[: len(idx)], res))
            )
            self._forker.note_group(len(idx), trunk_steps, hit)
        if scratch:
            full = scratch + [scratch[0]] * (
                padded_size(len(scratch), self.mesh) - len(scratch)
            )
            res = self.kernel(take(progs, full), take(keys, full))
            parts.append(
                (scratch, jax.tree_util.tree_map(lambda x: x[: len(scratch)], res))
            )
            self._forker.note_scratch(len(scratch))
        order = [i for idx, _ in parts for i in idx]
        inv = np.empty(batch, np.int64)
        inv[np.asarray(order)] = np.arange(batch)
        return LaneResult(
            *(
                jnp.take(
                    jnp.concatenate(
                        [jnp.asarray(getattr(res, f)) for _, res in parts],
                        axis=0,
                    ),
                    jnp.asarray(inv),
                    axis=0,
                )
                for f in LaneResult._fields
            )
        )

    def _base_trunk(self, progs, op, a, b, msg, min_j):
        """The chunk-wide BASE trunk for hierarchical sweep trunks: run
        the injection rows EVERY lane of the chunk shares (typically the
        app's dsl start events plus any common fuzz prefix) once, cache
        the snapshot, and let every group trunk derive from it via
        ``trunk_from`` instead of replaying the whole shared segment.

        The base must stop (a) inside the chunk-wide common region — row
        i's injection reads row i+1's kind (the final_seg lookahead), so
        the limit is one row short of the first divergence — and (b)
        strictly before the chunk's earliest wait-like/END row, so every
        lane is still ST_INJECT at the snapshot. Returns the cache entry
        ``(snapshot, steps)`` or None when no shareable base exists."""
        from ..device.fork import prefix_digest

        if self._forker.resume_runner is None or op.shape[0] < 2:
            return None
        msg_same = (msg == msg[:1]).all(axis=0)
        if msg_same.ndim > 1:
            msg_same = msg_same.all(axis=-1)
        same = (
            (op == op[:1]).all(axis=0)
            & (a == a[:1]).all(axis=0)
            & (b == b[:1]).all(axis=0)
            & msg_same
        )
        diverge = np.nonzero(~same)[0]
        common = int(diverge[0]) if len(diverge) else op.shape[1]
        op_limit = min(common - 1, min_j)
        if op_limit < 1:
            return None
        end = op_limit + 1
        bkey = prefix_digest(
            op[0, :end].tobytes(), a[0, :end].tobytes(),
            b[0, :end].tobytes(), msg[0, :end].tobytes(), b"base",
        )
        entry = self._forker.cache.peek(bkey)
        if entry is None:
            snap = self._base_runner(
                jax.tree_util.tree_map(lambda x: np.asarray(x)[0], progs),
                jax.random.PRNGKey(0),
                jnp.int32(op_limit),
            )
            self._forker.cache.put(bkey, snap, snap.steps)
            entry = (snap, snap.steps)
        return entry

    def run_chunk(
        self, seeds: Sequence[int], slice_index: int = 0, base_key: int = 0
    ) -> SweepChunkResult:
        """One slice-sized chunk: lanes = len(seeds). When mesh-sharded the
        batch is padded up to a multiple of the mesh axis by repeating
        seeds; padded lanes are excluded from every reported count."""
        seeds = list(seeds)
        from ..persist.supervisor import SUPERVISOR

        # Chunks are pure functions of (seeds, base_key): a failed or
        # poisoned launch re-dispatches the chunk from the same inputs
        # under the launch supervisor (bounded retry + backoff;
        # --strict-io turns exhausted retries into errors).
        with obs.span("device.sweep.chunk", job=self._job, lanes=len(seeds)):
            return SUPERVISOR.run(
                lambda attempt: self._harvest_chunk(
                    self._dispatch_chunk(seeds, base_key), slice_index
                ),
                label="sweep.launch",
            )

    def _harvest_chunk(self, handle, slice_index: int = 0) -> SweepChunkResult:
        from ..obs.profiler import PROFILER

        real, res, t0 = handle
        n_real = len(real)
        t_block = time.perf_counter()
        jax.block_until_ready(res)
        t_done = time.perf_counter()
        seconds = t_done - t0
        if PROFILER.enabled:
            PROFILER.block("sweep", n_real, t_done - t_block)
        # Chunked-path host share: the blocked wait is device time, the
        # rest of the dispatch->harvest span (lowering, fork planning,
        # accumulation below is counted by the NEXT chunk's span) is host.
        self._note_share(max(0.0, t_block - t0), t_done - t_block)
        lane_stats = None
        if obs.enabled():
            # Per-sweep device-lane telemetry: totals reduced ON-DEVICE
            # over the whole chunk, pulled host-side once (device.lane.*
            # counters; the [B] deliveries array itself never transfers).
            from ..obs import lane_stats as _ls

            lane_stats = _ls.reduce_lanes(
                res.status, res.violation, res.deliveries, n_real,
                invariant_interval=self.cfg.invariant_interval,
            )
        if self.launch_budget is not None:
            self.launch_budget.note_harvest("fuzz", n_real)
        violations = np.asarray(res.violation)[:n_real]
        statuses = np.asarray(res.status)[:n_real]
        lanes = np.nonzero(statuses == ST_VIOLATION)[0]
        if self.violation_hook is not None and len(lanes):
            # Streaming handoff: every violating lane of this chunk, in
            # lane (= seed) order, the moment the chunk harvests.
            self.violation_hook(
                np.asarray(real)[lanes], violations[lanes]
            )
        uniq, cnt = np.unique(violations, return_counts=True)
        codes = {
            int(c): int(k) for c, k in zip(uniq.tolist(), cnt.tolist())
            if c != 0
        }
        hashes = np.asarray(res.sched_hash)[:n_real]
        chunk_uniq = np.unique(hashes[statuses <= ST_VIOLATION])
        if lane_stats is not None:
            from ..obs import lane_stats as _ls

            _ls.record(
                lane_stats, driver="sweep",
                unique_schedules=int(chunk_uniq.size),
            )
        # One journal record per harvested chunk (obs/journal.py — one
        # branch when detached): the sweep's continuous wire format.
        self.chunk_index += 1
        if obs.journal.JOURNAL is not None:
            obs.journal.emit(
                "sweep.chunk",
                round=self.chunk_index,
                lanes=n_real,
                wall_s=round(seconds, 6),
                host_s=round(max(0.0, t_block - t0), 6),
                device_s=round(t_done - t_block, 6),
                violations=int((violations != 0).sum()),
                codes=codes,
                unique=int(chunk_uniq.size),
                overflow=int((statuses == ST_OVERFLOW).sum()),
            )
        return SweepChunkResult(
            slice_index=slice_index,
            lanes=n_real,
            violations=int((violations != 0).sum()),
            codes=codes,
            first_violating_lane=int(lanes[0]) if len(lanes) else None,
            first_violation_code=(
                int(violations[lanes[0]]) if len(lanes) else None
            ),
            first_violating_seed=(
                int(real[lanes[0]]) if len(lanes) else None
            ),
            seconds=seconds,
            overflow_lanes=int((statuses == ST_OVERFLOW).sum()),
            unfinished_lanes=int((statuses == ST_UNFINISHED).sum()),
            # Overflowed lanes aborted mid-schedule: their truncated
            # fingerprints are not explored schedules, keep them out.
            unique_hashes=chunk_uniq,
            lanes_digest=lanes_digest(real, statuses, violations, hashes),
            lane_sharding=lane_sharding_summary(res.status),
        )

    def sweep(
        self,
        total_lanes: int,
        chunk_size: int,
        num_slices: int = 1,
        stop_on_violation: bool = False,
        mode: Optional[str] = None,
    ) -> SweepResult:
        """Partition ``total_lanes`` seeds into chunks round-robined over
        ``num_slices`` logical slices (in one process they run
        sequentially; in a jax.distributed deployment each process runs its
        own slice_index's chunks).

        ``mode``: 'continuous' (the default for single-slice sweeps, mesh
        or not) harvests+refills finished lanes at short segment
        boundaries, so a fixed sweep never pays max_steps for its
        short lanes (TPU-first lane compaction; per-seed verdicts
        bit-identical to 'chunked' — tests/test_continuous.py). Under a
        mesh the segment/refill kernels run lane-sharded; only O(batch) status
        vectors reach the host between segments. 'chunked' launches fixed
        whole-batch kernels; multi-slice sweeps always use it (slices
        partition the seed space — see module docstring)."""
        if mode is None:
            mode = "continuous" if num_slices == 1 else "chunked"
        self._job = obs.new_job()
        with obs.spans.first_job(self._job, "sweep"):
            if mode == "continuous":
                if num_slices != 1:
                    raise ValueError(
                        "continuous sweeps are single-slice (slices "
                        "partition the seed space; use mode='chunked')"
                    )
                return self._sweep_continuous(
                    total_lanes, chunk_size, stop_on_violation
                )
            result = SweepResult()
            t0 = time.perf_counter()
            seed = 0
            chunk_idx = 0
            while seed < total_lanes:
                n = min(chunk_size, total_lanes - seed)
                chunk = self.run_chunk(
                    range(seed, seed + n), slice_index=chunk_idx % num_slices
                )
                result.chunks.append(chunk)
                seed += n
                chunk_idx += 1
                if stop_on_violation and chunk.violations:
                    break
            result.wall_seconds = time.perf_counter() - t0
            return result

    def _continuous_driver(
        self, batch: int, base_key: int = 0, program_gen=None
    ):
        """The ONE continuous-driver constructor (batch alignment, seg
        formula, per-seed key scheme): the plain and autotuned continuous
        sweeps both build here, so the lane-key scheme that makes their
        verdicts identical to ``run_chunk`` exists in exactly one copy.
        ``program_gen`` overrides the driver's generator (the autotuned
        path's epoch-tagging wrapper); overridden drivers bypass the
        cache — the wrapper closes over live controller state, so the
        driver is not told it is a function of the seed and calls it at
        refill only, never ahead (``seed_pure``)."""
        from ..device.continuous import ContinuousSweepDriver

        if self.mesh is not None:
            # Lane-shard the refill path too: round the batch up to a
            # mesh multiple (refill keeps every lane busy, so padding
            # costs nothing once the seed stream is longer than a batch).
            batch = ((batch + self._align - 1) // self._align) * self._align
        key = (batch, base_key)
        if program_gen is None:
            if getattr(self, "_cont_cache", None) and self._cont_cache[0] == key:
                return self._cont_cache[1]
        drv = ContinuousSweepDriver(
            self.app, self.cfg, program_gen or self.program_gen,
            batch=batch,
            seg_steps=segment_steps(self.cfg.max_steps),
            mesh=self.mesh,
            # Same per-seed key scheme as run_chunk => identical verdicts.
            # No np.uint32() wrapper: the seed must stay traceable so the
            # continuous driver's vectorized key derivation applies
            # (fold_in canonicalizes to uint32 itself).
            key_fn=lambda s: jax.random.fold_in(
                jax.random.PRNGKey(base_key), s
            ),
            seed_pure=program_gen is None,
        )
        if program_gen is None:
            self._cont_cache = (key, drv)
        return drv

    def _sweep_continuous(
        self,
        total_lanes: int,
        batch: int,
        stop_on_violation: bool,
        base_key: int = 0,
        program_gen=None,
        retire_hook=None,
    ) -> SweepResult:
        """Continuous sweep with vectorized harvest accumulation:
        retirements stream back as per-round ARRAYS
        (``_run_batches``) and fold into the result with array ops.
        ``retire_hook(seeds, statuses, codes, hashes)`` observes every
        accumulated retirement batch in order — the autotuned path's
        reward attribution rides it."""
        with obs.span("sweep.job", job=self._job, lanes=total_lanes):
            drv = self._continuous_driver(batch, base_key, program_gen)
            acc = _HarvestAccumulator()
            t0 = time.perf_counter()
            for seeds, statuses, codes, hashes in drv._run_batches(
                total_lanes
            ):
                with obs.span("sweep.fold", lanes=len(seeds)):
                    # Every retirement in this harvest round is PAID-FOR
                    # device work — count them all before deciding to
                    # stop. (The old array path truncated at the first
                    # violating retirement, mimicking the per-item loop's
                    # mid-round break; that threw away already-retired
                    # non-violating verdicts in the same round,
                    # undercounting lanes/codes the device had computed.
                    # tests/test_streaming.py pins the retained-lane
                    # counts.)
                    acc.add(seeds, statuses, codes, hashes)
                    if retire_hook is not None:
                        retire_hook(seeds, statuses, codes, hashes)
                    vio = np.flatnonzero(codes != 0)
                    if self.violation_hook is not None and len(vio):
                        # Streaming handoff from the continuous driver:
                        # the violating retirements, in retirement order,
                        # without stopping the sweep.
                        self.violation_hook(seeds[vio], codes[vio])
                if stop_on_violation and len(vio):
                    break
            with obs.span("sweep.finish"):
                chunk = acc.chunk(
                    slice_index=0, seconds=time.perf_counter() - t0
                )
                chunk.lane_sharding = drv.last_lane_sharding
                result = SweepResult(chunks=[chunk])
                result.occupancy = drv.last_occupancy
                # One chunk, harvested synchronously: its seconds ARE
                # wall time.
                result.wall_seconds = chunk.seconds
                # Host-share attribution: the driver's segment/harvest
                # split is exact for continuous sweeps (the status pull
                # is the sync point).
                self._note_share(
                    drv.last_harvest_seconds, drv.last_segment_seconds
                )
        return result

    def sweep_autotuned(
        self,
        total_lanes: int,
        chunk_size: int,
        controller,
        base_key: int = 0,
        mode: str = "chunked",
    ) -> SweepResult:
        """Autotuned sweep with the measurement-guided weight loop closed:
        before each reward round the controller proposes fuzzer weights;
        on harvest the round is scored by its NEW unique schedule
        fingerprints plus violations (cross-round dedup lives in the
        controller).

        ``mode='chunked'`` (the original loop): one proposal per fixed
        chunk — programs are generated under it (``_programs`` lowers per
        chunk, so the swap takes effect immediately) and the whole chunk's
        harvest is its reward. Clean attribution, but every chunk pays the
        full-batch round trip the continuous driver exists to avoid.

        ``mode='continuous'`` rides the lane-compacted continuous driver
        with segment-boundary attribution: every seed is tagged with the
        proposal epoch active when its program was GENERATED (the refill
        wrapper below — generation is the only moment weights touch a
        lane), retirements are bucketed by that tag as the driver streams
        them back at segment boundaries, and the controller's
        ``end_round`` fires once an epoch has ``chunk_size`` retired
        lanes. Attribution is exact — a lane is only ever credited to the
        proposal that generated it; epoch-k lanes still in flight when
        its reward fires land in the sweep result but not the reward
        signal (dropped, never mis-credited)."""
        self._job = obs.new_job()
        if mode == "continuous":
            # The epoch-tagged reward attribution rides the ONE shared
            # continuous path: a generator wrapper tags each seed with
            # the proposal epoch that generated it (generation is the
            # only moment fuzzer weights touch a lane, so the tag is
            # exact attribution — not an approximation), and a
            # _RewardBucket consumes the retirement arrays via the
            # retire_hook.
            epoch_of_seed: dict = {}
            cur_epoch = [0]

            def tagged_gen(seed: int):
                epoch_of_seed[seed] = cur_epoch[0]
                return self.program_gen(seed)

            bucket = _RewardBucket(
                controller, chunk_size, epoch_of_seed, cur_epoch
            )
            controller.begin_round()
            result = self._sweep_continuous(
                total_lanes, chunk_size, stop_on_violation=False,
                base_key=base_key, program_gen=tagged_gen,
                retire_hook=bucket.add,
            )
            bucket.close()
            obs.gauge("tune.continuous_attributed").set(
                result.lanes - bucket.dropped
            )
            return result
        result = SweepResult()
        t0 = time.perf_counter()
        seed = 0
        while seed < total_lanes:
            n = min(chunk_size, total_lanes - seed)
            controller.begin_round()
            chunk = self.run_chunk(
                range(seed, seed + n), slice_index=0, base_key=base_key
            )
            controller.end_round(
                hashes=(
                    chunk.unique_hashes
                    if chunk.unique_hashes is not None
                    else ()
                ),
                violations=chunk.violations,
                lanes=chunk.lanes,
            )
            result.chunks.append(chunk)
            seed += n
        result.wall_seconds = time.perf_counter() - t0
        return result

    def sweep_async(
        self, total_lanes: int, chunk_size: int, base_key: int = 0
    ):
        """Non-blocking explore (reference: RandomScheduler
        .nonBlockingExplore, RandomScheduler.scala:184-211): a generator
        yielding one SweepChunkResult per chunk while the NEXT chunk's
        kernel is already in flight (double-buffered jax async dispatch).
        The caller overlaps its own work — harvesting violations,
        launching minimization — with device execution, and ends the
        sweep early by just closing the generator (the reference's analog
        returns a future the caller completes). Per-chunk ``seconds``
        spans dispatch→harvest and therefore overlaps between chunks."""
        seed = 0
        pending = None  # (handle, slice_index)
        chunk_idx = 0
        while seed < total_lanes:
            n = min(chunk_size, total_lanes - seed)
            handle = self._dispatch_chunk(range(seed, seed + n), base_key)
            seed += n
            if pending is not None:
                yield self._harvest_chunk(*pending)
            pending = (handle, chunk_idx)
            chunk_idx += 1
        if pending is not None:
            yield self._harvest_chunk(*pending)

    def time_to_first_violation(
        self, chunk_size: int, max_lanes: int = 1_000_000
    ) -> Tuple[Optional[float], SweepResult]:
        """Wall-clock until the first violating lane (the BASELINE.md
        headline metric), sweeping chunk by chunk."""
        t0 = time.perf_counter()
        result = self.sweep(
            max_lanes, chunk_size, stop_on_violation=True
        )
        if result.violations:
            return time.perf_counter() - t0, result
        return None, result
