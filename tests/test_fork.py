"""Prefix-fork replay: bit-exact parity of forked lanes vs scratch
execution for the replay, explore, and DPOR kernels, plus the host-side
planner/cache and the driver wiring (checker, DeviceDPOR, SweepDriver)."""

import dataclasses

import numpy as np
import pytest

import jax

from demi_tpu.apps.broadcast import broadcast_send_generator, make_broadcast_app
from demi_tpu.apps.common import dsl_start_events, make_host_invariant
from demi_tpu.apps.raft import T_CLIENT, make_raft_app
from demi_tpu.config import SchedulerConfig
from demi_tpu.device import DeviceConfig
from demi_tpu.device.batch_oracle import DeviceReplayChecker, default_device_config
from demi_tpu.device.encoding import lower_expected_trace, lower_program, stack_programs
from demi_tpu.device.explore import make_explore_kernel
from demi_tpu.device.fork import (
    PrefixCache,
    PrefixPlanner,
    make_explore_prefix_runner,
    make_replay_prefix_runner,
    prefix_fork_enabled,
)
from demi_tpu.device.replay import make_replay_kernel
from demi_tpu.external_events import MessageConstructor, Send, WaitQuiescence
from demi_tpu.fuzzing import Fuzzer, FuzzerWeights
from demi_tpu.minimization.internal import (
    removable_delivery_indices,
    remove_delivery,
)
from demi_tpu.schedulers import RandomScheduler


# ---------------------------------------------------------------------------
# Host-side planner / cache units
# ---------------------------------------------------------------------------

def _removal_records(n_rows: int, bucket_removals):
    """Synthetic ddmin-level records: a baseline of distinct rows; each
    candidate removes one index (later rows shift left)."""
    base = np.zeros((n_rows + 1, 4), np.int32)
    base[:n_rows, 0] = 1  # kind
    base[:n_rows, 3] = np.arange(100, 100 + n_rows)  # distinct payloads
    out = []
    for k in bucket_removals:
        cand = np.concatenate([base[:k], base[k + 1:]], axis=0)
        out.append(cand)
    return np.stack(out)


def test_prefix_planner_groups_by_first_divergence_bucket():
    # Candidates removing index k diverge from the baseline in bucket
    # k // 8: removals 0..7 have no shareable prefix (scratch); 8..15
    # share the first 8 rows; 16..23 the first 16.
    removals = list(range(24))
    records = _removal_records(24, removals)
    lengths = (records[:, :, 0] != 0).sum(axis=1)
    planner = PrefixPlanner(bucket=8)
    groups, scratch = planner.plan(records, lengths)
    assert sorted(scratch) == list(range(8))
    by_len = {g.prefix_len: sorted(g.indices) for g in groups}
    assert by_len[8] == list(range(8, 16))
    assert by_len[16] == list(range(16, 24))
    # Every group's members really share the prefix byte-exactly.
    for g in groups:
        ref = records[g.indices[0], : g.prefix_len].tobytes()
        assert all(
            records[i, : g.prefix_len].tobytes() == ref for i in g.indices
        )


def test_prefix_planner_identical_trials_terminate():
    records = _removal_records(16, [12] * 6)  # six identical candidates
    lengths = (records[:, :, 0] != 0).sum(axis=1)
    groups, scratch = PrefixPlanner(bucket=4).plan(records, lengths)
    assert scratch == []
    assert len(groups) == 1
    # Identical trials group at their (bucketed) full length.
    assert groups[0].prefix_len == 12  # 15 rows -> last full 4-bucket
    assert sorted(groups[0].indices) == list(range(6))


def test_prefix_cache_lru_eviction():
    cache = PrefixCache(capacity=2)
    cache.put(b"a", "snap_a", 1)
    cache.put(b"b", "snap_b", 2)
    assert cache.get(b"a") == ("snap_a", 1)  # refresh a
    cache.put(b"c", "snap_c", 3)  # evicts b (LRU)
    assert b"b" not in cache
    assert cache.get(b"b") is None
    assert cache.get(b"a") == ("snap_a", 1)
    assert cache.get(b"c") == ("snap_c", 3)
    assert cache.hits == 3 and cache.misses == 1


def test_prefix_fork_env_switch(monkeypatch):
    monkeypatch.delenv("DEMI_PREFIX_FORK", raising=False)
    assert not prefix_fork_enabled()
    monkeypatch.setenv("DEMI_PREFIX_FORK", "1")
    assert prefix_fork_enabled()
    assert not prefix_fork_enabled(False)  # explicit arg wins
    monkeypatch.delenv("DEMI_PREFIX_FORK")
    assert prefix_fork_enabled(True)


# ---------------------------------------------------------------------------
# Fixtures: a deep raft schedule and its internal-minimization level
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def raft_level():
    app = make_raft_app(3)
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    program = dsl_start_events(app) + [
        Send(app.actor_name(0),
             MessageConstructor(lambda: (T_CLIENT, 0, 7, 0, 0, 0, 0))),
        WaitQuiescence(budget=40),
    ]
    result = RandomScheduler(
        config, seed=0, max_messages=200, invariant_check_interval=1,
        timer_weight=0.2,
    ).execute(program)
    trace = result.trace
    trace.set_original_externals(list(program))
    cands = [
        remove_delivery(trace, i) for i in removable_delivery_indices(trace)
    ]
    assert len(cands) >= 8
    return app, config, program, trace, cands


def test_replay_fork_parity_bit_exact(raft_level):
    """Forked replay lanes == scratch replay lanes on every ReplayResult
    field, for candidates sharing the baseline's first 8 records."""
    app, config, program, trace, cands = raft_level
    cfg = default_device_config(app, trace, program)
    r = cfg.max_steps + cfg.max_external_ops
    base = lower_expected_trace(app, cfg, trace, program, r)
    records = np.stack(
        [lower_expected_trace(app, cfg, c, program, r) for c in cands]
    )
    lengths = (records[:, :, 0] != 0).sum(axis=1)
    p = 8
    sel = [
        i for i in range(len(cands))
        if lengths[i] > p
        and records[i, :p].tobytes() == base[:p].tobytes()
    ]
    assert len(sel) >= 2
    sel_records = records[sel]
    keys = jax.random.split(jax.random.PRNGKey(3), len(sel))

    scratch = make_replay_kernel(app, cfg)(sel_records, keys)

    trunk_records = np.zeros_like(base)
    trunk_records[:p] = base[:p]
    snap = make_replay_prefix_runner(app, cfg)(
        trunk_records, jax.random.PRNGKey(9)
    )
    assert int(snap.steps) == p
    suffixes = np.zeros_like(sel_records)
    suffixes[:, : r - p] = sel_records[:, p:]
    forked = make_replay_kernel(app, cfg, start_state=True)(
        suffixes, keys, snap
    )
    for field in scratch._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(scratch, field)),
            np.asarray(getattr(forked, field)),
            err_msg=field,
        )


def test_checker_fork_verdicts_match_scratch(raft_level):
    """DeviceReplayChecker with prefix_fork on/off returns identical
    verdict lists, and the fork path's cache warms across calls."""
    app, config, program, trace, cands = raft_level
    cfg = default_device_config(app, trace, program)
    exts = [program] * len(cands)
    off = DeviceReplayChecker(app, cfg, config, prefix_fork=False)
    on = DeviceReplayChecker(app, cfg, config, prefix_fork=True)
    v_off = off.verdicts(cands, exts, 1)
    v_on = on.verdicts(cands, exts, 1)
    assert v_off == v_on
    first = dict(on.fork_stats)
    assert first["forked_lanes"] > 0
    assert first["steps_saved"] > 0
    # Second level (same trunks): every probe hits the cache.
    assert on.verdicts(cands, exts, 1) == v_off
    second = on.fork_stats
    assert second["prefix_hits"] > first["prefix_hits"]
    assert second["prefix_misses"] == first["prefix_misses"]


def test_explore_fork_parity_bit_exact(raft_level):
    """Forked explore lanes (trunk = injection segment, per-lane rng) ==
    scratch lanes on every LaneResult field. The scratch side runs the
    fixed-length scan and the forked side the dynamic while_loop — this
    pins the two loop forms equivalent on top of the fork itself. (The
    early-exit/while scratch form is covered by the sweep-driver parity
    test below, whose cfg sets early_exit=True.)"""
    app, _config, program, _trace, _cands = raft_level
    cfg = DeviceConfig.for_app(
        app, pool_capacity=64, max_steps=80, max_external_ops=16,
        invariant_interval=1,
    )
    prog = lower_program(app, cfg, program)
    progs = stack_programs([prog] * 8)
    keys = jax.random.split(jax.random.PRNGKey(1), 8)
    scratch = make_explore_kernel(app, cfg)(progs, keys)
    snap = make_explore_prefix_runner(app, cfg)(
        prog, jax.random.PRNGKey(0)
    )
    assert int(snap.steps) > 0  # the start events really ran
    forked = make_explore_kernel(app, cfg, start_state=True)(
        progs, keys, snap
    )
    for field in scratch._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(scratch, field)),
            np.asarray(getattr(forked, field)),
            err_msg=field,
        )


def test_fork_lanes_matches_start_state_kernel(raft_level):
    """``fork_lanes`` (the materialized broadcast) agrees with what the
    ``start_state=`` kernels do implicitly: every non-rng state leaf is
    the snapshot's, replicated over the lane axis; rng is per-lane."""
    import jax.numpy as jnp

    from demi_tpu.device.fork import fork_lanes

    app, _config, program, _trace, _cands = raft_level
    cfg = DeviceConfig.for_app(
        app, pool_capacity=64, max_steps=80, max_external_ops=16,
        invariant_interval=1,
    )
    prog = lower_program(app, cfg, program)
    snap = make_explore_prefix_runner(app, cfg)(prog, jax.random.PRNGKey(0))
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    states = fork_lanes(snap, keys)
    np.testing.assert_array_equal(np.asarray(states.rng), np.asarray(keys))
    for field in states._fields:
        if field == "rng":
            continue
        if getattr(snap.state, field) is None:
            # a leaf this shape does not carry (``insert_full_steps``)
            assert getattr(states, field) is None
            continue
        leaf = np.asarray(getattr(states, field))
        ref = np.asarray(getattr(snap.state, field))
        assert leaf.shape == (4,) + ref.shape, field
        for lane in range(4):
            np.testing.assert_array_equal(leaf[lane], ref, err_msg=field)
    assert jnp.all(states.status == snap.state.status).item()


def test_device_dpor_prefix_fork_matches_scratch():
    """End-to-end DeviceDPOR parity: with prefix forking on, every round's
    lanes are bit-identical to scratch, so the whole systematic search —
    explored set, frontier, found ordering — matches, while trunks
    genuinely fork (the reversal app's prescriptions share prefixes by
    construction)."""
    from test_device_dpor import _setup

    from demi_tpu.device.dpor_sweep import DeviceDPOR

    app, cfg, program = _setup(4)
    scratch = DeviceDPOR(app, cfg, program, batch_size=8)
    f_s = scratch.explore(target_code=1, max_rounds=30)
    forked = DeviceDPOR(
        app, cfg, program, batch_size=8, prefix_fork=True, fork_bucket=1,
        # The CPU default declines sub-amortizing groups (fork_min_group
        # 4); this test verifies the machinery itself, so let pairs fork.
        fork_min_group=2,
    )
    f_f = forked.explore(target_code=1, max_rounds=30)
    assert (f_s is None) == (f_f is None)
    assert f_s is not None, "reversal search found nothing"
    np.testing.assert_array_equal(f_s[0][: f_s[1]], f_f[0][: f_f[1]])
    assert scratch.explored == forked.explored
    assert scratch.interleavings == forked.interleavings
    stats = forked._forker.stats_view()
    assert stats["forked_lanes"] > 0
    assert stats["steps_saved"] > 0
    assert stats["prefix_hits"] > 0  # rounds reuse cached trunks


def test_sweep_driver_fork_chunked_parity():
    """Chunked sweeps with prefix forking return identical per-seed
    results (codes, hashes, first violating seed) — injection never
    consumes rng, so forked lanes resume the exact scratch stream."""
    from demi_tpu.parallel.sweep import SweepDriver

    app = make_broadcast_app(4, reliable=False)
    cfg = DeviceConfig.for_app(
        app, pool_capacity=64, max_steps=96, max_external_ops=24,
        early_exit=True,
    )
    fuzzer = Fuzzer(
        num_events=10,
        weights=FuzzerWeights(kill=0.05, send=0.6, wait_quiescence=0.15),
        message_gen=broadcast_send_generator(app),
        prefix=dsl_start_events(app),
        max_kills=1,
    )
    gen = lambda s: fuzzer.generate_fuzz_test(seed=s)  # noqa: E731
    r1 = SweepDriver(app, cfg, gen).sweep(64, 32, mode="chunked")
    forked_driver = SweepDriver(app, cfg, gen, prefix_fork=True)
    r2 = forked_driver.sweep(64, 32, mode="chunked")
    assert r1.violations == r2.violations
    assert r1.codes == r2.codes
    assert r1.unique_schedules == r2.unique_schedules
    assert r1.first_violating_seed == r2.first_violating_seed
    for c1, c2 in zip(r1.chunks, r2.chunks):
        np.testing.assert_array_equal(c1.unique_hashes, c2.unique_hashes)
    # Fuzzed programs share start-event prefixes only sometimes; a fixed
    # program forks the whole chunk.
    fixed = gen(0)
    d3 = SweepDriver(app, cfg, lambda s: fixed, prefix_fork=True)
    r3 = d3.sweep(32, 16, mode="chunked")
    assert r3.lanes == 32
    assert d3.fork_stats["forked_lanes"] == 32
    assert d3.fork_stats["prefix_hits"] >= 1  # chunk 2 reuses chunk 1's trunk


@pytest.mark.slow
def test_fork_parity_randomized_sweep(raft_level):
    """Randomized broader net: fuzzed broadcast traces, every internal-
    minimization level checked fork-vs-scratch for verdict equality."""
    app = make_broadcast_app(3, reliable=False)
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    fuzzer = Fuzzer(
        num_events=12,
        weights=FuzzerWeights(kill=0.05, send=0.6, wait_quiescence=0.15),
        message_gen=broadcast_send_generator(app),
        prefix=dsl_start_events(app),
        max_kills=1,
    )
    from demi_tpu.runner import fuzz

    checked = 0
    for seed in range(0, 60, 12):
        fr = fuzz(config, fuzzer, max_executions=12, seed=seed)
        if fr is None:
            continue
        cfg = default_device_config(app, fr.trace, fr.program)
        # External-DDMin-style candidates: drop one tail external at a
        # time (projections share the execution prefix).
        subsets = [
            fr.program[:k] for k in range(3, len(fr.program))
        ]
        projected = [
            fr.trace.filter_failure_detector_messages()
            .filter_checkpoint_messages()
            .subsequence_intersection(list(s))
            for s in subsets
        ]
        off = DeviceReplayChecker(app, cfg, config, prefix_fork=False)
        on = DeviceReplayChecker(app, cfg, config, prefix_fork=True, fork_bucket=2)
        assert off.verdicts(projected, subsets, fr.violation.code) == (
            on.verdicts(projected, subsets, fr.violation.code)
        )
        checked += 1
    assert checked >= 2
