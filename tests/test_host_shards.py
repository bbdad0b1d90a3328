"""Digest-range-sharded coordinator host half (demi_tpu/fleet/shard).

The contract under test is bit-identity: partitioning the admission
pipeline (racing scan, static/sleep filters, digest dedup) across N
digest-range shards must change NOTHING about the search — explored
set and log order, frontier order, digest sets, class ledger,
violation codes, wakeup guides, and the first-found record all equal
the 1-shard pipeline's at any shard count, through checkpoints, and
across N->M re-sharded restores.
"""

import os

import numpy as np
import pytest

from demi_tpu.analysis import SleepSets, StaticIndependence, sleep_cap
from demi_tpu.device.dpor_sweep import DeviceDPOR
from demi_tpu.fleet import build_fleet_workload
from demi_tpu.fleet.shard import (
    DigestShards,
    HostHalfTimer,
    ShardedAdmission,
    resolve_host_shards,
    shard_ids_of_digests,
    shard_of_key,
)

WORKLOAD = {
    "app": "raft", "nodes": 3, "bug": "multivote",
    "max_messages": 48, "pool": 64, "num_events": 8,
}


# -- unit layer: routing, the sharded set, the scan buffers ---------------


def test_shard_of_key_matches_vectorized_twin():
    rng = np.random.default_rng(7)
    digests = rng.integers(0, 2**64, size=(256, 2), dtype=np.uint64)
    keys = [row.tobytes() for row in digests]
    for n in (1, 2, 3, 4, 7, 16):
        ids = shard_ids_of_digests(digests, n)
        scalar = [shard_of_key(k, n) for k in keys]
        assert ids.tolist() == scalar, f"n={n}"
        assert all(0 <= s < n for s in scalar)


def test_shard_ranges_are_contiguous_and_ordered():
    # Range partition on the top 32 bits: sorting keys by that word must
    # yield non-decreasing shard ids (a contiguous range per shard).
    rng = np.random.default_rng(11)
    digests = rng.integers(0, 2**64, size=(512, 2), dtype=np.uint64)
    keys = sorted(
        (row.tobytes() for row in digests),
        key=lambda k: int.from_bytes(k[:8], "little") >> 32
        if __import__("sys").byteorder == "little"
        else int.from_bytes(k[:8], "big") >> 32,
    )
    ids = [shard_of_key(k, 4) for k in keys]
    assert ids == sorted(ids)


def test_digest_shards_set_surface_and_reshard():
    rng = np.random.default_rng(3)
    keys = {
        row.tobytes()
        for row in rng.integers(0, 2**64, size=(128, 2), dtype=np.uint64)
    }
    d4 = DigestShards(4, keys)
    assert len(d4) == len(keys)
    assert set(d4) == keys
    for k in list(keys)[:8]:
        assert k in d4
    assert rng.integers(0, 2**64, size=2, dtype=np.uint64).tobytes() not in d4
    # Slices are disjoint and each key lives on its owning shard.
    for s, sl in enumerate(d4.slices):
        for k in sl:
            assert shard_of_key(k, 4) == s
    # Construction from any iterable IS the N->M re-shard.
    d2 = DigestShards(2, d4)
    assert d2 == d4  # cross-n equality compares flat sets
    assert d4 == keys  # and so does equality vs a plain set
    extra = b"\x00" * 16
    d2.add(extra)
    assert extra in d2 and len(d2) == len(keys) + 1
    assert d2 != d4


def test_resolve_host_shards_is_what_it_is_told(monkeypatch):
    monkeypatch.setenv("DEMI_HOST_SHARDS", "4")  # no longer a channel
    assert resolve_host_shards() == 1
    assert resolve_host_shards(None) == 1
    assert resolve_host_shards(2) == 2
    assert resolve_host_shards(0) == 1
    assert resolve_host_shards(-3) == 1
    with pytest.raises(ValueError):
        resolve_host_shards("junk")


def test_scan_buffers_grow_monotonically_and_are_reused():
    from demi_tpu.native import ScanBuffers

    b = ScanBuffers()
    b.ensure(16, 64, 8)
    rows0, offs0 = b.rows, b.offsets
    assert b.rows.shape == (64, 8)
    # Smaller request reuses the same allocations.
    b.ensure(4, 16, 8)
    assert b.rows is rows0 and b.offsets is offs0
    # Growth reallocates; capacities are monotone.
    b.ensure(32, 128, 8)
    assert b.rows is not rows0
    assert b.cap_presc == 32 and b.cap_rows == 128
    # Width change forces a row realloc even at same capacity.
    b.ensure(32, 128, 12)
    assert b.rows.shape == (128, 12)


# -- integration layer: bit-identity on a real workload -------------------


def _make(app, cfg, program, shards, prune=False, static=False):
    rel = StaticIndependence.for_app(app)
    return DeviceDPOR(
        app, cfg, program, batch_size=8, prefix_fork=False,
        double_buffer=False,
        sleep_sets=SleepSets(independence=rel, prune=prune, cap=sleep_cap()),
        static_independence=rel if static else False,
        host_shards=shards,
    )


def _identity(d, found):
    return (
        tuple(d._explored_log), tuple(d.frontier),
        frozenset(d._explored_digests), frozenset(d._suppressed_digests),
        tuple(sorted(d.violation_codes)), frozenset(d.sleep.classes),
        d.interleavings,
        None if found is None else found[0][: found[1]].tobytes(),
    )


@pytest.mark.parametrize("prune,static", [(False, False), (True, True)])
def test_sharded_admission_bit_identical(prune, static):
    app, cfg, program = build_fleet_workload(WORKLOAD)
    ref = None
    for n in (1, 2, 3):
        d = _make(app, cfg, program, n, prune=prune, static=static)
        found = d.explore(max_rounds=3, stop_on_violation=False)
        ident = _identity(d, found)
        if ref is None:
            ref = ident
        else:
            assert ident == ref, f"shards={n} diverged (prune={prune})"
        if d._sharder is not None:
            assert d._sharder.rounds > 0
            d._sharder.close()


def test_serialize_env_is_bit_identical(monkeypatch):
    app, cfg, program = build_fleet_workload(WORKLOAD)
    d1 = _make(app, cfg, program, 2)
    f1 = d1.explore(max_rounds=2, stop_on_violation=False)
    monkeypatch.setenv("DEMI_HOST_SHARD_SERIALIZE", "1")
    d2 = _make(app, cfg, program, 2)
    assert d2._sharder is not None and d2._sharder.serialize
    f2 = d2.explore(max_rounds=2, stop_on_violation=False)
    assert _identity(d1, f1) == _identity(d2, f2)
    d1._sharder.close()


def test_last_round_carries_shard_stats():
    app, cfg, program = build_fleet_workload(WORKLOAD)
    d = _make(app, cfg, program, 2)
    d.explore(max_rounds=2, stop_on_violation=False)
    stats = d._last_round.get("host_shards")
    assert stats and len(stats) == 2
    for st in stats:
        for key in ("shard", "lanes", "rows", "candidates", "owned",
                    "dup", "fresh", "scan_s", "dedup_s", "wall_s"):
            assert key in st, key
    # Every candidate is owned by exactly one shard.
    assert sum(st["owned"] for st in stats) == sum(
        st["candidates"] for st in stats
    )
    d._sharder.close()


def test_reshard_checkpoint_resume_bit_identical():
    """An N-shard checkpoint restores into M shards (checkpoints are
    flat; restore re-partitions) and every continuation — including the
    source instance's own — lands bit-identical."""
    app, cfg, program = build_fleet_workload(WORKLOAD)
    src = _make(app, cfg, program, 2)
    src.explore(max_rounds=2, stop_on_violation=False)
    payload = src.checkpoint_state()
    ref = None
    for m in (1, 2, 4):
        dm = _make(app, cfg, program, m)
        dm.restore_state(payload)
        # The restored digest sets are re-partitioned to M ranges.
        if m > 1:
            assert isinstance(dm._explored_digests, DigestShards)
            assert dm._explored_digests.n == m
        found = dm.explore(max_rounds=2, stop_on_violation=False)
        ident = _identity(dm, found)
        if ref is None:
            ref = ident
        else:
            assert ident == ref, f"2->{m} re-sharded resume diverged"
        if dm._sharder is not None:
            dm._sharder.close()
    found = src.explore(max_rounds=2, stop_on_violation=False)
    assert _identity(src, found) == ref
    src._sharder.close()


def test_host_half_timer_uncontended_convention():
    app, cfg, program = build_fleet_workload(WORKLOAD)
    d = _make(app, cfg, program, 2)
    timer = HostHalfTimer(d)
    d.explore(max_rounds=2, stop_on_violation=False)
    assert timer.rounds >= 2
    assert timer.seconds > 0
    # Uncontended = wall - parallel-section wall + busy/n: bounded by
    # the measured wall whenever the shards did any concurrent work.
    assert 0 < timer.uncontended_seconds() <= timer.seconds + 1e-9
    assert timer.rounds_per_sec() > 0
    d._sharder.close()


def test_native_scan_seconds_counter_per_shard():
    from demi_tpu import obs

    app, cfg, program = build_fleet_workload(WORKLOAD)
    obs.enable()
    try:
        d = _make(app, cfg, program, 2)
        d.explore(max_rounds=2, stop_on_violation=False)
        series = obs.counter("native.scan_seconds").series
        assert series.get("shard=0", 0) > 0, series
        assert series.get("shard=1", 0) > 0, series
    finally:
        obs.disable()
        obs.REGISTRY.reset()
    d._sharder.close()


def test_profiler_host_scan_kind():
    from demi_tpu.obs.profiler import PROFILER

    app, cfg, program = build_fleet_workload(WORKLOAD)
    PROFILER.enable()
    PROFILER.reset()
    try:
        d = _make(app, cfg, program, 2)
        d.explore(max_rounds=2, stop_on_violation=False)
        ev = PROFILER.evidence()
        host_rows = [r for r in ev["launches"] if r["kind"] == "host"]
        assert host_rows, ev
        assert any("shards=2" in r["shape"] for r in host_rows)
        assert all(r["seconds"] >= 0 for r in host_rows)
    finally:
        PROFILER.disable()
        PROFILER.reset()
    d._sharder.close()


def test_calibrate_host_shards_cache_and_default(tmp_path):
    """Calibration contract: measured walk persisted to the TuningCache;
    a second call is a pure cache hit; no measure -> 1-shard default."""
    from demi_tpu.tune import TuningCache, calibrate_host_shards
    from demi_tpu.device import DeviceConfig
    from demi_tpu.apps.common import dsl_start_events
    from demi_tpu.apps.raft import make_raft_app

    app = make_raft_app(3, bug="multivote")
    cfg = DeviceConfig.for_app(app, pool_capacity=64, max_steps=48)
    cache = TuningCache(str(tmp_path / "tuning.json"))

    calls = []

    def fake_measure(params):
        n = int(params["host_shards"])
        calls.append(n)
        return {1: 10.0, 2: 19.0, 4: 12.0}[n]

    dec = calibrate_host_shards(
        app, cfg, batch=8, platform="cpu", cache=cache,
        measure=fake_measure,
    )
    assert dec.source == "calibrated"
    assert dec.shards == 2
    assert dec.rate == 19.0
    assert calls  # the axis was actually walked
    assert set(dec.rates) == {"host_shards=1", "host_shards=2",
                              "host_shards=4"}

    calls.clear()
    hit = calibrate_host_shards(
        app, cfg, batch=8, platform="cpu", cache=cache,
        measure=fake_measure,
    )
    assert hit.source == "cached"
    assert hit.shards == 2
    assert not calls  # cache hit costs no measurements

    default = calibrate_host_shards(
        app, cfg, batch=16, platform="cpu", cache=cache,
    )
    assert default.source == "default"
    assert default.shards == 1


def test_cli_dpor_host_shards_flag(monkeypatch, capsys):
    """--host-shards reaches the DeviceDPOR the verb's oracle builds as
    an argument, the process environment untouched, and the sharded
    search still runs."""
    import json

    from demi_tpu.cli import main
    from test_cli import _recorded

    built = _recorded(monkeypatch, DeviceDPOR)
    monkeypatch.delenv("DEMI_HOST_SHARDS", raising=False)
    environ = dict(os.environ)
    rc = main([
        "dpor", "--app", "raft", "--nodes", "2", "--bug", "multivote",
        "--batch", "8", "--rounds", "2", "--pool", "64",
        "--max-messages", "48", "--num-events", "6",
        "--host-shards", "2",
    ])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert [d._host_shards for d in built] == [2]
    assert built[0]._sharder is not None
    assert dict(os.environ) == environ
    assert rc in (0, 1)
    assert "interleavings" in out
