"""Vectorized host path: randomized parity native-vs-NumPy-vs-legacy for
prescription assembly, LCP grouping, and record packing, plus a whole
DeviceDPOR search against the per-lane assembly kept here and the
collapsed continuous-autotuned sweep.

The contract under test: every vectorized host-side rewrite (batch racing
analysis, digest dedup, array LCP planning, matrix packing, array harvest
accumulation) is BIT-IDENTICAL to the Python path it replaced — the PR's
win is time, never results."""

import numpy as np
import pytest

from demi_tpu.device.core import REC_DELIVERY, REC_TIMER
from demi_tpu.native import analysis as native_analysis
from demi_tpu.native.analysis import (
    _np_racing_prescriptions,
    analysis_native_available,
    digest_keys,
    prescription_digest,
    prescription_digests,
    racing_pair_scan,
    racing_prescriptions_batch,
)

needs_native = pytest.mark.native


def _rand_lane(n, w, rng):
    """Random parent-tracked records: kinds mix deliveries/timers/other,
    parent/prev columns point at earlier positions or -1."""
    recs = np.zeros((n, w), np.int32)
    if n == 0:
        return recs
    recs[:, 0] = rng.choice([0, 1, 2, 5], size=n, p=[0.1, 0.5, 0.2, 0.2])
    recs[:, 1] = rng.integers(0, 4, n)
    recs[:, 2] = rng.integers(0, 4, n)
    recs[:, 3: w - 2] = rng.integers(0, 5, (n, w - 5))
    for p in range(n):
        recs[p, w - 2] = rng.integers(-1, p) if p else -1
        recs[p, w - 1] = rng.integers(-1, p) if p else -1
    return recs


def _legacy_prescriptions(records, trace_len, rec_width):
    """The pre-vectorization per-lane assembly, verbatim (the
    ``racing_prescriptions`` body before the batch path existed) — the
    parity reference for both the native and NumPy batch paths."""
    recs = records[:trace_len, :rec_width]
    pairs = racing_pair_scan(recs)
    if len(pairs) == 0:
        return []
    is_delivery = np.isin(recs[:, 0], (REC_DELIVERY, REC_TIMER))
    positions = np.nonzero(is_delivery)[0]
    tuples = {int(p): tuple(int(x) for x in recs[p]) for p in positions}
    ordered = [int(p) for p in positions]
    out = []
    for i, j in pairs:
        k = np.searchsorted(positions, i)
        prefix = [tuples[p] for p in ordered[:k]]
        prefix.append(tuples[int(j)])
        out.append(tuple(prefix))
    return out


def _unpack(rows, offsets, lanes):
    return [
        (
            int(lanes[k]),
            tuple(
                tuple(int(x) for x in r)
                for r in rows[offsets[k]: offsets[k + 1]]
            ),
        )
        for k in range(len(lanes))
    ]


def test_batch_prescriptions_match_legacy_randomized():
    """The batch entry point (native or NumPy) equals the legacy per-lane
    scans concatenated — lane-major, pair order preserved, rows
    byte-identical — over randomized record batches."""
    rng = np.random.default_rng(7)
    w, rmax = 9, 48
    for _trial in range(12):
        batch = int(rng.integers(1, 8))
        recs3 = np.stack([_rand_lane(rmax, w, rng) for _ in range(batch)])
        lens = rng.integers(0, rmax + 1, batch)
        rows, offsets, lanes, digests = racing_prescriptions_batch(
            recs3, lens, w
        )
        expected = []
        for b in range(batch):
            for presc in _legacy_prescriptions(recs3[b], int(lens[b]), w):
                expected.append((b, presc))
        assert _unpack(rows, offsets, lanes) == expected
        # The returned digests (C++ running-prefix fold on the native
        # path) equal the vectorized NumPy pass over the packed rows.
        assert np.array_equal(digests, prescription_digests(rows, offsets))


def test_numpy_fallback_matches_native_or_reference():
    """The NumPy fallback is semantics-identical to the batch contract
    (and to the native path when a compiler exists)."""
    rng = np.random.default_rng(11)
    w, rmax, batch = 8, 32, 5
    recs3 = np.stack([_rand_lane(rmax, w, rng) for _ in range(batch)])
    lens = np.clip(rng.integers(0, rmax + 1, batch), 0, rmax).astype(np.int32)
    sliced = np.ascontiguousarray(recs3[:, :, :w], np.int32)
    np_out = _np_racing_prescriptions(sliced, lens)
    batch_out = racing_prescriptions_batch(recs3, lens, w)
    for a, b in zip(np_out, batch_out[:3]):
        assert np.array_equal(a, b)
    assert np.array_equal(
        batch_out[3], prescription_digests(np_out[0], np_out[1])
    )


@needs_native
def test_native_analysis_builds():
    """The native library must build here (the CI image has g++); a miss
    would silently demote every frontier round to the NumPy path."""
    if not analysis_native_available():
        pytest.skip("no working C++ compiler in this environment")
    assert analysis_native_available()


def test_fallback_note_fires_once(monkeypatch):
    """A native miss emits the one-time obs counter + log line (silent
    native-miss regressions must be visible)."""
    from demi_tpu import obs

    monkeypatch.setattr(native_analysis, "_fallback_noted", False)
    obs.REGISTRY.reset()
    obs.enable()
    try:
        native_analysis.note_fallback("test")
        native_analysis.note_fallback("test")  # second call: no double count
        assert obs.counter("native.analysis_fallback").total() == 1
    finally:
        obs.disable()
        obs.REGISTRY.reset()


def test_prescription_digests_are_content_keys():
    """Digests over packed rows: equal blocks <=> equal keys, distinct
    blocks get distinct keys, and the tuple-form digest
    (``prescription_digest``) lands in the same key space."""
    rng = np.random.default_rng(3)
    w, rmax, batch = 9, 40, 6
    recs3 = np.stack([_rand_lane(rmax, w, rng) for _ in range(batch)])
    lens = np.full(batch, rmax)
    rows, offsets, lanes, digests = racing_prescriptions_batch(
        recs3, lens, w
    )
    if not len(lanes):
        pytest.skip("randomized fixture produced no racing pairs")
    assert np.array_equal(digests, prescription_digests(rows, offsets))
    keys = digest_keys(digests)
    by_block = {}
    for k in range(len(lanes)):
        block = tuple(
            tuple(int(x) for x in r) for r in rows[offsets[k]: offsets[k + 1]]
        )
        assert by_block.setdefault(block, keys[k]) == keys[k]
        assert prescription_digest(block) == keys[k]
    inverse = {}
    for block, key in by_block.items():
        assert inverse.setdefault(key, block) == block
    # The empty prescription (frontier root) digests consistently too.
    assert prescription_digest(tuple()) == prescription_digest(tuple())


def test_prefix_planner_vectorized_matches_reference():
    """Array LCP grouping == the per-chunk-bytes recursion, compared as
    (prefix_len, member-set, cache-key) sets + scratch sets, over
    randomized bucket/min_group/records shapes."""
    from demi_tpu.device.fork import PrefixPlanner

    rng = np.random.default_rng(5)

    def norm(groups, scratch):
        return (
            sorted(
                (g.prefix_len, tuple(sorted(g.indices)), g.key)
                for g in groups
            ),
            sorted(scratch),
        )

    for _trial in range(60):
        n = int(rng.integers(0, 16))
        rmax = int(rng.integers(1, 33))
        w = int(rng.integers(1, 7))
        fam = rng.integers(0, 3, n)
        base = rng.integers(0, 3, (3, rmax, w)).astype(np.int32)
        records = base[fam] if n else np.zeros((0, rmax, w), np.int32)
        for i in range(n):
            j = int(rng.integers(0, rmax))
            records[i, j:] = rng.integers(0, 3, (rmax - j, w))
        lengths = rng.integers(0, rmax + 1, n)
        planner = PrefixPlanner(
            bucket=int(rng.integers(1, 9)),
            min_group=int(rng.integers(1, 4)),
        )
        got = planner.plan(records, lengths)
        ref = planner.plan_reference(records, lengths)
        assert norm(*got) == norm(*ref)
        for g in got[0]:
            shared = records[g.indices[0], : g.prefix_len].tobytes()
            assert all(
                records[i, : g.prefix_len].tobytes() == shared
                for i in g.indices
            )


def test_pack_records_vectorized_semantics():
    """_pack_records: uniform rows stack in one conversion, guards
    (overflow, REC_NONE hole) keep their messages, ragged rows still
    pack."""
    from demi_tpu.device import DeviceConfig
    from demi_tpu.device.encoding import _pack_records
    from test_device_dpor import _setup

    app, cfg, _program = _setup(3)
    del app
    w = cfg.msg_width
    recs = [[1, 0, 1] + [7] * w, [2, 1, 1] + [0] * w]
    out = _pack_records(cfg, recs, 8)
    assert out.shape == (8, cfg.rec_width)
    assert out[0, :3].tolist() == [1, 0, 1]
    assert out[1, 0] == 2
    assert not out[2:].any()
    with pytest.raises(ValueError, match="records > 1"):
        _pack_records(cfg, recs, 1)
    with pytest.raises(ValueError, match="REC_NONE hole"):
        _pack_records(cfg, [[1, 0, 1] + [0] * w, [0] * (3 + w)], 8)
    ragged = _pack_records(cfg, [[1, 0, 1], [2, 1, 1] + [3] * w], 8)
    assert ragged[0, :3].tolist() == [1, 0, 1]
    assert ragged[1, 3] == 3


def harvested_rounds(dpor):
    """Make ``dpor`` note every round it harvests, before and after its
    host half: ``(traces, lens, the batch's tuples, entries in the
    explored log afterwards)`` a round."""
    rounds = []
    process = dpor._process_round

    def noting(res, batch, *args, **kw):
        row = [
            np.array(res.trace), np.array(res.trace_len),
            dpor._list(batch).tuples(),
        ]
        rounds.append(row)
        hit = process(res, batch, *args, **kw)
        row.append(len(dpor._explored_log))
        return hit

    dpor._process_round = noting
    return rounds


def test_a_whole_search_is_the_per_lane_closure_of_its_own_traces():
    """A DeviceDPOR search on 3-node raft admits, round by round and in
    order, exactly what a loop kept here derives from the same harvested
    traces with ``_legacy_prescriptions`` and a tuple set: the shipped
    scan, digest dedup and columnar log against a reference that is no
    shipped code."""
    from demi_tpu.apps.common import dsl_start_events
    from demi_tpu.device.dpor_sweep import DeviceDPOR
    from demi_tpu.external_events import WaitQuiescence
    from demi_tpu.parallel.distributed import build_workload

    app, cfg, _fuzzer = build_workload({
        "app": "raft", "nodes": 3, "bug": "multivote", "seed": 0,
        "num_events": 12, "max_messages": 64, "pool": 48,
        "timer_weight": 0.2, "kill_weight": 0.05, "partition_weight": 0.0,
    }, record=True)
    program = dsl_start_events(app) + [WaitQuiescence()]
    dpor = DeviceDPOR(app, cfg, program, batch_size=16, double_buffer=False)
    rounds = harvested_rounds(dpor)
    dpor.explore(max_rounds=4, stop_on_violation=False)
    assert len(rounds) == 4

    admitted, seen, executed = [()], {()}, set()
    for traces, lens, batch, n_after in rounds:
        executed.update(batch)
        for lane in range(len(batch)):
            for presc in _legacy_prescriptions(
                traces[lane], int(lens[lane]), cfg.rec_width
            ):
                if presc not in seen:
                    seen.add(presc)
                    admitted.append(presc)
        # Round by round: the log, in admission order, is the closure.
        assert n_after == len(admitted)
    assert list(dpor._explored_log) == admitted
    assert dpor.explored == seen and len(seen) > 100
    assert dpor.interleavings == sum(len(r[2]) for r in rounds)
    # Nothing admitted is lost: it was run, or it waits.
    assert set(dpor.frontier) == seen - executed
    # Both ledgers ran: the host/device split is measured, not assumed.
    assert dpor.host_seconds > 0 and dpor.device_seconds > 0


def test_continuous_autotuned_attribution_parity():
    """The collapsed continuous-autotuned path (shared driver + reward
    bucket over retirement arrays) fires the EXACT reward sequence the
    per-item loop fired: same begin/end_round count, same (hashes,
    violations, lanes) per epoch, same sweep result."""
    from demi_tpu.apps.broadcast import make_broadcast_app
    from demi_tpu.apps.common import dsl_start_events
    from demi_tpu.device import DeviceConfig
    from demi_tpu.device.core import ST_OVERFLOW
    from demi_tpu.external_events import (
        MessageConstructor,
        Send,
        WaitQuiescence,
    )
    from demi_tpu.parallel.sweep import SweepDriver

    app = make_broadcast_app(3, reliable=False)
    starts = dsl_start_events(app)

    def gen(seed):
        return list(starts) + [
            Send(app.actor_name(seed % 3), MessageConstructor(lambda: (1, 0))),
            WaitQuiescence(),
        ]

    cfg = DeviceConfig.for_app(
        app, pool_capacity=32, max_steps=64, max_external_ops=16,
        invariant_interval=0, early_exit=True,
    )

    class Rec:
        def __init__(self):
            self.rounds = []
            self.begins = 0

        def begin_round(self):
            self.begins += 1

        def end_round(self, *, hashes=(), violations=0, lanes=1):
            self.rounds.append(
                (sorted(int(h) for h in hashes), violations, lanes)
            )

    new_ctl = Rec()
    result = SweepDriver(app, cfg, gen).sweep_autotuned(
        40, 8, new_ctl, mode="continuous"
    )

    # Reference: the per-item epoch bucketing over the same retirement
    # stream (the logic _sweep_autotuned_continuous used to inline).
    ref_ctl = Rec()
    epoch_of_seed = {}
    cur = [0]

    def tagged(seed):
        epoch_of_seed[seed] = cur[0]
        return gen(seed)

    drv = SweepDriver(app, cfg, gen)._continuous_driver(8, 0, tagged)
    lanes_total = 0
    bl = bv = 0
    bh = []
    ref_ctl.begin_round()
    for seed, st, code, h in drv._run(40):
        lanes_total += 1
        if epoch_of_seed.get(seed, cur[0]) != cur[0]:
            continue
        bl += 1
        if st != ST_OVERFLOW:
            bh.append(h)
        if code != 0:
            bv += 1
        if bl >= 8:
            ref_ctl.end_round(hashes=bh, violations=bv, lanes=bl)
            bl = bv = 0
            bh = []
            cur[0] += 1
            ref_ctl.begin_round()
    if bl:
        ref_ctl.end_round(hashes=bh, violations=bv, lanes=bl)

    assert new_ctl.rounds == ref_ctl.rounds
    assert new_ctl.begins == ref_ctl.begins
    assert result.lanes == lanes_total


def test_continuous_stop_on_violation_keeps_retired_round():
    """stop_on_violation stops at the first violating HARVEST ROUND but
    keeps every already-retired lane result in that round (they are
    paid-for device work — the old array path truncated them away); the
    first violating seed is still the first in retirement order."""
    from demi_tpu.apps.broadcast import (
        broadcast_send_generator,
        make_broadcast_app,
    )
    from demi_tpu.apps.common import dsl_start_events
    from demi_tpu.device import DeviceConfig
    from demi_tpu.fuzzing import Fuzzer, FuzzerWeights
    from demi_tpu.parallel.sweep import SweepDriver

    app = make_broadcast_app(4, reliable=False)
    fz = Fuzzer(
        num_events=8,
        weights=FuzzerWeights(send=0.6, wait_quiescence=0.25, kill=0.15),
        message_gen=broadcast_send_generator(app),
        prefix=dsl_start_events(app), max_kills=1,
    )

    def gen(seed):
        return fz.generate_fuzz_test(seed=seed)

    cfg = DeviceConfig.for_app(
        app, pool_capacity=64, max_steps=96, max_external_ops=24
    )
    driver = SweepDriver(app, cfg, gen)
    result = driver.sweep(64, 8, stop_on_violation=True)
    if result.violations == 0:
        pytest.skip("fixture found no violation to stop on")
    chunk = result.chunks[0]
    # The run stopped AT the first violation: exactly one violating lane
    # counted, and the first seed is recorded.
    assert chunk.violations >= 1
    assert chunk.first_violating_seed is not None
    assert chunk.lanes <= 64
    # Reference: per-item iteration over a fresh driver agrees on the
    # first violating seed.
    drv = SweepDriver(app, cfg, gen)._continuous_driver(8)
    first = None
    for seed, _st, code, _h in drv._run(64):
        if code != 0:
            first = seed
            break
    assert first == chunk.first_violating_seed
