"""Continuous sweep (mid-flight lane refill): per-seed verdicts identical
to the plain explore kernel, across a fault-heavy mixed-length corpus."""

import numpy as np

import jax

from demi_tpu.apps.broadcast import broadcast_send_generator, make_broadcast_app
from demi_tpu.apps.raft import make_raft_app, raft_send_generator
from demi_tpu.apps.common import dsl_start_events
from demi_tpu.device import DeviceConfig, make_explore_kernel
from demi_tpu.device.continuous import ContinuousSweepDriver
from demi_tpu.device.encoding import lower_program, stack_programs
from demi_tpu.fuzzing import Fuzzer, FuzzerWeights


def _parity(app, cfg, gen, n, batch, seg_steps):
    drv = ContinuousSweepDriver(app, cfg, gen, batch=batch, seg_steps=seg_steps)
    statuses, violations = drv.sweep(n)
    assert len(statuses) == n

    kernel = make_explore_kernel(app, cfg)
    progs = stack_programs([lower_program(app, cfg, gen(s)) for s in range(n)])
    keys = np.stack([np.asarray(jax.random.PRNGKey(s)) for s in range(n)])
    ref = kernel(progs, keys)
    ref_status = np.asarray(ref.status)
    ref_vio = np.asarray(ref.violation)
    for s in range(n):
        assert statuses[s] == int(ref_status[s]), s
        assert violations[s] == int(ref_vio[s]), s
    return violations


def _raft_fixture(max_steps):
    app = make_raft_app(3, bug="multivote")
    cfg = DeviceConfig.for_app(
        app, pool_capacity=96, max_steps=max_steps, max_external_ops=24,
        invariant_interval=1, timer_weight=0.1,
    )
    fz = Fuzzer(
        num_events=10,
        weights=FuzzerWeights(
            send=0.3, kill=0.1, wait_quiescence=0.3, hard_kill=0.15,
            restart=0.15,
        ),
        message_gen=raft_send_generator(app),
        prefix=dsl_start_events(app), max_kills=2, wait_budget=(5, 30),
    )
    return app, cfg, lambda s: fz.generate_fuzz_test(seed=s)


def test_continuous_matches_plain_kernel_broadcast():
    app = make_broadcast_app(4, reliable=False)
    cfg = DeviceConfig.for_app(
        app, pool_capacity=64, max_steps=96, max_external_ops=24
    )
    fz = Fuzzer(
        num_events=8,
        weights=FuzzerWeights(send=0.6, wait_quiescence=0.25, kill=0.15),
        message_gen=broadcast_send_generator(app),
        prefix=dsl_start_events(app), max_kills=1,
    )
    violations = _parity(
        app, cfg, lambda s: fz.generate_fuzz_test(seed=s), 32, 8, 16
    )
    assert any(violations.values())


def test_continuous_matches_plain_kernel_raft_faults():
    """Mixed-length lanes (full drains vs quick crashes) + the forced
    finalization path for budget-exhausted lanes."""
    _parity(*_raft_fixture(160), 24, 8, 32)


def test_continuous_nondivisible_seg_steps():
    """seg_steps that does NOT divide max_steps: the segment kernel must
    clamp each lane exactly at the step budget (advisor repro: raft
    multivote, max_steps=40, seg_steps=28 — seed 59 diverged before the
    per-lane budget mask)."""
    _parity(*_raft_fixture(40), 64, 8, 28)


def test_sweep_driver_continuous_parity_and_occupancy(monkeypatch):
    """SweepDriver.sweep defaults to the lane-compacted continuous path:
    per-seed verdicts must match chunked mode exactly (same fold_in key
    scheme), and on a heavy-tailed corpus the compacted sweep's lane-step
    occupancy must stay high (the whole point of the refill)."""
    from demi_tpu.device import continuous
    from demi_tpu.parallel.sweep import SweepDriver

    app, cfg, gen = _raft_fixture(160)
    driver = SweepDriver(app, cfg, gen)
    cont = driver.sweep(48, 8)  # default mode: continuous
    chunked = driver.sweep(48, 8, mode="chunked")
    assert cont.occupancy is not None and cont.occupancy > 0.5
    assert chunked.occupancy is None
    assert cont.lanes == chunked.lanes == 48
    assert cont.violations == chunked.violations > 0
    assert cont.codes == chunked.codes
    assert cont.unique_schedules == chunked.unique_schedules
    # Heavy-tailed corpus: quick-crash lanes end far below max_steps, so
    # the compacted sweep must scan meaningfully fewer lane-steps than
    # the fixed sweep's lanes * max_steps. That is the strict order's
    # pin; at 4 segments a life the harvest lags (PR 48), every lane
    # that stops on its own sits frozen through one more segment, and
    # the job scans whole rounds more: never more than the fixed sweep,
    # and the same live lane-steps.
    drv = driver._continuous_driver(8)
    assert drv._lag() == 1
    lagged = (drv.last_total_lane_steps, drv.last_live_lane_steps)
    monkeypatch.setattr(continuous, "_LAG_LIFE", 1 << 30)
    strict = driver.sweep(48, 8)
    assert drv._lag() == 0 and strict.lanes_digest == cont.lanes_digest
    assert 0 < drv.last_total_lane_steps < 48 * cfg.max_steps
    assert drv.last_total_lane_steps <= lagged[0] <= 48 * cfg.max_steps
    assert (lagged[0] - drv.last_total_lane_steps) % (8 * drv.seg_steps) == 0
    assert lagged[1] == drv.last_live_lane_steps
    # first_violating_seed is a real, replayable seed in BOTH modes.
    assert chunked.first_violating_seed in range(48)
    assert cont.first_violating_seed in range(48)


def test_continuous_time_to_first_violation():
    app = make_broadcast_app(4, reliable=False)
    cfg = DeviceConfig.for_app(
        app, pool_capacity=64, max_steps=96, max_external_ops=24
    )
    fz = Fuzzer(
        num_events=8,
        weights=FuzzerWeights(send=0.6, wait_quiescence=0.25, kill=0.15),
        message_gen=broadcast_send_generator(app),
        prefix=dsl_start_events(app), max_kills=1,
    )
    drv = ContinuousSweepDriver(
        app, cfg, lambda s: fz.generate_fuzz_test(seed=s), batch=8,
        seg_steps=16,
    )
    secs, seed = drv.time_to_first_violation(max_lanes=64)
    assert secs is not None and secs > 0
    assert seed is not None


def _broadcast_fixture():
    app = make_broadcast_app(4, reliable=False)
    cfg = DeviceConfig.for_app(
        app, pool_capacity=64, max_steps=96, max_external_ops=24
    )
    fz = Fuzzer(
        num_events=8,
        weights=FuzzerWeights(send=0.6, wait_quiescence=0.25, kill=0.15),
        message_gen=broadcast_send_generator(app),
        prefix=dsl_start_events(app), max_kills=1,
    )
    return app, cfg, lambda s: fz.generate_fuzz_test(seed=s)


def test_continuous_mesh_parity():
    """Lane-sharded continuous refill over the 8-device mesh: per-seed
    verdicts identical to the unsharded driver, occupancy accounting
    intact, and batches that aren't mesh multiples are rounded with inert
    surplus lanes (never yielded)."""
    from demi_tpu.parallel.mesh import make_mesh

    app, cfg, gen = _broadcast_fixture()
    mesh = make_mesh()
    assert mesh.size > 1, "conftest should provide the 8-device CPU mesh"
    plain = ContinuousSweepDriver(app, cfg, gen, batch=8, seg_steps=16)
    sharded = ContinuousSweepDriver(
        app, cfg, gen, batch=8, seg_steps=16, mesh=mesh
    )
    st_a, vio_a = plain.sweep(20)  # 20 < batch-aligned lanes: inert path
    st_b, vio_b = sharded.sweep(20)
    assert st_a == st_b
    assert vio_a == vio_b
    assert sharded.last_occupancy is not None


def test_sweep_driver_continuous_under_mesh():
    """SweepDriver end-to-end: continuous mode is the default for
    mesh-sharded drivers too, with verdict parity against the chunked
    path."""
    from demi_tpu.parallel.sweep import SweepDriver

    app, cfg, gen = _broadcast_fixture()
    driver_mesh = SweepDriver(app, cfg, gen, use_mesh=True)
    cont = driver_mesh.sweep(24, 8)  # default: continuous
    chunked = driver_mesh.sweep(24, 8, mode="chunked")
    assert cont.occupancy is not None
    assert cont.lanes == chunked.lanes == 24
    assert cont.violations == chunked.violations
    assert cont.codes == chunked.codes
    assert cont.unique_schedules == chunked.unique_schedules


def test_sweep_async_non_blocking_explore():
    """Device-tier nonBlockingExplore analog: chunk results stream while
    the next chunk's kernel is in flight; totals match the blocking sweep,
    and closing the generator ends the sweep early."""
    from demi_tpu.parallel.sweep import SweepDriver

    app, cfg, gen = _broadcast_fixture()
    driver = SweepDriver(app, cfg, gen)
    chunks = list(driver.sweep_async(24, 8))
    assert [c.lanes for c in chunks] == [8, 8, 8]
    blocking = driver.sweep(24, 8, mode="chunked")
    assert sum(c.violations for c in chunks) == blocking.violations
    # Early stop: draining only the first chunk is legal.
    it = driver.sweep_async(24, 8)
    first = next(it)
    it.close()
    assert first.lanes == 8


def test_host_non_blocking_explore():
    from demi_tpu.apps.common import dsl_start_events, make_host_invariant
    from demi_tpu.config import SchedulerConfig
    from demi_tpu.external_events import MessageConstructor, Send, WaitQuiescence
    from demi_tpu.schedulers import RandomScheduler

    app = make_broadcast_app(4, reliable=False)
    # Two nodes get the broadcast externally, two never do: with
    # per-delivery invariant checks, EVERY schedule's first delivery
    # creates disagreement — so the stream must yield a violating result
    # on its very first execution (deterministic early stop).
    program = dsl_start_events(app) + [
        Send(app.actor_name(0), MessageConstructor(lambda: (1, 0))),
        Send(app.actor_name(1), MessageConstructor(lambda: (1, 0))),
        WaitQuiescence(),
    ]
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    sched = RandomScheduler(config, seed=0, invariant_check_interval=1)
    seen = 0
    found = None
    for result in sched.non_blocking_explore(program, max_executions=50):
        seen += 1
        if result.violation is not None:
            found = result
            break  # early stop mid-stream
    assert found is not None and found.violation.code == 1
    assert seen == 1  # first execution already violates; stream stopped


def test_continuous_arbitrary_seed_partition():
    """A strided seed list (a distributed rank's partition) sweeps with
    verdicts identical to the plain kernel on those same seeds."""
    app, cfg, gen = _broadcast_fixture()
    seeds = list(range(1, 48, 3))  # rank-1-of-3-style stride
    drv = ContinuousSweepDriver(app, cfg, gen, batch=8, seg_steps=16)
    statuses, violations = drv.sweep(seeds=seeds)
    assert sorted(statuses) == seeds
    kernel = make_explore_kernel(app, cfg)
    progs = stack_programs([lower_program(app, cfg, gen(s)) for s in seeds])
    keys = np.stack([np.asarray(jax.random.PRNGKey(s)) for s in seeds])
    ref = kernel(progs, keys)
    for i, s in enumerate(seeds):
        assert statuses[s] == int(np.asarray(ref.status)[i]), s
        assert violations[s] == int(np.asarray(ref.violation)[i]), s


# -- programs made ahead, while the segment runs (PR 28) ---------------------

import itertools

import pytest

from demi_tpu.device import continuous


class _Ahead:
    """One driver (one set of kernels) whose generator logs its calls,
    reads a per-call ``base`` as the benchmark's does, and whose
    readiness probe is a stub that logs too: ``busy`` decides whether
    the device still runs the segment at the k-th probe."""

    N = 40          # more than three resident sets of 8
    BATCH = 8

    def __init__(self):
        app, cfg, gen = _broadcast_fixture()
        def logged(seed):
            self.log.append((self.base, seed))
            return gen(self.base + seed)

        # 28 does not divide the 96-step budget.
        self.drv = ContinuousSweepDriver(
            app, cfg, logged, batch=self.BATCH, seg_steps=28
        )
        self.reset(seed_pure=False)

    def ready(self, _array) -> bool:
        busy = self.busy(next(self._probes))
        self.log.append("busy" if busy else "ready")
        return not busy

    def reset(self, seed_pure, base=0, busy=lambda k: True):
        self.base, self.busy, self.log = base, busy, []
        self._probes = itertools.count()
        self.drv.seed_pure = seed_pure

    def run(self, seed_pure, base=0, busy=lambda k: True):
        """Every yielded batch of one whole sweep, as lists."""
        self.reset(seed_pure, base, busy)
        return [
            tuple(a.tolist() for a in batch)
            for batch in self.drv._run_batches(self.N)
        ]

    def made_ahead(self):
        """Seeds whose generator call came straight after a probe that
        found the device busy: made between a dispatch and its pull."""
        return [
            cur[1] for prev, cur in zip(self.log, self.log[1:])
            if prev == "busy" and not isinstance(cur, str)
        ]

    def generated(self):
        return [e for e in self.log if not isinstance(e, str)]


@pytest.fixture(scope="module")
def ahead():
    return _Ahead()


@pytest.fixture
def stub_ready(ahead, monkeypatch):
    monkeypatch.setattr(continuous, "_ready", ahead.ready)


@pytest.mark.parametrize("busy", ["always", "three_probes_in_four", "never"])
def test_made_ahead_changes_no_verdict_hash_or_batch_order(
    ahead, stub_ready, busy
):
    """(status, code, hash) per seed and the order of the yielded
    batches equal a run that makes nothing ahead, whether the gap
    serves the whole refill, part of it, or none."""
    plain = ahead.run(seed_pure=False)
    assert ahead.log == [(0, s) for s in range(ahead.N)]    # no probe
    policy = {
        "always": lambda k: True,
        "three_probes_in_four": lambda k: k % 4 != 3,
        "never": lambda k: False,
    }[busy]
    got = ahead.run(seed_pure=True, busy=policy)
    assert got == plain
    assert sum(len(b[0]) for b in got) == ahead.N
    # the generator still sees every seed once, in seed order
    assert ahead.generated() == [(0, s) for s in range(ahead.N)]
    made = ahead.made_ahead()
    if busy == "always":
        # every refill was served from the stock; the prime fill cannot be
        assert made == list(range(ahead.BATCH, ahead.N))
    elif busy == "never":
        assert made == []
    else:
        assert 0 < len(made) < ahead.N - ahead.BATCH


def test_a_stock_is_not_used_by_the_next_call(ahead, stub_ready):
    """The benchmark's generator closes over a base that changes with
    the job: a consumer that stops early leaves programs made under the
    old base, and the next call must not hand them out."""
    want = ahead.run(seed_pure=False, base=1000)
    ahead.reset(seed_pure=True)
    for _batch in ahead.drv._run_batches(ahead.N):
        break                       # as stop_on_violation does
    assert len(ahead.log) > ahead.BATCH      # a stock had been made
    got = ahead.run(seed_pure=True, base=1000)
    assert got == want
    # every program of the second call was generated in it, under its base
    assert ahead.generated() == [(1000, s) for s in range(ahead.N)]


def test_a_stateful_generator_is_called_only_at_refill(ahead, stub_ready):
    """Not declared a function of the seed (the autotuned sweep's
    epoch-tagging wrapper): no probe, and between two yields exactly the
    programs of the lanes the round refills, as before this PR."""
    ahead.reset(seed_pure=False)
    handed = ahead.BATCH
    seen = ahead.BATCH     # the prime fill, before the first segment
    for seeds, *_ in ahead.drv._run_batches(ahead.N):
        refilled = min(len(seeds), ahead.N - handed)
        handed += refilled
        seen += refilled
        assert len(ahead.log) == seen
    assert ahead.log == [(0, s) for s in range(ahead.N)]    # no probe


def test_only_the_cached_driver_is_told_its_generator_is_seed_pure():
    """``SweepDriver._continuous_driver`` draws the line where it
    already did: an overriding generator closes over live state."""
    from demi_tpu.parallel.sweep import SweepDriver

    app, cfg, gen = _broadcast_fixture()
    driver = SweepDriver(app, cfg, gen)
    assert driver._continuous_driver(8).seed_pure is True
    assert driver._continuous_driver(8, program_gen=gen).seed_pure is False
    assert ContinuousSweepDriver(app, cfg, gen, batch=8).seed_pure is False


def test_breaking_out_early_leaves_the_driver_reusable(ahead, stub_ready):
    want = ahead.run(seed_pure=False)
    ahead.reset(seed_pure=True)
    for _ in range(2):
        for _batch in ahead.drv._run_batches(ahead.N):
            break
    assert ahead.run(seed_pure=True) == want
    statuses, violations = ahead.drv.sweep(ahead.N)
    assert sorted(statuses) == list(range(ahead.N))


def test_memo_hits_are_not_made_ahead(stub_ready, ahead):
    """With a ``program_key`` memo a hit costs nothing at refill either:
    only the first period's programs are generated, ahead or not, and
    the verdicts equal the unmemoized driver's."""
    app, cfg, gen = _broadcast_fixture()
    calls = []

    def logged(seed):
        calls.append(seed)
        return gen(seed % 12)

    drv = ContinuousSweepDriver(
        app, cfg, logged, batch=8, seg_steps=16,
        program_key=lambda s: s % 12, seed_pure=True,
    )
    ahead.reset(seed_pure=True)      # the stub's policy: always busy
    got = drv.sweep(40)
    assert calls == list(range(12))
    drv.seed_pure, drv._lower_memo = False, {}
    assert drv.sweep(40) == got


def test_the_three_stop_rules_of_making_ahead(ahead, stub_ready):
    """Ready, room (active lanes), seeds used up; and an array with no
    readiness probe reads ready, so nothing is made ahead of it."""
    seeds = list(range(ahead.N))
    ahead.reset(seed_pure=True)
    stock = continuous._Stock(ahead.drv.cfg, 8)
    ahead.drv._make_ahead(None, seeds, 8, 3, stock)
    assert stock.count == 3 and ahead.generated() == [(0, 8), (0, 9), (0, 10)]
    ahead.drv._make_ahead(None, seeds, 8, 3, stock)     # no room left
    assert stock.count == 3
    ahead.drv._make_ahead(None, seeds, 8, 5, stock)     # continues in order
    assert ahead.generated()[3:] == [(0, 11), (0, 12)]
    stock.take(stock.count)
    ahead.drv._make_ahead(None, seeds, ahead.N - 2, 8, stock)   # seeds end
    assert stock.count == 2
    ahead.reset(seed_pure=True, busy=lambda k: k == 0)
    stock = continuous._Stock(ahead.drv.cfg, 8)
    ahead.drv._make_ahead(None, seeds, 0, 8, stock)     # ready after one
    assert stock.count == 1


def test_an_array_with_no_probe_reads_ready():
    import jax.numpy as jnp

    assert continuous._ready(object()) is True
    assert continuous._ready(np.zeros(3)) is True
    assert continuous._ready(jnp.zeros(3).block_until_ready()) is True


# -- PR 30: the resident set is one set of host arrays, written in place --

class _InPlace:
    """The broadcast fixture, its ``SweepDriver.run_chunk`` references
    (the same seeds through the plain explore kernel, under the sweep's
    ``fold_in(PRNGKey(0), seed)`` keys) and the ways a continuous driver
    can be fed, each held to them seed for seed."""

    N = 40          # five resident sets of 8: refills span many rounds
    BATCH = 8
    PERIOD = 12

    def __init__(self):
        from demi_tpu.parallel.sweep import SweepDriver

        self.app, self.cfg, self.gen = _broadcast_fixture()
        self.periodic = lambda s: self.gen(s % self.PERIOD)
        self.want = {
            name: SweepDriver(self.app, self.cfg, gen).run_chunk(range(self.N))
            for name, gen in (("gen", self.gen), ("periodic", self.periodic))
        }

    def driver(self, gen, **kwargs):
        return ContinuousSweepDriver(
            self.app, self.cfg, gen, batch=self.BATCH, seg_steps=28,
            key_fn=lambda s: jax.random.fold_in(jax.random.PRNGKey(0), s),
            **kwargs,
        )


@pytest.fixture(scope="module")
def in_place():
    return _InPlace()


# how the driver is fed -> (reference, programs lowered from rows of 40)
_FEEDS = {
    "every_refill_from_the_stock": ("gen", 40),
    "nothing_made_ahead": ("gen", 40),
    "some_made_ahead": ("gen", 40),
    "not_seed_pure": ("gen", 40),
    "program_key_memo": ("periodic", 12),
    "program_key_memo_not_seed_pure": ("periodic", 12),
    "plain_lists": ("gen", 0),
    "plain_lists_made_ahead": ("gen", 0),
    "two_device_mesh": ("gen", 40),
    "two_device_mesh_odd_batch": ("gen", 40),
}


@pytest.mark.parametrize("feed", list(_FEEDS))
def test_in_place_resident_arrays_match_run_chunk(in_place, monkeypatch, feed):
    """Statuses, codes and ``sched_hash``es, seed for seed (the order-free
    ``lanes_digest`` over them, the code ledger and the delivered
    sequences), equal ``SweepDriver.run_chunk`` on the same seeds however
    the programs reach the lanes; and the fills count what they lowered
    from op rows."""
    from demi_tpu import obs
    from demi_tpu.parallel.mesh import make_mesh
    from demi_tpu.parallel.sweep import lanes_digest

    ref, rows = _FEEDS[feed]
    want = in_place.want[ref]
    gen = in_place.gen
    probes = itertools.count()
    busy = {
        "nothing_made_ahead": lambda: False,
        "some_made_ahead": lambda: next(probes) % 3 != 2,
    }.get(feed, lambda: True)
    monkeypatch.setattr(continuous, "_ready", lambda _array: not busy())
    if feed in ("every_refill_from_the_stock", "nothing_made_ahead",
                "some_made_ahead"):
        drv = in_place.driver(gen, seed_pure=True)
    elif feed == "not_seed_pure":
        drv = in_place.driver(gen)
    elif feed.startswith("program_key_memo"):
        drv = in_place.driver(
            in_place.periodic, program_key=lambda s: s % in_place.PERIOD,
            seed_pure=feed == "program_key_memo",
        )
    elif feed.startswith("plain_lists"):
        drv = in_place.driver(
            lambda s: list(gen(s)), seed_pure=feed.endswith("made_ahead")
        )
    else:
        mesh = make_mesh(jax.devices()[:2])
        drv = in_place.driver(gen, seed_pure=True, mesh=mesh)
        if feed.endswith("odd_batch"):
            drv.batch = 7    # rounded up to a mesh multiple
    n = in_place.N
    obs.disable()
    obs.TRACER.clear()
    obs.enable()
    try:
        batches = list(drv._run_batches(n))
        counts = obs.stage_counts()
    finally:
        obs.disable()
        obs.TRACER.clear()
    seeds, statuses, codes, hashes = (
        np.concatenate([b[k] for b in batches]) for k in range(4)
    )
    assert sorted(seeds.tolist()) == list(range(n))
    assert lanes_digest(seeds, statuses, codes, hashes) == want.lanes_digest
    assert int((codes != 0).sum()) == want.violations
    assert np.array_equal(np.unique(hashes), want.unique_hashes)
    ledger = {
        int(c): int(k)
        for c, k in zip(*np.unique(codes[codes != 0], return_counts=True))
    }
    assert ledger == want.codes
    assert counts["sweep.programs"] == n
    assert counts["sweep.row_lowered"] == rows
    if feed.startswith("two_device_mesh"):
        assert drv.last_lane_sharding["devices"] == 2
    if feed == "every_refill_from_the_stock":
        assert counts["sweep.prefetched"] == n - in_place.BATCH
    if feed in ("nothing_made_ahead", "not_seed_pure", "plain_lists"):
        assert counts["sweep.prefetched"] == 0


def test_the_continuous_path_stacks_nothing(in_place, monkeypatch):
    """``stack_programs`` is off the continuous path: the resident set is
    allocated once a call and every fill writes into it."""
    from demi_tpu.device import encoding

    def refuse(_programs):
        raise AssertionError("stack_programs called on the continuous path")

    monkeypatch.setattr(encoding, "stack_programs", refuse)
    assert not hasattr(continuous, "stack_programs")
    made = []
    real = continuous.empty_programs
    monkeypatch.setattr(
        continuous, "empty_programs",
        lambda cfg, lanes: made.append(lanes) or real(cfg, lanes),
    )
    monkeypatch.setattr(continuous, "_ready", lambda _array: False)
    drv = in_place.driver(in_place.gen, seed_pure=True)
    statuses, _violations = drv.sweep(in_place.N)
    assert len(statuses) == in_place.N
    # one resident block and one stock block, for the whole sweep
    assert made == [in_place.BATCH, in_place.BATCH]


def test_a_made_ahead_program_never_touches_the_resident_arrays(
    in_place, monkeypatch
):
    """What is made while the device may still read the resident set goes
    to the stock's own block (the CPU backend may alias NumPy memory)."""
    drv = in_place.driver(in_place.gen, seed_pure=True)
    resident, stock = continuous.empty_programs(drv.cfg, 8), continuous._Stock(drv.cfg, 8)
    for arr in resident:
        arr[:] = 5
    monkeypatch.setattr(continuous, "_ready", lambda _array: False)
    drv._make_ahead(None, list(range(40)), 8, 6, stock)
    assert stock.count == 6 and all((arr == 5).all() for arr in resident)
    assert stock.made[:6].all() and stock.from_rows[:6].all()
    drv._fill(list(range(8, 12)), [1, 3, 4, 6], resident, stock)
    assert stock.count == 2 and stock.head == 4
    for lane, seed in zip([1, 3, 4, 6], range(8, 12)):
        want = lower_program(drv.app, drv.cfg, in_place.gen(seed))
        for arr, ref in zip(resident, want):
            assert np.array_equal(arr[lane], ref)
    assert all((arr[[0, 2, 5, 7]] == 5).all() for arr in resident)
    # the ring wraps: six more made, then a fill longer than the stock
    drv._make_ahead(None, list(range(40)), 12, 8, stock)
    assert stock.count == 8
    drv._fill(list(range(12, 22)), list(range(8)) + [0, 1], resident, stock)
    assert stock.count == 0
    for lane, seed in zip([2, 3, 4, 5, 6, 7, 0, 1], range(14, 22)):
        want = lower_program(drv.app, drv.cfg, in_place.gen(seed))
        for arr, ref in zip(resident, want):
            assert np.array_equal(arr[lane], ref)


# -- PR 46: the harvest runs one segment behind the device -------------------

import contextlib
import dataclasses

from demi_tpu import obs
from demi_tpu.device.core import ST_DONE, ST_UNFINISHED
from demi_tpu.parallel.sweep import lanes_digest

_NEVER = 1 << 30     # a ``_LAG_LIFE`` no budget reaches: the strict order


@contextlib.contextmanager
def _spans_live():
    """The stage tables, empty and recording, for the block's sweeps."""
    obs.disable()
    obs.TRACER.clear()
    obs.enable()
    try:
        yield
    finally:
        obs.disable()
        obs.TRACER.clear()


def _short_broadcast_fixture():
    """The broadcast app judges at quiescence: on 20 steps two floods in
    three are cut by the budget and end with no verdict."""
    app, cfg, gen = _broadcast_fixture()
    assert app.invariant_at == "quiescence" and cfg.invariant_interval == 0
    return app, dataclasses.replace(cfg, max_steps=20), gen


# case -> (fixture, seeds, resident lanes, seg_steps, devices of the mesh)
_LAG_CASES = {
    "broadcast": (_broadcast_fixture, 40, 8, 16, 0),
    "raft_faults": (lambda: _raft_fixture(160), 24, 8, 32, 0),
    "seg_steps_not_dividing": (lambda: _raft_fixture(40), 32, 8, 28, 0),
    "ends_at_the_budget": (lambda: _raft_fixture(48), 24, 8, 16, 0),
    "unfinished_at_the_budget": (_short_broadcast_fixture, 24, 8, 8, 0),
    "mesh": (_broadcast_fixture, 20, 8, 16, 2),
    "shorter_than_a_resident_set": (_broadcast_fixture, 5, 8, 16, 0),
    # PR 48, 4 segments a life: every lane runs to its budget; some
    # stop on their own, one of them a segment before its budget
    "budget": (lambda: _budget_fixture(), 24, 8, 16, 0),
    "mixed": (lambda: _raft_fixture(64), 64, 8, 16, 0),
    "budget_mesh": (lambda: _budget_fixture(), 20, 8, 16, 2),
}


def _budget_fixture():
    """A correct raft nobody kills: its timers keep every schedule going
    to its last step."""
    app = make_raft_app(3)
    cfg = DeviceConfig.for_app(
        app, pool_capacity=96, max_steps=64, max_external_ops=24,
        invariant_interval=1, timer_weight=0.1,
    )
    fz = Fuzzer(
        num_events=10,
        weights=FuzzerWeights(send=0.6, wait_quiescence=0.4),
        message_gen=raft_send_generator(app),
        prefix=dsl_start_events(app), wait_budget=(5, 30),
    )
    return app, cfg, lambda s: fz.generate_fuzz_test(seed=s)


class _Lagged:
    """One driver of a case and what the plain explore kernel says of
    the same seeds; ``run`` is one whole sweep under a forced lag."""

    def __init__(self, case):
        from demi_tpu.parallel.mesh import make_mesh

        fixture, self.n, batch, seg_steps, devices = _LAG_CASES[case]
        self.app, self.cfg, gen = fixture()
        self.gen = gen
        mesh = make_mesh(jax.devices()[:devices]) if devices else None
        self.drv = ContinuousSweepDriver(
            self.app, self.cfg, gen, batch=batch, seg_steps=seg_steps,
            mesh=mesh,
        )
        self.finalized = 0
        finalize = self.drv.finalize

        def counted(state):
            self.finalized += 1
            return finalize(state)

        self.drv.finalize = counted
        progs = stack_programs(
            [lower_program(self.app, self.cfg, gen(s)) for s in range(self.n)]
        )
        keys = np.stack(
            [np.asarray(jax.random.PRNGKey(s)) for s in range(self.n)]
        )
        ref = make_explore_kernel(self.app, self.cfg)(progs, keys)
        self.want = {
            s: (st, code, h) for s, st, code, h in zip(
                range(self.n), np.asarray(ref.status).tolist(),
                np.asarray(ref.violation).tolist(),
                np.asarray(ref.sched_hash).tolist(),
            )
        }

    def run(self, monkeypatch, lag):
        """The yielded batches, as lists."""
        monkeypatch.setattr(continuous, "_LAG_LIFE", 0 if lag else _NEVER)
        assert self.drv._lag() == lag
        return [
            tuple(a.tolist() for a in batch)
            for batch in self.drv._run_batches(self.n)
        ]


def _per_seed(batches):
    return {
        s: (st, code, h)
        for seeds, statuses, codes, hashes in batches
        for s, st, code, h in zip(seeds, statuses, codes, hashes)
    }


def _digest(per_seed):
    seeds = sorted(per_seed)
    cols = np.array([per_seed[s] for s in seeds], np.int64)
    return lanes_digest(np.array(seeds), cols[:, 0], cols[:, 1], cols[:, 2])


@pytest.mark.parametrize("case", list(_LAG_CASES))
def test_the_lagged_harvest_changes_no_verdict(case, monkeypatch):
    """(status, code, sched_hash) per seed and ``lanes_digest`` under
    the lag equal the strict order and the plain explore kernel; the
    yielded batches are the same in two runs; a frozen segment a
    schedule is what it costs."""
    fx = _Lagged(case)
    plain = fx.run(monkeypatch, lag=0)
    plain_steps = fx.drv.last_total_lane_steps
    fx.finalized = 0
    lagged = fx.run(monkeypatch, lag=1)
    assert _per_seed(plain) == fx.want
    assert _per_seed(lagged) == fx.want
    assert _digest(_per_seed(lagged)) == _digest(fx.want)
    # every seed once: no lane is retired by two paths
    assert sorted(s for batch in lagged for s in batch[0]) == list(range(fx.n))
    assert fx.run(monkeypatch, lag=1) == lagged
    assert fx.drv.last_total_lane_steps >= plain_steps
    assert 0 < fx.drv.last_live_lane_steps <= fx.drv.last_total_lane_steps
    statuses = [st for st, _code, _h in fx.want.values()]
    if case == "ends_at_the_budget":
        # the eager finalize ran, and no lane was left without a verdict
        assert fx.finalized > 0 and ST_UNFINISHED not in statuses
    if case == "unfinished_at_the_budget":
        assert fx.finalized > 0 and 0 < statuses.count(ST_UNFINISHED) < fx.n
        assert fx.drv.last_unfinished_lanes == statuses.count(ST_UNFINISHED)
    if case == "mesh":
        assert fx.drv.last_lane_sharding["devices"] == 2


def test_the_budget_path_is_queued_behind_its_segment(monkeypatch):
    """Lanes that spend their budget in a segment are finalized right
    behind it, from the host's own step counts: before the round's first
    look at the device, under either order."""
    fx = _Lagged("ends_at_the_budget")
    fx.drv.seed_pure = True
    log = []
    segment, finalize = fx.drv.segment, fx.drv.finalize
    fx.drv.segment = lambda *a: log.append("segment") or segment(*a)
    fx.drv.finalize = lambda state: log.append("finalize") or finalize(state)
    monkeypatch.setattr(
        continuous, "_ready", lambda _array: log.append("probe") or False
    )
    for lag in (0, 1):
        del log[:]
        assert _per_seed(fx.run(monkeypatch, lag)) == fx.want
        assert "finalize" in log and "probe" in log
        assert all(
            prev == "segment"
            for prev, cur in zip(log, log[1:]) if cur == "finalize"
        )


@pytest.mark.parametrize("how", ["break", "stop_on_violation"])
def test_an_outstanding_segment_leaves_the_driver_reusable(
    ahead, stub_ready, monkeypatch, how
):
    """One segment is in flight when a consumer stops: the next call
    starts from its own state."""
    from demi_tpu.parallel.sweep import SweepDriver

    want = ahead.run(seed_pure=False)
    monkeypatch.setattr(continuous, "_LAG_LIFE", 0)
    ahead.reset(seed_pure=True)
    if how == "break":
        for _ in range(2):
            for _batch in ahead.drv._run_batches(ahead.N):
                break
    else:
        app, cfg, gen = _broadcast_fixture()
        driver = SweepDriver(app, cfg, gen)
        assert driver._continuous_driver(8)._lag() == 1
        stopped = driver.sweep(64, 8, stop_on_violation=True)
        assert 0 < stopped.lanes < 64 and stopped.violations
        whole = driver.sweep(64, 8)
        chunked = driver.sweep(64, 8, mode="chunked")
        assert whole.lanes_digest == chunked.lanes_digest
        assert whole.codes == chunked.codes
    assert ahead.run(seed_pure=True) == want


@pytest.mark.parametrize("case", ["broadcast", "budget", "mixed"])
def test_a_fill_never_writes_a_set_a_segment_in_flight_took(
    case, monkeypatch
):
    """Under the lag the segment in flight reads the rows of the lanes
    it spends live while the fill writes their successors' (PR 48): a
    fill writes the other resident set. Every dispatched set still holds
    its bytes of the dispatch when the next segment is dispatched (the
    one pull between the two is of the segment before it), whatever is
    written meanwhile, and no fill's target shares memory with it."""
    fx = _Lagged(case)
    segment, fill = fx.drv.segment, fx.drv._fill
    flown = []      # (the set a dispatch took, its bytes then), newest last
    filled = []

    def held_to_its_bytes(state, progs, steps_run):
        if flown:
            took, then = flown[-1]
            assert all(
                np.array_equal(x, y) for x, y in zip(took, then)
            ), "a set was written while its segment was in flight"
        flown.append((progs, type(progs)(*(x.copy() for x in progs))))
        return segment(state, progs, steps_run)

    def not_the_set_in_flight(seeds, lanes, progs, stock=None):
        if flown:       # (the prime fill comes before any segment)
            filled.extend(lanes)
            took, _then = flown[-1]
            assert not any(
                np.shares_memory(x, y) for x, y in zip(progs, took)
            )
            # whatever the free set held in these rows goes
            for x in progs:
                x[np.asarray(lanes)] = 1 << 30
        return fill(seeds, lanes, progs, stock)

    fx.drv.segment, fx.drv._fill = held_to_its_bytes, not_the_set_in_flight
    assert _per_seed(fx.run(monkeypatch, lag=1)) == fx.want
    assert len(filled) == fx.n - fx.drv.batch
    # two sets, taken in turn at every refill round
    assert len({id(took.op) for took, _then in flown}) == 2


def test_the_lag_is_a_rule_on_the_budget_in_segments():
    """1 where a schedule's budget holds ``_LAG_LIFE`` segments, else
    0: by ``SweepDriver``'s ``seg_steps`` every budget of 32 steps and
    more (all nine sweep cells; ``raft5-sweep``'s 144 is 4 segments a
    life: PR 48), never a life cut into fewer (``tools/soak.py``'s 40
    steps in segments of 28)."""
    from demi_tpu.parallel.sweep import SweepDriver

    assert continuous._LAG_LIFE == 4
    app, cfg, gen = _broadcast_fixture()
    for max_steps, seg_steps, lag in (
        (4 * 28, 28, 1), (4 * 28 - 1, 28, 0), (96, 28, 0), (40, 28, 0),
        (96, 32, 0), (96, 16, 1), (4608, 64, 1),
    ):
        drv = ContinuousSweepDriver(
            app, dataclasses.replace(cfg, max_steps=max_steps), gen,
            seg_steps=seg_steps,
        )
        assert drv._lag() == lag, (max_steps, seg_steps)
    for max_steps, seg_steps, lag in (
        (144, 36, 1), (31, 8, 0), (32, 8, 1), (1023, 64, 1), (1024, 64, 1),
        (3328, 64, 1),
    ):
        drv = SweepDriver(
            app, dataclasses.replace(cfg, max_steps=max_steps), gen
        )._continuous_driver(8)
        assert (drv.seg_steps, drv._lag()) == (seg_steps, lag), max_steps


@pytest.mark.parametrize(
    "lag, busy, queued",
    [(1, True, "all_but_the_first"), (1, False, "none"), (0, None, "none")],
)
def test_the_dispatches_count_how_often_the_device_had_work_queued(
    in_place, monkeypatch, lag, busy, queued
):
    """``sweep.segments`` per dispatch, the dropped one included, and
    ``sweep.segments_queued`` where the segment before had not landed:
    under a stub that never or always reads ready, and with the real
    probe under the strict order, whose every dispatch follows a pull."""
    drv = in_place.driver(in_place.gen, seed_pure=True)
    monkeypatch.setattr(continuous, "_LAG_LIFE", 0 if lag else _NEVER)
    if busy is not None:
        monkeypatch.setattr(continuous, "_ready", lambda _array: not busy)
    with _spans_live():
        lanes = sum(len(b[0]) for b in drv._run_batches(in_place.N))
        counts = obs.stage_counts()
    assert lanes == in_place.N
    segments = drv.last_total_lane_steps // (in_place.BATCH * drv.seg_steps)
    assert counts["sweep.segments"] == segments > 0
    assert counts["sweep.lane_steps"] == drv.last_total_lane_steps
    assert counts["sweep.live_lane_steps"] == drv.last_live_lane_steps
    assert counts["sweep.segments_queued"] == {
        "all_but_the_first": segments - 1, "none": 0,
    }[queued]


def _benchmark_reader(name, monkeypatch):
    """``benchmarks/layer_metrics/<name>.py`` as the harness loads it,
    held to its entry in ``BENCHMARK.json``: a count of the drivers'
    layer, listed for the sweep cells."""
    import importlib.util
    import json
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry == {
        "name": name, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "drivers (host)",
        "moves": "schedules_per_s",
        "workloads": bench["end_to_end"][0]["workloads"],
    }
    monkeypatch.syspath_prepend(os.path.join(root, "benchmarks"))
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"),
        os.path.join(root, "benchmarks", "layer_metrics", name + ".py"),
    )
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    # (the reader holds what it imported; no other test finds a ``lib``)
    for module in [m for m in sys.modules if m.split(".")[0] == "lib"]:
        del sys.modules[module]
    return reader


def test_the_benchmarks_reader_of_the_two_counts(monkeypatch):
    """``benchmarks/layer_metrics/sweep.queued_segment_share.py`` over a
    traced job's table, and None where a program keeps no such counts
    (the parent's); its entry lists the sweep cells."""
    from demi_tpu.parallel.sweep import SweepDriver

    reader = _benchmark_reader("sweep.queued_segment_share", monkeypatch)
    app, cfg, gen = _broadcast_fixture()
    driver = SweepDriver(app, cfg, gen)
    monkeypatch.setattr(continuous, "_LAG_LIFE", 0)
    monkeypatch.setattr(continuous, "_ready", lambda _array: False)
    with _spans_live():
        assert reader.read(None) is None
        driver.sweep(24, 8)
        segments = obs.stage_counts()["sweep.segments"]
        share = reader.read(None)
        del obs.TRACER.counts["sweep.segments"]
        assert reader.read(None) is None
    assert share == pytest.approx(100.0 * (segments - 1) / segments)


def test_the_benchmarks_reader_of_the_budget_count(monkeypatch):
    """``benchmarks/layer_metrics/sweep.budget_refill_share.py`` (PR 48)
    over a traced job's table: the lanes that ran to their budget over
    the lanes retired, 0 under the strict order, and None where a
    program keeps no such count (the parent's)."""
    from demi_tpu.parallel.sweep import SweepDriver

    reader = _benchmark_reader("sweep.budget_refill_share", monkeypatch)
    app, cfg, gen = _raft_fixture(64)
    driver = SweepDriver(app, cfg, gen)
    assert driver._continuous_driver(8)._lag() == 1
    with _spans_live():
        assert reader.read(None) is None
        result = driver.sweep(32, 8)
        counts = obs.stage_counts()
        share = reader.read(None)
        del obs.TRACER.counts["sweep.budget_retired"]
        assert reader.read(None) is None
    assert counts["sweep.retired"] == result.lanes == 32
    # a lane that violates stops on its own; the others run on
    assert 32 - result.violations >= counts["sweep.budget_retired"] > 0
    assert share == pytest.approx(100.0 * counts["sweep.budget_retired"] / 32)
    monkeypatch.setattr(continuous, "_LAG_LIFE", _NEVER)
    with _spans_live():
        assert driver.sweep(32, 8).lanes_digest == result.lanes_digest
        assert reader.read(None) == 0.0


# -- PR 48: a lane the host knows spent is refilled behind its last segment --

def _first_finished_in(fx, segments):
    """Per seed of ``fx``, the segment (1-based) in which its lane first
    reads finished on its own, over ``segments`` segments of the
    driver's own kernels run side by side with no refill and no
    finalize; 0 where it is still running after them."""
    import jax.numpy as jnp

    progs = stack_programs(
        [lower_program(fx.app, fx.cfg, fx.gen(s)) for s in range(fx.n)]
    )
    keys = np.stack([np.asarray(jax.random.PRNGKey(s)) for s in range(fx.n)])
    segment = fx.drv.segment
    state = fx.drv.init(jnp.asarray(keys))
    first = np.zeros(fx.n, int)
    for k in range(segments):
        steps = jnp.full(fx.n, k * fx.drv.seg_steps, jnp.int32)
        state = segment(state, progs, steps)
        done = np.asarray(state.status) >= ST_DONE
        first[done & (first == 0)] = k + 1
    return first


def test_a_lane_that_runs_to_its_budget_pays_no_frozen_segment(monkeypatch):
    """Every lane of the fixture ends at its budget of 4 segments: under
    the lag the job dispatches the strict order's segments and no more
    (none after the last wave: its lanes are known spent and no seed is
    left), every lane-step is live, and every lane is retired by the
    budget path."""
    fx = _Lagged("budget")
    dispatched = []
    segment = fx.drv.segment
    fx.drv.segment = lambda *a: dispatched.append(1) or segment(*a)
    life = fx.cfg.max_steps // fx.drv.seg_steps
    assert life == continuous._LAG_LIFE == 4
    waves = fx.n // fx.drv.batch
    want = waves * life * fx.drv.batch * fx.drv.seg_steps
    for lag in (0, 1):
        del dispatched[:]
        with _spans_live():
            assert _per_seed(fx.run(monkeypatch, lag)) == fx.want
            counts = obs.stage_counts()
        assert len(dispatched) == counts["sweep.segments"] == waves * life
        assert fx.drv.last_total_lane_steps == want
        assert fx.drv.last_live_lane_steps == want == fx.n * fx.cfg.max_steps
        assert fx.drv.last_occupancy == 1.0
        assert counts["sweep.retired"] == fx.n
        assert counts["sweep.budget_retired"] == (fx.n if lag else 0)


def test_a_lane_that_stops_a_segment_before_its_budget_is_retired_once(
    monkeypatch,
):
    """Such a lane is seen finished at the pull of the very round that
    counts it spent: it goes the late way (frozen through one segment),
    not both ways. The budget path takes the lanes that run on, the
    late path those that stop, and a frozen segment is counted out of
    the live lane-steps for the latter alone."""
    fx = _Lagged("mixed")
    life = fx.cfg.max_steps // fx.drv.seg_steps
    first = _first_finished_in(fx, life)
    stopped = first > 0     # (in its last segment: the budget path's)
    late = stopped & (first < life)
    assert (first == life - 1).any() and (first == 0).any()
    with _spans_live():
        batches = fx.run(monkeypatch, lag=1)
        counts = obs.stage_counts()
    assert sorted(s for batch in batches for s in batch[0]) == list(range(fx.n))
    assert _per_seed(batches) == fx.want
    assert counts["sweep.retired"] == fx.n
    assert counts["sweep.budget_retired"] == fx.n - int(late.sum())
    # live: a lane's segments up to the one it is seen finished in
    lived = np.where(late, first, life) * fx.drv.seg_steps
    assert fx.drv.last_live_lane_steps == int(lived.sum())
    lagged_steps = fx.drv.last_total_lane_steps
    assert _per_seed(fx.run(monkeypatch, lag=0)) == fx.want
    assert fx.drv.last_live_lane_steps == int(lived.sum())
    assert fx.drv.last_total_lane_steps <= lagged_steps


def test_lanes_that_stop_on_their_own_take_no_budget_path(monkeypatch):
    """The broadcast fixture's floods quiesce inside their budget: the
    lag costs each its frozen segment, as before PR 48, and the count
    reads 0."""
    fx = _Lagged("broadcast")
    assert (_first_finished_in(fx, 5) > 0).all()
    with _spans_live():
        assert _per_seed(fx.run(monkeypatch, lag=1)) == fx.want
        counts = obs.stage_counts()
    assert counts["sweep.retired"] == fx.n
    assert counts["sweep.budget_retired"] == 0
