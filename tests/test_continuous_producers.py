"""Producer processes of the continuous sweep (PR 42;
``device/continuous.py``: ``_Ring``, ``_Producers``): a call's programs
made by forked children through shared memory give the bytes, the
pairing and the verdicts of the in-thread path; a child that dies or
hangs costs time and nothing else; no child and no mapping outlives a
call however it ends; and the mechanism engages only where the call's
own measurements say it pays.

Every wait in the driver has its own deadline, and every test here runs
under an alarm, so a child that hangs fails a test and never the suite."""

import atexit
import hashlib
import os
import signal
import time

import numpy as np
import pytest

from demi_tpu import obs
from demi_tpu.apps.common import dsl_start_events
from demi_tpu.apps.raft import make_raft_app, raft_send_generator
from demi_tpu.device import DeviceConfig, continuous
from demi_tpu.fuzzing import Fuzzer, FuzzerWeights
from demi_tpu.parallel.distributed import build_workload
from demi_tpu.parallel.sweep import SweepDriver

pytestmark = pytest.mark.skipif(
    not hasattr(os, "fork"), reason="producer processes need os.fork"
)

BATCH = 16      # a chunk is 4 programs, the ring 32 rows in 8 slots
LANES = 12 * BATCH


@pytest.fixture(autouse=True)
def alarm():
    def late(_sig, _frame):
        raise AssertionError("a test of the producers outlasted 180 s")

    before = signal.signal(signal.SIGALRM, late)
    signal.alarm(180)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, before)


@pytest.fixture
def spans():
    obs.TRACER.clear()
    obs.enable()
    yield
    obs.disable()
    obs.TRACER.clear()


def _raft():
    app = make_raft_app(3, bug="multivote")
    cfg = DeviceConfig.for_app(
        app, pool_capacity=96, max_steps=160, max_external_ops=24,
        invariant_interval=1, timer_weight=0.1,
    )
    fuzzer = Fuzzer(
        num_events=10,
        weights=FuzzerWeights(
            send=0.3, kill=0.1, wait_quiescence=0.3, hard_kill=0.15,
            restart=0.15,
        ),
        message_gen=raft_send_generator(app),
        prefix=dsl_start_events(app), max_kills=2, wait_budget=(5, 30),
    )
    return app, cfg, fuzzer


def _chain():
    """Chain replication: its master is the fuzzer's send generator and
    hears of every kill and restart between two sends (``note_fault``),
    so a program is a function of its seed only because
    ``generate_fuzz_test`` resets it."""
    return build_workload({
        "app": "chain", "nodes": 4, "bug": "no_resend", "log_cap": 8,
        "seed": 0, "num_events": 24, "max_messages": 192, "pool": 96,
        "timer_weight": 1.0, "send_weight": 0.55, "wait_weight": 0.25,
        "wait_budget": [1, 40], "hard_kill_weight": 0.08,
        "restart_weight": 0.12, "partition_weight": 0.0, "kill_weight": 0.0,
        "max_kills": 3,
    })


class _Sweeper:
    """One ``SweepDriver`` and its cached continuous driver (one set of
    kernels), run under a private ``producers`` count, with every
    segment's resident programs and every yielded batch recorded."""

    def __init__(self, built):
        self.app, self.cfg, self.fuzzer = built
        self.calls = []     # the generator's calls IN THIS PROCESS
        self.driver = SweepDriver(self.app, self.cfg, self.gen)
        self.drv = self.driver._continuous_driver(BATCH)
        self.handed = []
        segment = self.drv.segment

        def recording(state, progs, steps_run):
            digest = hashlib.sha256()
            for x in progs:
                digest.update(np.ascontiguousarray(x).tobytes())
            self.handed.append(digest.hexdigest())
            return segment(state, progs, steps_run)

        self.drv.segment = recording

    def gen(self, seed):
        self.calls.append(seed)
        return self.fuzzer.generate_fuzz_test(seed=seed)

    def reset(self, producers):
        self.drv._producers = producers
        self.calls.clear()
        self.handed.clear()

    def sweep(self, producers, lanes=LANES):
        """A whole job through ``SweepDriver.sweep``: what a verb runs."""
        self.reset(producers)
        found = []
        self.driver.violation_hook = lambda seeds, codes: found.extend(
            zip(np.asarray(seeds).tolist(), np.asarray(codes).tolist())
        )
        result = self.driver.sweep(lanes, BATCH, mode="continuous")
        assert result.lanes == lanes and result.overflow_lanes == 0
        return {
            "digest": result.lanes_digest, "violating": found,
            "handed": list(self.handed),
        }

    def batches(self, producers, lanes=LANES):
        self.reset(producers)
        return self.drv._run_batches(lanes)


@pytest.fixture(scope="module")
def raft():
    return _Sweeper(_raft())


@pytest.fixture(scope="module")
def chain():
    return _Sweeper(_chain())


def _gone(pids):
    """Every pid was reaped: asking again is ECHILD."""
    for pid in pids:
        with pytest.raises(ChildProcessError):
            os.waitpid(pid, os.WNOHANG)
    return True


# -- (a) the same bytes, pairing and verdicts --------------------------------

@pytest.fixture(scope="module")
def in_thread(raft, chain):
    return {"raft": raft.sweep(0), "chain": chain.sweep(0)}


@pytest.mark.parametrize("producers", [0, 1, 3])
@pytest.mark.parametrize("which", ["raft", "chain"])
def test_a_job_is_the_in_thread_jobs(which, producers, in_thread, request, spans):
    """The resident programs handed to every segment (so which lane
    holds which program at every round), the violating seeds in the
    order they retired, and the job's digest over (seed, status, code,
    hash) rows."""
    sweeper = request.getfixturevalue(which)
    got = sweeper.sweep(producers)
    want = in_thread[which]
    assert got["handed"] == want["handed"] and len(got["handed"]) > 12
    assert got["violating"] == want["violating"] and got["violating"]
    assert got["digest"] == want["digest"]
    counts = obs.stage_counts()
    assert counts["sweep.programs"] == LANES
    assert counts["sweep.producers"] == producers
    if producers:
        # all but the probe: made by children, copied out of the ring
        assert counts["sweep.produced"] == LANES - BATCH
        assert sweeper.calls == list(range(BATCH))
        assert counts["sweep.prefetched"] == 0
        assert counts["sweep.producer_ns"] > 0
    else:
        assert counts["sweep.produced"] == 0
        assert sweeper.calls == list(range(LANES))
        assert "sweep.producer_ns" not in counts


def test_the_prime_fill_is_served_from_the_ring_too(raft, spans, monkeypatch):
    """A resident set larger than the probe: its other programs are the
    producers', and the prime fill waits for a chunk that is not there
    yet. Who is first at the ring after the fork is a race, so the test
    decides it: a child makes nothing before the host thread is inside
    a ``sweep.starve`` wait (whose loop alone asks ``_reaped``)."""
    import multiprocessing

    host = os.getpid()
    waited_for = multiprocessing.get_context("fork").Event()

    def gen(seed):
        if os.getpid() != host:
            assert waited_for.wait(60)
        return raft.gen(seed)

    reaped = continuous._Producers._reaped

    def waiting(self, i):
        waited_for.set()
        return reaped(self, i)

    monkeypatch.setattr(continuous._Producers, "_reaped", waiting)
    lanes, batch = 160, 2 * continuous._PROBE
    drv = continuous.ContinuousSweepDriver(
        raft.app, raft.cfg, gen, batch=batch, seg_steps=32,
        seed_pure=True, producers=0,
    )
    want = drv.sweep(lanes)
    drv._producers = 2
    obs.TRACER.clear()
    raft.calls.clear()
    assert drv.sweep(lanes) == want
    assert raft.calls == list(range(continuous._PROBE))
    assert obs.stage_counts()["sweep.produced"] == lanes - continuous._PROBE
    assert obs.stage_totals()["sweep.starve"]["count"] >= 1


# -- (b) a lost child costs time, never a result ------------------------------

@pytest.mark.parametrize("how", ["killed", "stopped"])
def test_a_child_lost_after_its_first_chunk(raft, in_thread, how, spans, monkeypatch):
    """SIGKILL: the wait's ``waitpid`` finds it gone. SIGSTOP: it
    outstays the deadline and is killed. Either way the host thread
    makes that child's positions itself and the job is the same job."""
    monkeypatch.setattr(continuous, "_STARVE_DEADLINE_S", 0.3)
    seen, pids = [], []
    for batch in raft.batches(3):
        seen.append(tuple(a.tolist() for a in batch))
        if not pids:
            pids = list(raft.drv._producing.pids)
            assert len(pids) == 3 and all(pids)
            os.kill(pids[1], signal.SIGKILL if how == "killed" else signal.SIGSTOP)
    assert raft.handed == in_thread["raft"]["handed"]
    assert sum(len(b[0]) for b in seen) == LANES
    counts = obs.stage_counts()
    # the probe and the lost child's share, made in this process
    assert len(raft.calls) > BATCH
    assert counts["sweep.produced"] + len(raft.calls) == counts["sweep.programs"] == LANES
    assert sorted(raft.calls) == sorted(set(raft.calls))      # none made twice here
    assert raft.drv._producing is None and _gone(pids)


def test_a_generator_that_raises_in_a_child_raises_in_the_call(raft, spans):
    """The child leaves without a word; the host thread finds it gone,
    makes the chunk itself and meets the same exception."""
    bad = 5 * BATCH + 1
    fuzz = raft.fuzzer.generate_fuzz_test

    def gen(seed):
        if seed == bad:
            raise ValueError("no program for this seed")
        return fuzz(seed=seed)

    raft.reset(3)
    raft.drv.program_gen, before = gen, raft.drv.program_gen
    pids = []
    try:
        with pytest.raises(ValueError, match="no program for this seed"):
            for _batch in raft.drv._run_batches(LANES):
                pids = pids or list(raft.drv._producing.pids)
    finally:
        raft.drv.program_gen = before
    assert raft.drv._producing is None and _gone(pids)


# -- (c) nothing outlives a call ----------------------------------------------

def test_a_consumer_that_stops_early_leaves_no_child_and_no_mapping(raft):
    rounds = raft.batches(3)
    next(rounds)
    producers = raft.drv._producing
    pids, block = list(producers.pids), producers.ring.block
    assert all(pids) and not block.closed
    rounds.close()
    assert raft.drv._producing is None
    assert _gone(pids) and block.closed
    # and the driver is as reusable as ever
    assert sum(len(b[0]) for b in raft.batches(3)) == LANES
    _seconds, seed = raft.drv.time_to_first_violation(max_lanes=64 * LANES)
    assert seed is not None and raft.drv._producing is None


def test_a_child_leaves_through_os_exit(raft, tmp_path):
    """No ``atexit`` handler of the parent runs in a child (on the chip
    host one shuts the TPU client down), and a child that has made its
    last chunk is gone before the call ends."""
    marker = tmp_path / "ran"
    mine = os.getpid()

    def handler():
        if os.getpid() != mine:
            marker.write_text("a child ran the parent's atexit handler")

    atexit.register(handler)
    try:
        raft.sweep(3)
    finally:
        atexit.unregister(handler)
    assert not marker.exists()


# -- (d) when it does not engage ----------------------------------------------

@pytest.mark.parametrize("why", ["not_seed_pure", "program_key_memo", "too_short"])
def test_it_does_not_engage(raft, why, spans, monkeypatch):
    drv = raft.drv
    lanes = LANES
    raft.reset(3)
    if why == "not_seed_pure":
        monkeypatch.setattr(drv, "seed_pure", False)
    elif why == "program_key_memo":
        monkeypatch.setattr(drv, "_program_key", lambda s: s % 24)
        monkeypatch.setattr(drv, "_lower_memo", {})
    else:
        # nobody's count but the call's own: 40 programs are no fork's worth
        raft.reset(None)
        lanes = 40
    assert sum(len(b[0]) for b in drv._run_batches(lanes)) == lanes
    counts = obs.stage_counts()
    assert counts["sweep.programs"] == lanes
    assert counts["sweep.produced"] == counts["sweep.producers"] == 0
    assert "sweep.starve" not in obs.stage_totals()
    assert len(raft.calls) == (24 if why == "program_key_memo" else lanes)


def test_the_rule_itself_forks_where_it_pays(raft, in_thread, spans, monkeypatch):
    """Nobody's count. A fork made worth a microsecond of making: the
    first call forks mid-way, once a refill has made programs on the
    spot, behind what the stock holds; the driver remembers, and its
    next call forks at its prime fill. The number of children is what
    the first fork's own time says."""
    if continuous._cores() < 2:
        pytest.skip("one core: the rule forks none")
    monkeypatch.setattr(continuous, "_WORTH_S", 1e-6)
    monkeypatch.setattr(raft.drv, "_exposed", False)
    # a device that is never the slower: nothing is made ahead of it
    monkeypatch.setattr(continuous, "_ready", lambda _array: True)
    assert raft.sweep(None) == in_thread["raft"]
    counts = obs.stage_counts()
    assert raft.drv._exposed is True
    assert 1 <= counts["sweep.producers"] <= continuous._cores() - 1
    # the prime fill and the first refills were the host thread's
    assert 0 < counts["sweep.produced"] < LANES - BATCH
    assert len(raft.calls) + counts["sweep.produced"] == LANES
    assert raft.calls == list(range(len(raft.calls)))
    obs.TRACER.clear()
    assert raft.sweep(None) == in_thread["raft"]
    counts = obs.stage_counts()
    assert counts["sweep.produced"] == LANES - BATCH
    assert obs.stage_totals()["sweep.fork"]["count"] == 1
    # what a fork costs this process: the shortest it has timed
    assert 0 < continuous._least_fork_ns < 5e9


def test_a_mid_call_fork_starts_behind_what_the_stock_holds(raft, monkeypatch):
    """The ring begins with the host thread's stock, made up to a whole
    chunk; the children make what follows; the fill takes the same
    bytes in seed order across the seam, and knows whose they were."""
    drv, seeds, start = raft.drv, list(range(200)), 40
    raft.reset(2)
    stock = continuous._Stock(raft.cfg, BATCH)
    monkeypatch.setattr(continuous, "_ready", lambda _array: False)
    drv._make_ahead(None, seeds, start, 6, stock)
    assert stock.count == 6
    producers = drv._start_producers(seeds, start, BATCH, 30e3, stock)
    try:
        # six made ahead, two more to end the chunk of four they were in
        assert stock.count == 0 and raft.calls == seeds[start : start + 8]
        assert (producers.hosts, producers.base) == (8, 2)
        want = continuous.empty_programs(raft.cfg, 48)
        for lane, seed in enumerate(seeds[start : start + 48]):
            continuous.lower_into(
                raft.app, raft.cfg, raft.fuzzer.generate_fuzz_test(seed=seed),
                want, lane,
            )
        got = continuous.empty_programs(raft.cfg, 48)
        taken = hosts = 0
        for k in (5, 11, 32):
            rows, held = producers.take(k)
            assert held.all()
            hosts += producers.took_hosts
            for mine, ring in zip(got, producers.progs):
                mine[taken : taken + k] = ring[rows]
            producers.free(k)
            taken += k
        assert hosts == 8
        for mine, theirs in zip(got, want):
            assert np.array_equal(mine, theirs)
    finally:
        pids, drv._producing = list(producers.pids), None
        producers.close()
    assert _gone(pids)


@pytest.mark.parametrize("which", ["raft", "chain"])
def test_making_that_hides_beside_the_device_forks_nothing(
    which, request, in_thread, spans, monkeypatch
):
    """While the stock made ahead serves every refill the device is the
    slower of the two, and a fork would only cost: however long the
    call, however dear a program."""
    sweeper = request.getfixturevalue(which)
    monkeypatch.setattr(continuous, "_ready", lambda _array: False)
    monkeypatch.setattr(continuous, "_WORTH_S", 1e-6)
    monkeypatch.setattr(sweeper.drv, "_exposed", False)
    assert sweeper.sweep(None) == in_thread[which]
    counts = obs.stage_counts()
    assert counts["sweep.producers"] == counts["sweep.produced"] == 0
    assert counts["sweep.prefetched"] == LANES - BATCH
    assert sweeper.drv._exposed is False


def test_the_engagement_rule(raft, monkeypatch):
    """Whether: programs left x the measured cost of one, in producers
    that each have ``_WORTH_S`` of making, up to the cores beside the
    host thread's; the benchmark's cells at the ledger's costs (PR 41).
    How many: what the first fork's own time says."""
    drv = raft.drv
    monkeypatch.setattr(drv, "_producers", None)
    monkeypatch.setattr(continuous, "_cores", lambda: 13)
    most = drv._producer_count
    assert most(512 - 32, 35e3) == 0           # the flood cell: 18 ms a job
    assert most(1024 - 32, 34e3) == 0          # the spark cell: 35 ms
    assert most(4096 - 32, 129e3) == 2         # VSR: 0.53 s
    assert most(65536 - 32, 30.8e3) == 8       # raft5-sweep: 2.0 s
    assert most(131072 - 32, 29.4e3) == 12     # its x4: 3.85 s, 13 cores
    monkeypatch.setattr(continuous, "_cores", lambda: 3)
    assert most(65536 - 32, 30.8e3) == 2       # a core is the host thread's
    monkeypatch.setattr(continuous, "_cores", lambda: 1)
    assert most(65536 - 32, 30.8e3) == 0
    monkeypatch.setattr(continuous, "_cores", lambda: 13)
    monkeypatch.setattr(drv, "seed_pure", False)
    assert most(65536 - 32, 30.8e3) == 0
    # a fork of 60 ms (the chip host's, PERF.md PR 42) against the prime
    # fill's making: 8,160 programs at 36 us, 32,736, and a VSR set's
    fewest = continuous._fewest_wait
    assert fewest(8160 * 36e3, 60e6, 8) == 2
    assert fewest(32736 * 36e3, 60e6, 12) == 4
    assert fewest(2016 * 129e3, 60e6, 2) == 2
    assert fewest(32736 * 36e3, 60e6, 3) == 3       # never more than are worth it
    assert fewest(0, 60e6, 8) == 1                  # the probe was the whole set
    assert fewest(8160 * 36e3, 1e6, 8) == 8         # where a fork is cheap, all


# -- (e) the ring is bounded ---------------------------------------------------

def test_the_ring_does_not_grow_with_the_call(raft):
    sizes = []
    for lanes in (4 * BATCH, 64 * BATCH):
        rounds = raft.batches(2, lanes)
        next(rounds)
        ring = raft.drv._producing.ring
        sizes.append((len(ring.block), ring.room, ring.chunk, ring.slots))
        rounds.close()
    assert sizes[0] == sizes[1]
    assert sizes[0][1:] == (2 * BATCH, BATCH // 4, 8)
    per_program = sum(
        int(np.prod(x.shape[1:])) * x.dtype.itemsize
        for x in continuous.empty_programs(raft.cfg, 1)
    )
    assert sizes[0][0] <= (per_program + 1) * sizes[0][1] + 4096


# -- the counts that cross ------------------------------------------------------

def test_the_fuzzers_own_counts_are_summed_back(raft, spans):
    programs = obs.counter("fuzz.programs_generated")
    events = obs.counter("fuzz.events_generated")
    p0, e0 = programs.value(), events.value()
    raft.sweep(0)
    p1, e1 = programs.value(), events.value()
    raft.sweep(3)
    assert programs.value() - p1 == p1 - p0 == LANES
    assert events.value() - e1 == e1 - e0 > LANES
