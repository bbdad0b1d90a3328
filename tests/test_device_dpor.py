"""Batched device DPOR: parent-tracked records, racing analysis, frontier
exploration."""

import numpy as np
import pytest
import jax.numpy as jnp

from demi_tpu.apps.common import dsl_start_events
from demi_tpu.device import DeviceConfig
from demi_tpu.device.core import REC_DELIVERY
from demi_tpu.device.dpor_sweep import DeviceDPOR
from demi_tpu.dsl import DSLApp, vset
from demi_tpu.external_events import MessageConstructor, Send, WaitQuiescence


def make_reversal_app(k: int) -> DSLApp:
    """Violation iff the k messages (values 1..k) arrive exactly reversed —
    probability 1/k! per random schedule, so discovery requires systematic
    reordering, not luck."""

    def init_state(i):
        return np.zeros(k + 2, np.int32)

    def handler(actor_id, state, snd, msg):
        pos = state[0]
        expect = k - pos
        ok_so_far = state[1] == 0
        hit = (msg[1] == expect) & ok_so_far
        state = vset(state, 1, jnp.where(hit, 0, 1))
        state = vset(state, 0, pos + 1)
        done = (pos + 1 == k) & (state[1] == 0)
        state = vset(state, 2, jnp.where(done, 1, state[2]))
        return state, jnp.zeros((1, 4), jnp.int32)

    def invariant(states, alive):
        return jnp.where(jnp.any((states[:, 2] == 1) & alive), jnp.int32(1), 0)

    return DSLApp(
        name="v", num_actors=2, state_width=k + 2, msg_width=2, max_outbox=1,
        init_state=init_state, handler=handler, invariant=invariant,
    )


def _setup(k):
    app = make_reversal_app(k)
    cfg = DeviceConfig.for_app(
        app, pool_capacity=32, max_steps=32, max_external_ops=12,
        invariant_interval=1, record_trace=True, record_parents=True,
    )
    program = dsl_start_events(app) + [
        *[
            Send(app.actor_name(0), MessageConstructor(lambda v=v: (1, v)))
            for v in range(1, k + 1)
        ],
        WaitQuiescence(),
    ]
    return app, cfg, program


def test_device_dpor_finds_reversal_order():
    app, cfg, program = _setup(4)
    dpor = DeviceDPOR(app, cfg, program, batch_size=32)
    found = dpor.explore(target_code=1, max_rounds=30)
    assert found is not None, "device DPOR missed the 1/24 ordering"
    recs, n = found
    order = [int(r[4]) for r in recs[:n] if r[0] in (1, 2)]
    assert order == [4, 3, 2, 1]
    # Backtracking genuinely ran (the answer wasn't a lucky first lane).
    assert dpor.interleavings > 1


def test_device_dpor_exhausts_without_bug():
    """Correct app (no reachable violation): the frontier drains without a
    find, having explored multiple interleavings."""
    app, cfg, program = _setup(3)

    # target code 2 never occurs
    dpor = DeviceDPOR(app, cfg, program, batch_size=16)
    found = dpor.explore(target_code=2, max_rounds=50)
    assert found is None
    assert dpor.interleavings >= 2


def test_device_dpor_oracle_lifts_to_host():
    """DeviceDPOROracle finds the reversal ordering and returns a full host
    EventTrace whose violation matches."""
    from demi_tpu.apps.common import make_host_invariant
    from demi_tpu.config import SchedulerConfig
    from demi_tpu.device.dpor_sweep import DeviceDPOROracle
    from demi_tpu.minimization.test_oracle import IntViolation

    app, cfg, program = _setup(3)
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    oracle = DeviceDPOROracle(app, cfg, config, batch_size=16, max_rounds=20)
    trace = oracle.test(program, IntViolation(1))
    assert trace is not None
    assert oracle.last_interleavings >= 1
    # The lifted trace replays deterministically on the host.
    from demi_tpu.schedulers import STSScheduler

    sts = STSScheduler(config, trace)
    assert sts.test_with_trace(trace, program, IntViolation(1)) is not None


def test_racing_prescriptions_shape():
    """Unit: two concurrent same-receiver deliveries race; the prescription
    is the pre-branch prefix plus the flipped record."""
    recw = 7  # kind, a, b, msg0, msg1, parent, prev
    recs = np.zeros((4, recw), np.int32)
    # ext op created both messages (records 0,1 are ext sends: kind 13)
    recs[0] = [13, 0, 0, 1, 7, -1, -1]
    recs[1] = [13, 0, 0, 1, 8, -1, -1]
    # deliveries to actor 0, created by records 0 and 1; record 3's
    # program-order predecessor at actor 0 is record 2
    recs[2] = [REC_DELIVERY, 2, 0, 1, 7, 0, -1]
    recs[3] = [REC_DELIVERY, 2, 0, 1, 8, 1, 2]
    from demi_tpu.native import racing_prescriptions_batch

    rows, offsets, lanes, _digests = racing_prescriptions_batch(
        recs[None], np.asarray([4]), recw
    )
    assert lanes.tolist() == [0] and offsets.tolist() == [0, 1]
    # Flip: deliver record 3's message first (no prior deliveries).
    assert rows.tolist() == [recs[3].tolist()]


def test_device_dpor_steering_reproduces_in_first_batch():
    """Seeding the frontier with the recorded violating schedule makes the
    steered lane reproduce the violation in round 1 (device analog of
    DPORwHeuristics initial-trace steering)."""
    from demi_tpu.apps.common import make_host_invariant
    from demi_tpu.config import SchedulerConfig
    from demi_tpu.device.dpor_sweep import DeviceDPOROracle, steering_prescription
    from demi_tpu.minimization.test_oracle import IntViolation

    app, cfg, program = _setup(4)
    config = SchedulerConfig(invariant_check=make_host_invariant(app))

    # Record the violation the slow way.
    finder = DeviceDPOR(app, cfg, program, batch_size=32)
    found = finder.explore(target_code=1, max_rounds=30)
    assert found is not None
    # Lift to host to get an EventTrace to steer by.
    oracle = DeviceDPOROracle(app, cfg, config, batch_size=32, max_rounds=30)
    trace = oracle.test(program, IntViolation(1))
    assert trace is not None

    # Fresh, steered oracle: one round of one batch suffices, and the
    # steered prescription replays the full recorded schedule.
    steered = DeviceDPOROracle(
        app, cfg, config, batch_size=8, max_rounds=1, initial_trace=trace
    )
    presc = steering_prescription(app, cfg, trace, program)
    assert len(presc) == 4  # all four deliveries prescribed
    assert steered.test(program, IntViolation(1)) is not None
    assert steered.last_interleavings <= 8  # a single batch


def test_device_dpor_oracle_is_resumable():
    """Repeated probes of the same subsequence continue the persisted
    frontier instead of restarting (interleaving count accumulates, and
    the explored-set is shared)."""
    from demi_tpu.apps.common import make_host_invariant
    from demi_tpu.config import SchedulerConfig
    from demi_tpu.device.dpor_sweep import DeviceDPOROracle
    from demi_tpu.minimization.test_oracle import IntViolation

    app, cfg, program = _setup(3)
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    oracle = DeviceDPOROracle(app, cfg, config, batch_size=4, max_rounds=1)
    # Hunt for a code that never occurs: each probe runs one more round.
    assert oracle.test(program, IntViolation(2)) is None
    first = oracle.last_interleavings
    assert oracle.test(program, IntViolation(2)) is None
    assert oracle.last_interleavings > first  # resumed, not restarted
    inst = oracle._instance(program)
    assert len(oracle._instances) == 1
    assert inst.interleavings == oracle.last_interleavings


def test_incremental_ddmin_with_device_oracle():
    """IncrementalDDMin over the device-batched DPOR oracle minimizes the
    reversal case (noise external pruned)."""
    from demi_tpu.apps.common import make_host_invariant
    from demi_tpu.config import SchedulerConfig
    from demi_tpu.device.dpor_sweep import DeviceDPOROracle
    from demi_tpu.minimization.ddmin import make_dag
    from demi_tpu.minimization.incremental_ddmin import IncrementalDDMin
    from demi_tpu.minimization.test_oracle import IntViolation

    app, cfg, program = _setup(3)
    # Noise: an extra send to the OTHER actor that the violation never
    # needs.
    noise = Send(app.actor_name(1), MessageConstructor(lambda: (1, 9)))
    program = program[:-1] + [noise, WaitQuiescence()]
    config = SchedulerConfig(invariant_check=make_host_invariant(app))

    oracle = DeviceDPOROracle(app, cfg, config, batch_size=16, max_rounds=10)
    finder = DeviceDPOROracle(app, cfg, config, batch_size=16, max_rounds=30)
    trace = finder.test(program, IntViolation(1))
    assert trace is not None
    oracle.set_initial_trace(trace)

    inc = IncrementalDDMin(config, max_max_distance=4, oracle=oracle)
    mcs = inc.minimize(make_dag(program), IntViolation(1))
    kept = mcs.get_all_events()
    assert noise not in kept
    assert len(kept) < len(program)


def _raft3_multivote():
    from demi_tpu.apps.raft import make_raft_app

    app = make_raft_app(3, bug="multivote")
    cfg = DeviceConfig.for_app(
        app, pool_capacity=96, max_steps=96, max_external_ops=16,
        invariant_interval=1, timer_weight=0.2, record_trace=True,
        record_parents=True,
    )
    return app, cfg, dsl_start_events(app) + [WaitQuiescence()]


def _twopc4_presume_commit():
    from demi_tpu.apps.twopc import T_BEGIN, make_twopc_app

    app = make_twopc_app(4, bug="presume_commit")
    cfg = DeviceConfig.for_app(
        app, pool_capacity=64, max_steps=64, max_external_ops=8,
        invariant_interval=1, timer_weight=0.1, record_trace=True,
        record_parents=True,
    )
    return app, cfg, dsl_start_events(app) + [
        Send(app.actor_name(0), MessageConstructor(lambda: (T_BEGIN, 1, 0))),
        WaitQuiescence(),
    ]


@pytest.mark.parametrize(
    "setup,rounds",
    [
        (lambda: _setup(4), 3), (_raft3_multivote, 2),
        (_twopc4_presume_commit, 2),
    ],
    ids=["reversal", "raft3-multivote", "twopc4-presume-commit"],
)
def test_every_dpor_lane_lifts_to_the_host_code_for_code(setup, rounds):
    """The DPOR kernel against the host oracle: every lane of a round
    (prescribed prefix, then the explore step's own choices; padding
    lanes too) is a schedule the host delivers without divergence and
    judges with the same code."""
    from demi_tpu.apps.common import make_host_invariant
    from demi_tpu.config import SchedulerConfig
    from demi_tpu.device.encoding import device_trace_to_guide
    from demi_tpu.schedulers.guided import GuidedScheduler

    app, cfg, program = setup()
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    dpor = DeviceDPOR(app, cfg, program, batch_size=16)
    harvested = []
    harvest = dpor._harvest_round

    def keep(parts, batch_len):
        res = harvest(parts, batch_len)
        harvested.append(res)
        return res

    dpor._harvest_round = keep
    dpor.explore(max_rounds=rounds, stop_on_violation=False)
    assert len(harvested) == rounds
    codes = set()
    for res in harvested:
        traces, lens = np.asarray(res.trace), np.asarray(res.trace_len)
        violation = np.asarray(res.violation)
        for lane in range(len(lens)):
            guide = device_trace_to_guide(app, traces[lane], int(lens[lane]))
            host = GuidedScheduler(config, app).execute_guide(guide)
            host_code = host.violation.code if host.violation else 0
            assert host_code == int(violation[lane]), lane
            codes.add(host_code)
    assert 0 in codes and len(codes) > 1, "clean and violating lanes both"


def test_device_racing_scan_matches_host_dpor_racing_set():
    """Parity: the device racing-pair scan over HB-tracked records and the
    host DepTracker.racing_pairs over DporEvents flag the SAME pairs (as
    delivery-order indexes) for the same executed schedule — the device
    lane is steered to replay the host DPOR execution exactly."""
    import jax
    from demi_tpu.config import SchedulerConfig
    from demi_tpu.device.dpor_sweep import (
        make_dpor_kernel,
        steering_prescription,
    )
    from demi_tpu.device.encoding import lower_program
    from demi_tpu.device.explore import ExtProgram
    from demi_tpu.native import racing_pair_scan
    from demi_tpu.schedulers.dep_tracker import DepTracker
    from demi_tpu.schedulers.dpor import _DporExecution

    app, cfg, program = _setup(4)
    config = SchedulerConfig()
    tracker = DepTracker(config.fingerprinter)
    tracker.begin_execution()
    execution = _DporExecution(config, tracker, (), max_messages=64)
    result = execution.execute(list(program))
    host_trace = execution.delivered_ids
    assert len(host_trace) == 4
    host_pairs = set(tracker.racing_pairs(host_trace))

    presc = steering_prescription(app, cfg, result.trace, program)
    kernel = make_dpor_kernel(app, cfg)
    prog = lower_program(app, cfg, program)
    progs = ExtProgram(*(np.asarray(x)[None] for x in prog))
    prescs = np.zeros((1, cfg.max_steps, cfg.rec_width), np.int32)
    for t, rec in enumerate(presc):
        prescs[0, t] = rec
    keys = jax.random.PRNGKey(0)[None]
    res = kernel(progs, prescs, keys)
    recs = np.asarray(res.trace)[0][: int(np.asarray(res.trace_len)[0])]
    dev_positions = np.nonzero(np.isin(recs[:, 0], (1, 2)))[0]
    assert len(dev_positions) == len(host_trace), "steered replay diverged"
    rank = {int(p): k for k, p in enumerate(dev_positions)}
    dev_pairs = {
        (rank[int(i)], rank[int(j)])
        for i, j in racing_pair_scan(recs)
    }
    assert dev_pairs == host_pairs


def test_program_order_edges_shrink_racing_set_raft():
    """The program-order (prev) column prunes non-immediate races that
    creation-only HB flags: on a traced raft dyn_quorum schedule the new
    scan emits a strict subset of the creation-only pairs (fewer
    prescriptions per round), while recall is covered by the reversal /
    case-study tests still finding their violations."""
    import jax
    from demi_tpu.apps.common import dsl_start_events as starts
    from demi_tpu.apps.raft import make_raft_app, raft_send_generator
    from demi_tpu.device.explore import make_single_lane_trace_kernel
    from demi_tpu.device.encoding import lower_program
    from demi_tpu.fuzzing import Fuzzer, FuzzerWeights
    from demi_tpu.native import racing_pair_scan

    app = make_raft_app(3, bug="dyn_quorum")
    cfg = DeviceConfig.for_app(
        app, pool_capacity=96, max_steps=120, max_external_ops=24,
        invariant_interval=1, timer_weight=0.3, record_parents=True,
    )
    fz = Fuzzer(
        num_events=10,
        weights=FuzzerWeights(send=0.5, wait_quiescence=0.3, kill=0.1,
                              restart=0.1),
        message_gen=raft_send_generator(app),
        prefix=starts(app), max_kills=1,
    )
    kernel = make_single_lane_trace_kernel(app, cfg)
    total_new = total_old = 0
    for seed in range(6):
        prog = lower_program(app, cfg, fz.generate_fuzz_test(seed=seed))
        res = kernel(prog, jax.random.PRNGKey(seed))
        recs = np.asarray(res.trace)[: int(res.trace_len)]
        if len(recs) == 0:
            continue
        new_pairs = {tuple(p) for p in racing_pair_scan(recs)}
        legacy = recs.copy()
        legacy[:, -1] = -1  # drop program-order edges => creation-only scan
        old_pairs = {tuple(p) for p in racing_pair_scan(legacy)}
        assert new_pairs <= old_pairs
        total_new += len(new_pairs)
        total_old += len(old_pairs)
    assert total_old > 0
    assert total_new < total_old, (total_new, total_old)
