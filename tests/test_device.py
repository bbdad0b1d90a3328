"""Device-tier tests: vmapped explore kernel, batched replay kernel, and
device↔host parity via guided re-execution."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from demi_tpu.apps.broadcast import TAG_BCAST, make_broadcast_app
from demi_tpu.apps.common import dsl_start_events, make_host_invariant
from demi_tpu.config import SchedulerConfig
from demi_tpu.device import DeviceConfig, make_explore_kernel, make_replay_kernel
from demi_tpu.device.core import ST_DISPATCH, ST_DONE, ST_OVERFLOW, ST_VIOLATION
from demi_tpu.device.encoding import (
    device_trace_to_guide,
    lower_expected_trace,
    lower_program,
    stack_programs,
)
from demi_tpu.device.explore import make_single_lane_trace_kernel
from demi_tpu.external_events import (
    HardKill,
    Kill,
    MessageConstructor,
    Partition,
    Send,
    Start,
    UnPartition,
    WaitQuiescence,
)
from demi_tpu.schedulers import RandomScheduler, sts_oracle
from demi_tpu.schedulers.guided import GuidedScheduler


def _program(app, *extra):
    return dsl_start_events(app) + list(extra) + [WaitQuiescence()]


def _send(app, actor, bid):
    return Send(app.actor_name(actor), MessageConstructor(lambda: (TAG_BCAST, bid)))


def test_explore_unreliable_all_lanes_violate():
    app = make_broadcast_app(3, reliable=False)
    cfg = DeviceConfig.for_app(app, pool_capacity=64, max_steps=64, max_external_ops=8)
    kernel = make_explore_kernel(app, cfg)
    prog = lower_program(app, cfg, _program(app, _send(app, 0, 0)))
    batch = 32
    progs = stack_programs([prog] * batch)
    keys = jax.random.split(jax.random.PRNGKey(0), batch)
    res = kernel(progs, keys)
    assert np.all(np.asarray(res.status) == ST_VIOLATION)
    assert np.all(np.asarray(res.violation) == 1)
    assert np.all(np.asarray(res.deliveries) == 1)


def test_explore_reliable_no_violation():
    app = make_broadcast_app(3, reliable=True)
    cfg = DeviceConfig.for_app(app, pool_capacity=64, max_steps=64, max_external_ops=8)
    kernel = make_explore_kernel(app, cfg)
    prog = lower_program(app, cfg, _program(app, _send(app, 0, 0), _send(app, 1, 1)))
    batch = 32
    progs = stack_programs([prog] * batch)
    keys = jax.random.split(jax.random.PRNGKey(1), batch)
    res = kernel(progs, keys)
    assert np.all(np.asarray(res.status) == ST_DONE)
    assert np.all(np.asarray(res.violation) == 0)
    # 2 broadcasts fully relayed among 3 actors: 2 * (1 + 2 relays delivered
    # + duplicate relays) — at least 6 deliveries.
    assert np.all(np.asarray(res.deliveries) >= 6)


def test_explore_matches_host_on_deterministic_program():
    """Single possible interleaving → device and host must agree exactly."""
    app = make_broadcast_app(2, reliable=False)
    cfg = DeviceConfig.for_app(app, pool_capacity=32, max_steps=32, max_external_ops=8)
    program = _program(app, _send(app, 1, 3))
    host = RandomScheduler(
        SchedulerConfig(invariant_check=make_host_invariant(app)), seed=5
    ).execute(program)
    kernel = make_explore_kernel(app, cfg)
    progs = stack_programs([lower_program(app, cfg, program)])
    res = kernel(progs, jax.random.split(jax.random.PRNGKey(2), 1))
    host_code = host.violation.code if host.violation else 0
    assert int(res.violation[0]) == host_code == 1
    assert int(res.deliveries[0]) == host.deliveries == 1


def test_traced_lane_lifts_to_host_and_agrees():
    """Explore with kills; re-run a violating lane traced; guided host
    re-execution must reach the same violation."""
    app = make_broadcast_app(4, reliable=True)
    cfg = DeviceConfig.for_app(app, pool_capacity=128, max_steps=128, max_external_ops=16)
    kernel = make_explore_kernel(app, cfg)
    # Kill n1 after a quiescent period in which it may have partially relayed.
    program = dsl_start_events(app) + [
        _send(app, 1, 0),
        WaitQuiescence(),
        _send(app, 2, 1),
        Kill(app.actor_name(2)),
        WaitQuiescence(),
    ]
    batch = 64
    progs = stack_programs([lower_program(app, cfg, program)] * batch)
    keys = jax.random.split(jax.random.PRNGKey(7), batch)
    res = kernel(progs, keys)
    statuses = np.asarray(res.status)
    assert set(statuses.tolist()) <= {ST_DONE, ST_VIOLATION}

    # Every lane (violating or not) must lift cleanly and agree with host.
    traced = make_single_lane_trace_kernel(app, cfg)
    check = [int(i) for i in np.nonzero(statuses == ST_VIOLATION)[0][:2]]
    check += [int(i) for i in np.nonzero(statuses == ST_DONE)[0][:2]]
    assert check, "expected at least one lane to check"
    for lane in check:
        single = traced(
            jax.tree_util.tree_map(lambda x: x[lane], progs), keys[lane]
        )
        assert int(single.violation) == int(res.violation[lane])
        guide = device_trace_to_guide(
            app, np.asarray(single.trace), int(single.trace_len)
        )
        gs = GuidedScheduler(
            SchedulerConfig(invariant_check=make_host_invariant(app)), app
        )
        host_result = gs.execute_guide(guide)
        host_code = host_result.violation.code if host_result.violation else 0
        assert host_code == int(res.violation[lane])


def _hand_built_candidates():
    """One violating 3-node broadcast execution and four DDMin-style
    candidates whose verdicts are known by hand."""
    app = make_broadcast_app(3, reliable=False)
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    starts = dsl_start_events(app)
    s0, s1 = _send(app, 0, 0), _send(app, 1, 1)
    program = starts + [s0, s1, WaitQuiescence()]
    result = RandomScheduler(config, seed=3).execute(program)
    assert result.violation is not None
    cfg = DeviceConfig.for_app(app, pool_capacity=64, max_steps=64, max_external_ops=8)
    candidates = [
        program,  # full
        starts + [s0, WaitQuiescence()],  # drop second send
        starts[:2] + [s0, WaitQuiescence()],  # drop third actor + second send
        starts[:1] + [s0, WaitQuiescence()],  # single actor: no disagreement
    ]
    return app, config, cfg, result, candidates, [True, True, True, False]


def _corpus_candidates(name, peek):
    """The first violating execution of tests/test_differential.py's
    corpus for ``name``, lifted to the host, and every candidate that
    removes one of its externals (a DDMin level's shape), replayed with
    ``replay_peek=peek`` on the device and the same peek on the host."""
    import dataclasses

    from helpers import lift_lane_to_host
    from test_differential import CASES

    app, cfg, fz = CASES[name][0]()
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    traced = make_single_lane_trace_kernel(app, cfg)
    for seed in range(16):
        prog = lower_program(app, cfg, fz.generate_fuzz_test(seed=seed))
        key = jax.random.PRNGKey(seed)
        if int(traced(prog, key).violation) != 0:
            break
    else:
        raise AssertionError(f"{name}: no violating lane in 16 seeds")
    progs1 = jax.tree_util.tree_map(lambda x: np.asarray(x)[None], prog)
    _, host = lift_lane_to_host(app, cfg, progs1, key[None], 0, config)
    assert host.violation is not None
    # The lifted trace's own externals: the program's objects never ran
    # in this trace (runner.py).
    externals = list(host.trace.original_externals)
    candidates = [externals] + [
        externals[:i] + externals[i + 1:] for i in range(len(externals))
    ]
    return (
        app, config, dataclasses.replace(cfg, replay_peek=peek), host,
        candidates, None,
    )


REPLAY_CASES = {
    "hand-built": _hand_built_candidates,
    **{
        f"{name}-peek{peek}": (
            lambda name=name, peek=peek: _corpus_candidates(name, peek)
        )
        for name, peeks in (
            ("raft-faults", (0, 2)), ("broadcast-faults", (0, 2)),
            ("twopc-faults", (0,)),
        )
        for peek in peeks
    },
}


@pytest.mark.parametrize("case", list(REPLAY_CASES))
def test_replay_kernel_matches_host_sts_oracle(case):
    """Lower DDMin-style candidates and compare device replay verdicts with
    the host STS oracle."""
    app, config, cfg, result, candidates, known = REPLAY_CASES[case]()
    kernel = make_replay_kernel(app, cfg)
    oracle = sts_oracle(
        config, result.trace,
        allow_peek=cfg.replay_peek > 0, max_peek_messages=cfg.replay_peek,
    )
    max_records = cfg.max_steps + cfg.max_external_ops
    records = np.stack(
        [
            lower_expected_trace(
                app,
                cfg,
                result.trace.filter_failure_detector_messages()
                .filter_checkpoint_messages()
                .subsequence_intersection(c),
                c,
                max_records=max_records,
            )
            for c in candidates
        ]
    )
    keys = jax.random.split(jax.random.PRNGKey(0), len(candidates))
    res = kernel(records, keys)
    code = result.violation.code
    device_verdicts = [int(v) == code for v in res.violation]
    host_verdicts = [
        oracle.test(c, result.violation) is not None for c in candidates
    ]
    assert device_verdicts == host_verdicts
    assert device_verdicts[0], "the full execution must reproduce"
    if known is not None:
        assert device_verdicts == known


def test_pool_overflow_flags_lane():
    app = make_broadcast_app(8, reliable=True)
    cfg = DeviceConfig.for_app(app, pool_capacity=8, max_steps=64, max_external_ops=16)
    kernel = make_explore_kernel(app, cfg)
    program = _program(app, _send(app, 0, 0))  # relays overflow an 8-slot pool
    progs = stack_programs([lower_program(app, cfg, program)])
    res = kernel(progs, jax.random.split(jax.random.PRNGKey(0), 1))
    assert int(res.status[0]) == ST_OVERFLOW


def test_early_exit_matches_scan_results():
    """early_exit (while_loop) produces bit-identical lane results to the
    fixed-length scan — it only changes how long the loop runs."""
    import dataclasses

    import numpy as np
    import jax

    from demi_tpu.apps.broadcast import make_broadcast_app
    from demi_tpu.apps.common import dsl_start_events
    from demi_tpu.device import DeviceConfig, make_explore_kernel
    from demi_tpu.device.encoding import lower_program, stack_programs
    from demi_tpu.external_events import Kill, MessageConstructor, Send, WaitQuiescence

    app = make_broadcast_app(4, reliable=False)
    cfg = DeviceConfig.for_app(
        app, pool_capacity=64, max_steps=96, max_external_ops=16,
        invariant_interval=1, record_trace=True,
    )
    program = dsl_start_events(app) + [
        Send(app.actor_name(0), MessageConstructor(lambda: (1, 0))),
        Kill(app.actor_name(1)),
        WaitQuiescence(),
    ]
    B = 64
    progs = stack_programs([lower_program(app, cfg, program)] * B)
    keys = jax.random.split(jax.random.PRNGKey(3), B)
    scan_res = make_explore_kernel(app, cfg)(progs, keys)
    wl_cfg = dataclasses.replace(cfg, early_exit=True)
    wl_res = make_explore_kernel(app, wl_cfg)(progs, keys)
    for field in ("status", "violation", "deliveries", "trace", "trace_len"):
        assert np.array_equal(
            np.asarray(getattr(scan_res, field)),
            np.asarray(getattr(wl_res, field)),
        ), field


def test_replay_early_exit_matches_scan_results():
    """The replay kernel's early-exit path (the minimization default via
    default_device_config) is verdict-identical to the scan path across a
    batch of variable-length candidates."""
    import dataclasses

    import numpy as np
    import jax

    from demi_tpu.apps.common import dsl_start_events, make_host_invariant
    from demi_tpu.apps.raft import make_raft_app
    from demi_tpu.config import SchedulerConfig
    from demi_tpu.device import DeviceConfig
    from demi_tpu.device.encoding import lower_expected_trace
    from demi_tpu.device.replay import make_replay_kernel
    from demi_tpu.external_events import WaitQuiescence
    from demi_tpu.minimization.internal import (
        remove_delivery,
        removable_delivery_indices,
    )
    from demi_tpu.schedulers import RandomScheduler

    app = make_raft_app(3, bug="multivote")
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    program = dsl_start_events(app) + [WaitQuiescence()]
    found = None
    for seed in range(30):
        r = RandomScheduler(config, seed=seed, max_messages=120,
                            invariant_check_interval=1).execute(program)
        if r.violation is not None:
            found = r
            break
    assert found is not None

    cfg = DeviceConfig.for_app(
        app, pool_capacity=192, max_steps=200, max_external_ops=16,
        invariant_interval=1,
    )
    # Variable-length candidates: the full trace + several single-removals.
    candidates = [found.trace]
    for idx in removable_delivery_indices(found.trace)[:6]:
        candidates.append(remove_delivery(found.trace, idx))
    records = np.stack([
        lower_expected_trace(app, cfg, c, program, 216) for c in candidates
    ])
    keys = jax.random.split(jax.random.PRNGKey(0), len(candidates))

    scan_res = make_replay_kernel(app, cfg)(records, keys)
    wl_res = make_replay_kernel(
        app, dataclasses.replace(cfg, early_exit=True)
    )(records, keys)
    for field in ("status", "violation", "deliveries", "ignored_absent"):
        assert np.array_equal(
            np.asarray(getattr(scan_res, field)),
            np.asarray(getattr(wl_res, field)),
        ), field


def test_index_mode_parity_explore_and_replay():
    """'onehot' (TPU form: compare+where/reduce, no dynamic-index ops) and
    'scatter' (CPU form: native gathers/scatters) kernels are bit-identical
    — they are alternative lowerings of the same semantics (device/ops.py).
    Covers explore (traced, with kills + partitions in the program) and
    replay (wildcards included via a traced lane's own records)."""
    import dataclasses

    from demi_tpu.apps.raft import T_CLIENT, make_raft_app
    from demi_tpu.device.encoding import lower_program, stack_programs

    app = make_raft_app(3, bug="multivote")
    program = dsl_start_events(app) + [
        Send(app.actor_name(0), MessageConstructor(lambda: (T_CLIENT, 0, 7, 0, 0, 0, 0))),
        Partition(app.actor_name(0), app.actor_name(1)),
        UnPartition(app.actor_name(0), app.actor_name(1)),
        Kill(app.actor_name(2)),
        WaitQuiescence(budget=40),
    ]
    B = 32
    res = {}
    for mode in ("scatter", "onehot"):
        cfg = DeviceConfig.for_app(
            app, pool_capacity=64, max_steps=96, max_external_ops=16,
            invariant_interval=1, timer_weight=0.2, record_trace=True,
            index_mode=mode,
        )
        kernel = make_explore_kernel(app, cfg)
        progs = stack_programs([lower_program(app, cfg, program)] * B)
        keys = jax.random.split(jax.random.PRNGKey(11), B)
        res[mode] = (cfg, kernel(progs, keys))
    cfg_s, a = res["scatter"]
    _, b = res["onehot"]
    for field in ("status", "violation", "deliveries", "trace", "trace_len"):
        assert np.array_equal(
            np.asarray(getattr(a, field)), np.asarray(getattr(b, field))
        ), f"explore {field}"

    # Replay each traced lane's own records in both modes.
    recs = np.asarray(a.trace)
    keys = jax.random.split(jax.random.PRNGKey(12), B)
    out = {}
    for mode in ("scatter", "onehot"):
        cfg = dataclasses.replace(cfg_s, record_trace=False, index_mode=mode)
        out[mode] = make_replay_kernel(app, cfg)(recs, keys)
    for field in ("status", "violation", "deliveries", "ignored_absent"):
        assert np.array_equal(
            np.asarray(getattr(out["scatter"], field)),
            np.asarray(getattr(out["onehot"], field)),
        ), f"replay {field}"


def test_rng_split_bit_identical():
    """ops.rng_split must match jax.random.split exactly: every lane's
    schedule stream is drawn through it."""
    from demi_tpu.device.ops import rng_split

    key = jax.random.PRNGKey(1234)
    for n in (2, 3, 5):
        assert np.array_equal(
            np.asarray(jax.random.split(key, n)), np.asarray(rng_split(key, n))
        )


def test_prefix_sum_matches_cumsum():
    import jax.numpy as jnp

    from demi_tpu.device.ops import prefix_sum

    rng = np.random.default_rng(0)
    for n in (1, 2, 7, 96, 100):
        x = jnp.asarray(rng.integers(0, 5, n), jnp.int32)
        assert np.array_equal(
            np.asarray(prefix_sum(x, True)), np.cumsum(np.asarray(x))
        )


def test_int16_msg_storage_parity():
    """msg_dtype='int16' (halved pool-payload storage, the HBM-bandwidth
    lever for the step-loop carry) is bit-identical to int32 storage on
    both index modes, for explore and batched replay."""
    from demi_tpu.apps.raft import T_CLIENT, make_raft_app

    app = make_raft_app(3, bug="gap_append")

    def cmd(node, v):
        return Send(
            app.actor_name(node),
            MessageConstructor(lambda vv=v: (T_CLIENT, 0, vv, 0, 0, 0, 0)),
        )

    program = dsl_start_events(app) + [
        WaitQuiescence(budget=40),
        cmd(0, 10), cmd(1, 11),
        WaitQuiescence(budget=100),
    ]
    B = 32
    results = {}
    for index_mode in ("scatter", "onehot"):
        for dt in ("int32", "int16"):
            cfg = DeviceConfig.for_app(
                app, pool_capacity=96, max_steps=180, max_external_ops=16,
                invariant_interval=1, timer_weight=0.05,
                index_mode=index_mode, msg_dtype=dt,
            )
            progs = stack_programs([lower_program(app, cfg, program)] * B)
            keys = jax.random.split(jax.random.PRNGKey(0), B)
            results[(index_mode, dt)] = make_explore_kernel(app, cfg)(
                progs, keys
            )
    base = results[("scatter", "int32")]
    for key, res in results.items():
        for f in ("status", "violation", "deliveries"):
            assert (
                np.asarray(getattr(base, f)) == np.asarray(getattr(res, f))
            ).all(), (key, f)


def test_int16_out_of_range_payload_rejected():
    """Narrow storage silently wraps on device, so the host lowering
    boundary must reject out-of-range payloads loudly."""
    import pytest

    from demi_tpu.apps.broadcast import make_broadcast_app

    app = make_broadcast_app(3, reliable=False)
    cfg = DeviceConfig.for_app(
        app, pool_capacity=32, max_steps=32, max_external_ops=8,
        msg_dtype="int16",
    )
    program = dsl_start_events(app) + [
        Send(app.actor_name(0), MessageConstructor(lambda: (1, 70000))),
        WaitQuiescence(),
    ]
    with pytest.raises(ValueError, match="int16 range"):
        lower_program(app, cfg, program)


def _faulted_raft_case():
    from demi_tpu.apps.raft import T_CLIENT, make_raft_app

    app = make_raft_app(3)
    shapes = dict(
        pool_capacity=96, max_steps=128, max_external_ops=24,
        timer_weight=0.3,
    )
    program = dsl_start_events(app) + [
        Send(app.actor_name(0),
             MessageConstructor(lambda: (T_CLIENT, 0, 7, 0, 0, 0, 0))),
        Partition(app.actor_name(0), app.actor_name(1)),
        WaitQuiescence(30),
        UnPartition(app.actor_name(0), app.actor_name(1)),
        Kill(app.actor_name(2)),
        WaitQuiescence(30),
    ]
    return app, shapes, program, 16


def _faulted_flood_case():
    # The flood cell's shape class: 64 actors are two packed words, the
    # pool is bcast64-flood's 4,608; a soft kill, a hard kill and a
    # restart land inside the flood.
    app = make_broadcast_app(64)
    shapes = dict(
        pool_capacity=4608, max_steps=300, max_external_ops=72,
        invariant_interval=app.invariant_interval,
    )
    program = dsl_start_events(app) + [
        _send(app, 3, 1),
        WaitQuiescence(40),
        Kill(app.actor_name(40)),
        HardKill(app.actor_name(7)),
        WaitQuiescence(60),
        Start(app.actor_name(7)),
        WaitQuiescence(),
    ]
    return app, shapes, program, 4


@pytest.mark.parametrize(
    "case", [_faulted_raft_case, _faulted_flood_case],
    ids=["raft3-partition-kill-timers", "bcast64-kills-restart"],
)
def test_deliverable_mask_onehot_matches_scatter(case):
    """The one-hot path reads deliverable_mask's liveness bits from
    packed words, the scatter path by ``vec[idx]``: whole lanes must run
    bit-identical in both, across partitions, kills, restarts and
    timers."""
    app, shapes, program, batch = case()
    keys = jax.random.split(jax.random.PRNGKey(11), batch)
    results = {}
    for index_mode in ("onehot", "scatter"):
        cfg = DeviceConfig.for_app(app, index_mode=index_mode, **shapes)
        progs = stack_programs([lower_program(app, cfg, program)] * batch)
        results[index_mode] = make_explore_kernel(app, cfg)(progs, keys)
    assert int(np.asarray(results["scatter"].deliveries).min()) > 0
    for field in ("status", "violation", "deliveries", "sched_hash"):
        np.testing.assert_array_equal(
            np.asarray(getattr(results["onehot"], field)),
            np.asarray(getattr(results["scatter"], field)),
            err_msg=field,
        )


@pytest.mark.parametrize("n", [1, 5, 17, 31, 32, 33, 64, 65])
def test_packed_gather_bool_matches_indexing(n):
    """ops.packed_gather_bool against ``vec[idx]`` on random tables and
    indices, word boundaries included; an index out of range reads False,
    as the one-hot form does."""
    from demi_tpu.device import ops

    rng = np.random.default_rng(n)
    for _ in range(8):
        vec = rng.random(n) < 0.5
        idx = rng.integers(0, n, size=97).astype(np.int32)
        idx[:4] = [n, n + 31, 4096, -1]   # out of range, both ends
        got = np.asarray(ops.packed_gather_bool(jnp.asarray(vec), jnp.asarray(idx)))
        want = np.where((idx >= 0) & (idx < n), vec[np.clip(idx, 0, n - 1)], False)
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(
            got, np.asarray(ops.gather_vec(jnp.asarray(vec), jnp.asarray(idx), True))
        )
    # all-True table: every in-range bit reads True, so a mis-selected
    # word or shift cannot hide behind a random zero
    ones = jnp.ones(n, bool)
    every = jnp.arange(n, dtype=jnp.int32)
    assert bool(jnp.all(ops.packed_gather_bool(ones, every)))
