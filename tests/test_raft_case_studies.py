"""Raft known-bug case studies (reference-style raft-NN analogs): the two
round-2 log-divergence bugs, each detected and minimized, plus a clean
sweep on correct raft.

  gap_append    — Log Matching precheck dropped (raft-56-class): needs a
                  reordered AppendEntries; rare under random schedules, so
                  the device sweep is the discovery vehicle.
  commit_beyond — commit adopted before validating the append: a heartbeat
                  reordered ahead of its entries commits a hole.
"""

import numpy as np
import pytest

import jax

from demi_tpu.apps.common import dsl_start_events, make_host_invariant
from demi_tpu.apps.raft import T_CLIENT, make_raft_app
from demi_tpu.config import SchedulerConfig
from demi_tpu.device import DeviceConfig, make_explore_kernel
from demi_tpu.device.core import ST_OVERFLOW, ST_VIOLATION
from demi_tpu.device.encoding import lower_program, stack_programs
from demi_tpu.external_events import MessageConstructor, Send, WaitQuiescence
from demi_tpu.runner import sts_sched_ddmin
from demi_tpu.schedulers import RandomScheduler


def _program(app):
    def cmd(node, v):
        return Send(
            app.actor_name(node),
            MessageConstructor(lambda vv=v: (T_CLIENT, 0, vv, 0, 0, 0, 0)),
        )

    return dsl_start_events(app) + [
        WaitQuiescence(budget=40),
        cmd(0, 10), cmd(1, 11), cmd(2, 12),
        WaitQuiescence(budget=120),
    ]


def _device_cfg(app):
    return DeviceConfig.for_app(
        app, pool_capacity=96, max_steps=224, max_external_ops=16,
        invariant_interval=1, timer_weight=0.05,
    )


def test_commit_beyond_detected_and_minimized():
    app = make_raft_app(3, bug="commit_beyond")
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    program = _program(app)
    found = None
    for seed in range(40):
        r = RandomScheduler(
            config, seed=seed, max_messages=400,
            invariant_check_interval=1, timer_weight=0.05,
        ).execute(program)
        if r.violation is not None:
            found = r
            break
    assert found is not None, "commit_beyond never detected"
    assert found.violation.code == 2  # committed-prefix disagreement
    mcs, verified = sts_sched_ddmin(
        config, found.trace, program, found.violation
    )
    kept = mcs.get_all_events()
    assert verified is not None
    assert len(kept) < len(program)


def test_gap_append_device_sweep_and_host_lift():
    """Discovery via the device sweep (the bug needs reordering rare under
    host-seed scans), then host reproduction of a violating lane."""
    from demi_tpu.device.explore import make_single_lane_trace_kernel
    from demi_tpu.device.encoding import device_trace_to_guide
    from demi_tpu.schedulers.guided import GuidedScheduler

    app = make_raft_app(3, bug="gap_append")
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    cfg = _device_cfg(app)
    program = _program(app)
    B = 512
    kernel = make_explore_kernel(app, cfg)
    progs = stack_programs([lower_program(app, cfg, program)] * B)
    keys = jax.random.split(jax.random.PRNGKey(0), B)
    res = kernel(progs, keys)
    violations = np.asarray(res.violation)
    statuses = np.asarray(res.status)
    assert int((statuses == ST_OVERFLOW).sum()) == 0
    lanes = np.flatnonzero(statuses == ST_VIOLATION)
    assert len(lanes) > 0, "device sweep missed gap_append"
    assert set(violations[lanes]) == {2}

    # Traced re-run of the first violating lane, lifted to the host.
    from helpers import lift_lane_to_host

    single, host = lift_lane_to_host(app, cfg, progs, keys, int(lanes[0]), config)
    assert int(single.violation) == 2
    assert host.violation is not None and host.violation.code == 2

    # Minimize a lifted lane. externals=None selects the lifted trace's
    # own externals — the program's objects never executed in this trace,
    # so they would project to "absent" under STS (the round-4 verify
    # slice caught exactly that footgun).
    #
    # Real reduction required (gap_append needs at most 2 of the 3
    # client commands): <= would also pass for a no-op DDMin. WHICH
    # lanes reduce is schedule-dependent — a particular lane's MCS can
    # genuinely be its full external set under ignore-absent STS — so
    # the strict-reduction evidence may come from any of the first few
    # violating lanes (each independently verified to reproduce).
    reduced = False
    for lane in lanes[:4]:
        _single, h = lift_lane_to_host(
            app, cfg, progs, keys, int(lane), config
        )
        assert h.violation is not None and h.violation.code == 2
        mcs, verified = sts_sched_ddmin(config, h.trace, None, h.violation)
        assert verified is not None
        if len(mcs.get_all_events()) < len(h.trace.original_externals):
            reduced = True
            break
    assert reduced, "no violating lane's MCS reduced below its externals"


def test_correct_raft_clean_under_same_sweep():
    app = make_raft_app(3)
    cfg = _device_cfg(app)
    program = _program(app)
    B = 256
    kernel = make_explore_kernel(app, cfg)
    progs = stack_programs([lower_program(app, cfg, program)] * B)
    keys = jax.random.split(jax.random.PRNGKey(0), B)
    res = kernel(progs, keys)
    assert int((np.asarray(res.violation) != 0).sum()) == 0


def test_dyn_quorum_initialization_bug():
    """raft-58-initialization-class case study: quorum computed from the
    membership a node has *discovered* instead of the configured cluster
    size. Two nodes whose election timers fire before any peer exchange
    each see a 1-node cluster and both become term-1 leaders. Detected by
    the host fuzzer, minimized to its 2-Start core, and the same sweep on
    correct raft stays clean (the discovery tracking itself is benign)."""
    app = make_raft_app(3, bug="dyn_quorum")
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    program = _program(app)
    found = None
    for seed in range(20):
        r = RandomScheduler(
            config, seed=seed, max_messages=200,
            invariant_check_interval=1, timer_weight=0.3,
        ).execute(program)
        if r.violation is not None:
            found = r
            break
    assert found is not None, "dyn_quorum never produced two leaders"
    assert found.violation.code == 1  # Election Safety

    mcs, verified = sts_sched_ddmin(
        config, found.trace, program, found.violation
    )
    assert verified is not None
    kept = mcs.get_all_events()
    # The bug needs nothing beyond two nodes starting and their timers
    # firing: every client Send must be pruned.
    from demi_tpu.external_events import Send as _Send

    assert not any(isinstance(e, _Send) for e in kept)
    assert len(kept) < len(program)

    # Device sweep agrees (host/device parity for the HEARD tracking).
    cfg = _device_cfg(app)
    B = 128
    kernel = make_explore_kernel(app, cfg)
    progs = stack_programs([lower_program(app, cfg, program)] * B)
    keys = jax.random.split(jax.random.PRNGKey(0), B)
    res = kernel(progs, keys)
    statuses = np.asarray(res.status)
    assert int((statuses == ST_OVERFLOW).sum()) == 0
    lanes = np.flatnonzero(statuses == ST_VIOLATION)
    assert len(lanes) > 0
    assert set(np.asarray(res.violation)[lanes]) == {1}


def test_lost_vote_durability_on_crash_recovery():
    """raft-66-class persistence case study on UNMODIFIED Raft: the fixture
    keeps voted_for/term in memory only, so HardKill+restart wipes them —
    a restarted voter grants a second vote in a term it already voted in,
    electing two same-term leaders. Needs crash/recovery externals fired
    mid-flood (bounded WaitQuiescence budgets leave messages pending at
    segment boundaries) — unreachable with full-drain waits, which is why
    the fuzzer's wait_budget knob exists. Reference analog: the raft-NN
    known-bug branches exercised via Kill/Start atoms
    (tools/rerun_experiments.sh:7, ExternalEvents.scala:62-91)."""
    from demi_tpu.parallel.distributed import build_workload

    # The builder the CLI verbs share (`demi_tpu sweep --hard-kill-weight
    # 0.25 --restart-weight 0.3 ...`); no seeded bug flag: volatility IS
    # the bug.
    app, cfg, fz = build_workload({
        "app": "raft", "nodes": 3, "bug": None, "num_events": 10,
        "max_messages": 224, "pool": 96, "timer_weight": 0.05,
        "kill_weight": 0.01, "send_weight": 0.1, "wait_weight": 0.35,
        "hard_kill_weight": 0.25, "restart_weight": 0.3, "max_kills": 2,
        "wait_budget": [1, 25],
    })
    base, B = 768, 256  # empirically violating region of the seed space
    programs = [fz.generate_fuzz_test(seed=base + s) for s in range(B)]
    kernel = make_explore_kernel(app, cfg)
    progs = stack_programs([lower_program(app, cfg, p) for p in programs])
    keys = jax.random.split(jax.random.PRNGKey(base), B)
    res = kernel(progs, keys)
    statuses = np.asarray(res.status)
    assert int((statuses == ST_OVERFLOW).sum()) == 0
    lanes = np.flatnonzero(statuses == ST_VIOLATION)
    assert len(lanes) > 0, "crash-recovery sweep missed the durability race"
    assert set(np.asarray(res.violation)[lanes]) == {1}  # two leaders

    # Host lift: the violating lane's schedule must reproduce on the
    # sequential oracle (host/device parity for HardKill+restart flows).
    from helpers import lift_lane_to_host

    single, host = lift_lane_to_host(app, cfg, progs, keys, int(lanes[0]))
    assert int(single.violation) == 1
    assert host.violation is not None and host.violation.code == 1
