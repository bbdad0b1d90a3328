"""Eager reliable broadcast under a quiescence invariant, on the normal
path, at small size on the CPU (the deployment is
``benchmarks/configs/bcast64-flood.json`` cut to 8 nodes): the invariant's
cadence is the app's, every verb's builder reads it there; a run ends at
quiescence or has no verdict, on the device and on the host tier alike;
``max_sends`` bounds the floods a schedule holds; and the device lane, the
host oracle and the plain reference (``benchmarks/lib/flood_reference.py``:
sets and lists, no JAX) agree lane for lane."""

import dataclasses
import importlib.util
import json
import os
import sys

import jax
import numpy as np
import pytest

from demi_tpu import cli
from demi_tpu.apps.broadcast import make_broadcast_app
from demi_tpu.apps.common import dsl_start_events, make_host_invariant
from demi_tpu.apps.raft import make_raft_app
from demi_tpu.config import SchedulerConfig
from demi_tpu.device.core import (
    OP_SEND, ST_DONE, ST_OVERFLOW, ST_UNFINISHED, ST_VIOLATION, DeviceConfig,
)
from demi_tpu.device.encoding import (
    device_trace_to_guide, lower_program, stack_programs,
)
from demi_tpu.device.explore import make_explore_kernel
from demi_tpu.external_events import (
    HardKill, MessageConstructor, Send, Start, WaitQuiescence,
)
from demi_tpu.parallel.distributed import build_workload, workload_args
from demi_tpu.schedulers.guided import GuidedScheduler
from demi_tpu.schedulers.random import RandomScheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module   # dataclasses look their module up
    spec.loader.exec_module(module)
    return module


flood_reference = _load("benchmarks/lib/flood_reference.py", "flood_reference")

FLOOD8 = {
    "app": "broadcast", "nodes": 8, "bug": None, "seed": 0,
    "num_events": 6, "max_messages": 128, "pool": 128, "max_sends": 1,
    "send_weight": 0.5, "wait_weight": 0.2, "wait_budget": [2, 40],
    "kill_weight": 0.1, "hard_kill_weight": 0.1, "restart_weight": 0.1,
    "partition_weight": 0.0, "max_kills": 2, "timer_weight": 0.2,
}
LANES = 48
SWEEP = [
    "sweep", "--app", "broadcast", "--nodes", "8", "--batch", "32",
    "--pool", "128", "--max-messages", "128", "--num-events", "6",
]


def sweep_json(capsys, *extra):
    assert cli.main(SWEEP + list(extra)) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# -- (a) the cadence is the app's ------------------------------------------

def test_apps_say_when_their_invariant_may_be_judged():
    assert make_broadcast_app(4).invariant_at == "quiescence"
    assert make_broadcast_app(4).invariant_interval == 0
    assert make_raft_app(3).invariant_at == "delivery"
    assert make_raft_app(3).invariant_interval == 1
    with pytest.raises(ValueError):
        dataclasses.replace(make_broadcast_app(4), invariant_at="never")


@pytest.mark.parametrize("app_name,interval", [
    ("broadcast", 0), ("raft", 1), ("spark", 1), ("twopc", 1),
])
def test_one_builder_takes_the_cadence_from_the_app(app_name, interval):
    workload = {"app": app_name, "nodes": 4, "bug": None}
    app, cfg, _ = build_workload(workload)
    assert cfg.invariant_interval == interval == app.invariant_interval
    args = workload_args(workload)
    by_hand = DeviceConfig.for_app(
        app, pool_capacity=args.pool, max_steps=args.max_messages,
        max_external_ops=max(16, args.num_events + app.num_actors + 2),
        invariant_interval=interval, timer_weight=args.timer_weight,
    )
    assert cfg == by_hand == DeviceConfig.for_workload(app, args)
    host = make_host_invariant(app)
    assert host.at_quiescence == (interval == 0)
    assert SchedulerConfig(invariant_check=host).quiescence_invariant == (
        interval == 0
    )


def test_the_cli_has_fewer_hand_built_device_configs_than_it_had():
    with open(os.path.join(ROOT, "demi_tpu", "cli.py")) as f:
        source = f.read()
    assert "DeviceConfig.for_app(" not in source
    assert "invariant_interval=1" not in source


# -- (b) the sweep verb on the correct and on the planted-bug protocol -----

def test_the_correct_protocol_sweeps_clean_where_it_read_every_lane(capsys):
    out = sweep_json(capsys, "--max-sends", "1")
    assert out["violations"] == 0 and out["codes"] == {}
    assert out["overflow_lanes"] == 0 and out["unfinished_lanes"] == 0
    assert out["lanes"] == 32


def test_the_planted_bug_is_found_at_quiescence(capsys):
    out = sweep_json(capsys, "--max-sends", "1", "--bug", "x")
    assert out["codes"] == {"1": out["violations"]}
    # Every schedule that holds a send strands it at its first receiver.
    assert 24 <= out["violations"] <= 32
    assert out["unfinished_lanes"] == 0


@pytest.mark.parametrize("mode", ["continuous", "chunked"])
def test_an_undersized_step_budget_yields_unfinished_lanes_not_verdicts(
    capsys, mode
):
    out = sweep_json(
        capsys, "--max-sends", "1", "--bug", "x", "--max-messages", "10",
        "--sweep-mode", mode,
    )
    # 8 starts and the send already take nine steps: no lane gets through.
    assert out["unfinished_lanes"] >= 24
    assert out["violations"] == 0 and out["overflow_lanes"] == 0
    assert out["unfinished_lanes"] + out["unique_schedules"] <= 32 + 1


def test_a_raft_default_runs_digest_is_the_parents(capsys):
    """``sweep --app raft --nodes 3 --bug multivote --batch 64 --chunk 32``
    at the parent commit of the PR that moved the cadence to the app."""
    assert cli.main([
        "sweep", "--app", "raft", "--nodes", "3", "--bug", "multivote",
        "--batch", "64", "--chunk", "32",
    ]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["lanes_digest"] == "2b7d03a689e88ee8"
    assert out["violations"] == 22 and out["unfinished_lanes"] == 0


# -- (c) max_sends ----------------------------------------------------------

@pytest.mark.parametrize("cap", [None, 0, 1, 2])
def test_max_sends_draws_the_same_program_on_the_row_and_the_event_path(cap):
    app, cfg, fuzzer = build_workload(
        dict(FLOOD8, max_sends=cap, num_events=10)
    )
    assert fuzzer.max_sends == cap
    most = 0
    for seed in range(40):
        prog = fuzzer.generate_fuzz_test(seed=seed)
        assert prog.lowerable
        rows = lower_program(app, cfg, prog)
        events = lower_program(
            app, cfg, list(fuzzer.generate_fuzz_test(seed=seed))
        )
        for x, y in zip(rows, events):
            np.testing.assert_array_equal(x, y)
        sends = sum(isinstance(e, Send) for e in prog)
        most = max(most, sends)
        assert cap is None or sends <= cap
    assert most == (cap if cap is not None else most) and (cap == 0 or most > 0)


def test_a_capped_send_is_a_futile_draw_and_takes_nothing_from_the_rng():
    """Up to its first capped send a program is the uncapped one's."""
    _, _, free = build_workload(dict(FLOOD8, max_sends=None))
    _, _, capped = build_workload(dict(FLOOD8, max_sends=1))
    same = 0
    for seed in range(40):
        a = free.generate_fuzz_test(seed=seed)
        b = capped.generate_fuzz_test(seed=seed)
        second = [i for i, k in enumerate(a.kind) if k == OP_SEND][1:2]
        upto = second[0] if second else min(len(a.kind), len(b.kind)) - 1
        for x, y in ((a.kind, b.kind), (a.a, b.a), (a.b, b.b)):
            assert x[:upto] == y[:upto]
        same += not second
    assert same < 40   # the cap bit: some uncapped program sends twice


# -- (d) device, host oracle and the plain reference, lane for lane --------

@pytest.fixture(scope="module")
def flood():
    app, cfg, fuzzer = build_workload(dict(FLOOD8))
    traced = dataclasses.replace(cfg, record_trace=True)
    progs = stack_programs([
        lower_program(app, cfg, fuzzer.generate_fuzz_test(seed=s))
        for s in range(LANES)
    ])
    keys = jax.vmap(lambda s: jax.random.fold_in(jax.random.PRNGKey(0), s))(
        np.arange(LANES, dtype=np.uint32)
    )
    plain = make_explore_kernel(app, cfg)(progs, keys)
    res = make_explore_kernel(app, traced)(progs, keys)
    return app, cfg, fuzzer, jax.device_get(plain), jax.device_get(res)


def test_the_seeded_programs_cover_the_fault_plane(flood):
    app, cfg, fuzzer, plain, res = flood
    kinds = set()
    for s in range(LANES):
        kinds |= {type(e).__name__ for e in fuzzer.generate_fuzz_test(seed=s)}
    assert {"Kill", "HardKill", "Start", "Send", "WaitQuiescence"} <= kinds
    status = np.asarray(plain.status)
    assert set(status.tolist()) <= {ST_DONE, ST_VIOLATION}
    assert 0 < int((status == ST_VIOLATION).sum()) < LANES
    np.testing.assert_array_equal(plain.sched_hash, res.sched_hash)
    np.testing.assert_array_equal(plain.violation, res.violation)


@pytest.mark.parametrize("lane", range(LANES))
def test_device_host_oracle_and_plain_reference_agree(flood, lane):
    app, cfg, fuzzer, plain, res = flood
    code = int(plain.violation[lane])
    records, length = np.asarray(res.trace[lane]), int(res.trace_len[lane])
    sched = GuidedScheduler(
        SchedulerConfig(invariant_check=make_host_invariant(app)), app
    )
    host = sched.execute_guide(device_trace_to_guide(app, records, length))
    assert host.quiescent
    assert (host.violation.code if host.violation is not None else 0) == code
    ref = flood_reference.replay(app.num_actors, records, length)
    assert ref.quiescent and ref.code == code
    assert ref.deliveries == int(plain.deliveries[lane]) == host.deliveries
    for i in range(app.num_actors):
        actor = sched.system.actors.get(app.actor_name(i))
        if actor is None or app.actor_name(i) in sched.system.crashed:
            assert not ref.alive[i]
            continue
        mask = sum(1 << b for b in ref.delivered[i])
        assert int(actor.state[0]) == mask, (lane, i)
    if code:
        # Agreement only breaks on a node that lost its delivered set.
        events = list(fuzzer.generate_fuzz_test(seed=lane))
        killed = {e.name for e in events if isinstance(e, HardKill)}
        restarted = {
            e.name for e in events[app.num_actors:] if isinstance(e, Start)
        }
        assert killed & restarted


def test_the_reference_refuses_a_trace_that_is_not_the_protocols(flood):
    app, cfg, fuzzer, plain, res = flood
    lane = int(np.argmax(np.asarray(plain.deliveries)))
    records = np.array(res.trace[lane])
    length = int(res.trace_len[lane])
    first = next(i for i in range(length) if records[i][0] == 1)
    records[first][2] = (records[first][2] + 1) % app.num_actors
    with pytest.raises(flood_reference.Diverged):
        flood_reference.replay(app.num_actors, records, length)


# -- (e) a run ends at quiescence or has no verdict, in both tiers ---------

def _flood_program(app, budget):
    return dsl_start_events(app) + [
        Send(app.actor_name(0), MessageConstructor(lambda: (1, 0))),
        WaitQuiescence(budget=budget),
    ]


def _one_lane(app, cfg, program):
    progs = stack_programs([lower_program(app, cfg, program)])
    res = make_explore_kernel(app, cfg)(
        progs, jax.random.split(jax.random.PRNGKey(3), 1)
    )
    return int(res.status[0]), int(res.violation[0]), int(res.deliveries[0])


def test_the_final_wait_drains_its_budget_on_the_device_and_on_the_host():
    app = make_broadcast_app(4)
    cfg = DeviceConfig.for_workload(
        app, workload_args({"max_messages": 64, "pool": 64})
    )
    whole = 1 + 4 * 3
    assert _one_lane(app, cfg, _flood_program(app, 3)) == (ST_DONE, 0, whole)
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    host = RandomScheduler(config, seed=1, max_messages=64).execute(
        _flood_program(app, 3)
    )
    assert (host.deliveries, host.violation, host.quiescent) == (whole, None, True)
    # ... and is never judged mid-run, whatever interval a caller passes.
    host = RandomScheduler(
        config, seed=1, max_messages=64, invariant_check_interval=1
    ).execute(_flood_program(app, 3))
    assert (host.deliveries, host.violation, host.quiescent) == (whole, None, True)
    # Judged after any delivery, the same program ends when the budget does.
    early = dataclasses.replace(app, invariant_at="delivery")
    status, code, deliveries = _one_lane(
        early, dataclasses.replace(cfg, invariant_interval=0),
        _flood_program(early, 3),
    )
    assert (status, code, deliveries) == (ST_VIOLATION, 1, 3)
    host = RandomScheduler(
        SchedulerConfig(invariant_check=make_host_invariant(early)),
        seed=1, max_messages=64,
    ).execute(_flood_program(early, 3))
    assert host.deliveries == 3 and host.violation is not None


def test_a_bounded_wait_before_the_last_keeps_its_budget():
    app = make_broadcast_app(4)
    cfg = DeviceConfig.for_workload(
        app, workload_args({"max_messages": 64, "pool": 64})
    )
    program = _flood_program(app, 3) + [
        HardKill(app.actor_name(1)), WaitQuiescence(budget=2),
    ]
    status, code, deliveries = _one_lane(app, cfg, program)
    assert status == ST_DONE and code == 0
    # The kill landed after three deliveries and took mail with it.
    assert 3 <= deliveries < 1 + 4 * 3


def test_a_run_cut_by_its_cap_has_no_verdict_in_either_tier():
    app = make_broadcast_app(4, reliable=False)   # strands every broadcast
    small = DeviceConfig.for_workload(
        app, workload_args({"max_messages": 5, "pool": 64})
    )
    assert _one_lane(app, small, _flood_program(app, None)) == (
        ST_UNFINISHED, 0, 0
    )
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    reliable = make_broadcast_app(8)   # three deliveries reach three of 8
    cut = RandomScheduler(
        SchedulerConfig(invariant_check=make_host_invariant(reliable)),
        seed=1, max_messages=3,
    ).execute(_flood_program(reliable, None))
    assert (cut.deliveries, cut.violation, cut.quiescent) == (3, None, False)
    whole = RandomScheduler(config, seed=1, max_messages=64).execute(
        _flood_program(app, None)
    )
    assert whole.quiescent and whole.violation.code == 1
    # Judged after any delivery, the cut run is judged where it stopped.
    early = dataclasses.replace(reliable, invariant_at="delivery")
    judged = RandomScheduler(
        SchedulerConfig(invariant_check=make_host_invariant(early)),
        seed=1, max_messages=3,
    ).execute(_flood_program(early, None))
    assert judged.violation is not None and not judged.quiescent


def test_a_guide_that_stops_with_mail_deliverable_has_no_verdict():
    app = make_broadcast_app(4)
    cfg = dataclasses.replace(
        DeviceConfig.for_workload(
            app, workload_args({"max_messages": 9, "pool": 64})
        ),
        record_trace=True,
    )
    progs = stack_programs([lower_program(app, cfg, _flood_program(app, None))])
    res = make_explore_kernel(app, cfg)(
        progs, jax.random.split(jax.random.PRNGKey(3), 1)
    )
    assert int(res.status[0]) == ST_UNFINISHED and int(res.violation[0]) == 0
    guide = device_trace_to_guide(
        app, np.asarray(res.trace[0]), int(res.trace_len[0])
    )
    host = GuidedScheduler(
        SchedulerConfig(invariant_check=make_host_invariant(app)), app
    ).execute_guide(guide)
    assert host.violation is None and not host.quiescent
    ref = flood_reference.replay(
        app.num_actors, np.asarray(res.trace[0]), int(res.trace_len[0])
    )
    assert not ref.quiescent and ref.code == 1


def test_overflow_still_reads_overflow():
    app, cfg, fuzzer = build_workload(dict(FLOOD8, pool=16))
    progs = stack_programs([
        lower_program(app, cfg, fuzzer.generate_fuzz_test(seed=s))
        for s in range(16)
    ])
    res = make_explore_kernel(app, cfg)(
        progs, jax.random.split(jax.random.PRNGKey(0), 16)
    )
    assert ST_OVERFLOW in set(np.asarray(res.status).tolist())
    assert ST_UNFINISHED not in set(np.asarray(res.status).tolist())
