"""The fault plane on the normal path, at small size on the CPU: the
shared builder (``parallel/distributed.build_workload``, the CLI's flags)
says a crash-recovery-and-partition deployment; the device and the host
oracle agree on it lane for lane; a cut link drops by one rule in both
tiers; continuous and chunked sweeps give one digest.

The deployment is ``benchmarks/configs/raft5-nemesis.json`` cut to 5 nodes,
``log_cap`` 8, 160 steps, pool 96, 256 lanes."""

import dataclasses
import hashlib
from collections import Counter

import jax
import numpy as np
import pytest

from demi_tpu.apps.common import dsl_start_events, make_host_invariant
from demi_tpu.apps.raft import (
    T_CLIENT, T_ELECTION, make_raft_app, raft_send_generator,
)
from demi_tpu.config import SchedulerConfig
from demi_tpu.device.core import (
    OP_HARDKILL, OP_PARTITION, OP_START, OP_UNPARTITION, REC_EXT_BASE,
    ST_DONE, ST_OVERFLOW, ST_VIOLATION, init_state,
)
from demi_tpu.device.encoding import (
    _actor_or_external, count_ops, device_trace_to_guide, lower_program,
    stack_programs,
)
from demi_tpu.device.explore import make_any_step_fn, make_explore_kernel
from demi_tpu.external_events import (
    HardKill, Kill, MessageConstructor, Partition, Send, Start, UnPartition,
    WaitQuiescence,
)
from demi_tpu.fuzzing import Fuzzer, FuzzerWeights
from demi_tpu.parallel.distributed import build_workload
from demi_tpu.runner import lift_lane_to_host
from demi_tpu.schedulers.guided import GuidedScheduler

NEMESIS = {
    "app": "raft", "nodes": 5, "bug": None, "seed": 0, "log_cap": 8,
    "num_events": 24, "max_messages": 160, "pool": 96,
    "timer_weight": 0.05, "send_weight": 0.1, "wait_weight": 0.35,
    "hard_kill_weight": 0.25, "restart_weight": 0.3,
    "partition_weight": 0.1, "kill_weight": 0.0,
    "max_kills": 4, "wait_budget": [1, 25],
}
LANES = 256
BASE = 0


def programs_digest(app, cfg, fuzzer, n=64) -> str:
    h = hashlib.sha256()
    for s in range(n):
        p = lower_program(app, cfg, fuzzer.generate_fuzz_test(seed=s))
        for arr in (p.op, p.a, p.b, p.msg):
            h.update(arr.tobytes())
    return h.hexdigest()[:16]


# -- (a) the shared builder ------------------------------------------------

HAND_BUILT = {
    # tests/test_raft_case_studies.py's fuzzer before it called the builder
    "raft3-lost-vote": (
        {"app": "raft", "nodes": 3, "bug": None, "num_events": 10,
         "kill_weight": 0.01, "send_weight": 0.1, "wait_weight": 0.35,
         "hard_kill_weight": 0.25, "restart_weight": 0.3, "max_kills": 2,
         "wait_budget": [1, 25]},
        lambda app: Fuzzer(
            num_events=10,
            weights=FuzzerWeights(
                send=0.1, wait_quiescence=0.35, hard_kill=0.25, restart=0.3
            ),
            message_gen=raft_send_generator(app),
            prefix=dsl_start_events(app), max_kills=2, wait_budget=(1, 25),
        ),
    ),
    "raft5-nemesis": (
        NEMESIS,
        lambda app: Fuzzer(
            num_events=24,
            weights=FuzzerWeights(
                kill=0.0, send=0.1, wait_quiescence=0.35, partition=0.1,
                unpartition=0.1, hard_kill=0.25, restart=0.3,
            ),
            message_gen=raft_send_generator(app),
            prefix=dsl_start_events(app), max_kills=4, wait_budget=(1, 25),
        ),
    ),
}


@pytest.mark.parametrize("name", sorted(HAND_BUILT))
def test_builder_generates_the_hand_built_fuzzers_programs(name):
    workload, by_hand = HAND_BUILT[name]
    app, cfg, fuzzer = build_workload(dict(workload))
    assert programs_digest(app, cfg, fuzzer) == programs_digest(
        app, cfg, by_hand(app)
    )


# 64 programs of each app from the builder's defaults, recorded at the
# parent of the PR that made the literals flags (PR 27).
DEFAULT_DIGESTS = {
    ("raft", 5, "multivote"): "654111c7db378baa",
    ("raft", 3, None): "f282173d5a9971dd",
    ("broadcast", 4, "x"): "1d836d17b6b96d59",
    ("spark", 4, None): "0dcdfbe00371c8b7",
    ("twopc", 4, None): "8a72cba0a073bf94",
}


@pytest.mark.parametrize("app_name,nodes,bug", sorted(DEFAULT_DIGESTS, key=str))
def test_defaults_generate_the_programs_they_did(app_name, nodes, bug):
    app, cfg, fuzzer = build_workload({
        "app": app_name, "nodes": nodes, "bug": bug, "num_events": 12,
        "max_messages": 144, "pool": 96,
    })
    if app_name == "spark":
        # PR 33 addressed SubmitJob to the driver (same draws): with the
        # addressee taken off again the programs are the recorded ones.
        assert programs_digest(app, cfg, fuzzer) == "bcb10e1cae40b2b3"
        fuzzer.message_gen.target = None
    assert programs_digest(app, cfg, fuzzer) == DEFAULT_DIGESTS[
        (app_name, nodes, bug)
    ]


def test_cli_flags_say_the_deployment(capsys):
    """``demi_tpu sweep`` with the fault-plane flags builds the programs
    the workload dict builds, and runs them."""
    import argparse
    import json

    from demi_tpu import cli

    argv = [
        "sweep", "--app", "raft", "--nodes", "5", "--log-cap", "8",
        "--num-events", "24", "--max-messages", "160", "--pool", "96",
        "--timer-weight", "0.05", "--send-weight", "0.1",
        "--wait-weight", "0.35", "--hard-kill-weight", "0.25",
        "--restart-weight", "0.3", "--partition-weight", "0.1",
        "--kill-weight", "0.0",
        "--max-kills", "4", "--wait-budget", "1", "25", "--batch", "64",
    ]
    seen = {}
    real = cli.build_fuzzer

    def spy(app, args):
        seen["args"] = args
        return real(app, args)

    cli.build_fuzzer = spy
    try:
        assert cli.main(argv) == 0
    finally:
        cli.build_fuzzer = real
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["lanes"] == 64 and summary["overflow_lanes"] == 0
    args = seen["args"]
    assert isinstance(args, argparse.Namespace)
    app, cfg, fuzzer = build_workload(dict(NEMESIS))
    assert cli.build_app(args).state_width == app.state_width == 34
    assert programs_digest(app, cfg, real(app, args)) == programs_digest(
        app, cfg, fuzzer
    )


# -- (b) device against host on nemesis programs -----------------------------

@pytest.fixture(scope="module")
def swept():
    app, cfg, fuzzer = build_workload(dict(NEMESIS))
    programs = [fuzzer.generate_fuzz_test(seed=BASE + s) for s in range(LANES)]
    progs = stack_programs([lower_program(app, cfg, p) for p in programs])
    keys = jax.vmap(
        lambda s: jax.random.fold_in(jax.random.PRNGKey(0), s)
    )(np.arange(BASE, BASE + LANES, dtype=np.uint32))
    res = make_explore_kernel(app, cfg)(progs, keys)
    return app, cfg, fuzzer, progs, keys, res


def test_the_small_deployment_engages_the_fault_plane(swept):
    app, cfg, _fuzzer, progs, _keys, res = swept
    status = np.asarray(res.status)
    assert int((status == ST_OVERFLOW).sum()) == 0
    assert int((status == ST_VIOLATION).sum()) > 0
    counts = count_ops([
        jax.tree_util.tree_map(lambda x: x[i], progs) for i in range(LANES)
    ])
    assert counts["start"] == LANES * app.num_actors
    for kind in ("hard_kill", "restart", "partition", "unpartition"):
        assert counts[kind] > LANES // 2, counts
    assert counts["kill"] == 0
    assert sum(counts.values()) == int((progs.op != 0).sum())


def test_device_and_host_agree_lane_for_lane(swept):
    """Every violating lane and 32 clean ones, lifted to the host oracle:
    the single-lane re-run repeats the batch lane's delivered sequence
    (sched_hash), the host delivers the same sequence (a GuideDivergence
    fails the test) and reaches the same code; between them the lanes
    applied every fault op."""
    app, cfg, _fuzzer, progs, keys, res = swept
    status = np.asarray(res.status)
    code = np.asarray(res.violation)
    hashes = np.asarray(res.sched_hash)
    lanes = np.flatnonzero(status == ST_VIOLATION).tolist()
    lanes += np.flatnonzero(status == ST_DONE)[:32].tolist()
    assert len(lanes) > 32
    applied = Counter()
    for lane in lanes:
        single, host = lift_lane_to_host(app, cfg, progs, keys, lane)
        assert int(single.sched_hash) == int(hashes[lane])
        assert int(single.violation) == int(code[lane])
        host_code = host.violation.code if host.violation is not None else 0
        assert host_code == int(code[lane]), lane
        assert host.deliveries == int(single.deliveries)
        trace = np.asarray(single.trace)[: int(single.trace_len)]
        killed = set()
        for kind, a in zip(trace[:, 0].tolist(), trace[:, 1].tolist()):
            op = kind - REC_EXT_BASE
            if op == OP_HARDKILL:
                killed.add(a)
            elif op == OP_START and a in killed:
                killed.discard(a)
                applied["restart"] += 1
            applied[op] += 1
    for op in (OP_HARDKILL, "restart", OP_PARTITION, OP_UNPARTITION):
        assert applied[op] > 0, applied


# -- (c) the cut-link rule, host against device ------------------------------

def _final_state(app, cfg, program, key):
    """The lane's ScheduleState after its program ran (trace recorded)."""
    cfg = dataclasses.replace(cfg, record_trace=True)
    step = make_any_step_fn(app, cfg)
    prog = lower_program(app, cfg, program)

    @jax.jit
    def run(prog, key):
        def body(state, _):
            return step(state, prog), None

        state, _ = jax.lax.scan(
            body, init_state(app, cfg, key), None, length=cfg.max_steps
        )
        return state

    return run(prog, key)


def _device_pending(app, state):
    valid = np.asarray(state.pool_valid)
    rows = zip(
        np.asarray(state.pool_src)[valid].tolist(),
        np.asarray(state.pool_dst)[valid].tolist(),
        np.asarray(state.pool_timer)[valid].tolist(),
        map(tuple, np.asarray(state.pool_msg)[valid].tolist()),
    )
    return Counter(rows)


def _host_pending(app, sched):
    return Counter(
        (
            _actor_or_external(app, e.snd), _actor_or_external(app, e.rcv),
            e.is_timer, sched._msg_key(e.msg),
        )
        for e in sched.pending_entries()
    )


def _both_tiers(app, cfg, program, seed=0):
    """Run ``program`` on the device, replay its delivered sequence on the
    host oracle, and return both tiers' pending sets and the trace."""
    state = _final_state(app, cfg, program, jax.random.PRNGKey(seed))
    assert int(state.status) != ST_OVERFLOW
    trace = np.asarray(state.trace)[: int(state.trace_len)]
    sched = GuidedScheduler(
        SchedulerConfig(invariant_check=make_host_invariant(app)), app
    )
    sched.execute_guide(device_trace_to_guide(app, trace, len(trace)))
    return _device_pending(app, state), _host_pending(app, sched), trace


def _deliveries(trace, src, dst):
    """Message deliveries src -> dst, with their position in the trace."""
    return [
        i for i, rec in enumerate(trace.tolist())
        if rec[0] == 1 and rec[1] == src and rec[2] == dst
    ]


def _ext_position(trace, op, nth=0):
    return [
        i for i, rec in enumerate(trace.tolist())
        if rec[0] == REC_EXT_BASE + op
    ][nth]


@pytest.fixture(scope="module")
def raft3():
    app = make_raft_app(3)
    cfg = build_workload({
        "app": "raft", "nodes": 3, "bug": None, "num_events": 12,
        "max_messages": 160, "pool": 96, "timer_weight": 0.05,
    })[1]
    return app, cfg


def _cmd(app, node, value):
    return Send(
        app.actor_name(node),
        MessageConstructor(lambda v=value: (T_CLIENT, 0, v, 0, 0, 0, 0)),
    )


CUT_PROGRAMS = {
    # the link 0-1 is cut mid-flood and never healed
    "cut": lambda app: [
        WaitQuiescence(budget=30), _cmd(app, 0, 7), _cmd(app, 1, 8),
        WaitQuiescence(budget=6),
        Partition(app.actor_name(0), app.actor_name(1)),
        WaitQuiescence(budget=60),
    ],
    # cut, then healed: the link carries messages again
    "cut-heal": lambda app: [
        WaitQuiescence(budget=30), _cmd(app, 0, 7),
        WaitQuiescence(budget=6),
        Partition(app.actor_name(0), app.actor_name(1)),
        WaitQuiescence(budget=40),
        UnPartition(app.actor_name(0), app.actor_name(1)),
        _cmd(app, 1, 9),
        WaitQuiescence(budget=60),
    ],
    # two links cut at once, one end also hard-killed and restarted
    "cut-crash": lambda app: [
        WaitQuiescence(budget=25),
        Partition(app.actor_name(0), app.actor_name(1)),
        Partition(app.actor_name(1), app.actor_name(2)),
        _cmd(app, 2, 5), WaitQuiescence(budget=20),
        HardKill(app.actor_name(1)), WaitQuiescence(budget=15),
        Start(app.actor_name(1)),
        UnPartition(app.actor_name(0), app.actor_name(1)),
        WaitQuiescence(budget=40),
    ],
    # a hard-killed node's mail is lost while it is down; the run ends
    # with it down
    "crash-down": lambda app: [
        WaitQuiescence(budget=30), _cmd(app, 0, 7), WaitQuiescence(budget=6),
        HardKill(app.actor_name(1)), _cmd(app, 2, 4),
        WaitQuiescence(budget=80),
    ],
    # a soft kill holds its messages, as before: no partition op
    "isolate": lambda app: [
        WaitQuiescence(budget=30), _cmd(app, 0, 7),
        Kill(app.actor_name(2)), WaitQuiescence(budget=40),
        Start(app.actor_name(2)), WaitQuiescence(budget=30),
    ],
}


@pytest.mark.parametrize("name", sorted(CUT_PROGRAMS))
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_cut_link_rule_host_against_device(raft3, name, seed):
    """After every program both tiers hold the same pending set, the host
    delivers the device's sequence without divergence, and nothing is
    delivered over a link while it is cut."""
    app, cfg = raft3
    program = dsl_start_events(app) + CUT_PROGRAMS[name](app)
    device, host, trace = _both_tiers(app, cfg, program, seed)
    assert device == host
    cut = {}  # frozenset link -> position of its Partition
    for i, rec in enumerate(trace.tolist()):
        op = rec[0] - REC_EXT_BASE
        if op == OP_PARTITION:
            cut[frozenset(rec[1:3])] = i
        elif op == OP_UNPARTITION:
            cut.pop(frozenset(rec[1:3]), None)
        elif rec[0] == 1 and rec[1] < app.num_actors:
            assert frozenset(rec[1:3]) not in cut, (name, i, rec)
    if name == "cut":
        # nothing crosses 0-1 in the pool either, in either tier
        for src, dst, timer, _msg in device:
            assert timer or {src, dst} != {0, 1}
    if name == "crash-down":
        # no peer's message waits for the dead node, in either tier
        assert not [k for k in device if k[1] == 1 and k[0] < app.num_actors]
    if name == "cut-heal":
        heal = _ext_position(trace, OP_UNPARTITION)
        after = [
            i for i in _deliveries(trace, 0, 1) + _deliveries(trace, 1, 0)
            if i > heal
        ]
        assert after, "the healed link carried nothing"
    if name == "isolate":
        # the soft kill's messages were held, not dropped: deliveries to
        # and from the isolated node resume after its recovery
        recovered = [
            i for i, rec in enumerate(trace.tolist())
            if rec[0] == REC_EXT_BASE + OP_START and rec[1] == 2
        ][-1]
        assert [i for i in _deliveries(trace, 0, 2) if i > recovered]


def test_partition_drops_what_is_pending_on_the_link(raft3):
    """The Partition's own step: on the device it removes the link's
    messages from the pool, both directions, and nothing else; on the
    host the scheduler's pending set loses the same entries."""
    app, cfg = raft3
    cut = Partition(app.actor_name(0), app.actor_name(1))
    program = dsl_start_events(app) + [
        WaitQuiescence(budget=30), _cmd(app, 0, 7), WaitQuiescence(budget=8),
        cut, WaitQuiescence(budget=5),
    ]
    at = program.index(cut)
    cfg = dataclasses.replace(cfg, record_trace=True)
    step = make_any_step_fn(app, cfg)
    prog = lower_program(app, cfg, program)

    @jax.jit
    def history(prog, key):
        def body(state, _):
            state = step(state, prog)
            return state, state

        return jax.lax.scan(
            body, init_state(app, cfg, key), None, length=cfg.max_steps
        )

    for seed in range(8):
        last, states = history(prog, jax.random.PRNGKey(seed))
        cursor = np.asarray(states.ext_cursor)
        t = int(np.argmax(cursor == at + 1))  # the step that applied it
        assert cursor[t - 1] == at
        before, after = (
            _device_pending(
                app, jax.tree_util.tree_map(lambda x: x[k], states)
            )
            for k in (t - 1, t)
        )
        on_link = Counter({
            k: n for k, n in before.items()
            if not k[2] and {k[0], k[1]} == {0, 1}
        })
        assert before - on_link == after

        trace = np.asarray(last.trace)[: int(last.trace_len)]
        guide = device_trace_to_guide(app, trace, len(trace))
        k = guide.index(("ext", OP_PARTITION, 0, 1, (0,) * app.msg_width))
        sched = GuidedScheduler(
            SchedulerConfig(invariant_check=make_host_invariant(app)), app
        )
        sched.execute_guide(guide[:k])
        assert _host_pending(app, sched) == before
        sched._inject_one(cut)
        assert _host_pending(app, sched) == after
        if on_link:
            return
    pytest.fail("no seed left a message pending on the link")


def test_timers_and_externals_cross_a_cut_link_untouched(raft3):
    """Timers are self-sends and externals cross no link: with every link
    of node 0 cut, its election timer fires and a client command reaches
    it, on both tiers."""
    app, cfg = raft3
    program = dsl_start_events(app) + [
        Partition(app.actor_name(0), app.actor_name(1)),
        Partition(app.actor_name(0), app.actor_name(2)),
        _cmd(app, 0, 3),
        WaitQuiescence(budget=40),
    ]
    device, host, trace = _both_tiers(app, cfg, program)
    assert device == host
    rows = trace.tolist()
    assert [r for r in rows if r[0] == 2 and r[2] == 0 and r[3] == T_ELECTION]
    assert [r for r in rows if r[0] == 1 and r[1] == app.num_actors and r[2] == 0]
    assert not [
        r for r in rows if r[0] == 1 and r[1] < app.num_actors
        and 0 in (r[1], r[2]) and r[1] != r[2]
    ]


# Verdicts and delivered sequences (sched_hash) of 64 default programs,
# soft kills among them and no partition op, recorded at the parent of
# the PR that made cut links drop (PR 27): the rule changes nothing such
# a program executes.
NO_PARTITION_DIGEST = "7ab64fcae7af4d99"


def test_a_program_without_partitions_delivers_what_it_did():
    app, cfg, fuzzer = build_workload({
        "app": "raft", "nodes": 5, "bug": "multivote", "num_events": 12,
        "max_messages": 144, "pool": 96,
    })
    lanes = 64
    programs = [fuzzer.generate_fuzz_test(seed=s) for s in range(lanes)]
    assert not any(
        isinstance(e, (Partition, UnPartition)) for p in programs for e in p
    )
    assert any(isinstance(e, Kill) for p in programs for e in p)
    progs = stack_programs([lower_program(app, cfg, p) for p in programs])
    keys = jax.vmap(
        lambda s: jax.random.fold_in(jax.random.PRNGKey(0), s)
    )(np.arange(lanes, dtype=np.uint32))
    res = make_explore_kernel(app, cfg)(progs, keys)
    digest = hashlib.sha256(
        np.asarray(res.sched_hash).tobytes()
        + np.asarray(res.status).tobytes()
        + np.asarray(res.violation).tobytes()
    ).hexdigest()[:16]
    assert digest == NO_PARTITION_DIGEST


# -- (d) continuous and chunked sweeps --------------------------------------

def test_continuous_and_chunked_give_one_digest():
    from demi_tpu.parallel.sweep import SweepDriver

    app, cfg, fuzzer = build_workload(dict(NEMESIS))
    driver = SweepDriver(
        app, cfg, lambda s: fuzzer.generate_fuzz_test(seed=BASE + s)
    )
    continuous = driver.sweep(LANES, 64, mode="continuous")
    chunked = driver.sweep(LANES, 64, mode="chunked")
    assert continuous.lanes == chunked.lanes == LANES
    assert continuous.overflow_lanes == chunked.overflow_lanes == 0
    assert continuous.violations == chunked.violations > 0
    assert continuous.lanes_digest == chunked.lanes_digest
