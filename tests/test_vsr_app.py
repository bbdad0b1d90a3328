"""Viewstamped Replication Revisited (``apps/vsr.py``) on the normal path,
at small size on the CPU (the deployment is
``benchmarks/configs/vsr5-recovery.json`` cut to ``log_cap`` 4, so a
message is 9 words, 384 deliveries and 32 fuzzed events): the protocol's
four paths on the host tier, step by step (requests commit, a view change
keeps what was committed, a hard-killed replica recovers its log through
the primary, state transfer closes a gap); device lane, host oracle and the
plain reference (``benchmarks/lib/vsr_reference.py``: dicts and lists, no
JAX) agreeing lane for lane on fuzzed crash-recovery-and-partition
schedules; the unmodified protocol clean under at most f kills a program;
each seeded bug found by a small sweep and lifted with its code."""

import dataclasses
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from demi_tpu.apps import vsr
from demi_tpu.apps.common import make_host_invariant
from demi_tpu.config import SchedulerConfig
from demi_tpu.device.continuous import ContinuousSweepDriver
from demi_tpu.device.core import ST_DONE, ST_VIOLATION, insert_form
from demi_tpu.device.encoding import (
    device_trace_to_guide, lower_program, stack_programs,
)
from demi_tpu.device.explore import make_single_lane_trace_kernel
from demi_tpu.parallel.distributed import build_workload
from demi_tpu.parallel.sweep import SweepDriver
from demi_tpu.runtime.actor import dsl_actor_factory
from demi_tpu.runtime.system import ControlledActorSystem
from demi_tpu.schedulers.guided import GuidedScheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module   # dataclasses look their module up
    spec.loader.exec_module(module)
    return module


vsr_reference = _load("benchmarks/lib/vsr_reference.py", "vsr_reference")

L = 4


def workload(nodes=5, bug="recover_any", log_cap=L):
    return {
        "app": "vsr", "nodes": nodes, "bug": bug, "log_cap": log_cap,
        "seed": 0,
        "num_events": 32, "max_messages": 384, "pool": 128,
        "timer_weight": 0.05, "send_weight": 0.15, "wait_weight": 0.35,
        "wait_budget": [1, 25], "hard_kill_weight": 0.15,
        "restart_weight": 0.25, "partition_weight": 0.1, "kill_weight": 0.0,
        "max_kills": (nodes - 1) // 2,
    }


def lane_key(seed):
    return jax.random.fold_in(jax.random.PRNGKey(0), seed)


# -- (a) the protocol's paths, one delivery at a time, on the host tier -----

class Cluster:
    """The host tier's actor system with its mail held here, so that a
    test delivers what it names: ``ControlledActorSystem`` and the
    ``DSLActorAdapter`` over the app's one handler, no scheduler."""

    def __init__(self, n=5, bug=None):
        self.app = vsr.make_vsr_app(n, log_cap=L, bug=bug)
        self.system = ControlledActorSystem()
        self.mail = []
        for i in range(n):
            self.start(i)
            self.deliver(vsr.T_BOOT, i)

    def name(self, i):
        return self.app.actor_name(i)

    def start(self, i):
        self.mail += self.system.spawn(
            self.name(i), dsl_actor_factory(self.app, i)
        )

    def hard_kill(self, i):
        self.system.hard_kill(self.name(i))
        self.mail = [
            e for e in self.mail if self.name(i) not in (e.snd, e.rcv)
        ]

    def request(self, i, value):
        self.mail.append(self.system.inject(
            self.name(i), (vsr.T_REQUEST, value) + (0,) * (3 + L)
        ))

    def find(self, tag, dst=None):
        return [
            e for e in self.mail if e.msg[0] == tag
            and (dst is None or e.rcv == self.name(dst))
        ]

    def deliver(self, tag, dst=None):
        entry = self.find(tag, dst)[0]
        self.mail.remove(entry)
        self.mail += self.system.deliver(entry)

    def lose(self, tag, dst):
        for entry in self.find(tag, dst):
            self.mail.remove(entry)

    def drain(self):
        """Every message, oldest first, until only timers are left."""
        while True:
            due = [e for e in self.mail if not e.is_timer]
            if not due:
                return
            self.mail.remove(due[0])
            self.mail += self.system.deliver(due[0])

    def row(self, i):
        return self.system.actors[self.name(i)].state

    def log(self, i):
        row = self.row(i)
        return row[vsr.LOG : vsr.LOG + row[vsr.OPN]].tolist()

    def field(self, name):
        return [
            int(self.row(i)[getattr(vsr, name)])
            for i in range(self.app.num_actors)
            if self.name(i) in self.system.actors
        ]


def committed_cluster(n=5):
    """Two requests committed everywhere in view 0: one sent to the
    primary, one to a backup, which forwards it."""
    c = Cluster(n)
    c.request(0, 7)
    c.request(2, 8)
    c.drain()
    c.deliver(vsr.T_COMMIT_TIMER, 0)
    c.drain()
    return c


@pytest.mark.parametrize("n", [3, 5])
def test_requests_commit_at_every_replica(n):
    c = committed_cluster(n)
    assert c.field("STATUS") == [vsr.NORMAL] * n and c.field("VIEW") == [0] * n
    assert c.field("OPN") == c.field("COMMIT") == [2] * n
    assert all(c.log(i) == [7, 8] for i in range(n))
    # the same request again is no new entry
    c.request(0, 7)
    c.drain()
    assert c.field("OPN") == [2] * n


@pytest.mark.parametrize("n", [3, 5])
def test_a_view_change_keeps_committed_entries(n):
    c = committed_cluster(n)
    c.request(0, 9)                  # prepared at the primary alone
    c.lose(vsr.T_PREPARE, None)
    c.hard_kill(0)
    # replica 1 waits out its timeout: PATIENCE ticks, then view 1
    for _ in range(vsr.PATIENCE):
        c.deliver(vsr.T_VIEW_TIMER, 1)
        assert c.row(1)[vsr.VIEW] == 0
    c.deliver(vsr.T_VIEW_TIMER, 1)
    assert c.row(1)[vsr.STATUS] == vsr.VIEW_CHANGE
    c.drain()
    assert c.field("VIEW") == [1] * (n - 1)
    assert c.field("STATUS") == [vsr.NORMAL] * (n - 1)
    assert all(c.log(i) == [7, 8] for i in range(1, n))
    assert c.field("COMMIT") == [2] * (n - 1)
    # the new primary serves
    c.request(1, 10)
    c.drain()
    assert c.log(1) == [7, 8, 10] and c.row(1)[vsr.COMMIT] == 3


@pytest.mark.parametrize("n,victim", [(3, 2), (5, 3), (5, 4)])
def test_a_hard_killed_replica_recovers_its_log_through_the_primary(n, victim):
    c = committed_cluster(n)
    c.hard_kill(victim)
    c.start(victim)
    fresh = c.row(victim)
    assert fresh[vsr.INCARN] == 1 and fresh[vsr.STATUS] == vsr.BOOTING
    assert fresh[vsr.OPN] == 0 and not fresh[vsr.LOG : vsr.LOG + L].any()
    c.deliver(vsr.T_BOOT, victim)
    assert c.row(victim)[vsr.STATUS] == vsr.RECOVERING
    assert c.row(victim)[vsr.NONCE] == c.row(victim)[vsr.INCARN] == 2
    # a RECOVERING replica takes no part: a PREPARE finds it deaf
    c.request(0, 9)
    c.deliver(vsr.T_REQUEST, 0)
    c.deliver(vsr.T_PREPARE, victim)
    assert c.row(victim)[vsr.OPN] == 0
    # the backups' answers alone are not enough (sec. 4.3.3) ...
    for i in range(1, n):
        if i != victim:
            c.deliver(vsr.T_RECOVERY, i)
    while c.find(vsr.T_RECRESP, victim):
        c.deliver(vsr.T_RECRESP, victim)
    assert c.row(victim)[vsr.STATUS] == vsr.RECOVERING
    # ... the primary's brings the log
    c.drain()
    assert c.row(victim)[vsr.STATUS] == vsr.NORMAL
    assert c.log(victim) == [7, 8, 9] and c.row(victim)[vsr.COMMIT] >= 2


def test_with_recover_any_it_comes_back_with_nothing():
    c = Cluster(5, bug="recover_any")
    c.request(0, 7)
    c.drain()
    c.hard_kill(3)
    c.start(3)
    c.deliver(vsr.T_BOOT, 3)
    for i in (1, 2, 4):                  # f + 1 backups, no primary
        c.deliver(vsr.T_RECOVERY, i)
    while c.find(vsr.T_RECRESP, 3):
        c.deliver(vsr.T_RECRESP, 3)
    assert c.row(3)[vsr.STATUS] == vsr.NORMAL and c.log(3) == []


def test_state_transfer_closes_a_gap():
    c = Cluster(5)
    c.request(0, 7)
    c.deliver(vsr.T_REQUEST, 0)
    c.lose(vsr.T_PREPARE, 4)
    c.drain()
    c.request(0, 8)
    c.deliver(vsr.T_REQUEST, 0)
    c.deliver(vsr.T_PREPARE, 4)          # n = 2 at a replica that holds 0
    assert c.log(4) == [] and len(c.find(vsr.T_GETSTATE, 0)) == 1
    c.deliver(vsr.T_GETSTATE, 0)
    c.deliver(vsr.T_NEWSTATE, 4)
    assert c.log(4) == [7, 8]
    c.drain()
    assert c.field("OPN") == [2] * 5


def test_a_replica_behind_a_view_cuts_its_tail_and_asks():
    c = committed_cluster(5)
    c.request(0, 9)
    c.deliver(vsr.T_REQUEST, 0)
    c.lose(vsr.T_PREPARE, None)          # 9 is at the old primary alone
    for _ in range(vsr.PATIENCE + 1):
        c.deliver(vsr.T_VIEW_TIMER, 1)
    # the view changes without replica 0: nothing of it reaches it
    while True:
        c.lose(vsr.T_SVC, 0)
        c.lose(vsr.T_STARTVIEW, 0)
        due = [e for e in c.mail if not e.is_timer]
        if not due:
            break
        c.mail.remove(due[0])
        c.mail += c.system.deliver(due[0])
    assert c.row(0)[vsr.VIEW] == 0 and c.log(0) == [7, 8, 9]
    c.deliver(vsr.T_COMMIT_TIMER, 1)
    c.deliver(vsr.T_COMMIT, 0)           # a COMMIT of view 1
    assert c.log(0) == [7, 8] and len(c.find(vsr.T_GETSTATE, 1)) == 1
    c.drain()
    assert c.row(0)[vsr.VIEW] == 1 and c.row(0)[vsr.STATUS] == vsr.NORMAL


def test_the_shapes_are_the_issues():
    app = vsr.make_vsr_app(5, log_cap=32, bug="recover_any")
    assert (app.msg_width, app.max_outbox, app.state_width) == (37, 5, 121)
    assert app.state_width == vsr.state_width(5, 32) == 20 + 5 + 3 * 32
    assert app.durable == (vsr.INCARN, vsr.LOG_ROWS_SENT)
    assert app.timer_tags == (1, 2, 3) and len(app.tag_names) == 15
    assert [name for name, _ in app.progress] == [
        "views", "recoveries", "recovered", "committed", "log_rows",
    ]
    rows = app.initial_msgs(2)
    assert rows.shape == (3, 39) and rows[:, 2].tolist() == [1, 2, 3]
    with pytest.raises(ValueError):
        vsr.make_vsr_app(5, bug="no_such_bug")
    with pytest.raises(ValueError):
        vsr.make_vsr_app(2)


@pytest.mark.parametrize("nodes", [3, 5])
def test_no_branch_sends_more_rows_than_the_outbox_holds(nodes):
    app = vsr.make_vsr_app(nodes, log_cap=L)
    state = jnp.asarray(app.init_state(0))
    for tag in range(1, vsr.NUM_TAGS + 1):
        msg = jnp.zeros(app.msg_width, jnp.int32).at[0].set(tag)
        new, out = app.handler(jnp.int32(0), state, jnp.int32(1), msg)
        assert out.shape == (app.max_outbox, 2 + app.msg_width), tag
        assert new.shape == state.shape


# -- (b) device, host oracle and the plain reference, lane for lane ---------

# Fuzz seeds: the first 24, and eight of the 58 in the first 2,048 on which
# ``recover_any`` breaks the invariant.
SEEDS = list(range(24)) + [45, 60, 74, 132, 176, 226, 301, 309]


# The same agreement with the batch on the chip's path: ``index_mode``
# 'onehot' (a CPU's 'auto' is scatter, so (b) above never runs the one-hot
# insert) at a ``log_cap`` that makes a row 17 words, since the one-hot
# insert carries a payload as whole rows and its cost and layout follow W
# (the cell's is 37): four of the first seeds and two on which the bug
# fires.
WIDE_L = 12
WIDE_SEEDS = [0, 1, 2, 3, 45, 60]


def _swept(seeds, log_cap=L, index_mode=None):
    """The seeds run to their end through the continuous driver's own
    kernels (status, code, sequence hash, final actor rows), and what
    the per-lane lifts need. ``index_mode`` is the batch's; the lifts'
    single-lane kernel keeps the workload's (scatter, on a CPU)."""
    app, cfg, fuzzer = build_workload(workload(log_cap=log_cap))
    gen = lambda s: fuzzer.generate_fuzz_test(seed=s)  # noqa: E731
    lanes = len(seeds)
    progs = stack_programs([lower_program(app, cfg, gen(s)) for s in seeds])
    keys = jax.vmap(lane_key)(np.asarray(seeds, np.uint32))
    batch_cfg = cfg if index_mode is None else dataclasses.replace(
        cfg, index_mode=index_mode
    )
    drv = ContinuousSweepDriver(app, batch_cfg, gen, batch=lanes, seg_steps=64)
    state = drv.init(keys)
    for steps in range(0, cfg.max_steps, 64):
        state = drv.segment(state, progs, jnp.full(lanes, steps, jnp.int32))
    state = jax.device_get(drv.finalize(state))
    return {
        "app": app, "cfg": cfg, "batch_cfg": batch_cfg, "log_cap": log_cap,
        "progs": progs, "keys": keys, "state": state,
        "kernel": make_single_lane_trace_kernel(app, cfg), "lifted": {},
    }


@pytest.fixture(scope="module")
def swept():
    return _swept(SEEDS)


@pytest.fixture(scope="module")
def swept_wide():
    return _swept(WIDE_SEEDS, log_cap=WIDE_L, index_mode="onehot")


def lifted(swept, lane):
    """Lane ``lane`` re-run traced on one lane and executed on the host
    oracle: ``(single, host result, the host's actor rows)``, once."""
    if lane not in swept["lifted"]:
        app = swept["app"]
        single = swept["kernel"](
            jax.tree_util.tree_map(lambda x: x[lane], swept["progs"]),
            swept["keys"][lane],
        )
        guide = device_trace_to_guide(
            app, np.asarray(single.trace), int(single.trace_len)
        )
        sched = GuidedScheduler(
            SchedulerConfig(invariant_check=make_host_invariant(app)), app
        )
        host = sched.execute_guide(guide)
        rows = {
            app.actor_id(name): np.asarray(actor.state)
            for name, actor in sched.system.actors.items()
        }
        swept["lifted"][lane] = (single, host, rows)
    return swept["lifted"][lane]


def test_the_seeds_hold_both_verdicts(swept):
    state = swept["state"]
    assert set(np.asarray(state.status).tolist()) == {ST_DONE, ST_VIOLATION}
    assert (np.asarray(state.violation)[24:] != 0).all()
    assert (np.asarray(state.violation)[:24] != 0).sum() <= 2


def _device_and_host_agree(swept, lane):
    state = swept["state"]
    single, host, rows = lifted(swept, lane)
    code = int(state.violation[lane])
    host_code = host.violation.code if host.violation is not None else 0
    assert int(single.violation) == code == host_code
    assert int(single.sched_hash) == int(state.sched_hash[lane])
    assert int(single.deliveries) == int(state.deliveries[lane]) == host.deliveries
    assert rows, "no replica is left on the host"
    for i, row in rows.items():
        np.testing.assert_array_equal(row, state.actor_state[lane][i], str(i))


def _the_plain_reference_agrees(swept, lane):
    single, host, rows = lifted(swept, lane)
    ref = vsr_reference.replay(
        5, swept["log_cap"], np.asarray(single.trace).tolist(),
        int(single.trace_len), bug="recover_any",
    )
    host_code = host.violation.code if host.violation is not None else 0
    assert ref.code == host_code
    assert ref.step == ref.deliveries == host.deliveries
    for i, row in rows.items():
        assert (ref.views[i], ref.statuses[i], ref.commits[i]) == (
            int(row[vsr.VIEW]), int(row[vsr.STATUS]), int(row[vsr.COMMIT])
        ), i
        assert ref.logs[i] == row[vsr.LOG : vsr.LOG + row[vsr.OPN]].tolist()
        assert ref.spawns[i] == int(row[vsr.INCARN])
    assert ref.log_rows == int(
        swept["state"].actor_state[lane][:, vsr.LOG_ROWS_SENT].sum()
    )


@pytest.mark.parametrize("lane", range(len(SEEDS)))
def test_device_and_host_agree_on_a_fuzzed_lane(swept, lane):
    """Same code, same delivered sequence, same final rows."""
    _device_and_host_agree(swept, lane)


@pytest.mark.parametrize("lane", range(len(SEEDS)))
def test_the_plain_reference_agrees_on_a_fuzzed_lane(swept, lane):
    """Verdict, step and every replica's view, status, commit-number and
    log, against the host oracle's rows."""
    _the_plain_reference_agrees(swept, lane)


def test_the_wide_seeds_run_the_whole_row_insert(swept_wide):
    assert swept_wide["app"].msg_width == 5 + WIDE_L
    assert insert_form(swept_wide["batch_cfg"]) == "rows"
    assert insert_form(swept_wide["cfg"]) == "scatter"  # the lifts' lane
    codes = np.asarray(swept_wide["state"].violation)
    assert (codes[4:] != 0).all() and (codes[:4] == 0).sum() >= 3


@pytest.mark.parametrize("lane", range(len(WIDE_SEEDS)))
def test_device_and_host_agree_on_a_wide_row_lane(swept_wide, lane):
    """The one-hot batch over 17-word rows against its scatter lane and
    the host oracle."""
    _device_and_host_agree(swept_wide, lane)


@pytest.mark.parametrize("lane", range(len(WIDE_SEEDS)))
def test_the_plain_reference_agrees_on_a_wide_row_lane(swept_wide, lane):
    _the_plain_reference_agrees(swept_wide, lane)


def test_the_reference_without_the_bug_parts_from_the_program(swept):
    """The control: replayed by the unmodified protocol's rules, a lane in
    which the bug fired is refused or judged otherwise."""
    parted = 0
    for lane in range(24, len(SEEDS)):
        single, host, _rows = lifted(swept, lane)
        try:
            ref = vsr_reference.replay(
                5, L, np.asarray(single.trace).tolist(), int(single.trace_len),
                bug=None,
            )
        except vsr_reference.Diverged:
            parted += 1
            continue
        parted += ref.code != host.violation.code
    assert parted >= 6


def test_the_reference_is_plain():
    with open(vsr_reference.__file__, encoding="utf-8") as f:
        source = f.read()
    code = source.split('"""')[2]
    assert "import jax" not in code and "demi_tpu" not in code
    assert "numpy" not in code


# -- (c) the unmodified protocol is clean; each seeded bug is found ---------

def sweep_window(nodes, bug, seeds):
    app, cfg, fuzzer = build_workload(workload(nodes, bug))
    gen = lambda s: fuzzer.generate_fuzz_test(seed=s)  # noqa: E731
    driver = SweepDriver(app, cfg, gen)
    found = []
    driver.violation_hook = lambda s, c: found.extend(
        zip(np.asarray(s).tolist(), np.asarray(c).tolist())
    )
    chunk = driver.run_chunk(seeds)
    return app, cfg, gen, chunk, dict(found)


@pytest.mark.parametrize("nodes", [3, 5])
def test_with_at_most_f_kills_the_unmodified_protocol_is_clean(nodes):
    """On the windows in which the seeded bugs are found, and the lanes
    of (b)."""
    windows = list(range(64)) + list(range(160, 192)) + SEEDS[24:]
    _app, _cfg, _gen, chunk, found = sweep_window(nodes, None, windows)
    assert chunk.lanes == len(windows) and chunk.overflow_lanes == 0
    assert chunk.violations == 0 and not found


@pytest.mark.parametrize("nodes,bug,window,known", [
    (5, "recover_any", range(32, 64), (45, 60)),
    (3, "recover_any", range(160, 192), (164, 169, 176, 190)),
    (3, "dvc_by_opnum", range(0, 32), (12,)),
])
def test_a_seeded_bug_is_found_by_a_small_sweep_and_lifts(
    nodes, bug, window, known
):
    from demi_tpu.runner import lift_lane_to_host

    app, cfg, gen, chunk, found = sweep_window(nodes, bug, window)
    assert chunk.overflow_lanes == 0
    assert set(known) <= set(found) and chunk.violations == len(found)
    seed = known[0]
    progs = stack_programs([lower_program(app, cfg, gen(seed))])
    keys = jax.vmap(lane_key)(np.asarray([seed], np.uint32))
    single, host = lift_lane_to_host(app, cfg, progs, keys, 0)
    assert host.violation is not None
    assert int(single.violation) == host.violation.code == found[seed]


# -- (d) the normal path ------------------------------------------------------

def test_the_cli_builders_take_the_app():
    import argparse

    from demi_tpu import cli
    from demi_tpu.parallel.distributed import workload_args

    args = workload_args(workload())
    app = cli.build_app(args)
    assert (app.num_actors, app.msg_width) == (5, 5 + L)
    prog = cli.build_fuzzer(app, args).generate_fuzz_test(seed=3)
    assert prog.lowerable
    sends = [p for _at, p in prog.payloads]
    # the k-th send of a program is REQUEST(k)
    assert sends and [p[1] for p in sends] == list(range(1, len(sends) + 1))
    assert all(p[0] == vsr.T_REQUEST and len(p) == 5 + L for p in sends)
    with pytest.raises(SystemExit, match="vsr"):
        cli.build_app(argparse.Namespace(**{**vars(args), "app": "nope"}))


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_a_fuzzed_program_lowers_the_same_from_rows_and_from_events(seed):
    app, cfg, fuzzer = build_workload(workload())
    prog = fuzzer.generate_fuzz_test(seed=seed)
    assert prog.lowerable
    rows = lower_program(app, cfg, prog)
    events = lower_program(
        app, cfg, list(fuzzer.generate_fuzz_test(seed=seed))
    )
    for x, y in zip(rows, events):
        np.testing.assert_array_equal(x, y)
