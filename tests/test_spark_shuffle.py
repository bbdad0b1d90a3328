"""The Spark DAGScheduler fixture at a shuffle's width, on the normal
path, at small size on the CPU (the deployment is
``benchmarks/configs/spark17-shuffle200.json`` cut to 4 executors and 3
stages of 40 tasks, so a stage's mask crosses a word): task masks of
several words, ``SubmitJob`` addressed to the driver on both draw paths,
``stages``/``tasks`` as workload keys of the one builder, executor loss
judged alike by the device and the host oracle, and device lane, host
oracle and the plain reference (``benchmarks/lib/dag_reference.py``: sets
of (stage, task), no JAX) agreeing lane for lane."""

import argparse
import dataclasses
import hashlib
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from demi_tpu import cli
from demi_tpu.apps.common import (
    DSLSendGenerator, dsl_start_events, make_host_invariant,
)
from demi_tpu.apps.spark_dag import (
    CUR, DONE_FLAG, MASKS, T_DONE, T_LAUNCH, T_SUBMIT, make_spark_app,
    mask_words, spark_send_generator,
)
from demi_tpu.config import SchedulerConfig
from demi_tpu.device import DeviceConfig, make_explore_kernel
from demi_tpu.device.core import OP_SEND, ST_DONE, ST_VIOLATION
from demi_tpu.device.encoding import (
    device_trace_to_guide, lower_program, stack_programs,
)
from demi_tpu.external_events import (
    HardKill, Kill, MessageConstructor, Send, Start, WaitQuiescence,
)
from demi_tpu.parallel.distributed import DEFAULT_WORKLOAD, build_workload
from demi_tpu.schedulers.guided import GuidedScheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module   # dataclasses look their module up
    spec.loader.exec_module(module)
    return module


dag_reference = _load("benchmarks/lib/dag_reference.py", "dag_reference")

STAGES, TASKS, EXECUTORS = 3, 40, 4
DAG5 = {
    "app": "spark", "nodes": 1 + EXECUTORS, "stages": STAGES, "tasks": TASKS,
    "bug": None, "seed": 0, "num_events": 8, "max_sends": 1,
    "max_messages": 512, "pool": 256, "send_weight": 0.5, "wait_weight": 0.2,
    "wait_budget": [20, 160], "kill_weight": 0.05, "hard_kill_weight": 0.25,
    "restart_weight": 0.3, "partition_weight": 0.0, "max_kills": 3,
    "timer_weight": 0.2,
}
# Fuzz seeds: the first forty, and eight of the 25 in the first 1,024 whose
# job ends with credited work nobody holds (about one schedule in forty).
SEEDS = list(range(40)) + [62, 128, 149, 162, 188, 236, 258, 304]
LANES = len(SEEDS)
WHOLE_JOB = 1 + STAGES * 4 * TASKS   # the submit; a launch and a done a copy


def _sets(words, tasks=TASKS, stages=STAGES):
    """The (stage, task) set a node's mask words stand for."""
    w = mask_words(tasks)
    return {
        (s, 32 * k + bit)
        for s in range(stages) for k in range(w) for bit in range(32)
        if (int(words[MASKS + s * w + k]) >> bit) & 1
    }


# -- (a) masks wider than a word -------------------------------------------

@pytest.mark.parametrize("tasks,words", [
    (1, 1), (4, 1), (31, 1), (32, 1), (33, 2), (40, 2), (64, 2), (200, 7),
])
def test_a_stage_takes_as_many_words_as_its_tasks_need(tasks, words):
    assert mask_words(tasks) == words
    app = make_spark_app(num_workers=2, num_stages=3, tasks_per_stage=tasks)
    assert app.state_width == MASKS + 3 * words
    assert app.max_outbox == 2 * tasks + 1


@pytest.mark.parametrize("task", [0, 31, 32, 39])
@pytest.mark.parametrize("stage", [0, 2])
def test_a_task_on_either_side_of_the_word_boundary(stage, task):
    """An executor's launch and the driver's credit set exactly the bit
    of (stage, task): word ``task // 32`` of the stage, bit ``task % 32``."""
    app = make_spark_app(EXECUTORS, STAGES, TASKS)
    zero = jnp.zeros(app.state_width, jnp.int32)
    at = MASKS + stage * 2 + task // 32
    want = np.zeros(app.state_width, np.int64)
    want[at] = 1 << (task % 32)
    worker, out = app.handler(
        jnp.int32(2), zero, jnp.int32(0),
        jnp.asarray([T_LAUNCH, stage, task], jnp.int32),
    )
    np.testing.assert_array_equal(
        np.asarray(worker).astype(np.uint32), want.astype(np.uint32)
    )
    out = np.asarray(out)
    assert out[0].tolist() == [1, 0, T_DONE, stage, task]
    assert not out[1:, 0].any()
    driver, out = app.handler(
        jnp.int32(0), zero.at[CUR].set(stage), jnp.int32(2),
        jnp.asarray([T_DONE, stage, task], jnp.int32),
    )
    want[CUR] = stage
    np.testing.assert_array_equal(
        np.asarray(driver).astype(np.uint32), want.astype(np.uint32)
    )
    assert not np.asarray(out)[:, 0].any()
    assert _sets(np.asarray(driver)) == {(stage, task)}


@pytest.mark.parametrize("tasks", [31, 32, 40])
def test_a_stage_completes_on_its_last_task_and_not_before(tasks):
    app = make_spark_app(EXECUTORS, 2, tasks)
    state = jnp.zeros(app.state_width, jnp.int32)
    step = jax.jit(app.handler)
    order = list(range(tasks))
    order.remove(tasks - 9)
    order.append(tasks - 9)   # the last to come in is mid-word
    for t in order:
        assert int(state[CUR]) == 0
        state, out = step(
            jnp.int32(0), state, jnp.int32(1),
            jnp.asarray([T_DONE, 0, t], jnp.int32),
        )
    assert int(state[CUR]) == 1 and int(state[DONE_FLAG]) == 0
    out = np.asarray(out)
    assert out[:, 0].sum() == 2 * tasks          # stage 1, both copies
    assert set(out[: 2 * tasks, 2]) == {T_LAUNCH} and set(out[: 2 * tasks, 3]) == {1}
    assert sorted(out[: 2 * tasks, 4]) == sorted(list(range(tasks)) * 2)
    # every task's two copies go to two executors
    for t in range(tasks):
        assert len(set(out[out[:, 4] == t][:2, 1])) == 2
    assert _sets(np.asarray(state), tasks, 2) == {(0, t) for t in range(tasks)}


# blake2/sha over status, violation, deliveries, sched_hash of 64 lanes of
# the 2 x 4 job (one word a stage), recorded at the parent commit 8f7f33c.
PARENT_2X4 = {
    None: ("787ca2a0ef09273d", 0),
    "stale_task": ("4e92d11920f7bc1b", 8),
}


def _job_program(app, *faults):
    return dsl_start_events(app) + [
        Send(app.actor_name(0), MessageConstructor(lambda: (T_SUBMIT, 0, 0))),
        WaitQuiescence(budget=9), *faults, WaitQuiescence(),
    ]


@pytest.mark.parametrize("bug", [None, "stale_task"])
def test_up_to_31_tasks_the_layout_and_the_verdicts_are_the_parents(bug):
    app = make_spark_app(num_workers=3, num_stages=2, tasks_per_stage=4, bug=bug)
    assert app.state_width == 4 and app.max_outbox == 9
    cfg = DeviceConfig.for_app(
        app, pool_capacity=64, max_steps=96, max_external_ops=12,
        invariant_interval=1,
    )
    progs = stack_programs([lower_program(app, cfg, _job_program(app))] * 64)
    res = jax.device_get(make_explore_kernel(app, cfg)(
        progs, jax.random.split(jax.random.PRNGKey(5), 64)
    ))
    h = hashlib.sha256()
    for x in (res.status, res.violation, res.deliveries, res.sched_hash):
        h.update(np.asarray(x).tobytes())
    assert (h.hexdigest()[:16], int((res.violation != 0).sum())) == PARENT_2X4[bug]


# -- (b) an executor that is down holds nothing, in both tiers -------------

def test_lost_executors_are_judged_alike_on_the_device_and_the_host():
    """Executor 2 is hard-killed and restarted (its executed set is gone),
    executor 3 is isolated: what only those two held is credited work
    nobody holds. At the parent commit the device read a dead executor's
    last state and called all 64 of these lanes clean, the host oracle
    11 of them violating."""
    from helpers import lift_lane_to_host

    app = make_spark_app(num_workers=3, num_stages=2, tasks_per_stage=4)
    cfg = DeviceConfig.for_app(
        app, pool_capacity=64, max_steps=96, max_external_ops=12,
        invariant_interval=1,
    )
    starts = dsl_start_events(app)
    program = _job_program(
        app, HardKill(app.actor_name(2)), WaitQuiescence(budget=4),
        Start(app.actor_name(2), ctor=starts[2].ctor), Kill(app.actor_name(3)),
    )
    progs = stack_programs([lower_program(app, cfg, program)] * 64)
    keys = jax.random.split(jax.random.PRNGKey(5), 64)
    res = jax.device_get(make_explore_kernel(app, cfg)(progs, keys))
    codes = np.asarray(res.violation)
    assert int((codes != 0).sum()) == 11
    for lane in list(np.flatnonzero(codes)[:3]) + list(np.flatnonzero(codes == 0)[:3]):
        single, host = lift_lane_to_host(app, cfg, progs, keys, int(lane))
        host_code = host.violation.code if host.violation is not None else 0
        assert int(single.violation) == int(codes[lane]) == host_code


# -- (c) SubmitJob names the driver, with the draws it made ----------------

@pytest.mark.parametrize("mix", ["cell", "defaults"])
def test_every_submit_names_the_driver_on_both_draw_paths(mix):
    workload = dict(DAG5) if mix == "cell" else {
        "app": "spark", "nodes": 5, "num_events": 12,
    }
    app, cfg, fuzzer = build_workload(workload)
    anywhere = build_workload(workload)[2]
    anywhere.message_gen.target = None   # the generator as it was
    assert type(fuzzer.message_gen) is DSLSendGenerator
    assert fuzzer.message_gen.target == app.actor_name(0)
    submits = moved = 0
    for seed in range(96):
        prog = fuzzer.generate_fuzz_test(seed=seed)
        assert prog.lowerable
        rows = lower_program(app, cfg, prog)
        events = lower_program(
            app, cfg, list(fuzzer.generate_fuzz_test(seed=seed))
        )
        for x, y in zip(rows, events):
            np.testing.assert_array_equal(x, y)
        sends = [e for e in prog if isinstance(e, Send)]
        assert all(e.name == app.actor_name(0) for e in sends)
        assert len(sends) <= 1
        submits += len(sends)
        # Equal draws: up to where a futile submit (the driver down) let
        # the old generator send to somebody else, the program is the
        # old one with the addressee changed.
        old = anywhere.generate_fuzz_test(seed=seed)
        if len(old.kind) == len(prog.kind) and old.kind == prog.kind:
            assert old.b == prog.b
            assert [
                a for k, a in zip(old.kind, old.a) if k != OP_SEND
            ] == [a for k, a in zip(prog.kind, prog.a) if k != OP_SEND]
        else:
            moved += 1
            down = next(
                i for i, (x, y) in enumerate(zip(old.kind, prog.kind)) if x != y
            )
            assert old.kind[down] == OP_SEND   # the driver was down here
    assert submits >= 80 and moved <= 12


def test_a_submit_drawn_while_the_driver_is_down_is_futile_and_not_counted():
    import random

    app = make_spark_app(3)
    gen = spark_send_generator(app)
    workers = [app.actor_name(i) for i in (1, 2, 3)]
    rng = random.Random(3)
    state = rng.getstate()
    assert gen.generate_row(rng, workers) is None
    spent = rng.getstate()
    # ... the draw a random addressee took was made all the same,
    rng.setstate(state)
    rng.choice(workers)
    assert rng.getstate() == spent
    # ... and the one job a program may hold is still to be had.
    assert gen.generate_row(rng, workers + [app.actor_name(0)]) == (
        app.actor_name(0), (T_SUBMIT, 0, 0)
    )
    assert gen.generate_row(rng, workers + [app.actor_name(0)]) is None


# -- (d) stages and tasks are workload keys --------------------------------

def test_stages_and_tasks_are_shared_workload_defaults():
    assert DEFAULT_WORKLOAD["stages"] == 2 and DEFAULT_WORKLOAD["tasks"] == 4
    app, cfg, _ = build_workload({"app": "spark", "nodes": 4})
    assert (app.state_width, app.max_outbox) == (4, 9)
    # the other apps ignore them, as they ignore log_cap
    plain = build_workload({"app": "raft", "nodes": 3})[1]
    assert build_workload(
        {"app": "raft", "nodes": 3, "stages": 4, "tasks": 200}
    )[1] == plain


def _parse(*argv):
    """The sweep verb's namespace for ``argv``, without running it."""
    seen = {}
    real = cli.cmd_sweep
    cli.cmd_sweep = lambda args: seen.update(args=args) or 0
    try:
        assert cli.main(["sweep", *argv]) == 0
    finally:
        cli.cmd_sweep = real
    return seen["args"]


def test_the_flags_survive_the_workload_dict_into_the_builder():
    args = _parse(
        "--app", "spark", "--nodes", "17", "--stages", "4", "--tasks", "200",
        "--pool", "1024", "--max-messages", "3328", "--num-events", "8",
    )
    workload = cli._workload_dict(args)
    assert (workload["stages"], workload["tasks"]) == (4, 200)
    app, cfg, _ = build_workload(json.loads(json.dumps(workload)))
    assert dataclasses.asdict(cfg) == dataclasses.asdict(
        DeviceConfig.for_workload(cli.build_app(args), args)
    )
    # the deployment's shapes, as its configuration file states them
    with open(os.path.join(
        ROOT, "benchmarks", "configs", "spark17-shuffle200.json"
    )) as f:
        shapes = json.load(f)["shapes"]
    have = dataclasses.asdict(cfg)
    assert {k: have[k] for k in shapes} == shapes
    assert (cfg.state_width, cfg.max_outbox) == (2 + 7 * 4, 401)


def test_the_default_flags_build_the_job_they_built():
    args = _parse("--app", "spark", "--nodes", "4")
    assert (args.stages, args.tasks) == (2, 4)
    assert cli.build_app(args).state_width == 4


def test_the_tuning_cache_tells_a_shuffle_from_the_toy():
    toy = _parse("--app", "spark", "--nodes", "17")
    wide = _parse("--app", "spark", "--nodes", "17", "--stages", "4",
                  "--tasks", "200")
    assert cli._workload_discriminator(toy) != cli._workload_discriminator(wide)
    assert cli._workload_discriminator(wide) == {"workload": "spark:none:4x200"}
    raft = _parse("--app", "raft", "--nodes", "3", "--bug", "multivote")
    assert cli._workload_discriminator(raft) == {"workload": "raft:multivote"}


def test_a_resumed_manifest_from_before_the_flags_means_the_toy():
    ns = argparse.Namespace(**{**cli.FAULT_PLANE_DEFAULTS, "app": "spark",
                               "nodes": 4, "bug": None})
    assert cli.build_app(ns).max_outbox == 9


# -- (e) device, host oracle and the plain reference, lane for lane --------

@pytest.fixture(scope="module")
def dag():
    app, cfg, fuzzer = build_workload(dict(DAG5))
    # with the creation links, as the on-chip tool re-runs a lane
    traced = dataclasses.replace(cfg, record_trace=True, record_parents=True)
    progs = stack_programs([
        lower_program(app, cfg, fuzzer.generate_fuzz_test(seed=s))
        for s in SEEDS
    ])
    keys = jax.vmap(lambda s: jax.random.fold_in(jax.random.PRNGKey(0), s))(
        np.asarray(SEEDS, np.uint32)
    )
    plain = make_explore_kernel(app, cfg)(progs, keys)
    res = make_explore_kernel(app, traced)(progs, keys)
    return app, cfg, fuzzer, jax.device_get(plain), jax.device_get(res)


def test_the_seeded_programs_cover_the_fault_plane_and_whole_jobs(dag):
    app, cfg, fuzzer, plain, res = dag
    assert (cfg.state_width, cfg.max_outbox) == (2 + 2 * STAGES, 2 * TASKS + 1)
    kinds = set()
    for s in SEEDS:
        kinds |= {type(e).__name__ for e in fuzzer.generate_fuzz_test(seed=s)}
    assert {"Kill", "HardKill", "Start", "Send", "WaitQuiescence"} <= kinds
    status = np.asarray(plain.status)
    assert set(status.tolist()) <= {ST_DONE, ST_VIOLATION}
    assert int((status == ST_VIOLATION).sum()) == 8
    # some lanes run the whole job: every launch and every done delivered
    assert int((np.asarray(plain.deliveries) == WHOLE_JOB).sum()) >= 3
    np.testing.assert_array_equal(plain.sched_hash, res.sched_hash)
    np.testing.assert_array_equal(plain.violation, res.violation)


@pytest.mark.parametrize("lane", range(LANES))
def test_device_host_oracle_and_plain_reference_agree(dag, lane):
    app, cfg, fuzzer, plain, res = dag
    code = int(plain.violation[lane])
    records, length = np.asarray(res.trace[lane]), int(res.trace_len[lane])
    sched = GuidedScheduler(
        SchedulerConfig(invariant_check=make_host_invariant(app)), app
    )
    host = sched.execute_guide(device_trace_to_guide(app, records, length))
    assert (host.violation.code if host.violation is not None else 0) == code
    ref = dag_reference.replay(app.num_actors, STAGES, TASKS, records, length)
    assert ref.code == code and (ref.quiescent or code)
    # the verdict's step: a violating lane stops at the delivery that broke it
    assert ref.step == ref.deliveries == int(plain.deliveries[lane]) == host.deliveries
    for i in range(app.num_actors):
        actor = sched.system.actors.get(app.actor_name(i))
        if actor is None or app.actor_name(i) in sched.system.crashed:
            assert not ref.alive[i]
            continue
        state = np.asarray(actor.state)
        if i == 0:
            assert (int(state[CUR]), bool(state[DONE_FLAG])) == (ref.stage, ref.done)
            assert _sets(state) == ref.credited, lane
        else:
            assert _sets(state) == ref.executed[i], (lane, i)
    if code:
        # Credit nobody holds needs an executor that went down.
        events = list(fuzzer.generate_fuzz_test(seed=SEEDS[lane]))
        assert any(isinstance(e, (Kill, HardKill)) for e in events)
        assert ref.done


def test_the_reference_refuses_a_trace_that_is_not_the_protocols(dag):
    app, cfg, fuzzer, plain, res = dag
    lane = int(np.argmax(np.asarray(plain.deliveries)))
    records = np.array(res.trace[lane])
    length = int(res.trace_len[lane])
    first = next(
        i for i in range(length)
        if records[i][0] == 1 and records[i][3] == T_LAUNCH
    )
    records[first][5] = (records[first][5] + 1) % TASKS   # another task's launch,
    records[first][2] = 1 + (records[first][2] % EXECUTORS)  # at the wrong executor
    with pytest.raises(dag_reference.Diverged):
        dag_reference.replay(app.num_actors, STAGES, TASKS, records, length)


def test_the_reference_without_its_epoch_check_parts_from_the_device(dag):
    """The control: a reference that credits late duplicates to the
    current stage (the ``stale_task`` bug) must not pass for the
    protocol's."""
    app, cfg, fuzzer, plain, res = dag
    parted = 0
    for lane in range(LANES):
        records, length = np.asarray(res.trace[lane]), int(res.trace_len[lane])
        try:
            ref = dag_reference.replay(
                app.num_actors, STAGES, TASKS, records, length,
                epoch_check=False,
            )
        except dag_reference.Diverged:
            parted += 1
            continue
        parted += (
            ref.code != int(plain.violation[lane])
            or ref.step != int(plain.deliveries[lane])
        )
    assert parted >= 1


# -- (f) the seeded bug, at the wider masks --------------------------------

# Of the first 512 fuzz seeds of DAG5, the two whose job is clean under the
# protocol and ends with phantom credit under ``stale_task`` (a late
# duplicate credits a task of the next stage whose two copies are then
# lost); the nine that violate either way are SEEDS' and others.
STALE_SEEDS = [231, 495]


def _run_seeds(workload, seeds):
    app, cfg, fuzzer = build_workload(workload)
    traced = dataclasses.replace(cfg, record_trace=True, record_parents=True)
    progs = stack_programs([
        lower_program(app, cfg, fuzzer.generate_fuzz_test(seed=s)) for s in seeds
    ])
    keys = jax.vmap(lambda s: jax.random.fold_in(jax.random.PRNGKey(0), s))(
        np.asarray(seeds, np.uint32)
    )
    return app, jax.device_get(make_explore_kernel(app, traced)(progs, keys))


def test_stale_task_is_still_found_and_is_the_reference_without_the_check():
    _, clean = _run_seeds(dict(DAG5), STALE_SEEDS)
    assert not np.asarray(clean.violation).any()
    app, res = _run_seeds(dict(DAG5, bug="stale_task"), STALE_SEEDS)
    assert np.asarray(res.violation).tolist() == [1, 1]
    for lane in range(len(STALE_SEEDS)):
        records, length = np.asarray(res.trace[lane]), int(res.trace_len[lane])
        host = GuidedScheduler(
            SchedulerConfig(invariant_check=make_host_invariant(app)), app
        ).execute_guide(device_trace_to_guide(app, records, length))
        assert host.violation is not None and host.violation.code == 1
        buggy = dag_reference.replay(
            app.num_actors, STAGES, TASKS, records, length, epoch_check=False
        )
        assert buggy.code == 1 and buggy.step == int(res.deliveries[lane])
        # nobody that is up executed what the stale completions credited
        held = set().union(
            *(buggy.executed[i] for i in range(1, app.num_actors) if buggy.alive[i])
        )
        assert buggy.credited - held
        # ... and the protocol's own rules do not explain this lane.
        try:
            right = dag_reference.replay(
                app.num_actors, STAGES, TASKS, records, length
            )
        except dag_reference.Diverged:
            continue
        assert (right.code, right.step) != (1, int(res.deliveries[lane]))


# -- the one-hot insert's short pass over a whole run -------------------------

@pytest.fixture(scope="module")
def short_pass_runs():
    """``{mode: (rows, counts)}``: 48 schedules of 17 actors, 2 stages x
    40 tasks (an 82-row insert: the short pass is built) through the
    continuous driver, 16 resident, in the scatter lowering a CPU takes
    and the one-hot lowering a TPU takes."""
    from demi_tpu import obs
    from demi_tpu.device.continuous import ContinuousSweepDriver

    app, cfg, fuzzer = build_workload(dict(DAG5, nodes=17, stages=2))
    assert (cfg.num_actors, cfg.max_outbox) == (17, 81)
    got = {}
    for mode in ("scatter", "onehot"):
        driver = ContinuousSweepDriver(
            app, dataclasses.replace(cfg, index_mode=mode),
            lambda s: fuzzer.generate_fuzz_test(seed=s),
            batch=16, seg_steps=32, seed_pure=True,
        )
        obs.disable()
        obs.TRACER.clear()
        obs.enable()
        try:
            rows = sorted(driver._run(48))
            counts = obs.stage_counts()
        finally:
            obs.disable()
            obs.TRACER.clear()
        got[mode] = rows, counts
    return got


def test_a_whole_run_is_the_same_on_the_short_pass_as_on_the_scatter_insert(
    short_pass_runs,
):
    """The path a TPU runs, where a step of the resident set takes the
    insert's short pass unless some lane launches a stage, gives every
    lane's status, code and sequence hash, and every pool row entered
    (``seq_counter``, summed at the retire), as the scatter insert does;
    and it counts the steps that took the full pass."""
    lanes = 48
    (rows, counts), (want_rows, want_counts) = (
        short_pass_runs["onehot"], short_pass_runs["scatter"]
    )
    assert rows == want_rows
    assert [r[0] for r in rows] == list(range(lanes))
    assert any(r[2] == 0 and r[1] == 2 for r in rows)  # jobs ran to their end
    assert counts["sweep.rows_inserted"] == want_counts["sweep.rows_inserted"]
    assert counts["sweep.rows_inserted"] > lanes * 2 * 40
    # Most steps send one row or none: the short pass; a launch anywhere in
    # the resident set sends 80 and takes the full one.
    assert 0 < counts["sweep.insert_full_steps"] < counts["sweep.insert_steps"]
    assert counts["sweep.insert_steps"] >= counts["sweep.lane_steps"] // 2
    assert "sweep.insert_steps" not in want_counts


def test_only_the_launching_lanes_take_the_full_pass(short_pass_runs):
    """Of the steps in which some resident lane launches a stage, each
    lane is counted in those where it launched one itself (alone behind
    the batch's short pass: never 9 lanes of 16 in one step here), and
    the rows, violating lanes and sequence hashes are the scatter
    lowering's all the same."""
    (rows, counts), (want_rows, want_counts) = (
        short_pass_runs["onehot"], short_pass_runs["scatter"]
    )
    assert [(r[0], r[2], r[3]) for r in rows] == [
        (r[0], r[2], r[3]) for r in want_rows
    ]
    assert any(r[2] != 0 for r in rows)  # a violating lane among them
    assert (
        0 < counts["sweep.insert_full_lane_steps"]
        < counts["sweep.insert_full_steps"]
    )
    # a lane launches each of its two stages once, or twice after a loss
    assert counts["sweep.insert_full_lane_steps"] >= 48
    assert "sweep.insert_full_lane_steps" not in want_counts
