"""``--app raft_reconfig`` on the normal path (PR 47; the app's own rules
are ``test_raft_reconfig_app.py``, whose small workload this file takes):
``cli.build_app`` / ``build_fuzzer`` and ``--snapshot-every``; ``sweep``
finds the seeded bug; ``dpor`` and ``minimize`` run the app (its channels
keep no order); the fixed protocol violates in no lane of some thousands;
PR 42's producer processes make the operator's programs in seed order."""

import os

import jax
import numpy as np
import pytest

from demi_tpu.apps import raft_reconfig as rr
from demi_tpu.device.encoding import lower_program, stack_programs
from demi_tpu.parallel.distributed import build_workload
from demi_tpu.parallel.sweep import SweepDriver

from test_raft_reconfig_app import BUG, EVERY, L, lane_key, workload


def flags():
    return [
        "--app", "raft_reconfig", "--nodes", "7", "--bug", BUG,
        "--log-cap", str(L), "--snapshot-every", str(EVERY),
        "--num-events", "48", "--max-messages", "256",
        "--timer-weight", "0.1", "--send-weight", "0.5",
        "--wait-weight", "0.28", "--hard-kill-weight", "0.08",
        "--restart-weight", "0.1", "--partition-weight", "0.04",
        "--kill-weight", "0", "--max-kills", "4", "--wait-budget", "1", "40",
    ]


def _last_json(capsys):
    import json

    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_cli_builders_take_the_app():
    import argparse

    from demi_tpu import cli
    from demi_tpu.parallel.distributed import DEFAULT_WORKLOAD, workload_args

    assert DEFAULT_WORKLOAD["snapshot_every"] is None     # half of log_cap
    args = workload_args(workload())
    app = cli.build_app(args)
    assert (app.num_actors, app.msg_width, app.state_width) == (7, 18, 144)
    fuzzer = cli.build_fuzzer(app, args)
    assert isinstance(fuzzer.message_gen, rr.ReconfigOperator)
    prog = fuzzer.generate_fuzz_test(seed=3)
    assert prog.lowerable
    sends = [p for _at, p in prog.payloads]
    assert {p[0] for p in sends} == {rr.T_CLIENT, rr.T_ADMIN}
    assert all(len(p) == 18 for p in sends)
    default = cli.build_app(argparse.Namespace(
        **{**vars(args), "snapshot_every": None, "log_cap": 16}
    ))
    assert default.state_width == rr.state_width(7, 16)
    with pytest.raises(SystemExit, match="raft_reconfig, spark"):
        cli.build_app(argparse.Namespace(**{**vars(args), "app": "nope"}))
    with pytest.raises(SystemExit, match="--app raft_reconfig: .*snapshot"):
        cli.build_app(argparse.Namespace(**{**vars(args), "snapshot_every": 9}))


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


def test_sweep_on_the_cli_finds_the_seeded_bug(capsys):
    from demi_tpu.cli import main

    rc = main(["sweep"] + flags() + ["--pool", "128", "--batch", "192"])
    told = _last_json(capsys)
    assert rc in (0, 1)
    assert told["lanes"] == 192 and told["violating_seeds"] == [[165, 1]]
    assert told["overflow_lanes"] == told["unfinished_lanes"] == 0


def test_dpor_on_the_cli_runs_it(capsys):
    """Its channels keep no order ("any"), so DPOR takes it as it takes
    ``apps/raft.py``; a search of the seeded bugs by DPOR is PERF.md's
    open question, not this test's."""
    from demi_tpu.cli import main

    rc = main(
        ["dpor"] + flags() + ["--pool", "128", "--batch", "8", "--rounds", "2"]
    )
    told = _last_json(capsys)
    assert rc in (0, 1) and told["interleavings"] == 16


def test_minimize_on_the_cli_runs_it(tmp_path, capsys):
    """A violating lane of the sweep, lifted to the host and saved as an
    experiment, goes through ``minimize`` (under a stage budget: the whole
    run takes a minute): the delivery stages shorten it."""
    from demi_tpu.cli import main
    from demi_tpu.runner import lift_lane_to_host
    from demi_tpu.serialization import ExperimentSerializer

    app, cfg, fuzzer = build_workload(workload(BUG))
    prog = fuzzer.generate_fuzz_test(seed=165)
    progs = stack_programs([lower_program(app, cfg, prog)])
    keys = jax.vmap(lane_key)(np.asarray([165], np.uint32))
    _single, host = lift_lane_to_host(app, cfg, progs, keys, 0)
    assert host.violation.code == 1
    ExperimentSerializer.save(
        str(tmp_path), list(prog), host.trace, host.violation,
        app_name="raft_reconfig",
    )
    rc = main(
        ["minimize"] + flags() + ["-e", str(tmp_path), "--stage-budget", "2"]
    )
    told = _last_json(capsys)
    assert rc == 0 and told["externals"] == 56
    assert told["minimized_deliveries"] < told["deliveries"] == 116


def test_a_program_with_restarts_is_cut_into_atoms_whole():
    """``minimize`` over a crash-recovery program: a Start that follows a
    HardKill of the same server (or a second Partition of one link) used
    to take the open one's place in the atomizer, which then lost it."""
    from demi_tpu.minimization.event_dag import UnmodifiedEventDag

    app, _cfg, fuzzer = build_workload(workload(BUG))
    events = list(fuzzer.generate_fuzz_test(seed=165))
    kinds = [type(e).__name__ for e in events]
    assert kinds.count("Start") > 7 and "HardKill" in kinds
    atoms = UnmodifiedEventDag(events).get_atomic_events()
    assert sum(len(a.events) for a in atoms) == len(events)


@pytest.mark.skipif(not hasattr(os, "fork"), reason="producers need os.fork")
def test_producer_processes_make_the_operators_programs_in_seed_order():
    """PR 42's test with this generator: the operator hears of every
    fault between two sends (``note_fault``) and keeps a belief, so a
    program is a function of its seed only because ``generate_fuzz_test``
    resets it; forked producers then make, in seed order, the programs
    the host thread would: the same bytes handed to every segment, the
    same violating seeds, the same digest."""
    from test_continuous_producers import BATCH, LANES, _Sweeper

    sweeper = _Sweeper(build_workload(workload(BUG)))
    want = sweeper.sweep(0)
    assert sweeper.calls == list(range(LANES))
    got = sweeper.sweep(2)
    assert sweeper.calls == list(range(BATCH))   # the probe; children the rest
    assert got == want and len(want["handed"]) > 12
    assert want["violating"] == [(165, 1)]
    with pytest.raises(ChildProcessError):
        os.waitpid(-1, os.WNOHANG)


def test_the_fixed_protocol_violates_in_no_lane_of_some_thousands():
    app, cfg, fuzzer = build_workload(workload(None))
    driver = SweepDriver(app, cfg, lambda s: fuzzer.generate_fuzz_test(seed=s))
    result = driver.sweep(2048, 512)
    assert result.lanes == 2048 and result.overflow_lanes == 0
    assert result.violations == 0 and result.unfinished_lanes == 0
