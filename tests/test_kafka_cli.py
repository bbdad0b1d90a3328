"""``--app kafka`` on the normal path (PR 52; the app's own rules are
``test_kafka_app.py``, whose small workload this file takes):
``cli.build_app`` / ``build_fuzzer``; ``sweep`` finds
the seeded bug; ``dpor`` REFUSES the app by ``FIFO_REFUSAL`` (its links keep
per-pair order, which DPOR's reordering does not: ROADMAP B-I.13);
``minimize`` and ``fuzz`` run it; the fixed protocol (KIP-101 with KIP-279's
reply) violates in no lane of some hundreds; PR 42's producer processes make
the operator's programs in seed order."""

import os

import jax
import numpy as np
import pytest

from demi_tpu.apps import kafka as kf
from demi_tpu.device.encoding import lower_program, stack_programs
from demi_tpu.parallel.distributed import build_workload
from demi_tpu.parallel.sweep import SweepDriver

from test_kafka_app import BUG, L, N, lane_key, workload


def flags(bug=BUG):
    return [
        "--app", "kafka", "--nodes", str(N), "--log-cap", str(L), "--num-events", "48", "--max-messages", "256",
        "--timer-weight", "0.3", "--send-weight", "0.4",
        "--wait-weight", "0.28", "--hard-kill-weight", "0.08",
        "--restart-weight", "0.12", "--partition-weight", "0.04",
        "--kill-weight", "0", "--max-kills", "4", "--wait-budget", "1", "40",
    ] + (["--bug", bug] if bug else [])


def _last_json(capsys):
    import json

    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_cli_builders_take_the_app():
    import argparse

    from demi_tpu import cli
    from demi_tpu.parallel.distributed import workload_args

    args = workload_args(workload())
    app = cli.build_app(args)
    assert (app.num_actors, app.msg_width, app.max_outbox) == (5, 50, 16)
    assert app.state_width == kf.state_layout(N, L)["width"][0] == 280
    fuzzer = cli.build_fuzzer(app, args)
    assert isinstance(fuzzer.message_gen, kf.ProduceOperator)
    assert fuzzer.unkillable == {"k4"}
    prog = fuzzer.generate_fuzz_test(seed=3)
    assert prog.lowerable
    sends = [p for _at, p in prog.payloads]
    assert {p[0] for p in sends} == {kf.T_PRODUCE}
    assert all(len(p) == 50 and 0 <= p[1] < N for p in sends)
    default = cli.build_app(argparse.Namespace(
        **{**vars(args), "nodes": 6, "log_cap": 24}
    ))
    assert (default.state_width, default.msg_width, default.max_outbox) == (
        421, 50, 19
    )
    with pytest.raises(SystemExit, match="chain, kafka, paxos"):
        cli.build_app(argparse.Namespace(**{**vars(args), "app": "nope"}))
    with pytest.raises(SystemExit, match="--app kafka: .*3..8 brokers"):
        cli.build_app(argparse.Namespace(**{**vars(args), "nodes": 3}))


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


@pytest.fixture(scope="module")
def swept_cli():
    """One ``sweep`` through ``cli.main`` at 1,024 deliveries: what it
    printed."""
    import contextlib
    import io
    import json

    from demi_tpu.cli import main

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = main(
            ["sweep"] + flags() + ["--pool", "128", "--batch", "256",
                                   "--max-messages", "1024",
                                   "--num-events", "96"]
        )
    return rc, json.loads(out.getvalue().strip().splitlines()[-1])


def test_sweep_on_the_cli_finds_the_seeded_bug(swept_cli):
    rc, told = swept_cli
    assert rc in (0, 1)
    assert told["lanes"] == 256 and len(told["violating_seeds"]) >= 1
    assert {code for _seed, code in told["violating_seeds"]} <= {1, 2}
    assert told["overflow_lanes"] == told["unfinished_lanes"] == 0


def test_dpor_on_the_cli_refuses_it_by_name():
    """Its links keep per-pair order; DPOR's reorderings do not, so the
    verb refuses the app with ``FIFO_REFUSAL`` before it builds anything
    (ROADMAP B-I.13: DPOR and minimize over FIFO channels)."""
    from demi_tpu.cli import main
    from demi_tpu.device.dpor_sweep import FIFO_REFUSAL

    with pytest.raises(SystemExit) as refusal:
        main(["dpor"] + flags() + ["--pool", "128", "--batch", "8",
                                   "--rounds", "2"])
    assert FIFO_REFUSAL in str(refusal.value)


def test_fuzz_on_the_cli_runs_it_under_fifo_order(capsys):
    from demi_tpu.cli import main

    rc = main(["fuzz"] + flags(None) + ["--max-executions", "3"])
    out = capsys.readouterr().out
    assert rc in (0, 1) and out.strip()


def test_minimize_on_the_cli_runs_it(tmp_path, capsys, swept_cli):
    """A violating lane of the sweep, lifted to the host and saved as an
    experiment, goes through ``minimize`` (under a stage budget)."""
    from demi_tpu.cli import main
    from demi_tpu.runner import lift_lane_to_host
    from demi_tpu.serialization import ExperimentSerializer

    _rc, told = swept_cli
    seed, code = told["violating_seeds"][0]
    app, cfg, fuzzer = build_workload(
        workload(BUG, max_messages=1024, num_events=96)
    )
    prog = fuzzer.generate_fuzz_test(seed=seed)
    progs = stack_programs([lower_program(app, cfg, prog)])
    keys = jax.vmap(lane_key)(np.asarray([seed], np.uint32))
    _single, host = lift_lane_to_host(app, cfg, progs, keys, 0)
    assert host.violation.code == code
    ExperimentSerializer.save(
        str(tmp_path), list(prog), host.trace, host.violation,
        app_name="kafka",
    )
    rc = main(
        ["minimize"] + flags() + ["--max-messages", "1024", "--num-events",
                                  "96", "-e", str(tmp_path),
                                  "--stage-budget", "2"]
    )
    told = _last_json(capsys)
    assert rc == 0 and told["externals"] >= N
    assert told["minimized_deliveries"] <= told["deliveries"]


@pytest.mark.skipif(not hasattr(os, "fork"), reason="producers need os.fork")
def test_producer_processes_make_the_operators_programs_in_seed_order():
    """PR 42's test with this generator: the operator hears of every
    fault between two sends (``note_fault``), so a program is a function
    of its seed only because ``generate_fuzz_test`` resets it; forked
    producers then make, in seed order, the programs the host thread
    would: the same bytes handed to every segment, the same violating
    seeds, the same digest."""
    from test_continuous_producers import BATCH, LANES, _Sweeper

    sweeper = _Sweeper(build_workload(workload(BUG)))
    want = sweeper.sweep(0)
    assert sweeper.calls == list(range(LANES))
    got = sweeper.sweep(2)
    assert sweeper.calls == list(range(BATCH))   # the probe; children the rest
    assert got == want and len(want["handed"]) > 12
    with pytest.raises(ChildProcessError):
        os.waitpid(-1, os.WNOHANG)


def test_the_fixed_protocol_violates_in_no_lane_of_some_hundreds():
    app, cfg, fuzzer = build_workload(
        workload(None, max_messages=1024, num_events=96)
    )
    driver = SweepDriver(app, cfg, lambda s: fuzzer.generate_fuzz_test(seed=s))
    result = driver.sweep(768, 256)
    assert result.lanes == 768 and result.overflow_lanes == 0
    assert result.violations == 0 and result.unfinished_lanes == 0
