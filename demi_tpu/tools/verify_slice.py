"""End-to-end verification slice (SURVEY.md §7.4): device fuzz sweep →
violating lane → traced re-run → host lift (GuidedScheduler) → DDMin →
verified MCS.

Run: ``python -m demi_tpu.tools.verify_slice``.
Exits nonzero if any stage fails; prints one status line per stage.

This is the smoke path the verify skill drives; it lives in-repo so it
can't rot (the /tmp copy it replaces went stale after an API change).
"""

from __future__ import annotations

import argparse
import sys


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--lanes", type=int, default=256)
    parser.add_argument(
        "--adapter", action="store_true",
        help="run the external-app slice instead: unmodified asyncio app "
             "-> fuzz -> violation -> gamut-minimize -> strict replay",
    )
    args = parser.parse_args(argv)
    if args.adapter:
        return adapter_slice()

    import jax
    import numpy as np

    from ..apps.common import dsl_start_events, make_host_invariant
    from ..apps.raft import T_CLIENT, make_raft_app
    from ..config import SchedulerConfig
    from ..device import DeviceConfig, make_explore_kernel
    from ..device.core import ST_OVERFLOW, ST_VIOLATION
    from ..device.encoding import lower_program, stack_programs
    from ..external_events import MessageConstructor, Send, WaitQuiescence
    from ..runner import lift_lane_to_host, sts_sched_ddmin

    app = make_raft_app(3, bug="gap_append")
    cfg = DeviceConfig.for_app(
        app, pool_capacity=96, max_steps=224, max_external_ops=16,
        invariant_interval=1, timer_weight=0.05,
    )

    def cmd(node, v):
        return Send(
            app.actor_name(node),
            MessageConstructor(lambda vv=v: (T_CLIENT, 0, vv, 0, 0, 0, 0)),
        )

    program = dsl_start_events(app) + [
        WaitQuiescence(budget=40),
        cmd(0, 10), cmd(1, 11), cmd(2, 12),
        WaitQuiescence(budget=120),
    ]

    B = args.lanes
    kernel = make_explore_kernel(app, cfg)
    progs = stack_programs([lower_program(app, cfg, program)] * B)
    keys = jax.random.split(jax.random.PRNGKey(0), B)
    res = kernel(progs, keys)
    st = np.asarray(res.status)
    assert int((st == ST_OVERFLOW).sum()) == 0, "pool overflow: raise pool_capacity"
    lanes = np.flatnonzero(st == ST_VIOLATION)
    print(f"[1/5] sweep: {len(lanes)} violating of {B} lanes")
    assert len(lanes) > 0, "sweep found no violation"

    lane = int(lanes[0])
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    single, host = lift_lane_to_host(app, cfg, progs, keys, lane, config)
    assert int(single.violation) != 0, "traced re-run disagrees with sweep"
    print(f"[2/5] traced re-run: violation code {int(single.violation)}")
    assert host.violation is not None, "host lift lost the violation"
    print(f"[3/5] host lift: violation code {host.violation.code}")

    # externals=None: minimize over the lifted trace's own externals (the
    # program's objects never executed in this trace — see runner.py).
    mcs, verified = sts_sched_ddmin(config, host.trace, None, host.violation)
    kept = mcs.get_all_events()
    n_orig = len(host.trace.original_externals)
    print(f"[4/5] DDMin: {n_orig} -> {len(kept)} externals")
    assert verified is not None, "MCS failed verification"
    print("[5/5] MCS verified — SLICE OK")
    return 0


def adapter_slice() -> int:
    """External-app slice: the unmodified asyncio UDP-lock fixture under
    fuzz -> phantom-grant violation -> canonical gamut -> strict replay.
    The app-specific pieces (predicate, driver program) come from the
    fixture's integration surface (udp_lock_main.py), shared with
    tests/test_asyncio_adapter.py."""
    import os

    from ..bridge import BridgeSession, bridge_invariant
    from ..config import SchedulerConfig
    from ..runner import FuzzResult, run_the_gamut
    from ..schedulers import RandomScheduler
    from ..schedulers.replay import ReplayScheduler

    repo = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    fixtures = os.path.join(repo, "tests", "fixtures")
    sys.path.insert(0, fixtures)
    from udp_lock_main import make_program, phantom_grant

    launcher = [sys.executable, os.path.join(fixtures, "udp_lock_main.py")]
    env = {"PYTHONPATH": repo + os.pathsep + os.environ.get("PYTHONPATH", "")}

    with BridgeSession(launcher, env=env) as session:
        print(f"[1/4] adapter registered: {', '.join(session.actor_names)}")
        config = SchedulerConfig(
            invariant_check=bridge_invariant(predicate=phantom_grant)
        )
        program = make_program(session)
        found = None
        for seed in range(40):
            r = RandomScheduler(
                config, seed=seed, max_messages=120,
                invariant_check_interval=1, timer_weight=0.4,
            ).execute(program)
            if r.violation is not None:
                found = r
                break
        assert found is not None, "phantom grant never surfaced"
        print(f"[2/4] violation {found.violation} at seed {seed}")
        gamut = run_the_gamut(
            config,
            FuzzResult(program=program, trace=found.trace,
                       violation=found.violation, executions=seed + 1),
        )
        print(
            f"[3/4] gamut: {len(program)} -> {len(gamut.mcs_externals)} "
            f"externals over {len(gamut.stages)} stages"
        )
        assert len(gamut.mcs_externals) < len(program)
        replayed = ReplayScheduler(config).replay(found.trace, program)
        assert replayed.violation is not None
        assert replayed.violation.matches(found.violation)
        print("[4/4] strict replay reproduced — ADAPTER SLICE OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
