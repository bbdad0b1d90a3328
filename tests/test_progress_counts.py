"""``DSLApp.progress``: named counts of what a finished schedule's
protocol got done, summed by the continuous sweep over the lanes it
retires as ``sweep.app.<name>``, only while spans are live; an app that
names none gets no kernel and no pull."""

import numpy as np
import pytest

from demi_tpu import obs
from demi_tpu.apps import vsr
from demi_tpu.parallel.distributed import build_workload
from demi_tpu.parallel.sweep import SweepDriver

VSR3 = {
    "app": "vsr", "nodes": 3, "bug": None, "log_cap": 4, "num_events": 24,
    "max_messages": 256, "pool": 96, "timer_weight": 0.05, "send_weight": 0.15,
    "wait_weight": 0.35, "wait_budget": [1, 25], "hard_kill_weight": 0.15,
    "restart_weight": 0.25, "partition_weight": 0.1, "kill_weight": 0.0,
    "max_kills": 1,
}
NAMES = ["views", "recoveries", "recovered", "committed", "log_rows"]


def driver_of(workload):
    app, cfg, fuzzer = build_workload(dict(workload))
    return SweepDriver(app, cfg, lambda s: fuzzer.generate_fuzz_test(seed=s))


def app_counts():
    return {
        k: v for k, v in obs.stage_counts().items() if k.startswith("sweep.app.")
    }


@pytest.fixture
def spans():
    obs.TRACER.clear()
    yield
    obs.disable()
    obs.TRACER.clear()


def test_the_counts_appear_only_while_spans_are_live(spans):
    driver = driver_of(VSR3)
    driver.sweep(48, 16, mode="continuous")
    assert app_counts() == {}
    obs.enable()
    driver.sweep(48, 16, mode="continuous")
    counts = obs.stage_counts()
    assert sorted(app_counts()) == sorted(f"sweep.app.{n}" for n in NAMES)
    assert counts["sweep.retired"] == 48
    # every schedule has some replica in a view past 0 or not; most change one
    assert counts["sweep.app.views"] >= 48
    assert 0 < counts["sweep.app.recovered"] <= counts["sweep.app.recoveries"]
    assert counts["sweep.app.committed"] > 0
    assert 0 < counts["sweep.app.log_rows"] < counts["sweep.rows_inserted"]


def test_the_counts_are_the_retired_lanes_own(spans):
    """Against the functions run over the lanes' final rows by hand."""
    import jax
    import jax.numpy as jnp

    from demi_tpu.device.encoding import lower_program, stack_programs

    app, cfg, fuzzer = build_workload(dict(VSR3))
    gen = lambda s: fuzzer.generate_fuzz_test(seed=s)  # noqa: E731
    driver = SweepDriver(app, cfg, gen)
    obs.enable()
    driver.sweep(16, 16, mode="continuous")
    counts = app_counts()
    drv = driver._continuous_driver(16)
    progs = stack_programs([lower_program(app, cfg, gen(s)) for s in range(16)])
    state = drv.init(drv._vkeys(jnp.arange(16, dtype=jnp.uint32)))
    for steps in range(0, cfg.max_steps, drv.seg_steps):
        state = drv.segment(state, progs, jnp.full(16, steps, jnp.int32))
    rows = np.asarray(jax.device_get(drv.finalize(state)).actor_state)
    assert counts["sweep.app.views"] == rows[:, :, vsr.VIEW].max(axis=1).sum()
    assert counts["sweep.app.recoveries"] == np.maximum(
        rows[:, :, vsr.INCARN] - 1, 0
    ).sum()
    assert counts["sweep.app.log_rows"] == rows[:, :, vsr.LOG_ROWS_SENT].sum()


def test_an_app_with_none_adds_no_pull_and_counts_nothing(spans):
    driver = driver_of({"app": "raft", "nodes": 3, "bug": "multivote",
                        "num_events": 8, "max_messages": 64, "pool": 48})
    assert driver.app.progress == ()
    obs.enable()
    driver.sweep(16, 16, mode="continuous")
    assert driver._continuous_driver(16)._progress is None
    assert app_counts() == {} and obs.stage_counts()["sweep.retired"] == 16
