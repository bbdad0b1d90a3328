"""Async DPOR pipeline (DEMI_ASYNC_MIN): double-buffered frontier rounds
and window-batched oracle probes stay bit-identical to the synchronous
loop — explored set, frontier order, interleaving counts, and found
records all pinned, with and without prefix forking stacked on top."""

import numpy as np
import pytest

from demi_tpu.apps.common import make_host_invariant
from demi_tpu.config import SchedulerConfig
from demi_tpu.device.dpor_sweep import (
    DeviceDPOR,
    DeviceDPOROracle,
    make_dpor_kernel,
)
from demi_tpu.external_events import MessageConstructor, Send, WaitQuiescence
from demi_tpu.minimization.ddmin import make_dag
from demi_tpu.minimization.incremental_ddmin import IncrementalDDMin
from demi_tpu.minimization.test_oracle import IntViolation

from test_device_dpor import _setup


@pytest.fixture(scope="module")
def reversal():
    """The k=3 reversal app plus ONE jitted scratch kernel and ONE fork
    kernel shared by every DeviceDPOR in this module (each bare
    constructor call would otherwise re-jit an identical closure)."""
    app, cfg, program = _setup(3)
    kernel = make_dpor_kernel(app, cfg)
    fork_kernel = make_dpor_kernel(app, cfg, start_state=True)
    return app, cfg, program, kernel, fork_kernel


def _drain(dpor, target_code=2, max_rounds=6):
    found = dpor.explore(target_code=target_code, max_rounds=max_rounds)
    return found


def test_double_buffer_frontier_parity(reversal):
    """Exhaustive drain (target code never occurs): the double-buffered
    loop's explored set, frontier (order included), and interleaving
    count equal the synchronous loop's, and in-flight launches really
    happened."""
    app, cfg, program, kernel, _ = reversal
    # batch_size 2: a frozen generation spans several rounds, so the
    # remainder is non-empty at dispatch time and in-flight speculation
    # actually fires (one full-batch launch would swallow the whole
    # generation and leave nothing to speculate on).
    sync = DeviceDPOR(
        app, cfg, program, batch_size=2, double_buffer=False, kernel=kernel
    )
    dbuf = DeviceDPOR(
        app, cfg, program, batch_size=2, double_buffer=True, kernel=kernel
    )
    assert _drain(sync, max_rounds=8) is None
    assert _drain(dbuf, max_rounds=8) is None
    assert dbuf.explored == sync.explored
    assert dbuf.frontier == sync.frontier
    assert dbuf.interleavings == sync.interleavings
    stats = dbuf.async_stats
    assert stats["inflight_rounds"] > 0
    # Every dispatched launch lands in exactly one bucket: harvested as
    # the next round (hit) or discarded (waste) — never both.
    assert stats["inflight_hits"] + stats["inflight_waste"] == stats[
        "inflight_rounds"
    ]
    assert sync.async_stats["inflight_rounds"] == 0


def test_double_buffer_find_parity(reversal):
    """Violation search: both loops find the SAME violating lane —
    records byte-identical — after the same number of interleavings."""
    app, cfg, program, kernel, _ = reversal
    sync = DeviceDPOR(
        app, cfg, program, batch_size=8, double_buffer=False, kernel=kernel
    )
    dbuf = DeviceDPOR(
        app, cfg, program, batch_size=8, double_buffer=True, kernel=kernel
    )
    fs = sync.explore(target_code=1, max_rounds=30)
    fd = dbuf.explore(target_code=1, max_rounds=30)
    assert fs is not None and fd is not None
    recs_s, n_s = fs
    recs_d, n_d = fd
    assert n_s == n_d
    assert np.array_equal(recs_s, recs_d)
    assert dbuf.interleavings == sync.interleavings
    assert dbuf.explored == sync.explored


def test_double_buffer_parity_with_prefix_fork(reversal):
    """The full async stack — double-buffered rounds over prescribed
    fork groups (min_group lowered so the small sibling groups actually
    fork) — still matches the synchronous scratch loop bit for bit."""
    app, cfg, program, kernel, fork_kernel = reversal
    sync = DeviceDPOR(
        app, cfg, program, batch_size=2, double_buffer=False, kernel=kernel
    )
    stack = DeviceDPOR(
        app, cfg, program, batch_size=2, double_buffer=True,
        prefix_fork=True, fork_min_group=2, kernel=kernel,
        fork_kernel=fork_kernel,
    )
    assert _drain(sync, max_rounds=8) is None
    assert _drain(stack, max_rounds=8) is None
    assert stack.explored == sync.explored
    assert stack.frontier == sync.frontier
    assert stack.interleavings == sync.interleavings
    fs = DeviceDPOR(
        app, cfg, program, batch_size=8, double_buffer=True,
        prefix_fork=True, fork_min_group=2, kernel=kernel,
        fork_kernel=fork_kernel,
    ).explore(target_code=1, max_rounds=30)
    fr = DeviceDPOR(
        app, cfg, program, batch_size=8, double_buffer=False, kernel=kernel
    ).explore(target_code=1, max_rounds=30)
    assert fs is not None and fr is not None
    assert fs[1] == fr[1]
    assert np.array_equal(fs[0], fr[0])


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_oracle_told_async_min_hands_its_instances_the_answer(
    reversal, monkeypatch, platform
):
    """An oracle told ``async_min=True`` decides its instances'
    in-flight rounds by the rule a bare DeviceDPOR applies to the
    variable (on where host and device are disjoint, off on a CPU) and
    hands the boolean down: no variable is set, and an explicit
    ``double_buffer`` still wins."""
    import types

    from demi_tpu.device import dpor_sweep

    app, cfg, program, _kernel, _ = reversal
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    monkeypatch.delenv("DEMI_ASYNC_MIN", raising=False)
    monkeypatch.setattr(
        dpor_sweep.jax, "devices",
        lambda *a, **kw: [types.SimpleNamespace(platform=platform)],
    )
    want = platform != "cpu"
    told = DeviceDPOROracle(app, cfg, config, batch_size=4, async_min=True)
    assert told.supports_async
    assert told._instance(program)._double_buffer is want
    untold = DeviceDPOROracle(app, cfg, config, batch_size=4)
    assert not untold.supports_async
    assert untold._instance(program)._double_buffer is False
    pinned = DeviceDPOROracle(
        app, cfg, config, batch_size=4, async_min=True,
        double_buffer=not want,
    )
    assert pinned._instance(program)._double_buffer is (not want)
    # A bare DeviceDPOR (the benchmark cell, the tests) is told nothing
    # and resolves as it did.
    assert dpor_sweep._resolve_double_buffer(None) is False
    monkeypatch.setenv("DEMI_ASYNC_MIN", "1")
    assert dpor_sweep._resolve_double_buffer(None) is want


def test_window_unconsulted_probe_keeps_state():
    """test_window commits a probe's resumable instance state only when
    its resolver is consulted: the unconsulted probe's instance looks
    exactly as if the sequential path had never reached it."""
    app, cfg, program = _setup(3)
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    oracle = DeviceDPOROracle(
        app, cfg, config, batch_size=4, max_rounds=1, async_min=True
    )
    c1 = list(program)
    c2 = [e for e in program[:-2]] + [program[-1]]
    resolvers = oracle.test_window([c1, c2], IntViolation(2))
    assert len(resolvers) == 2
    assert resolvers[0]() is None  # consult ONLY the first probe
    inst1 = oracle._instances[tuple(e.eid for e in c1)]
    inst2 = oracle._instances[tuple(e.eid for e in c2)]
    assert inst1.interleavings > 0  # committed by the consult
    assert inst2.interleavings == 0  # restored pre-window state
    assert inst2.frontier == [tuple()]
    assert inst2.explored == {tuple()}
    # A later sequential probe starts the search the window already paid
    # for device-side — same observable behavior as a fresh instance.
    assert oracle.test(c2, IntViolation(2)) is None
    assert inst2.interleavings > 0


def test_incremental_ddmin_window_parity():
    """IncrementalDDMin over the device DPOR oracle: the speculative
    (window-batched left/right probes, double-buffered rounds) run
    returns the SAME minimized event set as the sequential run."""
    app, cfg, program = _setup(3)
    noise = Send(app.actor_name(1), MessageConstructor(lambda: (1, 9)))
    program = program[:-1] + [noise, WaitQuiescence()]
    config = SchedulerConfig(invariant_check=make_host_invariant(app))

    finder = DeviceDPOROracle(app, cfg, config, batch_size=16, max_rounds=30)
    trace = finder.test(program, IntViolation(1))
    assert trace is not None

    def run(async_on):
        oracle = DeviceDPOROracle(
            app, cfg, config, batch_size=16, max_rounds=10,
            async_min=async_on, double_buffer=async_on,
        )
        oracle.set_initial_trace(trace)
        inc = IncrementalDDMin(
            config, max_max_distance=4, oracle=oracle,
            speculative=async_on,
        )
        return inc.minimize(make_dag(program), IntViolation(1))

    mcs_sync = run(False)
    mcs_async = run(True)
    kept_sync = [e.eid for e in mcs_sync.get_all_events()]
    kept_async = [e.eid for e in mcs_async.get_all_events()]
    assert kept_async == kept_sync
    assert noise.eid not in kept_async
    assert len(kept_async) < len(program)


def test_report_renders_dpor_pipeline_counters(tmp_path):
    """report.py's Telemetry Pipeline block includes the DPOR in-flight
    round economics and resume-trunk derivations — even in a dpor-only
    run that emits no pipe.* series at all."""
    import json

    from demi_tpu.tools.report import render_report

    snap = {
        "counters": {
            "dpor.inflight_rounds": {"": 10},
            "dpor.inflight_hits": {"": 7},
            "dpor.inflight_waste": {"": 3},
            "dpor.trunk_parent_hits": {"": 5},
        },
        "gauges": {},
        "histograms": {},
    }
    (tmp_path / "obs_snapshot.json").write_text(json.dumps(snap))
    text = render_report(str(tmp_path))
    assert "### Pipeline" in text
    assert "DPOR in-flight rounds: 10 dispatched" in text
    assert "7 became the next round / 3 discarded" in text
    assert "70.0% useful" in text
    assert "DPOR resume trunks: 5 derived" in text
