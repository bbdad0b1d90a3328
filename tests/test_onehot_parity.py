"""The branch a TPU takes, run on the CPU: ``index_mode='onehot'`` is what
``DeviceConfig.index_mode='auto'`` resolves to on a TPU, and tier-1 runs on
a CPU, where 'auto' means 'scatter'. Explore and round kernels have their
own parity tests (test_device.py, test_rounds.py); this module pins the
DPOR, replay and prefix-fork kernels: every output field equal between the
two lowerings on the 3-node raft fixture.

Kept small and in a module conftest does not list as heavy, so it runs
before the tier-1 time cap bites.
"""

import dataclasses

import numpy as np
import pytest

import jax

from demi_tpu.apps.common import dsl_start_events
from demi_tpu.apps.raft import T_CLIENT, make_raft_app
from demi_tpu.device import DeviceConfig
from demi_tpu.device.dpor_sweep import make_dpor_kernel
from demi_tpu.device.encoding import lower_program
from demi_tpu.device.explore import broadcast_program, make_explore_kernel
from demi_tpu.device.fork import (
    make_dpor_prefix_runner,
    make_explore_prefix_runner,
    make_replay_prefix_runner,
)
from demi_tpu.device.replay import make_replay_kernel
from demi_tpu.external_events import MessageConstructor, Send, WaitQuiescence

MODES = ("scatter", "onehot")
B = 8
PREFIX = 6  # trunk length (records) for the fork kernels


def _assert_equal_trees(a, b, what: str) -> None:
    for field in type(a)._fields:
        assert np.array_equal(
            np.asarray(getattr(a, field)), np.asarray(getattr(b, field))
        ), f"{what}: {field} differs between scatter and onehot"


@pytest.fixture(scope="module")
def fixture():
    app = make_raft_app(3, bug="multivote")
    program = dsl_start_events(app) + [
        Send(app.actor_name(0),
             MessageConstructor(lambda: (T_CLIENT, 0, 7, 0, 0, 0, 0))),
        WaitQuiescence(budget=24),
    ]
    cfgs = {
        mode: DeviceConfig.for_app(
            app, pool_capacity=32, max_steps=40, max_external_ops=8,
            invariant_interval=1, timer_weight=0.2, record_trace=True,
            record_parents=True, index_mode=mode,
        )
        for mode in MODES
    }
    keys = jax.random.split(jax.random.PRNGKey(3), B)
    cfg = cfgs["scatter"]
    progs = broadcast_program(lower_program(app, cfg, program), B)
    # A first DPOR round with empty prescriptions: its parent-tracked
    # traces are the prescriptions (DPOR) and expected records (replay)
    # the other kernels are driven with.
    empty = np.zeros((B, cfg.max_steps, cfg.rec_width), np.int32)
    dpor_kernels = {mode: make_dpor_kernel(app, cfgs[mode]) for mode in MODES}
    first = dpor_kernels["scatter"](progs, empty, keys)
    traces = np.asarray(first.trace)
    assert int(np.asarray(first.trace_len).min()) > PREFIX
    return app, cfgs, progs, keys, traces, dpor_kernels


def test_dpor_kernel_index_mode_parity(fixture):
    _app, _cfgs, progs, keys, traces, dpor_kernels = fixture
    prescs = np.zeros_like(traces)
    prescs[:, :12] = traces[:, :12]
    out = {mode: dpor_kernels[mode](progs, prescs, keys) for mode in MODES}
    _assert_equal_trees(out["scatter"], out["onehot"], "dpor kernel")


def test_dpor_fork_kernel_index_mode_parity(fixture):
    app, cfgs, progs, keys, traces, _kernels = fixture
    # Every lane follows lane 0's first PREFIX records, then its own rng.
    presc = np.zeros_like(traces[0])
    presc[:PREFIX] = traces[0, :PREFIX]
    prescs = np.repeat(presc[None], B, axis=0)
    prog = jax.tree_util.tree_map(lambda x: x[0], progs)
    out = {}
    for mode in MODES:
        snap = make_dpor_prefix_runner(app, cfgs[mode])(prog, presc, keys[0])
        out[mode] = (
            snap,
            make_dpor_kernel(app, cfgs[mode], start_state=True)(
                progs, prescs, keys, snap
            ),
        )
    _assert_equal_trees(
        out["scatter"][0].state, out["onehot"][0].state, "dpor trunk state"
    )
    _assert_equal_trees(out["scatter"][1], out["onehot"][1], "dpor fork")


def _untraced(cfg):
    return dataclasses.replace(cfg, record_trace=False, record_parents=False)


def _replay_records(cfgs, traces):
    # Replay records carry no parent columns.
    return np.ascontiguousarray(
        traces[:, :, : _untraced(cfgs["scatter"]).rec_width]
    )


def test_replay_kernel_index_mode_parity(fixture):
    app, cfgs, _progs, keys, traces, _kernels = fixture
    records = _replay_records(cfgs, traces)
    out = {
        mode: make_replay_kernel(app, _untraced(cfgs[mode]))(records, keys)
        for mode in MODES
    }
    _assert_equal_trees(out["scatter"], out["onehot"], "replay kernel")


def test_replay_fork_kernel_index_mode_parity(fixture):
    app, cfgs, _progs, keys, traces, _kernels = fixture
    records = _replay_records(cfgs, traces)
    trunk = np.zeros_like(records[0])
    trunk[:PREFIX] = records[0, :PREFIX]
    # Lane i forks from lane 0's prefix into lane 0's own suffix with one
    # later record dropped: a DDMin level's candidates in miniature.
    suffixes = np.zeros_like(records)
    for i in range(B):
        rest = np.delete(records[0, PREFIX:], i, axis=0)
        suffixes[i, : len(rest)] = rest
    out = {}
    for mode in MODES:
        cfg = _untraced(cfgs[mode])
        snap = make_replay_prefix_runner(app, cfg)(trunk, keys[0])
        out[mode] = make_replay_kernel(app, cfg, start_state=True)(
            suffixes, keys, snap
        )
    _assert_equal_trees(out["scatter"], out["onehot"], "replay fork")


def test_explore_fork_kernel_index_mode_parity(fixture):
    app, cfgs, progs, keys, _traces, _kernels = fixture
    prog = jax.tree_util.tree_map(lambda x: x[0], progs)
    out = {}
    for mode in MODES:
        cfg = _untraced(cfgs[mode])
        snap = make_explore_prefix_runner(app, cfg)(prog, keys[0])
        out[mode] = make_explore_kernel(app, cfg, start_state=True)(
            progs, keys, snap
        )
    _assert_equal_trees(out["scatter"], out["onehot"], "explore fork")
