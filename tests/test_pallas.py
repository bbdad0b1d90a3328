"""Pallas explore backend: parity with the XLA kernel, Mosaic traceability.

The pallas kernel (demi_tpu/device/pallas_explore.py) must be bit-identical
to the XLA explore kernel — the violating-lane lift re-runs a lane's seed
through the XLA single-lane trace kernel, so the two backends must produce
the same schedule stream. Under JAX_PLATFORMS=cpu the kernel runs in
interpret mode; the Mosaic-coverage test proves the traced step contains
only primitives the TPU Mosaic lowering has rules for. Whether the kernels
compile on a chip is a chip run's finding (PERF.md, "Bring-up").
"""

import numpy as np

import jax
import jax.numpy as jnp

from demi_tpu.apps.broadcast import make_broadcast_app
from demi_tpu.apps.common import dsl_start_events
from demi_tpu.apps.raft import T_CLIENT, make_raft_app
from demi_tpu.apps.spark_dag import make_spark_app
from demi_tpu.device import DeviceConfig, make_explore_kernel
from demi_tpu.device.encoding import lower_program, stack_programs
from demi_tpu.device.explore import ExtProgram, make_run_lane
from demi_tpu.device.pallas_explore import make_explore_kernel_pallas
from demi_tpu.external_events import (
    Kill,
    MessageConstructor,
    Partition,
    Send,
    WaitQuiescence,
)


def _assert_lane_results_equal(a, b):
    for field in ("status", "violation", "deliveries"):
        av, bv = np.asarray(getattr(a, field)), np.asarray(getattr(b, field))
        assert (av == bv).all(), (field, av, bv)


def test_pallas_parity_broadcast():
    app = make_broadcast_app(4, reliable=False)
    cfg = DeviceConfig.for_app(
        app, pool_capacity=64, max_steps=96, max_external_ops=16,
        invariant_interval=1,
    )
    prog = dsl_start_events(app) + [
        Send(app.actor_name(0), MessageConstructor(lambda: (1, 0))),
        WaitQuiescence(),
    ]
    B = 40  # not a block multiple: exercises lane padding
    progs = stack_programs([lower_program(app, cfg, prog)] * B)
    keys = jax.random.split(jax.random.PRNGKey(0), B)
    xla = make_explore_kernel(app, cfg)(progs, keys)
    xla_t = make_explore_kernel(app, cfg, lane_axis="trailing")(progs, keys)
    _assert_lane_results_equal(xla, xla_t)
    for lane_axis in ("leading", "trailing"):
        pal = make_explore_kernel_pallas(
            app, cfg, block_lanes=16, lane_axis=lane_axis
        )(progs, keys)
        _assert_lane_results_equal(xla, pal)
        assert int((np.asarray(pal.violation) != 0).sum()) > 0


def test_pallas_parity_raft_faults():
    """Raft with kills/partitions + timer weighting + early exit — the full
    step feature set under the pallas backend."""
    app = make_raft_app(3, bug="gap_append")
    cfg = DeviceConfig.for_app(
        app, pool_capacity=96, max_steps=160, max_external_ops=16,
        invariant_interval=1, timer_weight=0.05, early_exit=True,
    )

    def cmd(node, v):
        return Send(
            app.actor_name(node),
            MessageConstructor(lambda vv=v: (T_CLIENT, 0, vv, 0, 0, 0, 0)),
        )

    prog = dsl_start_events(app) + [
        WaitQuiescence(budget=30),
        cmd(0, 10), cmd(1, 11),
        Partition(app.actor_name(0), app.actor_name(2)),
        cmd(2, 12),
        Kill(app.actor_name(1)),
        WaitQuiescence(budget=60),
    ]
    B = 32
    progs = stack_programs([lower_program(app, cfg, prog)] * B)
    keys = jax.random.split(jax.random.PRNGKey(7), B)
    xla = make_explore_kernel(app, cfg)(progs, keys)
    pal = make_explore_kernel_pallas(app, cfg, block_lanes=8)(progs, keys)
    _assert_lane_results_equal(xla, pal)


def test_pallas_replay_parity():
    """The pallas replay twin must agree verdict-for-verdict with the XLA
    batched STS oracle on DDMin-style candidates (incl. ignore-absent
    counts), across both early-exit and scan-form XLA baselines."""
    from demi_tpu.apps.common import make_host_invariant
    from demi_tpu.config import SchedulerConfig
    from demi_tpu.device import make_replay_kernel
    from demi_tpu.device.encoding import lower_expected_trace
    from demi_tpu.device.pallas_explore import make_replay_kernel_pallas
    from demi_tpu.schedulers import RandomScheduler

    app = make_broadcast_app(3, reliable=False)
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    starts = dsl_start_events(app)

    def send(node, bid):
        return Send(
            app.actor_name(node), MessageConstructor(lambda b=bid: (1, b))
        )

    s0, s1 = send(0, 0), send(1, 1)
    program = starts + [s0, s1, WaitQuiescence()]
    result = RandomScheduler(config, seed=3).execute(program)
    assert result.violation is not None

    for early_exit in (False, True):
        cfg = DeviceConfig.for_app(
            app, pool_capacity=64, max_steps=64, max_external_ops=8,
            early_exit=early_exit,
        )
        candidates = [
            program,
            starts + [s0, WaitQuiescence()],
            starts[:2] + [s0, WaitQuiescence()],
            starts[:1] + [s0, WaitQuiescence()],
            starts[:1] + [WaitQuiescence()],  # 5 lanes: exercises padding
        ]
        records = np.stack(
            [
                lower_expected_trace(
                    app,
                    cfg,
                    result.trace.filter_failure_detector_messages()
                    .filter_checkpoint_messages()
                    .subsequence_intersection(c),
                    c,
                    max_records=64,
                )
                for c in candidates
            ]
        )
        keys = jax.random.split(jax.random.PRNGKey(0), len(candidates))
        xla = make_replay_kernel(app, cfg)(records, keys)
        pal = make_replay_kernel_pallas(app, cfg, block_lanes=4)(
            records, keys
        )
        for field in ("status", "violation", "deliveries", "ignored_absent"):
            av = np.asarray(getattr(xla, field))
            bv = np.asarray(getattr(pal, field))
            assert (av == bv).all(), (early_exit, field, av, bv)


def test_batched_ddmin_on_pallas_backend():
    """The device-batched DDMin pipeline runs unchanged on the pallas
    replay backend (DeviceReplayChecker(impl='pallas')) and produces a
    reproducing MCS."""
    from demi_tpu.apps.common import make_host_invariant
    from demi_tpu.config import SchedulerConfig
    from demi_tpu.device.batch_oracle import (
        DeviceReplayChecker,
        DeviceSTSOracle,
    )
    from demi_tpu.minimization.ddmin import BatchedDDMin, make_dag
    from demi_tpu.runner import fuzz, sts_oracle
    from demi_tpu.fuzzing import Fuzzer, FuzzerWeights
    from demi_tpu.apps.broadcast import broadcast_send_generator

    app = make_broadcast_app(3, reliable=False)
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    fuzzer = Fuzzer(
        num_events=6,
        weights=FuzzerWeights(send=0.8, wait_quiescence=0.2),
        message_gen=broadcast_send_generator(app),
        prefix=dsl_start_events(app),
    )
    fr = fuzz(config, fuzzer, max_executions=50)
    assert fr is not None
    cfg = DeviceConfig.for_app(
        app, pool_capacity=64, max_steps=128, max_external_ops=32
    )
    checker = DeviceReplayChecker(app, cfg, config, impl="pallas")
    oracle = DeviceSTSOracle(app, cfg, config, fr.trace, checker=checker)
    mcs = BatchedDDMin(oracle).minimize(make_dag(fr.program), fr.violation)
    assert len(mcs.get_all_events()) < len(fr.program)
    assert (
        sts_oracle(config, fr.trace).test(mcs.get_all_events(), fr.violation)
        is not None
    )


def test_pallas_dpor_parity():
    """The pallas DPOR sweep twin (trace outputs included) must be
    bit-identical to the XLA kernel — the host racing-pair analysis
    consumes the traces directly."""
    from demi_tpu.device.dpor_sweep import make_dpor_kernel
    from demi_tpu.device.encoding import lower_program, stack_programs
    from demi_tpu.device.pallas_explore import make_dpor_kernel_pallas

    app = make_broadcast_app(3, reliable=False)
    cfg = DeviceConfig.for_app(
        app, pool_capacity=64, max_steps=48, max_external_ops=8,
        invariant_interval=1, record_trace=True, record_parents=True,
    )
    prog = dsl_start_events(app) + [
        Send(app.actor_name(0), MessageConstructor(lambda: (1, 0))),
        WaitQuiescence(),
    ]
    B = 12
    progs = stack_programs([lower_program(app, cfg, prog)] * B)
    prescs = np.zeros((B, cfg.max_steps, cfg.rec_width), np.int32)
    keys = jax.random.split(jax.random.PRNGKey(5), B)
    xla = make_dpor_kernel(app, cfg)(progs, prescs, keys)
    pal = make_dpor_kernel_pallas(app, cfg, block_lanes=4)(
        progs, prescs, keys
    )
    for field in ("status", "violation", "deliveries", "trace", "trace_len"):
        av = np.asarray(getattr(xla, field))
        bv = np.asarray(getattr(pal, field))
        assert (av == bv).all(), field


def test_rng_split_bit_identical():
    """ops.rng_split must match jax.random.split exactly — the pallas and
    XLA backends must draw the same schedule stream."""
    from demi_tpu.device.ops import rng_split

    key = jax.random.PRNGKey(1234)
    for n in (2, 3, 5):
        assert np.array_equal(
            np.asarray(jax.random.split(key, n)), np.asarray(rng_split(key, n))
        )


def test_prefix_sum_matches_cumsum():
    from demi_tpu.device.ops import prefix_sum

    rng = np.random.default_rng(0)
    for n in (1, 2, 7, 96, 100):
        x = jnp.asarray(rng.integers(0, 5, n), jnp.int32)
        assert np.array_equal(
            np.asarray(prefix_sum(x, True)), np.cumsum(np.asarray(x))
        )


def _traced_primitives(app, cfg):
    run_lane = make_run_lane(app, cfg)
    e, w, bl = cfg.max_external_ops, cfg.msg_width, 8
    ex = ExtProgram(
        op=jax.ShapeDtypeStruct((bl, e), jnp.int32),
        a=jax.ShapeDtypeStruct((bl, e), jnp.int32),
        b=jax.ShapeDtypeStruct((bl, e), jnp.int32),
        msg=jax.ShapeDtypeStruct((bl, e, w), jnp.int32),
    )
    jx = jax.make_jaxpr(lambda p, k: jax.vmap(run_lane)(p, k))(
        ex, jax.ShapeDtypeStruct((bl, 2), jnp.uint32)
    )
    acc = set()

    def walk(j):
        for eq in j.eqns:
            acc.add(eq.primitive.name)
            for v in eq.params.values():
                if hasattr(v, "jaxpr"):
                    walk(v.jaxpr)
                if isinstance(v, (list, tuple)):
                    for x in v:
                        if hasattr(x, "jaxpr"):
                            walk(x.jaxpr)

    walk(jx.jaxpr)
    return acc


def test_mosaic_primitive_coverage():
    """Every primitive in the one-hot step (all three fixture apps, incl.
    early-exit while_loop and timer weighting) has a Mosaic TPU lowering
    rule. Necessary for the pallas kernels to compile on a TPU, not
    sufficient: on the v5e Mosaic refuses them on shapes, not on
    primitives (PERF.md, "Bring-up")."""
    from jax._src.pallas.mosaic import lowering

    per_kernel_type = list(lowering.lowering_rules.values())
    regs = {
        getattr(k, "name", str(k)) for k in per_kernel_type[0].keys()
    } | {"jit", "pjit", "closed_call", "custom_jvp_call"}

    from demi_tpu.apps.twopc import make_twopc_app

    cases = [
        (
            make_raft_app(5),
            dict(timer_weight=0.2, early_exit=True),
        ),
        (make_spark_app(num_workers=3, bug="stale_task"), dict(early_exit=True)),
        (make_broadcast_app(8, reliable=True), dict(srcdst_fifo=True)),
        (make_twopc_app(4, bug="presume_commit"), dict(timer_weight=0.1)),
    ]
    for app, overrides in cases:
        cfg = DeviceConfig.for_app(
            app, pool_capacity=96, max_steps=64, max_external_ops=16,
            invariant_interval=1, index_mode="onehot", **overrides,
        )
        missing = _traced_primitives(app, cfg) - regs
        assert not missing, (app.name, sorted(missing))
