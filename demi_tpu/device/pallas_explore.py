"""Pallas backends for the device kernels: the whole step loop runs inside
one kernel, with each grid cell holding a block of lanes' full schedule
state in VMEM for the entire run.

Why: the XLA kernels (device/explore.py, device/replay.py) are step loops
whose carry — the complete per-lane ScheduleState — round-trips HBM every
step.  At 8k lanes the carry is tens of MB, so the loop is
HBM-bandwidth-bound even after the one-hot rewrite removed the serialized
scatters.  A Pallas kernel gridded over lane blocks keeps a block's state
resident in VMEM across all steps: HBM traffic drops to one read of the
inputs and one write of the verdicts per lane, regardless of step count.
This is the TPU-native answer to the reference's per-message JVM dispatch
cycle (SURVEY.md §3.1, Instrumenter.scala:913-1109) at its hottest.

Semantics are single-source: the kernel bodies call the SAME
`make_run_lane` / `make_replay_run_lane` step machinery as the XLA
kernels (vmapped over the lane block), so the backends are bit-identical
— including the `jax.random` schedule stream, which the traced
single-lane re-run (device/explore.py make_single_lane_trace_kernel)
depends on when lifting a violating lane to the host oracle.

Under ``JAX_PLATFORMS=cpu`` (the test boot) the kernels run in Pallas
interpret mode, which is how the parity suite validates them
(tests/test_pallas.py). Everywhere else they go to the Mosaic compiler,
whose refusal is the caller's error. As of PR 21 Mosaic (jax 0.9.0,
libtpu 0.0.34) refuses all three kernels on the TPU v5e — see PERF.md,
"Bring-up" — so ``--impl pallas`` raises there.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..dsl import DSLApp
from .core import DeviceConfig
from .explore import ExtProgram, LaneResult, make_run_lane
from .replay import ReplayResult, make_replay_run_lane


def _pad_to(x, b: int, axis: int = 0):
    """Pad ``axis`` of ``x`` up to a multiple of ``b`` with zeros."""
    axis = axis % x.ndim
    n = x.shape[axis]
    rem = (-n) % b
    if rem == 0:
        return x
    pad = [(0, rem) if i == axis else (0, 0) for i in range(x.ndim)]
    return jnp.pad(x, pad)


def _check_pallas_cfg(cfg: DeviceConfig, interpret: Optional[bool]):
    if interpret is None:
        # Interpret mode is for runs that FORCED the CPU (the test boot).
        # It is never reached because a chip was expected and some other
        # backend came up: there the Mosaic compile fails loudly.
        interpret = (
            os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
        )
    if not interpret and not cfg.use_onehot:
        # Scatter-mode kernels trace cumsum/searchsorted/scatter, none of
        # which have Mosaic lowerings — fail fast instead of deep inside
        # the Mosaic compiler.
        raise ValueError(
            "pallas kernels require the one-hot index mode on TPU "
            "(DeviceConfig(index_mode='onehot' or 'auto'))"
        )
    if not interpret and cfg.packed_gathers:
        # The packed shift/mask gathers are XLA-validated only; their
        # Mosaic lowering (uint32 shifts on padded lanes) is unproven.
        raise ValueError(
            "packed_gathers is XLA-only; drop impl='pallas' or the flag"
        )
    if not interpret and cfg.round_delivery:
        # The round step's Mosaic lowering is unvalidated (gumbel/uniform
        # sampling + 2-D record scatters); use the XLA backend for round
        # mode — its win is step-count reduction, which XLA gets too.
        raise ValueError(
            "round_delivery is XLA-only; drop impl='pallas' for round mode"
        )
    return interpret


def _make_blocked_kernel(
    block_fn,
    in_structs: Sequence[jax.ShapeDtypeStruct],
    block_lanes: int,
    interpret: bool,
    lane_dim_in: int = 0,
):
    """Generic lane-blocked pallas_call wrapper.

    ``block_fn(*block_arrays) -> tuple of arrays with leading dim
    block_lanes`` is traced once on ``in_structs`` (each with leading dim
    block_lanes); output shapes/dtypes come from the traced jaxpr.
    Every constant the trace closes over (init-state tables, timer-tag
    vectors, ...) is hoisted into an explicit kernel operand, because
    Pallas kernels may not capture constant arrays. jax.closure_convert
    only hoists inexact-dtype constants, and this state machine is
    all-integer — hence the manual jaxpr-consts threading. Bools ride as
    int32 (Mosaic mask operands are awkward) and scalars as [1] vectors.
    """
    closed_jaxpr = jax.make_jaxpr(block_fn)(*in_structs)
    consts = closed_jaxpr.consts
    out_avals = closed_jaxpr.out_avals
    for a in out_avals:
        if not a.shape or a.shape[0] != block_lanes:
            raise ValueError(
                f"block_fn outputs must have leading dim {block_lanes}, "
                f"got {a.shape}"
            )

    def _wire(c):
        """(operand_to_pass, restore_fn) for one hoisted constant."""
        arr = jnp.asarray(c)
        restore_dtype = arr.dtype
        if arr.dtype == jnp.bool_:
            arr = arr.astype(jnp.int32)
        shaped = arr.reshape((1,)) if arr.ndim == 0 else arr
        squeeze = arr.ndim == 0

        def restore(v):
            if squeeze:
                v = v.reshape(())
            return v.astype(restore_dtype)

        return shaped, restore

    const_ops, const_restores = (
        zip(*(_wire(c) for c in consts)) if consts else ((), ())
    )
    n_in = len(in_structs)

    def kernel(*refs):
        in_refs = refs[:n_in]
        const_refs = refs[n_in : n_in + len(const_ops)]
        out_refs = refs[n_in + len(const_ops):]
        cvals = [
            restore(ref[...])
            for ref, restore in zip(const_refs, const_restores)
        ]
        outs = jax.core.eval_jaxpr(
            closed_jaxpr.jaxpr, cvals, *(r[...] for r in in_refs)
        )
        for ref, val in zip(out_refs, outs):
            ref[...] = val

    def call(*arrays):
        n_lanes = arrays[0].shape[lane_dim_in]
        padded_arrays = [
            _pad_to(jnp.asarray(a), block_lanes, axis=lane_dim_in)
            for a in arrays
        ]
        padded = padded_arrays[0].shape[lane_dim_in]
        grid = (padded // block_lanes,)

        def in_spec(struct):
            nd = len(struct.shape)
            if lane_dim_in == 0:
                return pl.BlockSpec(
                    (block_lanes,) + tuple(struct.shape[1:]),
                    lambda i, nd=nd: (i,) + (0,) * (nd - 1),
                )
            return pl.BlockSpec(
                tuple(struct.shape[:-1]) + (block_lanes,),
                lambda i, nd=nd: (0,) * (nd - 1) + (i,),
            )

        def out_spec(aval):
            nd = len(aval.shape)
            return pl.BlockSpec(
                (block_lanes,) + tuple(aval.shape[1:]),
                lambda i, nd=nd: (i,) + (0,) * (nd - 1),
            )

        const_specs = [
            pl.BlockSpec(c.shape, lambda i, nd=c.ndim: (0,) * nd)
            for c in const_ops
        ]
        outs = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[in_spec(s) for s in in_structs] + const_specs,
            out_specs=[out_spec(a) for a in out_avals],
            out_shape=[
                jax.ShapeDtypeStruct((padded,) + tuple(a.shape[1:]), a.dtype)
                for a in out_avals
            ],
            interpret=interpret,
        )(*padded_arrays, *const_ops)
        return [o[:n_lanes] for o in outs]

    return call


def make_explore_kernel_pallas(
    app: DSLApp,
    cfg: DeviceConfig,
    block_lanes: int = 128,
    interpret: Optional[bool] = None,
    lane_axis: str = "leading",
):
    """Pallas twin of ``make_explore_kernel``: ``kernel(progs, keys) ->
    LaneResult`` with empty traces (sweeps record verdicts only; traced
    re-runs of interesting lanes use the XLA single-lane kernel).

    ``block_lanes`` sets the VMEM working set: one block's ScheduleState
    (~pool_capacity * (7 + msg_width) ints per lane) must fit. The lane
    batch is padded to a block multiple with inert all-zero programs.

    ``lane_axis='trailing'`` batches lanes along the LAST array axis
    inside the kernel (vmap in_axes=-1): elementwise/reduce ops then see
    [pool, lanes]-shaped data whose minor dimension is the lane block —
    the axis Mosaic vectorizes — instead of a 96-wide pool axis. Same
    results bit-for-bit; a pure layout experiment for the TPU (the
    bench matrix measures both).
    """
    if cfg.record_trace:
        raise ValueError(
            "pallas explore kernel records verdicts only; use the XLA "
            "single-lane trace kernel for trace extraction"
        )
    if lane_axis not in ("leading", "trailing"):
        raise ValueError(f"lane_axis must be leading/trailing, got {lane_axis!r}")
    interpret = _check_pallas_cfg(cfg, interpret)
    run_lane = make_run_lane(app, cfg)
    e, w = cfg.max_external_ops, cfg.msg_width
    bl = block_lanes
    trailing = lane_axis == "trailing"

    if trailing:
        def block_fn(op, a, b, msg, keys):
            res = jax.vmap(run_lane, in_axes=-1, out_axes=0)(
                ExtProgram(op=op, a=a, b=b, msg=msg), keys
            )
            return res.status, res.violation, res.deliveries, res.sched_hash

        in_structs = [
            jax.ShapeDtypeStruct((e, bl), jnp.int32),
            jax.ShapeDtypeStruct((e, bl), jnp.int32),
            jax.ShapeDtypeStruct((e, bl), jnp.int32),
            jax.ShapeDtypeStruct((e, w, bl), jnp.int32),
            jax.ShapeDtypeStruct((2, bl), jnp.uint32),
        ]
        blocked = _make_blocked_kernel(
            block_fn, in_structs, bl, interpret, lane_dim_in=-1
        )
    else:
        def block_fn(op, a, b, msg, keys):
            res = jax.vmap(run_lane)(
                ExtProgram(op=op, a=a, b=b, msg=msg), keys
            )
            return res.status, res.violation, res.deliveries, res.sched_hash

        in_structs = [
            jax.ShapeDtypeStruct((bl, e), jnp.int32),
            jax.ShapeDtypeStruct((bl, e), jnp.int32),
            jax.ShapeDtypeStruct((bl, e), jnp.int32),
            jax.ShapeDtypeStruct((bl, e, w), jnp.int32),
            jax.ShapeDtypeStruct((bl, 2), jnp.uint32),
        ]
        blocked = _make_blocked_kernel(block_fn, in_structs, bl, interpret)

    def call(progs: ExtProgram, keys) -> LaneResult:
        n_lanes = keys.shape[0]
        ins = (progs.op, progs.a, progs.b, progs.msg, keys)
        if trailing:
            ins = tuple(jnp.moveaxis(jnp.asarray(x), 0, -1) for x in ins)
        st, vio, dl, sh = blocked(*ins)
        empty = jnp.zeros((n_lanes, 0, 0), jnp.int32)
        return LaneResult(
            status=st,
            violation=vio,
            deliveries=dl,
            trace=empty,
            trace_len=jnp.zeros((n_lanes,), jnp.int32),
            sched_hash=sh,
        )

    return jax.jit(call)


def make_dpor_kernel_pallas(
    app: DSLApp,
    cfg: DeviceConfig,
    block_lanes: int = 64,
    interpret: Optional[bool] = None,
):
    """Pallas twin of ``make_dpor_kernel``: the frontier-batched DPOR
    sweep with VMEM-resident lane blocks, traces included — each lane's
    parent-tracked trace ([max_steps, rec_width]) is a kernel output, so
    the VMEM working set per lane is pool + trace (size accordingly:
    block_lanes * max_steps * rec_width * 4 bytes for the traces alone).
    """
    from .dpor_sweep import make_dpor_run_lane

    interpret = _check_pallas_cfg(cfg, interpret)
    run_lane = make_dpor_run_lane(app, cfg)
    e, w = cfg.max_external_ops, cfg.msg_width
    bl = block_lanes

    def block_fn(op, a, b, msg, prescs, keys):
        res = jax.vmap(run_lane)(
            ExtProgram(op=op, a=a, b=b, msg=msg), prescs, keys
        )
        return (
            res.status, res.violation, res.deliveries, res.trace,
            res.trace_len, res.sched_hash,
        )

    in_structs = [
        jax.ShapeDtypeStruct((bl, e), jnp.int32),
        jax.ShapeDtypeStruct((bl, e), jnp.int32),
        jax.ShapeDtypeStruct((bl, e), jnp.int32),
        jax.ShapeDtypeStruct((bl, e, w), jnp.int32),
        jax.ShapeDtypeStruct((bl, cfg.max_steps, cfg.rec_width), jnp.int32),
        jax.ShapeDtypeStruct((bl, 2), jnp.uint32),
    ]
    blocked = _make_blocked_kernel(block_fn, in_structs, bl, interpret)

    def call(progs: ExtProgram, prescs, keys) -> LaneResult:
        st, vio, dl, tr, tl, sh = blocked(
            progs.op, progs.a, progs.b, progs.msg, prescs, keys
        )
        return LaneResult(
            status=st, violation=vio, deliveries=dl, trace=tr, trace_len=tl,
            sched_hash=sh,
        )

    return jax.jit(call)


def make_replay_kernel_pallas(
    app: DSLApp,
    cfg: DeviceConfig,
    block_lanes: int = 128,
    interpret: Optional[bool] = None,
):
    """Pallas twin of ``make_replay_kernel``: ``kernel(records[B, R, W],
    keys[B]) -> ReplayResult[B]`` — the batched STS ignore-absent oracle
    with VMEM-resident lane blocks.

    The record loop always runs in the early-exit (while_loop + one-hot
    record fetch) form: the non-early-exit ``lax.scan`` over records
    slices its xs with dynamic_slice, which has no Mosaic lowering.
    Results are identical either way (the scan form is just the padded
    equivalent)."""
    if cfg.record_trace:
        raise ValueError("pallas replay kernel records verdicts only")
    interpret = _check_pallas_cfg(cfg, interpret)
    if not cfg.early_exit:
        cfg = dataclasses.replace(cfg, early_exit=True)
    run_lane = make_replay_run_lane(app, cfg)

    def _kernel_for(n_records: int):
        def block_fn(records, keys):
            res = jax.vmap(run_lane)(records, keys)
            return (
                res.status,
                res.violation,
                res.deliveries,
                res.ignored_absent,
                res.peeked,
            )

        in_structs = [
            jax.ShapeDtypeStruct(
                (block_lanes, n_records, cfg.rec_width), jnp.int32
            ),
            jax.ShapeDtypeStruct((block_lanes, 2), jnp.uint32),
        ]
        return _make_blocked_kernel(
            block_fn, in_structs, block_lanes, interpret
        )

    cache = {}

    def call(records, keys) -> ReplayResult:
        n_records = records.shape[1]
        if n_records not in cache:
            cache[n_records] = jax.jit(_kernel_for(n_records))
        st, vio, dl, ig, pk = cache[n_records](records, keys)
        return ReplayResult(
            status=st, violation=vio, deliveries=dl, ignored_absent=ig,
            peeked=pk,
        )

    return call
