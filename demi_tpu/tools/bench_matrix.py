"""Kernel benchmark matrix: explore throughput across variants and sizes.

For the (scarce) real-TPU windows: one run measures the explore kernel's
variants across batch sizes on the 5-node raft headline workload,
printing one JSON line per cell as it goes (so a killed run still leaves
data).

    python -m demi_tpu.tools.bench_matrix
    python -m demi_tpu.tools.bench_matrix --batches 4096,8192
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--batches", default="2048,8192,16384")
    p.add_argument("--reps", type=int, default=3)
    p.add_argument("--msg-dtype", default="int32", dest="msg_dtype",
                   choices=("int32", "int16"))
    args = p.parse_args(argv)

    import jax

    # bench.py lives at the repo root, not in the package.
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))
    from bench import _raft_workload

    from ..device import DeviceConfig, make_explore_kernel
    from ..device.encoding import lower_program, stack_programs

    app, program = _raft_workload()
    cfg = DeviceConfig.for_app(
        app, pool_capacity=96, max_steps=144, max_external_ops=24,
        invariant_interval=1, timer_weight=0.2, msg_dtype=args.msg_dtype,
    )
    platform = jax.devices()[0].platform
    prog1 = lower_program(app, cfg, program)

    def measure(kernel, batch, prog_override=None):
        progs = stack_programs([prog_override or prog1] * batch)
        keys = jax.random.split(jax.random.PRNGKey(0), batch)
        t0 = time.perf_counter()
        jax.block_until_ready(kernel(progs, keys))
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for r in range(1, args.reps + 1):
            res = kernel(progs, jax.random.split(jax.random.PRNGKey(r), batch))
        jax.block_until_ready(res)
        secs = time.perf_counter() - t0
        return args.reps * batch / secs, compile_s

    batches = [int(x) for x in args.batches.split(",")]
    for lane_axis in ("leading", "trailing"):
        for batch in batches:
            tag = "xla" if lane_axis == "leading" else "xla-trailing"
            try:
                sps, comp = measure(
                    make_explore_kernel(app, cfg, lane_axis=lane_axis), batch
                )
                print(json.dumps({
                    "impl": tag, "platform": platform, "batch": batch,
                    "schedules_per_sec": round(sps, 1),
                    "compile_s": round(comp, 1),
                }), flush=True)
            except Exception as e:
                print(json.dumps({
                    "impl": tag, "batch": batch, "error": repr(e)[:300]
                }), flush=True)
    # Round-delivery variants (round-granularity invariant checks; see
    # DESIGN.md §3b) — the per-step-parallelism lever on this hardware.
    rcfg = dataclasses.replace(cfg, round_delivery=True, early_exit=True)
    for lane_axis in ("leading", "trailing"):
        for batch in batches:
            tag = f"xla-round-{lane_axis}-ee"  # -ee: rcfg sets early_exit
            try:
                sps, comp = measure(
                    make_explore_kernel(app, rcfg, lane_axis=lane_axis),
                    batch,
                )
                print(json.dumps({
                    "impl": tag, "platform": platform, "batch": batch,
                    "schedules_per_sec": round(sps, 1),
                    "compile_s": round(comp, 1),
                }), flush=True)
            except Exception as e:
                print(json.dumps({
                    "impl": tag, "batch": batch, "error": repr(e)[:300]
                }), flush=True)
    # Prefix-fork explore (start_state=): the trunk runs the shared
    # injection prefix once, lanes fork from the snapshot with per-lane
    # rng — results bit-identical to scratch. This column keeps the fork
    # kernels measured (and their lowering exercised) next to the scratch
    # ones on every matrix run.
    from ..device.explore import make_explore_kernel as _mek
    from ..device.fork import make_explore_prefix_runner

    for batch in batches[:1]:
        try:
            snap = make_explore_prefix_runner(app, cfg)(
                prog1, jax.random.PRNGKey(0)
            )
            fork_kernel = _mek(app, cfg, start_state=True)
            progs = stack_programs([prog1] * batch)
            keys0 = jax.random.split(jax.random.PRNGKey(0), batch)
            t0 = time.perf_counter()
            jax.block_until_ready(fork_kernel(progs, keys0, snap))
            comp = time.perf_counter() - t0
            t0 = time.perf_counter()
            for r in range(1, args.reps + 1):
                res = fork_kernel(
                    progs, jax.random.split(jax.random.PRNGKey(r), batch), snap
                )
            jax.block_until_ready(res)
            secs = time.perf_counter() - t0
            print(json.dumps({
                "impl": "xla-fork", "platform": platform, "batch": batch,
                "schedules_per_sec": round(args.reps * batch / secs, 1),
                "compile_s": round(comp, 1),
                "trunk_steps": int(snap.steps),
            }), flush=True)
        except Exception as e:
            print(json.dumps({
                "impl": "xla-fork", "batch": batch, "error": repr(e)[:300],
            }), flush=True)

    # Sustained continuous-refill throughput (the config-5 shape): the
    # segment/refill driver on the same workload — ranks the refill
    # path's overhead against the one-shot kernels on this hardware.
    from ..device.continuous import ContinuousSweepDriver

    for batch in batches[:1]:
        try:
            drv = ContinuousSweepDriver(
                app, cfg, lambda s: program, batch=batch, seg_steps=36,
                program_key=lambda s: 0,  # one fixed program: lower once
            )
            drv.sweep(batch + 64)  # warm at the real shape, incl. refill
            total = batch * (args.reps + 1)
            t0 = time.perf_counter()
            n = sum(1 for _ in drv._run(total))
            secs = time.perf_counter() - t0
            print(json.dumps({
                "impl": "xla-continuous", "platform": platform,
                "batch": batch, "lanes": n,
                "schedules_per_sec": round(n / secs, 1),
                "occupancy": round(drv.last_occupancy or 0, 3),
                "harvest_fraction": round(
                    drv.last_harvest_seconds
                    / max(drv.last_segment_seconds
                          + drv.last_harvest_seconds, 1e-9), 3),
            }), flush=True)
        except Exception as e:
            print(json.dumps({
                "impl": "xla-continuous", "batch": batch,
                "error": repr(e)[:300],
            }), flush=True)

    # Early-exit loop variant, trailing layout only (the known-best
    # layout): while_loop tracks the slowest LIVE lane instead of paying
    # max_steps — measured ~+10-15% on CPU for this workload (lanes
    # quiesce at ~120/144); the TPU verdict is what this cell is for.
    ee_cfg = DeviceConfig.for_app(
        app, pool_capacity=96, max_steps=144, max_external_ops=24,
        invariant_interval=1, timer_weight=0.2, msg_dtype=args.msg_dtype,
        early_exit=True,
    )
    for batch in batches:
        tag = "xla-trailing-ee"
        try:
            sps, comp = measure(
                make_explore_kernel(app, ee_cfg, lane_axis="trailing"), batch
            )
            print(json.dumps({
                "impl": tag, "platform": platform, "batch": batch,
                "schedules_per_sec": round(sps, 1),
                "compile_s": round(comp, 1),
            }), flush=True)
        except Exception as e:
            print(json.dumps({
                "impl": tag, "batch": batch, "error": repr(e)[:300],
            }), flush=True)

    # Config-5 fixture pair (64-actor reliable flood, P=4608): the
    # per-delivery step cost is pool-linear, so this is where round
    # mode's step-count collapse shows — sequential vs round on the SAME
    # programs/seeds (VERDICT r4 #2's measured cell). Lane counts stay
    # tiny: the cell measures per-lane step cost, not sweep scale.
    from demi_tpu.apps.broadcast import make_broadcast_app
    from demi_tpu.apps.common import dsl_start_events
    from demi_tpu.external_events import (
        Kill, MessageConstructor, Send, WaitQuiescence,
    )

    bapp = make_broadcast_app(64, reliable=True)
    bstarts = dsl_start_events(bapp)
    bprogram = list(bstarts) + [
        Send(bapp.actor_name(0), MessageConstructor(lambda: (1, 0))),
        Kill(bapp.actor_name(1)),
        WaitQuiescence(),
    ]
    b_lanes = 8 if platform in ("cpu",) else 256
    for tag, steps, rnd in (
        ("config5-seq", 4608, False),
        ("config5-round", 224, True),
    ):
        bcfg = DeviceConfig.for_app(
            bapp, pool_capacity=4608, max_steps=steps,
            max_external_ops=80, early_exit=True, round_delivery=rnd,
        )
        try:
            sps, comp = measure(
                make_explore_kernel(bapp, bcfg),
                b_lanes,
                prog_override=lower_program(bapp, bcfg, bprogram),
            )
            print(json.dumps({
                "impl": tag, "platform": platform, "batch": b_lanes,
                "schedules_per_sec": round(sps, 2),
                "compile_s": round(comp, 1),
            }), flush=True)
        except Exception as e:
            print(json.dumps({
                "impl": tag, "batch": b_lanes, "error": repr(e)[:300],
            }), flush=True)


if __name__ == "__main__":
    main()
