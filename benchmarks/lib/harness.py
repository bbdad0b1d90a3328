"""One run of one cell: set-up, warm jobs, the window, the comparison that
decides ``correct``, the metrics, the result line.

The verb module (``verbs/<verb>.py``) is the only part that touches the
program. It gives:

``STEP_KERNEL``            the jitted step kernel's module name in a trace
``setup(cell, devices)``   build the app, the drivers, the kernels -> ctx
``run_job(ctx, job)``      one whole job -> dict with ``work`` and counters
``instrument(ctx, on)``    put spans around the calls into each layer, or take them off
``check(ctx, records, warm, rng)``  -> (checks, attempted, failed)
``counters(ctx, records)`` sums for the per-layer readers
``close(ctx)``             free what set-up made on disk
"""

from __future__ import annotations

import gc
import itertools
import json
import random
import shutil
import tempfile
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

from . import cells, jobs, trace as trace_lib, watch
from .spans import span


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclass
class Check:
    """One number the comparison holds to a limit; ``ok`` is value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Obs:
    """What a per-layer reader may read."""
    cell: cells.Cell
    device: dict
    stats: jobs.WindowStats
    counters: dict                      # the verb's sums and the harness's watches
    trace: Optional[dict] = None        # lib.trace.reduce_trace's result + traced counters
    peak_bytes: Optional[int] = None
    peaks: dict = field(default_factory=dict)

    @property
    def on_chip(self) -> bool:
        return self.device["platform"] == "tpu"


def device_info(devices) -> dict:
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def traced_jobs(verb, ctx, cell, warm: List[jobs.Job], trace_dir: str) -> List[jobs.JobRecord]:
    """Whole jobs under the profiler, until the traffic file's
    ``trace_seconds`` have been traced. They repeat the warm jobs and are
    not part of the window."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0   # every Python call is far too much
    options.host_tracer_level = 2
    want = float(cell.traffic["trace_seconds"])
    verb.instrument(ctx, True)
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with span(trace_lib.WINDOW_SPAN):
            records = jobs.closed_loop(
                lambda job: verb.run_job(ctx, job), itertools.repeat(warm), want
            )
    finally:
        jax.profiler.stop_trace()
        verb.instrument(ctx, False)
    return records


def run(
    bench_file: str,
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    t_start: float,
    require_tpu: bool = True,
    log: Callable[[str], None] = print,
) -> dict:
    """Run the cell and return the result object (the last line of the
    run's standard output). ``require_tpu=False`` is for the CPU
    rehearsals under ``tests/``; ``run.py`` never passes it."""
    cell = cells.load_cell(bench_file, workload)
    import jax

    all_devices = jax.devices()
    device = device_info(all_devices)
    log(f"[bench] cell {cell.name}: {cell.config_name} x {cell.traffic_name}, "
        f"{cell.chips} chip(s); device {json.dumps(device)}")
    if require_tpu and (
        device["platform"] != "tpu" or len(jax.local_devices()) < cell.chips
    ):
        raise NoChip(
            f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX found "
            f"{device['count']} x {device['platform']}"
        )
    devices = jax.local_devices()[: cell.chips]
    verb = cells.load_verb(cell)
    compiles = watch.CompileWatch()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    ctx = None
    try:
        # -- set-up: build, then one whole job of each shape, discarded ----
        with span("bench.setup"):
            ctx = verb.setup(cell, devices)
            warm_list = jobs.warm_jobs(cell.traffic["panel"], seed)
            warm = {}
            for job in warm_list:
                t0 = time.perf_counter()
                warm[job.sub_seed] = verb.run_job(ctx, job)
                log(f"[bench] warm job sub-seed {job.sub_seed}: "
                    f"{time.perf_counter() - t0:.3f} s")
        # -- traced jobs (--trace 1): before the window, not part of it ---
        traced_records = None
        if trace:
            traced_records = traced_jobs(verb, ctx, cell, warm_list, trace_dir)
        # What set-up built stays out of the collector's passes, and the
        # window starts from the same heap in every run, traced or not; the
        # collector stays on in the window.
        gc.collect()
        gc.freeze()
        setup_s = time.perf_counter() - t_start
        log(f"[bench] setup_s {setup_s:.3f}" + (" (traced jobs included)" if trace else ""))

        # -- the window -----------------------------------------------------
        gcw = watch.GcWatch()
        gc_by_job = []

        def window_job(job):
            before = gcw.seconds
            gcw.longest = 0.0
            out = verb.run_job(ctx, job)
            gc_by_job.append((gcw.seconds - before, gcw.longest))
            return out

        gcw.start()
        compiles.start()
        with span("bench.window"):
            records = jobs.closed_loop(
                window_job, jobs.passes(cell.traffic["panel"], seed), seconds
            )
        compiles.stop()
        gcw.stop()
        peak = peak_bytes(devices)
        stats = jobs.window_stats(records)
        for r, (gc_s, gc_longest) in zip(records, gc_by_job):
            split = "".join(
                f", driver {k} {r.out[k]:.3f}" for k in ("host_s", "device_s")
                if k in r.out
            )
            log(f"[bench] job {r.job.index} sub-seed {r.job.sub_seed}: work "
                f"{r.out['work']} in {r.seconds:.4f} s (cpu {r.cpu_s:.3f} s, "
                f"collector {gc_s:.3f} s, longest pause {gc_longest:.3f} s{split})")
        log(f"[bench] window: {stats.jobs} whole jobs, work {stats.work} in "
            f"{stats.seconds:.4f} s; mean rate {stats.mean_rate:.4f}, median "
            f"over jobs {stats.median_rate:.4f}; job seconds mean "
            f"{stats.mean_job_s:.4f} median {stats.median_job_s:.4f}")
        log(f"[bench] window: {compiles.events} trace/lower/compile events, "
            f"tracing and lowering {compiles.trace_lower_s:.3f} s, "
            f"{compiles.compiles} compilations the cache did not serve "
            f"({compiles.compile_s:.3f} s)")

        # -- the comparison that decides `correct` ---------------------------
        checks, attempted, failed = verb.check(
            ctx, records, warm, random.Random(seed)
        )
        for c in checks:
            log(f"[bench] check {c.name}: {c.value} (limit {c.limit}) "
                f"{'ok' if c.ok else 'FAILED'}")
        correct = all(c.ok for c in checks) and failed == 0

        # -- the trace, read after the window so that it leaves the heap alone
        traced = None
        if trace:
            try:
                traced = trace_lib.reduce_trace(
                    trace_lib.load_xplane(trace_dir), verb.STEP_KERNEL
                )
            except ValueError as e:
                if device["platform"] == "tpu":
                    raise
                log(f"[bench] no device trace on {device['platform']}: {e}")
            if traced is not None:
                traced["counters"] = verb.counters(ctx, traced_records)
                log("[bench] trace lines " + json.dumps(traced["lines"]))
                log("[bench] trace modules " + json.dumps(traced["modules"]))
                log(f"[bench] traced {len(traced_records)} job(s): window "
                    f"{traced['window_s']:.3f} s, busy per device "
                    f"{traced['busy_s_per_device']}, step kernel "
                    f"{traced['kernel_runs']} runs {traced['kernel_s']:.4f} s, "
                    f"counters {json.dumps(traced['counters'])}")

        # -- metrics ---------------------------------------------------------
        metrics = {}
        if not trace:
            for m in cell.metrics("end_to_end"):
                if m["name"] == "setup_s":
                    value = setup_s
                else:
                    value = getattr(stats, cell.traffic["end_to_end"][m["name"]])
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            counters = verb.counters(ctx, records)
            counters.update(
                window_compiles=compiles.compiles,
                trace_lower_s=compiles.trace_lower_s, gc_s=gcw.seconds,
                gc_collections=gcw.collections, window_s=stats.seconds,
            )
            obs = Obs(
                cell=cell, device=device, stats=stats, counters=counters,
                trace=traced, peak_bytes=peak,
            )
            if obs.on_chip:
                obs.peaks = cells.load_peaks(cell, device["kind"])
            for m in cell.metrics("per_layer"):
                value = cells.load_reader(cell, m["name"])(obs)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result = {
            "correct": bool(correct),
            "attempted": int(attempted),
            "failed": int(failed),
            "metrics": metrics,
            "device": dict(device, memory_peak_bytes=peak),
        }
        if traced is not None:
            result["device"]["busy_s"] = traced["busy_s"]
            result["device"]["window_s"] = traced["window_s"]
            result["breakdown"] = traced["breakdown"]
        return result
    finally:
        compiles.close()
        if ctx is not None:
            verb.close(ctx)
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
