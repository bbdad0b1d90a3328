"""The arithmetic the per-layer readers share. A reader that finds
nothing to read returns None and the harness leaves the metric out; a
number that only a chip can give is None anywhere else."""

from __future__ import annotations

import statistics
from typing import Optional

from .bytes_per_step import least_bytes_per_lane_step


def window_compiles(obs) -> float:
    return float(obs.counters["window_compiles"])


def trace_lower_share(obs) -> float:
    """Seconds of jaxpr tracing and lowering inside the window (which no
    cache keeps) over the window's seconds."""
    return 100.0 * obs.counters["trace_lower_s"] / obs.counters["window_s"]


def host_share(obs) -> Optional[float]:
    """The driver's own split of its wall time, over the window's jobs."""
    host, device = obs.counters.get("host_s"), obs.counters.get("device_s")
    if host is None or not host + device > 0:
        return None
    return 100.0 * host / (host + device)


def gc_share(obs) -> float:
    return 100.0 * obs.counters["gc_s"] / obs.counters["window_s"]


def job_cv(obs) -> float:
    """Standard deviation over mean of the per-job rate inside the window.
    Where the panel is fixed, each job's seconds are first divided by the
    mean of its own panel entry, so that the entries' different sizes do
    not count as unsteadiness."""
    rows = obs.stats.per_job
    if obs.cell.traffic["panel"]["from"] != "fixed":
        return 100.0 * obs.stats.rate_cv
    by_entry: dict = {}
    for _index, sub, _work, secs in rows:
        by_entry.setdefault(sub, []).append(secs)
    scaled = [
        secs / statistics.fmean(by_entry[sub]) for _i, sub, _w, secs in rows
    ]
    return 100.0 * statistics.pstdev(scaled) if len(scaled) > 1 else 0.0


def _traced(obs) -> Optional[dict]:
    return obs.trace if obs.on_chip and obs.trace is not None else None


def kernel_ns_per_lane_step(obs) -> Optional[float]:
    """Device time of the step kernel's executions in the traced jobs,
    summed over the chips, over the lane-steps those jobs ran."""
    t = _traced(obs)
    if t is None or not t["kernel_runs"] or not t["counters"]["lane_steps"]:
        return None
    return 1e9 * t["kernel_s"] / t["counters"]["lane_steps"]


def segment_roofline(obs) -> Optional[float]:
    """The least time the traced segments' bytes take at the device's
    memory bandwidth, over the segment kernel's time. Bound: bytes."""
    t = _traced(obs)
    if t is None or not t["kernel_runs"] or not t["counters"]["lane_steps"]:
        return None
    per_step = least_bytes_per_lane_step(
        obs.cell.config["shapes"], t["counters"]["seg_steps"]
    )
    least_s = t["counters"]["lane_steps"] * per_step / obs.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / t["kernel_s"]


def device_idle_share(obs) -> Optional[float]:
    t = _traced(obs)
    if t is None:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def device_peak_hbm_mb(obs) -> Optional[float]:
    if not obs.on_chip or obs.peak_bytes is None:
        return None
    return obs.peak_bytes / 1e6


def replays_per_s(obs) -> Optional[float]:
    if not obs.counters.get("job_s"):
        return None
    return obs.counters["replays"] / obs.counters["job_s"]
