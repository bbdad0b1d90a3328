"""From a profiler trace to device busy and idle time, a kernel's time,
the operations that took most of it, and the idle gaps by what the host
was doing. Works on a neutral form (planes -> lines -> events) so that the
same reduction reads a live ``.xplane.pb`` and the small recorded trace
that ``tests/`` checks it on.

An event is ``[name, start_ns, duration_ns]``. A device plane is one whose
name starts with ``/device:TPU:``; the host plane is ``/host:CPU``.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.trace_window"

Event = Tuple[str, float, float]


def load_xplane(trace_dir: str) -> List[dict]:
    """The newest ``.xplane.pb`` under ``trace_dir`` in the neutral form,
    keeping the device planes whole and, of the host plane, only the
    benchmark's own spans."""
    from jax.profiler import ProfileData

    files = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    planes = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        if not device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            events = [
                [op_name(e.name), float(e.start_ns), float(e.duration_ns)]
                for e in line.events
                if device or e.name.startswith(SPAN_PREFIX)
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def _line(plane: dict, name: str) -> List[Event]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def self_times(events: List[Event]) -> Dict[str, float]:
    """Seconds by operation name, an operation's time less that of the
    operations nested inside it (a ``while`` holds its body's fusions)."""
    totals: Dict[str, float] = {}
    stack: List[list] = []  # [name, end, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _end, self_ns = stack.pop()
            totals[name] = totals.get(name, 0.0) + self_ns

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return {k: v / 1e9 for k, v in totals.items()}


def op_name(name: str) -> str:
    """``%fusion.12 = s32[8192,96]{...} fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def module_base(name: str) -> str:
    """``jit_seg_lane(1234)`` -> ``jit_seg_lane``."""
    return name.split("(", 1)[0].strip()


def host_spans(planes: List[dict]) -> List[Event]:
    spans: List[Event] = []
    for plane in planes:
        if plane["name"] == HOST_PLANE:
            for line in plane["lines"]:
                spans += [e for e in line["events"] if e[0].startswith(SPAN_PREFIX)]
    return spans


def reduce_trace(planes: List[dict], step_kernel: Optional[str] = None) -> dict:
    """The numbers the per-layer readers and ``device`` take from a trace.

    The window is the ``bench.trace_window`` span on the profiler's clock.
    Busy is the union of the intervals in which an operation ran (the
    ``XLA Ops`` line; the ``XLA Modules`` line where a device has no such
    line), clipped to the window and averaged over the device planes.
    """
    spans = host_spans(planes)
    window = [e for e in spans if e[0] == WINDOW_SPAN]
    if not window:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    lo = window[0][1]
    hi = lo + window[0][2]
    devices = [p for p in planes if p["name"].startswith(DEVICE_PREFIX)]
    if not devices:
        raise ValueError("the trace holds no device plane")
    busy_s, kernel_s, kernel_runs = [], [], []
    first_busy = None
    ops: Dict[str, float] = {}
    modules: Dict[str, float] = {}
    for plane in devices:
        events = _line(plane, OPS_LINE) or _line(plane, MODULES_LINE)
        busy = clip(union((s, s + d) for _n, s, d in events), lo, hi)
        busy_s.append(sum(b - a for a, b in busy) / 1e9)
        if first_busy is None:
            first_busy = busy
        for name, secs in self_times(_line(plane, OPS_LINE)).items():
            ops[name] = ops.get(name, 0.0) + secs
        k_s, k_n = 0.0, 0
        for name, start, dur in _line(plane, MODULES_LINE):
            base = module_base(name)
            modules[base] = modules.get(base, 0.0) + dur / 1e9
            if step_kernel is not None and base == step_kernel and lo <= start < hi:
                k_s += dur / 1e9
                k_n += 1
        kernel_s.append(k_s)
        kernel_runs.append(k_n)
    n = len(devices)
    # Idle gaps of the first device, by the innermost benchmark span open
    # at the gap's middle.
    gaps: Dict[str, float] = {}
    edge = lo
    for a, b in first_busy + [(hi, hi)]:
        if a > edge:
            mid = (edge + a) / 2
            open_spans = [
                e for e in spans
                if e[0] != WINDOW_SPAN and e[1] <= mid < e[1] + e[2]
            ]
            name = max(open_spans, key=lambda e: e[1])[0] if open_spans else "(no span)"
            gaps[name] = gaps.get(name, 0.0) + (a - edge) / 1e9
        edge = max(edge, b)

    def top(d: Dict[str, float]) -> list:
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {
        "lines": {
            p["name"]: {ln["name"]: len(ln["events"]) for ln in p["lines"]}
            for p in planes
        },
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_s) / n,
        "busy_s_per_device": busy_s,
        "devices": n,
        "kernel_s": sum(kernel_s),            # summed over devices
        "kernel_runs": sum(kernel_runs),      # executions, summed over devices
        "modules": top(modules),
        "breakdown": {"device_ops": top(ops), "idle_gaps": top(gaps)},
    }
