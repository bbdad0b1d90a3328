"""The reduction from a trace to idle share, kernel time and the
breakdown gives known numbers: on a synthetic trace worked out by hand,
and on a small trace recorded on the chip (``data/recorded_trace.json``,
see ``record_trace.py``) against a brute-force count."""

import json
import os

import pytest

from lib import trace as T

MS = 1e6  # ns


def synthetic():
    ops = [
        ["while.1", 10 * MS, 40 * MS],        # holds the two fusions below
        ["fusion.1", 10 * MS, 10 * MS],
        ["fusion.2", 30 * MS, 20 * MS],
        ["copy.3", 70 * MS, 10 * MS],
    ]
    modules = [["jit_step(123)", 10 * MS, 40 * MS], ["jit_other(9)", 70 * MS, 10 * MS]]
    spans = [
        [T.WINDOW_SPAN, 0.0, 100 * MS],
        ["bench.x.job", 0.0, 100 * MS],
        ["bench.x.host_half", 50 * MS, 20 * MS],
    ]
    dev = lambda n, shift: {  # noqa: E731
        "name": f"{T.DEVICE_PREFIX}{n}",
        "lines": [
            {"name": T.OPS_LINE, "events": [[a, s + shift, d] for a, s, d in ops]},
            {"name": T.MODULES_LINE, "events": [[a, s + shift, d] for a, s, d in modules]},
        ],
    }
    host = {"name": T.HOST_PLANE, "lines": [{"name": "python3", "events": spans}]}
    return [dev(0, 0.0), dev(1, 5 * MS), host]


def test_synthetic_trace_known_numbers():
    r = T.reduce_trace(synthetic(), "jit_step")
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s_per_device"] == pytest.approx([0.050, 0.050])
    assert r["busy_s"] == pytest.approx(0.050)           # idle share 50%
    assert r["kernel_runs"] == 2 and r["kernel_s"] == pytest.approx(0.080)
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["while.1"] == pytest.approx(0.020)         # 40 - 10 - 20, twice over
    assert ops["fusion.2"] == pytest.approx(0.040)
    gaps = dict(r["breakdown"]["idle_gaps"])
    # device 0 idles 0-10 (job), 50-70 (host_half), 80-100 (job)
    assert gaps["bench.x.host_half"] == pytest.approx(0.020)
    assert gaps["bench.x.job"] == pytest.approx(0.030)


def test_window_clips_what_ran_outside_it():
    planes = synthetic()
    planes[2]["lines"][0]["events"][0] = [T.WINDOW_SPAN, 20 * MS, 40 * MS]  # 20..60
    r = T.reduce_trace(planes[:1] + planes[2:], "jit_step")
    assert r["busy_s"] == pytest.approx(0.030)            # 20..50 of the while
    assert r["kernel_runs"] == 0                          # it started before the window


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        T.reduce_trace(synthetic()[2:], "jit_step")


RECORDED = os.path.join(os.path.dirname(__file__), "data", "recorded_trace.json")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_trace_against_brute_force():
    with open(RECORDED) as f:
        planes = json.load(f)
    r = T.reduce_trace(planes, "jit_run_lane")
    dev = planes[0]
    ops = T._line(dev, T.OPS_LINE)
    window = next(e for e in T.host_spans(planes) if e[0] == T.WINDOW_SPAN)
    lo, hi = window[1], window[1] + window[2]
    # brute force: mark a fine grid over the window, interval by interval
    import numpy as np

    n = 400_000
    step = (hi - lo) / n
    grid = np.zeros(n, bool)
    for _name, start, dur in ops:
        a = int(np.ceil((max(start, lo) - lo) / step - 0.5))
        b = int(np.ceil((min(start + dur, hi) - lo) / step - 0.5))
        grid[max(a, 0):max(b, 0)] = True
    hit = int(grid.sum())
    assert r["busy_s"] == pytest.approx(hit * step / 1e9, rel=2e-3)
    assert sum(v for _k, v in T.self_times(ops).items()) == pytest.approx(
        r["busy_s"], rel=1e-6
    )
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["kernel_runs"] >= 1 and r["kernel_s"] > 0
