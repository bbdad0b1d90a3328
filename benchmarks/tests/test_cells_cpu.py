"""Every verb's cell runs end to end at tiny size on the CPU through
``lib.harness.run`` with the look for a chip skipped, and writes no device
metric there; ``run.py`` itself refuses to run without a TPU; a job's
sub-seed repeats its digest exactly; every control comes out not correct.
"""

import os
import subprocess
import sys
import time

import pytest

import controls
import tiny
from lib import cells, harness, jobs

SEED = 2**31 + 12345  # the driver's seeds are large
DEVICE_METRICS = ("kernel_ns_per_lane_step", "device_idle_share",
                  "device_peak_hbm_mb", "roofline")


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny.write(str(tmp_path_factory.mktemp("tiny")))


def run(bench, cell, trace=False, seconds=0.5, seed=SEED):
    lines = []
    result = harness.run(
        bench, cell, seed, seconds, trace, time.perf_counter(),
        require_tpu=False, log=lines.append,
    )
    return result, lines


@pytest.mark.parametrize("cell", [c[0] for c in tiny.CELLS])
def test_cell_runs_end_to_end_on_cpu(bench, cell):
    result, lines = run(bench, cell)
    assert result["correct"] is True and result["failed"] == 0, lines
    assert result["attempted"] > 0
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert result["device"]["platform"] == "cpu"
    names = set(result["metrics"])
    assert "setup_s" in names and len(names) == 2
    assert all(m["value"] > 0 for m in result["metrics"].values())
    # every number compared is printed beside its limit
    assert sum("check " in ln and "(limit " in ln for ln in lines) >= 3


@pytest.mark.parametrize("cell", ["tiny-sweep", "tiny-dpor", "tiny-minimize"])
def test_traced_run_reports_layers_but_no_device_metric_on_cpu(bench, cell):
    result, lines = run(bench, cell, trace=True)
    assert result["correct"] is True, lines
    names = set(result["metrics"])
    verb = tiny.VERB_OF[cell]
    assert f"{verb}.window_compiles" in names and f"{verb}.job_cv" in names
    assert not [n for n in names if any(d in n for d in DEVICE_METRICS)]
    assert "busy_s" not in result["device"] and "breakdown" not in result
    assert "setup_s" not in names


def test_run_py_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(tiny.BENCH, "run.py"), "--workload",
         "raft5-sweep", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=tiny.ROOT, timeout=300,
    )
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]


def test_run_py_refuses_in_a_directory_without_the_program(tmp_path):
    import shutil

    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(
        tiny.BENCH, tmp_path / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "raft5-sweep",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=tmp_path, timeout=300,
    )
    assert proc.returncode not in (0, None)
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]


def test_a_sub_seed_repeats_its_job_exactly(bench):
    import jax

    cell = cells.load_cell(bench, "tiny-sweep")
    verb = cells.load_verb(cell)
    ctx = verb.setup(cell, jax.local_devices()[:1])
    job = jobs.Job(0, jobs.sub_seed(SEED, 3))
    a = verb.run_job(ctx, job)
    b = verb.run_job(ctx, jobs.Job(9, job.sub_seed))
    other = verb.run_job(ctx, jobs.Job(1, jobs.sub_seed(SEED, 4)))
    assert a["digest"] == b["digest"] and a["violations"] == b["violations"]
    assert other["digest"] != a["digest"]


CONTROL_CASES = [
    (cell, control)
    for cell, verb in (("tiny-sweep", "sweep"), ("tiny-dpor", "dpor"),
                       ("tiny-minimize", "minimize"))
    for control in controls.CONTROLS[verb]
]


@pytest.mark.parametrize(
    "cell,control", CONTROL_CASES, ids=[f"{c}-{f.__name__}" for c, f in CONTROL_CASES]
)
def test_control_comes_out_not_correct(bench, cell, control):
    verb = cells.load_verb(cells.load_cell(bench, cell))
    undo = control(verb)
    try:
        result, lines = run(bench, cell)
    finally:
        undo()
    assert result["correct"] is False, lines
    assert any("FAILED" in ln for ln in lines)
