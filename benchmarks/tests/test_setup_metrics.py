"""The ``setup.*`` per-layer metrics (``layer_metrics/setup.*.py`` over
``lib/setup_ledger.py``, PR 36): entries of ``per_layer`` looked up by
name, read from the program's own set-up ledger in a ``--trace 1`` run of
the tiny cells, cut at the end of the warm job, closing on the process's
age there, and absent, with no error, from a program without the ledger
(the PR's parent)."""

import json
import os
import time

import pytest

import tiny
from lib import cells, harness, setup_ledger

NAMES = [
    "setup.pre_program_s", "setup.import_s", "setup.native_build_s",
    "setup.build_s", "setup.trace_s", "setup.lower_s", "setup.compile_s",
    "setup.cache_load_s", "setup.cache_hit_share", "setup.first_job_s",
    "setup.unattributed_s",
]
SIX = [
    "raft5-sweep", "raft5-dpor", "raft5-nemesis-sweep", "raft5-sweep-x4",
    "bcast64-flood-sweep", "spark17-shuffle200-sweep",
]


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny.write(str(tmp_path_factory.mktemp("tiny")))


@pytest.fixture
def first_process():
    """The ledger as a process that has run no job holds it: a run of
    ``run.py`` is one process, this module runs several cells in one."""
    from demi_tpu import obs
    from demi_tpu.obs import spans

    obs.TRACER.clear()
    spans._reset_setup()
    yield
    obs.TRACER.clear()
    spans._reset_setup()


@pytest.mark.parametrize("name", NAMES)
def test_each_metric_is_an_entry_with_a_reader(name):
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        entries = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    share = name == "setup.cache_hit_share"
    assert entries == [{
        "name": name, "unit": "%" if share else "s",
        "better": "higher" if share else "lower",
        "source": "program_counter", "layer": "entry point / harness",
        "moves": "setup_s", "workloads": SIX,
    }]
    assert os.path.exists(
        os.path.join(tiny.BENCH, "layer_metrics", name + ".py")
    )


@pytest.mark.parametrize("cell,verb", [("tiny-sweep", "sweep"), ("tiny-dpor", "dpor")])
def test_a_traced_cell_reports_them_and_they_close_on_the_age(
    bench, first_process, cell, verb
):
    from demi_tpu import obs

    lines = []
    result = harness.run(
        bench, cell, 2**31 + 3636, 0.3, True, time.perf_counter(),
        require_tpu=False, log=lines.append,
    )
    assert result["correct"] is True, lines
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NAMES) <= set(metrics)
    for name in NAMES:
        assert metrics[name] is not None and metrics[name] >= 0, name
    found = obs.setup_ledger()
    assert found["first_job"]["verb"] == verb
    # cut at the warm job's end: the traced jobs and the window ran since
    assert obs.stage_totals()["setup.build"]["count"] >= found["stages"]["setup.build"]["count"]
    assert metrics["setup.first_job_s"] == found["stages"]["setup.first_job"]["seconds"]
    assert metrics["setup.trace_s"] == found["stages"]["compile.trace"]["seconds"]
    assert metrics["setup.native_build_s"] > 0 and metrics["setup.build_s"] > 0
    # the first job holds the compile path: this process compiled in it
    assert metrics["setup.trace_s"] + metrics["setup.lower_s"] > 0
    assert metrics["setup.first_job_s"] > metrics["setup.trace_s"]
    # the disjoint stages and what no stage names are the process's age
    parts = setup_ledger.disjoint(found)
    assert sum(parts.values()) + metrics["setup.unattributed_s"] == pytest.approx(
        found["first_job"]["end_s"], abs=1e-6
    )
    assert parts["pre_program"] == metrics["setup.pre_program_s"]


def test_a_program_without_the_ledger_gives_none_and_no_error(
    bench, first_process, monkeypatch
):
    from demi_tpu import obs

    cell = cells.load_cell(bench, "tiny-sweep")
    readers = {name: cells.load_reader(cell, name) for name in NAMES}
    # no job has ended: nothing to cut at
    with obs.stage("setup.build", what="t"):
        pass
    assert all(read(None) is None for read in readers.values())
    with obs.spans.first_job(obs.new_job(), "sweep"):
        assert all(read(None) is None for read in readers.values())
    for name, read in readers.items():
        if name != "setup.cache_hit_share":     # no compile request yet
            assert read(None) is not None, name
    # the parent: demi_tpu.obs has no such function
    monkeypatch.delattr(obs, "setup_ledger")
    assert all(read(None) is None for read in readers.values())
