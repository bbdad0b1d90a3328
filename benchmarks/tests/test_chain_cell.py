"""``chain7-fifo-sweep`` (PR 40), rehearsed at tiny size on the CPU the way
``tiny.write`` adds cells: the deployment of ``configs/chain7-fifo.json``
cut to 4 servers, ``log_cap`` 8 (an outbox of 10 rows), 384 deliveries, 32
fuzzed events and a pool of 64, under a traffic file shaped like
``traffic/fuzz-continuous-chain.json``, as one more cell of the tiny
benchmark (``tiny-chain4-sweep``; ``tiny.py`` itself is a file the benchmark
has, so the cell is added here, as ``test_vsr_cell.py`` adds its own). The
three per-layer metrics the PR brought are read from the program's counts in
the traced run, on a hand-made counter table, and are absent, with no error,
from a program or an app that keeps no such counts (the PR's parent; raft).
The plain reference replays the cell's own lanes and refuses a hand-made
out-of-order sequence; its control, the protocol as published, must part
from the program's seeded bug; the protocol as published over channels that
keep no order violates. The real files are held to what ISSUE 40 fixed;
nothing here pins the tail of ``per_layer`` or the full list of sweep
cells."""

import json
import os
import time

import pytest

import controls
import tiny
from lib import cells, chain_reference, harness

CELL = "tiny-chain4-sweep"
REAL_CELL = "chain7-fifo-sweep"
METRICS = ("sweep.fifo_blocked_share", "sweep.resend_row_share",
           "sweep.commits_per_schedule")


def real(relative):
    with open(os.path.join(tiny.BENCH, relative), encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("tiny"))
    path = tiny.write(tmp)
    config = real("configs/chain7-fifo.json")
    config["workload"].update(
        nodes=4, log_cap=8, num_events=32, max_messages=384, pool=64,
    )
    config["shapes"].update(
        num_actors=4, state_width=23, max_outbox=10, pool_capacity=64,
        max_steps=384, max_external_ops=38,
    )
    traffic = real("traffic/fuzz-continuous-chain.json")
    traffic["job"].update(schedules=192, resident_lanes_per_chip=64)
    traffic["trace_seconds"] = 0.01
    traffic["check"] = {"lift_violating": 2, "lift_clean": 2}
    for sub, name, body in (
        ("configs", "tiny-chain4", config),
        ("traffic", "tiny-fuzz-chain", traffic),
    ):
        with open(os.path.join(tmp, "extra", sub, name + ".json"), "w") as f:
            json.dump(body, f)
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-chain4", "file": "extra/configs/tiny-chain4.json",
    })
    bench["workloads"].append({
        "name": CELL, "config": "tiny-chain4", "traffic": "tiny-fuzz-chain",
        "chips": 1,
    })
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if metric["name"] in METRICS:
            metric["workloads"] = [CELL]
        elif "tiny-sweep" in metric.get("workloads", ()):
            metric["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return path


def run(bench, trace, cell=CELL, seed=2**31 + 4040):
    lines = []
    result = harness.run(
        bench, cell, seed, 0.3, trace, time.perf_counter(),
        require_tpu=False, log=lines.append,
    )
    return result, lines


def test_the_cell_runs_end_to_end_on_cpu(bench):
    result, lines = run(bench, trace=False)
    assert result["correct"] is True and result["failed"] == 0, lines
    assert result["attempted"] >= 192
    assert set(result["metrics"]) == {"schedules_per_s", "setup_s"}
    assert sum("check " in ln and "(limit " in ln for ln in lines) >= 3


def test_the_traced_run_reports_the_new_metrics(bench):
    from demi_tpu import obs

    obs.TRACER.clear()
    result, lines = run(bench, trace=True)
    assert result["correct"] is True, lines
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    counts = obs.stage_counts()
    assert metrics["sweep.row_lowered_share"] == 100.0
    assert metrics["sweep.fifo_blocked_share"] == pytest.approx(
        100.0 - 100.0 * counts["sweep.fifo_head_rows"]
        / counts["sweep.fifo_pending_rows"]
    )
    assert metrics["sweep.resend_row_share"] == pytest.approx(
        100.0 * counts["sweep.app.resent"] / counts["sweep.rows_inserted"]
    )
    assert metrics["sweep.commits_per_schedule"] == pytest.approx(
        counts["sweep.app.committed"] / counts["sweep.retired"]
    )
    assert counts["sweep.app.reconfigs"] > 0
    assert 0 < metrics["sweep.fifo_blocked_share"] < 100
    assert 0 < metrics["sweep.resend_row_share"] < 50
    assert 2 < metrics["sweep.commits_per_schedule"] <= 8
    assert "sweep.insert_short_share" not in metrics   # an outbox of 10 rows
    assert metrics["sweep.fault_op_share"] > 5
    assert metrics["sweep.quiesced_share"] == 100.0    # no timer: all quiesce


def test_a_raft_cell_reports_none_of_them(bench):
    """The readers on a program whose channels keep no order and whose app
    names no progress count: absent, no error."""
    from demi_tpu import obs

    obs.TRACER.clear()
    result, lines = run(bench, trace=True, cell="tiny-sweep", seed=2**31 + 4041)
    assert result["correct"] is True, lines
    assert not set(METRICS) & set(result["metrics"])
    cell = cells.load_cell(bench, CELL)
    assert "sweep.job" in obs.stage_totals()
    assert "sweep.fifo_pending_rows" not in obs.stage_counts()
    for name in METRICS:
        assert cells.load_reader(cell, name)(None) is None, name


@pytest.mark.parametrize("name,counts,value", [
    ("sweep.fifo_blocked_share",
     {"sweep.fifo_head_rows": 300, "sweep.fifo_pending_rows": 1000}, 70.0),
    ("sweep.resend_row_share",
     {"sweep.app.resent": 32, "sweep.rows_inserted": 1000}, 3.2),
    ("sweep.commits_per_schedule",
     {"sweep.app.committed": 456, "sweep.retired": 8}, 57.0),
])
def test_a_reader_on_a_hand_made_counter_table(bench, name, counts, value):
    from demi_tpu import obs

    read = cells.load_reader(cells.load_cell(bench, CELL), name)
    obs.TRACER.clear()
    assert read(None) is None   # no tables' root: the parent's untraced run
    obs.enable()
    try:
        with obs.span("sweep.job"):  # the parent's traced job: the root, no count
            obs.stage_count("sweep.retired", 8)
            obs.stage_count("sweep.rows_inserted", 1000)
        assert read(None) is None
        obs.TRACER.clear()
        with obs.span("sweep.job"):
            for key, n in counts.items():
                obs.stage_count(key, n)
        assert read(None) == pytest.approx(value)
    finally:
        obs.disable()
        obs.TRACER.clear()


@pytest.mark.parametrize("control,correct", [
    (controls.sweep_small_pool, False),
    (controls.sweep_corrupt_codes, False),
])
def test_the_stock_controls_on_this_cell(bench, control, correct):
    undo = control(cells.load_verb(cells.load_cell(bench, CELL)))
    try:
        result, lines = run(bench, trace=False)
    finally:
        undo()
    assert result["correct"] is correct, lines


def test_the_plain_reference_agrees_on_the_cells_own_lanes(bench):
    from chain_reference_on_chip import reference_check

    report = reference_check(bench, CELL, 2**31 + 4042, lanes=16, require_tpu=False)
    assert report["lanes"] == 16 and report["disagreeing"] == 0, report
    assert report["job_overflow"] == 0 and report["violating"] >= 1
    assert report["job_codes"] == [1] and 8 < report["peak_pending"] <= 64


def test_the_protocol_as_published_parts_from_the_seeded_bug(bench):
    from chain_reference_on_chip import reference_check

    report = reference_check(
        bench, CELL, 2**31 + 4042, lanes=16, require_tpu=False, control=True
    )
    assert report["control"] is True and report["violating"] >= 1
    assert report["violating_parted"] == report["violating"], report


def test_without_the_discipline_the_protocol_as_published_violates(bench):
    from chain_reference_on_chip import any_channels_control

    report = any_channels_control(bench, CELL, 2**31 + 4042, require_tpu=False)
    assert report["lanes"] == 192 and report["overflow"] == 0
    assert report["violations"] > 96, report


def _records(*rows):
    return [list(r) + [0] * (6 - len(r)) for r in rows]


def test_the_references_fifo_check_fails_on_an_out_of_order_sequence():
    """Two updates to the head of a 2-server chain: delivered as sent the
    reference replays them; the second first, it refuses the delivery."""
    start = [(11, 0, 0), (11, 1, 0)]                 # REC_EXT_BASE + OP_START
    sends = [(13, 0, 0, 1, 1, 0), (13, 0, 0, 1, 2, 0)]   # UPDATE(1), UPDATE(2)
    in_order = _records(
        *start, *sends, (1, 2, 0, 1, 1, 0), (1, 2, 0, 1, 2, 0),
    )
    outcome = chain_reference.replay(2, 4, in_order, len(in_order))
    assert outcome.hists[0] == [1, 2] and outcome.code == 0
    reordered = _records(*start, *sends, (1, 2, 0, 1, 2, 0))
    with pytest.raises(chain_reference.Diverged, match="not the head of its queue"):
        chain_reference.replay(2, 4, reordered, len(reordered))


def test_the_reference_is_plain():
    with open(chain_reference.__file__, encoding="utf-8") as f:
        code = f.read().split('"""')[2]
    assert "import jax" not in code and "demi_tpu" not in code
    assert "numpy" not in code


def test_the_real_entries_are_what_the_issue_fixed():
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "chain7-fifo", "fuzz-continuous-chain", 1
    )
    assert len(cell["why"]) <= 200
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, unit, better, layer in zip(
        METRICS, ("%", "%", "commits/schedule"), ("lower", "higher", "higher"),
        ("kernels", "kernels", "entry point / harness"),
    ):
        new = by_name[name]
        assert new["workloads"] == [REAL_CELL], name
        assert (new["moves"], new["source"], new["better"], new["unit"],
                new["layer"]) == (
            "schedules_per_s", "program_counter", better, unit, layer
        )
        assert os.path.exists(
            os.path.join(tiny.BENCH, "layer_metrics", name + ".py")
        )
    # every metric the older sweep cells share, and the short pass's
    for name, metric in by_name.items():
        if "raft5-nemesis-sweep" in metric.get("workloads", ()):
            assert REAL_CELL in metric["workloads"], name
    assert REAL_CELL in by_name["sweep.insert_short_share"]["workloads"]
    assert REAL_CELL in by_name["explore_segment_roofline"]["workloads"]
    assert sum(REAL_CELL in m.get("workloads", ()) for m in by_name.values()) == 37
    rate = next(m for m in bench["end_to_end"] if m["name"] == "schedules_per_s")
    assert REAL_CELL in rate["workloads"]
    entry = next(c for c in bench["configs"] if c["name"] == "chain7-fifo")
    config = real("configs/chain7-fifo.json")
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["source"].startswith(
        "van Renesse & Schneider, Chain Replication for Supporting High "
        "Throughput and Availability, OSDI 2004"
    )
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == [
        "chips", "log_cap", "max_messages", "schedules",
    ]
    assert config["architecture"] is None
    workload = config["workload"]
    assert (workload["app"], workload["nodes"], workload["log_cap"],
            workload["bug"]) == ("chain", 7, 64, "no_resend")
    assert (workload["num_events"], workload["max_messages"]) == (96, 2048)
    assert workload["pool"] in (256, 512) and workload["max_kills"] == 3
    assert (workload["timer_weight"], workload["send_weight"],
            workload["wait_weight"], workload["hard_kill_weight"],
            workload["restart_weight"], workload["partition_weight"],
            workload["kill_weight"]) == (1.0, 0.55, 0.25, 0.08, 0.12, 0.0, 0.0)
    assert workload["wait_budget"] == [1, 40]
    assert config["shapes"] == {
        "num_actors": 7, "state_width": 79, "msg_width": 3, "max_outbox": 66,
        "pool_capacity": workload["pool"], "max_steps": 2048,
        "max_external_ops": 105, "invariant_interval": 1,
        "srcdst_fifo": True, "msg_dtype": "int32",
    }
    assert set(config["assumed"]) <= set(workload)
    assert all(workload[k] == v for k, v in config["assumed"].items())
    assert len(config["departures_from_the_paper"]) >= 5 and config["guarantees"]
    assert any("FIFO" in g for g in config["guarantees"])
    chain = real("traffic/fuzz-continuous-chain.json")
    assert chain["job"] in (
        {"schedules": 8192, "resident_lanes_per_chip": 2048, "mode": "continuous"},
        {"schedules": 4096, "resident_lanes_per_chip": 1024, "mode": "continuous"},
    )
    assert chain["check"] == {"lift_violating": 8, "lift_clean": 16}
    deep = real("traffic/fuzz-continuous-deep.json")
    same = ("verb", "panel", "seed_changes", "seed_keeps", "end_to_end",
            "trace_seconds", "check")
    assert all(chain[k] == deep[k] for k in same)


def test_the_real_shapes_are_what_the_program_builds():
    import dataclasses

    from demi_tpu.parallel.distributed import build_workload

    config = real("configs/chain7-fifo.json")
    app, cfg, _fuzzer = build_workload(dict(config["workload"]))
    have = dataclasses.asdict(cfg)
    assert {k: have[k] for k in config["shapes"]} == config["shapes"]
    # the file refuses a program that forgets the discipline
    loose = dataclasses.asdict(dataclasses.replace(cfg, srcdst_fifo=False))
    assert loose["srcdst_fifo"] != config["shapes"]["srcdst_fifo"]
    assert app.channels == "fifo"
