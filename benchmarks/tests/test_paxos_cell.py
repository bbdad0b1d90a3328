"""``paxos11-datagram-sweep`` (PR 44), rehearsed at tiny size on the CPU the
way ``tiny.write`` adds cells: the deployment of
``configs/paxos11-datagram.json`` cut to f = 1 (2 replicas, 2 leaders, 3
acceptors: 7 actors), ``log_cap`` 4 (an outbox of 13 rows, 12-word
messages), 512 steps, 32 fuzzed events and a pool of 128, under a traffic
file shaped like ``traffic/fuzz-continuous-paxos.json``, as one more cell of
the tiny benchmark (``tiny-paxos7-sweep``; ``tiny.py`` itself is a file the
benchmark has, so the cell is added here, as ``test_chain_cell.py`` adds its
own). The three per-layer metrics the PR brought are read from the program's
counts in the traced run, on a hand-made counter table, and are absent, with
no error, from a program or an app that keeps no such counts (the PR's
parent; raft). The plain reference replays the cell's own lanes, kept and
discarded deliveries included, and refuses a hand-made second delivery of a
consumed message; its control, the protocol as published, must part from
the program's seeded bug; the seeded bug over a network that repeats and
loses nothing violates in no lane. The real files are held to what ISSUE 44
fixed by looking their own entries up; nothing here pins the tail of
``per_layer``, the length of a list or the full list of sweep cells."""

import json
import os
import time

import pytest

import controls
import tiny
from lib import cells, harness, paxos_reference

CELL = "tiny-paxos7-sweep"
REAL_CELL = "paxos11-datagram-sweep"
METRICS = ("sweep.kept_delivery_share", "sweep.discarded_row_share",
           "sweep.preempts_per_schedule")


def real(relative):
    with open(os.path.join(tiny.BENCH, relative), encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("tiny"))
    path = tiny.write(tmp)
    config = real("configs/paxos11-datagram.json")
    config["workload"].update(
        nodes=7, log_cap=4, num_events=32, max_messages=512, pool=128,
        max_dups=32, max_drops=4,
    )
    config["shapes"].update(
        num_actors=7, state_width=24, msg_width=12, max_outbox=13,
        pool_capacity=128, max_steps=512, max_external_ops=41,
        max_dups=32, max_drops=4,
    )
    traffic = real("traffic/fuzz-continuous-paxos.json")
    traffic["job"].update(schedules=192, resident_lanes_per_chip=64)
    traffic["trace_seconds"] = 0.01
    traffic["check"] = {"lift_violating": 2, "lift_clean": 2}
    for sub, name, body in (
        ("configs", "tiny-paxos7", config),
        ("traffic", "tiny-fuzz-paxos", traffic),
    ):
        with open(os.path.join(tmp, "extra", sub, name + ".json"), "w") as f:
            json.dump(body, f)
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-paxos7", "file": "extra/configs/tiny-paxos7.json",
    })
    bench["workloads"].append({
        "name": CELL, "config": "tiny-paxos7", "traffic": "tiny-fuzz-paxos",
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


def run(bench, trace, cell=CELL, seed=2**31 + 4400):
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
    assert metrics["sweep.kept_delivery_share"] == pytest.approx(
        100.0 * counts["sweep.net.kept"] / counts["sweep.net.delivered"]
    )
    assert metrics["sweep.discarded_row_share"] == pytest.approx(
        100.0 * counts["sweep.net.discarded"] / counts["sweep.rows_inserted"]
    )
    assert metrics["sweep.preempts_per_schedule"] == pytest.approx(
        counts["sweep.app.preempts"] / counts["sweep.retired"]
    )
    assert metrics["sweep.commits_per_schedule"] == pytest.approx(
        counts["sweep.app.committed"] / counts["sweep.retired"]
    )
    assert counts["sweep.app.adoptions"] > 0
    # a quarter of the deliveries of actors' messages, under the budget
    assert 5 < metrics["sweep.kept_delivery_share"] < 25
    assert 0 < metrics["sweep.discarded_row_share"] < 2
    assert 0.5 < metrics["sweep.preempts_per_schedule"] < 20
    assert 0 < metrics["sweep.commits_per_schedule"] <= 4
    assert "sweep.insert_short_share" not in metrics   # an outbox of 13 rows


def test_a_raft_cell_reports_none_of_them(bench):
    """The readers on a program whose network repeats and loses nothing
    and whose app names no progress count: absent, no error."""
    from demi_tpu import obs

    obs.TRACER.clear()
    result, lines = run(bench, trace=True, cell="tiny-sweep", seed=2**31 + 4401)
    assert result["correct"] is True, lines
    assert not set(METRICS) & set(result["metrics"])
    cell = cells.load_cell(bench, CELL)
    assert "sweep.job" in obs.stage_totals()
    assert not [k for k in obs.stage_counts() if k.startswith("sweep.net.")]
    for name in METRICS:
        assert cells.load_reader(cell, name)(None) is None, name


@pytest.mark.parametrize("name,counts,value", [
    ("sweep.kept_delivery_share",
     {"sweep.net.kept": 130, "sweep.net.delivered": 1000}, 13.0),
    ("sweep.discarded_row_share",
     {"sweep.net.discarded": 9, "sweep.rows_inserted": 1000}, 0.9),
    ("sweep.preempts_per_schedule",
     {"sweep.app.preempts": 68, "sweep.retired": 8}, 8.5),
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


def test_the_stock_control_with_teeth_here(bench):
    """A pool too small fails the run. ``controls.sweep_corrupt_codes``
    is not run on this cell: it alters the codes of the last job's
    violating lanes, and a 192-lane job of this tiny deployment holds
    none as often as not (0 to 3 in the first jobs of twelve seeds, CPU,
    PR 44); ``test_chain_cell.py`` and ``test_vsr_cell.py`` hold it."""
    undo = controls.sweep_small_pool(
        cells.load_verb(cells.load_cell(bench, CELL))
    )
    try:
        result, lines = run(bench, trace=False)
    finally:
        undo()
    assert result["correct"] is False, lines


def test_the_plain_reference_agrees_on_the_cells_own_lanes(bench):
    from paxos_reference_on_chip import reference_check

    report = reference_check(bench, CELL, 2**31 + 4402, lanes=16, require_tpu=False)
    assert report["lanes"] == 16 and report["disagreeing"] == 0, report
    assert report["job_overflow"] == 0 and report["violating"] >= 1
    assert report["job_codes"] == [1] and 8 < report["peak_pending"] <= 128
    assert report["kept"] > 0 and report["discarded"] > 0


def test_the_protocol_as_published_parts_from_the_seeded_bug(bench):
    from paxos_reference_on_chip import reference_check

    report = reference_check(
        bench, CELL, 2**31 + 4402, lanes=16, require_tpu=False, control=True
    )
    assert report["control"] is True and report["violating"] >= 1
    assert report["violating_parted"] == report["violating"], report


def test_without_duplication_the_seeded_bug_violates_in_no_lane(bench):
    from paxos_reference_on_chip import reliable_control

    report = reliable_control(bench, CELL, 2**31 + 4402, require_tpu=False)
    assert report["lanes"] == 192 and report["overflow"] == 0
    assert report["violations"] == 0, report


def _records(*rows):
    return [list(r) + [0] * (15 - len(r)) for r in rows]


def test_the_reference_refuses_a_second_delivery_of_a_consumed_message():
    """A 7-actor deployment, ``log_cap`` 4: leader 2's P1A to acceptor 4
    kept once is delivered twice; consumed at its first delivery, the
    second is refused. So is a client's send that is kept."""
    starts = [(11, i, 0) for i in range(7)]           # REC_EXT_BASE + OP_START
    p1a = (2, 4, 4, 0)                                # src, dst, tag P1A, ballot 0
    kept = _records(*starts, (5,) + p1a, (1,) + p1a)
    outcome = paxos_reference.replay(7, 4, kept, len(kept))
    assert (outcome.kept, outcome.deliveries, outcome.code) == (1, 2, 0)
    assert outcome.digests[4][:2] == ("acceptor", 0)
    consumed = _records(*starts, (1,) + p1a, (1,) + p1a)
    with pytest.raises(paxos_reference.Diverged, match="consumed"):
        paxos_reference.replay(7, 4, consumed, len(consumed))
    request = (13, 0, 0, 1, 1)                        # OP_SEND REQUEST(1) to replica 0
    client = _records(*starts, request, (5, 7, 0, 1, 1))
    with pytest.raises(paxos_reference.Diverged, match="exactly once"):
        paxos_reference.replay(7, 4, client, len(client))


def test_the_reference_is_plain():
    with open(paxos_reference.__file__, encoding="utf-8") as f:
        code = f.read().split('"""')[2]
    assert "import jax" not in code and "demi_tpu" not in code
    assert "numpy" not in code


def test_the_real_entries_are_what_the_issue_fixed():
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "paxos11-datagram", "fuzz-continuous-paxos", 1
    )
    assert len(cell["why"]) <= 200
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, unit, better in zip(
        METRICS, ("%", "%", "1/schedule"), ("higher", "lower", "higher"),
    ):
        new = by_name[name]
        assert new["workloads"] == [REAL_CELL], name
        assert (new["moves"], new["source"], new["better"], new["unit"],
                new["layer"]) == (
            "schedules_per_s", "program_counter", better, unit, "kernels"
        )
        assert os.path.exists(
            os.path.join(tiny.BENCH, "layer_metrics", name + ".py")
        )
    # every metric the older sweep cells share, the short pass's and the
    # commits' (looked up by the cells an older metric already lists)
    for name, metric in by_name.items():
        if "raft5-nemesis-sweep" in metric.get("workloads", ()):
            assert REAL_CELL in metric["workloads"], name
    assert REAL_CELL in by_name["sweep.insert_short_share"]["workloads"]
    assert REAL_CELL in by_name["sweep.commits_per_schedule"]["workloads"]
    assert REAL_CELL in by_name["explore_segment_roofline"]["workloads"]
    rate = next(m for m in bench["end_to_end"] if m["name"] == "schedules_per_s")
    assert REAL_CELL in rate["workloads"]
    entry = next(c for c in bench["configs"] if c["name"] == "paxos11-datagram")
    config = real("configs/paxos11-datagram.json")
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["source"].startswith(
        "van Renesse & Altinbuken, Paxos Made Moderately Complex"
    )
    assert "Paxos Made Simple, sec. 2.1" in entry["source"]
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == [
        "chips", "log_cap", "max_messages", "schedules",
    ]
    assert config["architecture"] is None
    workload = config["workload"]
    assert (workload["app"], workload["nodes"], workload["log_cap"],
            workload["bug"]) == ("paxos", 11, 32, "count_replies")
    assert workload["num_events"] == 96
    assert workload["max_messages"] in (3072, 4096, 6144, 8192)
    assert workload["pool"] in (512, 1024) and workload["max_kills"] == 4
    assert (workload["timer_weight"], workload["send_weight"],
            workload["wait_weight"], workload["hard_kill_weight"],
            workload["restart_weight"], workload["partition_weight"],
            workload["kill_weight"]) == (0.2, 0.60, 0.28, 0.12, 0.0, 0.0, 0.0)
    assert workload["wait_budget"] == [1, 40]
    assert 0.05 <= workload["dup_weight"] <= 0.25
    assert (workload["drop_weight"], workload["max_dups"],
            workload["max_drops"]) == (0.02, 256, 16)
    assert config["shapes"] == {
        "num_actors": 11, "state_width": 136, "msg_width": 68,
        "max_outbox": 161, "pool_capacity": workload["pool"],
        "max_steps": workload["max_messages"], "max_external_ops": 109,
        "invariant_interval": 1, "datagram": True,
        "dup_weight": workload["dup_weight"], "drop_weight": 0.02,
        "max_dups": 256, "max_drops": 16, "msg_dtype": "int32",
    }
    assert set(config["assumed"]) <= set(workload)
    assert all(workload[k] == v for k, v in config["assumed"].items())
    assert len(config["departures_from_the_paper"]) >= 5 and config["guarantees"]
    assert any("exactly once" in g for g in config["guarantees"])
    paxos = real("traffic/fuzz-continuous-paxos.json")
    assert paxos["job"] in (
        {"schedules": 2048, "resident_lanes_per_chip": 1024, "mode": "continuous"},
        {"schedules": 1024, "resident_lanes_per_chip": 512, "mode": "continuous"},
        {"schedules": 512, "resident_lanes_per_chip": 256, "mode": "continuous"},
        {"schedules": 4096, "resident_lanes_per_chip": 2048, "mode": "continuous"},
    )
    assert paxos["check"] == {"lift_violating": 8, "lift_clean": 16}
    chain = real("traffic/fuzz-continuous-chain.json")
    same = ("verb", "panel", "seed_changes", "seed_keeps", "end_to_end",
            "trace_seconds", "check")
    assert all(paxos[k] == chain[k] for k in same)


def test_the_real_shapes_are_what_the_program_builds():
    import dataclasses

    from demi_tpu.parallel.distributed import build_workload

    config = real("configs/paxos11-datagram.json")
    app, cfg, _fuzzer = build_workload(dict(config["workload"]))
    have = dataclasses.asdict(cfg)
    assert {k: have[k] for k in config["shapes"]} == config["shapes"]
    # the file refuses a program that forgets the discipline or a knob
    for loose in (
        dataclasses.replace(cfg, datagram=False, dup_weight=0.0, drop_weight=0.0),
        dataclasses.replace(cfg, max_dups=0),
    ):
        assert {
            k: dataclasses.asdict(loose)[k] for k in config["shapes"]
        } != config["shapes"]
    assert app.channels == "datagram"
