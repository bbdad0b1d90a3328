"""CLI integration: the full subcommand surface driven in-process on the
(unreliable) broadcast fixture — fuzz saves an experiment, minimize
shrinks it with device-batched trials, replay reproduces, sweep counts
violations, shiviz/dot export."""

import json
import os

import pytest

from demi_tpu.cli import main


@pytest.fixture(scope="module")
def exp_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("exp")
    rc = main([
        "fuzz", "--app", "broadcast", "--nodes", "4", "--bug", "unreliable",
        "--max-executions", "50", "-o", str(d),
    ])
    assert rc == 0
    return d


def _common(exp):
    return ["--app", "broadcast", "--nodes", "4", "--bug", "unreliable",
            "-e", str(exp)]


def test_cli_minimize(exp_dir, capsys):
    rc = main(["minimize"] + _common(exp_dir))
    assert rc == 0
    out = capsys.readouterr().out
    assert "MCS + minimized trace saved" in out
    assert "trials" in out  # device-batched stages report trial counts


def test_cli_replay(exp_dir, capsys):
    rc = main(["replay"] + _common(exp_dir))
    assert rc == 0
    assert "violation" in capsys.readouterr().out


def test_cli_sweep(capsys):
    rc = main([
        "sweep", "--app", "broadcast", "--nodes", "4", "--bug", "unreliable",
        "--batch", "32", "--pool", "64", "--max-messages", "96",
    ])
    assert rc == 0
    data = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert data["lanes"] == 32
    assert data["violations"] > 0


def test_cli_shiviz_and_dot(exp_dir, capsys, tmp_path):
    rc = main(["shiviz"] + _common(exp_dir))
    assert rc == 0
    # ShiViz log lines: "<node> {<vector-clock JSON>}"
    assert '{"' in capsys.readouterr().out

    out_file = tmp_path / "exp.dot"
    rc = main(["dot"] + _common(exp_dir) + ["-o", str(out_file)])
    assert rc == 0
    text = out_file.read_text()
    assert text.startswith("digraph trace {")


def test_cli_report(exp_dir, capsys):
    rc = main(["minimize"] + _common(exp_dir)) if not (exp_dir / "mcs.json").exists() else 0
    assert rc == 0
    rc = main(["report", "-e", str(exp_dir)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "# Experiment report" in out
    assert "## Violation" in out
    assert "External reduction" in out


def test_cli_bridge_fuzz(capsys):
    import sys

    rc = main([
        "bridge-fuzz",
        "--launcher", f"{sys.executable} -m demi_tpu.bridge.demo_app --bug",
        "--send", '["go"]', "--to", "client", "--num-sends", "2",
        "--max-executions", "10",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "registered actors: client, server, monitor" in out
    assert "violation" in out
    assert "MCS verified" in out


def test_cli_minimize_peek_rejects_unsupported_combos(exp_dir):
    import pytest as _pytest

    with _pytest.raises(SystemExit, match="device-batched"):
        main(["minimize"] + _common(exp_dir) + ["--peek", "3", "--host"])
    with _pytest.raises(SystemExit, match="never peeks"):
        main(["minimize"] + _common(exp_dir)
             + ["--peek", "3", "--strategy", "incddmin"])
    with _pytest.raises(SystemExit, match=">= 0"):
        main(["minimize"] + _common(exp_dir) + ["--peek", "-1"])


def test_cli_bridge_fuzz_stream_app_with_invariant(capsys, monkeypatch):
    import os
    import sys

    fixtures = os.path.join(os.path.dirname(__file__), "fixtures")
    monkeypatch.syspath_prepend(fixtures)
    # The spawned launcher child must import demi_tpu. Prepend the repo
    # but keep whatever PYTHONPATH already carries (the TPU plugin site),
    # and never leave an empty entry (CPython reads '' as cwd).
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.setenv(
        "PYTHONPATH",
        os.pathsep.join(
            p for p in (repo, os.environ.get("PYTHONPATH")) if p
        ),
    )
    rc = main([
        "bridge-fuzz",
        "--launcher",
        f"{sys.executable} {os.path.join(fixtures, 'tcp_counter_main.py')}",
        "--num-sends", "0", "--max-executions", "10",
        "--invariant", "tcp_counter_main:lost_update",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "violation" in out and "MCS verified" in out


def test_cli_lint_zoo_clean_json(capsys):
    """CI contract: `demi_tpu lint demi_tpu.apps --format json` exits 0
    with zero error-level findings on the bundled zoo."""
    rc = main(["lint", "demi_tpu.apps", "demi_tpu.bridge.demo_app",
               "--format", "json"])
    out = capsys.readouterr().out
    assert rc == 0
    data = json.loads(out)
    assert data["counts"]["error"] == 0
    assert set(data["counts"]) == {"total", "error", "warning", "info"}


def test_cli_lint_flags_seeded_fixture(tmp_path, capsys):
    bad = tmp_path / "bad_app.py"
    bad.write_text(
        "import time\n"
        "def handler(actor_id, state, snd, msg):\n"
        "    return state, time.time()\n"
    )
    rc = main(["lint", str(bad)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "wall-clock" in out
    assert f"{bad}:3" in out
    assert "hint:" in out

    # JSON mode carries rule/severity/location for tooling.
    rc = main(["lint", str(bad), "--format", "json"])
    data = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert data["counts"]["error"] == 1
    f = data["findings"][0]
    assert f["rule"] == "wall-clock" and f["line"] == 3


def test_cli_dpor_sleep_sets(capsys):
    """`demi_tpu dpor --sleep-sets`: the summary JSON carries the
    sleep-set ledger (prune counts by kind, classes, redundancy ratio)
    next to the interleaving count."""
    rc = main([
        "dpor", "--app", "broadcast", "--nodes", "3", "--bug", "unreliable",
        "--batch", "8", "--rounds", "2", "--pool", "32",
        "--max-messages", "48", "--sleep-sets",
    ])
    out = capsys.readouterr().out
    summary = json.loads(out.strip().splitlines()[-1])
    assert rc in (0, 1)  # found / exhausted are both valid outcomes
    assert summary["interleavings"] > 0
    sleep = summary["sleep_sets"]
    for key in ("pruned", "classes", "explored", "redundancy_ratio"):
        assert key in sleep, key
    for kind in ("sleep", "class"):
        assert kind in sleep["pruned"], kind


def test_cli_stats_prom_smoke(tmp_path, capsys):
    """`demi_tpu stats --prom` renders a saved snapshot in the
    Prometheus text exposition (tier-1, no TTY, no live run)."""
    snap = {
        "counters": {"dpor.interleavings": {"": 42}},
        "gauges": {"dpor.host_share": {"": 0.5}},
        "histograms": {},
    }
    p = tmp_path / "snap.json"
    p.write_text(json.dumps(snap))
    rc = main(["stats", "-i", str(p), "--prom"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "# TYPE demi_dpor_interleavings_total counter" in out
    assert "demi_dpor_interleavings_total 42" in out
    assert "demi_dpor_host_share 0.5" in out


def test_cli_top_once_smoke(tmp_path, capsys):
    """`demi_tpu top DIR --once` renders one dashboard frame from a
    journaled directory and exits 0 (tier-1, no TTY needed)."""
    from demi_tpu.obs import journal

    d = str(tmp_path)
    j = journal.RoundJournal(d)
    for i in range(3):
        j.emit(
            "dpor.round", round=i + 1, wall_s=0.5, host_s=0.4,
            device_s=0.1, batch=8, depth=40, fresh=10, redundant=2,
            distance_pruned=0, violations=[7] if i == 2 else [],
            frontier=100 + i, explored=50 + i, interleavings=8 * (i + 1),
            inflight_hits=0, inflight_waste=0,
        )
    j.emit("sweep.chunk", round=1, lanes=32, wall_s=0.2, violations=3,
           codes={"7": 3}, unique=30, overflow=0)
    j.emit("minimize.level", round=1, stage="ddmin", wall_s=0.1,
           candidates=4, granularity=2, externals=10, adopted=True)
    j.close()
    rc = main(["top", d, "--once"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "demi_tpu top" in out
    assert "DPOR  round 3" in out
    assert "rounds/sec" in out
    assert "frontier 102" in out
    assert "violations: codes [7]" in out
    assert "SWEEP  chunk 1" in out
    assert "MINIMIZE" not in out or "level 1" in out
    # An empty dir renders a helpful frame instead of crashing.
    empty = tmp_path / "empty"
    empty.mkdir()
    rc = main(["top", str(empty), "--once"])
    assert rc == 0
    assert "no journal records yet" in capsys.readouterr().out


def test_cli_top_once_fleet_panel(tmp_path, capsys):
    """`demi_tpu top DIR --once` over a coordinator journal renders the
    FLEET panel (workers alive, leases outstanding, global class
    frontier, aggregate interleavings/sec, per-worker round share)."""
    from demi_tpu.obs import journal

    d = str(tmp_path)
    j = journal.RoundJournal(d)
    j.emit("fleet.worker", worker="w0", event="hello", workers_alive=1)
    j.emit("fleet.worker", worker="w1", event="hello", workers_alive=2)
    for i in range(4):
        j.emit(
            "fleet.round", round=i + 1, worker=f"w{i % 2}", lease=i,
            wall_s=0.05, busy_s=0.04, host_s=0.01, batch=16, fresh=6,
            redundant=1, violations=[], frontier=40 - i, explored=10 + i,
            interleavings=16 * (i + 1), classes=9 + i, warm_skips=3,
            workers_alive=2, leases_outstanding=2,
        )
    j.close()
    rc = main(["top", d, "--once"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "FLEET  round 4" in out
    assert "workers alive 2" in out
    assert "leases outstanding 2" in out
    assert "global class frontier 12" in out
    assert "aggregate interleavings/sec" in out
    assert "rounds by worker" in out and "w0" in out and "w1" in out
    assert "warm-start skips 3" in out


def test_cli_dpor_profile_rounds(tmp_path, capsys, monkeypatch):
    """`dpor --profile-rounds N`: the summary carries the launch-shape
    ledger and the evidence lands in the tuning cache under the
    profile=launch workload key (the cost model's input)."""
    cache_path = tmp_path / "tune.json"
    monkeypatch.setenv("DEMI_TUNE_CACHE", str(cache_path))
    rc = main([
        "dpor", "--app", "broadcast", "--nodes", "3", "--bug",
        "unreliable", "--batch", "8", "--rounds", "2",
        "--max-messages", "60", "--profile-rounds", "1",
        "--profile-trace", str(tmp_path / "trace"),
    ])
    assert rc in (0, 1)
    out = capsys.readouterr().out
    summary = json.loads(
        [line for line in out.splitlines() if line.startswith("{")][-1]
    )
    prof = summary["launch_profile"]
    assert prof["profile"] == "launch" and prof["source"] == "measured"
    kinds = {(r["kernel"], r["kind"]) for r in prof["launches"]}
    assert ("dpor", "dispatch") in kinds
    assert ("dpor", "block") in kinds
    for row in prof["launches"]:
        assert row["launches"] >= 1 and row["seconds"] >= 0
    # Persisted evidence is a TuningCache consumer away.
    from demi_tpu.tune import TuningCache

    key = summary["launch_profile_cache"]["key"]
    assert "profile=launch" in key
    assert TuningCache(str(cache_path)).get(key)["launches"]
    # The stage table of the same run, whose spans feed the ledger's
    # dispatch and block rows; the trace holds them as demi.<stage>.
    stages = summary["stages"]
    assert stages["dpor.search"]["count"] == 1
    block = next(
        r for r in prof["launches"]
        if (r["kernel"], r["kind"]) == ("dpor", "block")
    )
    assert stages["dpor.block"]["count"] == block["launches"]
    import glob

    import jax

    (pb,) = glob.glob(
        str(tmp_path / "trace" / "plugins" / "profile" / "*" / "*.xplane.pb")
    )
    names = {
        ev.name
        for plane in jax.profiler.ProfileData.from_file(pb).planes
        for line in plane.lines for ev in line.events
    }
    assert {"demi.dpor.round", "demi.dpor.block"} <= names


_SWITCHES = ("DEMI_PREFIX_FORK", "DEMI_ASYNC_MIN", "DEMI_HOST_SHARDS")


def _recorded(monkeypatch, cls):
    """Every ``cls`` built from here on, in order."""
    built = []
    init = cls.__init__

    def recording(self, *args, **kw):
        init(self, *args, **kw)
        built.append(self)

    monkeypatch.setattr(cls, "__init__", recording)
    return built


def _last_json(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_cli_dpor_tells_its_engine_and_leaves_no_switch_behind(
    monkeypatch, capsys
):
    """`dpor --prefix-fork --async-min --host-shards 2` builds its
    DeviceDPOR with a forker and two shards under an async oracle; a
    second `dpor` in the same process with none of the flags builds one
    with none of them, because the first wrote nothing into
    os.environ."""
    from demi_tpu.device.dpor_sweep import DeviceDPOR, DeviceDPOROracle

    for name in _SWITCHES:
        monkeypatch.delenv(name, raising=False)
    dpors = _recorded(monkeypatch, DeviceDPOR)
    oracles = _recorded(monkeypatch, DeviceDPOROracle)
    argv = [
        "dpor", "--app", "raft", "--nodes", "2", "--bug", "multivote",
        "--batch", "8", "--rounds", "2", "--pool", "64",
        "--max-messages", "48", "--num-events", "6",
    ]
    rc = main(argv + ["--prefix-fork", "--async-min", "--host-shards", "2"])
    told = _last_json(capsys)
    assert rc in (0, 1)
    assert len(dpors) == len(oracles) == 1
    assert dpors[0]._forker is not None and dpors[0]._host_shards == 2
    assert oracles[0].supports_async
    # On a CPU an in-flight launch burns the host's own cores: the
    # oracle's rule keeps them off here and on elsewhere
    # (tests/test_async_dpor.py holds the rule off a CPU).
    assert dpors[0]._double_buffer is False
    assert "prefix_fork" in told and told["async"]["inflight_rounds"] == 0
    assert not [name for name in _SWITCHES if name in os.environ]

    rc = main(argv)
    plain = _last_json(capsys)
    assert rc in (0, 1)
    assert len(dpors) == len(oracles) == 2
    assert dpors[1]._forker is None and dpors[1]._host_shards == 1
    assert dpors[1]._sharder is None and dpors[1]._double_buffer is False
    assert not oracles[1].supports_async
    assert "prefix_fork" not in plain and "async" not in plain
    assert plain["interleavings"] == told["interleavings"]
    assert not [name for name in _SWITCHES if name in os.environ]


def test_cli_sweep_tells_its_driver_and_leaves_no_switch_behind(
    monkeypatch, capsys
):
    """The same pair for `sweep --prefix-fork`: the first call's
    SweepDriver forks, the second's does not."""
    from demi_tpu.parallel.sweep import SweepDriver

    monkeypatch.delenv("DEMI_PREFIX_FORK", raising=False)
    drivers = _recorded(monkeypatch, SweepDriver)
    argv = [
        "sweep", "--app", "broadcast", "--nodes", "4", "--bug",
        "unreliable", "--batch", "32", "--pool", "64",
        "--max-messages", "96",
    ]
    assert main(argv + ["--prefix-fork"]) == 0
    forked = _last_json(capsys)
    assert drivers[-1].fork_stats is not None and "prefix_fork" in forked
    assert "DEMI_PREFIX_FORK" not in os.environ

    assert main(argv) == 0
    plain = _last_json(capsys)
    assert drivers[-1].fork_stats is None and "prefix_fork" not in plain
    assert plain["lanes_digest"] == forked["lanes_digest"]
    assert "DEMI_PREFIX_FORK" not in os.environ
