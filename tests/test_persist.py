"""Durable exploration state (demi_tpu/persist): crash-safe checkpoint
store semantics, bit-identical save→load round-trips of every frontier
field, kill-and-resume parity on the seeded zoo fixtures, launch
supervisor retry/degradation, and the hardened cache/stage loaders."""

import json
import os
import shutil
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from demi_tpu.apps.broadcast import make_broadcast_app
from demi_tpu.apps.common import dsl_start_events, make_host_invariant
from demi_tpu.apps.raft import T_CLIENT, make_raft_app
from demi_tpu.config import SchedulerConfig
from demi_tpu.device import DeviceConfig
from demi_tpu.device.dpor_sweep import DeviceDPOR, steering_prescription
from demi_tpu.external_events import (
    MessageConstructor,
    Send,
    WaitQuiescence,
)
from demi_tpu.persist import (
    CheckpointMismatch,
    CheckpointStore,
    LaunchSupervisor,
    PreemptionGuard,
    StrictIOError,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# CheckpointStore semantics
# ---------------------------------------------------------------------------

def test_store_save_load_roundtrip(tmp_path):
    store = CheckpointStore(str(tmp_path))
    store.save({"a": {"x": [1, 2, 3]}}, meta={"command": "t", "k": 1})
    store.save({"a": {"x": [4]}, "b": "hello"}, meta={"command": "t", "k": 2})
    ckpt = store.load_latest()
    assert ckpt is not None
    assert ckpt.generation == 2
    assert ckpt.meta == {"command": "t", "k": 2}
    assert ckpt.sections == {"a": {"x": [4]}, "b": "hello"}
    assert store.stats["snapshots_written"] == 2
    assert store.stats["restore_hits"] == 1


def test_store_corrupt_falls_back_to_previous_generation(tmp_path):
    store = CheckpointStore(str(tmp_path))
    store.save({"a": {"gen": 1}}, meta={"command": "t"})
    store.save({"a": {"gen": 2}}, meta={"command": "t"})
    # Torn write: truncate the newest generation's section mid-file.
    with open(tmp_path / "ckpt-000002" / "a.json", "w") as f:
        f.write('{"gen":')
    ckpt = store.load_latest()
    assert ckpt is not None and ckpt.generation == 1
    assert ckpt.sections["a"] == {"gen": 1}
    assert store.stats["corrupt_fallbacks"] == 1
    # Both generations corrupt: degrade to None, never raise.
    with open(tmp_path / "ckpt-000001" / "a.json", "w") as f:
        f.write("garbage")
    store2 = CheckpointStore(str(tmp_path))
    assert store2.load_latest() is None
    assert store2.stats["corrupt_fallbacks"] == 2


def test_store_rejects_newer_format_version(tmp_path):
    store = CheckpointStore(str(tmp_path))
    store.save({"a": 1}, meta={})
    path = tmp_path / "ckpt-000001" / "MANIFEST.json"
    manifest = json.loads(path.read_text())
    manifest["format_version"] = 99
    path.write_text(json.dumps(manifest))
    assert CheckpointStore(str(tmp_path)).load_latest() is None


def test_store_keeps_last_k_and_ignores_tmp(tmp_path):
    store = CheckpointStore(str(tmp_path), keep=3)
    for i in range(5):
        store.save({"a": i}, meta={})
    assert store.generations() == [3, 4, 5]
    # A stale .tmp dir (crashed writer) is invisible to the loader and
    # swept by the next save.
    os.makedirs(tmp_path / "ckpt-000009.tmp")
    assert store.load_latest().sections["a"] == 4  # generation 5's value
    store.save({"a": 5}, meta={})
    assert not os.path.exists(tmp_path / "ckpt-000009.tmp")


# ---------------------------------------------------------------------------
# DeviceDPOR round-trips
# ---------------------------------------------------------------------------

def _seeded_fixture(name):
    """Deep seeded frontier (the bench config-9/10 recipe at test
    shape): fuzz a violating trace on the host, seed a DeviceDPOR with
    its steering prescription."""
    from demi_tpu.schedulers import RandomScheduler

    if name == "raft":
        app = make_raft_app(3, bug="multivote")
        program = dsl_start_events(app) + [
            Send(
                app.actor_name(i % 3),
                MessageConstructor(
                    lambda v=10 + i: (T_CLIENT, 0, v, 0, 0, 0, 0)
                ),
            )
            for i in range(2)
        ] + [WaitQuiescence()]
        budget = 80
    else:
        app = make_broadcast_app(3, reliable=False)
        program = dsl_start_events(app) + [
            Send(app.actor_name(0), MessageConstructor(lambda: (1, 5))),
            Send(app.actor_name(1), MessageConstructor(lambda: (1, 6))),
            WaitQuiescence(),
        ]
        budget = 48
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    fr = None
    for seed in range(12):
        r = RandomScheduler(
            config, seed=seed, max_messages=budget,
            invariant_check_interval=1,
        ).execute(program)
        if r.violation is not None:
            fr = r
            break
    assert fr is not None, f"no seed violation on {name}"
    trace = fr.trace
    trace.set_original_externals(list(program))
    from demi_tpu.device.batch_oracle import default_device_config

    cfg = default_device_config(
        app, trace, program, record_trace=True, record_parents=True,
    )
    presc = steering_prescription(app, cfg, trace, program)
    return app, cfg, program, presc


def _dpor_identity(d):
    return (
        d.explored, d._explored_log, d._explored_digests,
        d.frontier, d.original, d.max_distance, d.interleavings,
        d.round_batch, d.violation_codes,
        d._suppressed_digests, d._sleep_rows,
        {k: np.asarray(v).tolist() for k, v in d._guides.items()},
    )


@pytest.mark.parametrize("name", ["raft", "broadcast"])
def test_device_dpor_checkpoint_roundtrip_bit_identical(name, tmp_path):
    """Every frontier field survives save→(store JSON)→load
    bit-identically, and the restored instance's packed kernel inputs
    (prescriptions, sleep rows, node ordinals) equal the original's."""
    app, cfg, program, presc = _seeded_fixture(name)
    d = DeviceDPOR(app, cfg, program, batch_size=8, double_buffer=False,
                   prefix_fork=False)
    d.seed(presc)
    for _ in range(3):
        if not d.frontier:
            break
        d.explore(max_rounds=1)
    store = CheckpointStore(str(tmp_path))
    store.save({"dpor": d.checkpoint_state()}, meta={"command": "t"})
    loaded = store.load_latest().sections["dpor"]

    fresh = DeviceDPOR(app, cfg, program, batch_size=8,
                       double_buffer=False, prefix_fork=False)
    fresh.restore_state(loaded)
    assert _dpor_identity(fresh) == _dpor_identity(d)
    # Packed kernel inputs for the identical next round.
    if d.frontier:
        batch_a, _ = d._select_batch(d.frontier)
        batch_b, _ = fresh._select_batch(fresh.frontier)
        assert batch_a == batch_b
        assert np.array_equal(d._pack(batch_a), fresh._pack(batch_b))


def test_device_dpor_checkpoint_rejects_workload_mismatch(tmp_path):
    app, cfg, program, presc = _seeded_fixture("broadcast")
    d = DeviceDPOR(app, cfg, program, batch_size=8)
    payload = d.checkpoint_state()
    other = DeviceDPOR(app, cfg, program, batch_size=16)
    with pytest.raises(CheckpointMismatch):
        other.restore_state(payload)
    # Same shapes, different HANDLERS (seeded bug vs none): the name
    # alone can't tell them apart, the behavior fingerprint must.
    bugged = make_raft_app(3, bug="multivote")
    clean = make_raft_app(3)
    assert bugged.name == clean.name  # the collision being guarded
    cfg_r = DeviceConfig.for_app(
        bugged, pool_capacity=64, max_steps=40, max_external_ops=16,
        invariant_interval=1, record_trace=True, record_parents=True,
    )
    prog_r = dsl_start_events(bugged) + [WaitQuiescence()]
    payload_r = DeviceDPOR(
        bugged, cfg_r, prog_r, batch_size=8
    ).checkpoint_state()
    with pytest.raises(CheckpointMismatch):
        DeviceDPOR(clean, cfg_r, prog_r, batch_size=8).restore_state(
            payload_r
        )


@pytest.mark.parametrize("stored", ["vectorized", "legacy"])
def test_a_checkpoint_that_names_its_host_path(stored):
    """Checkpoints written before the per-lane 'legacy' host path went
    name the path that wrote them, in the workload a restore compares
    whole, and carry a tuple set only that path wrote. One that says
    'vectorized' restores and continues bit-identically; one that says
    'legacy' never kept the digest set and is refused by the key's
    name."""
    import json

    from demi_tpu.persist.checkpoint import _pack_rows

    app, cfg, program, presc = _seeded_fixture("raft")

    def new():
        d = DeviceDPOR(app, cfg, program, batch_size=8,
                       double_buffer=False, prefix_fork=False)
        d.seed(presc)
        return d

    d = new()
    d.explore(max_rounds=2)
    old = json.loads(json.dumps(d.checkpoint_state()))
    assert "host_path" not in old["workload"] and "suppressed" not in old
    old["workload"]["host_path"] = stored
    old["suppressed"] = _pack_rows([])
    fresh = new()
    if stored == "legacy":
        with pytest.raises(CheckpointMismatch, match="'host_path'"):
            fresh.restore_state(old)
        return
    fresh.restore_state(old)
    assert _dpor_identity(fresh) == _dpor_identity(d)
    d.explore(max_rounds=2)
    fresh.explore(max_rounds=2)
    assert _dpor_identity(fresh) == _dpor_identity(d)
    assert len(d.explored) > 1


@pytest.mark.parametrize("name", ["raft", "broadcast"])
def test_kill_and_resume_parity(name, tmp_path):
    """The acceptance pin: a run checkpointed at an arbitrary round
    boundary and resumed into a FRESH explorer converges to the
    uninterrupted run's exact state — same violation-code set, same
    first-found records, same explored/frontier — on raft + broadcast."""
    app, cfg, program, presc = _seeded_fixture(name)
    rounds = 5
    kill_at = 2

    def new():
        d = DeviceDPOR(app, cfg, program, batch_size=8,
                       double_buffer=False, prefix_fork=False)
        d.seed(presc)
        return d

    def drive(d, start, n, founds):
        done = start
        while done < n and d.frontier:
            f = d.explore(max_rounds=1)
            done += 1
            if f is not None:
                founds.append((f[0][: f[1]].tobytes(), int(f[1])))
        return done

    # Uninterrupted reference.
    ref = new()
    founds_ref = []
    drive(ref, 0, rounds, founds_ref)

    # Killed-and-resumed: checkpoint at the boundary, restore into a
    # fresh instance (the dead process's memory is gone), continue.
    store = CheckpointStore(str(tmp_path))
    a = new()
    founds_b = []
    done = drive(a, 0, kill_at, founds_b)
    store.save({"dpor": a.checkpoint_state()}, meta={"rounds_done": done})
    del a  # the "crash"
    b = new()
    ckpt = store.load_latest()
    b.restore_state(ckpt.sections["dpor"])
    drive(b, int(ckpt.meta["rounds_done"]), rounds, founds_b)

    assert b.violation_codes == ref.violation_codes
    assert founds_b[:1] == founds_ref[:1]
    assert b.explored == ref.explored
    assert b.frontier == ref.frontier
    assert b.interleavings == ref.interleavings


def test_sleep_set_state_roundtrip(tmp_path):
    """Sleep-mode durable state: frontier sleep rows ([B, sleep_cap,
    recw] packed input included), Mazurkiewicz class keys, wakeup
    guides, and the node wakeup ledger all survive bit-identically, and
    the resumed pruned run stays on the uninterrupted run's trajectory."""
    from demi_tpu.analysis import SleepSets, StaticIndependence

    app, cfg, program, presc = _seeded_fixture("raft")
    rel = StaticIndependence.for_app(app)

    def new():
        d = DeviceDPOR(
            app, cfg, program, batch_size=8, double_buffer=False,
            prefix_fork=False,
            sleep_sets=SleepSets(independence=rel, cap=4),
        )
        d.seed(presc)
        return d

    ref = new()
    for _ in range(3):
        if not ref.frontier:
            break
        ref.explore(max_rounds=1)

    a = new()
    for _ in range(2):
        a.explore(max_rounds=1)
    store = CheckpointStore(str(tmp_path))
    store.save({"dpor": a.checkpoint_state()}, meta={})
    b = new()
    b.restore_state(store.load_latest().sections["dpor"])
    assert b.sleep.classes == a.sleep.classes
    assert b.sleep._node_flips == a.sleep._node_flips
    assert b.sleep.pruned_total == a.sleep.pruned_total
    assert b._sleep_rows == a._sleep_rows
    assert set(b._guides) == set(a._guides)
    for k in a._guides:
        assert np.array_equal(a._guides[k], b._guides[k]), k
    if a.frontier:
        batch_a, _ = a._select_batch(a.frontier)
        batch_b, _ = b._select_batch(b.frontier)
        assert batch_a == batch_b
        assert np.array_equal(a._pack_sleep(batch_a), b._pack_sleep(batch_b))
        assert np.array_equal(a._sleep_from(batch_a), b._sleep_from(batch_b))
    # Continue the restored run to the reference horizon: same classes,
    # same explored set.
    if b.frontier:
        b.explore(max_rounds=1)
    assert b.explored == ref.explored
    assert b.sleep.classes == ref.sleep.classes
    assert b.violation_codes == ref.violation_codes


# ---------------------------------------------------------------------------
# Host DPORScheduler + controller round-trips
# ---------------------------------------------------------------------------

def test_host_dpor_checkpoint_roundtrip():
    from demi_tpu.schedulers.dpor import DPORScheduler

    app = make_broadcast_app(2, reliable=False)
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    program = dsl_start_events(app) + [
        Send(app.actor_name(0), MessageConstructor(lambda: (1, 5))),
        WaitQuiescence(),
    ]

    def new():
        return DPORScheduler(config, max_messages=40,
                             max_interleavings=6)

    ref = new()
    ref.explore(program)
    ref.explore(program)  # continue past the first budget

    a = new()
    a.explore(program)
    payload = json.loads(json.dumps(a.checkpoint_state()))
    b = new()
    b.restore_state(payload)
    assert b._explored == a._explored
    assert sorted(b._backtracks) == sorted(a._backtracks)
    assert b.interleavings_explored == a.interleavings_explored
    assert b.original_trace_ids == a.original_trace_ids
    b.explore(program)
    assert b._explored == ref._explored
    assert b.interleavings_explored == ref.interleavings_explored


def test_controller_and_fuzzer_roundtrip():
    from demi_tpu.fuzzing import Fuzzer, FuzzerWeights
    from demi_tpu.tune import ExplorationController

    class _Gen:
        def generate(self, rng, alive):
            return None

        def reset(self):
            pass

    fz = Fuzzer(
        num_events=4,
        weights=FuzzerWeights(send=0.5, kill=0.1, wait_quiescence=0.2),
        message_gen=_Gen(), prefix=[],
    )
    ctrl = ExplorationController(fz)
    for i in range(5):
        ctrl.begin_round()
        ctrl.end_round(hashes=[i, i + 1], violations=i % 2, lanes=2)
    payload = json.loads(json.dumps(ctrl.checkpoint_state()))

    fz2 = Fuzzer(
        num_events=4,
        weights=FuzzerWeights(send=0.5, kill=0.1, wait_quiescence=0.2),
        message_gen=_Gen(), prefix=[],
    )
    ctrl2 = ExplorationController(fz2)
    ctrl2.restore_state(payload)
    assert ctrl2.seen_hashes == ctrl.seen_hashes
    assert ctrl2.rounds == ctrl.rounds
    assert ctrl2.weight_tuner.checkpoint_state() == (
        ctrl.weight_tuner.checkpoint_state()
    )
    assert fz2.weights.as_dict() == fz.weights.as_dict()
    # The next proposal is identical — the resumed tuner continues the
    # same coordinate-descent trajectory.
    assert ctrl.weight_tuner.propose() == ctrl2.weight_tuner.propose()


def test_fuzz_resume_matches_uninterrupted():
    """runner.fuzz(start_execution=k) finds the same violation at the
    same execution count as the uninterrupted loop (executions are pure
    functions of (seed, i))."""
    from demi_tpu.runner import fuzz
    from demi_tpu.cli import build_app, build_fuzzer
    from demi_tpu.parallel.distributed import workload_args

    args = workload_args(dict(
        app="broadcast", nodes=3, bug="drop", seed=0, num_events=8,
        max_messages=60, timer_weight=0.2, kill_weight=0.05,
        partition_weight=0.0,
    ))
    app = build_app(args)
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    full = fuzz(config, build_fuzzer(app, args), max_executions=40,
                seed=0, max_messages=60, invariant_check_interval=1)
    assert full is not None
    k = max(0, full.executions - 2)
    resumed = fuzz(config, build_fuzzer(app, args), max_executions=40,
                   seed=0, max_messages=60, invariant_check_interval=1,
                   start_execution=k)
    assert resumed is not None
    assert resumed.executions == full.executions
    assert resumed.violation == full.violation


# ---------------------------------------------------------------------------
# Launch supervisor
# ---------------------------------------------------------------------------

def test_supervisor_retries_then_succeeds():
    sup = LaunchSupervisor(retries=2, backoff=0.0, strict=False)
    calls = []

    def flaky(attempt):
        calls.append(attempt)
        if attempt < 2:
            raise RuntimeError("poisoned")
        return "ok"

    assert sup.run(flaky, label="t") == "ok"
    assert calls == [0, 1, 2]
    assert sup.stats["retries"] == 2
    assert not sup.degraded("t")


def test_supervisor_degrades_permanently_to_fallback():
    sup = LaunchSupervisor(retries=1, backoff=0.0, strict=False)
    calls = []

    def broken(attempt):
        calls.append(attempt)
        raise RuntimeError("dead")

    assert sup.run(broken, label="t", fallback=lambda: "twin") == "twin"
    assert sup.degraded("t")
    assert sup.stats["degradations"] == 1
    # Degraded surface: straight to the fallback, no further attempts.
    n = len(calls)
    assert sup.run(broken, label="t", fallback=lambda: "twin") == "twin"
    assert len(calls) == n


def test_supervisor_strict_io_raises():
    sup = LaunchSupervisor(retries=0, backoff=0.0, strict=True)
    with pytest.raises(StrictIOError):
        sup.run(lambda a: (_ for _ in ()).throw(RuntimeError("x")),
                label="t", fallback=lambda: "twin")
    assert not sup.degraded("t")


def test_supervisor_no_fallback_reraises():
    sup = LaunchSupervisor(retries=1, backoff=0.0, strict=False)
    with pytest.raises(RuntimeError):
        sup.run(lambda a: (_ for _ in ()).throw(RuntimeError("x")),
                label="t")


def test_native_analysis_degrades_to_numpy_twin(monkeypatch):
    """A native analyzer that raises degrades permanently to the NumPy
    twin — same results, run survives."""
    from demi_tpu.native import analysis as na
    from demi_tpu.persist import supervisor as sup_mod

    sup = LaunchSupervisor(retries=0, backoff=0.0, strict=False)
    monkeypatch.setattr(sup_mod, "SUPERVISOR", sup)

    class _Boom:
        def __getattr__(self, name):
            def crash(*a, **kw):
                raise OSError("native analyzer crashed")

            return crash

    monkeypatch.setattr(na, "_load_native", lambda: _Boom())
    rng = np.random.RandomState(0)
    records = rng.randint(0, 4, size=(2, 10, 7)).astype(np.int32)
    records[:, :, 0] = 1
    lens = np.asarray([10, 10], np.int32)
    rows, offsets, lanes, digests = na.racing_prescriptions_batch(
        records, lens, 7
    )
    want = na._np_racing_prescriptions(
        np.ascontiguousarray(records[:, :, :7]), lens
    )
    assert np.array_equal(rows, want[0])
    assert sup.degraded("native.analysis")
    # Second call: straight to the twin (no retry storm).
    na.racing_prescriptions_batch(records, lens, 7)
    assert sup.stats["failures"] == 1


# ---------------------------------------------------------------------------
# Preemption guard + CLI subprocess (SIGTERM satellite)
# ---------------------------------------------------------------------------

def test_preemption_guard_sets_flag_and_restores_handler():
    prev = signal.getsignal(signal.SIGTERM)
    with PreemptionGuard() as guard:
        assert not guard.requested
        os.kill(os.getpid(), signal.SIGTERM)
        # Delivered synchronously in CPython's main thread on the next
        # bytecode boundary.
        time.sleep(0.01)
        assert guard.requested
        assert guard.signum == signal.SIGTERM
    assert signal.getsignal(signal.SIGTERM) is prev


def test_cli_sigterm_writes_loadable_checkpoint(tmp_path):
    """The CI contract: SIGTERM a `demi_tpu dpor --checkpoint-dir` run
    mid-round; it must exit 3 with a loadable, manifest-valid
    checkpoint in the directory."""
    ckdir = str(tmp_path / "ck")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("DEMI_OBS", None)
    proc = subprocess.Popen(
        [sys.executable, "-m", "demi_tpu", "dpor", "--app", "raft",
         "--bug", "multivote", "--nodes", "3", "--batch", "4",
         "--rounds", "500", "--max-messages", "60",
         "--checkpoint-dir", ckdir, "--checkpoint-every", "1"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=REPO,
    )
    deadline = time.time() + 180
    ready = False
    while time.time() < deadline:
        line = proc.stdout.readline()
        if "checkpointing to" in line:
            ready = True
            break
    assert ready, "dpor run never reached its checkpoint loop"
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=300)
    assert proc.returncode == 3, out
    assert '"preempted": true' in out
    store = CheckpointStore(ckdir)
    ckpt = store.load_latest()
    assert ckpt is not None
    assert ckpt.meta["command"] == "dpor"
    assert "dpor" in ckpt.sections
    # The payload is restorable into a fresh explorer of the recorded
    # shape.
    saved = ckpt.meta["cli_args"]
    app = make_raft_app(saved["nodes"], bug=saved["bug"])
    cfg = DeviceConfig.for_app(
        app, pool_capacity=saved["pool"],
        max_steps=saved["max_messages"],
        max_external_ops=max(
            16, saved["num_events"] + app.num_actors + 2
        ),
        invariant_interval=1, timer_weight=saved["timer_weight"],
        record_trace=True, record_parents=True,
    )
    program = dsl_start_events(app) + [WaitQuiescence()]
    d = DeviceDPOR(app, cfg, program, batch_size=saved["batch"])
    d.restore_state(ckpt.sections["dpor"])
    assert len(d.explored) >= 1
    # The round journal was written alongside the checkpoints and is a
    # contiguous 1..rounds_done prefix (SIGTERM lands at a round
    # boundary, so journal and checkpoint agree on the round count).
    from demi_tpu.obs import journal

    ok, rounds = journal.contiguous_rounds(
        journal.read_records(ckdir), "dpor.round"
    )
    assert ok and rounds, rounds
    assert rounds[-1] == ckpt.meta["rounds_done"]


# ---------------------------------------------------------------------------
# Hardened loaders (satellites)
# ---------------------------------------------------------------------------

def test_tuning_cache_corrupt_falls_back_with_counter(tmp_path, capsys):
    from demi_tpu import obs
    from demi_tpu.tune import TuningCache

    path = tmp_path / "tune.json"
    path.write_text('{"key": {"v":')  # torn write
    before = obs.counter("tune.cache_corrupt").total()
    cache = TuningCache(str(path))
    assert cache.get("key") is None  # degraded to empty, no raise
    assert obs.counter("tune.cache_corrupt").total() == before + 1
    assert "corrupt" in capsys.readouterr().err
    # Non-dict top level counts too.
    path2 = tmp_path / "tune2.json"
    path2.write_text("[1, 2]")
    assert TuningCache(str(path2)).get("key") is None
    assert obs.counter("tune.cache_corrupt").total() == before + 2
    # A merely-absent cache is NOT corruption.
    c3 = TuningCache(str(tmp_path / "nope.json"))
    assert c3.get("key") is None
    assert obs.counter("tune.cache_corrupt").total() == before + 2
    # The degraded cache still works read-write.
    cache.put("key", {"v": 1})
    assert cache.get("key") == {"v": 1}


def test_load_stage_truncated_returns_none(tmp_path, capsys):
    from demi_tpu import obs
    from demi_tpu.serialization import load_stage, save_stage
    from demi_tpu.trace import EventTrace

    d = str(tmp_path)
    save_stage(d, "s1", [], EventTrace([], []))
    assert load_stage(d, "s1") is not None
    # Truncate mid-file (the crashed-writer shape).
    path = os.path.join(d, "stage_s1.json")
    data = open(path).read()
    with open(path, "w") as f:
        f.write(data[: len(data) // 2])
    before = obs.counter("persist.stage_corrupt").total()
    assert load_stage(d, "s1") is None
    assert obs.counter("persist.stage_corrupt").total() == before + 1
    assert "truncated" in capsys.readouterr().err
    assert load_stage(d, "absent") is None  # absent stays silent


def test_load_dep_graph_corrupt_returns_none(tmp_path, capsys):
    from demi_tpu.fingerprints import FingerprintFactory
    from demi_tpu.serialization import load_dep_graph

    d = str(tmp_path)
    with open(os.path.join(d, "dep_graph.json"), "w") as f:
        f.write('[{"id": 1, "bad"')
    assert load_dep_graph(d, FingerprintFactory()) is None
    assert "corrupt" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# Report block
# ---------------------------------------------------------------------------

def test_report_durability_block(tmp_path):
    from demi_tpu.tools.report import render_report

    d = str(tmp_path)
    snap = {
        "counters": {
            "persist.snapshots_written": {"": 4.0},
            "persist.snapshot_bytes": {"": 123456.0},
            "persist.restore_hits": {"": 1.0},
            "persist.corrupt_fallbacks": {"": 1.0},
            "persist.launch_failures": {"label=dpor.launch": 2.0},
            "persist.launch_retries": {"label=dpor.launch": 2.0},
            "persist.degradations": {"label=native.analysis": 1.0},
            "tune.cache_corrupt": {"": 1.0},
        },
        "gauges": {},
        "histograms": {},
    }
    with open(os.path.join(d, "obs_snapshot.json"), "w") as f:
        json.dump(snap, f)
    text = render_report(d)
    assert "### Durability" in text
    assert "checkpoints written: 4" in text
    assert "corrupt snapshots degraded to a previous generation: 1" in text
    assert "launch failures: 2 (2 retried)" in text
    assert "surfaces degraded to host twins: 1" in text
    assert "corrupt tuning caches degraded to empty: 1" in text


# ---------------------------------------------------------------------------
# Journal continuity across kill-resume (obs/journal.py satellite)
# ---------------------------------------------------------------------------

def test_journal_contiguous_across_simulated_kill_resume(tmp_path, capsys):
    """A `dpor --checkpoint-dir` run journals one record per round; a
    resume from an OLDER generation (the SIGKILL shape: the dead run
    journaled rounds past the snapshot being restored) must continue the
    SAME journal with no duplicated and no missing rounds — the records
    past the restore point are truncated and re-journaled by the resumed
    incarnation."""
    from demi_tpu.cli import main
    from demi_tpu.obs import journal

    d = str(tmp_path / "ck")
    rc = main([
        "dpor", "--app", "raft", "--bug", "multivote", "--nodes", "3",
        "--batch", "8", "--rounds", "4", "--max-messages", "60",
        "--checkpoint-dir", d, "--checkpoint-every", "2",
    ])
    assert rc in (0, 1)
    want = json.loads(
        [line for line in capsys.readouterr().out.splitlines()
         if line.startswith("{")][-1]
    )
    ok, rounds = journal.contiguous_rounds(
        journal.read_records(d), "dpor.round"
    )
    assert ok and rounds == [1, 2, 3, 4]
    # Simulate the kill landing after the round-2 checkpoint: every
    # later generation is gone, but the journal still carries rounds
    # 3..4 from the dead run.
    gens = sorted(g for g in os.listdir(d) if g.startswith("ckpt-"))
    for g in gens[1:]:
        shutil.rmtree(os.path.join(d, g))
    rc = main(["resume", d])
    assert rc in (0, 1)
    got = json.loads(
        [line for line in capsys.readouterr().out.splitlines()
         if line.startswith("{")][-1]
    )
    recs = journal.read_records(d, "dpor.round")
    ok, rounds = journal.contiguous_rounds(
        journal.read_records(d), "dpor.round"
    )
    assert ok and rounds == [1, 2, 3, 4], rounds
    # Rounds 3..4 were re-journaled by the resumed incarnation.
    assert [r["inc"] for r in recs] == [0, 0, 1, 1]
    # And the resumed search itself converged identically (the PR 10
    # parity surface, re-checked here so journal truncation can never
    # mask a state divergence).
    for key in ("explored", "interleavings", "violation_codes",
                "rounds_done"):
        assert want[key] == got[key], key
    # The per-round records agree with the final summary.
    assert recs[-1]["explored"] == got["explored"]
    assert recs[-1]["interleavings"] == got["interleavings"]
