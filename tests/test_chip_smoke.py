"""What PR 21 (chip bring-up) added, checked on the CPU: chip_smoke.py's
phases at the tiny size, its refusals, and the rules that keep a lost chip
from reading as a slow run — compile-cache placement, backends that raise
instead of substituting, the fleet launcher's worker environment.

A module conftest does not list as heavy, so it runs before the tier-1
time cap bites. The smoke itself runs ONCE, in a child process (the verbs
set process-wide DEMI_* switches), on two virtual CPU devices so the
several-devices path is the one exercised.
"""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _cpu_env(devices: int) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    for key in list(env):
        if key.startswith("DEMI_"):
            del env[key]
    return env


@pytest.fixture(scope="module")
def tiny_run():
    proc = subprocess.run(
        [sys.executable, SMOKE, "--size", "tiny", "--expect-platform", "cpu"],
        env=_cpu_env(2), cwd=REPO, capture_output=True, text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    return lines, json.loads(lines[-2])["smoke"]["phases"]


def test_smoke_last_line_is_the_contract_object(tiny_run):
    lines, _phases = tiny_run
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 2},
    }
    # No benchmark, no claimed gain: the results record ends with it.
    assert lines[-2].endswith('"claim": null}')
    assert lines[0].startswith("[smoke] device: platform=cpu")


def test_smoke_sweep_modes_agree_lane_for_lane(tiny_run):
    _lines, phases = tiny_run
    runs = [
        phases[k]
        for k in ("sweep_continuous", "sweep_chunked", "sweep_chunked_warm")
    ]
    assert len({r["lanes_digest"] for r in runs}) == 1
    for r in runs:
        assert r["lanes"] == 64 and r["overflow_lanes"] == 0
        assert r["violations"] > 0 and r["unique_schedules"] > 0
        # Compile seconds are reported apart from run seconds. run_s is
        # their difference from the wall's, and a few ms either side of
        # zero when a loaded worker's phase is nearly all tracing.
        assert r["compile_s"] > 0 and r["wall_s"] > 0
        assert r["wall_s"] == pytest.approx(r["compile_s"] + r["run_s"], abs=0.01)


def test_smoke_lift_agrees_with_host_oracle(tiny_run):
    _lines, phases = tiny_run
    assert phases["lift"]["lanes_lifted"] == 2
    assert phases["lift"]["host_agrees"] is True


def test_smoke_reconfig_sweep_restarts_from_disk_and_lifts(tiny_run):
    """PR 47's phase: ``--app raft_reconfig`` under crash-recovery and
    partitions; the lifted lanes' host rows hold compactions, installed
    snapshots and restarts from the durable row."""
    _lines, phases = tiny_run
    r = phases["reconfig_sweep"]
    assert r["lanes"] == 32 and r["lanes_lifted"] == 3
    assert r["host_agrees"] is True and r["violations"] == 0
    assert r["commit"] >= 3 and r["compactions"] > 0 and r["restores"] > 0


def test_smoke_kafka_sweep_lifts_over_fifo_links(tiny_run):
    """PR 52's phase: ``--app kafka`` under crash-recovery and cuts over
    per-pair FIFO links; the lifted lanes' host rows hold elections, fenced
    fetches and restarts from the durable rows."""
    _lines, phases = tiny_run
    r = phases["kafka_sweep"]
    assert r["lanes"] == 32 and r["lanes_lifted"] in (3, 4)
    assert r["host_agrees"] is True and r["violations"] <= 1
    assert r["elected"] >= 6 and r["fenced"] > 0 and r["restores"] > 0


def test_smoke_dpor_runs_its_budget_then_finds_and_verifies(tiny_run):
    _lines, phases = tiny_run
    assert phases["dpor_rounds"]["interleavings"] == 16 * 2
    assert phases["dpor_find"]["host_verified"] is True
    assert phases["dpor_find"]["deliveries"] > 0


def test_smoke_minimize_verifies_mcs_and_replays(tiny_run):
    _lines, phases = tiny_run
    m = phases["minimize"]
    assert m["mcs_verified"] is True
    assert 0 < m["mcs_externals"] <= m["externals"]
    assert m["minimized_deliveries"] <= m["deliveries"]
    assert "fuzz" in phases and "replay" in phases


def test_smoke_uses_every_device_it_finds(tiny_run):
    _lines, phases = tiny_run
    for name in ("sweep_continuous", "sweep_chunked", "dpor_rounds", "minimize"):
        assert phases[name]["lane_sharding"]["devices"] == 2, name
    assert phases["sweep_chunked"]["lane_sharding"]["lanes_per_device"] == 16
    parity = phases["mesh_parity"]
    assert parity["one_device"]["devices"] == 1
    assert parity["all_devices"]["devices"] == 2


def test_smoke_builds_native_libraries_and_names_the_scan(tiny_run):
    _lines, phases = tiny_run
    native = phases["native"]
    assert native["libdemi_analysis"] == native["libdemi_records"] == "built"
    assert phases["dpor_rounds"]["racing_scan"] == "native"


def test_smoke_refuses_to_run_without_the_expected_platform():
    """Told to expect a TPU (the default) and finding a CPU, the smoke
    exits non-zero and prints no result."""
    proc = subprocess.run(
        [sys.executable, SMOKE], env=_cpu_env(1), cwd=REPO,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "expected a 'tpu' device" in proc.stderr


def test_smoke_phase_failure_is_the_scripts_failure(monkeypatch):
    """No except on the path turns a phase's failure into exit 0."""
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)

    def boom(_smoke):
        raise RuntimeError("device lost")

    monkeypatch.setattr(smoke, "phase_native", boom)
    with pytest.raises(RuntimeError, match="device lost"):
        smoke.main(["--size", "tiny", "--expect-platform", "cpu"])


# -- compile cache ---------------------------------------------------------

def test_compile_cache_dir_resolution():
    from demi_tpu.device import compile_cache_dir

    # Placed from outside: the program sets no directory in code.
    assert compile_cache_dir({"JAX_COMPILATION_CACHE_DIR": "/elsewhere"}) is None
    # The CPU test boot does not fill the checkout.
    assert compile_cache_dir({"JAX_PLATFORMS": "cpu"}) is None
    # Otherwise: one fixed path inside the checkout, git-ignored.
    for env in ({}, {"JAX_PLATFORMS": "tpu,cpu"}):
        assert compile_cache_dir(env) == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_dir_as_jax_sees_it(tmp_path):
    """Importing the package with JAX_COMPILATION_CACHE_DIR set leaves
    JAX's own reading of it in place; unset (and not CPU-pinned), JAX's
    config holds the in-checkout path. No backend is initialised."""
    code = (
        "import demi_tpu.device, jax; "
        "print(jax.config.jax_compilation_cache_dir)"
    )
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    outside = str(tmp_path / "cache")

    def cache_dir(extra):
        out = subprocess.run(
            [sys.executable, "-c", code], env={**env, **extra}, cwd=REPO,
            capture_output=True, text=True, timeout=120, check=True,
        )
        return out.stdout.strip().splitlines()[-1]

    assert cache_dir({"JAX_COMPILATION_CACHE_DIR": outside}) == outside
    assert cache_dir({}) == os.path.join(REPO, ".jax_cache")


def _raft_cfg(**overrides):
    from demi_tpu.apps.raft import make_raft_app
    from demi_tpu.device import DeviceConfig

    app = make_raft_app(3)
    return app, DeviceConfig.for_app(
        app, pool_capacity=32, max_steps=32, max_external_ops=8, **overrides
    )


def test_host_oracle_names_the_environment_it_needs(monkeypatch):
    import jax

    from demi_tpu.utils import hostjit

    def no_cpu_backend(backend=None):
        raise RuntimeError("Unknown backend cpu")

    hostjit._cpu_device.cache_clear()
    monkeypatch.setattr(jax, "local_devices", no_cpu_backend)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    try:
        with pytest.raises(RuntimeError, match="JAX_PLATFORMS=tpu,cpu"):
            hostjit.host_jit(lambda x: x + 1)(1)
    finally:
        hostjit._cpu_device.cache_clear()


# -- one process per chip --------------------------------------------------

def test_fleet_worker_environment_one_chip_each():
    from demi_tpu.fleet.coordinator import worker_environment

    base = {
        "JAX_PLATFORMS": "tpu,cpu", "XLA_FLAGS": "--xla_foo=1",
        # What a four-chip host's environment says of the whole host.
        "TPU_CHIPS_PER_HOST_BOUNDS": "2,2,1", "TPU_HOST_BOUNDS": "1,1,1",
        "TPU_RUNTIME_METRICS_PORTS": "8431,8432,8433,8434",
    }
    envs = [worker_environment(base, i, 4) for i in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
    assert [e["TPU_RUNTIME_METRICS_PORTS"] for e in envs] == [
        "8431", "8432", "8433", "8434",
    ]
    for e in envs:
        # The caller's platform choice passes through: no CPU pin.
        assert e["JAX_PLATFORMS"] == "tpu,cpu"
        assert e["XLA_FLAGS"] == "--xla_foo=1"
        for name in (
            "TPU_CHIPS_PER_PROCESS_BOUNDS", "TPU_PROCESS_BOUNDS",
            "TPU_CHIPS_PER_HOST_BOUNDS", "TPU_HOST_BOUNDS",
        ):
            assert e[name] == "1,1,1", name
        assert REPO in e["PYTHONPATH"].split(os.pathsep)
    # An unset JAX_PLATFORMS stays unset (JAX's own choice in the worker).
    assert "JAX_PLATFORMS" not in worker_environment({}, 0, 1)
    assert worker_environment({}, 0, 1)["TPU_VISIBLE_CHIPS"] == "0"


def test_fleet_worker_environment_cpu_only_when_the_caller_pinned_it():
    from demi_tpu.fleet.coordinator import worker_environment

    env = worker_environment(
        {"JAX_PLATFORMS": "cpu",
         "XLA_FLAGS": "--xla_force_host_platform_device_count=8 --xla_foo=1"},
        1, 2, devices_per_worker=2,
    )
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["XLA_FLAGS"].split() == [
        "--xla_foo=1", "--xla_force_host_platform_device_count=2",
    ]
    assert "TPU_VISIBLE_CHIPS" not in env
    # On an accelerator host: one chip each, or a lone worker with all.
    with pytest.raises(ValueError, match="one chip"):
        worker_environment({}, 0, 2, devices_per_worker=2)
    assert "TPU_VISIBLE_CHIPS" not in worker_environment(
        {}, 0, 1, devices_per_worker=4
    )


def test_fleet_coordinator_initialises_no_backend():
    """`demi_tpu fleet` end to end: the worker reports the device its
    runtime came up on, and the coordinator process — which plans rounds
    and derives lane seeds in NumPy — never initialised a JAX backend."""
    proc = subprocess.run(
        [sys.executable, "-m", "demi_tpu", "fleet", "--app", "broadcast",
         "--nodes", "3", "--bug", "x", "--workers", "1", "--batch", "4",
         "--rounds", "2", "--pool", "32", "--max-messages", "32",
         "--strict-io"],
        env=_cpu_env(1), cwd=REPO, capture_output=True, text=True,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    summary = json.loads(proc.stdout.strip().splitlines()[-1])
    assert summary["coordinator_backend_initialized"] is False
    assert summary["worker_returncodes"] == [0]
    assert summary["rounds"] >= 1
    device = summary["per_worker"]["w0"]["device"]
    assert device["platform"] == "cpu" and device["devices"] == 1


# -- results that can be compared across device counts ---------------------

def test_lanes_digest_is_order_free_and_content_sensitive():
    from demi_tpu.parallel.sweep import lanes_digest

    rng = np.random.default_rng(0)
    n = 257
    seeds = np.arange(n)
    statuses = rng.integers(2, 5, n)
    codes = rng.integers(0, 3, n)
    hashes = rng.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32)
    whole = lanes_digest(seeds, statuses, codes, hashes)
    perm = rng.permutation(n)
    assert lanes_digest(
        seeds[perm], statuses[perm], codes[perm], hashes[perm]
    ) == whole
    # Harvested in two parts (another chunking, another device count).
    parts = sum(
        lanes_digest(seeds[s], statuses[s], codes[s], hashes[s])
        for s in (slice(0, 100), slice(100, n))
    ) % (1 << 64)
    assert parts == whole
    changed = codes.copy()
    changed[17] += 1
    assert lanes_digest(seeds, statuses, changed, hashes) != whole
    # Two lanes swapping results is a different sweep.
    swapped = hashes.copy()
    swapped[[3, 4]] = swapped[[4, 3]]
    assert lanes_digest(seeds, statuses, codes, swapped) != whole


# -- native libraries are keyed on source content --------------------------

def test_native_build_is_keyed_on_source_content(tmp_path, monkeypatch):
    from demi_tpu.native import build

    monkeypatch.setattr(build, "BUILD_DIR", str(tmp_path / "_build"))
    src = tmp_path / "lib.cpp"
    src.write_text('extern "C" int answer() { return 1; }\n')
    first = build.build_library(str(src), "libprobe")
    if first is None:
        pytest.skip("no working C++ compiler in this environment")
    built_at = os.path.getmtime(first)
    # Same content: the library on disk is reused, whatever its mtime.
    os.utime(str(src))
    assert build.build_library(str(src), "libprobe") == first
    assert os.path.getmtime(first) == built_at
    # New content: a new library; the one built from other source goes.
    src.write_text('extern "C" int answer() { return 2; }\n')
    second = build.build_library(str(src), "libprobe")
    assert second != first and os.path.exists(second)
    assert not os.path.exists(first)
    # A stale library under the new name's stem is not trusted either:
    # the name carries the digest, so it is simply never looked up.
    assert os.path.basename(second).startswith("libprobe-")
    assert build.build_library(str(tmp_path / "missing.cpp"), "libprobe") is None


# -- small rules the bring-up added ----------------------------------------

def test_local_lane_mesh_rule():
    """Every local device when there is more than one and the batch
    splits evenly; else the single-device kernels. (conftest boots 8
    virtual devices.)"""
    import jax

    from demi_tpu.parallel.mesh import LANES, local_lane_mesh

    n = jax.local_device_count()
    assert n == 8
    assert local_lane_mesh().shape[LANES] == n  # padded drivers: any batch
    assert local_lane_mesh(4 * n).shape[LANES] == n
    assert local_lane_mesh(n + 1) is None
    assert local_lane_mesh(4) is None


def test_device_fields_and_lane_sharding_summary():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from demi_tpu.parallel.mesh import (
        LANES,
        device_fields,
        lane_sharding_summary,
        make_mesh,
    )

    assert device_fields() == {
        "platform": "cpu", "device_kind": "cpu",
        "devices": jax.local_device_count(),
    }
    x = jnp.arange(32)
    assert lane_sharding_summary(x) == {"devices": 1, "lanes_per_device": 32}
    mesh = make_mesh(jax.devices()[:4])
    sharded = jax.device_put(x, NamedSharding(mesh, PartitionSpec(LANES)))
    assert lane_sharding_summary(sharded) == {
        "devices": 4, "lanes_per_device": 8,
    }


def test_round_seeds_fold_to_the_round_keys():
    """The lease wire carries seeds; folding them where the kernel runs
    gives exactly the keys the in-process loop uses, in both key modes."""
    from demi_tpu.apps.common import dsl_start_events
    from demi_tpu.device.dpor_sweep import DeviceDPOR, lane_keys
    from demi_tpu.external_events import WaitQuiescence

    app, cfg = _raft_cfg(record_trace=True, record_parents=True)
    program = dsl_start_events(app) + [WaitQuiescence()]
    batch = [tuple(), ((1, 0, 1, 0, 0, 0, 0, 0, 0, 0, -1, -1),)]
    for mode in ("position", "content"):
        dpor = DeviceDPOR(app, cfg, program, batch_size=2, key_mode=mode)
        seeds = dpor._round_seeds(2, 40, batch=batch)
        assert seeds.dtype == np.uint32 and isinstance(seeds, np.ndarray)
        if mode == "position":
            assert seeds.tolist() == [40, 41]
        assert np.array_equal(
            np.asarray(lane_keys(seeds)),
            np.asarray(dpor._round_keys(2, 40, batch=batch)),
        )


def test_build_dpor_kernel_selects_the_sharded_twin(monkeypatch):
    import jax

    from demi_tpu.device import dpor_sweep
    from demi_tpu.parallel import mesh as mesh_mod

    app, cfg = _raft_cfg(record_trace=True, record_parents=True)
    calls = []
    monkeypatch.setattr(
        dpor_sweep, "make_dpor_kernel",
        lambda *a, **kw: calls.append(("plain", kw)) or "plain",
    )
    monkeypatch.setattr(
        mesh_mod, "shard_dpor_kernel",
        lambda *a, **kw: calls.append(("mesh", kw)) or "mesh",
    )
    monkeypatch.setattr(
        mesh_mod, "shard_dpor_sleep_kernel",
        lambda *a, **kw: calls.append(("mesh-sleep", kw)) or "mesh-sleep",
    )
    mesh = mesh_mod.make_mesh(jax.devices()[:2])
    assert dpor_sweep.build_dpor_kernel(app, cfg) == "plain"
    assert dpor_sweep.build_dpor_kernel(app, cfg, mesh=mesh) == "mesh"
    assert dpor_sweep.build_dpor_kernel(
        app, cfg, mesh=mesh, sleep_cap=4, start_state=True
    ) == "mesh-sleep"
    assert calls[2][1]["start_state"] is True


def test_scan_backend_names_what_serves_the_racing_scan(monkeypatch):
    from demi_tpu.native import analysis
    from demi_tpu.persist import supervisor as sup_mod

    monkeypatch.setattr(analysis, "_load_native", lambda: object())
    assert analysis.scan_backend() == "native"
    degraded = sup_mod.LaunchSupervisor(retries=0, strict=False)
    degraded._degrade("native.analysis", "test")
    monkeypatch.setattr(sup_mod, "SUPERVISOR", degraded)
    assert analysis.scan_backend() == "numpy"
    monkeypatch.setattr(sup_mod, "SUPERVISOR", sup_mod.LaunchSupervisor())
    monkeypatch.setattr(analysis, "_load_native", lambda: None)
    assert analysis.scan_backend() == "numpy"


def test_strict_io_error_keeps_the_failures_message():
    """A compiler's diagnostics live in str(exc); some reprs drop them."""
    from demi_tpu.persist.supervisor import LaunchSupervisor, StrictIOError

    class Opaque(Exception):
        def __repr__(self):
            return "Opaque(<object>)"

    def fail(_attempt):
        raise Opaque("vector types must have positive constant sizes")

    sup = LaunchSupervisor(retries=0, backoff=0.0, strict=True)
    with pytest.raises(StrictIOError, match="Opaque: vector types must"):
        sup.run(fail, label="sweep.launch")


def test_coordinator_holds_a_kernel_that_refuses_to_launch():
    from demi_tpu.fleet.coordinator import FleetCoordinator, build_fleet_workload

    workload = {"app": "broadcast", "nodes": 3, "bug": "x", "pool": 32,
                "max_messages": 32}
    app, cfg, program = build_fleet_workload(workload)
    co = FleetCoordinator(app, cfg, program, workload=workload, batch_size=4)
    try:
        with pytest.raises(RuntimeError, match="launches no kernels"):
            co.dpor.kernel(None, None, None)
    finally:
        co.close()
