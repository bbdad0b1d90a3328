"""Tier-1 bench smoke: the bench.py sections run at tiny shapes and emit
their JSON keys. bench drift previously had no coverage — a renamed or
dropped key surfaced only on the next (scarce) TPU window.

NOTE: the config-14 (multi-tenant service) smoke lives in
tests/test_zzz_service.py and the config-17 (differential exploration)
smoke in tests/test_zzzz_bench_delta.py, not here — the 870s tier-1
cap truncates the suite tail, so new heavy tests must collect AFTER
every existing file instead of pushing seed tests past the cap
(dots-vs-seed is the tier-1 metric)."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_bench(config: str, env_extra: dict) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    # The smoke must measure the DEFAULT paths: strip switches that would
    # change kernels or output keys.
    for var in ("DEMI_OBS", "DEMI_AUTOTUNE", "DEMI_PREFIX_FORK",
                "DEMI_ASYNC_MIN", "DEMI_BENCH_IMPL",
                "DEMI_STATIC_PRUNE", "DEMI_SANITIZE", "DEMI_SLEEP_SETS"):
        env.pop(var, None)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--config", config],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=420,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    record = json.loads(out.stdout.strip().splitlines()[-1])
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in record, (key, record)
    return record


def test_bench_config2_smoke():
    record = _run_bench("2", {"DEMI_BENCH_DPOR_ROUNDS": "1"})
    assert record["metric"].startswith("interleavings/sec")
    section = record["config2"]
    for key in ("app", "batch", "rounds", "interleavings",
                "interleavings_per_sec", "frontier", "explored", "seconds",
                "host_seconds", "device_seconds", "host_share",
                "device_share"):
        assert key in section, key
    assert record["value"] == section["interleavings_per_sec"]
    assert section["interleavings"] > 0
    if section["host_share"] is not None:
        assert 0.0 <= section["host_share"] <= 1.0
        assert abs(
            section["host_share"] + section["device_share"] - 1.0
        ) < 1e-6
    # Static-commutativity A/B: pruning must have removed ONLY no-op
    # flips (bench asserts it internally; the keys + invariants are the
    # smoke contract) and must actually prune on the raft fixture.
    static = section["static"]
    for key in ("static_pruned", "explored_without", "explored_with",
                "removed_prescriptions", "interleavings_match",
                "noop_only", "commuting_tag_pairs"):
        assert key in static, key
    assert static["noop_only"] is True
    assert static["interleavings_match"] is True
    assert sum(static["static_pruned"].values()) > 0
    assert (
        static["explored_without"] - static["explored_with"]
        == static["removed_prescriptions"]
    )


def test_bench_config5_smoke():
    record = _run_bench("5", {"DEMI_BENCH_CONFIG5_LANES": "24"})
    assert record["metric"].startswith("schedules/sec")
    section = record["config5"]
    for key in ("actors", "mode", "lanes", "schedules_per_sec",
                "unique_schedules", "violations", "seconds",
                "overflow_lanes", "host_seconds", "device_seconds",
                "host_share", "device_share"):
        assert key in section, key
    assert section["lanes"] == 24
    if section["host_share"] is not None:
        assert 0.0 <= section["host_share"] <= 1.0


def test_bench_config3_smoke():
    record = _run_bench("3", {})
    assert record["metric"].startswith("oracle replays/sec")
    section = record["config3"]
    assert "error" not in section, section
    for key in ("app", "externals", "mcs_externals", "ddmin_levels",
                "replays", "replays_per_sec", "seconds"):
        assert key in section, key
    assert section["replays"] > 0
    assert section["mcs_externals"] <= section["externals"]


def test_bench_config6_smoke():
    record = _run_bench(
        "6",
        {
            "DEMI_BENCH_CONFIG6_BUDGET": "16",
            "DEMI_BENCH_CONFIG6_CANDIDATES": "8",
            "DEMI_BENCH_CONFIG6_REPS": "1",
        },
    )
    assert record["metric"].startswith("oracle trials/sec")
    section = record["config6"]
    assert "error" not in section, section
    for key in ("app", "deliveries", "candidates", "reps",
                "scratch_trials_per_sec", "fork_trials_per_sec", "speedup",
                "verdicts_match", "prefix_hit_rate", "steps_saved",
                "forked_lanes", "scratch_lanes", "fork_groups"):
        assert key in section, key
    # The acceptance-grade speedup needs the DEEP level (bench default);
    # at smoke depth only the bit-exactness contract is asserted.
    assert section["verdicts_match"] is True
    assert section["forked_lanes"] > 0


def test_bench_config7_smoke():
    record = _run_bench(
        "7",
        {
            # Tiny end-to-end pipeline: shallow violation scan, one rep.
            "DEMI_BENCH_CONFIG7_BUDGET": "120",
            "DEMI_BENCH_CONFIG7_SEEDS": "10",
            "DEMI_BENCH_CONFIG7_COMMANDS": "0",
            "DEMI_BENCH_CONFIG7_REPS": "1",
        },
    )
    assert record["metric"].startswith("pipeline speedup")
    section = record["config7"]
    assert "error" not in section, section
    for key in ("app", "deliveries", "externals", "mcs_externals",
                "final_deliveries", "ddmin_levels", "reps",
                "sync_seconds", "async_seconds", "speedup",
                "verdicts_match", "mcs_match",
                "speculation_hits", "speculation_waste", "spec_exec_hits",
                "spec_exec_waste",
                "lowering_cache_hit_rate", "overlap_fraction", "launches",
                "fork"):
        assert key in section, key
    for key in ("prefix_hit_rate", "parent_trunks", "steps_saved"):
        assert key in section["fork"], key
    # The acceptance-grade >=1.3x needs the DEEP fixture (bench default);
    # at smoke depth only the bit-exactness contract is asserted.
    assert section["verdicts_match"] is True
    assert section["mcs_match"] is True


def test_bench_config9_smoke():
    record = _run_bench(
        "9",
        {
            # Tiny A/B: shallow seed scan, few rounds, no strict-
            # reduction requirement (the class duplicates that make the
            # reduction strict need the deep default frontier).
            "DEMI_BENCH_CONFIG9_BUDGET": "120",
            "DEMI_BENCH_CONFIG9_SEEDS": "10",
            "DEMI_BENCH_CONFIG9_BATCH": "8",
            "DEMI_BENCH_CONFIG9_ROUNDS": "3",
            "DEMI_BENCH_CONFIG9_STRICT": "0",
        },
    )
    assert record["metric"].startswith("redundancy ratio")
    section = record["config9"]
    assert "error" not in section, section
    for key in ("app", "seed_deliveries", "batch", "rounds", "sleep_cap",
                "explored_base", "explored_pruned", "explored_reduction",
                "classes_base", "classes_pruned",
                "redundancy_ratio_base", "redundancy_ratio_pruned",
                "ratio_gap", "sleep_pruned", "violations_match",
                "found_match", "violation_codes",
                "rounds_per_sec_base", "rounds_per_sec_pruned"):
        assert key in section, key
    # The A/B identity contracts the bench asserts internally, echoed
    # into the JSON: violations and first-found records bit-identical,
    # and pruning never admits MORE schedules or a WORSE ratio.
    assert section["violations_match"] is True
    assert section["found_match"] is True
    assert section["explored_pruned"] <= section["explored_base"]
    assert (
        section["redundancy_ratio_pruned"]
        <= section["redundancy_ratio_base"]
    )
    for key in ("sleep", "class"):
        assert key in section["sleep_pruned"], key
    assert record["value"] == section["redundancy_ratio_pruned"]


def test_bench_config10_smoke():
    record = _run_bench(
        "10",
        {
            # Tiny durability A/B: shallow seed scan, few rounds, one
            # checkpoint generation.
            "DEMI_BENCH_CONFIG10_BUDGET": "120",
            "DEMI_BENCH_CONFIG10_SEEDS": "10",
            "DEMI_BENCH_CONFIG10_BATCH": "8",
            "DEMI_BENCH_CONFIG10_ROUNDS": "4",
            "DEMI_BENCH_CONFIG10_EVERY": "2",
        },
    )
    assert record["metric"].startswith("checkpoint overhead %")
    section = record["config10"]
    assert "error" not in section, section
    for key in ("app", "seed_deliveries", "batch", "rounds",
                "checkpoint_every", "explored", "violation_codes",
                "snapshots_written", "snapshot_bytes",
                "rounds_per_sec_plain", "rounds_per_sec_checkpointed",
                "checkpoint_overhead_pct", "time_to_resume_s",
                "restore_match"):
        assert key in section, key
    # The identity contracts the bench asserts internally, echoed into
    # the JSON: snapshotting changes nothing, and the cold restore is
    # bit-identical to the writer's final state.
    assert section["restore_match"] is True
    assert section["snapshots_written"] >= 1
    assert section["snapshot_bytes"] > 0
    assert section["time_to_resume_s"] >= 0
    assert record["value"] == section["checkpoint_overhead_pct"]


def test_bench_config12_smoke():
    record = _run_bench(
        "12",
        {
            # Tiny streaming-vs-staged A/B: short shallow sweep, one
            # frame (shallow traces keep the replay shapes — and their
            # compiles — small).
            "DEMI_BENCH_CONFIG12_LANES": "32",
            "DEMI_BENCH_CONFIG12_CHUNK": "8",
            "DEMI_BENCH_CONFIG12_MAX_MCS": "1",
            "DEMI_BENCH_CONFIG12_STEPS": "96",
        },
    )
    assert record["metric"].startswith("MCSes/hour speedup")
    section = record["config12"]
    assert "error" not in section, section
    for key in ("app", "lanes", "chunk", "max_mcs", "split", "violations",
                "mcs_count", "ttf_mcs_staged_s", "ttf_mcs_streaming_s",
                "wall_staged_s", "wall_streaming_s", "mcs_per_hour_staged",
                "mcs_per_hour_streaming", "speedup", "mcs_match",
                "codes_match", "tiers_interleaved", "queue",
                "journal_enqueues", "journal_frames", "budget"):
        assert key in section, key
    for key in ("enqueued", "done", "skipped", "depth", "max_depth"):
        assert key in section["queue"], key
    # The acceptance-grade >=1.3x MCSes/hour needs the DEEP fixture
    # (bench default lanes); at smoke shapes only the identity
    # contracts — bit-identical MCS artifacts and violation codes — are
    # asserted (the bench asserts them internally too).
    assert section["mcs_match"] is True
    assert section["codes_match"] is True
    assert section["mcs_count"] >= 1
    assert section["journal_frames"] == section["queue"]["done"]
    assert record["value"] == section["speedup"]


def test_bench_config13_smoke():
    record = _run_bench(
        "13",
        {
            # Tiny fleet curve: shallow seed scan, two rounds, ONE
            # worker count (each fleet run pays a worker-process jax
            # startup + compile, the dominant smoke cost; multi-worker
            # parity is tests/test_fleet.py's job). The scaling
            # thresholds need the default shapes, so strict is off and
            # only the identity contracts — coverage/violation parity,
            # zero warm re-exploration — are asserted; the bench
            # asserts them internally too.
            "DEMI_BENCH_CONFIG13_ROUNDS": "2",
            "DEMI_BENCH_CONFIG13_WORKERS": "1",
            "DEMI_BENCH_CONFIG13_BUDGET": "120",
            "DEMI_BENCH_CONFIG13_SEEDS": "4",
            "DEMI_BENCH_CONFIG13_BATCH": "8",
            "DEMI_BENCH_CONFIG13_STRICT": "0",
        },
    )
    assert record["metric"].startswith("aggregate interleavings/sec")
    section = record["config13"]
    assert "error" not in section, section
    for key in ("app", "batch", "rounds", "seed_deliveries", "baseline",
                "curve", "scaling", "coverage_match", "violations_match",
                "warm_start"):
        assert key in section, key
    for key in ("interleavings", "explored", "classes", "violation_codes",
                "rounds", "wall_seconds"):
        assert key in section["baseline"], key
    assert len(section["curve"]) == 1
    for pt in section["curve"]:
        for key in ("workers", "rounds", "interleavings",
                    "aggregate_interleavings_per_sec", "scaling_x",
                    "busy_seconds", "wall_seconds", "per_worker",
                    "violating_rounds", "violations_per_hour",
                    "coverage_match", "violations_match",
                    "leases_reissued"):
            assert key in pt, key
        assert pt["coverage_match"] is True
        assert pt["violations_match"] is True
        assert pt["rounds"] == section["baseline"]["rounds"]
    for key in ("covered_loaded", "warm_skips", "reexplored_classes",
                "explored", "rounds", "store_segments"):
        assert key in section["warm_start"], key
    assert section["warm_start"]["reexplored_classes"] == 0
    assert section["warm_start"]["covered_loaded"] > 0
    assert record["value"] == section["curve"][-1]["scaling_x"]


def test_bench_config16_smoke():
    record = _run_bench(
        "16",
        {
            # Tiny shard curve: shallow seed scan, two rounds, two
            # shard counts. The >=1.6x/2.5x scaling floors need the
            # default shapes, so strict is off; every identity
            # contract — bit-identical state at each shard count and
            # the N->M re-sharded resume — is still asserted
            # internally by the bench and re-checked here. The fleet
            # parity leg is skipped (each fleet run pays a worker
            # subprocess jax startup + compile; tests/test_fleet.py
            # covers 2 workers x 2 host shards directly).
            "DEMI_BENCH_CONFIG16_ROUNDS": "2",
            "DEMI_BENCH_CONFIG16_SHARDS": "1,2",
            "DEMI_BENCH_CONFIG16_BUDGET": "120",
            "DEMI_BENCH_CONFIG16_SEEDS": "4",
            "DEMI_BENCH_CONFIG16_BATCH": "8",
            "DEMI_BENCH_CONFIG16_STRICT": "0",
            "DEMI_BENCH_CONFIG16_FLEET": "0",
        },
    )
    assert record["metric"].startswith("host-half rounds/sec scaling")
    section = record["config16"]
    assert "error" not in section, section
    for key in ("app", "batch", "rounds", "seed_deliveries", "sleep_cap",
                "curve", "scaling", "bit_identical",
                "reshard_resume_match"):
        assert key in section, key
    assert len(section["curve"]) == 2
    for pt in section["curve"]:
        for key in ("shards", "rounds", "host_seconds",
                    "host_rounds_per_sec", "host_x", "bit_match"):
            assert key in pt, key
        assert pt["bit_match"] is True
        assert pt["host_seconds"] > 0
    assert section["curve"][0]["shards"] == 1
    assert section["curve"][0]["host_x"] == 1.0
    assert section["bit_identical"] is True
    assert section["reshard_resume_match"] is True
    assert "fleet" not in section  # skipped leg stays absent, not null
    assert record["value"] == section["curve"][-1]["host_x"]


def test_cli_lint_zoo_clean_subprocess():
    """Tier-1 CI contract at the real entry point: `demi_tpu lint` over
    the bundled zoo exits 0 with zero findings — run as a subprocess so
    entry-point or import-time rot cannot hide behind in-process test
    shortcuts."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "demi_tpu", "lint", "--format", "json"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=180,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    findings = json.loads(out.stdout)
    assert findings["findings"] == [], findings


def test_bench_config8_smoke():
    record = _run_bench(
        "8",
        {
            # Tiny frontier: shallow seed scan, two timed rounds, one rep.
            "DEMI_BENCH_CONFIG8_BUDGET": "120",
            "DEMI_BENCH_CONFIG8_SEEDS": "10",
            "DEMI_BENCH_CONFIG8_BATCH": "8",
            "DEMI_BENCH_CONFIG8_ROUNDS": "2",
            "DEMI_BENCH_CONFIG8_REPS": "1",
            "DEMI_BENCH_CONFIG8_WARM": "1",
        },
    )
    assert record["metric"].startswith("frontier rounds/sec")
    section = record["config8"]
    assert "error" not in section, section
    for key in ("app", "seed_deliveries", "batch", "rounds", "reps",
                "interleavings", "sync_seconds", "async_seconds", "speedup",
                "sync_rounds_per_sec", "async_rounds_per_sec",
                "explored_match", "frontier_match", "interleavings_match",
                "explored", "frontier", "inflight", "fork",
                "host_share", "device_share"):
        assert key in section, key
    for key in ("inflight_rounds", "inflight_hits", "inflight_waste"):
        assert key in section["inflight"], key
    for key in ("prefix_hit_rate", "parent_trunks", "anchor_trunks",
                "steps_saved", "mean_group_size"):
        assert key in section["fork"], key
    assert "host_path" not in section
    # The acceptance-grade >=1.2x (async) needs the DEEP saturated
    # frontier (bench default); at smoke shapes only the equality
    # contract — the async loop explores the EXACT same schedule space —
    # is asserted.
    assert section["explored_match"] is True
    assert section["frontier_match"] is True
    assert section["interleavings_match"] is True
    assert section["interleavings"] > 0
    # Static-pruning A/B on the seeded deep fixture: no-op-only removal
    # with static_pruned > 0 (the deep raft frontier always carries
    # fungible timer/heartbeat races).
    static = section["static"]
    assert static["noop_only"] is True
    assert static["interleavings_match"] is True
    assert sum(static["static_pruned"].values()) > 0


def test_bench_config11_smoke():
    record = _run_bench(
        "11",
        {
            # Tiny continuous-obs A/B: shallow seed scan, few rounds.
            "DEMI_BENCH_CONFIG11_BUDGET": "120",
            "DEMI_BENCH_CONFIG11_SEEDS": "10",
            "DEMI_BENCH_CONFIG11_BATCH": "8",
            "DEMI_BENCH_CONFIG11_ROUNDS": "4",
        },
    )
    assert record["metric"].startswith("continuous-obs overhead %")
    section = record["config11"]
    assert "error" not in section, section
    for key in ("app", "seed_deliveries", "batch", "rounds",
                "journal_records", "journal_contiguous",
                "journal_schema_ok", "timeseries_samples",
                "prom_renders", "explored", "explored_match",
                "violations_match", "rounds_per_sec_plain",
                "rounds_per_sec_journaled", "journal_overhead_pct"):
        assert key in section, key
    # The identity contracts the bench asserts internally, echoed into
    # the JSON: observing the run changes nothing, the journal is
    # round-contiguous with the full per-round schema, and the time
    # series sampled every round.
    assert section["explored_match"] is True
    assert section["violations_match"] is True
    assert section["journal_contiguous"] is True
    assert section["journal_schema_ok"] is True
    assert section["journal_records"] >= 1
    assert section["timeseries_samples"] == section["journal_records"]
    assert section["prom_renders"] is True
    assert record["value"] == section["journal_overhead_pct"]
