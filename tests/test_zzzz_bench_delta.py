"""Tier-1 smoke for bench ``--config 17`` (differential exploration,
ISSUE 18): the section runs at a tiny shape and emits its JSON keys
with the four hard contracts — violation parity, witness parity, audit
soundness, unknown-degrades — all true.

Collected AFTER every other file (the test_bench_smoke.py NOTE: the
870s tier-1 cap truncates the suite tail, so heavy new smokes must not
push seed tests past the cap). The ≥3x reduction floor needs the
default shapes and is asserted by the bench itself under STRICT=1;
the tiny shape here asserts the identity contracts only."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_config17_smoke():
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        # Tiny frontier: fewer rounds and narrow lanes; the seed scan
        # keeps its default knobs (a shallower scan finds no violation
        # to seed). Strict off: the reduction floor is a default-shape
        # property — the identity contracts below must hold at ANY
        # shape and the bench asserts them internally regardless.
        DEMI_BENCH_CONFIG17_ROUNDS="4",
        DEMI_BENCH_CONFIG17_BATCH="8",
        DEMI_BENCH_CONFIG17_STRICT="0",
    )
    for var in ("DEMI_OBS", "DEMI_AUTOTUNE", "DEMI_PREFIX_FORK",
                "DEMI_ASYNC_MIN", "DEMI_BENCH_IMPL",
                "DEMI_STATIC_PRUNE", "DEMI_SANITIZE", "DEMI_SLEEP_SETS"):
        env.pop(var, None)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--config", "17"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=420,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    record = json.loads(out.stdout.strip().splitlines()[-1])
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in record, (key, record)
    assert record["metric"].startswith("re-explored classes")
    section = record["config17"]
    assert "error" not in section, section
    for key in ("app", "batch", "rounds", "seed_deliveries", "edit",
                "changed_tags", "cone_tags", "cone_size",
                "stored_classes", "transferred", "reseeded", "pending",
                "skipped_launches", "reexplored_scratch",
                "reexplored_delta", "reduction_x", "violation_codes",
                "violations_match", "witnesses_match", "audit_sound",
                "unknown_degrades", "opaque_reason", "walls"):
        assert key in section, key
    # One edited handler => a one-tag change cone (the heartbeat's
    # effect sets overlap nothing transitively).
    assert section["changed_tags"] == [2]
    assert section["cone_tags"] == [2]
    assert section["cone_size"] == 1
    # Real transfer AND real re-exploration — neither degenerate.
    assert section["transferred"] > 0
    assert section["reseeded"] >= 1  # at least the trunk revalidation
    assert section["reexplored_delta"] <= section["reexplored_scratch"]
    assert section["reduction_x"] >= 1.0
    assert record["value"] == section["reduction_x"]
    # The four hard contracts (bench asserts these internally too).
    assert section["violations_match"] is True
    assert section["witnesses_match"] is True
    assert section["audit_sound"] is True
    assert section["unknown_degrades"] is True
    assert "unknown" in section["opaque_reason"]
