"""Sharded exploration fleet (demi_tpu/fleet): ledger merge algebra,
content-addressed store degradation, coordinator/worker coverage parity
vs the single-process loop (preemption included), and the cross-run
warm start."""

import hashlib
import os

import numpy as np
import pytest

from demi_tpu import obs
from demi_tpu.analysis import SleepSets, StaticIndependence, sleep_cap
from demi_tpu.fleet import (
    ClassLedger,
    ClassStore,
    build_fleet_workload,
    run_fleet,
    set_digest,
)

#: Small-but-racy fixture: raft elections derive hundreds of racing
#: prescriptions within a few rounds at this budget.
WORKLOAD = {
    "app": "raft", "nodes": 3, "bug": "multivote",
    "max_messages": 48, "pool": 64, "num_events": 8,
}


def _rand_ledger(rng: np.random.RandomState) -> ClassLedger:
    n = rng.randint(0, 6)
    classes = []
    for _ in range(n):
        m = rng.randint(1, 4)
        classes.append(
            tuple(
                tuple(int(x) for x in rng.randint(0, 9, size=5))
                for _ in range(m)
            )
        )
    codes = [int(c) for c in rng.randint(1, 5, size=rng.randint(0, 3))]
    return ClassLedger(classes=classes, violation_codes=codes)


def test_class_ledger_merge_associative_commutative():
    """Fleet aggregation contract (mirror of the PR 11 obs merge
    audit): per-worker ledgers merge to ONE answer under any order or
    grouping."""
    import itertools

    for seed in range(10):
        rng = np.random.RandomState(seed)
        ledgers = [_rand_ledger(rng) for _ in range(4)]
        ref = ClassLedger.merged(ledgers)
        for perm in itertools.permutations(range(4)):
            assert ClassLedger.merged([ledgers[i] for i in perm]) == ref
        # Arbitrary grouping: ((a+b) + (c+d)) and (a + (b + (c + d))).
        left = ClassLedger.merged(ledgers[:2]).merge(
            ClassLedger.merged(ledgers[2:])
        )
        right = ledgers[0:1][0]
        right = ClassLedger.merged(
            [ledgers[0], ClassLedger.merged(ledgers[1:])]
        )
        assert left == ref and right == ref
        # Round-trip through the wire payload preserves identity.
        assert ClassLedger.from_payload(ref.to_payload()) == ref


def test_class_store_corrupt_segment_degrades(tmp_path):
    """A torn or bit-rotted segment fails its own content address and
    is skipped (counted in persist.corrupt_fallbacks), degrading to the
    remaining good segments — never a crash."""
    store = ClassStore(str(tmp_path), "fp-test")
    l1 = ClassLedger(classes=[((1, 2, 3),)], violation_codes=[7])
    l2 = ClassLedger(classes=[((4, 5, 6), (7, 8, 9))])
    p1 = store.publish(l1)
    p2 = store.publish(l2)
    assert p1 != p2
    # Identical ledger re-publish is a content-addressed no-op.
    assert store.publish(l1) == p1
    assert ClassStore(str(tmp_path), "fp-test").load() == ClassLedger.merged(
        [l1, l2]
    )
    # Corrupt one segment in place; also drop a torn partial write.
    with open(p2, "r+b") as f:
        f.write(b"\x00\x01")
    with open(os.path.join(store.dir, "nothex.seg"), "wb") as f:
        f.write(b"torn")
    before = obs.counter("persist.corrupt_fallbacks").total()
    st = ClassStore(str(tmp_path), "fp-test")
    loaded = st.load()
    assert loaded == l1  # degraded to the good segment
    assert st.stats["segments_corrupt"] == 2
    assert obs.counter("persist.corrupt_fallbacks").total() == before + 2
    # A different workload fingerprint sees an empty store.
    assert len(ClassStore(str(tmp_path), "other-fp").load()) == 0


def test_relabel_snapshot_worker_label_prom():
    """Merged fleet snapshots carry a worker label on every series, and
    the Prometheus exposition (`stats --prom`) renders it."""
    from demi_tpu.obs import merge_snapshots, relabel_snapshot
    from demi_tpu.obs.timeseries import prom_text

    w0 = {"counters": {"dpor.host_seconds": {"": 1.5}},
          "gauges": {"dpor.host_share": {"": 0.25}},
          "gauge_stamps": {"dpor.host_share": {"": 10.0}}}
    w1 = {"counters": {"dpor.host_seconds": {"": 2.5}},
          "gauges": {"dpor.host_share": {"": 0.5}},
          "gauge_stamps": {"dpor.host_share": {"": 11.0}}}
    merged = merge_snapshots(
        relabel_snapshot(w0, worker="w0"), relabel_snapshot(w1, worker="w1")
    )
    assert merged["counters"]["dpor.host_seconds"] == {
        "worker=w0": 1.5, "worker=w1": 2.5
    }
    assert merged["gauges"]["dpor.host_share"]["worker=w0"] == 0.25
    text = prom_text(merged)
    assert 'demi_dpor_host_share{worker="w0"} 0.25' in text
    assert 'demi_dpor_host_seconds_total{worker="w1"} 2.5' in text


def _baseline(batch=8, rounds=4):
    from demi_tpu.device.dpor_sweep import DeviceDPOR

    app, cfg, program = build_fleet_workload(WORKLOAD)
    rel = StaticIndependence.for_app(app)
    base = DeviceDPOR(
        app, cfg, program, batch_size=batch, prefix_fork=False,
        double_buffer=False,
        sleep_sets=SleepSets(independence=rel, prune=False, cap=sleep_cap()),
    )
    found = base.explore(max_rounds=rounds, stop_on_violation=False)
    return base, found


def test_fleet_parity_with_preempted_worker():
    """2-worker fleet vs the single-process loop: the explored
    prescription set, Mazurkiewicz class set, violation codes, and
    frontier size are bit-identical — with worker w0 dying abruptly
    while HOLDING a lease (the coordinator revokes and re-leases it,
    re-execution is bit-identical) and each worker's rounds sharded
    over a 2-device local mesh (the intra-slice sleep-kernel twin)."""
    base, found = _baseline()
    s = run_fleet(
        WORKLOAD, workers=2, batch=8, rounds=4,
        devices_per_worker=2,
        worker_env={"w0": {"DEMI_FLEET_DIE_AFTER": "1"}},
        timeout=420.0,
    )
    assert s["explored_sha"] == set_digest(base.explored)
    assert s["classes_sha"] == set_digest(base.sleep.classes)
    assert s["violation_codes"] == sorted(base.violation_codes)
    assert s["explored"] == len(base.explored)
    assert s["frontier"] == len(base.frontier)
    assert s["rounds"] == base.round_index
    bfound = (
        hashlib.sha256(found[0][: found[1]].tobytes()).hexdigest()[:16]
        if found is not None
        else None
    )
    assert s["first_found_sha"] == bfound
    # The preemption really happened and was really healed: w0 died
    # holding its first lease, and the surviving worker re-executed it.
    assert 17 in s["worker_returncodes"]
    assert s["leases_reissued"] >= 1
    assert sum(pw["rounds"] for pw in s["per_worker"].values()) >= s["rounds"]


def test_fleet_warm_start_across_runs(tmp_path):
    """Run 1 publishes its class ledger to the content-addressed store;
    run 2 of the same workload loads it and re-explores ZERO covered
    classes — only the root round executes and the frontier drains."""
    store = str(tmp_path / "classes")
    s1 = run_fleet(
        WORKLOAD, workers=1, batch=8, rounds=3,
        class_store_dir=store, timeout=420.0,
    )
    assert s1["classes"] > 1
    assert s1["store"]["segments"] == 1
    s2 = run_fleet(
        WORKLOAD, workers=1, batch=8, rounds=3,
        class_store_dir=store, warm_start=True, prune=True, timeout=420.0,
    )
    assert s2["warm_covered"] == s1["classes"]
    assert s2["warm_skips"] > 0
    assert s2["explored"] == 1  # the root re-executes; nothing else
    assert s2["rounds"] == 1
    assert s2["frontier"] == 0


def test_explore_stop_on_violation_flag():
    """Coverage mode (`stop_on_violation=False`) keeps draining rounds
    past a hit and still returns the FIRST violating lane's records —
    the fleet-parity baseline contract."""
    from demi_tpu.apps.common import make_host_invariant
    from demi_tpu.config import SchedulerConfig
    from demi_tpu.device.dpor_sweep import DeviceDPOR, steering_prescription
    from demi_tpu.schedulers import RandomScheduler

    wl = dict(WORKLOAD, commands=3, max_messages=160, pool=256)
    app, cfg, program = build_fleet_workload(wl)
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    fr = None
    for seed in range(4):
        r = RandomScheduler(
            config, seed=seed, max_messages=120, invariant_check_interval=1
        ).execute(program)
        if r.violation is not None:
            fr = r
            break
    assert fr is not None
    fr.trace.set_original_externals(list(program))
    presc = steering_prescription(app, cfg, fr.trace, program)

    def run(stop):
        d = DeviceDPOR(
            app, cfg, program, batch_size=8, prefix_fork=False,
            double_buffer=False,
        )
        d.seed(presc)
        found = d.explore(max_rounds=3, stop_on_violation=stop)
        return d, found

    stopped, f1 = run(True)
    drained, f2 = run(False)
    # The seeded schedule violates in round 1 on both paths.
    assert f1 is not None and f2 is not None
    assert f1[0][: f1[1]].tobytes() == f2[0][: f2[1]].tobytes()
    assert stopped.round_index == 1  # stopped at the hit
    assert drained.round_index == 3  # kept draining the budget
    assert len(drained.explored) >= len(stopped.explored)
    assert drained.violation_codes >= stopped.violation_codes


def test_fleet_journal_and_top_panel(tmp_path):
    """The coordinator journal's fleet.* records drive the `demi_tpu
    top` FLEET panel (synthetic records — the render contract, not the
    fleet itself)."""
    from demi_tpu.obs import journal
    from demi_tpu.tools.top import render_frame

    d = str(tmp_path / "run")
    j = journal.RoundJournal(d)
    j.emit("fleet.worker", worker="w0", event="hello", workers_alive=1)
    for i in range(3):
        j.emit(
            "fleet.round", round=i + 1, worker=f"w{i % 2}", lease=i,
            wall_s=0.05, busy_s=0.04, host_s=0.01, batch=8, fresh=4,
            redundant=1, violations=[2] if i == 2 else [],
            frontier=10 - i, explored=8 + i, interleavings=8 * (i + 1),
            classes=8 + i, warm_skips=2, workers_alive=2,
            leases_outstanding=1,
        )
    j.close()
    frame = render_frame(d, window=10)
    assert "FLEET" in frame
    assert "workers alive 2" in frame
    assert "global class frontier 10" in frame
    assert "leases outstanding 1" in frame
    assert "rounds by worker" in frame
    assert "warm-start skips 2" in frame


def test_straggler_early_release_is_journaled(tmp_path):
    """Straggler policy unit: with >=5 completed lease walls, an
    outstanding lease older than factor x median (floored at 0.25s) is
    revoked back to the queue, counted, and journaled as
    fleet.straggler — while a young lease survives the same scan."""
    import time as _time

    from demi_tpu.fleet.coordinator import FleetCoordinator, Lease
    from demi_tpu.obs import journal

    app, cfg, program = build_fleet_workload(WORKLOAD)
    co = FleetCoordinator(
        app, cfg, program, workload=WORKLOAD, batch_size=8,
        max_rounds=2, journal_dir=str(tmp_path), straggler_factor=4.0,
    )
    try:
        co._lease_walls = [0.01, 0.012, 0.009, 0.011, 0.01]
        now = _time.monotonic()
        slow = Lease(7, 3, [("x",)], 1, None, None, None, None)
        young = Lease(8, 4, [("y",)], 1, None, None, None, None)
        co._outstanding[7] = (slow, "w0", now + 120.0, now - 1.0)
        co._outstanding[8] = (young, "w1", now + 120.0, now - 0.01)
        with co._lock:
            co._check_expired_locked()
        assert co._stragglers == 1
        assert [le.lease_id for le in co._requeue] == [7]
        assert 7 not in co._outstanding and 8 in co._outstanding
        # The deadline-expiry path was NOT what fired.
        assert co._releases == 1
    finally:
        co.close()
        if co._journal_attached_here:
            obs.journal.detach()
    recs = journal.read_records(str(tmp_path), kind="fleet.straggler")
    assert len(recs) == 1
    rec = recs[0]
    assert rec["worker"] == "w0" and rec["round"] == 3 and rec["lease"] == 7
    assert rec["wall_s"] >= 0.25  # the re-lease floor
    assert rec["median_s"] == pytest.approx(0.01)
    assert rec["factor"] == 4.0


def test_fleet_tracing_stitch_smoke(tmp_path):
    """Tier-1 smoke for `demi_tpu trace stitch`: a 2-worker fleet run
    with telemetry on exports span sidecars for the coordinator and
    every worker next to the journal; the stitcher merges them into ONE
    valid Perfetto document — per-process metadata, globally monotonic
    clock-aligned timestamps, bracket-valid B/E per (pid, tid) — with
    each worker's fleet.execute span linked to (and inside) the
    coordinator's fleet.lease span for the same round."""
    import json as _json

    from demi_tpu.obs import distributed as dtrace

    d = str(tmp_path / "run")
    obs.REGISTRY.reset()
    obs.TRACER.clear()
    obs.enable()
    try:
        s = run_fleet(
            WORKLOAD, workers=2, batch=8, rounds=3,
            journal_dir=d, timeout=420.0,
        )
    finally:
        obs.disable()
        obs.REGISTRY.reset()
        obs.TRACER.clear()
    assert s["rounds"] >= 1

    out = str(tmp_path / "pod.json")
    summary = dtrace.stitch([d], out)
    procs = set(summary["processes"])
    assert "coordinator" in procs
    assert {"worker-w0", "worker-w1"} <= procs
    assert summary["spans"] > 0 and summary["journal_records"] > 0

    doc = _json.loads(open(out).read())
    events = doc["traceEvents"]
    named = {
        e["args"]["name"] for e in events
        if e.get("ph") == "M" and e["name"] == "process_name"
    }
    assert {"coordinator", "worker-w0", "worker-w1"} <= named
    be = [e for e in events if e.get("ph") in ("B", "E")]
    last = -1
    stacks = {}
    for e in be:
        assert e["ts"] >= last  # clock-aligned merge is ts-monotonic
        last = e["ts"]
        st = stacks.setdefault((e["pid"], e["tid"]), [])
        if e["ph"] == "B":
            st.append(e["name"])
        else:
            assert st and st.pop() == e["name"]
    assert all(not st for st in stacks.values())
    assert any(e.get("ph") == "i" for e in events)  # journal records

    # Parent/child linkage + containment, from the sidecars (they carry
    # span intervals directly). Same-host wall anchors agree to ~ms;
    # the slack absorbs scheduling noise, not clock skew.
    meta_c, spans_c = dtrace.read_process(
        os.path.join(d, "spans-coordinator.jsonl")
    )
    shift_c = meta_c["epoch_unix_us"] + meta_c["clock_offset_us"]
    leases = {
        sp["args"]["round"]: sp for sp in spans_c
        if sp["name"] == "fleet.lease"
    }
    assert leases
    trace_ids = {sp["args"]["trace_id"] for sp in leases.values()}
    assert len(trace_ids) == 1  # one pod-wide trace root
    slack = 250_000.0  # us
    execs = 0
    for w in ("w0", "w1"):
        meta_w, spans_w = dtrace.read_process(
            os.path.join(d, f"spans-worker-{w}.jsonl")
        )
        shift_w = meta_w["epoch_unix_us"] + meta_w["clock_offset_us"]
        for sp in spans_w:
            if sp["name"] != "fleet.execute":
                continue
            rnd = sp["args"]["round"]
            if rnd not in leases:
                continue
            execs += 1
            lease = leases[rnd]
            assert sp["args"]["trace_id"] == lease["args"]["trace_id"]
            assert sp["args"]["parent_span"] == lease["args"]["span_id"]
            b = lease["ts"] + shift_c
            e_ = lease["ts"] + lease["dur"] + shift_c
            assert sp["ts"] + shift_w >= b - slack
            assert sp["ts"] + sp["dur"] + shift_w <= e_ + slack
    assert execs >= 1


def test_late_result_after_requeue_is_accepted(tmp_path):
    """Late-result acceptance unit: a lease whose deadline fires moves
    to the requeue; when the original worker then answers LATE, the
    result is accepted iff the round is still un-reserved — the
    re-lease is cancelled, and a second copy of the same answer is
    dropped as a duplicate."""
    import time as _time

    from demi_tpu.fleet.coordinator import FleetCoordinator
    from demi_tpu.persist.checkpoint import pack_array

    app, cfg, program = build_fleet_workload(WORKLOAD)
    co = FleetCoordinator(
        app, cfg, program, workload=WORKLOAD, batch_size=8,
        max_rounds=2, journal_dir=str(tmp_path),
    )
    try:
        assert co.worker_hello("w0")["op"] == "config"
        # Freeze the starting generation as serve() would, without
        # opening the socket server.
        co._gen = list(co.dpor.frontier)
        msg = co.next_lease("w0")
        assert msg["op"] == "lease"
        lid = msg["lease"]
        lease, worker, _deadline, t_issue = co._outstanding[lid]
        # Execute the round in-process the way a worker would (the
        # coordinator itself holds no kernel): the result bytes a (slow)
        # worker would have sent.
        from demi_tpu.device.dpor_sweep import build_dpor_kernel, lane_keys

        kernel = build_dpor_kernel(app, cfg, **co.dpor._sleep_kernel_args())
        keys = lane_keys(lease.seeds)
        if lease.sleeps is not None:
            res = kernel(
                co.dpor._progs(len(lease.batch)), lease.prescs,
                keys, lease.sleeps, lease.sfrom,
            )
        else:
            res = kernel(
                co.dpor._progs(len(lease.batch)), lease.prescs, keys
            )
        result_msg = {
            "op": "result", "lease": lid, "worker": "w0", "busy_s": 0.01,
            "res": {
                f: pack_array(np.asarray(getattr(res, f)))
                for f in type(res)._fields
            },
        }
        # Fire the deadline: the lease is revoked to the requeue.
        co._outstanding[lid] = (
            lease, worker, _time.monotonic() - 1.0, t_issue
        )
        with co._lock:
            co._check_expired_locked()
        assert lid not in co._outstanding
        assert [le.lease_id for le in co._requeue] == [lid]
        assert co._releases == 1
        # The late answer lands while the round is still un-reserved:
        # accepted, and the pending re-lease is cancelled.
        ack = co.submit("w0", result_msg)
        assert ack.get("op") == "ok" and not ack.get("duplicate")
        assert not co._requeue
        # The accepted round drained straight through the canonical
        # merge: the coordinator's host half processed it.
        assert co._processed == 1
        assert co.dpor.round_index == 1
        assert co.workers["w0"]["rounds"] == 1
        # The same bytes again (e.g. from the re-leased worker racing
        # in) are recognized as already served and dropped.
        dup = co.submit("w1", result_msg)
        assert dup == {"op": "ok", "duplicate": True}
        assert co._processed == 1
        assert co.workers.get("w1", {}).get("rounds", 0) == 0
    finally:
        co.close()
        if co._journal_attached_here:
            obs.journal.detach()


def test_fleet_parity_two_workers_two_host_shards():
    """2 workers x 2 coordinator admission shards, one worker killed
    while holding a lease: coverage, class set, violation codes, and
    the first-found record are bit-identical to the 1-worker x 1-shard
    sequential baseline — the digest-range shard merge composes with
    lease revocation and re-execution."""
    base, found = _baseline()
    s = run_fleet(
        WORKLOAD, workers=2, batch=8, rounds=4,
        host_shards=2, max_outstanding=1,
        worker_env={"w0": {"DEMI_FLEET_DIE_AFTER": "1"}},
        timeout=420.0,
    )
    assert s["explored_sha"] == set_digest(base.explored)
    assert s["classes_sha"] == set_digest(base.sleep.classes)
    assert s["violation_codes"] == sorted(base.violation_codes)
    assert s["explored"] == len(base.explored)
    assert s["frontier"] == len(base.frontier)
    bfound = (
        hashlib.sha256(found[0][: found[1]].tobytes()).hexdigest()[:16]
        if found is not None
        else None
    )
    assert s["first_found_sha"] == bfound
    assert 17 in s["worker_returncodes"]
    assert s["leases_reissued"] >= 1
