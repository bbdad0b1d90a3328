"""Randomized differential soak: continuous-driver verdict parity against
the plain explore kernel, across fuzzed corpora, apps, and backends.

    python -m demi_tpu.tools.soak --seconds 600
    python -m demi_tpu.tools.soak --rounds 20 --variants xla,mesh

Each round draws a fresh fuzz corpus (app rotates raft-faults /
broadcast+WaitCondition / spark), runs it through the requested
continuous-driver variants, and asserts every per-seed (status,
violation) verdict equals the plain kernel's. Exit 0 = no divergence.
This is the long-form companion to tests/test_continuous.py (which pins
fixed corpora); round-4 runs: 70 rounds (r3 code) + 115+ rounds (r4
code) with zero divergences.
"""

from __future__ import annotations

import argparse
import sys
import time


def _family(pick: int, with_conditions: bool):
    """Shared app-family rotation for both soak modes: (app, gen_msgs,
    weights, cfg_kw, ncond). One definition so the modes cannot drift
    onto different configurations."""
    import dataclasses

    import jax.numpy as jnp

    from ..apps.broadcast import broadcast_send_generator, make_broadcast_app
    from ..apps.raft import make_raft_app, raft_send_generator
    from ..apps.spark_dag import make_spark_app, spark_send_generator
    from ..fuzzing import FuzzerWeights

    if pick == 0:
        app = make_raft_app(3, bug="multivote")
        return (
            app, raft_send_generator(app),
            FuzzerWeights(send=0.3, kill=0.1, wait_quiescence=0.3,
                          hard_kill=0.15, restart=0.15),
            dict(pool_capacity=96, max_steps=160, max_external_ops=24,
                 invariant_interval=1, timer_weight=0.1),
            0,
        )
    if pick == 1:
        app = make_broadcast_app(4, reliable=False)
        weights = FuzzerWeights(send=0.5, wait_quiescence=0.25, kill=0.1)
        ncond = 0
        if with_conditions:
            def _all0(states, alive):
                return jnp.all(~alive | ((states[:, 0] & 1) != 0))

            app = dataclasses.replace(app, conditions=(_all0,))
            weights = FuzzerWeights(send=0.5, wait_quiescence=0.15,
                                    kill=0.1, wait_condition=0.25)
            ncond = 1
        return (
            app, broadcast_send_generator(app), weights,
            dict(pool_capacity=64, max_steps=96, max_external_ops=24),
            ncond,
        )
    app = make_spark_app(num_workers=3, num_stages=2, tasks_per_stage=3,
                         bug="stale_task")
    return (
        app, spark_send_generator(app),
        FuzzerWeights(send=0.4, kill=0.1, wait_quiescence=0.3,
                      hard_kill=0.1, restart=0.1),
        dict(pool_capacity=128, max_steps=160, max_external_ops=24,
             invariant_interval=1),
        0,
    )


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--seconds", type=float, default=600.0)
    p.add_argument("--rounds", type=int, default=None,
                   help="stop after N rounds instead of --seconds")
    p.add_argument("--variants", default="xla,mesh",
                   help="comma list: xla, mesh")
    p.add_argument("--lanes", type=int, default=24)
    p.add_argument("--seed", type=int, default=20260730)
    p.add_argument(
        "--mode", default="continuous",
        choices=("continuous", "round-pin", "kill-resume",
                 "service-kill-resume"),
        help="continuous: per-seed verdict parity across continuous-driver "
             "variants; round-pin: fuzzed round-delivery lanes recorded and "
             "replayed through the sequential replay kernel "
             "(ignored_absent must be 0 — every round execution is a legal "
             "sequential schedule); kill-resume: SIGKILL a checkpointed "
             "DPOR soak mid-run and verify the resumed run converges to "
             "the uninterrupted run's violation set (bit-parity on "
             "explored/interleavings/first-found); service-kill-resume: "
             "SIGKILL a `demi_tpu serve` daemon mid-queue (two tenants' "
             "jobs in flight) and verify `serve --resume --drain` "
             "converges every tenant's artifact set exactly (no frame "
             "lost, none minimized twice)",
    )
    args = p.parse_args(argv)

    if args.mode == "round-pin":
        return _round_pin_soak(args)
    if args.mode == "kill-resume":
        return _kill_resume_soak(args)
    if args.mode == "service-kill-resume":
        return _service_kill_resume_soak(args)

    import numpy as np

    import jax
    import jax.numpy as jnp

    from ..apps.common import dsl_start_events
    from ..device import DeviceConfig, make_explore_kernel
    from ..device.continuous import ContinuousSweepDriver
    from ..device.encoding import lower_program, stack_programs
    from ..fuzzing import Fuzzer
    from ..parallel.mesh import make_mesh

    variant_kw = {"xla": {}, "mesh": {}}  # mesh: built only if asked for
    names = [v.strip() for v in args.variants.split(",") if v.strip()]
    for v in names:
        if v not in variant_kw:
            raise SystemExit(f"unknown variant {v!r}")
    if "mesh" in names:
        variant_kw["mesh"]["mesh"] = make_mesh()

    rng = np.random.RandomState(args.seed)
    rounds = 0
    t0 = time.time()
    n = args.lanes
    while True:
        if args.rounds is not None:
            if rounds >= args.rounds:
                break
        elif time.time() - t0 >= args.seconds:
            break
        rounds += 1
        app, gen_msgs, weights, cfg_kw, ncond = _family(
            rounds % 3, with_conditions=True
        )
        cfg = DeviceConfig.for_app(app, **cfg_kw)
        fz = Fuzzer(num_events=int(rng.randint(6, 12)), weights=weights,
                    message_gen=gen_msgs, prefix=dsl_start_events(app),
                    max_kills=2, wait_budget=(5, 30), num_conditions=ncond)
        base = int(rng.randint(0, 1 << 30))
        gen = lambda s: fz.generate_fuzz_test(seed=base + s)  # noqa: E731
        kernel = make_explore_kernel(app, cfg)
        progs = stack_programs(
            [lower_program(app, cfg, gen(s)) for s in range(n)]
        )
        keys = np.stack(
            [np.asarray(jax.random.PRNGKey(s)) for s in range(n)]
        )
        ref = kernel(progs, keys)
        ref_st = np.asarray(ref.status)
        ref_vio = np.asarray(ref.violation)
        for name in names:
            drv = ContinuousSweepDriver(
                app, cfg, gen, batch=8,
                seg_steps=int(rng.choice([16, 28, 32])),
                seed_pure=True,
                **variant_kw[name],
            )
            st, vio = drv.sweep(n)
            for s in range(n):
                if st[s] != int(ref_st[s]) or vio[s] != int(ref_vio[s]):
                    print(
                        f"DIVERGENCE round={rounds} app={app.name} "
                        f"variant={name} seed={s} base={base}: "
                        f"cont=({st[s]},{vio[s]}) "
                        f"plain=({int(ref_st[s])},{int(ref_vio[s])})",
                        flush=True,
                    )
                    return 2
        if rounds % 5 == 0:
            print(f"round {rounds} ok ({time.time() - t0:.0f}s)", flush=True)
    print(
        f"SOAK OK: {rounds} rounds, "
        f"{len(names) * n * rounds} lane-verdicts compared",
        flush=True,
    )
    return 0


def _round_pin_soak(args) -> int:
    """Round-delivery robustness: fuzzed programs over the three app
    families run as single round-mode lanes with record_trace; each
    recorded linearization replays through the SEQUENTIAL replay kernel
    and must match exactly (ignored_absent == 0, same deliveries/
    status/violation) — tests/test_rounds.py's pin, at soak scale."""
    import numpy as np

    import jax

    from ..apps.common import dsl_start_events
    from ..device import DeviceConfig
    from ..device.encoding import lower_program
    from ..device.explore import make_run_lane
    from ..device.replay import make_replay_run_lane
    from ..fuzzing import Fuzzer

    rng = np.random.RandomState(args.seed)
    rounds = 0
    checked = 0
    skipped = 0
    t0 = time.time()
    kernels = {}
    while True:
        if args.rounds is not None:
            if rounds >= args.rounds:
                break
        elif time.time() - t0 >= args.seconds:
            break
        rounds += 1
        # Conditions stay off here: the sequential replay kernel applies
        # records without consulting segment conditions, so a
        # cond-gated round lane would not be a like-for-like pin.
        app, gen_msgs, weights, cfg_kw, _nc = _family(
            rounds % 3, with_conditions=False
        )
        # One compiled kernel pair per app family (shapes are constant).
        if app.name not in kernels:
            rcfg = DeviceConfig.for_app(
                app, **{**cfg_kw, "invariant_interval": 0},
                round_delivery=True, record_trace=True,
                trace_capacity=cfg_kw["max_steps"] * 2,
            )
            pcfg = DeviceConfig.for_app(
                app,
                **{
                    **cfg_kw,
                    "invariant_interval": 0,
                    "max_steps": rcfg.trace_rows,
                    # Rounds free consumed entries before inserting, so
                    # the linearization's transient peak can exceed the
                    # round lane's by up to num_actors slots.
                    "pool_capacity": (
                        cfg_kw["pool_capacity"] + app.num_actors
                    ),
                },
            )
            kernels[app.name] = (
                rcfg,
                jax.jit(make_run_lane(app, rcfg)),
                jax.jit(make_replay_run_lane(app, pcfg)),
            )
        rcfg, run, replay = kernels[app.name]
        fz = Fuzzer(num_events=int(rng.randint(6, 12)), weights=weights,
                    message_gen=gen_msgs, prefix=dsl_start_events(app),
                    max_kills=2, wait_budget=(5, 30))
        for s in range(args.lanes):
            base = int(rng.randint(0, 1 << 30))
            prog = lower_program(app, rcfg, fz.generate_fuzz_test(seed=base))
            key = jax.random.PRNGKey(base)
            res = run(prog, key)
            tl = int(res.trace_len)
            if int(res.status) == 4 or tl > rcfg.trace_rows:
                skipped += 1  # pool/trace overflow: config, not semantics
                continue
            trace = np.asarray(res.trace)[:tl]
            rep = replay(trace, key)
            ok = (
                int(rep.ignored_absent) == 0
                and int(rep.deliveries) == int(res.deliveries)
                and int(rep.status) == int(res.status)
                and int(rep.violation) == int(res.violation)
            )
            checked += 1
            if not ok:
                print(
                    f"ROUND-PIN DIVERGENCE round={rounds} app={app.name} "
                    f"base={base}: round=({int(res.status)},"
                    f"{int(res.violation)},{int(res.deliveries)}) "
                    f"replay=({int(rep.status)},{int(rep.violation)},"
                    f"{int(rep.deliveries)},ign={int(rep.ignored_absent)})",
                    flush=True,
                )
                return 2
        if rounds % 5 == 0:
            print(
                f"round-pin {rounds} ok, {checked} lanes, "
                f"{skipped} overflow-skipped ({time.time() - t0:.0f}s)",
                flush=True,
            )
    if checked < max(1, (checked + skipped) // 2):
        # Silent coverage collapse (a family overflowing on most seeds)
        # must fail the soak, not pass vacuously — and must not log OK
        # first (exit-3 runs used to print both lines).
        print(
            f"ROUND-PIN SOAK: >50% of lanes overflow-skipped "
            f"({checked} checked, {skipped} skipped)",
            flush=True,
        )
        return 3
    print(
        f"ROUND-PIN SOAK OK: {rounds} rounds, {checked} lanes "
        f"({skipped} overflow-skipped)",
        flush=True,
    )
    return 0


def _kill_resume_soak(args) -> int:
    """Preemption-tolerance soak (demi_tpu.persist): per cycle, run one
    checkpointed DPOR search to completion (the reference), then run the
    SAME search again, SIGKILL it mid-soak — the harshest preemption:
    no handler runs, a snapshot write may be torn mid-file — and
    ``demi_tpu resume`` it to completion. The resumed run must converge
    to the uninterrupted run's results EXACTLY: same violation-code set,
    same first-found record digest, same explored count and
    interleavings (checkpoints are atomic + generation-versioned, and
    rounds are deterministic in the restored state, so kill-and-resume
    is bit-parity, not just eventual agreement). The kill delay grows
    with the cycle index so the SIGKILL lands at different phases —
    including inside checkpoint writes."""
    import json
    import os
    import shutil
    import signal
    import subprocess
    import tempfile

    cycles = args.rounds if args.rounds is not None else 3
    rounds = int(os.environ.get("DEMI_SOAK_KR_ROUNDS", "8"))
    base_cmd = [
        sys.executable, "-m", "demi_tpu", "dpor",
        "--app", "raft", "--bug", "multivote", "--nodes", "3",
        "--batch", "8", "--rounds", str(rounds), "--max-messages", "60",
        "--checkpoint-every", "1",
    ]
    env = dict(os.environ, JAX_PLATFORMS=os.environ.get(
        "JAX_PLATFORMS", "cpu"
    ))

    def summary_of(out: str):
        for line in reversed(out.strip().splitlines()):
            line = line.strip()
            if line.startswith("{"):
                return json.loads(line)
        return None

    t0 = time.time()
    for cycle in range(cycles):
        if args.rounds is None and time.time() - t0 >= args.seconds:
            break
        workdir = tempfile.mkdtemp(prefix="demi_kr_")
        try:
            dir_u = os.path.join(workdir, "uninterrupted")
            dir_k = os.path.join(workdir, "killed")
            ref = subprocess.run(
                base_cmd + ["--checkpoint-dir", dir_u],
                capture_output=True, text=True, env=env, timeout=600,
            )
            want = summary_of(ref.stdout)
            if want is None:
                print(f"KILL-RESUME: no summary from reference run\n"
                      f"{ref.stdout}\n{ref.stderr}", flush=True)
                return 2
            proc = subprocess.Popen(
                base_cmd + ["--checkpoint-dir", dir_k],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=env,
            )
            # Kill once at least one complete generation exists, after a
            # cycle-dependent extra delay (land in different phases).
            deadline = time.time() + 300
            while time.time() < deadline:
                gens = [
                    e for e in (
                        os.listdir(dir_k) if os.path.isdir(dir_k) else []
                    )
                    if e.startswith("ckpt-") and not e.endswith(".tmp")
                ]
                if gens or proc.poll() is not None:
                    break
                time.sleep(0.05)
            time.sleep(0.1 * cycle)
            if proc.poll() is None:
                proc.send_signal(signal.SIGKILL)
            proc.communicate(timeout=60)
            res = subprocess.run(
                [sys.executable, "-m", "demi_tpu", "resume", dir_k],
                capture_output=True, text=True, env=env, timeout=600,
            )
            got = summary_of(res.stdout)
            if got is None:
                print(f"KILL-RESUME: no summary from resumed run\n"
                      f"{res.stdout}\n{res.stderr}", flush=True)
                return 2
            for key in ("violation_codes", "first_found", "explored",
                        "interleavings", "rounds_done",
                        "violation_found"):
                if want.get(key) != got.get(key):
                    print(
                        f"KILL-RESUME DIVERGENCE cycle={cycle} "
                        f"key={key}: uninterrupted={want.get(key)!r} "
                        f"resumed={got.get(key)!r}",
                        flush=True,
                    )
                    return 2
            # Journal continuity (obs/journal.py): the resumed run must
            # have continued the SAME round journal with no duplicated
            # and no missing rounds — even when the SIGKILL landed
            # between a checkpoint and later journaled rounds (resume
            # truncates those, then re-journals them).
            from ..obs import journal as _journal

            rounds = [
                r.get("round")
                for r in _journal.read_records(dir_k, "dpor.round")
            ]
            # Rotation-tolerant continuity: a long soak's journal may
            # have rotated away its oldest rounds, so require a
            # gap-free, duplicate-free run ENDING at rounds_done (a
            # fresh-start prefix of 1..N satisfies this too).
            ok = bool(rounds) and rounds == list(
                range(rounds[0], rounds[0] + len(rounds))
            )
            if not ok or rounds[-1] != got.get("rounds_done"):
                print(
                    f"KILL-RESUME JOURNAL GAP cycle={cycle}: rounds="
                    f"{rounds} rounds_done={got.get('rounds_done')}",
                    flush=True,
                )
                return 2
            print(
                f"kill-resume cycle {cycle} ok "
                f"(explored={got.get('explored')}, "
                f"codes={got.get('violation_codes')}, "
                f"{time.time() - t0:.0f}s)",
                flush=True,
            )
        finally:
            shutil.rmtree(workdir, ignore_errors=True)
    print("KILL-RESUME SOAK OK", flush=True)
    return 0


def _service_kill_resume_soak(args) -> int:
    """Service preemption-tolerance soak (demi_tpu/service): per cycle,
    run a two-tenant job mix on an in-process service to completion
    (the reference artifact sets), then serve the SAME mix from a
    `demi_tpu serve` daemon, SIGKILL the daemon mid-queue — no handler
    runs, a checkpoint write may be torn — and `serve --resume --drain`
    it to completion. Every tenant's fetched artifact set must converge
    EXACTLY to the reference (eid-insensitive signatures): no violation
    frame lost, none minimized twice (the namespaced-queue dedup), and
    the durable per-job frame counters must agree. Runs at tiny shapes
    (DEMI_SOAK_SKR_LANES overrides)."""
    import json
    import os
    import shutil
    import signal
    import subprocess
    import tempfile

    from ..service import ExplorationService, artifact_signature

    cycles = args.rounds if args.rounds is not None else 3
    lanes = int(os.environ.get("DEMI_SOAK_SKR_LANES", "12"))
    chunk = int(os.environ.get("DEMI_SOAK_SKR_CHUNK", "8"))
    max_frames = int(os.environ.get("DEMI_SOAK_SKR_FRAMES", "2"))
    workload = {
        "app": "broadcast", "nodes": 4, "bug": "x", "num_events": 8,
        "max_messages": 96, "pool": 64,
    }
    tenants = [("acme", 0), ("umbrella", 1)]
    env = dict(os.environ, JAX_PLATFORMS=os.environ.get(
        "JAX_PLATFORMS", "cpu"
    ))
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))

    def sig_sets(frame_lists):
        return {
            name: {
                int(f["seed"]): artifact_signature(f["result"])
                for f in frames
                if f["status"] == "done"
            }
            for name, frames in frame_lists.items()
        }

    # Reference: in-process, uninterrupted.
    ref = ExplorationService(None, default_chunk=chunk)
    ref_jobs = {}
    for name, base in tenants:
        job = ref.submit(
            name, workload, lanes=lanes, chunk=chunk, base_key=base,
            max_frames=max_frames, wildcards=False,
        )
        ref_jobs[name] = job["job"]
    ref.run_until_idle()
    want = sig_sets({
        name: ref.job_frames(jid) for name, jid in ref_jobs.items()
    })
    want_counts = {
        name: ref.jobs[jid].frames_done for name, jid in ref_jobs.items()
    }

    t0 = time.time()
    for cycle in range(cycles):
        if args.rounds is None and time.time() - t0 >= args.seconds:
            break
        workdir = tempfile.mkdtemp(prefix="demi_skr_")
        try:
            state = os.path.join(workdir, "state")
            proc = subprocess.Popen(
                [sys.executable, "-m", "demi_tpu", "serve",
                 "--state-dir", state, "--chunk", str(chunk)],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=env, cwd=repo,
            )
            addr = json.loads(proc.stdout.readline())["addr"]
            for name, base in tenants:
                sub = subprocess.run(
                    [sys.executable, "-m", "demi_tpu", "submit",
                     "--addr", addr, "--tenant", name,
                     "--app", "broadcast", "--nodes", "4", "--bug", "x",
                     "--num-events", "8", "--max-messages", "96",
                     "--pool", "64", "--lanes", str(lanes),
                     "--chunk", str(chunk), "--base-key", str(base),
                     "--max-frames", str(max_frames), "--no-wildcards"],
                    capture_output=True, text=True, env=env, timeout=180,
                    cwd=repo,
                )
                if sub.returncode != 0:
                    print(f"SERVICE-KILL-RESUME: submit failed\n"
                          f"{sub.stdout}\n{sub.stderr}", flush=True)
                    return 2
            # Kill once at least one checkpoint generation exists, plus
            # a cycle-dependent delay so the SIGKILL lands in different
            # phases (mid-sweep, mid-minimize, mid-checkpoint-write).
            deadline = time.time() + 300
            while time.time() < deadline:
                gens = [
                    e for e in (
                        os.listdir(state) if os.path.isdir(state) else []
                    )
                    if e.startswith("ckpt-") and not e.endswith(".tmp")
                ]
                if gens or proc.poll() is not None:
                    break
                time.sleep(0.05)
            time.sleep(0.2 * cycle)
            if proc.poll() is None:
                proc.send_signal(signal.SIGKILL)
            proc.communicate(timeout=60)
            res = subprocess.run(
                [sys.executable, "-m", "demi_tpu", "serve",
                 "--state-dir", state, "--resume", "--drain",
                 "--chunk", str(chunk)],
                capture_output=True, text=True, env=env, timeout=600,
                cwd=repo,
            )
            if res.returncode != 0:
                print(f"SERVICE-KILL-RESUME: resume failed rc="
                      f"{res.returncode}\n{res.stdout}\n{res.stderr}",
                      flush=True)
                return 2
            summary = json.loads(res.stdout.strip().splitlines()[-1])
            by_tenant = {
                j["tenant"]: j for j in summary["jobs"]
            }
            # Fetch-equivalent: the resumed daemon exited; read the
            # artifacts from its final checkpoint (the same frames a
            # `jobs --fetch` would have returned).
            from ..persist import CheckpointStore

            ckpt = CheckpointStore(state).load_latest()
            frames = ckpt.sections["service"]["queue"]["frames"]
            got_lists = {name: [] for name, _ in tenants}
            for f in frames:
                tenant = f.get("ns", "").split("/")[0]
                if tenant in got_lists:
                    got_lists[tenant].append(f)
            got = sig_sets(got_lists)
            for name, _ in tenants:
                if got.get(name) != want.get(name):
                    print(
                        f"SERVICE-KILL-RESUME DIVERGENCE cycle={cycle} "
                        f"tenant={name}: want "
                        f"{sorted(want.get(name, {}))} got "
                        f"{sorted(got.get(name, {}))}",
                        flush=True,
                    )
                    return 2
                if by_tenant[name]["frames_done"] != want_counts[name]:
                    print(
                        f"SERVICE-KILL-RESUME FRAME COUNT cycle={cycle} "
                        f"tenant={name}: want {want_counts[name]} got "
                        f"{by_tenant[name]['frames_done']} (a frame was "
                        "lost or minimized twice)",
                        flush=True,
                    )
                    return 2
            print(
                f"service-kill-resume cycle {cycle} ok "
                f"(frames={ {n: by_tenant[n]['frames_done'] for n, _ in tenants} }, "
                f"{time.time() - t0:.0f}s)",
                flush=True,
            )
        finally:
            shutil.rmtree(workdir, ignore_errors=True)
    print("SERVICE-KILL-RESUME SOAK OK", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
