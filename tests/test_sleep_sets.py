"""Sleep sets & race-reversal DPOR: canonical class keys, device wake
tracking, the native/NumPy sleep filter, and the pruned-vs-unpruned
parity contracts on raft, broadcast, and spark fixtures across the
device and host DPORScheduler tiers."""

import numpy as np
import jax.numpy as jnp
import pytest

from demi_tpu.analysis import (
    BIG_ORDINAL,
    SleepSets,
    StaticIndependence,
    canonical_class_key,
    np_wake_ordinals,
    rows_content_equal,
    rows_independent,
)
from demi_tpu.apps.broadcast import make_broadcast_app
from demi_tpu.apps.common import dsl_start_events, make_host_invariant
from demi_tpu.apps.raft import T_CLIENT, make_raft_app
from demi_tpu.apps.spark_dag import make_spark_app
from demi_tpu.config import SchedulerConfig
from demi_tpu.device import DeviceConfig
from demi_tpu.device.core import REC_DELIVERY, REC_TIMER
from demi_tpu.device.dpor_sweep import DeviceDPOR, make_dpor_kernel
from demi_tpu.dsl import DSLApp, vset
from demi_tpu.external_events import MessageConstructor, Send, WaitQuiescence
from demi_tpu.native.analysis import (
    _apply_sleep_filter,
    analysis_native_available,
    racing_prescriptions_batch,
)
from demi_tpu.schedulers.dpor import DPORScheduler

W = 7  # kind, src, dst, msg0, msg1, parent, prev


# ---------------------------------------------------------------------------
# Canonical class keys
# ---------------------------------------------------------------------------

def _row(kind=1, src=0, dst=0, m0=0, m1=0, parent=-1, prev=-1):
    return [kind, src, dst, m0, m1, parent, prev]


def test_canonical_key_merges_independent_reorderings():
    a = _row(dst=1, m0=5)
    b = _row(dst=2, m0=6)
    k1 = canonical_class_key(np.array([a, b]), [3, 7], W)
    k2 = canonical_class_key(np.array([b, a]), [7, 3], W)
    assert k1 == k2


def test_canonical_key_keeps_dependent_orderings_distinct():
    a = _row(dst=1, m0=5)
    c = _row(dst=1, m0=6)  # same receiver: dependent
    k1 = canonical_class_key(np.array([a, c]), [3, 7], W)
    k2 = canonical_class_key(np.array([c, a]), [7, 3], W)
    assert k1 != k2


def test_canonical_key_respects_creation_edges():
    a = _row(dst=1, m0=5)
    b_created = _row(dst=2, m0=6, parent=3)  # created by a (a's pos = 3)
    b_free = _row(dst=2, m0=6, parent=-1)
    k_created = canonical_class_key(np.array([a, b_created]), [3, 7], W)
    k_free = canonical_class_key(np.array([a, b_free]), [3, 7], W)
    assert k_created != k_free


def test_canonical_key_matrix_commute_merges_same_receiver():
    # Tags 1 and 2 commute per the matrix: same-receiver reorder merges.
    m = np.zeros((4, 4), np.uint8)
    m[1, 2] = m[2, 1] = 1
    a = _row(dst=1, m0=1)
    b = _row(dst=1, m0=2)
    k1 = canonical_class_key(np.array([a, b]), [3, 7], W, matrix=m)
    k2 = canonical_class_key(np.array([b, a]), [7, 3], W, matrix=m)
    assert k1 == k2
    # Without the matrix they stay distinct.
    assert canonical_class_key(
        np.array([a, b]), [3, 7], W
    ) != canonical_class_key(np.array([b, a]), [7, 3], W)


def test_canonical_key_is_linearization_invariant_fuzz():
    """Randomized: adjacent-transposing any independent pair of a
    sequence never changes its class key."""
    rng = np.random.default_rng(42)
    for _ in range(40):
        n = int(rng.integers(2, 8))
        rows = np.zeros((n, W), np.int64)
        rows[:, 0] = 1
        rows[:, 1] = rng.integers(0, 3, n)
        rows[:, 2] = rng.integers(0, 3, n)
        rows[:, 3] = rng.integers(0, 4, n)
        pos = np.arange(n) * 2 + 1
        rows[:, W - 2] = -1
        key = canonical_class_key(rows, list(pos), W)
        for t in range(n - 1):
            if rows[t, 2] == rows[t + 1, 2]:
                continue  # dependent: not a valid transposition
            if rows[t + 1, W - 2] == pos[t]:
                continue  # creation edge
            swapped = rows.copy()
            swapped[[t, t + 1]] = swapped[[t + 1, t]]
            spos = list(pos)
            spos[t], spos[t + 1] = spos[t + 1], spos[t]
            assert canonical_class_key(swapped, spos, W) == key


# ---------------------------------------------------------------------------
# Independence / wake-tracking primitives
# ---------------------------------------------------------------------------

def test_rows_independent_and_content_equal():
    a = _row(dst=1, m0=5)
    b = _row(dst=2, m0=5)
    c = _row(dst=1, m0=5)
    assert rows_independent(a, b, W)
    assert not rows_independent(a, c, W)
    assert rows_content_equal(a, c, W)
    # Timers compare without src.
    t1 = _row(kind=REC_TIMER, src=9, dst=1, m0=3)
    t2 = _row(kind=REC_TIMER, src=4, dst=1, m0=3)
    assert rows_content_equal(t1, t2, W)
    m = np.zeros((4, 4), np.uint8)
    m[2, 3] = m[3, 2] = 1
    assert rows_independent(_row(dst=1, m0=2), _row(dst=1, m0=3), W, m)


def test_np_wake_ordinals():
    sleep_rows = np.array([
        _row(dst=1, m0=7),     # woken by any dst-1 delivery
        _row(dst=2, m0=8),     # content-matched below
        [0] * W,               # empty slot
    ])
    deliveries = np.array([
        _row(dst=1, m0=1),     # ordinal 0: pre-node (untracked)
        _row(dst=3, m0=2),     # ordinal 1: independent of both
        _row(dst=2, m0=8),     # ordinal 2: content == row 1 -> slept hit
        _row(dst=1, m0=4),     # ordinal 3: wakes row 0
    ])
    wake, slept = np_wake_ordinals(deliveries, 1, sleep_rows, W)
    assert wake[0] == 3
    assert wake[1] == 2
    assert wake[2] >= BIG_ORDINAL
    assert slept == 2
    # Before the node nothing tracks.
    wake, slept = np_wake_ordinals(deliveries[:1], 1, sleep_rows, W)
    assert all(w >= BIG_ORDINAL for w in wake) and slept >= BIG_ORDINAL


def test_sleep_sets_child_rows_and_ledger():
    s = SleepSets(cap=2)
    node = b"node"
    f1 = tuple(_row(dst=1, m0=1))
    f2 = tuple(_row(dst=2, m0=2))
    f3 = tuple(_row(dst=3, m0=3))
    s.note_admitted_flip(node, f1)
    # f2 independent of f1 (different receivers): f1 sleeps in f2's child.
    assert s.child_sleep_rows(node, f2, W) == (f1,)
    s.note_admitted_flip(node, f2)
    # Cap bounds the set; same-receiver (dependent) flips never sleep.
    assert s.child_sleep_rows(node, f3, W) == (f1, f2)
    f1_same = tuple(_row(dst=1, m0=9))
    assert f1 not in s.child_sleep_rows(node, f1_same, W)


# ---------------------------------------------------------------------------
# Native vs NumPy sleep filter parity
# ---------------------------------------------------------------------------

def _rand_lane(n, w, rng):
    recs = np.zeros((n, w), np.int32)
    if n == 0:
        return recs
    recs[:, 0] = rng.choice([0, 1, 2, 5], size=n, p=[0.1, 0.5, 0.2, 0.2])
    recs[:, 1] = rng.integers(0, 4, n)
    recs[:, 2] = rng.integers(0, 4, n)
    recs[:, 3: w - 2] = rng.integers(0, 5, (n, w - 5))
    for p in range(n):
        recs[p, w - 2] = rng.integers(-1, p) if p else -1
        recs[p, w - 1] = rng.integers(-1, p) if p else -1
    return recs


@pytest.mark.native
def test_sleep_filter_native_numpy_parity_fuzz():
    """The native per-pair sleep filter and the NumPy post-filter twin
    produce bit-identical surviving streams and counts."""
    assert analysis_native_available()
    rng = np.random.default_rng(17)
    w = 8
    for trial in range(10):
        batch = int(rng.integers(1, 5))
        rmax = int(rng.integers(4, 24))
        records = np.stack([_rand_lane(rmax, w, rng) for _ in range(batch)])
        lens = rng.integers(0, rmax + 1, batch).astype(np.int32)
        scap = 3
        sleep_rows = np.zeros((batch, scap, w), np.int32)
        for b in range(batch):
            for s in range(scap):
                if rng.random() < 0.6:
                    sleep_rows[b, s] = _rand_lane(1, w, rng)[0]
                    sleep_rows[b, s, 0] = rng.choice([1, 2])
        wake = rng.integers(0, 6, (batch, scap)).astype(np.int32)
        wake[rng.random((batch, scap)) < 0.5] = BIG_ORDINAL
        slept = rng.integers(0, 8, batch).astype(np.int32)
        slept[rng.random(batch) < 0.6] = BIG_ORDINAL
        presc = rng.integers(0, 4, batch).astype(np.int32)
        ctx = (sleep_rows, wake, slept, presc)

        sl_native = SleepSets(cap=scap)
        native = racing_prescriptions_batch(
            records, lens, w, sleep=sl_native, sleep_ctx=ctx
        )
        # Unfiltered stream + the NumPy twin applied by hand.
        raw = racing_prescriptions_batch(records, lens, w)
        sl_np = SleepSets(cap=scap)
        twin = _apply_sleep_filter(*raw, sleep=sl_np, sleep_ctx=ctx)
        assert np.array_equal(native[0], twin[0]), trial
        assert np.array_equal(native[1], twin[1])
        assert np.array_equal(native[2], twin[2])
        assert np.array_equal(native[3], twin[3])
        assert sl_native.pruned_total == sl_np.pruned_total


# ---------------------------------------------------------------------------
# Device tier: wake parity, guides, A/B contracts
# ---------------------------------------------------------------------------

def make_two_receiver_app() -> DSLApp:
    """Racing deliveries at two receivers: each actor flags a violation
    iff its tag-2 message lands before its tag-1 message — two
    independent order bugs, the diamond sleep sets exist to prune."""

    def init_state(actor_id):
        return np.zeros(2, np.int32)

    def handler(actor_id, state, snd, msg):
        tag = msg[0]
        first = state[1] == 0
        got_b_first = jnp.where((tag == 2) & first, 1, state[0])
        state = vset(state, 0, got_b_first)
        state = vset(state, 1, 1)
        return state, jnp.zeros((1, 4), jnp.int32)

    def invariant(states, alive):
        return jnp.where(jnp.any((states[:, 0] == 1) & alive), jnp.int32(1), 0)

    return DSLApp(
        name="two", num_actors=2, state_width=2, msg_width=2, max_outbox=1,
        init_state=init_state, handler=handler, invariant=invariant,
    )


def _two_receiver_setup():
    app = make_two_receiver_app()
    cfg = DeviceConfig.for_app(
        app, pool_capacity=16, max_steps=16, max_external_ops=10,
        invariant_interval=1, record_trace=True, record_parents=True,
    )
    program = dsl_start_events(app) + [
        Send(app.actor_name(0), MessageConstructor(lambda: (1, 0))),
        Send(app.actor_name(0), MessageConstructor(lambda: (2, 0))),
        Send(app.actor_name(1), MessageConstructor(lambda: (1, 1))),
        Send(app.actor_name(1), MessageConstructor(lambda: (2, 1))),
        WaitQuiescence(),
    ]
    return app, cfg, program


def _drain(dpor, max_rounds=40):
    founds = []
    rounds = 0
    while dpor.frontier and rounds < max_rounds:
        f = dpor.explore(max_rounds=1)
        rounds += 1
        if f is not None:
            founds.append((f[0][: f[1]].tobytes(), int(f[1])))
    return founds


def make_commute_app() -> DSLApp:
    """One receiver, four message tags: 1 and 2 write DISJOINT fields
    (they commute — the matrix below declares it), 3 trips the
    violation iff delivered before 1, 4 pads depth. Commuting
    same-receiver races are where sleep rows attach (sibling flips at a
    node are same-receiver, so only matrix-commuting ones sleep) and
    where reversal guides produce equivalent-class duplicates."""

    def init_state(actor_id):
        return np.zeros(3, np.int32)

    def handler(actor_id, state, snd, msg):
        tag = msg[0]
        state = vset(state, 0, jnp.where(tag == 1, 1, state[0]))
        state = vset(state, 1, jnp.where(tag == 2, 1, state[1]))
        state = vset(
            state, 2,
            jnp.where((tag == 3) & (state[0] == 0), 1, state[2]),
        )
        return state, jnp.zeros((1, 4), jnp.int32)

    def invariant(states, alive):
        return jnp.where(jnp.any((states[:, 2] == 1) & alive), jnp.int32(1), 0)

    return DSLApp(
        name="comm", num_actors=2, state_width=3, msg_width=2, max_outbox=1,
        init_state=init_state, handler=handler, invariant=invariant,
    )


COMMUTE_MATRIX = np.zeros((7, 7), np.uint8)
COMMUTE_MATRIX[1, 2] = COMMUTE_MATRIX[2, 1] = 1


def _commute_setup():
    app = make_commute_app()
    cfg = DeviceConfig.for_app(
        app, pool_capacity=16, max_steps=20, max_external_ops=12,
        invariant_interval=1, record_trace=True, record_parents=True,
    )
    program = dsl_start_events(app) + [
        Send(app.actor_name(0), MessageConstructor(lambda t=t: (t, 0)))
        for t in (1, 2, 3, 4)
    ] + [WaitQuiescence()]
    # Seed: a non-violating lane's delivery rows from a plain probe (the
    # config-8/9 seeded-search shape, deterministic under fixed keys).
    probe = DeviceDPOR(app, cfg, program, batch_size=8)
    batch = [tuple()] * 8
    res = probe.kernel(
        probe._progs(8), probe._pack(batch), probe._round_keys(8, 0)
    )
    viols = np.asarray(res.violation)
    lens = np.asarray(res.trace_len)
    traces = np.asarray(res.trace)
    lane = int(np.flatnonzero(viols == 0)[0])
    recs = traces[lane, : lens[lane], : cfg.rec_width]
    seed = tuple(
        tuple(int(x) for x in r)
        for r in recs[np.isin(recs[:, 0], (REC_DELIVERY, REC_TIMER))]
    )
    return app, cfg, program, seed


def _commute_sleep_run(app, cfg, program, seed, kernel, prune, **kw):
    sl = SleepSets(prune=prune, cap=4)
    sl.matrix = COMMUTE_MATRIX
    d = DeviceDPOR(
        app, cfg, program, batch_size=8, kernel=kernel, sleep_sets=sl, **kw
    )
    d.seed(seed)
    return d, _drain(d, max_rounds=60)


def test_device_sleep_prunes_commuting_diamond():
    """The headline mechanism end to end: the observe-mode baseline
    admits duplicate-class schedules (ratio > 1), the pruned run
    suppresses exactly them — strictly fewer explored at FULL class
    coverage, identical violations and first find."""
    app, cfg, program, seed = _commute_setup()
    kernel = make_dpor_kernel(
        app, cfg, sleep_cap=4, commute_matrix=COMMUTE_MATRIX
    )
    base, founds_base = _commute_sleep_run(
        app, cfg, program, seed, kernel, prune=False
    )
    pruned, founds_pruned = _commute_sleep_run(
        app, cfg, program, seed, kernel, prune=True
    )
    assert base.violation_codes == pruned.violation_codes == {1}
    assert founds_base[:1] == founds_pruned[:1]
    # Strictly fewer schedules explored, same class coverage: the
    # pruned run sits AT the optimal lower bound.
    assert len(pruned.explored) < len(base.explored)
    assert pruned.sleep.classes == base.sleep.classes
    assert pruned.sleep.pruned > 0
    ratio_base = base.sleep.redundancy_ratio(len(base.explored))
    ratio_pruned = pruned.sleep.redundancy_ratio(len(pruned.explored))
    assert ratio_base > 1.0
    assert ratio_pruned == 1.0


def test_device_sleep_wake_parity_with_numpy_twin():
    """Device-tracked wake/slept ordinals equal the NumPy twin computed
    over the lane's own delivered records."""
    app, cfg, program = _two_receiver_setup()
    sl = SleepSets(prune=True, cap=4)
    d = DeviceDPOR(app, cfg, program, batch_size=8, sleep_sets=sl)
    d.explore(max_rounds=1)  # round 1: derive + admit with sleep rows
    batch, _rest = d._select_batch(d._ordered_frontier(d.frontier))
    prescs = d._pack(batch)
    keys = d._round_keys(len(batch), d.interleavings, batch=batch)
    sleeps = d._pack_sleep(batch)
    sfrom = d._sleep_from(batch)
    res = d.kernel(d._progs(len(batch)), prescs, keys, sleeps, sfrom)
    traces = np.asarray(res.trace)
    lens = np.asarray(res.trace_len)
    recw = cfg.rec_width
    for b in range(len(batch)):
        recs = traces[b, : int(lens[b]), :recw]
        deliv = recs[np.isin(recs[:, 0], (REC_DELIVERY, REC_TIMER))]
        wake, slept = np_wake_ordinals(
            deliv, int(sfrom[b]), sleeps[b], recw, sl.matrix
        )
        dev_wake = np.asarray(res.sleep_wake)[b]
        dev_slept = int(np.asarray(res.sleep_slept)[b])
        assert np.array_equal(
            np.minimum(wake, BIG_ORDINAL), np.minimum(dev_wake, BIG_ORDINAL)
        ), b
        assert min(slept, BIG_ORDINAL) == min(dev_slept, BIG_ORDINAL)


def _asleep_per_pair(presc, lane_sleep, wake, slept, presc_deliv, recw):
    """The sleep filter for ONE racing pair, spelt out: its branch lies
    beyond the lane's redundant-suffix marker, or, at or after the
    lane's node, its flip has the content of a row still asleep there
    (kind, receiver, payload; the sender too unless a timer)."""
    branch, flip = len(presc) - 1, presc[-1]
    if branch > slept:
        return True
    if branch < presc_deliv:
        return False
    for s, srow in enumerate(lane_sleep):
        if wake[s] < branch:
            continue
        if (
            flip[0] == srow[0] and flip[2] == srow[2]
            and flip[3: recw - 2] == srow[3: recw - 2]
            and (flip[0] == REC_TIMER or flip[1] == srow[1])
        ):
            return True
    return False


def test_a_sleep_search_is_the_per_pair_filter_over_its_own_traces(
    monkeypatch
):
    """A whole search with sleep sets and pruning on, held round by round
    to a per-lane, per-pair loop kept in the tests: the candidates the
    shipped scan lets through are the per-lane prescriptions
    (test_host_path._legacy_prescriptions) that the per-pair filter above
    leaves awake, in order; what is admitted is the not-yet-seen ones of
    those, in order, less exactly the ones the class ledger counts; and
    the two prune counters add up to what the loop dropped."""
    import demi_tpu.native as native
    from test_host_path import (
        _legacy_prescriptions,
        _unpack,
        harvested_rounds,
    )

    app, cfg, program, seed = _commute_setup()
    recw = cfg.rec_width
    sl = SleepSets(prune=True, cap=4)
    sl.matrix = COMMUTE_MATRIX
    d = DeviceDPOR(
        app, cfg, program, batch_size=8, sleep_sets=sl,
        kernel=make_dpor_kernel(
            app, cfg, sleep_cap=4, commute_matrix=COMMUTE_MATRIX
        ),
    )
    d.seed(seed)
    rounds = harvested_rounds(d)
    scans = []
    scan = native.racing_prescriptions_batch

    def noting(traces, lens, w, **kw):
        out = scan(traces, lens, w, **kw)
        ctx = [np.array(x) for x in kw["sleep_ctx"]]
        lane_sleep = [
            d._sleep_rows.get(p, ()) for p in rounds[-1][2]
        ]
        scans.append((
            _unpack(*(np.array(x) for x in out[:3])), ctx, lane_sleep,
        ))
        return out

    monkeypatch.setattr(native, "racing_prescriptions_batch", noting)
    _drain(d, max_rounds=60)
    assert len(scans) == len(rounds) > 3

    # The seed went in before the first round, by the front door.
    seen, suppressed, n_before = {(), seed}, set(), 2
    slept_n = class_n = 0
    for (traces, lens, batch, n_after), (got, ctx, lane_sleep) in zip(
        rounds, scans
    ):
        _rows, wake, slept, presc_deliv = ctx
        want = []
        for lane in range(len(batch)):
            for presc in _legacy_prescriptions(
                traces[lane], int(lens[lane]), recw
            ):
                if _asleep_per_pair(
                    presc, lane_sleep[lane], wake[lane].tolist(),
                    int(slept[lane]), int(presc_deliv[lane]), recw,
                ):
                    slept_n += 1
                else:
                    want.append((lane, presc))
        assert got == want
        new = list(dict.fromkeys(
            p for _lane, p in want if p not in seen and p not in suppressed
        ))
        admitted = [d._explored_log[k] for k in range(n_before, n_after)]
        # Admitted in candidate order; the rest met a class seen before.
        assert admitted == [p for p in new if p in set(admitted)]
        seen.update(admitted)
        suppressed.update(p for p in new if p not in seen)
        class_n += len(new) - len(admitted)
        n_before = n_after
    assert d.explored == seen
    assert sl.pruned_total == {"sleep": slept_n, "class": class_n}
    assert slept_n > 0 and class_n > 0  # under real pruning pressure
    assert not (seen & suppressed)


def test_device_sleep_fork_parity():
    """Prefix forking is an execution strategy: with sleep sets on, the
    forked run's explored/frontier/violations equal the scratch run's
    (trunk prefixes are clamped below every member's node, so the
    per-lane wake tracking still covers the whole tracked region)."""
    app, cfg, program, seed = _commute_setup()
    kernel = make_dpor_kernel(
        app, cfg, sleep_cap=4, commute_matrix=COMMUTE_MATRIX
    )
    fork_kernel = make_dpor_kernel(
        app, cfg, start_state=True, sleep_cap=4,
        commute_matrix=COMMUTE_MATRIX,
    )
    scratch, _ = _commute_sleep_run(
        app, cfg, program, seed, kernel, prune=True
    )
    forked, _ = _commute_sleep_run(
        app, cfg, program, seed, kernel, prune=True,
        prefix_fork=True, fork_kernel=fork_kernel,
        fork_bucket=2, fork_min_group=2,
    )
    assert scratch.explored == forked.explored
    assert scratch.frontier == forked.frontier
    assert scratch.violation_codes == forked.violation_codes
    assert scratch.sleep.pruned_total == forked.sleep.pruned_total


def _fixture_apps():
    raft = make_raft_app(3)
    raft_prog = dsl_start_events(raft) + [
        Send(raft.actor_name(0),
             MessageConstructor(lambda: (T_CLIENT, 0, 7, 0, 0, 0, 0))),
        WaitQuiescence(),
    ]
    bcast = make_broadcast_app(3, reliable=False)
    bcast_prog = dsl_start_events(bcast) + [
        Send(bcast.actor_name(0), MessageConstructor(lambda: (1, 5))),
        Send(bcast.actor_name(1), MessageConstructor(lambda: (1, 6))),
        WaitQuiescence(),
    ]
    spark = make_spark_app(num_workers=2, num_stages=2, tasks_per_stage=2)
    spark_prog = dsl_start_events(spark) + [WaitQuiescence()]
    return [
        ("raft", raft, raft_prog, dict(pool_capacity=64, max_steps=40)),
        ("broadcast", bcast, bcast_prog, dict(pool_capacity=32, max_steps=32)),
        ("spark", spark, spark_prog, dict(pool_capacity=48, max_steps=40)),
    ]


@pytest.mark.parametrize("name_idx", [0, 1, 2], ids=["raft", "broadcast", "spark"])
def test_device_sleep_ab_violation_preservation(name_idx):
    """Randomized A/B on the zoo fixtures: sleep-set-pruned exploration
    yields the identical violation-code set and first-found records,
    with explored count never larger — device vectorized tier."""
    name, app, program, shape = _fixture_apps()[name_idx]
    cfg = DeviceConfig.for_app(
        app, max_external_ops=16, invariant_interval=1,
        record_trace=True, record_parents=True, **shape,
    )
    rel = StaticIndependence.for_app(app)
    kernel = make_dpor_kernel(
        app, cfg, sleep_cap=4, commute_matrix=rel.device_matrix()
    )

    def run(prune):
        d = DeviceDPOR(
            app, cfg, program, batch_size=8, kernel=kernel,
            sleep_sets=SleepSets(independence=rel, prune=prune, cap=4),
        )
        return d, _drain(d, max_rounds=12)

    base, founds_base = run(False)
    pruned, founds_pruned = run(True)
    assert base.violation_codes == pruned.violation_codes, name
    assert founds_base[:1] == founds_pruned[:1], name
    # Admission-time class dedup keeps the pruned run AT the class
    # lower bound; the baseline may drift above it. (Raw explored
    # counts only compare at equal coverage — i.e. full drain, which
    # these zoo spaces are too large for at tier-1 budgets — so the
    # per-run ratios are the budget-independent contract here.)
    rb = base.sleep.redundancy_ratio(len(base.explored)) or 1.0
    rp = pruned.sleep.redundancy_ratio(len(pruned.explored)) or 1.0
    assert rp == 1.0
    assert rb >= 1.0
    if not base.frontier and not pruned.frontier:  # both drained
        assert len(pruned.explored) <= len(base.explored)


# ---------------------------------------------------------------------------
# Host tier
# ---------------------------------------------------------------------------

class _TagCommuteRel:
    """Host-tier dependence stub: same-receiver tag pairs in ``pairs``
    commute for wake/sleep purposes (the sleep_dependence= channel —
    static pruning stays off, so the races themselves are explored)."""

    def __init__(self, pairs):
        self.pairs = {frozenset(p) for p in pairs}

    def host_commutes_kind(self, a, b):
        ta = a.fingerprint[0] if isinstance(a.fingerprint, tuple) else None
        tb = b.fingerprint[0] if isinstance(b.fingerprint, tuple) else None
        if a.rcv == b.rcv and frozenset((ta, tb)) in self.pairs:
            return "commute"
        return None


class _NeverMatches:
    def matches(self, v):
        return False


def test_host_dpor_sleep_prunes_and_preserves_violations():
    """Host DPORScheduler: sleep sets prune already-reversed races (the
    commuting-tags fixture) at exhaustion, and the violation search
    still finds the same violation."""
    app = make_commute_app()
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    program = dsl_start_events(app) + [
        Send(app.actor_name(0), MessageConstructor(lambda t=t: (t, 0)))
        for t in (1, 2, 3, 4)
    ] + [WaitQuiescence()]

    def run(sleep, target=None):
        s = DPORScheduler(
            config, max_interleavings=500, sleep_sets=sleep,
            sleep_dependence=_TagCommuteRel([(1, 2)]) if sleep else None,
        )
        result = s.explore(program, target_violation=target)
        return s, result

    # Violation search: both find the same order-dependent violation.
    base, rb = run(False)
    pruned, rp = run(True)
    assert rb is not None and rb.violation is not None
    assert rp is not None and rp.violation is not None
    assert rb.violation == rp.violation
    # Exhaustive drain (unmatchable target): pruning fires and never
    # explores MORE.
    base_x, _ = run(False, target=_NeverMatches())
    pruned_x, _ = run(True, target=_NeverMatches())
    assert (
        pruned_x.interleavings_explored <= base_x.interleavings_explored
    )
    assert pruned_x.sleep_pruned > 0


@pytest.mark.parametrize("reliable", [True, False])
def test_host_dpor_sleep_exhaustive_equivalence(reliable):
    """On a bug-free (and a buggy) broadcast fixture, sleep-set
    exploration reaches the same verdict as the full search."""
    app = make_broadcast_app(2, reliable=reliable)
    config = SchedulerConfig(invariant_check=make_host_invariant(app))
    program = dsl_start_events(app) + [
        Send(app.actor_name(0), MessageConstructor(lambda: (1, 0))),
        Send(app.actor_name(1), MessageConstructor(lambda: (1, 1))),
        WaitQuiescence(),
    ]
    base = DPORScheduler(config, max_interleavings=80, sleep_sets=False)
    r_base = base.explore(program)
    pruned = DPORScheduler(config, max_interleavings=80, sleep_sets=True)
    r_pruned = pruned.explore(program)
    assert (r_base is None) == (r_pruned is None)
    if r_base is not None:
        assert r_base.violation == r_pruned.violation
    assert pruned.interleavings_explored <= base.interleavings_explored


# ---------------------------------------------------------------------------
# Guides & trunk anchors
# ---------------------------------------------------------------------------

def test_make_guide_reverses_one_race():
    app, cfg, program = _two_receiver_setup()
    d = DeviceDPOR(
        app, cfg, program, batch_size=4, sleep_sets=SleepSets(cap=4)
    )
    deliv = [tuple(_row(dst=0, m0=k)) for k in range(5)]
    guide = d._make_guide(deliv, 1, deliv[3], 3)
    got = [tuple(r) for r in guide.tolist()]
    assert got == [deliv[0], deliv[3], deliv[1], deliv[2], deliv[4]]
    # Unknown flip ordinal: located by content search past the branch.
    guide2 = d._make_guide(deliv, 1, deliv[3], None)
    assert np.array_equal(guide, guide2)


def test_trunk_anchor_chain_bit_exact_and_cached():
    """Anchor-chained trunk building equals the straight trunk bit for
    bit and leaves resumable anchors in the cache."""
    from demi_tpu.device.fork import (
        PrefixForker,
        make_dpor_prefix_resume_runner,
        make_dpor_prefix_runner,
        prefix_digest,
    )
    from demi_tpu.device.explore import ExtProgram
    from demi_tpu.device.encoding import lower_program
    import jax

    app, cfg, program, seed = _commute_setup()
    d = DeviceDPOR(app, cfg, program, batch_size=8)
    d.seed(seed)
    d.explore(max_rounds=2)
    deep = max(d.explored, key=len)
    assert len(deep) >= 4
    prescs = d._pack([deep])
    prog = ExtProgram(*(np.asarray(x) for x in lower_program(app, cfg, program)))
    runner = make_dpor_prefix_runner(app, cfg)
    resume = make_dpor_prefix_resume_runner(app, cfg)
    plen = (len(deep) // 2) * 2

    plain = PrefixForker(runner, bucket=2, driver="dpor", resume_runner=resume)
    snap_a, _, _ = plain.trunk_hier_prescribed(
        prefix_digest(prescs[0, :plen].tobytes()), prog, prescs[0],
        jax.random.PRNGKey(0), plen,
    )
    chained = PrefixForker(
        runner, bucket=2, driver="dpor", resume_runner=resume,
        anchor_stride=1,
    )
    snap_b, _, _ = chained.trunk_hier_prescribed(
        prefix_digest(prescs[0, :plen].tobytes()), prog, prescs[0],
        jax.random.PRNGKey(0), plen,
    )
    for field in ("steps", "cursor"):
        assert int(getattr(snap_a, field)) == int(getattr(snap_b, field))
    sa = jax.tree_util.tree_leaves(snap_a.state)
    sb = jax.tree_util.tree_leaves(snap_b.state)
    for xa, xb in zip(sa, sb):
        assert np.array_equal(np.asarray(xa), np.asarray(xb))
    # Anchors cached at every stride boundary below the prefix.
    for q in range(2, plen, 2):
        assert prefix_digest(prescs[0, :q].tobytes()) in chained.cache
