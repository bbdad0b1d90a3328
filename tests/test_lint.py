"""Static analysis subsystem: determinism lint (rule fixtures,
suppression, clean-zoo baseline), field-effect extraction +
StaticIndependence soundness (randomized both-order execution checks),
device/host static pruning parity and no-op-only guarantees, and the
DEMI_SANITIZE runtime sanitizer."""

import time as _time

import numpy as np
import pytest

from demi_tpu.analysis import (
    StaticIndependence,
    analyze_dsl_app,
    effects_commute,
    lint_source,
    lint_targets,
)
from demi_tpu.analysis.effects import EffectSet
from demi_tpu.analysis.rules import ERROR, RULES
from demi_tpu.apps.broadcast import make_broadcast_app
from demi_tpu.apps.raft import T_CLIENT, T_HEARTBEAT, make_raft_app
from demi_tpu.apps.spark_dag import make_spark_app


# ---------------------------------------------------------------------------
# Lint rules: one seeded-bad fixture per rule, flagged at the right line
# ---------------------------------------------------------------------------

_RULE_FIXTURES = {
    # rule id -> (source, expected line of the finding)
    "wall-clock": (
        "import time\n"
        "def handler(actor_id, state, snd, msg):\n"
        "    t = time.time()\n"
        "    return state, t\n",
        3,
    ),
    "unseeded-random": (
        "import random\n"
        "def receive(self, ctx, snd, msg):\n"
        "    return random.randint(0, 9)\n",
        3,
    ),
    "id-ordering": (
        "def handler(actor_id, state, snd, msg):\n"
        "    order = sorted(state, key=lambda x: id(x))\n"
        "    return state, order\n",
        2,
    ),
    "set-iteration": (
        "def on_tick(actor_id, state, snd, msg):\n"
        "    seen = {1, 2, 3}\n"
        "    for x in seen:\n"
        "        pass\n"
        "    return state, None\n",
        3,
    ),
    "module-state": (
        "CACHE = {}\n"
        "def receive(self, ctx, snd, msg):\n"
        "    CACHE['k'] = msg\n"
        "    return None\n",
        3,
    ),
    "msg-mutation": (
        "def receive(self, ctx, snd, msg):\n"
        "    msg.append(1)\n"
        "    return None\n",
        2,
    ),
    "thread-spawn": (
        "import threading\n"
        "def receive(self, ctx, snd, msg):\n"
        "    threading.Thread(target=print).start()\n",
        3,
    ),
    "blocking-io": (
        "import time\n"
        "def on_io(actor_id, state, snd, msg):\n"
        "    time.sleep(0.5)\n"
        "    return state, None\n",
        3,
    ),
}


@pytest.mark.parametrize("rule_id", sorted(_RULE_FIXTURES))
def test_rule_fixture_flagged(rule_id):
    src, line = _RULE_FIXTURES[rule_id]
    findings = lint_source(src, f"{rule_id}.py")
    hits = [f for f in findings if f.rule == rule_id]
    assert hits, f"rule {rule_id} did not fire"
    assert hits[0].line == line
    assert hits[0].severity == RULES[rule_id].severity
    assert hits[0].hint == RULES[rule_id].hint


def test_suppression_on_line_and_def():
    src = (
        "import time\n"
        "def handler(actor_id, state, snd, msg):\n"
        "    t = time.time()  # demi: allow(wall-clock)\n"
        "    return state, t\n"
    )
    assert lint_source(src, "s.py") == []
    src_def = (
        "import time\n"
        "def handler(actor_id, state, snd, msg):  # demi: allow(wall-clock)\n"
        "    t = time.time()\n"
        "    u = time.monotonic()\n"
        "    return state, (t, u)\n"
    )
    assert lint_source(src_def, "s.py") == []
    # A different rule id does NOT suppress.
    src_wrong = (
        "import time\n"
        "def handler(actor_id, state, snd, msg):\n"
        "    t = time.time()  # demi: allow(unseeded-random)\n"
        "    return state, t\n"
    )
    assert [f.rule for f in lint_source(src_wrong, "s.py")] == ["wall-clock"]


def test_non_handler_code_out_of_scope():
    src = (
        "import time, random\n"
        "def build_cli():\n"
        "    return time.time(), random.random()\n"
    )
    assert lint_source(src, "s.py") == []


def test_actor_class_methods_are_in_scope():
    src = (
        "import time\n"
        "class Node(Actor):\n"
        "    def helper(self):\n"
        "        return time.time()\n"
        "    def receive(self, ctx, snd, msg):\n"
        "        return self.helper()\n"
    )
    findings = lint_source(src, "s.py")
    assert [f.rule for f in findings] == ["wall-clock"]
    assert findings[0].handler == "Node"


def test_zoo_is_clean():
    """Satellite: the bundled apps + the bridge demo app lint clean —
    zero findings at error level (the shipped baseline the CI contract
    `demi_tpu lint demi_tpu.apps` rests on)."""
    findings = lint_targets()
    errors = [f for f in findings if f.severity == ERROR]
    assert errors == [], [f.to_json() for f in errors]


# ---------------------------------------------------------------------------
# Field-effect extraction + the may-commute relation
# ---------------------------------------------------------------------------

def test_raft_per_tag_effects():
    app = make_raft_app(3, bug="multivote")
    eff = analyze_dsl_app(app)
    assert eff.failure is None
    hb = eff.effect_for(T_HEARTBEAT)
    # HeartbeatTimer: pure reads + the |=-accumulated HEARD mask.
    assert hb.writes == frozenset()
    assert len(hb.or_writes) == 1
    assert effects_commute(hb, hb)
    # Everything else conflicts with itself (elections write ROLE/TERM,
    # appends write the log, ...).
    for t in (1, 3, 4, 5, 6, 7):
        e = eff.effect_for(t)
        if t != T_HEARTBEAT:
            assert not effects_commute(e, e), t
    # Out-of-range tags are UNKNOWN-conservative through the relation.
    rel = StaticIndependence.for_app(app)
    assert not rel.may_commute(99, T_HEARTBEAT)
    assert rel.may_commute(T_HEARTBEAT, T_HEARTBEAT)


def test_unanalyzable_handler_degrades_to_unknown():
    def handler(actor_id, state, snd, msg):
        try:  # try/except is outside the interpreter's modeled subset
            state = state * 2
        except ValueError:
            pass
        return state, None

    class FakeApp:
        tag_names = ("", "A", "B")
        timer_tags = ()

    FakeApp.handler = staticmethod(handler)
    eff = analyze_dsl_app(FakeApp)
    assert eff.failure is not None
    assert eff.default.is_unknown()
    assert not effects_commute(eff.effect_for(1), eff.effect_for(1))


def test_effectset_union_degrades_or_writes():
    a = EffectSet(reads=frozenset({1}), writes=frozenset(),
                  or_writes=frozenset({5}))
    b = EffectSet(reads=frozenset({2}), writes=frozenset({5}))
    u = a.union(b)
    assert u.writes == frozenset({5})
    assert u.or_writes == frozenset()  # plain write wins over |= on merge


def test_device_matrix_shape_and_catchall():
    app = make_raft_app(3)
    rel = StaticIndependence.for_app(app)
    mat = rel.device_matrix()
    n = rel.app_effects.n_tags
    assert mat.shape == (n + 2, n + 2)
    assert mat.dtype == np.uint8
    assert not mat[n + 1].any() and not mat[:, n + 1].any()  # unknown row
    assert np.array_equal(mat, mat.T)  # commutation is symmetric
    assert mat[T_HEARTBEAT, T_HEARTBEAT] == 1


def _random_msg(rng, app, tag):
    msg = rng.integers(0, 4, app.msg_width).astype(np.int32)
    msg[0] = tag
    return tuple(int(x) for x in msg)


def _apply(app, aid, state, snd, msg):
    s, out = app.handler(
        np.int32(aid), np.asarray(state, np.int32), np.int32(snd),
        np.asarray(msg, np.int32),
    )
    rows = np.asarray(out)
    rows = rows[rows[:, 0] != 0] if len(rows) else rows
    return np.asarray(s, np.int32), sorted(map(tuple, rows.tolist()))


def test_commute_claims_hold_dynamically_randomized():
    """Soundness fuzz: every tag pair StaticIndependence declares
    commuting must actually commute — both delivery orders from random
    states yield the same final state and the same emitted rows. This is
    the dynamic check backing 'unsoundness impossible by construction'."""
    rng = np.random.default_rng(42)
    apps = [make_raft_app(3, bug="multivote"), make_spark_app(2)]
    checked = 0
    for app in apps:
        eff = analyze_dsl_app(app)
        pairs = [
            (a, b)
            for a in range(1, eff.n_tags + 1)
            for b in range(a, eff.n_tags + 1)
            if effects_commute(eff.effect_for(a), eff.effect_for(b))
        ]
        for a, b in pairs:
            for _ in range(6):
                aid = int(rng.integers(0, app.num_actors))
                state = rng.integers(-1, 5, app.state_width).astype(np.int32)
                m1, m2 = _random_msg(rng, app, a), _random_msg(rng, app, b)
                snd1 = aid if a in app.timer_tags else int(
                    rng.integers(0, app.num_actors)
                )
                snd2 = aid if b in app.timer_tags else int(
                    rng.integers(0, app.num_actors)
                )
                s1, o1 = _apply(app, aid, state, snd1, m1)
                s12, o12 = _apply(app, aid, s1, snd2, m2)
                s2, o2 = _apply(app, aid, state, snd2, m2)
                s21, o21 = _apply(app, aid, s2, snd1, m1)
                assert np.array_equal(s12, s21), (app.name, a, b)
                assert sorted(o1 + o12) == sorted(o2 + o21), (app.name, a, b)
                checked += 1
    assert checked > 0  # raft hb x hb + spark submit pairs exist


def test_dep_tracker_prunes_only_declared_and_observed_noops():
    """Host-tier satellite: racing_pairs(trace, independence) drops
    EXACTLY the pairs the relation declares commuting — and each such
    pair is verified observationally commuting (both orders executed on
    the app handler), i.e. never a pair dep_tracker would have observed
    as dependent."""
    from demi_tpu.fingerprints import FingerprintFactory
    from demi_tpu.schedulers.dep_tracker import ROOT, DepTracker

    rng = np.random.default_rng(7)
    app = make_raft_app(3, bug="multivote")
    rel = StaticIndependence.for_app(app)
    tracker = DepTracker(FingerprintFactory())
    tracker.begin_execution()
    hb = (T_HEARTBEAT, 0, 0, 0, 0, 0, 0)
    trace = []
    parents = [ROOT]
    # A raft-shaped event stream: fungible heartbeat timers racing among
    # client commands and vote traffic at one receiver.
    stream = [
        ("r1", "r0", hb, True),
        ("r1", "r0", hb, True),
        ("ext", "r0", (T_CLIENT, 0, 11, 0, 0, 0, 0), False),
        ("r1", "r0", hb, True),
        ("r2", "r0", (3, 1, -1, 0, 0, 0, 0), False),  # RequestVote
        ("r2", "r0", (3, 1, -1, 0, 0, 0, 0), False),  # identical vote req
    ]
    for snd, rcv, msg, is_timer in stream:
        ev = tracker.event_for(snd, rcv, msg, rng.choice(parents), is_timer)
        trace.append(ev.id)
        parents.append(ev.id)
    plain = tracker.racing_pairs(trace)
    pruned_run = tracker.racing_pairs(trace, independence=rel)
    dropped = [p for p in plain if p not in pruned_run]
    assert dropped, "fixture must contain prunable pairs"
    assert pruned_run == [
        p
        for p in plain
        if rel.host_commutes_kind(
            tracker.events[trace[p[0]]], tracker.events[trace[p[1]]]
        )
        is None
    ]
    # Each dropped pair commutes observationally.
    for i, j in dropped:
        e1, e2 = tracker.events[trace[i]], tracker.events[trace[j]]
        aid = app.actor_id(e1.rcv)
        state = rng.integers(-1, 5, app.state_width).astype(np.int32)
        snd1 = aid if e1.is_timer else 1
        snd2 = aid if e2.is_timer else 1
        s12, o = _apply(app, aid, _apply(app, aid, state, snd1,
                                         e1.fingerprint)[0], snd2,
                        e2.fingerprint)
        s21, o2 = _apply(app, aid, _apply(app, aid, state, snd2,
                                          e2.fingerprint)[0], snd1,
                         e1.fingerprint)
        assert np.array_equal(s12, s21)


# ---------------------------------------------------------------------------
# Device-tier pruning: A/B no-op-only + host-path parity
# ---------------------------------------------------------------------------

def _dpor_fixture(app, program, pool=96, max_steps=64):
    from demi_tpu.device import DeviceConfig

    return DeviceConfig.for_app(
        app, pool_capacity=pool, max_steps=max_steps, max_external_ops=16,
        invariant_interval=1, record_trace=True, record_parents=True,
    )


def _raft_dpor_setup():
    from demi_tpu.apps.common import dsl_start_events
    from demi_tpu.device.dpor_sweep import make_dpor_kernel
    from demi_tpu.external_events import MessageConstructor, Send, WaitQuiescence

    app = make_raft_app(3, bug="multivote")
    program = dsl_start_events(app) + [
        Send(app.actor_name(0),
             MessageConstructor(lambda: (T_CLIENT, 0, 7, 0, 0, 0, 0))),
        Send(app.actor_name(1),
             MessageConstructor(lambda: (T_CLIENT, 0, 8, 0, 0, 0, 0))),
        WaitQuiescence(),
    ]
    cfg = _dpor_fixture(app, program)
    return app, cfg, program, make_dpor_kernel(app, cfg)


def _explore(app, cfg, program, kernel, rel, rounds=2, batch=8):
    from demi_tpu.device.dpor_sweep import DeviceDPOR

    d = DeviceDPOR(
        app, cfg, program, batch_size=batch, prefix_fork=False,
        double_buffer=False, kernel=kernel,
        static_independence=rel if rel is not None else False,
    )
    d.explore(target_code=99, max_rounds=rounds)
    return d


def test_device_static_prune_noop_only_raft():
    """Acceptance: with static pruning enabled on the raft fixture,
    interleavings are bit-identical to the unpruned run, the explored
    set/frontier shrink by EXACTLY (a subset of) the audited no-op
    prescriptions, and analysis.static_pruned > 0."""
    app, cfg, program, kernel = _raft_dpor_setup()
    base = _explore(app, cfg, program, kernel, None)
    rel = StaticIndependence.for_app(app, audit=True)
    pruned = _explore(app, cfg, program, kernel, rel)
    assert rel.pruned > 0
    assert pruned.static_stats == rel.pruned_total
    assert base.interleavings == pruned.interleavings
    assert not (pruned.explored - base.explored)
    audit = set(rel.pruned_prescriptions)
    assert (base.explored - pruned.explored) <= audit
    assert set(base.frontier) - set(pruned.frontier) <= audit
    assert not (set(pruned.frontier) - set(base.frontier))


def test_device_static_prune_broadcast_bit_identical():
    """Broadcast half of the acceptance: relays carry distinct senders
    and external ids are distinct, so the relation finds nothing to
    prune — the pruned run must be EXACTLY the unpruned run."""
    from demi_tpu.apps.broadcast import TAG_BCAST
    from demi_tpu.apps.common import dsl_start_events
    from demi_tpu.device.dpor_sweep import make_dpor_kernel
    from demi_tpu.external_events import MessageConstructor, Send, WaitQuiescence

    app = make_broadcast_app(4, reliable=False)
    program = dsl_start_events(app) + [
        Send(app.actor_name(0), MessageConstructor(lambda: (TAG_BCAST, 0))),
        Send(app.actor_name(1), MessageConstructor(lambda: (TAG_BCAST, 1))),
        WaitQuiescence(),
    ]
    cfg = _dpor_fixture(app, program, pool=64, max_steps=48)
    kernel = make_dpor_kernel(app, cfg)
    base = _explore(app, cfg, program, kernel, None)
    rel = StaticIndependence.for_app(app, audit=True)
    pruned = _explore(app, cfg, program, kernel, rel)
    assert base.interleavings == pruned.interleavings
    assert (base.explored - pruned.explored) <= set(rel.pruned_prescriptions)
    assert not (pruned.explored - base.explored)


def test_batch_filter_native_numpy_parity_randomized():
    """The native per-pair filter and the NumPy post-filter (the audit
    path) emit the same surviving stream and the same pruned counts —
    randomized, with a synthetic commute matrix so both kinds fire."""
    from demi_tpu.native.analysis import racing_prescriptions_batch

    rng = np.random.default_rng(5)
    w, rmax = 9, 40

    def rand_lane(n):
        recs = np.zeros((n, w), np.int32)
        recs[:, 0] = rng.choice([0, 1, 2, 5], size=n, p=[0.1, 0.5, 0.2, 0.2])
        recs[:, 1] = rng.integers(0, 4, n)
        recs[:, 2] = rng.integers(0, 4, n)
        recs[:, 3: w - 2] = rng.integers(0, 3, (n, w - 5))
        for p in range(n):
            recs[p, w - 2] = rng.integers(-1, p) if p else -1
            recs[p, w - 1] = rng.integers(-1, p) if p else -1
        return recs

    def make_rel(audit):
        rel = StaticIndependence(app_effects=None, fungible=True, audit=audit)
        mat = np.zeros((4, 4), np.uint8)
        mat[1, 1] = mat[2, 2] = mat[1, 2] = mat[2, 1] = 1
        rel.device_matrix = lambda: mat
        return rel

    for _ in range(6):
        batch = int(rng.integers(1, 6))
        recs3 = np.stack([rand_lane(rmax) for _ in range(batch)])
        lens = rng.integers(0, rmax + 1, batch).astype(np.int32)
        fast = make_rel(False)
        out_fast = racing_prescriptions_batch(
            recs3, lens, w, independence=fast
        )
        audit = make_rel(True)
        out_audit = racing_prescriptions_batch(
            recs3, lens, w, independence=audit
        )
        for a, b in zip(out_fast, out_audit):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        assert fast.pruned_total == audit.pruned_total
        assert len(audit.pruned_prescriptions) == audit.pruned
        plain = racing_prescriptions_batch(recs3, lens, w)
        assert len(plain[2]) - len(out_fast[2]) == fast.pruned


# ---------------------------------------------------------------------------
# Runtime sanitizer
# ---------------------------------------------------------------------------

@pytest.fixture
def sanitizing():
    from demi_tpu.analysis import sanitize

    sanitize.enable(strict=False)
    sanitize.reset_stats()
    yield sanitize
    sanitize.reset()
    sanitize.reset_stats()


def _system():
    from demi_tpu.runtime.system import ControlledActorSystem

    return ControlledActorSystem()


def test_sanitizer_catches_receive_mutation(sanitizing):
    from demi_tpu.runtime.actor import Actor

    class Mutator(Actor):
        def receive(self, ctx, snd, msg):
            msg.append("oops")

    sys_ = _system()
    sys_.spawn("a", Mutator)
    sys_.deliver(sys_.inject("a", ["payload"]))
    assert sanitizing.stats()["mutations_receive"] == 1


def test_sanitizer_catches_pending_mutation(sanitizing):
    from demi_tpu.runtime.actor import Actor

    class Sender(Actor):
        def __init__(self):
            self.buf = []

        def receive(self, ctx, snd, msg):
            self.buf.append(1)
            ctx.send("b", self.buf)  # shared mutable payload...
            self.buf.append(2)       # ...mutated after the send

    class Sink(Actor):
        def receive(self, ctx, snd, msg):
            pass

    sys_ = _system()
    sys_.spawn("a", Sender)
    sys_.spawn("b", Sink)
    pend = sys_.deliver(sys_.inject("a", ("go",)))
    assert pend[0].sent_digest is not None
    sys_.deliver(pend[0])
    assert sanitizing.stats()["mutations_pending"] == 1


def test_sanitizer_traps_time_and_random(sanitizing):
    import random as _random

    from demi_tpu.runtime.actor import Actor

    class Clocky(Actor):
        def receive(self, ctx, snd, msg):
            _time.time()
            _random.random()
            ctx.rng().randint(0, 9)  # sanctioned: must NOT trap

    sys_ = _system()
    sys_.spawn("a", Clocky)
    sys_.deliver(sys_.inject("a", ("tick",)))
    st = sanitizing.stats()
    assert st["time_reads"] == 1
    assert st["random_draws"] == 1
    # Traps restored after the delivery: calls outside a handler are
    # real and uncounted.
    assert _time.time() > 0
    _random.random()
    assert sanitizing.stats() == st


def test_sanitizer_strict_raises_harness_error(sanitizing):
    from demi_tpu.analysis.sanitize import SanitizerError
    from demi_tpu.runtime.actor import Actor
    from demi_tpu.runtime.system import HarnessError

    class Clocky(Actor):
        def receive(self, ctx, snd, msg):
            _time.time()

    sanitizing.enable(strict=True)
    sys_ = _system()
    sys_.spawn("a", Clocky)
    with pytest.raises(SanitizerError) as ei:
        sys_.deliver(sys_.inject("a", ("tick",)))
    assert isinstance(ei.value, HarnessError)
    # The actor is NOT marked crashed — nondeterminism is infrastructure.
    assert not sys_.is_crashed("a")


def test_ctx_rng_is_replay_stable():
    from demi_tpu.runtime.actor import Actor

    class RngUser(Actor):
        def __init__(self):
            self.vals = []

        def receive(self, ctx, snd, msg):
            self.vals.append(ctx.rng().randint(0, 10**9))

    def run():
        sys_ = _system()
        sys_.spawn("r", RngUser)
        for payload in (("a",), ("b",)):
            sys_.deliver(sys_.inject("r", payload))
        return sys_.actors["r"].vals

    first, second = run(), run()
    assert first == second
    assert first[0] != first[1]  # distinct deliveries draw distinct streams


def test_sanitizer_off_is_zero_overhead_path():
    from demi_tpu.analysis import sanitize
    from demi_tpu.runtime.actor import Actor

    sanitize.disable()

    class Plain(Actor):
        def receive(self, ctx, snd, msg):
            _time.time()

    sys_ = _system()
    sys_.spawn("a", Plain)
    pend = sys_.deliver(sys_.inject("a", ("x",)))
    assert sanitize.stats()["time_reads"] == 0
    assert all(e.sent_digest is None for e in pend)
    sanitize.reset()  # restore env-driven resolution


def test_np_random_reports_once():
    src = (
        "import numpy as np\n"
        "def handler(actor_id, state, snd, msg):\n"
        "    return state, np.random.choice([1, 2])\n"
    )
    findings = lint_source(src, "s.py")
    assert len(findings) == 1
    assert findings[0].rule == "unseeded-random"
    assert "np.random.choice" in findings[0].message


def test_actor_alias_escape_degrades_to_unknown():
    """A self-attr container escaping into an alias or a call argument
    must degrade the actor-class effect scan to UNKNOWN (mutation
    through the alias is invisible to the attribute-store scan)."""
    from demi_tpu.analysis import analyze_actor_class

    class Aliasing:
        def receive(self, ctx, snd, msg):
            if msg[0] == 1:
                q = self.queue  # noqa: F841 — alias escape
            elif msg[0] == 2:
                ctx.send("x", self.queue)  # call-arg escape

    eff = analyze_actor_class(Aliasing)
    assert eff.effect_for(1).is_unknown()
    assert eff.effect_for(2).is_unknown()

    class Clean:
        def receive(self, ctx, snd, msg):
            if msg[0] == 1:
                self.count = self.count + 1  # consumed by value: precise
            elif msg[0] == 2:
                self.other = len(self.items)  # pure-builtin arg: precise

    eff = analyze_actor_class(Clean)
    e1, e2 = eff.effect_for(1), eff.effect_for(2)
    assert not e1.is_unknown() and not e2.is_unknown()
    assert e1.writes == frozenset({"count"})
    assert e2.writes == frozenset({"other"})
    from demi_tpu.analysis import effects_commute

    assert effects_commute(e1, e2)


def test_loops_in_handlers_degrade_to_unknown():
    def handler(actor_id, state, snd, msg):
        for _ in range(2):
            state = state
        return state, None

    class FakeApp:
        tag_names = ("", "A")
        timer_tags = ()

    FakeApp.handler = staticmethod(handler)
    eff = analyze_dsl_app(FakeApp)
    assert eff.failure is not None
    assert eff.default.is_unknown()
