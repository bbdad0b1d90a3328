"""A fuzzed program is its op rows first (PR 30): ``generate_fuzz_test``
records what it draws as int rows (``fuzzing/program.py``), the events
are a view of the rows, and ``lower_program`` lowers an unedited
``FuzzProgram`` from the rows with no event object.

Held here, per app generator x weight mix: the row path equals the
per-event path array for array; a hash over 2,048 lowered programs
equals a constant recorded at the parent commit (``fd04a45``, where
``generate_fuzz_test`` built event objects and ``lower_program`` walked
them: that loop no longer exists to compare against); and the view keeps
the event list's contract."""
import pickle
import random

import numpy as np
import pytest

from demi_tpu.external_events import (
    ExternalEvent,
    MessageConstructor,
    Send,
    Start,
    WaitQuiescence,
    sanity_check_externals,
)
from demi_tpu.fuzzing import FuzzProgram, MessageGenerator
import dataclasses
import hashlib

import jax.numpy as jnp

from demi_tpu.apps.broadcast import broadcast_send_generator, make_broadcast_app
from demi_tpu.apps.chain import chain_send_generator, make_chain_app
from demi_tpu.apps.common import dsl_start_events
from demi_tpu.apps.raft import make_raft_app, raft_send_generator
from demi_tpu.apps.spark_dag import make_spark_app, spark_send_generator
from demi_tpu.apps.twopc import make_twopc_app, twopc_send_generator
from demi_tpu.device import DeviceConfig
from demi_tpu.device.encoding import empty_programs, lower_into, lower_program
from demi_tpu.fuzzing import Fuzzer, FuzzerWeights


def _untargeted(gen):
    gen.target = None
    return gen


def _all_started(states, alive):
    return jnp.all(alive)


APPS = {
    "raft": (lambda: make_raft_app(5, log_cap=8, bug="multivote"), raft_send_generator),
    "spark": (lambda: make_spark_app(num_workers=3), spark_send_generator),
    # The spark generator as it was at the parent commit: SubmitJob to
    # any alive actor (since PR 33 it names the driver, with the same
    # draws); keeps the parent's four hashes pinned.
    "spark-anywhere": (
        lambda: make_spark_app(num_workers=3),
        lambda app: _untargeted(spark_send_generator(app)),
    ),
    "twopc": (lambda: make_twopc_app(4), twopc_send_generator),
    "broadcast": (
        lambda: dataclasses.replace(
            make_broadcast_app(4, reliable=False),
            conditions=(_all_started, _all_started),
        ),
        broadcast_send_generator,
    ),
    "chain": (lambda: make_chain_app(4), chain_send_generator),
}

# name -> (num_events, FuzzerWeights kwargs, Fuzzer kwargs)
MIXES = {
    # The sweep verb's defaults (raft5-sweep's mix).
    "defaults": (12, dict(kill=0.05, send=0.6, wait_quiescence=0.15), dict(max_kills=1)),
    # raft5-nemesis's mix.
    "nemesis": (
        48,
        dict(kill=0.0, send=0.1, wait_quiescence=0.35, partition=0.1,
             unpartition=0.1, hard_kill=0.25, restart=0.3),
        dict(max_kills=4, wait_budget=(1, 25)),
    ),
    "atomic": (
        16,
        dict(kill=0.02, send=0.3, wait_quiescence=0.15, atomic_block=0.3,
             hard_kill=0.05, restart=0.1),
        dict(max_kills=2, wait_budget=(1, 9)),
    ),
    # Only drawn for an app with a conditions table (broadcast here).
    "waitcond": (
        14,
        dict(kill=0.05, send=0.4, wait_quiescence=0.1, wait_condition=0.3,
             partition=0.05, unpartition=0.05),
        dict(max_kills=1, wait_budget=(1, 12)),
    ),
    # More sends asked for than any bounded generator gives: runs dry.
    "dry": (24, dict(kill=0.0, send=1.0, wait_quiescence=0.05), dict()),
}

CASES = [
    (app, mix)
    for app in APPS
    for mix in MIXES
    if mix != "waitcond" or app == "broadcast"
]

BIG = (((2147490031 << 16) + 3) << 20)  # the benchmark's seed form, > 2**64
HASH_SEEDS = list(range(1024)) + [BIG + s for s in range(1024)]


def build(app_name, mix_name):
    make_app, make_gen = APPS[app_name]
    app = make_app()
    num_events, weights, kwargs = MIXES[mix_name]
    if mix_name == "waitcond":
        kwargs = dict(kwargs, num_conditions=len(app.conditions))
    fuzzer = Fuzzer(
        num_events=num_events,
        weights=FuzzerWeights(**weights),
        message_gen=make_gen(app),
        prefix=dsl_start_events(app),
        **kwargs,
    )
    cfg = DeviceConfig.for_app(
        app, pool_capacity=32, max_steps=32,
        max_external_ops=num_events + app.num_actors + 2,
    )
    return app, cfg, fuzzer


def lowered_hash(app, cfg, fuzzer, seeds=HASH_SEEDS):
    h = hashlib.blake2b(digest_size=16)
    for seed in seeds:
        p = lower_program(app, cfg, fuzzer.generate_fuzz_test(seed=seed))
        for arr in (p.op, p.a, p.b, p.msg):
            h.update(arr.tobytes())
    return h.hexdigest()


# blake2b-128 over op, a, b, msg of lower_program(generate_fuzz_test(s))
# for s in HASH_SEEDS, recorded at the parent commit fd04a45.
PARENT_HASH = {
    "raft-defaults": "289fe27bf917806bb4f8b9c2fd5c43a3",
    "raft-nemesis": "7ffc6c12500fa4f1b9add23e4a82e8c5",
    "raft-atomic": "3e13e204cee61e2d0ab6a2885dc6805b",
    "raft-dry": "a06d24d915d033d815f112833b765a4c",
    # recorded at PR 33, which addressed SubmitJob to the driver
    "spark-defaults": "cd45f2e2d9422ca773fcb840295cdc18",
    "spark-nemesis": "71d671040e93fca3a88d50573202246a",
    "spark-atomic": "46353034e1c357fd6fce170f344f920b",
    "spark-dry": "8d3d33071e67b9353e3c4085c373e4c5",
    "spark-anywhere-defaults": "5685124ff12f851eb40380b268eac045",
    "spark-anywhere-nemesis": "7abc724d06b0f6318b8d538ffb602026",
    "spark-anywhere-atomic": "874e7fa00aaa488170f3cb9ec5a4295d",
    "spark-anywhere-dry": "ed29a37b7fb2733556bbc86629620bd0",
    "twopc-defaults": "fca04ff18a6e7f5f663e32bef5000040",
    "twopc-nemesis": "3fa1f75012346385fd400c50801eac73",
    "twopc-atomic": "9092040dc7d7b5cf679c10864ca5de86",
    "twopc-dry": "1f59593b8ec6b5402c5da1442d90c62e",
    "broadcast-defaults": "12a3a6d95e39b07f8603168dde281c2a",
    "broadcast-nemesis": "fdc5505d9c403baae522499a74c46060",
    "broadcast-atomic": "bf82c796b53e7861c0555845305ea385",
    "broadcast-waitcond": "124f14b172cc172034125ad58f0816d3",
    "broadcast-dry": "59c4a707950d38f21ccd2bef0768b3d7",
    "chain-defaults": "ebe1720747df4a4d8ec9e6761d84eda1",
    "chain-nemesis": "8885f938546ad6aa5899e41b073058ee",
    "chain-atomic": "9064aec4e29dfce6cd965ae50ac8fdb6",
    "chain-dry": "de55926e4153a11ff5ea02c3edf6bf3e"
}

IDS = [f"{app}-{mix}" for app, mix in CASES]
VIEW_SEEDS = [0, 1, 7, 1023, BIG, BIG + 5, BIG + 1023, 2**64 + 3]


@pytest.fixture(scope="module", params=CASES, ids=IDS)
def case(request):
    app_name, mix_name = request.param
    return (f"{app_name}-{mix_name}",) + build(app_name, mix_name)


def test_lowered_programs_hash_as_at_the_parent_commit(case):
    name, app, cfg, fuzzer = case
    assert lowered_hash(app, cfg, fuzzer) == PARENT_HASH[name]


def test_row_path_equals_event_path(case):
    _name, app, cfg, fuzzer = case
    for seed in HASH_SEEDS[::8]:
        prog = fuzzer.generate_fuzz_test(seed=seed)
        assert type(prog) is FuzzProgram and prog.lowerable
        from_rows = lower_program(app, cfg, prog)
        assert prog._events is None, "the row path made events"
        from_events = lower_program(app, cfg, list(prog))
        for got, want in zip(from_rows, from_events):
            assert got.dtype == want.dtype and np.array_equal(got, want)


def test_lower_into_overwrites_the_lanes_old_program(case):
    """A lane is written over whatever it held, by either path."""
    _name, app, cfg, fuzzer = case
    out = empty_programs(cfg, 3)
    for arr in out:
        arr[:] = 77
    long_one = max(
        (fuzzer.generate_fuzz_test(seed=s) for s in range(32)), key=len
    )
    assert lower_into(app, cfg, long_one, out, 1) is True
    short = fuzzer.generate_fuzz_test(seed=BIG)
    assert lower_into(app, cfg, short, out, 1) is True
    assert lower_into(app, cfg, list(short), out, 2) is False
    want = lower_program(app, cfg, list(short))
    for arr, ref in zip(out, want):
        assert np.array_equal(arr[1], ref) and np.array_equal(arr[2], ref)
        assert (arr[0] == 77).all()


def test_the_view_is_the_event_list(case):
    _name, app, cfg, fuzzer = case
    n_prefix = len(fuzzer.prefix)
    for seed in VIEW_SEEDS:
        prog = fuzzer.generate_fuzz_test(seed=seed)
        n = len(prog)  # answered from the rows
        assert prog._events is None
        events = list(prog)
        assert len(events) == n and isinstance(events, list)
        assert all(isinstance(e, ExternalEvent) for e in events)
        assert all(prog[i] is events[i] for i in range(n))
        assert prog[-1] is events[-1] and list(iter(prog)) == events
        assert isinstance(prog[n_prefix:], list)
        assert prog[n_prefix:] == events[n_prefix:]
        # the prefix is the fuzzer's own Start objects, as it always was
        assert all(a is b for a, b in zip(events, fuzzer.prefix))
        eids = [e.eid for e in events]
        assert eids == sorted(eids) and len(set(eids)) == n
        # ends in an unbounded wait, no two waits in a row
        assert isinstance(events[-1], WaitQuiescence)
        assert events[-1].budget is None
        assert not any(
            isinstance(x, WaitQuiescence) and isinstance(y, WaitQuiescence)
            for x, y in zip(events, events[1:])
        )
        ctor = {e.name: e.ctor for e in fuzzer.prefix}
        for e in events[n_prefix:]:
            if isinstance(e, Start):  # a restart carries the ctor
                assert e.ctor is ctor[e.name] and e.ctor is not None
        # block ids: contiguous runs of >= 2 sends, each id above its
        # members' eids and below the next event's
        blocks = {}
        for i, e in enumerate(events):
            if e.block_id is not None:
                blocks.setdefault(e.block_id, []).append(i)
        assert len(blocks) == len(prog.blocks)
        for bid, members in blocks.items():
            assert members == list(range(members[0], members[-1] + 1))
            assert len(members) >= 2
            assert all(isinstance(events[i], Send) for i in members)
            assert events[members[-1]].eid < bid
            assert bid < events[members[-1] + 1].eid
        sanity_check_externals(events)


def test_the_draw_loop_emits_nothing_the_sanity_check_rejects(case):
    """``sanity_check_externals`` runs when the events are made, not per
    generated program: over many seeds it never fires."""
    _name, _app, _cfg, fuzzer = case
    for seed in range(200):
        sanity_check_externals(list(fuzzer.generate_fuzz_test(seed=seed)))


def test_same_seed_same_program_and_fresh_events(case):
    _name, app, cfg, fuzzer = case
    one = fuzzer.generate_fuzz_test(seed=BIG + 9)
    two = fuzzer.generate_fuzz_test(seed=BIG + 9)
    assert (one.kind, one.a, one.b, one.payloads, one.blocks) == (
        two.kind, two.a, two.b, two.payloads, two.blocks
    )
    n_prefix = len(fuzzer.prefix)
    assert not {e.eid for e in one[n_prefix:]} & {e.eid for e in two[n_prefix:]}


def _no_ctor_fuzzer(fuzzer):
    """The same fuzzer over Starts whose ctor pickles (``dsl_actor_factory``'s
    closures do not, in a list of events either)."""
    return Fuzzer(
        num_events=fuzzer.num_events, weights=fuzzer.weights,
        message_gen=fuzzer.message_gen,
        prefix=[Start(e.name) for e in fuzzer.prefix],
        max_kills=fuzzer.max_kills, wait_budget=fuzzer.wait_budget,
        num_conditions=fuzzer.num_conditions,
    )


def test_pickle_round_trips(case):
    _name, app, cfg, fuzzer = case
    plain = _no_ctor_fuzzer(fuzzer)
    prog = plain.generate_fuzz_test(seed=BIG + 3)
    back = pickle.loads(pickle.dumps(prog))
    assert type(back) is FuzzProgram and back._events is None
    for got, want in zip(lower_program(app, cfg, back), lower_program(app, cfg, prog)):
        assert np.array_equal(got, want)
    # the rows are what is pickled: a looked-at program's copy makes its
    # own events (the cached ones hold closures, as a list's always did)
    events = list(prog)
    seen = pickle.loads(pickle.dumps(prog))
    assert seen._events is None
    assert [type(e) for e in seen] == [type(e) for e in events]
    n_prefix = len(plain.prefix)  # the prefix events travel as they are
    assert [e.eid for e in seen[:n_prefix]] == [e.eid for e in plain.prefix]
    assert not {e.eid for e in seen[n_prefix:]} & {e.eid for e in events}


# -- what is not a plain fuzzed program takes the per-event loop ----------

def _raft():
    return build("raft", "defaults")


def test_a_list_takes_the_event_path_and_says_so():
    app, cfg, fuzzer = _raft()
    prog = fuzzer.generate_fuzz_test(seed=5)
    out = empty_programs(cfg, 1)
    assert lower_into(app, cfg, prog, out, 0) is True
    assert lower_into(app, cfg, list(prog), out, 0) is False
    assert lower_into(app, cfg, prog[:-2] + [WaitQuiescence()], out, 0) is False


def test_a_fuzz_program_cannot_be_edited_in_place():
    _app, _cfg, fuzzer = _raft()
    prog = fuzzer.generate_fuzz_test(seed=5)
    with pytest.raises(TypeError):
        prog[0] = prog[1]
    with pytest.raises(TypeError):
        prog + [WaitQuiescence()]
    with pytest.raises(AttributeError):
        prog.append(WaitQuiescence())
    assert not isinstance(prog, list)


class _OnlyGenerate(MessageGenerator):
    """The public contract alone: a ``Send`` with a late-bound ctor."""

    def __init__(self, app):
        self.app = app
        self.made = []

    def generate(self, rng, alive):
        if not alive:
            return None
        send = Send(
            rng.choice(list(alive)),
            MessageConstructor(lambda: (1, 0, len(self.made))),
        )
        self.made.append(send)
        return send


def test_a_generator_with_only_generate_still_works():
    app, cfg, fuzzer = _raft()
    gen = _OnlyGenerate(app)
    fz = Fuzzer(
        num_events=10, weights=fuzzer.weights, message_gen=gen,
        prefix=fuzzer.prefix, max_kills=1,
    )
    prog = fz.generate_fuzz_test(seed=3)
    assert not prog.lowerable and gen.made
    sends = [e for e in prog if isinstance(e, Send)]
    assert all(a is b for a, b in zip(sends, gen.made))  # its own objects
    out = empty_programs(cfg, 1)
    assert lower_into(app, cfg, prog, out, 0) is False
    want = lower_program(app, cfg, list(prog))
    assert all(np.array_equal(x[0], y) for x, y in zip(out, want))


def test_a_subclass_that_overrides_only_generate_means_that_one():
    from demi_tpu.apps.common import DSLSendGenerator

    app, cfg, fuzzer = _raft()
    calls = []

    class Loud(DSLSendGenerator):
        def generate(self, rng, alive):
            send = super().generate(rng, alive)
            calls.append(send)
            return send

    fz = Fuzzer(
        num_events=fuzzer.num_events, weights=fuzzer.weights,
        message_gen=Loud(app, fuzzer.message_gen.make_msg),
        prefix=fuzzer.prefix, max_kills=fuzzer.max_kills,
    )
    prog = fz.generate_fuzz_test(seed=11)
    assert calls and not prog.lowerable
    ref = fuzzer.generate_fuzz_test(seed=11)
    for got, want in zip(lower_program(app, cfg, prog), lower_program(app, cfg, ref)):
        assert np.array_equal(got, want)


def test_a_send_to_a_never_started_actor_is_rejected_at_the_draw():
    app, _cfg, fuzzer = _raft()

    class Stray(MessageGenerator):
        def generate(self, rng, alive):
            return Send("nobody", MessageConstructor(lambda: (1, 0, 0)))

    fz = Fuzzer(
        num_events=4, weights=FuzzerWeights(send=1.0), message_gen=Stray(),
        prefix=fuzzer.prefix,
    )
    with pytest.raises(ValueError, match="never-started"):
        fz.generate_fuzz_test(seed=0)


@pytest.mark.parametrize("tail", ["none", "wait", "bounded_wait", "send"])
def test_a_prefix_or_postfix_that_is_no_row_takes_the_event_path(tail):
    """Bootstrap sends in the prefix, a postfix: the rows are then only
    the drawn part, the view holds the whole and applies the
    trailing-wait rule, and the lowering walks the events."""
    app, cfg, fuzzer = _raft()
    boot = Send(app.actor_name(0), MessageConstructor(lambda: (1, 0, 99)))
    postfix = {
        "none": [],
        "wait": [WaitQuiescence()],
        "bounded_wait": [WaitQuiescence(budget=3)],
        "send": [Send(app.actor_name(1), MessageConstructor(lambda: (1, 0, 98)))],
    }[tail]
    fz = Fuzzer(
        num_events=8, weights=fuzzer.weights,
        message_gen=fuzzer.message_gen,
        prefix=list(fuzzer.prefix) + [boot, WaitQuiescence(budget=2)],
        postfix=postfix, max_kills=1, wait_budget=(1, 5),
    )
    for seed in range(40):
        prog = fz.generate_fuzz_test(seed=seed)
        assert not prog.lowerable
        events = list(prog)
        assert len(prog) == len(events)
        assert events[: len(fz.prefix)] == fz.prefix
        assert isinstance(events[-1], WaitQuiescence)
        if tail == "bounded_wait":
            assert events[-1] is postfix[-1]  # kept verbatim
        else:
            assert events[-1].budget is None
        if tail == "send":
            assert events[-2] is postfix[-1]
        # the first drawn event is no wait: the prefix ends in one
        assert not isinstance(events[len(fz.prefix)], WaitQuiescence)
        out = empty_programs(cfg, 1)
        assert lower_into(app, cfg, prog, out, 0) is False


def test_a_prefix_in_another_order_lowers_through_the_name_table():
    app, cfg, fuzzer = _raft()
    shuffled = list(fuzzer.prefix)
    random.Random(4).shuffle(shuffled)
    assert [e.name for e in shuffled] != [e.name for e in fuzzer.prefix]
    fz = Fuzzer(
        num_events=12, weights=FuzzerWeights(
            kill=0.1, send=0.4, wait_quiescence=0.1, partition=0.2,
            unpartition=0.1, hard_kill=0.1, restart=0.2,
        ),
        message_gen=fuzzer.message_gen, prefix=shuffled, max_kills=3,
        wait_budget=(1, 7),
    )
    for seed in range(60):
        prog = fz.generate_fuzz_test(seed=seed)
        assert prog.lowerable
        for got, want in zip(
            lower_program(app, cfg, prog), lower_program(app, cfg, list(prog))
        ):
            assert np.array_equal(got, want)


def test_live_weights_are_read_at_every_call():
    """The autotuned sweep swaps (and a caller may edit) the weights
    between programs."""
    app, cfg, fuzzer = _raft()
    before = fuzzer.generate_fuzz_test(seed=2).kind
    old = fuzzer.weights
    try:
        fuzzer.set_weights(FuzzerWeights(send=0.0, kill=0.0, wait_quiescence=1.0))
        only_waits = fuzzer.generate_fuzz_test(seed=2)
        assert len(only_waits) == len(fuzzer.prefix) + 1
        fuzzer.weights.send = 5.0  # edited in place
        assert len(fuzzer.generate_fuzz_test(seed=2)) > len(only_waits)
    finally:
        fuzzer.set_weights(old)
    assert fuzzer.generate_fuzz_test(seed=2).kind == before


def test_a_payload_out_of_int16_range_is_still_rejected():
    import dataclasses as dc

    app, cfg, fuzzer = _raft()
    from demi_tpu.apps.common import DSLSendGenerator

    fz = Fuzzer(
        num_events=6, weights=FuzzerWeights(send=1.0),
        message_gen=DSLSendGenerator(app, lambda rng, n: (1, 0, 40000 + n)),
        prefix=fuzzer.prefix,
    )
    narrow = dc.replace(cfg, msg_dtype="int16")
    prog = fz.generate_fuzz_test(seed=1)
    lower_program(app, cfg, prog)
    with pytest.raises(ValueError, match="int16"):
        lower_program(app, narrow, prog)


def test_a_wait_budget_below_one_is_rejected_at_the_draw():
    app, _cfg, fuzzer = _raft()
    fz = Fuzzer(
        num_events=6, weights=FuzzerWeights(send=0.5, wait_quiescence=0.5),
        message_gen=fuzzer.message_gen, prefix=fuzzer.prefix,
        wait_budget=(0, 0),
    )
    with pytest.raises(ValueError, match="budget"):
        for seed in range(20):
            fz.generate_fuzz_test(seed=seed)


def test_a_cond_id_the_app_has_no_predicate_for_is_rejected_by_both_paths():
    app, cfg, fuzzer = build("broadcast", "waitcond")
    fuzzer.num_conditions = len(app.conditions) + 3
    prog = next(
        p for p in (fuzzer.generate_fuzz_test(seed=s) for s in range(400))
        if any(k == 8 and a >= len(app.conditions) for k, a in zip(p.kind, p.a))
    )
    with pytest.raises(ValueError, match="cond_id"):
        lower_program(app, cfg, prog)
    with pytest.raises(ValueError, match="cond_id"):
        lower_program(app, cfg, list(prog))
