"""The pool insert a TPU runs, held to the one a CPU runs.

``insert_rows`` under ``index_mode='onehot'`` decides the insert from the
slot's side (a free slot's rank among free slots says which row it
receives: one ``[K, P]`` compare, one contraction); under ``'scatter'`` it
finds each row's slot number and scatters. Tier-1 runs on a CPU, where
``'auto'`` means scatter, so these cases are what guards the chip's path:
every field of the resulting state equal, over random pools and proposals.

Where the insert carries many rows (``core._short_insert_built``: the
flood's and the spark DAG's shapes) a batch of lanes takes a short pass
over the first ``INSERT_SHORT_ROWS`` valid rows in every step where no
lane inserts more, picked by one ``lax.switch`` for the whole batch: the
second half holds that pass to the scatter insert and to the full pass
over where the rows sit and how many there are, and holds the compiled
segment to one ``case`` there and none at raft's shape. Where up to
``INSERT_BURST_LANES`` lanes insert more, only they are then taken through
the full pass, one at a time; past that the whole batch is: the third part
holds the three branches to each other and the two counts a lane carries
to what was taken. The last tests hold the passes down: the lowered
one-hot insert compares over ``[K, P]`` once a branch (over one lane's in
the loop, over the batch's past the limit, never on the short pass).

Outside the short pass a slot reads its payload as a whole row
(``core._landed_rows``: K selects over ``[P, W]``, no column of the rows
taken): ``vsr5-p256`` (the VSR cell's 37-word rows) and a 16-word shape
run every parity case above beside raft's 7 words, and the last tests hold
the insert's jaxpr to no per-column slice there, and to its columns on the
short pass.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from demi_tpu import obs
from demi_tpu.apps.broadcast import make_broadcast_app
from demi_tpu.apps.common import dsl_start_events
from demi_tpu.apps.paxos import make_paxos_app
from demi_tpu.apps.raft import make_raft_app
from demi_tpu.apps.spark_dag import make_spark_app
from demi_tpu.apps.vsr import make_vsr_app
from demi_tpu.device.continuous import make_init_kernel, make_segment_kernel
from demi_tpu.device.core import (
    INSERT_BURST_LANES, INSERT_SHORT_FACTOR, INSERT_SHORT_ROWS, ST_DONE,
    ST_OVERFLOW, DeviceConfig, _landed_by_batch, _landed_full,
    _short_insert_built, init_state, insert_form, insert_rows,
)
from demi_tpu.device.dpor_sweep import build_dpor_kernel
from demi_tpu.device.encoding import empty_programs
from demi_tpu.device.encoding import lower_program
from demi_tpu.device.explore import (
    ExtProgram, broadcast_program, make_explore_kernel,
)
from demi_tpu.external_events import MessageConstructor, Send, WaitQuiescence
from demi_tpu.obs import spans

LANES = 4
SHAPES = {
    # name: (app builder, nodes, pool, K)
    "raft3-p32": (make_raft_app, 3, 32, None),
    "raft3-p96": (make_raft_app, 3, 96, None),
    "raft5-p96": (make_raft_app, 5, 96, None),
    "raft5-p256": (make_raft_app, 5, 256, None),
    "bcast8-p96": (make_broadcast_app, 8, 96, None),
    "bcast64-p4608": (make_broadcast_app, 64, 4608, 65),
    # a driver and 4 executors, 2 stages x 40 tasks: an 81-row outbox
    "spark5-p256": (
        lambda nodes: make_spark_app(nodes - 1, 2, 40), 5, 256, 82
    ),
    # the VSR cell's: 37-word rows (5 + log_cap 32); the fused step
    # proposes a start's 3 rows, a send and a 5-row outbox
    "vsr5-p256": (lambda nodes: make_vsr_app(nodes, log_cap=32), 5, 256, 9),
    # a width between raft's 7 and VSR's 37
    "vsr5-w16-p96": (lambda nodes: make_vsr_app(nodes, log_cap=11), 5, 96, 9),
    # the paxos cell's class: a 43-row insert of 20-word rows, so that
    # the payload's columns are most of the short pass's work
    "paxos11-p128": (lambda nodes: make_paxos_app(nodes, log_cap=8), 11, 128, None),
}
C = INSERT_SHORT_ROWS
FILLS = (0.0, 0.3, 0.83, 1.0)


def _app(shape):
    builder, nodes, _pool, _k = SHAPES[shape]
    return builder(nodes)


def _cfgs(shape, **overrides):
    app = _app(shape)
    pool = SHAPES[shape][2]
    return app, {
        mode: DeviceConfig.for_app(
            app, pool_capacity=pool, index_mode=mode, **overrides
        )
        for mode in ("scatter", "onehot")
    }


def _rows_k(shape, app):
    # The step's one insert carries the injection's rows and an outbox.
    return SHAPES[shape][3] or app.max_outbox + 2


def _case(app, cfg, rng, k, fill, *, n_rows=None, faults=False,
          externals=False, crec=None):
    """One lane: a random pool ``fill`` full, and K proposed rows."""
    n, p, w = cfg.num_actors, cfg.pool_capacity, cfg.msg_width
    state = init_state(app, cfg, jnp.asarray(rng.integers(0, 2**31, 2), jnp.uint32))
    valid = np.zeros(p, bool)
    valid[rng.permutation(p)[: int(round(fill * p))]] = True
    src = rng.integers(0, n + 1, p)
    dst = rng.integers(0, n, p)
    timer = (rng.random(p) < 0.2) & (src < n)
    dst = np.where(timer, np.minimum(src, n - 1), dst)
    msg_dtype = np.dtype(cfg.msg_dtype)
    info = np.iinfo(msg_dtype)
    cut = np.zeros((n, n), bool)
    stopped = np.zeros(n, bool)
    if faults:
        a, b = rng.permutation(n)[:2]
        cut[a, b] = cut[b, a] = True
        stopped[rng.integers(0, n)] = True
    state = state._replace(
        cut=jnp.asarray(cut),
        stopped=jnp.asarray(stopped),
        started=jnp.ones(n, bool),
        pool_valid=jnp.asarray(valid),
        pool_src=jnp.asarray(src, jnp.int32),
        pool_dst=jnp.asarray(dst, jnp.int32),
        pool_timer=jnp.asarray(timer),
        pool_parked=jnp.asarray(timer & (rng.random(p) < 0.3)),
        pool_msg=jnp.asarray(
            rng.integers(info.min, info.max, (p, w)), msg_dtype
        ),
        pool_seq=jnp.asarray(rng.integers(0, 1000, p), jnp.int32),
        pool_crec=jnp.asarray(rng.integers(-1, 50, p), jnp.int32),
        pool_head=jnp.asarray(rng.random(state.pool_head.shape[0]) < 0.5),
        seq_counter=jnp.int32(rng.integers(0, 5000)),
    )
    if n_rows is None:
        row_valid = rng.random(k) < 0.7
    else:
        row_valid = np.zeros(k, bool)
        row_valid[rng.permutation(k)[:n_rows]] = True
    row_src = rng.integers(0, n, k)
    if externals:
        row_src = np.where(rng.random(k) < 0.4, n, row_src)
    row_dst = rng.integers(0, n, k)
    row_timer = (rng.random(k) < 0.25) & (row_src < n)
    row_dst = np.where(row_timer, np.minimum(row_src, n - 1), row_dst)
    rows = (
        jnp.asarray(row_valid),
        jnp.asarray(row_src, jnp.int32),
        jnp.asarray(row_dst, jnp.int32),
        jnp.asarray(row_timer),
        jnp.asarray(row_timer & (rng.random(k) < 0.5)),
        # Proposals are int32 whatever the pool stores: the full range, so
        # a narrow pool's cast is part of what is compared.
        jnp.asarray(
            rng.integers(-(2**31), 2**31 - 1, (k, w)), jnp.int32
        ),
    )
    if crec == "scalar":
        rows += (jnp.int32(rng.integers(0, 100)),)
    elif crec == "rows":
        rows += (jnp.asarray(rng.integers(-1, 100, k), jnp.int32),)
    return state, rows


def _stack(cases):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *cases)


def _with_count(states, cfg):
    """The lanes as ``init_state`` makes them under ``cfg``: with the
    count of full passes where its insert has a short one (the cases are
    built from the scatter config's state, which has none)."""
    if not _short_insert_built(cfg):
        return states
    lanes = states.status.shape[0]
    zeros = jnp.zeros(lanes, jnp.int32)
    return states._replace(
        insert_full_steps=zeros, insert_full_lane_steps=zeros
    )


def _insert_both(cfgs, cases):
    """The cases as one batch through each mode's insert; where the
    one-hot insert picks its pass by the batch, also ``onehot-full``: the
    same lanes without the count, which take the full pass."""
    states, rows = _stack([c[0] for c in cases]), _stack([c[1] for c in cases])
    out = {}
    for mode, cfg in cfgs.items():
        fn = jax.jit(jax.vmap(
            lambda s, r, cfg=cfg: insert_rows(s, cfg, *r)
        ))
        out[mode] = fn(_with_count(states, cfg), rows)
        if mode == "onehot" and _short_insert_built(cfg):
            out["onehot-full"] = fn(states, rows)
    return out


def _assert_same(out, what, modes=("scatter", "onehot")):
    a, b = (out[m] for m in modes)
    for field in type(a)._fields:
        if field.startswith("insert_full_"):  # the one-hot insert's alone
            continue
        x, y = np.asarray(getattr(a, field)), np.asarray(getattr(b, field))
        assert x.dtype == y.dtype, f"{what}: {field} dtype"
        assert np.array_equal(x, y), (
            f"{what}: {field} differs between scatter and onehot"
        )


def _check(shape, seed, fill, lanes=LANES, cfg_overrides=None, **case_kw):
    app, cfgs = _cfgs(shape, **(cfg_overrides or {}))
    k = _rows_k(shape, app)
    rng = np.random.default_rng(seed)
    cases = [
        _case(app, cfgs["scatter"], rng, k, fill, **case_kw)
        for _ in range(lanes)
    ]
    out = _insert_both(cfgs, cases)
    _assert_same(out, f"{shape} fill {fill}")
    return out


@pytest.mark.parametrize("fill", FILLS)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_onehot_insert_equals_scatter_insert(shape, fill):
    out = _check(shape, 11, fill)
    if fill == 0.0:
        # Something landed: the comparison is not of two no-ops.
        assert np.asarray(out["onehot"].pool_valid).any()
    if fill == 1.0:
        assert (np.asarray(out["onehot"].status) == ST_OVERFLOW).all()


@pytest.mark.parametrize("extra", [0, 1], ids=["exact-fit", "one-too-many"])
@pytest.mark.parametrize("shape", [
    "raft5-p96", "bcast8-p96", "bcast64-p4608", "vsr5-p256", "vsr5-w16-p96",
])
def test_the_overflow_edge(shape, extra):
    app, cfgs = _cfgs(shape)
    k = _rows_k(shape, app)
    p = cfgs["scatter"].pool_capacity
    n_free = k - 2
    rng = np.random.default_rng(5)
    cases = [
        _case(app, cfgs["scatter"], rng, k, (p - n_free) / p,
              n_rows=n_free + extra)
        for _ in range(LANES)
    ]
    out = _insert_both(cfgs, cases)
    _assert_same(out, f"{shape} n_rows = n_free + {extra}")
    status = np.asarray(out["onehot"].status)
    assert (status == ST_OVERFLOW).all() == bool(extra)
    assert np.asarray(out["onehot"].pool_valid).all()


FEATURES = {
    "cut-links-and-stopped-receivers": dict(faults=True),
    "external-senders": dict(externals=True),
    "faults-and-externals": dict(faults=True, externals=True),
    "int16-payloads": dict(cfg_overrides=dict(msg_dtype="int16")),
    "srcdst-fifo-heads": dict(
        cfg_overrides=dict(srcdst_fifo=True), externals=True
    ),
    "scalar-crec": dict(
        cfg_overrides=dict(record_trace=True, record_parents=True),
        crec="scalar",
    ),
    "per-row-crec": dict(
        cfg_overrides=dict(record_trace=True, record_parents=True),
        crec="rows",
    ),
    "fifo-heads-int16-per-row-crec-faults": dict(
        cfg_overrides=dict(
            srcdst_fifo=True, msg_dtype="int16", record_trace=True,
            record_parents=True,
        ),
        crec="rows", faults=True, externals=True,
    ),
}


@pytest.mark.parametrize("feature", list(FEATURES))
@pytest.mark.parametrize("shape", ["raft5-p96", "bcast8-p96", "vsr5-p256"])
def test_onehot_insert_equals_scatter_insert_with(shape, feature):
    kw = dict(FEATURES[feature])
    for fill in (0.3, 0.9):
        out = _check(shape, 23, fill, **kw)
    if "fifo" in feature:
        assert out["onehot"].pool_head.shape[-1] == SHAPES[shape][2]


def test_the_flood_shape_with_everything_on():
    _check(
        "bcast64-p4608", 31, 0.83, lanes=2,
        cfg_overrides=dict(
            srcdst_fifo=True, record_trace=True, record_parents=True,
        ),
        crec="rows", faults=True, externals=True,
    )


def test_explore_kernel_on_a_flood_run_to_quiescence():
    """The whole kernel, not the insert alone: a broadcast flood (every
    delivery's outbox is N rows) run to quiescence in both lowerings."""
    app = make_broadcast_app(6)
    program = dsl_start_events(app) + [
        Send(app.actor_name(0), MessageConstructor(lambda: (1, 0))),
        WaitQuiescence(),
    ]
    out = {}
    for mode in ("scatter", "onehot"):
        cfg = DeviceConfig.for_app(
            app, pool_capacity=40, max_steps=64, max_external_ops=16,
            invariant_interval=app.invariant_interval, record_trace=True,
            index_mode=mode,
        )
        progs = broadcast_program(lower_program(app, cfg, program), 6)
        keys = jax.random.split(jax.random.PRNGKey(4), 6)
        out[mode] = make_explore_kernel(app, cfg)(progs, keys)
    _assert_same(out, "explore kernel, broadcast flood")
    # 1 + 6 * 5 deliveries, and the run ended in a verdict.
    assert (np.asarray(out["onehot"].deliveries) == 31).all()
    assert (np.asarray(out["onehot"].status) == 2).all()


# -- the short pass --------------------------------------------------------

RANKED_SHAPES = ["spark5-p256", "bcast64-p4608"]
# rows valid in the step, a lane: the last lane takes ``last``
ROW_COUNTS = {
    "none": dict(each=0, last=0),
    "one": dict(each=1, last=1),
    "c": dict(each=C, last=C),
    "c-plus-one-in-one-lane": dict(each=1, last=C + 1),
    "all-lanes-full": dict(each=None, last=None),
}
PLACES = ("prefix", "holes-from-lost", "injection-and-outbox")


def _ranked_case(app, cfg, rng, k, n_rows, place, *, n_free=None, crec=None):
    """One lane with exactly ``n_rows`` rows entering the pool (None:
    every row of K), sitting where ``place`` says: the first rows of K;
    anywhere in K with rows between them lost at the send (to a stopped
    receiver, over a cut link); the injection's row 0 from the external
    sender and the rest scattered over the outbox."""
    n, p = cfg.num_actors, cfg.pool_capacity
    fill = 0.5 if n_free is None else (p - n_free) / p
    state, rows = _case(app, cfg, rng, k, fill, n_rows=0, crec=crec)
    n_rows = k if n_rows is None else n_rows
    valid = np.zeros(k, bool)
    src = rng.integers(1, n, k)
    dst = (src + 1 + rng.integers(0, n - 2, k)) % n  # never the sender
    timer = np.zeros(k, bool)
    cut = np.zeros((n, n), bool)
    stopped = np.zeros(n, bool)
    if place == "prefix":
        valid[:n_rows] = True
    elif place == "holes-from-lost":
        # Twice the rows proposed (K allowing); the surplus is lost.
        stopped[0] = True
        cut[1, 2] = cut[2, 1] = True
        dst = np.where(dst == 0, (src % (n - 1)) + 1, dst)
        clash = ((src == 1) & (dst == 2)) | ((src == 2) & (dst == 1))
        dst = np.where(clash, 3, dst)
        at = rng.permutation(k)[: min(k, 2 * n_rows)]
        valid[at] = True
        for i, row in enumerate(at[n_rows:]):  # anywhere among the others
            if i % 2:
                src[row], dst[row] = 1, 2
            else:
                dst[row] = 0
    else:
        if n_rows:
            valid[0] = True
            src[0] = n  # the external sender: crosses no link
            valid[1 + rng.permutation(k - 1)[: n_rows - 1]] = True
    state = state._replace(
        cut=jnp.asarray(cut), stopped=jnp.asarray(stopped)
    )
    rows = (
        jnp.asarray(valid), jnp.asarray(src, jnp.int32),
        jnp.asarray(dst, jnp.int32), jnp.asarray(timer),
    ) + rows[4:]
    return state, rows


def _ranked_check(shape, counts, place, seed=41, lanes=LANES,
                  cfg_overrides=None, **case_kw):
    """A batch through the scatter insert, the one-hot insert that picks
    its pass, and the one-hot insert's full pass: all three equal, every
    row that was meant to enter entered, and the pass taken is the short
    one unless a lane inserts more than it holds."""
    app, cfgs = _cfgs(shape, **(cfg_overrides or {}))
    k = _rows_k(shape, app)
    assert k > INSERT_SHORT_FACTOR * C
    rng = np.random.default_rng(seed)
    per_lane = [counts["each"]] * (lanes - 1) + [counts["last"]]
    cases = [
        _ranked_case(app, cfgs["scatter"], rng, k, n_rows, place, **case_kw)
        for n_rows in per_lane
    ]
    before = np.asarray(_stack([c[0] for c in cases]).pool_valid).sum(axis=1)
    out = _insert_both(cfgs, cases)
    what = f"{shape} {per_lane} {place}"
    _assert_same(out, what)
    _assert_same(out, what + " (full pass)", modes=("scatter", "onehot-full"))
    most = max(k if n_rows is None else n_rows for n_rows in per_lane)
    took_full = np.asarray(out["onehot"].insert_full_steps)
    assert (took_full == int(most > C)).all(), what
    assert out["onehot-full"].insert_full_steps is None
    entered = np.asarray(out["onehot"].pool_valid).sum(axis=1) - before
    return out, entered, per_lane


@pytest.mark.parametrize("place", PLACES)
@pytest.mark.parametrize("counts", list(ROW_COUNTS))
@pytest.mark.parametrize("shape", RANKED_SHAPES)
def test_short_insert_equals_scatter_and_full_insert(shape, counts, place):
    out, entered, per_lane = _ranked_check(shape, ROW_COUNTS[counts], place)
    for got, n_rows in zip(entered, per_lane):
        if n_rows is not None:  # half the pool is free: they all fit
            assert got == n_rows


RANKED_FEATURES = {
    "per-row-crec": dict(
        cfg_overrides=dict(record_trace=True, record_parents=True),
        crec="rows",
    ),
    "scalar-crec": dict(
        cfg_overrides=dict(record_trace=True, record_parents=True),
        crec="scalar",
    ),
    "srcdst-fifo-heads": dict(cfg_overrides=dict(srcdst_fifo=True)),
    "int16-payloads": dict(cfg_overrides=dict(msg_dtype="int16")),
    "fifo-heads-int16-per-row-crec": dict(
        cfg_overrides=dict(
            srcdst_fifo=True, msg_dtype="int16", record_trace=True,
            record_parents=True,
        ),
        crec="rows",
    ),
}


@pytest.mark.parametrize("feature", list(RANKED_FEATURES))
@pytest.mark.parametrize("counts", ["c", "c-plus-one-in-one-lane"])
def test_short_insert_equals_scatter_and_full_insert_with(counts, feature):
    for place in PLACES[1:]:
        _ranked_check(
            "spark5-p256", ROW_COUNTS[counts], place, seed=43,
            **RANKED_FEATURES[feature],
        )


@pytest.mark.parametrize("extra", [0, 1], ids=["exact-fit", "one-too-many"])
@pytest.mark.parametrize("shape", RANKED_SHAPES)
def test_the_overflow_edge_on_the_short_pass(shape, extra):
    n_free = C - 3
    out, entered, _ = _ranked_check(
        shape, dict(each=n_free + extra, last=n_free + extra),
        "holes-from-lost", n_free=n_free,
    )
    assert (entered == n_free).all()
    assert (np.asarray(out["onehot"].status) == ST_OVERFLOW).all() == bool(extra)
    assert np.asarray(out["onehot"].pool_valid).all()


# -- the bursting lanes alone ----------------------------------------------

BURST_SHAPES = RANKED_SHAPES + ["paxos11-p128"]
BURST_LANES = INSERT_BURST_LANES + 3
# how many lanes of the batch insert more than the short pass holds
BURSTS = {
    "no-lane-bursts": 0,
    "one-lane-bursts": 1,
    "as-many-as-the-loop-takes": INSERT_BURST_LANES,
    "one-more-than-the-loop-takes": INSERT_BURST_LANES + 1,
    "every-lane-bursts": BURST_LANES,
}


def _burst_check(shape, bursting, seed=53, *, overflowing=(), frozen=()):
    """A batch of BURST_LANES lanes of which ``bursting`` (lane numbers)
    insert more rows than the short pass holds and the others at most
    that, through the scatter insert, the one-hot insert that picks its
    pass, and the one-hot insert's full pass: all three equal field for
    field, and the lanes' two counts say which pass the batch and each
    lane took. ``overflowing`` lanes have fewer free slots than rows;
    ``frozen`` ones are done and insert nothing."""
    app, cfgs = _cfgs(shape)
    k = _rows_k(shape, app)
    assert k > INSERT_SHORT_FACTOR * C
    rng = np.random.default_rng(seed)
    cases = []
    for lane in range(BURST_LANES):
        if lane in frozen:
            n_rows = 0
        elif lane in bursting:
            n_rows = int(rng.integers(C + 1, k + 1))
        else:
            n_rows = int(rng.integers(0, C + 1))
        state, rows = _ranked_case(
            app, cfgs["scatter"], rng, k, n_rows, PLACES[lane % len(PLACES)],
            n_free=max(n_rows - 3, 0) if lane in overflowing else None,
        )
        if lane in frozen:
            state = state._replace(status=jnp.int32(ST_DONE))
        cases.append((state, rows))
    out = _insert_both(cfgs, cases)
    what = f"{shape} bursting {sorted(bursting)}"
    _assert_same(out, what)
    _assert_same(out, what + " (full pass)", modes=("scatter", "onehot-full"))
    burst = np.isin(np.arange(BURST_LANES), sorted(bursting))
    past = len(bursting) > INSERT_BURST_LANES
    np.testing.assert_array_equal(
        out["onehot"].insert_full_steps,
        np.full(BURST_LANES, int(bool(bursting))), what,
    )
    np.testing.assert_array_equal(
        out["onehot"].insert_full_lane_steps, (burst | past).astype(int), what
    )
    return out, cases


@pytest.mark.parametrize("bursts", list(BURSTS))
@pytest.mark.parametrize("shape", BURST_SHAPES)
def test_the_bursting_lanes_alone_take_the_full_pass(shape, bursts):
    rng = np.random.default_rng(59)
    bursting = set(rng.permutation(BURST_LANES)[: BURSTS[bursts]].tolist())
    out, _ = _burst_check(shape, bursting)
    # Something landed in every lane that sent rows.
    assert np.asarray(out["onehot"].pool_valid).any()


@pytest.mark.parametrize("shape", BURST_SHAPES)
def test_a_bursting_lane_that_overflows_its_pool(shape):
    out, _ = _burst_check(shape, {2, 5}, overflowing={5})
    status = np.asarray(out["onehot"].status)
    assert status[5] == ST_OVERFLOW and (np.delete(status, 5) != ST_OVERFLOW).all()
    assert np.asarray(out["onehot"].pool_valid)[5].all()


@pytest.mark.parametrize("shape", BURST_SHAPES)
def test_a_bursting_lane_next_to_a_frozen_one(shape):
    out, cases = _burst_check(shape, {3}, frozen={2, 4})
    for lane in (2, 4):
        assert int(out["onehot"].status[lane]) == ST_DONE
        for field in ("pool_valid", "pool_src", "pool_dst", "pool_msg",
                      "pool_seq", "seq_counter"):
            np.testing.assert_array_equal(
                np.asarray(getattr(out["onehot"], field))[lane],
                getattr(cases[lane][0], field), field,
            )


@pytest.mark.parametrize("n_burst", [0, 1, INSERT_BURST_LANES,
                                     INSERT_BURST_LANES + 1])
def test_the_batch_rule_gives_the_full_pass_values(n_burst):
    """``_landed_by_batch`` under ``vmap`` against ``vmap(_landed_full)``
    alone, column for column, on ranks and columns made by hand (K 40,
    P 96, 5 columns): whichever branch the count of bursting lanes
    picks."""
    b, k, p, ncols = INSERT_BURST_LANES + 2, 40, 96, 5
    rng = np.random.default_rng(61 + n_burst)
    n_rows = rng.integers(0, C + 1, b)
    n_rows[rng.permutation(b)[:n_burst]] = rng.integers(C + 1, k + 1, n_burst)
    valid = np.zeros((b, k), bool)
    for lane in range(b):
        valid[lane, rng.permutation(k)[: n_rows[lane]]] = True
    want = jnp.asarray(np.where(valid, np.cumsum(valid, axis=1), -1), jnp.int32)
    prefix = jnp.asarray(np.cumsum(rng.random((b, p)) < 0.6, axis=1), jnp.int32)
    cols = tuple(
        jnp.asarray(rng.integers(-(2**31), 2**31 - 1, (b, k)), jnp.int32)
        for _ in range(ncols)
    )
    landed, took, took_lane = jax.jit(jax.vmap(_landed_by_batch))(
        want, prefix, jnp.asarray(n_rows, jnp.int32), cols
    )
    for got, full in zip(landed, jax.vmap(_landed_full)(want, prefix, cols)):
        np.testing.assert_array_equal(got, full)
    assert (np.asarray(took) == int(n_burst > 0)).all()
    np.testing.assert_array_equal(
        took_lane, (n_rows > C) | (n_burst > INSERT_BURST_LANES)
    )


def test_one_lane_alone_equals_its_lane_of_a_batch():
    """The unbatched call (the single-lane ``run_lane`` of the checks and
    lifts) takes the full pass and gives what the batch gave its lane."""
    app, cfgs = _cfgs("spark5-p256")
    cfg = cfgs["onehot"]
    rng = np.random.default_rng(47)
    cases = [
        _ranked_case(app, cfg, rng, 82, n_rows, "injection-and-outbox")
        for n_rows in (0, 3, C, 2)
    ]
    batch = _insert_both(cfgs, cases)["onehot"]
    assert (np.asarray(batch.insert_full_steps) == 0).all()
    one = jax.jit(lambda s, r: insert_rows(s, cfg, *r))
    for lane, (state, rows) in enumerate(cases):
        alone = one(
            state._replace(
                insert_full_steps=jnp.int32(0),
                insert_full_lane_steps=jnp.int32(0),
            ),
            rows,
        )
        assert int(alone.insert_full_steps) == 1
        assert int(alone.insert_full_lane_steps) == 1
        for field in type(alone)._fields:
            if field.startswith("insert_full_") or getattr(alone, field) is None:
                continue  # (a leaf only a datagram kernel carries)
            assert np.array_equal(
                np.asarray(getattr(alone, field)),
                np.asarray(getattr(batch, field))[lane],
            ), field


def _segment_text(shape, lanes=4):
    app, cfgs = _cfgs(shape, max_steps=64)
    cfg = cfgs["onehot"]
    state = make_init_kernel(app, cfg)(
        jax.random.split(jax.random.PRNGKey(0), lanes)
    )
    progs = ExtProgram(*(jnp.asarray(x) for x in empty_programs(cfg, lanes)))
    segment = make_segment_kernel(app, cfg, 8)
    text = segment.lower(state, progs, jnp.zeros(lanes, jnp.int32)).as_text()
    return text, state


@pytest.mark.parametrize("shape,cases", [
    ("spark5-p256", 1), ("bcast64-p4608", 1), ("paxos11-p128", 1),
    ("raft5-p96", 0), ("bcast8-p96", 0),
])
def test_the_segment_branches_once_where_the_short_pass_is_built(shape, cases):
    """``jit(vmap(scan(step)))`` as the continuous driver compiles it: one
    real ``case`` a step at the wide shapes, on a predicate that is one
    scalar for the resident set; at raft's shape the program holds none
    and the lanes carry no count."""
    text, state = _segment_text(shape)
    assert text.count("stablehlo.case") == cases
    assert (state.insert_full_steps is not None) == bool(cases)
    assert (state.insert_full_lane_steps is not None) == bool(cases)
    if cases:  # the branch index is a scalar, not a value a lane
        assert re.search(r"\}\) : \(tensor<i32>\) -> ", text)
        # three regions: the short pass, the short pass and a loop over
        # the bursting lanes, the full pass
        regions = _case_regions(text)
        assert len(regions) == 3
        assert ["stablehlo.while" in r for r in regions] == [False, True, False]


# -- the passes do not come back -------------------------------------------

def _batched_insert(cfg, app, k, lanes=2, crec=None):
    """``(fn, states, rows)``: the one-hot insert of a batch of ``lanes``
    half-full lanes, to lower or to trace."""
    rng = np.random.default_rng(0)
    cases = [_case(app, cfg, rng, k, 0.5, crec=crec) for _ in range(lanes)]
    states = _with_count(_stack([c[0] for c in cases]), cfg)
    rows = _stack([c[1] for c in cases])
    return jax.vmap(lambda s, r: insert_rows(s, cfg, *r)), states, rows


def _case_regions(text):
    """The regions of the lowered text's one ``case``, in branch order."""
    start = text.index("stablehlo.case")
    regions = [[]]
    for line in text[start:].splitlines()[1:]:
        if line.strip().startswith("}, {"):
            regions.append([])
        elif line.strip().startswith("}) :"):
            break
        else:
            regions[-1].append(line)
    return ["\n".join(r) for r in regions]


def _kp_compares(cfg, app, k, lanes=2):
    """How many compare ops over the batch's [B, K, P] the lowered insert
    of a batch holds outside any branch and in each branch of its
    ``case`` (none where the short pass is not built), and how many over
    one lane's [K, P] (``lane-branch<i>``: the loop's)."""
    fn, states, rows = _batched_insert(cfg, app, k, lanes)
    text = jax.jit(fn).lower(states, rows).as_text()
    p = cfg.pool_capacity
    batch = re.compile(rf"stablehlo\.compare.*->\s*tensor<{lanes}x{k}x{p}xi1>")
    lane = re.compile(rf"stablehlo\.compare.*->\s*tensor<{k}x{p}xi1>")
    regions = _case_regions(text) if "stablehlo.case" in text else []
    inside = sum(len(batch.findall(r)) for r in regions)
    counts = {"outside": len(batch.findall(text)) - inside}
    for i, region in enumerate(regions):
        for name, pattern in ((f"branch{i}", batch), (f"lane-branch{i}", lane)):
            if pattern.findall(region):
                counts[name] = len(pattern.findall(region))
    return counts, text


# Under ``track_fifo_heads`` a second question is asked over [K, P] (does
# the pool hold the row's channel already: a src and a dst compare), which
# is not the insert's and stays, outside the branches.
@pytest.mark.parametrize("fifo,outside", [(False, 0), (True, 2)])
def test_the_onehot_insert_compares_over_rows_and_slots_once(fifo, outside):
    app, cfgs = _cfgs("bcast64-p4608", srcdst_fifo=fifo)
    counts, text = _kp_compares(cfgs["onehot"], app, 65)
    # The short pass compares over [K] and [P], a rank at a time, and
    # never over [K, P]; the loop over the bursting lanes once over one
    # lane's [K, P], behind the short pass; the full pass once over the
    # batch's.
    assert counts == {"outside": outside, "lane-branch1": 1, "branch2": 1}
    assert text.count("stablehlo.case") == 1
    # Both regions that take the short pass compare rank by rank: once a
    # rank over [K] and over [P], whatever the columns (the second of
    # them before its loop).
    regions = _case_regions(text)
    for short in (regions[0], regions[1].split("stablehlo.while")[0]):
        for width in (65, 4608):
            assert len(re.findall(
                rf"stablehlo\.compare.*->\s*tensor<2x{width}xi1>", short
            )) == C


@pytest.mark.parametrize("fifo,expected", [(False, 1), (True, 3)])
def test_the_small_insert_compares_over_rows_and_slots_once(fifo, expected):
    """Where no short pass is built there is no branch, and one compare."""
    app, cfgs = _cfgs("raft5-p96", srcdst_fifo=fifo)
    counts, text = _kp_compares(cfgs["onehot"], app, app.max_outbox + 2)
    assert counts == {"outside": expected}
    assert "stablehlo.case" not in text


# -- the whole-row form -----------------------------------------------------

def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _insert_ops(shape, lanes=2, crec=None, **overrides):
    """``{(primitive, output shape): count}`` over the jaxpr of a batch's
    one-hot insert, nested calls included."""
    app, cfgs = _cfgs(shape, **overrides)
    cfg = cfgs["onehot"]
    k = _rows_k(shape, app)
    fn, states, rows = _batched_insert(cfg, app, k, lanes, crec)
    jaxpr = jax.make_jaxpr(fn)(states, rows)
    counts = {}
    for eqn in _eqns(jaxpr.jaxpr):
        key = (eqn.primitive.name, tuple(eqn.outvars[0].aval.shape))
        counts[key] = counts.get(key, 0) + 1
    return counts, cfg, k


@pytest.mark.parametrize("shape", [
    "raft5-p96", "raft5-p256", "bcast8-p96", "vsr5-w16-p96", "vsr5-p256",
])
def test_the_insert_takes_no_column_of_its_rows(shape):
    """One [K, P] compare and one select over it (the packed word); the
    payload is K whole-row slices and K selects over [P, W], with no
    stack: nothing repeats W-fold."""
    counts, cfg, k = _insert_ops(shape)
    assert insert_form(cfg) == "rows"
    b, p, w = 2, cfg.pool_capacity, cfg.msg_width
    assert counts[("eq", (b, k, p))] == 1
    assert counts[("select_n", (b, k, p))] == 1
    assert ("slice", (b, k, 1)) not in counts
    assert not any(name == "concatenate" for name, _shape in counts)
    assert counts[("slice", (b, 1, w))] == k
    assert counts[("select_n", (b, p, w))] == k


def test_a_per_row_crec_is_the_one_more_column():
    counts, cfg, k = _insert_ops(
        "vsr5-p256", crec="rows", record_trace=True, record_parents=True
    )
    assert counts[("select_n", (2, k, cfg.pool_capacity))] == 2
    assert ("slice", (2, k, 1)) not in counts


@pytest.mark.parametrize("shape", RANKED_SHAPES)
def test_the_short_pass_keeps_its_columns(shape):
    """K there is a whole outbox (65, 82 rows) and W 2 or 3: a [K] slice
    a payload word into the one ``case``, one stack after it."""
    counts, cfg, k = _insert_ops(shape)
    assert insert_form(cfg) == "short"
    assert insert_form(dataclasses.replace(cfg, index_mode="scatter")) == "scatter"
    b, p, w = 2, cfg.pool_capacity, cfg.msg_width
    assert counts[("slice", (b, k, 1))] == w
    assert counts[("concatenate", (b, p, w))] == 1
    assert ("slice", (b, 1, w)) not in counts


@pytest.mark.parametrize("shape,form", [
    ("vsr5-p256", "rows"), ("raft5-p96", "rows"),
    ("bcast64-p4608", "short"), ("spark5-p256", "short"),
])
def test_the_build_stage_says_which_insert_a_kernel_has(shape, form):
    app, cfgs = _cfgs(shape, max_steps=16)
    spans._reset_setup()
    try:
        make_segment_kernel(app, cfgs["onehot"], 8)
        make_segment_kernel(app, cfgs["scatter"], 8)
        said = [
            (e["args"]["what"], e["args"]["insert"])
            for e in obs.setup_ledger()["timeline"]
            if e["name"] == "setup.build" and "insert" in e["args"]
        ]
        assert said == [
            ("make_segment_kernel", form), ("make_segment_kernel", "scatter"),
        ]
        if form != "short":
            spans._reset_setup()
            dpor_cfg = DeviceConfig.for_app(
                app, pool_capacity=SHAPES[shape][2], max_steps=16,
                record_trace=True, record_parents=True, index_mode="onehot",
            )
            build_dpor_kernel(app, dpor_cfg)
            (entry,) = [
                e for e in obs.setup_ledger()["timeline"]
                if e["args"].get("what") == "build_dpor_kernel"
            ]
            assert entry["args"]["insert"] == form
    finally:
        spans._reset_setup()
