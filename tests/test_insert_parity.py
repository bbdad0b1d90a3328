"""The pool insert a TPU runs, held to the one a CPU runs.

``insert_rows`` under ``index_mode='onehot'`` decides the insert from the
slot's side (a free slot's rank among free slots says which row it
receives: one ``[K, P]`` compare, one contraction); under ``'scatter'`` it
finds each row's slot number and scatters. Tier-1 runs on a CPU, where
``'auto'`` means scatter, so these cases are what guards the chip's path:
every field of the resulting state equal, over random pools and proposals.
The last test holds the passes down: the lowered one-hot insert compares
over ``[K, P]`` once.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from demi_tpu.apps.broadcast import make_broadcast_app
from demi_tpu.apps.common import dsl_start_events
from demi_tpu.apps.raft import make_raft_app
from demi_tpu.device.core import (
    ST_OVERFLOW, DeviceConfig, init_state, insert_rows,
)
from demi_tpu.device.encoding import lower_program
from demi_tpu.device.explore import broadcast_program, make_explore_kernel
from demi_tpu.external_events import MessageConstructor, Send, WaitQuiescence

LANES = 4
SHAPES = {
    # name: (app builder, nodes, pool, K)
    "raft3-p32": (make_raft_app, 3, 32, None),
    "raft3-p96": (make_raft_app, 3, 96, None),
    "raft5-p96": (make_raft_app, 5, 96, None),
    "raft5-p256": (make_raft_app, 5, 256, None),
    "bcast8-p96": (make_broadcast_app, 8, 96, None),
    "bcast64-p4608": (make_broadcast_app, 64, 4608, 65),
}
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


def _insert_both(cfgs, cases):
    states, rows = _stack([c[0] for c in cases]), _stack([c[1] for c in cases])
    out = {}
    for mode, cfg in cfgs.items():
        fn = jax.jit(jax.vmap(
            lambda s, r, cfg=cfg: insert_rows(s, cfg, *r)
        ))
        out[mode] = fn(states, rows)
    return out


def _assert_same(out, what):
    a, b = out["scatter"], out["onehot"]
    for field in type(a)._fields:
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
@pytest.mark.parametrize("shape", ["raft5-p96", "bcast8-p96", "bcast64-p4608"])
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
@pytest.mark.parametrize("shape", ["raft5-p96", "bcast8-p96"])
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


# -- the passes do not come back -------------------------------------------

def _kp_compares(cfg, app, k):
    """How many compare ops of shape [K, P] the lowered insert holds."""
    rng = np.random.default_rng(0)
    state, rows = _case(app, cfg, rng, k, 0.5)
    text = jax.jit(
        lambda s, r: insert_rows(s, cfg, *r)
    ).lower(state, rows).as_text()
    p = cfg.pool_capacity
    pattern = re.compile(
        rf"stablehlo\.compare.*->\s*tensor<{k}x{p}xi1>"
    )
    return sum(bool(pattern.search(line)) for line in text.splitlines())


# Under ``track_fifo_heads`` a second question is asked over [K, P] (does
# the pool hold the row's channel already: a src and a dst compare), which
# is not the insert's and stays.
@pytest.mark.parametrize("fifo,expected", [(False, 1), (True, 3)])
def test_the_onehot_insert_compares_over_rows_and_slots_once(fifo, expected):
    app, cfgs = _cfgs("bcast64-p4608", srcdst_fifo=fifo)
    assert _kp_compares(cfgs["onehot"], app, 65) == expected
