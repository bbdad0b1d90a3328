"""The one-hot reads of the per-actor tables, on both sides of their bound.

``ops.gather_rows`` and ``ops.gather_mat`` under ``oh=True`` (what a TPU
runs) take one of two forms by the size of the one-hot product they would
materialise (``ops.table_read_form``, ``ops.SELECT_READ_MAX``): a
select-and-reduce under the bound, the ``einsum`` they always were over it.
Tier-1 runs on a CPU, where ``index_mode='auto'`` is scatter, so these cases
are what holds both forms to the scatter read, at the benchmark's shape
classes, and what keeps the ``dot_general`` out of the step where it pinned
the v5e's layouts (PERF.md, PR 50): the step's jaxpr at raft's shapes holds
none, the flood's holds the two it had, and the ``setup.build`` stage says
the same. The last test compiles a segment for a described v5e through
``demi_tpu.tools.described_v5e`` where the TPU's compiler can be loaded.
"""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from demi_tpu import obs
from demi_tpu.device import ops
from demi_tpu.device.continuous import make_init_kernel, make_segment_kernel
from demi_tpu.device.core import init_state, table_reads
from demi_tpu.device.encoding import empty_programs
from demi_tpu.device.explore import ExtProgram, make_any_step_fn
from demi_tpu.obs import spans
from demi_tpu.parallel.distributed import build_workload
from demi_tpu.tools import described_v5e

LANES = 3
# (k indices, n table rows, w words): timer parking's read of ``timer_mem``
# for an outbox, or the insert's read of ``cut`` for its rows, as the
# benchmark's deployments shape them.
SHAPES = {
    "raft": (5, 5, 7),
    "vsr": (5, 5, 37),
    "raft7-reconfig": (10, 7, 18),
    "chain": (67, 7, 7),
    "flood-rows": (64, 64, 2),
    "spark": (402, 17, 17),
    "flood": (65, 64, 64),
    "paxos": (161, 11, 68),
}
SELECT = {"raft", "vsr", "raft7-reconfig", "chain"}
DTYPES = {"bool": np.bool_, "int32": np.int32, "int16": np.int16}


def _table(rng, shape, dtype):
    if dtype == np.bool_:
        return rng.random(shape) < 0.5
    # both signs, and past int16's half range: a sum that saturated or a
    # one-hot cast that lost the sign would show
    info = np.iinfo(dtype)
    return rng.integers(info.min, info.max, shape, dtype=dtype, endpoint=True)


def _indices(rng, lanes, k, n):
    """In range mostly; every lane also reads below 0, at n and past it."""
    idx = rng.integers(0, n, (lanes, k))
    idx[:, 0] = -1
    idx[:, k // 2] = n
    idx[:, -1] = n + 3
    return idx.astype(np.int32)


def test_the_bound_splits_the_benchmarks_shapes():
    forms = {
        name: ops.table_read_form(*shape) for name, shape in SHAPES.items()
    }
    assert {name for name, form in forms.items() if form == "select"} == SELECT
    assert set(forms.values()) == {"select", "dot"}
    # the gap the bound sits in: chain replication's cut read under it, the
    # flood's timer_mem read over it
    assert 67 * 7 * 7 < ops.SELECT_READ_MAX < 64 * 64 * 2


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_gather_rows_onehot_equals_scatter(shape, dtype):
    k, n, w = SHAPES[shape]
    rng = np.random.default_rng(k * n + w)
    mats = _table(rng, (LANES, n, w), DTYPES[dtype])
    idx = _indices(rng, LANES, k, n)
    got = jax.jit(jax.vmap(lambda m, i: ops.gather_rows(m, i, True)))(mats, idx)
    inside = (idx >= 0) & (idx < n)
    # the scatter form is only defined in range: read it there
    scatter = jax.jit(jax.vmap(lambda m, i: ops.gather_rows(m, i, False)))(
        mats, np.where(inside, idx, 0)
    )
    want = np.where(inside[:, :, None], np.asarray(scatter), 0)
    assert got.dtype == mats.dtype
    np.testing.assert_array_equal(np.asarray(got), want.astype(mats.dtype))


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_gather_mat_onehot_equals_scatter(shape, dtype):
    k, n, m = SHAPES[shape]
    rng = np.random.default_rng(k + n * m)
    mats = _table(rng, (LANES, n, m), DTYPES[dtype])
    ri = _indices(rng, LANES, k, n)
    ci = np.roll(_indices(rng, LANES, k, m), 1, axis=1)
    got = jax.jit(jax.vmap(lambda t, r, c: ops.gather_mat(t, r, c, True)))(
        mats, ri, ci
    )
    inside = (ri >= 0) & (ri < n) & (ci >= 0) & (ci < m)
    scatter = jax.jit(jax.vmap(lambda t, r, c: ops.gather_mat(t, r, c, False)))(
        mats, np.where(inside, ri, 0), np.where(inside, ci, 0)
    )
    want = np.where(inside, np.asarray(scatter), 0)
    assert got.dtype == mats.dtype
    np.testing.assert_array_equal(np.asarray(got), want.astype(mats.dtype))


# -- the dot_general does not come back --------------------------------------

def _onehot(configuration):
    app, cfg, _fuzzer = build_workload(
        described_v5e.load_workload(configuration)
    )
    return app, dataclasses.replace(cfg, index_mode="onehot")


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _primitives(inner)


@pytest.mark.parametrize("configuration,form", [
    ("raft5-multivote", "select"), ("bcast64-flood", "dot"),
])
def test_the_step_holds_a_dot_general_only_over_the_bound(configuration, form):
    app, cfg = _onehot(configuration)
    said = table_reads(app, cfg)
    assert said == {"gather_rows": form, "gather_mat": form}
    state = jax.eval_shape(
        lambda key: init_state(app, cfg, key), jax.random.PRNGKey(0)
    )
    prog = ExtProgram(*(x[0] for x in empty_programs(cfg, 1)))
    jaxpr = jax.make_jaxpr(make_any_step_fn(app, cfg))(state, prog)
    dots = sum(name == "dot_general" for name in _primitives(jaxpr.jaxpr))
    assert dots == sum(f == "dot" for f in said.values())
    scatter = dataclasses.replace(cfg, index_mode="scatter")
    assert set(table_reads(app, scatter).values()) == {"scatter"}


def test_the_build_stage_says_which_form_the_table_reads_take():
    spans._reset_setup()
    try:
        for configuration in ("raft5-multivote", "bcast64-flood"):
            make_segment_kernel(*_onehot(configuration), 8)
        said = [
            e["args"]["table_reads"]
            for e in obs.setup_ledger()["timeline"]
            if e["args"].get("what") == "make_segment_kernel"
        ]
        assert said == [
            {"gather_rows": "select", "gather_mat": "select"},
            {"gather_rows": "dot", "gather_mat": "dot"},
        ]
    finally:
        spans._reset_setup()


@pytest.mark.parametrize("configuration,sha", [
    ("bcast64-flood",
     "6c7900d666f9d27825ec65898f1622ae8ab9e971f61746bf3b6ac0e0357f96c9"),
    ("spark17-shuffle200",
     "0e595e1ccbc8aef8843741606092a27fc9ef0e974071eb76059b75aeace23222"),
    ("paxos11-datagram",
     "c9de9d0aa59e463da7328cf187ec0c19a637c03c85e04c4dd09bad3f34d62b47"),
])
def test_the_wide_outbox_deployments_lower_to_the_parents_segment(
    configuration, sha
):
    """The one-hot segment (4 lanes, 8 steps) of the three deployments whose
    every table read is over the bound, byte for byte what commit d19aad4
    lowered, before the reads had a second form."""
    app, cfg = _onehot(configuration)
    state = make_init_kernel(app, cfg)(
        jax.random.split(jax.random.PRNGKey(0), 4)
    )
    progs = ExtProgram(*(jnp.asarray(x) for x in empty_programs(cfg, 4)))
    text = make_segment_kernel(app, cfg, 8).lower(
        state, progs, jnp.zeros(4, jnp.int32)
    ).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == sha


# -- the compile for a described v5e -----------------------------------------

def test_a_described_v5e_carries_raft3s_segment_batch_minor():
    lanes = 256
    try:
        compiled, seg_steps = described_v5e.compile_segment(
            described_v5e.load_workload("raft3-multivote"), lanes, seg_steps=8
        )
    except RuntimeError as e:
        pytest.skip(str(e))
    assert seg_steps == 8
    out = described_v5e.report(compiled.as_text(), lanes, top=4)
    assert out["dot_generals"] == 0
    assert out["not_batch_minor"] == []
    assert out["estimated_cycles"] == sum(
        op["cycles"] for op in described_v5e.module_ops(compiled.as_text())
    ) > 0
    assert len(out["top"]) == 4
    assert out["top"][0]["cycles"] >= out["top"][-1]["cycles"]
