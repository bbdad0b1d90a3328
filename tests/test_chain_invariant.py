"""The replication invariant of ``apps/chain.py`` held to the expression it
had at PR 40 (every pair of members over a slice of Hist), which is kept
here verbatim as the oracle: the app's form (one reference row, whole rows
under a word mask) must give the same int32 code for every state, crafted
corner by crafted corner and over random states, called on one state and
under ``jax.vmap`` as the step kernel calls it."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from demi_tpu.apps import chain
from demi_tpu.apps.chain import (
    ACKED, AWAKE, CATCHING_UP, HIST, MEMBER, OPN, OUT, STATUS,
)

SIZES = [(7, 64), (3, 4), (1, 1)]
BATCH = 64
FLIP = 0x4000   # over every update's number: a flipped entry is nobody's
I32 = np.iinfo(np.int32)


def pairwise_oracle(L):
    """PR 40's ``invariant(states, alive)``, as it stood."""

    def invariant(states, alive):
        member = (
            alive & (states[:, AWAKE] == 1) & (states[:, STATUS] == MEMBER)
        )
        opn, acked = states[:, OPN], states[:, ACKED]
        hists = states[:, HIST:]
        both = member[:, None] & member[None, :]
        shared = (
            jnp.arange(L)[None, None, :]
            < jnp.minimum(opn[:, None], opn[None, :])[:, :, None]
        )
        differ = hists[:, None, :] != hists[None, :, :]
        diverged = jnp.any(both[:, :, None] & shared & differ)
        lost = jnp.any(both & (opn[:, None] < acked[None, :]))
        return jnp.where(
            diverged, jnp.int32(1), jnp.where(lost, jnp.int32(2), 0)
        )

    return invariant


@functools.lru_cache(maxsize=None)
def judges(n, L):
    """(the app's invariant, the same under vmap, the oracle under vmap)."""
    app = chain.make_chain_app(n, log_cap=L, bug=None)
    assert app.state_width == HIST + L
    return (
        jax.jit(app.invariant),
        jax.jit(jax.vmap(app.invariant)),
        jax.jit(jax.vmap(pairwise_oracle(L))),
    )


def garbage(rng, shape):
    """Any int32, the two ends among them."""
    out = rng.integers(I32.min, I32.max, size=shape, endpoint=True)
    ends = rng.choice([I32.min, I32.max, -1, 0], size=shape)
    return np.where(rng.random(shape) < 0.1, ends, out).astype(np.int32)


def cluster(n, L, rng, opn=None):
    """``n`` live members of one chain that agree: server i holds the first
    ``opn[i]`` of the chain's updates and garbage behind them; nothing is
    acknowledged beyond what the shortest holds. Code 0."""
    states = np.zeros((n, HIST + L), np.int32)
    updates = (rng.permutation(10_000)[:L] + 1).astype(np.int32)
    opn = rng.integers(0, L + 1, n) if opn is None else np.asarray(opn)
    states[:, :HIST] = rng.integers(0, 4, (n, HIST))
    states[:, STATUS], states[:, AWAKE], states[:, OPN] = MEMBER, 1, opn
    states[:, ACKED] = rng.integers(0, opn.min() + 1, n)
    states[:, HIST:] = np.where(
        np.arange(L)[None, :] < opn[:, None], updates, garbage(rng, (n, L))
    )
    return states, np.ones(n, bool)


def two(n, rng):
    i, j = rng.choice(n, 2, replace=False)
    return int(i), int(j)


def of_two(corner):
    """A corner that takes two servers; a chain of one gets ``anything``."""

    @functools.wraps(corner)
    def made(n, L, rng):
        return corner(n, L, rng) if n > 1 else anything(n, L, rng)

    return made


def stranger(rng, states, alive, i, how):
    """Server i leaves the membership in one of four ways and its row turns
    to garbage, OPN and ACKED included."""
    states[i] = garbage(rng, states.shape[1])
    states[i, STATUS], states[i, AWAKE] = MEMBER, 1
    if how == "out":
        states[i, STATUS] = OUT
    elif how == "catching_up":
        states[i, STATUS] = CATCHING_UP
    elif how == "asleep":
        states[i, AWAKE] = 0
    else:
        alive[i] = False


# -- the corners: (n, L, rng) -> states, alive, the code (None: whatever the
# oracle says) ------------------------------------------------------------

def clean(n, L, rng):
    return (*cluster(n, L, rng), 0)


def non_members(n, L, rng):
    states, alive = cluster(n, L, rng)
    for i in rng.choice(n, max(1, n // 2), replace=False):
        stranger(rng, states, alive, i, rng.choice(["out", "catching_up", "asleep"]))
    return states, alive, 0


def dead_servers(n, L, rng):
    states, alive = cluster(n, L, rng)
    for i in rng.choice(n, max(1, n // 2), replace=False):
        stranger(rng, states, alive, i, "dead")
    return states, alive, 0


def no_member(n, L, rng):
    states, alive = cluster(n, L, rng)
    for i in range(n):
        stranger(rng, states, alive, i, rng.choice(["out", "catching_up", "asleep", "dead"]))
    return states, alive, 0


@of_two
def tie_for_the_longest(n, L, rng):
    opn = rng.integers(0, L + 1, n)
    i, j = two(n, rng)
    opn[i] = opn[j] = opn.max()
    return (*cluster(n, L, rng, opn), 0)


@of_two
def tie_that_diverges(n, L, rng):
    opn = rng.integers(0, L + 1, n)
    i, j = two(n, rng)
    opn[i] = opn[j] = max(opn.max(), 1)
    states, alive = cluster(n, L, rng, opn)
    states[i, HIST + rng.integers(0, opn[i])] ^= FLIP
    return states, alive, 1


def opn_zero(n, L, rng):
    opn = rng.integers(0, L + 1, n)
    opn[rng.integers(0, n)] = 0
    return (*cluster(n, L, rng, opn), 0)


def opn_full(n, L, rng):
    return (*cluster(n, L, rng, np.full(n, L)), 0)


@of_two
def differs_beyond_the_shorter(n, L, rng):
    opn = rng.integers(0, L + 1, n)
    i, j = two(n, rng)
    opn[i], opn[j] = rng.integers(0, L), L
    states, alive = cluster(n, L, rng, opn)
    states[i, HIST + rng.integers(opn[i], L)] = states[j, HIST] + 20_000
    return states, alive, 0


@of_two
def differs_inside(n, L, rng):
    opn = rng.integers(1, L + 1, n)
    states, alive = cluster(n, L, rng, opn)
    i, j = two(n, rng)
    states[i, HIST + rng.integers(0, min(opn[i], opn[j]))] ^= FLIP
    return states, alive, 1


@of_two
def differs_at_entry_zero(n, L, rng):
    states, alive = cluster(n, L, rng, rng.integers(1, L + 1, n))
    states[two(n, rng)[0], HIST] ^= FLIP
    return states, alive, 1


@of_two
def differs_at_the_last_entry(n, L, rng):
    opn = rng.integers(0, L + 1, n)
    i, j = two(n, rng)
    opn[i] = opn[j] = L
    states, alive = cluster(n, L, rng, opn)
    states[i, HIST + L - 1] ^= FLIP
    return states, alive, 1


@of_two
def lost_alone(n, L, rng):
    opn = rng.integers(0, L + 1, n)
    i, j = two(n, rng)
    opn[i], opn[j] = rng.integers(0, L), L
    states, alive = cluster(n, L, rng, opn)
    states[j, ACKED] = rng.integers(opn[i] + 1, L + 1)
    return states, alive, 2


@of_two
def diverged_and_lost(n, L, rng):
    opn = rng.integers(1, L + 1, n)
    i, j = two(n, rng)
    opn[i], opn[j] = rng.integers(1, L), L
    states, alive = cluster(n, L, rng, opn)
    states[j, ACKED] = L
    states[i, HIST + rng.integers(0, opn[i])] ^= FLIP
    return states, alive, 1


def garbage_where_nobody_looks(n, L, rng):
    """Strangers of every kind beside members that agree, OPN beyond L and
    below 0 among the strangers' scalars."""
    states, alive = cluster(n, L, rng)
    for i in range(n):
        if i and rng.random() < 0.5:
            stranger(rng, states, alive, i, rng.choice(["out", "catching_up", "asleep", "dead"]))
    return states, alive, 0


def anything(n, L, rng):
    """A random state: members and strangers, histories that part anywhere
    or nowhere, acknowledgements that anyone may lack, and now and then an
    OPN no handler writes (beyond L, below 0)."""
    opn = rng.choice(np.r_[0, L, np.arange(L + 1)], n)
    states, alive = cluster(n, L, rng, opn)
    states[:, ACKED] = np.where(
        rng.random(n) < 0.8, states[:, ACKED], rng.integers(0, L + 1, n)
    )
    for i in range(n):
        roll = rng.random()
        if roll < 0.25:
            stranger(rng, states, alive, i, rng.choice(["out", "catching_up", "asleep", "dead"]))
        elif roll < 0.4:
            states[i, HIST + rng.integers(0, L)] ^= FLIP
        elif roll < 0.45:
            states[i, OPN] = rng.choice([-1, L + 1, I32.max, I32.min])
    return states, alive, None


CORNERS = [
    clean, non_members, dead_servers, no_member, tie_for_the_longest,
    tie_that_diverges, opn_zero, opn_full, differs_beyond_the_shorter,
    differs_inside, differs_at_entry_zero, differs_at_the_last_entry,
    lost_alone, diverged_and_lost, garbage_where_nobody_looks, anything,
]


@pytest.mark.parametrize("corner", CORNERS, ids=lambda f: f.__name__)
@pytest.mark.parametrize("size", SIZES, ids=lambda s: f"{s[0]}x{s[1]}")
def test_the_invariant_gives_pr40s_code(size, corner):
    n, L = size
    one, batched, oracle = judges(n, L)
    rng = np.random.default_rng([n, L, CORNERS.index(corner)])
    lanes = BATCH * (64 if corner is anything else 1)
    made = [corner(n, L, rng) for _ in range(lanes)]
    states = np.stack([m[0] for m in made])
    alive = np.stack([m[1] for m in made])
    want = np.asarray(oracle(states, alive))
    expected = [m[2] for m in made]
    if expected[0] is not None:
        assert want.tolist() == expected          # the corner is the corner
    got = np.asarray(batched(states, alive))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)
    for lane in range(4):                         # and on one state alone
        assert int(one(states[lane], alive[lane])) == int(want[lane])
    if corner is anything and n > 1:
        assert set(want.tolist()) == {0, 1, 2}


@pytest.mark.parametrize("size", SIZES[:2], ids=lambda s: f"{s[0]}x{s[1]}")
def test_the_invariant_reads_whole_rows_and_one_reference(size):
    """What the chip's layout rests on (DESIGN.md sec. 3): no slice of the
    rows, neither of Hist nor of a column, no gather, and nothing shaped
    ``[N, N, ...]``; the oracle above has all but the gather."""
    n, L = size

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)

    def census(fn):
        states = jax.ShapeDtypeStruct((n, HIST + L), jnp.int32)
        alive = jax.ShapeDtypeStruct((n,), jnp.bool_)
        eqns = list(walk(jax.make_jaxpr(fn)(states, alive).jaxpr))
        names = {e.primitive.name for e in eqns}
        pairs = [
            v.aval.shape for e in eqns for v in e.outvars
            if v.aval.shape[:2] == (n, n)
        ]
        return names & {"slice", "dynamic_slice", "gather"}, pairs

    app = chain.make_chain_app(n, log_cap=L, bug=None)
    assert census(app.invariant) == (set(), [])
    sliced, pairs = census(pairwise_oracle(L))
    assert sliced == {"slice"} and (n, n, L) in pairs
