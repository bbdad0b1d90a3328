"""Dual-mode dynamic-index primitives for the device kernels.

On TPU, XLA lowers vmapped dynamic-index gathers/scatters (``x[i]``,
``x.at[i].set``) inside a scan to serialized scatter ops in slow memory
(profiled: ~27 scatters/step at ~3-5 ms each dominated the explore step —
~130 ms/step for an 8k-lane batch, 4x slower than CPU). The same accesses
expressed as one-hot compare + where/reduce are pure elementwise/VPU code
and cost ~0.01 ms/step.

On CPU the native scatters are faster (O(1) vs O(n) work), so every helper
takes ``oh: bool`` — True selects the one-hot form. The kernels resolve the
mode once per build from ``DeviceConfig.index_mode`` ('auto' picks one-hot
exactly when the default JAX backend is a TPU).

Both modes are bit-identical by construction (tests/test_device.py parity
case runs the explore kernel in both and compares all outputs).

The one-hot reads of a table by a vector of indices (``gather_rows``,
``gather_mat``) have two one-hot forms of their own, picked by the size
of the one-hot product (``table_read_form``; tests/test_table_reads.py).

The pool insert is the one access with no helper here: ``core.insert_rows``
scatters by slot number in scatter mode (``rank_slots`` below) and, in
one-hot mode, never computes a slot number at all: each slot reads its
row off its own rank among the free slots (tests/test_insert_parity.py).
Where an insert carries many rows (an outbox of 32 or more) it is also the
one place a vmapped kernel branches for real: a ``custom_vmap`` rule puts
ONE ``lax.cond`` over the whole batch of lanes, which takes a short pass
over the first 8 valid rows in every step where no lane sends more. A
``cond`` on a lane's own count would run both branches under ``vmap``; a
predicate reduced over the batch is a scalar, and stays a ``case``.
Outside that short pass a hit slot takes its payload as a whole row: K
selects over ``pool_msg``'s own ``[P, W]``, the merge into the live pool
being the same pass (``core._landed_rows``); only the packed word and a
per-row creator link go through the shared ``[K, P]`` compare.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax._src import prng as _prng

# The one-hot forms are the SAME semantics handlers use via the dsl
# helpers — delegate so the subtle parts (bool-dtype reductions, the
# enabled-mask fold, out-of-range-drops) live in exactly one place.
from ..dsl import row_set as _row_set
from ..dsl import vgather as _vgather
from ..dsl import vget as _vget
from ..dsl import vset as _vset


def prefix_sum(x: jnp.ndarray, oh: bool) -> jnp.ndarray:
    """Inclusive prefix sum over an int vector.

    One-hot mode uses Hillis-Steele shifted adds (log2(n) pad+slice+add
    rounds) in place of the ``cumsum`` primitive: bit-identical to cumsum
    (integer adds are associative; tests/test_device.py). The form was
    chosen for the Pallas twin of the kernels (removed in PR 29; Mosaic
    lowers no ``cumsum``) and stays because it is the compiled program
    the chip's numbers are of: replacing it is a measured change."""
    if not oh:
        return jnp.cumsum(x)
    n = x.shape[0]
    d = 1
    while d < n:
        x = x + jnp.pad(x[:-d], (d, 0))
        d *= 2
    return x


def rng_split(key: jnp.ndarray, n: int = 2) -> jnp.ndarray:
    """``jax.random.split`` for raw uint32 keys, traced to threefry2x32 +
    iota_2x32_shape instead of the opaque ``random_split`` primitive.
    Bit-identical to jax.random.split (tests/test_device.py); kept, like
    ``prefix_sum``'s form, because every lane's RNG stream and the
    compiled step are built on it."""
    return _prng.threefry_split(key, (n,))


def onehot(i, n: int) -> jnp.ndarray:
    """bool[n], True at position ``i`` (all-False when i is out of range —
    the mask-style analog of a dropped scatter)."""
    return jnp.arange(n) == i


def get_scalar(vec: jnp.ndarray, i, oh: bool):
    """vec[i] with out-of-range reading as 0/False in one-hot mode."""
    if oh:
        return _vget(vec, i)
    return vec[i]


def get_row(mat: jnp.ndarray, i, oh: bool):
    """mat[i] ([n, w] -> [w]); out-of-range reads zeros in one-hot mode."""
    if oh:
        m = onehot(i, mat.shape[0])
        return jnp.sum(jnp.where(m[:, None], mat, 0), axis=0)
    return mat[i]


def set_scalar(vec: jnp.ndarray, i, val, enabled, oh: bool):
    """Functional ``vec[i] = val if enabled`` (no-op when i out of range
    in one-hot mode; scatter mode requires i in range)."""
    if oh:
        return _vset(vec, i, val, enabled)
    return vec.at[i].set(jnp.where(enabled, val, vec[i]))


def set_row(mat: jnp.ndarray, i, row, enabled, oh: bool):
    """Functional ``mat[i] = row if enabled`` for [n, w] mat."""
    if oh:
        return _row_set(mat, i, row, enabled)
    return mat.at[i].set(jnp.where(enabled, row, mat[i]))


def gather_vec(vec: jnp.ndarray, idx: jnp.ndarray, oh: bool):
    """vec[idx] for idx[k] into vec[n] -> [k]."""
    if oh:
        return _vgather(vec, idx)
    return vec[idx]


# The one-hot read of a table (``gather_rows``, ``gather_mat``) has two
# forms, chosen by the elements of the one-hot product it would
# materialise for a lane (k indices x the table's n x w), which is static
# at trace time. A contraction (``einsum``: a ``dot_general``) is real work
# where an outbox of 65 to 402 rows fills the tile; a batched
# ``dot_general`` wants its batch axis major, though, so under ``vmap`` the
# v5e compiler carries its operands, and every fusion that shares one with
# them, row-major: at raft's 5 x 5 x 7 that put 5 actors on the 128 lanes
# and cost 69% of the step (PERF.md, PR 50). A select-and-reduce leaves the
# compiler free to carry them batch-minor like the rest of the step. The
# bound sits in the gap between the largest read that gains (chain
# replication's 67 x 7 x 7 = 3,283) and the smallest read of a deployment
# that loses (the flood's 64 x 64 x 2 = 8,192), so every read of the three
# wide-outbox deployments stays the contraction it was.
SELECT_READ_MAX = 4096


def table_read_form(k: int, n: int, w: int) -> str:
    """``"select"`` or ``"dot"``: the one-hot form of a read of ``k``
    indices into an ``[n, w]`` table."""
    return "select" if k * n * w <= SELECT_READ_MAX else "dot"


def _select_rows(hit: jnp.ndarray, mat: jnp.ndarray) -> jnp.ndarray:
    """hit[k, n] one-hot (or all-False) rows, mat[n, w] -> [k, w]: the row
    each picks, zeros where it picks none. One term of each sum is
    nonzero: the same bits as the integer contraction."""
    if mat.dtype == jnp.bool_:
        return jnp.any(hit[:, :, None] & mat[None], axis=1)
    picked = jnp.sum(jnp.where(hit[:, :, None], mat[None], 0), axis=1)
    return picked.astype(mat.dtype)


def gather_rows(mat: jnp.ndarray, idx: jnp.ndarray, oh: bool):
    """mat[idx] for idx[k] into mat[n, w] -> [k, w]; out-of-range reads
    zeros in one-hot mode."""
    if oh:
        n, w = mat.shape
        hit = idx[:, None] == jnp.arange(n)[None, :]
        if table_read_form(idx.shape[0], n, w) == "select":
            return _select_rows(hit, mat)
        return jnp.einsum("kn,nw->kw", hit.astype(mat.dtype), mat)
    return mat[idx]


def gather_mat(mat: jnp.ndarray, ri: jnp.ndarray, ci: jnp.ndarray, oh: bool):
    """mat[ri, ci] for paired index vectors ri[k], ci[k] into mat[n, m];
    out-of-range reads 0/False in one-hot mode."""
    if oh:
        n, m = mat.shape
        roh = ri[:, None] == jnp.arange(n)[None, :]
        coh = ci[:, None] == jnp.arange(m)[None, :]
        if table_read_form(ri.shape[0], n, m) == "select":
            rows = _select_rows(roh, mat)
        else:
            rows = jnp.einsum(
                "kn,nm->km", roh.astype(jnp.int32), mat.astype(jnp.int32)
            )
        picked = jnp.sum(jnp.where(coh, rows, 0), axis=1)
        if mat.dtype == jnp.bool_:
            return picked.astype(bool)
        return picked.astype(mat.dtype)
    return mat[ri, ci]


def pack_bits(vec: jnp.ndarray) -> jnp.ndarray:
    """bool[N] -> uint32[ceil(N/32)] little-endian bit-pack."""
    n = vec.shape[0]
    pad = (-n) % 32
    v = jnp.pad(vec, (0, pad)).reshape(-1, 32)
    return jnp.sum(
        v.astype(jnp.uint32)
        << jnp.arange(32, dtype=jnp.uint32)[None, :],
        axis=1,
    )


def packed_gather_bool(vec: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """vec[idx] for bool vec[N], idx[P], without a dynamic gather and
    without the [P, N] one-hot compare: the table packs to ceil(N/32)
    words, each entry takes its word (idx >> 5) by one [P] select per
    word (nothing [P, words]-shaped: on the v5e the selects read 4%
    under a [P, 2] one-hot and its sum at N = 64, PERF.md PR 34) and
    its bit (idx & 31) by shift and mask. Out-of-range idx reads False,
    like the one-hot form. ``core.deliverable_mask`` reads its liveness
    bits this way on the one-hot path."""
    words = pack_bits(vec)
    widx = idx >> 5
    w = jnp.zeros(idx.shape, jnp.uint32)
    for j in range(words.shape[0]):
        w = jnp.where(widx == j, words[j], w)
    return ((w >> (idx & 31).astype(jnp.uint32)) & 1).astype(bool)


def first_true_index(mask: jnp.ndarray, k, oh: bool):
    """Index of the (k+1)-th True in ``mask`` (k 0-based); mask.shape[0] when
    there are fewer. The one-hot form avoids searchsorted (binary-search
    gathers serialize on TPU)."""
    cum = prefix_sum(mask.astype(jnp.int32), oh)
    if oh:
        return jnp.sum((cum < k + 1).astype(jnp.int32))
    return jnp.searchsorted(cum, k + 1, side="left").astype(jnp.int32)


def rank_slots(prefix: jnp.ndarray, want: jnp.ndarray):
    """For each want[i] (1-indexed rank), the first index where the
    nondecreasing ``prefix`` reaches it — vectorized searchsorted-left.
    Scatter mode only: the one-hot insert never needs a slot's number
    (``core.insert_rows``)."""
    return jnp.searchsorted(prefix, want, side="left").astype(jnp.int32)
