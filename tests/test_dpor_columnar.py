"""The DPOR driver's columnar explored log (device/explored_log.py): the
search it runs is the one the tuple-keeping driver ran (golden constants
recorded at the parent commit), and ``explored`` / ``frontier`` still
read as the set and the list of tuples they were."""

import hashlib
import json

import numpy as np
import pytest

from demi_tpu.device.dpor_sweep import DeviceDPOR, build_dpor_kernel
from demi_tpu.device.explored_log import ExploredView, PrescList
from demi_tpu.native import prescription_digest

RAFT3 = {
    "app": "raft", "nodes": 3, "bug": "multivote", "seed": 0,
    "num_events": 12, "max_messages": 64, "pool": 48, "timer_weight": 0.2,
    "kill_weight": 0.05, "partition_weight": 0.0,
}
ROUNDS, BATCH = 6, 16

# Recorded at the parent commit (5e8ae57, tuples everywhere): sha256 over
# every harvested round's packed prescriptions, len(explored),
# len(frontier), sha256 of repr(tuple(frontier)). One ``explore`` of six
# rounds, and two of three (a call's end re-forms the generation).
GOLDEN_WHOLE = (
    "85fbf13737532f5e5540946744469c571d8a6fbb201e3b4be6a00b3d85fe53f9",
    1183, 1102,
    "cdfd3045b7d3376ddbdbcd2bb32e7e781bc3cdaecd1e2d4f2bb0f434c4a4cef7",
)
GOLDEN_SPLIT = (
    "086c00e49db967ba5ddf503ca00d3766dcb4de4c0af977cdf9189a2f38fbf368",
    1034, 953,
    "aeaa9a9712e31934bb0de60a2d891c90b417e32faf23027197f7f57b8d00edff",
)


@pytest.fixture(scope="module")
def raft3():
    from demi_tpu.apps.common import dsl_start_events
    from demi_tpu.external_events import WaitQuiescence
    from demi_tpu.parallel.distributed import build_workload

    app, cfg, _fuzzer = build_workload(dict(RAFT3), record=True)
    program = dsl_start_events(app) + [WaitQuiescence()]
    return app, cfg, program, build_dpor_kernel(app, cfg)


def _driver(raft3, digest=None, **kw):
    app, cfg, program, kernel = raft3
    kw.setdefault("double_buffer", False)
    d = DeviceDPOR(app, cfg, program, batch_size=BATCH, kernel=kernel, **kw)
    if digest is not None:
        harvest = d._supervised_harvest

        def hashed(parts, batch, prescs, keys):
            digest.update(np.ascontiguousarray(prescs).tobytes())
            return harvest(parts, batch, prescs, keys)

        d._supervised_harvest = hashed
    return d


def _answer(d, digest):
    return (
        digest.hexdigest(), len(d.explored), len(d.frontier),
        hashlib.sha256(repr(tuple(d.frontier)).encode()).hexdigest(),
    )


@pytest.mark.parametrize("case", [
    "sequential", "host_shards_2", "double_buffer",
    "split", "checkpoint_restore",
])
def test_search_is_the_parents(raft3, case):
    kw = {
        "host_shards_2": {"host_shards": 2},
        "double_buffer": {"double_buffer": True},
    }.get(case, {})
    digest = hashlib.sha256()
    d = _driver(raft3, digest, **kw)
    if case in ("split", "checkpoint_restore"):
        d.explore(max_rounds=ROUNDS // 2, stop_on_violation=False)
        if case == "checkpoint_restore":
            payload = json.loads(json.dumps(d.checkpoint_state()))
            d = _driver(raft3, digest)
            d.restore_state(payload)
        d.explore(max_rounds=ROUNDS - ROUNDS // 2, stop_on_violation=False)
        assert _answer(d, digest) == GOLDEN_SPLIT
    else:
        d.explore(max_rounds=ROUNDS, stop_on_violation=False)
        assert _answer(d, digest) == GOLDEN_WHOLE


@pytest.fixture(scope="module")
def searched(raft3):
    d = _driver(raft3)
    d.explore(max_rounds=3, stop_on_violation=False)
    return d, set(d._explored_log)


def test_explored_is_no_tuple_set_and_reads_as_one(searched):
    d, as_set = searched
    assert isinstance(d.explored, ExploredView)
    assert not isinstance(d.explored, (set, frozenset))
    with pytest.raises(AttributeError):
        d.explored.add(((1,),))
    assert len(d.explored) == len(as_set) == len(d._explored_log)
    present = d._explored_log[len(as_set) // 2]
    absent = present[:-1] + (tuple(x + 1 for x in present[-1]),)
    assert present in d.explored and present in as_set
    assert absent not in d.explored and absent not in as_set
    assert () in d.explored and "junk" not in d.explored
    assert d.explored == as_set and as_set == d.explored
    assert d.explored <= as_set and as_set <= d.explored
    assert d.explored - as_set == set()
    assert as_set - d.explored == set()
    assert d.explored - {present} == as_set - {present}
    assert not d.explored == as_set - {present}
    # iteration is admission order
    assert list(d.explored) == list(d._explored_log)
    assert list(d.explored)[0] == ()


def test_a_fresh_driver_has_explored_the_root(raft3):
    fresh = _driver(raft3)
    assert {()} == fresh.explored and fresh.explored == {()}
    assert fresh.frontier == [()] and len(fresh.frontier) == 1


def test_log_columns_hold_what_the_tuples_say(searched):
    """Every entry materializes to the prescription its stored digest
    was taken over, row for row what ``_pack`` hands the kernel."""
    d, _as_set = searched
    log = d._explored_log
    n = len(log)
    for i, p in enumerate(log):
        assert log.key(i) == prescription_digest(p)
        assert len(p) == log.length[i]
    packed = d._pack(PrescList(log, range(n)))
    for i, p in enumerate(log):
        want = np.zeros_like(packed[i])
        if p:
            want[: len(p)] = np.asarray(p, np.int32)
        assert np.array_equal(packed[i], want)
    # python ints all the way down, as the tuple lists held
    deep = max(log, key=len)
    assert all(type(x) is int for row in deep for x in row)


def test_frontier_items_iterate_as_rows_of_ints(searched):
    d, as_set = searched
    assert isinstance(d.frontier, PrescList)
    tuples = list(d.frontier)
    assert len(tuples) == len(d.frontier) > 0
    assert set(tuples) <= as_set
    for k in (0, len(tuples) // 2, len(tuples) - 1, -1):
        rows = [tuple(int(x) for x in row) for row in d.frontier[k]]
        assert tuple(rows) == tuples[k]
    assert d.frontier == tuples and tuple(d.frontier) == tuple(tuples)
    assert d.frontier[1:3] == tuples[1:3]
    # a selection from tuples handed in from outside is the same one
    batch, rest = d._select_batch(d.frontier)
    batch2, rest2 = d._select_batch(tuples)
    assert batch == batch2 and rest == rest2
    assert len(batch) == BATCH and len(rest) == len(tuples) - BATCH
    assert np.array_equal(d._pack(batch), d._pack(list(batch)))


def test_selection_orders_like_the_tuple_sort(searched):
    """The lazy bucket order, fully resolved, is the parent's rule:
    deepest 8-row bucket first, content order within a bucket."""
    d, _as_set = searched
    tuples = list(d.frontier)
    want = sorted(tuples, key=lambda p: (-(len(p) // 8), p))
    ordered = d._ordered_frontier(d.frontier)
    assert ordered._unsorted      # buckets are left for a reader
    assert ordered[len(want) - 1] == want[-1]    # one bucket resolved
    assert list(ordered) == want
    assert d._ordered_frontier(ordered) is ordered


def test_an_external_tuple_is_found_by_digest_and_by_tuple(raft3, searched):
    d0, _as_set = searched
    deep = max(d0._explored_log, key=len)
    outside = deep[:3] + (tuple(x + 7 for x in deep[3]),)
    d = _driver(raft3)
    assert outside not in d.explored
    index = d.admit_tuples([outside])
    assert index == 1 and len(d.explored) == 2
    assert outside in d.explored
    assert prescription_digest(outside) in d._explored_digests
    assert d._explored_log[index] == outside
    assert d._explored_log.index_of(outside) == index
    assert d.explored == {(), outside}
    d.seed(deep)
    assert d.frontier[0] == deep and deep in d.explored
    assert d.frontier == [deep, ()]


def test_window_snapshot_rolls_the_log_back(raft3):
    from demi_tpu.device.dpor_sweep import (
        _dpor_restore_state,
        _dpor_search_state,
    )

    d = _driver(raft3)
    d.explore(max_rounds=1, stop_on_violation=False)
    pre = _dpor_search_state(d)
    before = (list(d.explored), list(d.frontier), set(d._explored_digests))
    d.explore(max_rounds=1, stop_on_violation=False)
    post = _dpor_search_state(d)
    after = (list(d.explored), list(d.frontier), set(d._explored_digests))
    assert len(after[0]) > len(before[0])
    _dpor_restore_state(d, pre)
    assert (list(d.explored), list(d.frontier),
            set(d._explored_digests)) == before
    _dpor_restore_state(d, post)
    assert (list(d.explored), list(d.frontier),
            set(d._explored_digests)) == after


@pytest.mark.parametrize("shards", [1, 2])
@pytest.mark.parametrize("pads", [True, False])
def test_bulk_admission_decides_what_the_per_candidate_loop_decides(
    raft3, pads, shards
):
    """The bulk path (no sleep sets, no distance gate) against the
    per-candidate loop (chosen here by a distance gate that gates
    nothing): the same fresh / redundant / pruned counts every round,
    the same log and frontier — with padding lanes admitted and with
    them masked out, sequential and sharded."""
    seed = None
    if not pads:
        # closed exploration needs a seeded lane beside the padding ones
        probe = _driver(raft3)
        probe.explore(max_rounds=1, stop_on_violation=False)
        seed = max(probe._explored_log, key=len)
    runs = []
    for each in (False, True):
        d = _driver(raft3, host_shards=shards)
        d.pad_exploration = pads
        if seed is not None:
            d.seed(seed)
        if each:
            d.max_distance = 1 << 20
        counts = []
        for _ in range(3):
            d.explore(max_rounds=1, stop_on_violation=False)
            counts.append({
                k: d._last_round[k]
                for k in ("fresh", "redundant", "distance_pruned")
            })
        runs.append((counts, tuple(d._explored_log), tuple(d.frontier),
                     set(d._explored_digests)))
    assert runs[0] == runs[1]
    assert sum(c["fresh"] for c in runs[0][0]) == len(runs[0][1]) - (
        1 if seed is None else 2
    )
    if not pads:
        assert sum(c["distance_pruned"] for c in runs[0][0]) > 0


def test_content_seeds_read_the_stored_digest(raft3, searched):
    """Content-mode lane seeds from the log's digest column are the ones
    digesting each tuple anew gives (a caller with tuples from outside
    gets those, admitted or not)."""
    d0, _as_set = searched
    app, cfg, program, kernel = raft3
    d = DeviceDPOR(app, cfg, program, batch_size=BATCH, kernel=kernel,
                   key_mode="content", double_buffer=False)
    d.explore(max_rounds=2, stop_on_violation=False)
    batch, _rest = d._select_batch(d.frontier)
    stored = d._round_seeds(len(batch), 0, batch=batch)
    anew = d._round_seeds(len(batch), 0, batch=list(batch))
    assert stored.dtype == anew.dtype == np.uint32
    assert np.array_equal(stored, anew)
    never_admitted = [max(d0._explored_log, key=len)[:2]]
    assert d._round_seeds(1, 0, batch=never_admitted).shape == (1,)


def test_default_search_admits_in_bulk_and_an_override_is_honoured(raft3):
    """With no sleep sets, no distance gate and the driver's own
    ``_admit``, nothing runs per candidate; an overriding ``_admit`` (a
    subclass, a control's injected fault) gets every candidate."""
    plain = _driver(raft3)
    plain._fresh_each = None    # calling it would raise
    plain.explore(max_rounds=2, stop_on_violation=False)

    app, cfg, program, kernel = raft3
    refused = []

    class Refusing(DeviceDPOR):
        def _admit(self, presc, key, frontier):
            if len(refused) % 5 == 4:
                refused.append(presc)
                return False
            refused.append(None)
            return super()._admit(presc, key, frontier)

    d = Refusing(app, cfg, program, batch_size=BATCH, kernel=kernel,
                 double_buffer=False)
    d.explore(max_rounds=2, stop_on_violation=False)
    dropped = [p for p in refused if p is not None]
    assert dropped and len(d.explored) < len(plain.explored)
    assert d._last_round["distance_pruned"] > 0
