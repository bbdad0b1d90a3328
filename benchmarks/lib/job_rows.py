"""The arithmetic of the per-layer metrics that read the rows the
program keeps of its jobs (``demi_tpu.obs.job_ledger()``: a row of its
own stages and counts for every job that ran once a profiler session
had been seen). In a ``--trace 1`` run the traced jobs leave rows with
``profiled`` true, and the window's jobs, which run after the session
has ended and after set-up's ``gc.freeze()``, rows with ``recorded``
false: the jobs the rate is made of, from inside. ``stage_share.py``
reads the traced jobs' totals; this reads the window's rows, and holds
them to the harness's own clock before it trusts them. A program
without the ledger (the parent of the PR that brought it) gives None,
and the harness leaves the metric out."""

from __future__ import annotations

from typing import Iterable, List, Optional

from .stage_share import DPOR_ROOT, SWEEP_ROOT

# How far the window's rows may fall short of its jobs' seconds, summed:
# a row is the root span's interval and a job the harness's clock pair
# around ``run_job``, which holds the row. The sweep verb's ``run_job``
# adds two concatenates; the dpor verb's builds a ``DeviceDPOR`` before
# ``dpor.search`` opens. Measured on the chip (PR 49, five cells): a job
# is 0.1-0.8 ms longer than its row, 0.007-0.029% of the window, for
# ``dpor.search`` too, so neither root needs more than the 5%.
TOLERANCE = {SWEEP_ROOT: 0.05, DPOR_ROOT: 0.05}
# The stages a per-layer metric names, of the traced job or of the
# window's: what is left (the root's, the round's, the prime fill's and
# the fill loop's own time, the collector's passes, a stage nobody has
# named yet) is ``unattributed``.
SWEEP_NAMED = (
    "sweep.block", "sweep.fuzz", "sweep.lower", "sweep.stack",
    "sweep.refill", "sweep.finalize", "sweep.pull", "sweep.retire",
    "sweep.fold", "sweep.finish", "sweep.fork", "sweep.starve",
)


def ledger() -> Optional[List[dict]]:
    """``job_ledger()``, or None where the program has no such ledger."""
    try:
        from demi_tpu.obs import job_ledger
    except ImportError:
        return None
    return job_ledger()


def profiled(root: str) -> Optional[List[dict]]:
    """The rows of ``root`` a profiler session recorded: the traced jobs."""
    rows = ledger()
    if rows is None:
        return None
    return [r for r in rows if r["root"] == root and r["profiled"]]


def window(obs, root: str) -> Optional[List[dict]]:
    """The rows of the window's jobs: those of ``root`` with ``recorded``
    false that follow the last profiled row, the first ``obs.stats.jobs``
    of them (the check's jobs come after), matched to
    ``obs.stats.per_job`` in order. None unless there are that many, no
    row is longer than its job, and the two sums lie within the root's
    tolerance."""
    rows = ledger()
    if rows is None:
        return None
    last = max((i for i, r in enumerate(rows) if r["profiled"]), default=-1)
    rows = [
        r for r in rows[last + 1:] if r["root"] == root and not r["recorded"]
    ][: obs.stats.jobs]
    if not rows or len(rows) < obs.stats.jobs:
        return None
    jobs = [secs for _index, _sub, _work, secs in obs.stats.per_job]
    if any(r["seconds"] > secs for r, secs in zip(rows, jobs)):
        return None
    if sum(r["seconds"] for r in rows) < (1.0 - TOLERANCE[root]) * sum(jobs):
        return None
    return rows


def _seconds(rows: List[dict]) -> float:
    return sum(r["seconds"] for r in rows)


def stretch(obs, root: str) -> Optional[float]:
    """Mean seconds of the traced jobs' rows over mean seconds of the
    window's: how far a traced job is from the jobs the rate is made of."""
    rows, traced = window(obs, root), profiled(root)
    if not rows or not traced:
        return None
    return (_seconds(traced) / len(traced)) / (_seconds(rows) / len(rows))


def stage_share(
    obs, root: str, stages: Iterable[str], column: str = "self_seconds"
) -> Optional[float]:
    """``column`` of ``stages`` summed over the window's rows, over the
    rows' seconds, in %; 0.0 for stages that never ran."""
    rows = window(obs, root)
    if not rows:
        return None
    stages = tuple(stages)
    own = sum(
        r["stages"][s][column] for r in rows for s in stages if s in r["stages"]
    )
    return 100.0 * own / _seconds(rows)


def unattributed_share(obs, root: str, named: Iterable[str]) -> Optional[float]:
    """Self seconds of every stage of the window's rows that is not in
    ``named``, over the rows' seconds, in %."""
    rows = window(obs, root)
    if not rows:
        return None
    named = frozenset(named)
    own = sum(
        t["self_seconds"]
        for r in rows for s, t in r["stages"].items() if s not in named
    )
    return 100.0 * own / _seconds(rows)


def count(rows: List[dict], name: str) -> Optional[int]:
    """The count ``name`` summed over ``rows``; None where no row keeps it."""
    if not any(name in r["counts"] for r in rows):
        return None
    return sum(r["counts"].get(name, 0) for r in rows)


def ns_share(obs, root: str, name: str) -> Optional[float]:
    """The nanoseconds the count ``name`` holds over the window's rows'
    seconds, in %."""
    rows = window(obs, root)
    if not rows:
        return None
    ns = count(rows, name)
    return None if ns is None else 100.0 * ns / 1e9 / _seconds(rows)


def count_ratio(obs, root: str, part: str, whole: str) -> Optional[float]:
    """The count ``part`` over the count ``whole`` in the window's rows, in %."""
    rows = window(obs, root)
    if not rows:
        return None
    n = count(rows, whole)
    return None if not n else 100.0 * (count(rows, part) or 0) / n


def device_idle_share(obs, root: str) -> Optional[float]:
    """100 x (1 - busy / the window's rows' seconds): the chip's busy
    seconds a lane-step in the traced jobs (the profiler trace: a chip's
    busy seconds do not stretch under the profiler, the host's do) times
    the lane-steps the window's jobs ran. Chip only. Under 0 is a fault
    of the reckoning and is reported as it reads."""
    rows = window(obs, root)
    t = obs.trace if obs.on_chip else None
    if not rows or t is None or not t["counters"].get("lane_steps"):
        return None
    busy = t["busy_s"] / t["counters"]["lane_steps"] * obs.counters["lane_steps"]
    return 100.0 * (1.0 - busy / _seconds(rows))
