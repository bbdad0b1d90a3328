"""A window is a closed loop of whole jobs, one client.

A job is what one user command does (``traffic/<mix>.json`` says which).
The loop starts another pass of jobs while less than ``--seconds`` have
gone by, lets the pass in progress finish, and every rate is the work of
whole jobs over the seconds from the window's start to the end of the last
whole job. Nothing is divided by ``--seconds``; no partial job counts.

Each job has a sub-seed of its own, ``seed * 2**16 + index``, so two runs
with one ``--seed`` do the same work in the same order.

A traffic file's ``panel`` says what a pass is:

``{"from": "seed", "size": 1}``
    every job is fresh; job ``j`` has the sub-seed of index ``j``.
``{"from": "fixed", "seeds": [...], "warm": n}``
    a pass is those sub-seeds, each once, in an order shuffled by the
    sub-seed of the pass's index: the seed changes the order of the work
    and not the work. For verbs whose jobs differ so much that a window
    cannot average over them.
"""

from __future__ import annotations

import random
import statistics
import time
from dataclasses import dataclass, field
from typing import Callable, Iterator, List


def sub_seed(seed: int, index: int) -> int:
    return int(seed) * 2**16 + int(index)


@dataclass
class Job:
    index: int       # position in the run, 0 for the window's first job
    sub_seed: int    # what the verb makes the job's inputs from


@dataclass
class JobRecord:
    job: Job
    start_s: float   # from the window's start
    end_s: float
    out: dict        # what the verb's run_job returned; ``work`` is required
    cpu_s: float = 0.0   # this process's CPU seconds (all threads) in the job

    @property
    def seconds(self) -> float:
        return self.end_s - self.start_s


def passes(panel: dict, seed: int) -> Iterator[List[Job]]:
    """Passes of jobs without end, as the traffic file's panel says."""
    index = 0
    p = 0
    while True:
        if panel["from"] == "seed":
            subs = [sub_seed(seed, index + i) for i in range(panel["size"])]
        elif panel["from"] == "fixed":
            subs = list(panel["seeds"])
            random.Random(sub_seed(seed, p)).shuffle(subs)
        else:
            raise ValueError(f"panel.from is {panel['from']!r}")
        yield [Job(index + i, s) for i, s in enumerate(subs)]
        index += len(subs)
        p += 1


def warm_jobs(panel: dict, seed: int) -> List[Job]:
    """One whole job of each shape the window will run: the head of the
    window's first pass itself, so that its repeat can be compared with
    it. ``panel["warm"]`` says how many jobs that takes (all of the pass
    where every entry has shapes of its own, one where they share them)."""
    first = next(passes(panel, seed))
    return first[: panel.get("warm", len(first))]


def closed_loop(
    run_job: Callable[[Job], dict],
    job_passes: Iterator[List[Job]],
    seconds: float,
    clock: Callable[[], float] = time.perf_counter,
) -> List[JobRecord]:
    records: List[JobRecord] = []
    t0 = clock()
    while clock() - t0 < seconds:
        for job in next(job_passes):
            start, cpu0 = clock() - t0, time.process_time()
            out = run_job(job)
            records.append(JobRecord(
                job, start, clock() - t0, out, time.process_time() - cpu0
            ))
    return records


@dataclass
class WindowStats:
    jobs: int
    work: float
    seconds: float               # window start to the end of the last job
    mean_rate: float             # total over total: what a user pays
    median_rate: float           # median over jobs of work/seconds
    mean_job_s: float
    median_job_s: float
    rate_cv: float               # stdev/mean of the per-job rate
    seconds_cv: float            # stdev/mean of the per-job seconds
    per_job: list = field(default_factory=list)


def window_stats(records: List[JobRecord]) -> WindowStats:
    if not records:
        raise ValueError("the window holds no whole job")
    secs = [r.seconds for r in records]
    rates = [r.out["work"] / r.seconds for r in records]
    work = float(sum(r.out["work"] for r in records))
    span = records[-1].end_s

    def cv(xs):
        return statistics.pstdev(xs) / statistics.fmean(xs) if len(xs) > 1 else 0.0

    return WindowStats(
        jobs=len(records), work=work, seconds=span,
        mean_rate=work / span, median_rate=statistics.median(rates),
        mean_job_s=statistics.fmean(secs), median_job_s=statistics.median(secs),
        rate_cv=cv(rates), seconds_cv=cv(secs),
        per_job=[(r.job.index, r.job.sub_seed, r.out["work"], r.seconds)
                 for r in records],
    )
