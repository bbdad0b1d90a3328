"""The window's rule: whole jobs, seeded panels, honest divisors."""

from lib import jobs


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_sub_seed_rule_and_large_seeds():
    assert jobs.sub_seed(3, 5) == 3 * 2**16 + 5
    assert jobs.sub_seed(2**31 + 11, 0) == (2**31 + 11) * 65536


def test_closed_loop_counts_whole_jobs_over_their_own_time():
    clock = Clock()

    def run_job(job):
        clock.t += 4.0          # every job takes 4 s
        return {"work": 100}

    recs = jobs.closed_loop(
        run_job, jobs.passes({"from": "seed", "size": 1}, 7), 10.0, clock
    )
    # jobs start at 0, 4, 8 (< 10) and the third finishes at 12: it counts whole
    assert [r.start_s for r in recs] == [0.0, 4.0, 8.0]
    stats = jobs.window_stats(recs)
    assert stats.jobs == 3 and stats.work == 300
    assert stats.seconds == 12.0            # not --seconds
    assert stats.mean_rate == 25.0
    assert [r.job.sub_seed for r in recs] == [jobs.sub_seed(7, j) for j in range(3)]


def test_same_seed_same_jobs_and_warm_is_the_first_pass():
    panel = {"from": "seed", "size": 1}
    a, b = jobs.passes(panel, 11), jobs.passes(panel, 11)
    assert [next(a)[0].sub_seed for _ in range(4)] == [next(b)[0].sub_seed for _ in range(4)]
    assert jobs.warm_jobs(panel, 11)[0].sub_seed == jobs.sub_seed(11, 0)


def test_fixed_panel_changes_order_not_work():
    panel = {"from": "fixed", "seeds": [0, 1, 2]}
    orders = set()
    for seed in range(12):
        first = [j.sub_seed for j in next(jobs.passes(panel, seed))]
        assert sorted(first) == [0, 1, 2]
        orders.add(tuple(first))
    assert len(orders) > 1
    # a pass is admitted whole: three 5 s jobs against a 7 s window run all three
    clock = Clock()

    def run_job(job):
        clock.t += 5.0
        return {"work": 1}

    recs = jobs.closed_loop(run_job, jobs.passes(panel, 3), 7.0, clock)
    assert len(recs) == 3 and jobs.window_stats(recs).seconds == 15.0
