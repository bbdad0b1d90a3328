"""sweep.window_prime_share (%): seconds of `sweep.prime` (the first resident set's fill, its forks and the first `init`, children included) over the seconds of the window's jobs' rows: the part of a job before its first segment is dispatched, which nothing hides."""

from lib.job_rows import SWEEP_ROOT, stage_share


def read(obs):
    return stage_share(obs, SWEEP_ROOT, ("sweep.prime",), "seconds")
