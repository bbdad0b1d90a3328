"""dpor.job_cv (%): standard deviation over mean of the per-job rate inside one window; the benchmark's own steadiness. Host clock."""

from lib.readers import job_cv


def read(obs):
    return job_cv(obs)
