"""sweep.traced_job_stretch (ratio): mean seconds of the traced jobs' rows (`obs.job_ledger()`, `profiled`) over mean seconds of the window's jobs' rows (`recorded` false, after the last profiled one): how far the job every traced share and `sweep.device_idle_share` describe is from the jobs `schedules_per_s` is made of. 1.0 means a traced job is a window job. None on a program without the ledger, or where the rows do not match the window's jobs."""

from lib.job_rows import SWEEP_ROOT, stretch


def read(obs):
    return stretch(obs, SWEEP_ROOT)
