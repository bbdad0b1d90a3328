"""dpor.traced_job_stretch (ratio): mean seconds of the traced searches' rows (`obs.job_ledger()`, `profiled`) over mean seconds of the window's searches' rows: how far the job every traced `dpor.*_share` describes is from the jobs `interleavings_per_s` is made of. None on a program without the ledger, or where the rows do not match the window's jobs."""

from lib.job_rows import DPOR_ROOT, stretch


def read(obs):
    return stretch(obs, DPOR_ROOT)
