"""sweep.window_starve_share (%): self seconds the window's jobs' fills spent waiting for a chunk the producer processes had not finished (`sweep.starve`) over the seconds of their rows: `sweep.starve_share` for the jobs the rate is made of. None where the rows keep no `sweep.producers` count."""

from lib.job_rows import SWEEP_ROOT, count, stage_share, window


def read(obs):
    rows = window(obs, SWEEP_ROOT)
    if not rows or count(rows, "sweep.producers") is None:
        return None
    return stage_share(obs, SWEEP_ROOT, ("sweep.starve",))
