"""sweep.window_fork_share (%): seconds of `sweep.fork` (the `os.fork()` calls of the producer processes, on the host thread) over the seconds of the window's jobs' rows; 0 where none forked."""

from lib.job_rows import SWEEP_ROOT, stage_share


def read(obs):
    return stage_share(obs, SWEEP_ROOT, ("sweep.fork",), "seconds")
