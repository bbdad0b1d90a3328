"""sweep.window_wait_share (%): nanoseconds the host thread was blocked at a round's status pull (`sweep.wait_ns`) over the seconds of the window's jobs' rows: the host waiting for the chip; high where the device sets the pace."""

from lib.job_rows import SWEEP_ROOT, ns_share


def read(obs):
    return ns_share(obs, SWEEP_ROOT, "sweep.wait_ns")
