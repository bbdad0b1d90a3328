"""dpor.window_scan_share (%): self seconds of the native racing scan and the digest keys (`dpor.scan`) over the seconds of the window's searches' rows: `dpor.scan_share` for the jobs the rate is made of."""

from lib.job_rows import DPOR_ROOT, stage_share


def read(obs):
    return stage_share(obs, DPOR_ROOT, ("dpor.scan",))
