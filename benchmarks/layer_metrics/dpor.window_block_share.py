"""dpor.window_block_share (%): self seconds of the device wait (`dpor.block`) over the seconds of the window's searches' rows: `dpor.block_share` for the jobs the rate is made of; about 100 - `dpor.host_share`."""

from lib.job_rows import DPOR_ROOT, stage_share


def read(obs):
    return stage_share(obs, DPOR_ROOT, ("dpor.block",))
