"""sweep.block_share (%): self time of the segment dispatch up to the status pull (the sync point); the cross-check of 100 - sweep.host_share, over the seconds of the traced jobs' root span."""

from lib.stage_share import SWEEP_ROOT, share


def read(obs):
    return share(SWEEP_ROOT, ("sweep.block",))
