"""dpor.block_share (%): self time of the device wait (`_supervised_harvest`); the cross-check of 100 - dpor.host_share, over the seconds of the traced jobs' root span."""

from lib.stage_share import DPOR_ROOT, share


def read(obs):
    return share(DPOR_ROOT, ("dpor.block",))
