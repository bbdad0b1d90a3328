"""dpor.unattributed_share (%): self time of dpor.search, dpor.round, dpor.violations and dpor.account: what no stage of its own names, over the seconds of the traced jobs' root span."""

from lib.stage_share import DPOR_ROOT, DPOR_UNATTRIBUTED, share


def read(obs):
    return share(DPOR_ROOT, DPOR_UNATTRIBUTED)
