"""sweep.unattributed_share (%): self time of sweep.job, sweep.prime, sweep.round and the fill loop (`sweep.fill` less its fuzzing and lowering) plus the collector passes under them: what no stage of its own names, over the seconds of the traced jobs' root span."""

from lib.stage_share import SWEEP_ROOT, SWEEP_UNATTRIBUTED, share


def read(obs):
    return share(SWEEP_ROOT, SWEEP_UNATTRIBUTED)
