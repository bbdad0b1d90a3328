"""sweep.stack_share (%): self time of re-stacking every resident program at each fill, over the seconds of the traced jobs' root span."""

from lib.stage_share import SWEEP_ROOT, share


def read(obs):
    return share(SWEEP_ROOT, ("sweep.stack",))
