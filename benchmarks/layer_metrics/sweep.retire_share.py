"""sweep.retire_share (%): self time of pulling verdicts, gathering retired lanes, folding them into the result and finishing it, over the seconds of the traced jobs' root span."""

from lib.stage_share import SWEEP_ROOT, share


def read(obs):
    return share(SWEEP_ROOT, ("sweep.pull", "sweep.retire", "sweep.fold", "sweep.finish"))
