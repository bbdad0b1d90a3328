"""sweep.lower_share (%): time of `lower_program` for those programs, summed the same way (`sweep.lower`), over the seconds of the traced jobs' root span."""

from lib.stage_share import SWEEP_ROOT, share


def read(obs):
    return share(SWEEP_ROOT, ("sweep.lower",))
