"""dpor.fresh_share (%): prescriptions admitted to the frontier over the racing prescriptions the scan returned, in the traced jobs."""

from lib.stage_share import DPOR_ROOT, count_ratio


def read(obs):
    return count_ratio("dpor.fresh", "dpor.candidates", DPOR_ROOT)
