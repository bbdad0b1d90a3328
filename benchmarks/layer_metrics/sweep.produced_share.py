"""sweep.produced_share (%): programs the traced jobs' fills copied out of the shared ring that a producer process made (`sweep.produced`) over the programs they put in a lane (`sweep.programs`): how often the producers engage (0 in a cell whose making is not worth a fork). A program that keeps no such count gives none."""

from lib.stage_share import SWEEP_ROOT, count_ratio, tables


def read(obs):
    found = tables()
    if found is None or "sweep.produced" not in found[1]:
        return None
    return count_ratio("sweep.produced", "sweep.programs", SWEEP_ROOT)
