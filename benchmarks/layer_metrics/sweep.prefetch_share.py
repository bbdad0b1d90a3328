"""sweep.prefetch_share (%): programs the traced jobs' fills took from the stock made while a segment ran on the device (`sweep.prefetched`) over the programs they put in a lane (`sweep.programs`): how often making ahead engages. A program that keeps no such counts gives none."""

from lib.stage_share import SWEEP_ROOT, count_ratio


def read(obs):
    return count_ratio("sweep.prefetched", "sweep.programs", SWEEP_ROOT)
