"""sweep.pool_peak_share (%): the fullest pool of each traced job, in rows (`sweep.pool_peak_rows`: the most valid rows any resident lane held at a segment boundary, sampled by the driver while spans are live), over the pool's capacity (`sweep.pool_rows`, once a job): how much of the step kernel's O(pool) work is over real rows. Sampled every segment, so a peak between two boundaries reads up to a segment's deliveries low. A program that keeps no such counts gives none."""

from lib.stage_share import SWEEP_ROOT, count_ratio


def read(obs):
    return count_ratio("sweep.pool_peak_rows", "sweep.pool_rows", SWEEP_ROOT)
