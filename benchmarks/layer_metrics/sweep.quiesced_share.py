"""sweep.quiesced_share (%): lanes the traced jobs retired with a verdict (`sweep.quiesced`: done or violating, so neither overflowed nor cut unfinished before quiescence) over the lanes they retired (`sweep.retired`). 100 wherever every schedule ran to its end; under an invariant judged at quiescence only, anything less is lanes with no verdict. A program that keeps no such counts gives none."""

from lib.stage_share import SWEEP_ROOT, count_ratio


def read(obs):
    return count_ratio("sweep.quiesced", "sweep.retired", SWEEP_ROOT)
