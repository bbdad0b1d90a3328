"""sweep.fuzz_share (%): time of `program_gen(seed)` for the lanes being filled, summed over the fill loop by a clock pair per lane (`sweep.fuzz`), over the seconds of the traced jobs' root span."""

from lib.stage_share import SWEEP_ROOT, share


def read(obs):
    return share(SWEEP_ROOT, ("sweep.fuzz",))
