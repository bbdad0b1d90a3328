"""dpor.admit_us_per_candidate (us): seconds of `_admit_stream` in the traced jobs (its collector pauses included) over the racing prescriptions the scan handed it."""

from lib.stage_share import DPOR_ROOT, seconds_per_count


def read(obs):
    value = seconds_per_count("dpor.admit", "dpor.candidates", DPOR_ROOT)
    return None if value is None else 1e6 * value
