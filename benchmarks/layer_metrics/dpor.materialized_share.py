"""dpor.materialized_share (%): explored-log entries the driver turned into Python tuples (`dpor.materialized`) over the prescriptions it admitted (`dpor.fresh`), in the traced jobs. A program that keeps no such count gives none."""

from lib.stage_share import DPOR_ROOT, count_ratio, tables


def read(obs):
    found = tables()
    if found is None or "dpor.materialized" not in found[1]:
        return None
    return count_ratio("dpor.materialized", "dpor.fresh", DPOR_ROOT)
