"""sweep.row_lowered_share (%): programs the traced jobs' fills lowered from a fuzzed program's op rows, with no event object made (`sweep.row_lowered`), over the programs they put in a lane (`sweep.programs`): how often the row path engages (hand-written lists, and `program_key` memo hits, count for none). A program that keeps no such count gives none."""

from lib.stage_share import SWEEP_ROOT, count_ratio, tables


def read(obs):
    found = tables()
    if found is None or "sweep.row_lowered" not in found[1]:
        return None
    return count_ratio("sweep.row_lowered", "sweep.programs", SWEEP_ROOT)
