"""sweep.insert_full_lane_share (%): of the steps the traced jobs' retired lanes were scanned (`sweep.insert_steps`), those in which the lane's own rows went through the pool insert's full [K, P] pass (`sweep.insert_full_lane_steps`: each lane's `insert_full_lane_steps`, pulled at the retire while spans are live): the lane sent more than the short pass's 8 rows and was taken through the pass alone, or so many lanes did at once that the whole batch was. `sweep.insert_short_share` says how often some resident lane bursts (the traffic's); this says how much of the resident set then pays for it. A program that keeps no such count (no short pass in its insert, or the parent of the PR that brought the count) gives none."""

from lib.stage_share import SWEEP_ROOT, count_ratio, tables

COUNT = "sweep.insert_full_lane_steps"


def read(obs):
    found = tables()
    if found is None or COUNT not in found[1]:
        return None
    return count_ratio(COUNT, "sweep.insert_steps", SWEEP_ROOT)
