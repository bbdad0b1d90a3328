"""sweep.budget_refill_share (%): lanes of the traced jobs that the host knew spent when it dispatched their last segment, so that their refill was queued behind that segment and they sat through no frozen one (`sweep.budget_retired`), over the lanes retired (`sweep.retired`): how much of the traffic the lagged harvest costs nothing. Near 100 where schedules run to their step budget, 0 where they stop on their own or the order is strict. A program that keeps no such count gives none."""

from lib.stage_share import SWEEP_ROOT, count_ratio, tables


def read(obs):
    found = tables()
    if found is None or "sweep.budget_retired" not in found[1]:
        return None
    return count_ratio("sweep.budget_retired", "sweep.retired", SWEEP_ROOT)
