"""sweep.insert_short_share (%): of the steps the traced jobs' retired lanes were scanned (`sweep.insert_steps`), those whose pool insert took the short pass over the first 8 valid rows: 100 less the share in which some resident lane sent more, so that the whole batch took the full [K, P] pass (`sweep.insert_full_steps`: each lane's `insert_full_steps`, pulled at the retire while spans are live). A program whose insert has no short pass (a small `max_outbox`, or the parent of the PR that brought it) keeps no such counts and gives none."""

from lib.stage_share import SWEEP_ROOT, count_ratio


def read(obs):
    full = count_ratio("sweep.insert_full_steps", "sweep.insert_steps", SWEEP_ROOT)
    return None if full is None else 100.0 - full
