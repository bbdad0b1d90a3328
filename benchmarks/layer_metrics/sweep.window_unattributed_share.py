"""sweep.window_unattributed_share (%): self seconds, in the window's jobs' rows, of every stage no per-layer metric names (`sweep.job`, `sweep.prime`, `sweep.round`, the fill loop's own `sweep.fill`, `gc.pause`, and any stage added since) over the rows' seconds: the rows' self-check, as `setup.unattributed_s` is the set-up ledger's."""

from lib.job_rows import SWEEP_NAMED, SWEEP_ROOT, unattributed_share


def read(obs):
    return unattributed_share(obs, SWEEP_ROOT, SWEEP_NAMED)
