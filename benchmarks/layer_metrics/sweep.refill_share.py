"""sweep.refill_share (%): self time of the refill bookkeeping, `keys_for` + `init` + `refill` dispatch, and the overdue path, over the seconds of the traced jobs' root span."""

from lib.stage_share import SWEEP_ROOT, share


def read(obs):
    return share(SWEEP_ROOT, ("sweep.refill", "sweep.finalize"))
