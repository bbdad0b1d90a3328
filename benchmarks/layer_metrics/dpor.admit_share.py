"""dpor.admit_share (%): self time of `_admit_stream` (digest dedup, tuple materialisation, admission), collector pauses left out, over the seconds of the traced jobs' root span. It triggers nearly all of the search's collector passes, and the traced job runs before set-up's `gc.freeze()`: with its pauses it is the share of a pre-freeze job."""

from lib.stage_share import DPOR_ROOT, share


def read(obs):
    return share(DPOR_ROOT, ("dpor.admit",))
