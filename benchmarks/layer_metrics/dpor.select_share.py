"""dpor.select_share (%): self time of the round selection (`_merge_generations`, `_select_batch` and its frontier sort), over the seconds of the traced jobs' root span."""

from lib.stage_share import DPOR_ROOT, share


def read(obs):
    return share(DPOR_ROOT, ("dpor.select",))
