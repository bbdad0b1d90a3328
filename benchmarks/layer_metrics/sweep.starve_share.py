"""sweep.starve_share (%): self time the traced jobs' fills spent waiting for a chunk the producer processes had not finished (`sweep.starve`), over the seconds of the root span: how often the producers fall behind the chips (0 where none were forked). A program that has no producers (it keeps no `sweep.producers` count) gives none."""

from lib.stage_share import SWEEP_ROOT, share, tables


def read(obs):
    found = tables()
    if found is None or "sweep.producers" not in found[1]:
        return None
    return share(SWEEP_ROOT, ("sweep.starve",))
