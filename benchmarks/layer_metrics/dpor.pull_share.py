"""dpor.pull_share (%): self time of the device-to-host copies of violation, trace and trace_len at the top of `_process_round`, over the seconds of the traced jobs' root span."""

from lib.stage_share import DPOR_ROOT, share


def read(obs):
    return share(DPOR_ROOT, ("dpor.pull",))
