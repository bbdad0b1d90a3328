"""dpor.launch_share (%): self time of packing the round's prescriptions and keys and of the host enqueue (`_pack`, `_round_keys`, `_dispatch_round`), over the seconds of the traced jobs' root span."""

from lib.stage_share import DPOR_ROOT, share


def read(obs):
    return share(DPOR_ROOT, ("dpor.pack", "dpor.dispatch"))
