"""dpor.scan_share (%): self time of the native racing scan and the digest keys (`racing_prescriptions_batch`, `digest_keys`), over the seconds of the traced jobs' root span."""

from lib.stage_share import DPOR_ROOT, share


def read(obs):
    return share(DPOR_ROOT, ("dpor.scan",))
