"""sweep.host_share (%): SweepDriver's own split of its wall time (host_seconds over host plus device seconds), over the window's jobs."""

from lib.readers import host_share


def read(obs):
    return host_share(obs)
