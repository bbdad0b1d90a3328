"""dpor.host_share (%): DeviceDPOR's own split of its wall time per round (_account_round), over the window's jobs."""

from lib.readers import host_share


def read(obs):
    return host_share(obs)
