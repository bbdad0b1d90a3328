"""minimize.device_idle_share (%): 1 - union of device-operation intervals over the traced window, mean over chips. Profiler trace."""

from lib.readers import device_idle_share


def read(obs):
    return device_idle_share(obs)
