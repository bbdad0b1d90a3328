"""sweep.window_device_idle_share (%): 100 x (1 - busy / the window's jobs' seconds), busy being the traced jobs' device-busy seconds a lane-step (profiler trace, mean over chips) times the lane-steps the window's jobs ran: the idle chip of the jobs the rate is made of, where `sweep.device_idle_share` is the traced job's. Chip only; under 0 is a fault of the reckoning."""

from lib.job_rows import SWEEP_ROOT, device_idle_share


def read(obs):
    return device_idle_share(obs, SWEEP_ROOT)
