"""sweep.device_peak_hbm_mb (MB): memory_stats()['peak_bytes_in_use'], the largest over the cell's chips."""

from lib.readers import device_peak_hbm_mb


def read(obs):
    return device_peak_hbm_mb(obs)
