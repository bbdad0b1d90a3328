"""explore_segment_roofline (%): least time of the traced segments' bytes (lib/bytes_per_step.py, one state read and write per lane per segment) at the device's memory bandwidth (peaks.json) over the segment kernel's time. Bound: bytes."""

from lib.readers import segment_roofline


def read(obs):
    return segment_roofline(obs)
