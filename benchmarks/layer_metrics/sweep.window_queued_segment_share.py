"""sweep.window_queued_segment_share (%): segment dispatches of the window's jobs that found the segment before them still on the device (`sweep.segments_queued`) over all their dispatches (`sweep.segments`): `sweep.queued_segment_share` without the profiler lengthening a dispatch."""

from lib.job_rows import SWEEP_ROOT, count_ratio


def read(obs):
    return count_ratio(obs, SWEEP_ROOT, "sweep.segments_queued", "sweep.segments")
