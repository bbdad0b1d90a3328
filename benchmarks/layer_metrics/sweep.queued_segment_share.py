"""sweep.queued_segment_share (%): segment dispatches of the traced jobs that found the segment before them still running or waiting on the device (`sweep.segments_queued`) over all their segment dispatches (`sweep.segments`): how often the chip had its next segment in its queue when it finished one. 100 means it never waited for the host's round; 0 where the harvest runs no segment behind the device. A program that keeps no such counts gives none."""

from lib.stage_share import SWEEP_ROOT, count_ratio


def read(obs):
    return count_ratio("sweep.segments_queued", "sweep.segments", SWEEP_ROOT)
