"""sweep.live_step_share (%): lane-steps the traced jobs' segments ran with a live (unfinished, unparked) lane (`sweep.live_lane_steps`) over the lane-steps they executed (`sweep.lane_steps`): how full harvest-and-refill keeps the resident set. A program that keeps no such counts gives none."""

from lib.stage_share import SWEEP_ROOT, count_ratio


def read(obs):
    return count_ratio("sweep.live_lane_steps", "sweep.lane_steps", SWEEP_ROOT)
