"""minimize.kernel_ns_per_lane_step (ns): device time of the step kernel's executions in the traced jobs, summed over chips, over the lane-steps those jobs ran. Profiler trace and program counter."""

from lib.readers import kernel_ns_per_lane_step


def read(obs):
    return kernel_ns_per_lane_step(obs)
