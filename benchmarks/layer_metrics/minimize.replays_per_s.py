"""minimize.replays_per_s (replays/s): the minimization statistics' total replays over the jobs' seconds."""

from lib.readers import replays_per_s


def read(obs):
    return replays_per_s(obs)
