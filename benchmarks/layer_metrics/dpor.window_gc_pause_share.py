"""dpor.window_gc_pause_share (%): self seconds of the collector passes that started inside a stage of a window's search (`gc.pause`) over the seconds of the window's searches' rows: the same heap `dpor.gc_share` times from outside (after `gc.freeze()`), less the passes between two searches."""

from lib.job_rows import DPOR_ROOT, stage_share


def read(obs):
    return stage_share(obs, DPOR_ROOT, ("gc.pause",))
