"""dpor.gc_pause_share (%): collector passes that started inside a stage of the search (`gc.pause` spans), over the seconds of the traced jobs' root span. The traced job runs before set-up's `gc.collect(); gc.freeze()`, on another heap than the window's jobs: it is not dpor.gc_share's cross-check (PERF.md, PR 24)."""

from lib.stage_share import DPOR_ROOT, GC, share


def read(obs):
    return share(DPOR_ROOT, (GC,))
