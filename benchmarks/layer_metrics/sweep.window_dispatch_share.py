"""sweep.window_dispatch_share (%): nanoseconds the host thread spent inside a round's `segment(...)` dispatch and the `finalize` queued behind it (`sweep.dispatch_ns`, the clock pair `_rounds` takes every round) over the seconds of the window's jobs' rows: what a dispatch costs the host where its round is longer than a segment."""

from lib.job_rows import SWEEP_ROOT, ns_share


def read(obs):
    return ns_share(obs, SWEEP_ROOT, "sweep.dispatch_ns")
