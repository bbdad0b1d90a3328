"""The arithmetic of the per-layer metrics that read the program's own
stage spans (``demi_tpu/obs/spans.py``): the per-name totals the traced
jobs left in ``demi_tpu.obs.stage_totals()`` / ``stage_counts()``. The
spans are live only while the profiler records, so the window's jobs add
nothing to them. A program without the tables (the parent of the PR that
brought them) or without the root span gives None, and the harness
leaves the metric out."""

from __future__ import annotations

from typing import Iterable, Optional, Tuple

DPOR_ROOT = "dpor.search"
SWEEP_ROOT = "sweep.job"
GC = "gc.pause"
# What no stage of its own names: the root's, the round's and the small
# bookkeeping stages' self time. ``sweep.fill`` is the per-lane loop that
# fuzzes and lowers; its per-lane clocks hand those two their time as
# ``sweep.fuzz`` and ``sweep.lower``, and what is left is the loop's own.
DPOR_UNATTRIBUTED = ("dpor.search", "dpor.round", "dpor.violations", "dpor.account")
SWEEP_UNATTRIBUTED = ("sweep.job", "sweep.prime", "sweep.round", "sweep.fill", GC)


def tables() -> Optional[Tuple[dict, dict]]:
    """``(stage_totals(), stage_counts())``, or None where the program
    has no such tables."""
    try:
        from demi_tpu.obs import stage_counts, stage_totals
    except ImportError:
        return None
    return stage_totals(), stage_counts()


def share(root: str, stages: Iterable[str]) -> Optional[float]:
    """Self seconds of ``stages`` over the seconds of ``root``, in %."""
    found = tables()
    if found is None:
        return None
    totals, _counts = found
    whole = totals.get(root, {}).get("seconds", 0.0)
    if not whole > 0:
        return None
    own = sum(totals[s]["self_seconds"] for s in stages if s in totals)
    return 100.0 * own / whole


def seconds_per_count(stage: str, count: str, root: str) -> Optional[float]:
    """Seconds of ``stage`` (its collector pauses included) over the
    count ``count``."""
    found = tables()
    if found is None:
        return None
    totals, counts = found
    if root not in totals or stage not in totals or not counts.get(count):
        return None
    return totals[stage]["seconds"] / counts[count]


def count_ratio(part: str, whole: str, root: str) -> Optional[float]:
    """The count ``part`` over the count ``whole``, in %."""
    found = tables()
    if found is None:
        return None
    totals, counts = found
    if root not in totals or not counts.get(whole):
        return None
    return 100.0 * counts.get(part, 0) / counts[whole]
