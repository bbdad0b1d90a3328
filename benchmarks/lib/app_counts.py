"""The per-layer metrics over an app's own progress counts: what
``DSLApp.progress`` names, summed by the continuous sweep over the lanes
it retires and kept as ``sweep.app.<name>`` while spans are live. A
program without such counts (the parent of the PR that brought them), an
app that names none, or a run with no traced job gives None, and the
harness leaves the metric out."""

from __future__ import annotations

from typing import Optional

from .stage_share import SWEEP_ROOT, tables

PREFIX = "sweep.app."


def app_ratio(name: str, whole: str, percent: bool = False) -> Optional[float]:
    """The count ``sweep.app.<name>`` over the count ``whole``."""
    found = tables()
    if found is None:
        return None
    totals, counts = found
    if SWEEP_ROOT not in totals or PREFIX + name not in counts:
        return None
    if not counts.get(whole):
        return None
    return (100.0 if percent else 1.0) * counts[PREFIX + name] / counts[whole]
