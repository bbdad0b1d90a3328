"""The arithmetic of the ``setup.*`` per-layer metrics: they read the
program's own set-up ledger (``demi_tpu.obs.setup_ledger()``: the set-up
stages and the compile events, always on), cut at the end of the
process's first job, which in a run of the benchmark is the warm job. So
the traced jobs and the window add nothing to them, and they are what
``setup_s`` is made of up to the warm job's end. A program without the
ledger (the parent of the PR that brought it) gives None, and the harness
leaves the metric out."""

from __future__ import annotations

from typing import Optional

FIRST_JOB = "setup.first_job"


def ledger() -> Optional[dict]:
    """The ledger once job 1 has ended; None where the program has no
    ledger, or no job of it has ended."""
    try:
        from demi_tpu.obs import setup_ledger
    except ImportError:
        return None
    found = setup_ledger()
    job = found.get("first_job")
    if not job or job.get("end_s") is None:
        return None
    return found


def stage_seconds(name: str, column: str = "seconds") -> Optional[float]:
    """``column`` of the stage ``name`` up to job 1's end; 0.0 for a
    stage that never ran."""
    found = ledger()
    if found is None:
        return None
    return float(found["stages"].get(name, {}).get(column, 0.0))


def pre_program_s() -> Optional[float]:
    found = ledger()
    return None if found is None else found["pre_program_s"]


def cache_hit_share() -> Optional[float]:
    """Compile requests the persistent cache served over all compile
    requests up to job 1's end, in %."""
    found = ledger()
    if found is None:
        return None
    hits, misses = found["compile"]["cache_hits"], found["compile"]["compiles"]
    if not hits + misses:
        return None
    return 100.0 * hits / (hits + misses)


def disjoint(found: dict) -> dict:
    """The parts of the process's age at job 1's end that a stage names,
    none inside another: what came before the program, every stage's
    self seconds up to job 1's start, and job 1 whole."""
    parts = {
        name: row["self_seconds"]
        for name, row in found["before_first_job"].items()
    }
    parts["pre_program"] = found["pre_program_s"]
    parts[FIRST_JOB] = found["stages"][FIRST_JOB]["seconds"]
    return parts


def unattributed_s() -> Optional[float]:
    """The process's age at job 1's end less ``disjoint``'s parts."""
    found = ledger()
    if found is None or found["pre_program_s"] is None:
        return None
    return found["first_job"]["end_s"] - sum(disjoint(found).values())
