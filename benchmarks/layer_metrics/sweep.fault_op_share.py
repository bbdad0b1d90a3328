"""sweep.fault_op_share (%): hard-kill, restart, partition and unpartition ops over all external ops the traced jobs lowered (`sweep.ops.<kind>`, counted per fill): how much of the fault plane the cell's traffic engages. It describes the traffic and moves nothing: a constant of the traffic file's weights that no optimisation changes, so `moves` and `better` are only what the schema needs. A program that keeps no such counts gives none."""

from lib.stage_share import SWEEP_ROOT, tables

PREFIX = "sweep.ops."
FAULTS = ("hard_kill", "restart", "partition", "unpartition")


def read(obs):
    found = tables()
    if found is None:
        return None
    totals, counts = found
    lowered = sum(n for name, n in counts.items() if name.startswith(PREFIX))
    if SWEEP_ROOT not in totals or not lowered:
        return None
    return 100.0 * sum(counts.get(PREFIX + kind, 0) for kind in FAULTS) / lowered
