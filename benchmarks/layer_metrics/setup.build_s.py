"""setup.build_s (s): self seconds of `setup.build` (the workload, the drivers' constructors, what makes the jitted kernel objects) up to the warm job's end, its imports and compile events taken out."""

from lib.setup_ledger import stage_seconds


def read(obs):
    return stage_seconds("setup.build", "self_seconds")
