"""setup.import_s (s): self seconds of the `setup.import` stages up to the warm job's end: the program's own packages (and `import jax` where the program is the first to want it; in a run of the benchmark the harness was)."""

from lib.setup_ledger import stage_seconds


def read(obs):
    return stage_seconds("setup.import", "self_seconds")
