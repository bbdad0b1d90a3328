"""setup.unattributed_s (s): the process's age at the warm job's end less what a stage names (what came before the program, every stage's self seconds before the warm job, the warm job whole): the ledger's own self-check."""

from lib.setup_ledger import unattributed_s


def read(obs):
    return unattributed_s()
