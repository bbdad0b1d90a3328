"""setup.first_job_s (s): seconds of `setup.first_job`, the warm job whole, the compile path inside it included."""

from lib.setup_ledger import stage_seconds


def read(obs):
    return stage_seconds("setup.first_job")
