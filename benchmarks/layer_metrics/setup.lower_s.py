"""setup.lower_s (s): `compile.lower`: seconds of lowering jaxprs to MLIR modules up to the warm job's end."""

from lib.setup_ledger import stage_seconds


def read(obs):
    return stage_seconds("compile.lower")
