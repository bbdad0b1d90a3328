"""setup.compile_s (s): `compile.backend`: seconds of backend compiles the persistent cache did not serve, up to the warm job's end. Near 0 on a warm cache; a just-edited tree pays it."""

from lib.setup_ledger import stage_seconds


def read(obs):
    return stage_seconds("compile.backend")
