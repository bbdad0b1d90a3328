"""setup.trace_s (s): `compile.trace`: seconds of jaxpr tracing up to the warm job's end, a function traced inside another's tracing counted once."""

from lib.setup_ledger import stage_seconds


def read(obs):
    return stage_seconds("compile.trace")
