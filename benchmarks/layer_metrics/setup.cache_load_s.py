"""setup.cache_load_s (s): `compile.cache_load`: seconds of compile requests the persistent cache served (key, read, deserialise, load), up to the warm job's end."""

from lib.setup_ledger import stage_seconds


def read(obs):
    return stage_seconds("compile.cache_load")
