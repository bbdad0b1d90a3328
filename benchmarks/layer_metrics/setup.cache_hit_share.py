"""setup.cache_hit_share (%): compile requests the persistent cache served over all compile requests up to the warm job's end: what separates `first_setup_s` from `setup_s`. JAX keeps only programs that took a second or more to compile, so the small ones miss on a warm cache too."""

from lib.setup_ledger import cache_hit_share


def read(obs):
    return cache_hit_share()
