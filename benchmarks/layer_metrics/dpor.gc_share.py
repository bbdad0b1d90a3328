"""dpor.gc_share (%): seconds CPython's collector ran inside the window (gc.callbacks) over the window's seconds."""

from lib.readers import gc_share


def read(obs):
    return gc_share(obs)
