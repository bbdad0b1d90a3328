"""minimize.window_compiles (count): backend compilations inside the window that the persistent cache did not serve, however short; jax.monitoring, harness side. 0 where set-up warmed every shape and the program keeps its jitted functions."""

from lib.readers import window_compiles


def read(obs):
    return window_compiles(obs)
