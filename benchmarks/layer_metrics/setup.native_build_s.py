"""setup.native_build_s (s): seconds of `setup.native_build` (`native/build.py: build_library`: hash the source, find or compile the `.so`) up to the warm job's end."""

from lib.setup_ledger import stage_seconds


def read(obs):
    return stage_seconds("setup.native_build")
